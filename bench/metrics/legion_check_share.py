"""Share of the traced Legion passes spent checking each stage's outputs
against ``x @ w`` (``legion.check``) and cross-validating its traffic and
cycles against ``simulate()`` (``legion.validate``): those spans' seconds
over the ``bench.pass`` spans' seconds, in percent."""
from bench import program_trace

SPANS = ("legion.check", "legion.validate")


def read(view):
    r = program_trace.of(view)
    if r is None or not r.has_spans("legion."):
        return None
    passes = r.span_seconds("bench.pass")
    if passes <= 0:
        return None
    return 100.0 * sum(r.span_seconds(s) for s in SPANS) / passes
