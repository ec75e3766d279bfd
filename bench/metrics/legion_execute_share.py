"""Share of the traced Legion passes spent in the executor's assignment
loop (``legion.execute``: every tile call and its instrument events): those
spans' seconds over the ``bench.pass`` spans' seconds, in percent."""
from bench import program_trace

SPAN = "legion.execute"


def read(view):
    r = program_trace.of(view)
    if r is None or not r.has_spans("legion."):
        return None
    passes = r.span_seconds("bench.pass")
    if passes <= 0:
        return None
    return 100.0 * r.span_seconds(SPAN) / passes
