"""Milliseconds per decode-only engine step in which the device waits on
the engine's own host work: the step's ``serve.admit``, ``serve.chunks``,
``serve.ensure_kv``, ``serve.decode`` (host arrays, transfers, dispatch)
and ``serve.finish`` spans, the mean over the steps of the traced window
that carried no prompt chunk.  In such a step the device has nothing
queued while these run: the previous step's tokens are on the host (its
``serve.sync`` returned) and the next decode is dispatched at the end of
``serve.decode``.  It reads the host's spans alone: the device planes'
clock is not the host's to the millisecond (``bench/program_trace.py``)."""
from bench import program_trace

PHASES = ("serve.admit", "serve.chunks", "serve.ensure_kv", "serve.decode",
          "serve.finish")


def read(view):
    r = program_trace.of(view)
    if r is None or not r.has_spans("serve."):
        return None
    phases = [s for s in r.spans if s.name in PHASES]
    per_step = []
    for s, step in zip(view.observed["traced_steps"],
                       r.spans_named("bench.step")):
        if s["positions"] and not s["chunks"]:
            per_step.append(sum(p.end - p.start for p in phases
                                if step.start <= p.start
                                and p.end <= step.end))
    if not per_step:
        return None
    return 1e-6 * sum(per_step) / len(per_step)
