"""Device-busy milliseconds of an engine step that carried no prompt chunk
(the jitted decode, its sampling and slot bookkeeping), per such step."""
from bench.trace_reduce import paired


def decode_only(view):
    return [(s, busy) for s, busy in
            paired(view.trace, "bench.step", view.observed["traced_steps"])
            if s["positions"] and not s["chunks"]]


def read(view):
    steps = decode_only(view)
    if not steps:
        return None
    return 1e3 * sum(b for _, b in steps) / len(steps)
