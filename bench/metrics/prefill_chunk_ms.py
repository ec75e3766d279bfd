"""Device-busy milliseconds per 256 prompt tokens of chunked prefill: the
busy time of steps that carried chunks, less one decode step's
(``decode_step_ms``) where the step also decoded, over their chunk tokens
in units of 256."""
from bench.trace_reduce import paired

UNIT = 256


def read(view):
    pairs = paired(view.trace, "bench.step", view.observed["traced_steps"])
    decode = [b for s, b in pairs if s["positions"] and not s["chunks"]]
    chunked = [(s, b) for s, b in pairs if s["chunks"]]
    if not decode or not chunked:
        return None
    per_decode = sum(decode) / len(decode)
    busy = sum(b - (per_decode if s["positions"] else 0.0)
               for s, b in chunked)
    tokens = sum(c[0] for s, _ in chunked for c in s["chunks"])
    return 1e3 * busy / (tokens / UNIT)
