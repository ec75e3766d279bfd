"""The whole serve step's share of the chip's int8 peak: the operations
that every token processed in the traced window needs (prompt chunks and
decoded tokens, ``bench/work``) over window seconds times the peak, in
percent."""
from bench.work import serve as work


def read(view):
    m = view.observed["model"]
    steps = view.observed["traced_steps"]
    if not steps or view.trace.window_s <= 0:
        return None
    ops = 0.0
    for s in steps:
        if s["positions"]:
            ops += work.decode_step(m, s["positions"])[0]
        for tokens, pos0, last in s["chunks"]:
            ops += work.prefill_chunk(m, tokens, pos0, last)[0]
    return 100.0 * ops / (view.trace.window_s * view.peak["int8_ops_per_s"])
