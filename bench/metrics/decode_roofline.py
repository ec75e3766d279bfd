"""Share of its roofline that the decode program reached: the least time
the chip could take for each decode-only step's work (``bench/work``),
summed, over those steps' device-busy time, in percent."""
from bench.trace_reduce import paired
from bench.work import serve as work


def read(view):
    m = view.observed["model"]
    least = busy = 0.0
    for s, b in paired(view.trace, "bench.step",
                       view.observed["traced_steps"]):
        if s["positions"] and not s["chunks"]:
            least += work.least_seconds(
                *work.decode_step(m, s["positions"]), view.peak)
            busy += b
    return 100.0 * least / busy if busy > 0 else None
