"""Share of its roofline that the MoE decode program reached: the least
time the chip could take for each decode-only step's work
(``bench/work/moe.py``, the held experts its routing counts say were hit),
summed, over those steps' device-busy time, in percent.  Steps without
routing counts (a program that keeps none) are not read."""
from bench.trace_reduce import paired
from bench.work import moe as work


def read(view):
    m = view.observed["model"]
    least = busy = 0.0
    for s, b in paired(view.trace, "bench.step",
                       view.observed["traced_steps"]):
        if s["positions"] and not s["chunks"] and "moe_experts_hit" in s:
            least += work.least_seconds(*work.decode_step(
                m, s["positions"], s["moe_routed"], s["moe_experts_hit"]),
                view.peak)
            busy += b
    return 100.0 * least / busy if busy > 0 else None
