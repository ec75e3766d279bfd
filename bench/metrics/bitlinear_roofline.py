"""Share of its roofline that the bitlinear Pallas kernel reached in the
Legion executor: the least time of every tile call it made in the traced
window (``bench/work``), summed, over the device time of its kernel
events, in percent."""
from bench.work import legion as work

KERNEL = "bitlinear"


def read(view):
    calls = view.observed.get("tile_calls", [])
    kernel_s = sum(view.trace.op_seconds(KERNEL).values())
    if not calls or kernel_s <= 0:
        return None
    peak = view.peak
    least = sum(max(ops / peak["int8_ops_per_s"],
                    nbytes / peak["hbm_bytes_per_s"])
                for ops, nbytes in (work.tile_call(*c) for c in calls))
    return 100.0 * least / kernel_s
