"""The whole Legion pass's share of the chip's int8 peak: the Program's
GEMM operations at the shapes its workloads declare, over the traced
passes' mean host seconds times the peak, in percent."""


def read(view):
    passes = view.observed.get("passes", [])
    if not passes:
        return None
    pass_s = sum(p["t1"] - p["t0"] for p in passes) / len(passes)
    return 100.0 * view.observed["program_ops"] / (
        pass_s * view.peak["int8_ops_per_s"])
