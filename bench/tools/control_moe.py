"""Readings that set the MoE serve cell's limit: the program's number and
its control's, on the chip at the cell's own size, several seeds in one
process.  Not run by the benchmark's own runs.

    python3 bench/tools/control_moe.py --workload serve-moonlight-decode \
        --seconds <s> --seeds 1 2 3

For each seed it runs the cell's window at its own load, then reads the
widest logit gap of the served tokens and of the tokens the float8
reference (``references/moonlight.py``) puts first, and holds each to the
cell's limit by the comparison a run makes.  One JSON line per seed.
"""
import argparse
import json
import sys
import time
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from bench import harness, serving  # noqa: E402


def reading(cell, seed, seconds, clock, peak):
    drv = harness.load_module(harness.BENCH / "drivers"
                              / f"{cell.mix['driver']}.py")
    ctx = harness.RunContext(cell, seed, seconds, False, time.perf_counter(),
                             clock, peak, cell.chips)
    engine, window, _ = drv.serve_window(ctx)
    sample = serving.check_sample(window, seed, cell.mix["check"]["requests"])
    serving.free(engine)
    del engine
    r = drv.readings(cell.config, seed, sample)
    for side in ("program", "control"):
        r[f"{side}_correct"] = harness.correct({"logit_gap": {
            "value": r[side], "limit": cell.config["check"]["logit_gap"]}})
    r["limit"] = cell.config["check"]["logit_gap"]
    return r


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.find_cell(harness.load_json(_ROOT / "BENCHMARK.json"),
                             args.workload)
    harness.enable_compile_cache()
    clock = harness.CompileClock()
    peak = harness.peak_of(harness.device_info(cell.chips)["kind"])
    for seed in args.seeds:
        t = time.perf_counter()
        r = reading(cell, seed, args.seconds, clock, peak)
        r.update(seed=seed, seconds=time.perf_counter() - t)
        print(json.dumps(r), flush=True)


if __name__ == "__main__":
    main()
