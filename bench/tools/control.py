"""Readings that set a cell's limit: the program's number and its
control's, on the chip at the cell's own size, several seeds in one
process.  Not run by the benchmark's own runs.

    python3 bench/tools/control.py --workload <cell> --seconds <s> --seeds 1 2 3

A serve cell runs the cell's window at its own load for each seed, then
reads the widest logit gap of the served tokens and of the tokens the
float8 reference puts first.  The Legion cell runs one pass of each
Program and counts the elements that differ from the reference, for the
program and for the reference computed with int4 links.  Each reading is
held to the cell's own limit by the comparison a run makes
(``harness.correct``): the program has to come out correct, the control
not.  One JSON line per seed.
"""
import argparse
import json
import sys
import time
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from bench import harness  # noqa: E402


def serve_reading(cell, seed, seconds, clock, peak):
    from bench import serving, traffic

    ctx = harness.RunContext(cell, seed, seconds, False, time.perf_counter(),
                             clock, peak, cell.chips)
    engine = serving.build_engine(cell.config, seed)
    serving.warm_up(engine, cell.config, cell.mix)
    reqs = traffic.generate(cell.mix, seed, cell.config["vocab_size"],
                            seconds)
    window = serving.run_window(ctx, engine, reqs)
    if cell.mix["driver"] == "serve_open_loop":
        serving.drain_first_tokens(engine, window)
    sample = serving.check_sample(window, seed, cell.mix["check"]["requests"])
    serving.free(engine)
    del engine
    r = serving.readings(cell.config, seed, sample)
    return judged(r, "logit_gap", cell.config["check"]["logit_gap"])


def legion_reading(cell, seed):
    from bench.drivers import legion_passes as drv
    from bench.references import legion_attention as ref
    from repro.core import config as accel_configs
    from repro.core.workloads import AttentionSpec
    from repro.legion import Machine, PipelinedExecutor, lower_attention

    g = cell.config["legion"]
    block = drv.block_of(cell.config)
    spec = AttentionSpec(name=cell.config["registry"], **block)
    rows = ref.streamed_rows(block, seed, cell.mix["programs"])
    wqkv, wo = ref.weights(block, seed)
    machine = Machine(getattr(accel_configs, g["accelerator"])(),
                      backend=PipelinedExecutor(),
                      kernel_backend=g["kernel_backend"])
    prog = low = 0
    for x in rows:
        rep = machine.run(lower_attention(spec, x=x,
                                          seed=ref.weight_seed(seed)))
        want = ref.outputs(block, x, wqkv, wo)
        prog += ref.mismatches({k: r.outputs for k, r in
                                rep.stage_reports.items()}, want)
        low += ref.mismatches(ref.outputs(block, x, wqkv, wo, mag=7), want)
    return judged({"program": prog, "control": low}, "stage_mismatch",
                  g["mismatch_limit"])


def judged(r: dict, name: str, limit: float) -> dict:
    """The readings with what a run would decide on each: ``correct`` is
    ``harness.correct`` of the number beside the cell's limit."""
    for side in ("program", "control"):
        r[f"{side}_correct"] = harness.correct(
            {name: {"value": r[side], "limit": limit}})
    r["limit"] = limit
    return r


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.find_cell(harness.load_json(_ROOT / "BENCHMARK.json"),
                             args.workload)
    harness.enable_compile_cache()
    clock = harness.CompileClock()
    device = harness.device_info(cell.chips)
    peak = harness.peak_of(device["kind"])
    for seed in args.seeds:
        t = time.perf_counter()
        if cell.mix["driver"] == "legion_passes":
            r = legion_reading(cell, seed)
        else:
            r = serve_reading(cell, seed, args.seconds, clock, peak)
        r.update(seed=seed, seconds=time.perf_counter() - t)
        print(json.dumps(r), flush=True)


if __name__ == "__main__":
    main()
