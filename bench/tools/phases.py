"""Where a traced run's time goes, by the program's own spans: for each span
name its count, seconds and self seconds; the window's idle gaps, each named
by the innermost span open at its midpoint; the device seconds of each
compiled module; and the cell's per-layer metrics.  Not run by the
benchmark's own runs.

    python3 bench/tools/phases.py --workload <cell> --seeds 1 2 3 [--out DIR]

Each seed runs the cell's traced window (the mix's lead-in and traced span,
as ``bench/run.py --trace 1`` does) through its driver, all in one process,
and prints one JSON line; ``--out`` also writes it to
``DIR/phases_<cell>_<seed>.json``.
"""
import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from bench import harness, program_trace  # noqa: E402


def phases(r: program_trace.Reduced, top: int = 12) -> dict:
    """Count, seconds and self seconds of each span name in the window."""
    out = {}
    for name in sorted({s.name for s in r.spans}):
        spans = r.spans_named(name)
        if spans:
            out[name] = {"count": len(spans), "s": r.span_seconds(name),
                         "self_s": r.self_seconds(name)}

    def ranked(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                ][:top]

    return {"window_s": r.window_s, "busy_s": r.busy_s, "spans": out,
            "idle_gaps": ranked(r.idle_by_span()),
            "modules": ranked(r.module_seconds())}


def run_seed(cell, seed, seconds, clock, peak) -> dict:
    driver = harness.load_module(
        harness.BENCH / "drivers" / f"{cell.mix['driver']}.py")
    trace_dir = tempfile.mkdtemp(prefix="phases_trace_")
    try:
        ctx = harness.RunContext(cell, seed, seconds, True,
                                 time.perf_counter(), clock, peak,
                                 cell.chips, trace_dir)
        outcome = driver.run(ctx)
        r = program_trace.Reduced(program_trace.load(
            harness._trace_file(trace_dir)), cell.chips)
    finally:
        shutil.rmtree(trace_dir, True)
    view = harness.View(outcome.observed, r, peak)
    metrics = {m["name"]: harness.load_module(
        harness.metric_file(m["name"])).read(view) for m in cell.per_layer}
    return {"workload": cell.name, "seed": seed,
            "correct": harness.correct(outcome.checks),
            "metrics": metrics, **phases(r)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    spec = harness.load_json(_ROOT / "BENCHMARK.json")
    cell = harness.find_cell(spec, args.workload)
    harness.enable_compile_cache()
    clock = harness.CompileClock()
    peak = harness.peak_of(harness.device_info(cell.chips)["kind"])
    for seed in args.seeds:
        line = json.dumps(run_seed(cell, seed, spec["run_seconds"], clock,
                                   peak), default=float)
        print(line, flush=True)
        if args.out:
            Path(args.out).mkdir(parents=True, exist_ok=True)
            (Path(args.out) / f"phases_{cell.name}_{seed}.json").write_text(
                line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
