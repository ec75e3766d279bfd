"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix, driver and per-layer metrics are
found by name from ``BENCHMARK.json``.  Set-up (weights from the seed,
compiling or loading every program the cell's traffic uses) is timed from
the start of this process; then the window runs for ``--seconds``; then
what the window produced is compared with a plain reference.  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics read from a profiler trace.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), and last ``checks``, each number compared beside its limit; the
same numbers are the last lines of standard error.  Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no result.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = harness.load_json(_ROOT / "BENCHMARK.json")
    cell = harness.find_cell(spec, args.workload)
    harness.enable_compile_cache()
    try:
        line = harness.run_cell(cell, args.seed, args.seconds,
                                bool(args.trace), T_PROCESS)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.print_result(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
