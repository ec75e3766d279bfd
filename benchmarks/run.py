"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows; each module's ``run()``
additionally returns the rows as machine-readable dicts (see
``benchmarks/README.md`` for the schema).  Paper headline ratios are
asserted inside the figure benchmarks (fig7/fig8/fig9/fig10/scaling), so a
green run IS the reproduction gate.  A module that raises is reported and
the harness exits nonzero after the remaining modules ran — CI never
mistakes a crashed benchmark for a green one.

On top of the in-module asserts, the harness always applies the trajectory
gates to every emitted row: any derived ``*_err`` fraction above 5% or any
``overlap_x`` ratio below 1.0 (overlapped > serial) fails the run.
``--json DIR`` additionally writes one ``BENCH_<module>.json`` per
executed module into ``DIR`` (the benchmark-trajectory CI artifact), each
marked ``ok`` from its own module's result and gates only.

    PYTHONPATH=src python -m benchmarks.run                    # everything
    PYTHONPATH=src python -m benchmarks.run dse legion_program # subsets
    PYTHONPATH=src python -m benchmarks.run legion --json out  # + artifacts
"""
from __future__ import annotations

import json
import os
import sys
import traceback
from typing import Dict, List, Optional, Tuple

MAX_ERR_FRACTION = 0.05     # cross-validation gate: measured vs simulate()
MIN_OVERLAP_X = 1.0         # pipeline gate: overlapped must never exceed serial
# Paged-KV gate: the allocator guarantees < one page of padding per active
# request, so padding can never reach the whole page pool — a waste
# fraction at or above 1.0 means the page accounting itself broke.
MAX_WASTE_FRAC = 1.0


def _jsonable(obj):
    """numpy scalars and other numerics -> plain JSON numbers."""
    try:
        return int(obj) if float(obj).is_integer() else float(obj)
    except (TypeError, ValueError):
        return str(obj)


def _diff_friendly(obj):
    """Recursively pin floats to 6 significant digits so the trajectory
    artifacts diff cleanly between runs (benchmarks/compare.py input)."""
    if isinstance(obj, bool):
        return obj
    if isinstance(obj, float):
        return float(f"{obj:.6g}")
    if isinstance(obj, dict):
        return {k: _diff_friendly(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_diff_friendly(v) for v in obj]
    return obj


def gate_failures(rows: List[dict]) -> List[str]:
    """Trajectory gates over emitted derived values (benchmarks/README.md):
    ``*_err`` keys are error fractions (<= 5%; ``page_xval_err`` from the
    paged serve rows rides this), ``overlap_x`` keys are serial/overlapped
    cycle ratios (>= 1.0), and ``*waste_frac`` page-padding shares must
    stay under 1.0."""
    bad = []
    for row in rows:
        for key, val in row.get("derived", {}).items():
            if not isinstance(val, (int, float)) or isinstance(val, bool):
                continue
            if key.endswith("_err") and val > MAX_ERR_FRACTION:
                bad.append(f"{row['name']}: {key}={val:.4f} > "
                           f"{MAX_ERR_FRACTION:.0%} cross-validation gate")
            if key == "overlap_x" and val < MIN_OVERLAP_X:
                bad.append(f"{row['name']}: {key}={val:.4f} < "
                           f"{MIN_OVERLAP_X} (overlapped > serial)")
            if key.endswith("waste_frac") and val >= MAX_WASTE_FRAC:
                bad.append(f"{row['name']}: {key}={val:.4f} >= "
                           f"{MAX_WASTE_FRAC} (page accounting broke)")
    return bad


def write_json(json_dir: str, module: str, ok: bool, error: Optional[str],
               rows: List[dict]) -> str:
    """One ``BENCH_<module>.json`` trajectory artifact (schema v1)."""
    os.makedirs(json_dir, exist_ok=True)
    path = os.path.join(json_dir, f"BENCH_{module}.json")
    with open(path, "w") as fh:
        json.dump(
            _diff_friendly({
                "schema": 1,
                "module": module,
                "ok": ok,
                "error": error,
                "gates": {"max_err_fraction": MAX_ERR_FRACTION,
                          "min_overlap_x": MIN_OVERLAP_X,
                          "max_waste_frac": MAX_WASTE_FRAC},
                "rows": rows,
            }),
            fh, indent=2, sort_keys=True, default=_jsonable,
        )
        fh.write("\n")
    return path


def main(argv: Optional[List[str]] = None) -> None:
    from benchmarks import (
        dse, evaluation, kernel_bench, legion_program, legion_runtime,
        legion_sharded, roofline, serve_load, serve_pipeline, tpu_scale,
        workload_zoo,
    )
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    args = list(sys.argv[1:] if argv is None else argv)
    json_dir = None
    if "--json" in args:
        i = args.index("--json")
        if i + 1 >= len(args):
            print("--json needs an output directory", file=sys.stderr)
            sys.exit(2)
        json_dir = args[i + 1]
        del args[i:i + 2]
    which = set(args)

    def want(tag: str) -> bool:
        return not which or any(w in tag for w in which)

    # module registry — keep alphabetized by module name
    modules: List[Tuple[str, object]] = [
        ("dse", dse),
        ("evaluation", evaluation),
        ("kernel_bench", kernel_bench),
        ("legion_program", legion_program),
        ("legion_runtime", legion_runtime),
        ("legion_sharded", legion_sharded),
        ("roofline", roofline),
        ("serve_load", serve_load),
        ("serve_pipeline", serve_pipeline),
        ("tpu_scale", tpu_scale),
        ("workload_zoo", workload_zoo),
    ]
    assert [name for name, _ in modules] == \
        sorted(name for name, _ in modules), "module registry unalphabetized"

    selected = [(tag, module) for tag, module in modules if want(tag)]
    if which and not selected:
        print(f"# no benchmark module matches {sorted(which)}; registry: "
              f"{', '.join(name for name, _ in modules)}", file=sys.stderr)
        sys.exit(2)

    print("name,us_per_call,derived")
    # per module: (ok, error, rows, that module's own gate failures)
    results: Dict[str, Tuple[bool, Optional[str], List[dict], List[str]]] = {}
    rows: List[dict] = []
    failures: List[str] = []
    gate_bad: List[str] = []
    for tag, module in selected:
        try:
            mod_rows = module.run()
            mod_gates = gate_failures(mod_rows)
            results[tag] = (True, None, mod_rows, mod_gates)
            rows += mod_rows
            gate_bad += mod_gates
        except Exception:
            failures.append(tag)
            results[tag] = (False, traceback.format_exc(), [], [])
            traceback.print_exc()

    if json_dir is not None:
        for tag, (ok, error, mod_rows, mod_gates) in results.items():
            path = write_json(json_dir, tag, ok and not mod_gates, error,
                              mod_rows)
            print(f"# wrote {path}", file=sys.stderr)

    for msg in gate_bad:
        print(f"# TRAJECTORY GATE FAILED: {msg}", file=sys.stderr)
    if failures or gate_bad:
        print(f"# {len(failures)} benchmark module(s) FAILED"
              f"{': ' + ', '.join(failures) if failures else ''}; "
              f"{len(gate_bad)} trajectory gate(s) tripped "
              f"({len(rows)} rows)", file=sys.stderr)
        sys.exit(1)
    print(f"# {len(rows)} benchmark rows, all paper-headline asserts passed",
          file=sys.stderr)


if __name__ == "__main__":
    main()
