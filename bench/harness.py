"""The benchmark's harness: finds a cell's files by name, runs its driver,
reads its metrics and prints the result.

Everything that belongs to one thing sits in a file of its own, found by the
name ``BENCHMARK.json`` gives it:

* a configuration: the ``file`` of its entry (``bench/configs/``);
* a traffic mix: ``bench/traffic/<traffic>.json``, which names its driver;
* a driver: ``bench/drivers/<driver>.py``, with ``run(ctx) -> Outcome``;
* a per-layer metric: ``bench/metrics/<name>.py``, with
  ``read(view) -> float | None``; a name with a suffix after a dot, such
  as ``device_idle_share.serve``, falls back to the reader of its stem
  (``device_idle_share.py``) when it has no file of its own.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace(".", "_")
        .replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload with everything it names, loaded from its files."""

    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(spec: dict, name: str, root: Path = ROOT) -> Cell:
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
    return Cell(
        name=name, chips=w["chips"],
        config=load_json(root / cfg["file"]),
        mix=load_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
    )


def peak_of(kind: str) -> dict:
    """The published peaks of a device kind; an unknown kind is an error."""
    table = load_json(BENCH / "peaks.json")
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json; "
                       f"known: {sorted(table)}")
    return table[kind]


def enable_compile_cache() -> str:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` or the fixed
    ``.jax_cache/`` of this checkout; every program is kept, however
    quickly it compiled."""
    import jax

    path = os.environ.get(CACHE_ENV) or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def metric_file(name: str) -> Path:
    """The reader of a per-layer metric: its own file, or its stem's."""
    own = BENCH / "metrics" / f"{name}.py"
    if own.is_file():
        return own
    return BENCH / "metrics" / f"{name.split('.')[0]}.py"


def device_info(chips: int) -> dict:
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    if info["platform"] != "tpu":
        raise NoChip(f"the benchmark needs a TPU; JAX found {info}")
    if info["count"] < chips:
        raise NoChip(f"the cell needs {chips} chip(s); JAX found {info}")
    return info


class CompileClock:
    """Counts JAX's backend compiles and persistent-cache reads."""

    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw) -> None:
        if event == self.COMPILE:
            self.seconds += secs
            self.compiles += 1
            self.names.append(kw.get("fun_name", ""))

    def _event(self, event, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def count(self) -> int:
        return self.compiles + self.cache_hits


class RunContext:
    """What a driver gets: the cell, the run's options, and the clocks."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 t_process: float, clock: CompileClock, peak: dict,
                 chips: int, trace_dir: Optional[str] = None) -> None:
        self.config = cell.config
        self.mix = cell.mix
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.peak = peak
        self.chips = chips
        self.clock = clock
        self._t_process = t_process
        self.setup_s: Optional[float] = None
        self.window_compiles: Optional[int] = None
        self.memory_peak_bytes: Optional[int] = None
        self.trace_dir = trace_dir
        self._annotation = None
        self._compiles0 = 0

    def open_window(self) -> float:
        """Set-up ends here; returns the window's start on perf_counter."""
        now = time.perf_counter()
        self.setup_s = now - self._t_process
        self._compiles0 = self.clock.count()
        self._names0 = len(self.clock.names)
        return now

    def close_window(self) -> None:
        self.window_compiles = self.clock.count() - self._compiles0
        self.window_compiled = self.clock.names[self._names0:]

    def read_memory(self) -> None:
        """The fullest chip's high-water mark so far."""
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.devices()[:self.chips]]
        peaks = [p for p in peaks if p is not None]
        self.memory_peak_bytes = max(peaks) if peaks else None

    def start_trace(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._annotation = jax.profiler.TraceAnnotation("bench.window")
        self._annotation.__enter__()

    def stop_trace(self) -> None:
        import jax

        self._annotation.__exit__(None, None, None)
        self._annotation = None
        jax.profiler.stop_trace()


@dataclasses.dataclass
class Outcome:
    """What a driver returns."""

    end_to_end: Dict[str, float]
    checks: Dict[str, Dict[str, float]]     # name -> {"value", "limit"}
    attempted: int
    failed: int
    observed: dict                           # what per-layer readers read
    notes: Dict[str, object] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class View:
    """What a per-layer metric reader reads."""

    observed: dict
    trace: object                            # trace_reduce.Reduced
    peak: dict


def _trace_file(trace_dir: str) -> str:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return str(found[-1])


def correct(checks: Dict[str, Dict[str, float]]) -> bool:
    """A run is correct where every number compared is within its limit."""
    return bool(checks) and all(c["value"] <= c["limit"]
                                for c in checks.values())


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_process: float) -> dict:
    """Run one cell and return its result line (a dict); the numbers
    compared go to standard error, each beside its limit."""
    from bench import trace_reduce

    clock = CompileClock()
    device = device_info(cell.chips)
    peak = peak_of(device["kind"])
    driver = load_module(BENCH / "drivers" / f"{cell.mix['driver']}.py")
    with contextlib.ExitStack() as stack:
        trace_dir = None
        if trace:
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            stack.callback(shutil.rmtree, trace_dir, True)
        ctx = RunContext(cell, seed, seconds, trace, t_process, clock, peak,
                         cell.chips, trace_dir)
        outcome = driver.run(ctx)
        line: dict = {"correct": None, "attempted": outcome.attempted,
                      "failed": outcome.failed, "metrics": {}}
        device["memory_peak_bytes"] = ctx.memory_peak_bytes
        if trace:
            reduced = trace_reduce.Reduced(
                trace_reduce.load(_trace_file(trace_dir)), cell.chips)
            view = View(outcome.observed, reduced, peak)
            for m in cell.per_layer:
                reader = load_module(metric_file(m["name"]))
                value = reader.read(view)
                if value is not None:
                    line["metrics"][m["name"]] = {"value": value,
                                                  "unit": m["unit"]}
            device["busy_s"] = reduced.busy_s
            device["window_s"] = reduced.window_s
            line["breakdown"] = reduced.breakdown()
        else:
            values = dict(outcome.end_to_end, setup_s=ctx.setup_s)
            for m in cell.end_to_end:
                line["metrics"][m["name"]] = {"value": values[m["name"]],
                                              "unit": m["unit"]}
    line["device"] = device
    line["correct"] = correct(outcome.checks)
    notes = dict(outcome.notes, setup_s=ctx.setup_s,
                 window_compiles=ctx.window_compiles,
                 window_compiled=sorted(set(ctx.window_compiled)),
                 compile_s_total=clock.seconds)
    print("notes " + json.dumps(notes, default=float), flush=True)
    line["checks"] = outcome.checks
    return line


def print_result(line: dict) -> None:
    """The numbers compared, as the last lines of standard error, then the
    result as the last line of standard output."""
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line, default=float), flush=True)
