"""The program's own spans, recorded into the device trace, for the
per-layer readers that need them.

The engine opens ``serve.*`` spans inside each step and the Legion
executor ``legion.*`` spans inside each pass (``jax.profiler``
annotations, recorded while a trace is on).  :mod:`bench.trace_reduce`,
which the harness reduces every traced run with, keeps the benchmark's
own ``bench.*`` spans alone.  :func:`load` here keeps all three, and each
chip's ``XLA Modules`` line: one event per execution of a compiled
program, named after its jitted function (``jit_serve_decode``).
:class:`Reduced` gives every reading ``trace_reduce.Reduced`` gives, the
same on the same trace, and adds:

* idle gaps named by the innermost span open at the gap's midpoint;
* a span's self time, its length less the part its child spans cover;
* device seconds per module.

:func:`of` finds the traced run's ``.xplane.pb`` again for a reader,
since the harness hands the reader its reduction and not the file.

The device planes' clock runs about 1 ms apart from the host's: on one TPU
v5e the decode program's first operation shows about 1 ms before the host
call that dispatched it ends.  So a reading that sets device time against
host spans holds at the scale of spans much longer than that (a Legion
check, a pass), not at that of an engine step's phases; the readers of
those phases use the host's spans alone, or the device's events alone.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import heapq
import tempfile
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional

from bench import trace_reduce
from bench.trace_reduce import Event, gaps, op_name, overlap, union

SPAN_PREFIXES = ("bench.", "serve.", "legion.")
MODULES_LINE = "XLA Modules"
# where the harness keeps a traced run's profile while its readers run:
# tempfile.mkdtemp(prefix=...) in harness.run_cell
HARNESS_TRACE_GLOB = "bench_trace_*/plugins/profile/*/*.xplane.pb"


@dataclasses.dataclass
class Trace(trace_reduce.Trace):
    modules: Dict[str, List[Event]] = dataclasses.field(
        default_factory=dict)           # chip plane name -> module runs


def load(path: str) -> Trace:
    """Device operations and module runs per chip, and every host span
    named ``bench.*``, ``serve.*`` or ``legion.*``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            lines = list(plane.lines)
            ops = ([ln for ln in lines if ln.name == trace_reduce.OPS_LINE]
                   or lines)
            devices[plane.name] = [
                Event(op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                for ln in ops for e in ln.events]
            modules[plane.name] = [
                Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                for ln in lines if ln.name == MODULES_LINE
                for e in ln.events]
        elif plane.name.startswith("/host:"):
            spans.extend(
                Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                for ln in plane.lines for e in ln.events
                if e.name.startswith(SPAN_PREFIXES))
    spans.sort(key=lambda e: e.start)
    return Trace(devices, spans, modules)


class Reduced(trace_reduce.Reduced):
    """``trace_reduce.Reduced`` over a trace that holds the program's spans
    and module runs too."""

    def __init__(self, trace: Trace, chips: int) -> None:
        super().__init__(trace, chips)
        self.modules = {
            name: [e for e in evs if e.end > self.t0 and e.start < self.t1]
            for name, evs in trace.modules.items()}

    def has_spans(self, prefix: str) -> bool:
        return any(s.name.startswith(prefix) for s in self.spans)

    def idle_by_span(self) -> Dict[str, float]:
        """Idle seconds of the window, by the innermost host span that holds
        the gap's midpoint (``host`` where none does): of the spans open
        then, the latest opened, and of those the first to close.  Where
        spans do not nest, as the benchmark's own do not, that is the
        latest-opened span that holds it, as ``trace_reduce`` names it."""
        out: Dict[str, float] = defaultdict(float)
        for merged in self.busy.values():
            heap: list = []
            i = 0
            for g0, g1 in gaps(merged, self.t0, self.t1):
                mid = 0.5 * (g0 + g1)
                while i < len(self.spans) and self.spans[i].start <= mid:
                    s = self.spans[i]
                    heapq.heappush(heap, (-s.start, s.end, i))
                    i += 1
                while heap and heap[0][1] < mid:
                    heapq.heappop(heap)     # closed before all later gaps
                name = self.spans[heap[0][2]].name if heap else "host"
                out[name] += (g1 - g0) * 1e-9 / self.chips
        return out

    def span_seconds(self, name: str) -> float:
        """Seconds of every span named ``name`` in the window."""
        return sum(s.end - s.start for s in self.spans_named(name)) * 1e-9

    def self_seconds(self, name: str) -> float:
        """Seconds of every span named ``name`` in the window, less the part
        of each that the other spans inside it cover."""
        starts = [s.start for s in self.spans]
        total = 0.0
        for p in self.spans_named(name):
            lo = bisect.bisect_left(starts, p.start)
            hi = bisect.bisect_right(starts, p.end)
            inner = union([(s.start, s.end) for s in self.spans[lo:hi]
                           if s is not p and s.end <= p.end])
            total += (p.end - p.start) - overlap(inner, p.start, p.end)
        return total * 1e-9

    def busy_in_each(self, spans: List[Event]) -> List[float]:
        """``busy_in`` of each span (device-busy seconds inside it, averaged
        over chips), in one sorted look-up per span."""
        out = [0.0] * len(spans)
        for merged in self.busy.values():
            starts = [s for s, _ in merged]
            for k, sp in enumerate(spans):
                t0, t1 = max(sp.start, self.t0), min(sp.end, self.t1)
                i = max(bisect.bisect_right(starts, t0) - 1, 0)
                while i < len(merged) and merged[i][0] < t1:
                    s, e = merged[i]
                    out[k] += max(0.0, min(e, t1) - max(s, t0))
                    i += 1
        return [v * 1e-9 / self.chips for v in out]

    def module_seconds(self, match: Optional[str] = None
                       ) -> Dict[str, float]:
        """Device seconds per module name inside the window (averaged over
        chips), optionally only names containing ``match``."""
        out: Dict[str, float] = defaultdict(float)
        for evs in self.modules.values():
            for e in evs:
                if match is None or match in e.name:
                    out[e.name] += (min(e.end, self.t1)
                                    - max(e.start, self.t0)) * 1e-9
        return {k: v / self.chips for k, v in out.items()}

    def module_runs(self, match: str) -> List[Event]:
        """Runs of modules whose name contains ``match`` that lie wholly
        inside the window, on every chip."""
        return [e for evs in self.modules.values() for e in evs
                if match in e.name and e.start >= self.t0
                and e.end <= self.t1]


def _mtime(p: Path) -> int:
    try:
        return p.stat().st_mtime_ns
    except OSError:
        return 0


@functools.lru_cache(maxsize=1)
def _reduce(path: str, mtime_ns: int, chips: int) -> Reduced:
    return Reduced(load(path), chips)


def of(view) -> Optional[Reduced]:
    """The reduction with the program's spans of the run ``view`` reads:
    ``view.trace`` where it is one already, else the profile under the
    temporary directory whose window is the one ``view.trace`` holds
    (newest first).  None where no profile there has that window."""
    r = view.trace
    if isinstance(r, Reduced):
        return r
    found = sorted(Path(tempfile.gettempdir()).glob(HARNESS_TRACE_GLOB),
                   key=_mtime, reverse=True)
    for p in found:
        try:
            got = _reduce(str(p), _mtime(p), r.chips)
        except (OSError, RuntimeError, ValueError):
            continue        # gone since, not whole yet, or holds no window
        if (got.t0, got.t1) == (r.t0, r.t1):
            return got
    return None
