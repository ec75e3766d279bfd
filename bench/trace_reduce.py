"""One reduction from a profiler trace (``.xplane.pb``) to device time.

:func:`load` reads the device operations of every chip and the benchmark's
own host spans (``bench.*`` annotations).  :class:`Reduced` then gives, for
the traced window (the ``bench.window`` span):

* busy time: the union of the intervals in which an operation ran on a
  chip, averaged over the chips; the idle share is 1 - busy / window;
* device time inside any host span (a step, a pass);
* the operations that took most time, and the idle gaps named by the host
  span the gap falls in.

Times are nanoseconds on the trace's clock, which the profiler shares
between the host and the device planes.
"""
from __future__ import annotations

import bisect
import dataclasses
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Event:
    name: str
    start: float
    end: float


def op_name(text: str) -> str:
    """``while.14`` from an operation's HLO text ``%while.14 = (...) ...``
    (the device planes name each event by its whole instruction)."""
    return text.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Event]]     # chip plane name -> operations
    spans: List[Event]                  # host spans named bench.*


def load(path: str) -> Trace:
    """Device operations per chip and the benchmark's host spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            plane_lines = list(plane.lines)
            ops = ([ln for ln in plane_lines if ln.name == OPS_LINE]
                   or plane_lines)
            devices[plane.name] = [
                Event(op_name(e.name), e.start_ns,
                      e.start_ns + e.duration_ns)
                for ln in ops for e in ln.events]
        elif plane.name.startswith("/host:"):
            spans.extend(
                Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                for ln in plane.lines for e in ln.events
                if e.name.startswith(SPAN_PREFIX))
    spans.sort(key=lambda e: e.start)
    return Trace(devices, spans)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def overlap(merged: Sequence[Interval], t0: float, t1: float) -> float:
    """Length of ``merged`` inside ``[t0, t1]``."""
    i = max(bisect.bisect_right([s for s, _ in merged], t0) - 1, 0)
    total = 0.0
    for s, e in merged[i:]:
        if s >= t1:
            break
        total += max(0.0, min(e, t1) - max(s, t0))
    return total


def gaps(merged: Sequence[Interval], t0: float, t1: float) -> List[Interval]:
    """Idle intervals of ``[t0, t1]`` outside ``merged``."""
    out, cur = [], t0
    for s, e in merged:
        if e <= cur:
            continue
        if s >= t1:
            break
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        out.append((cur, t1))
    return out


class Reduced:
    """A trace reduced over its ``bench.window`` span."""

    def __init__(self, trace: Trace, chips: int) -> None:
        windows = [s for s in trace.spans if s.name == WINDOW_SPAN]
        if not windows:
            raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
        self.t0, self.t1 = windows[0].start, windows[0].end
        self.chips = chips
        self.spans = [s for s in trace.spans if s.name != WINDOW_SPAN]
        self.ops = {name: [e for e in evs if e.end > self.t0
                           and e.start < self.t1]
                    for name, evs in trace.devices.items()}
        self.busy = {name: union([(e.start, e.end) for e in evs])
                     for name, evs in self.ops.items()}

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def busy_in(self, t0: float, t1: float) -> float:
        """Device-busy seconds inside ``[t0, t1]``, averaged over chips."""
        t0, t1 = max(t0, self.t0), min(t1, self.t1)
        if t1 <= t0:
            return 0.0
        total = sum(overlap(b, t0, t1) for b in self.busy.values())
        return total * 1e-9 / self.chips

    @property
    def busy_s(self) -> float:
        return self.busy_in(self.t0, self.t1)

    def spans_named(self, name: str) -> List[Event]:
        return [s for s in self.spans if s.name == name
                and s.start >= self.t0 and s.end <= self.t1]

    def op_seconds(self, match: Optional[str] = None) -> Dict[str, float]:
        """Device seconds per operation name inside the window (averaged
        over chips), optionally only names containing ``match``."""
        out: Dict[str, float] = defaultdict(float)
        for evs in self.ops.values():
            for e in evs:
                if match is None or match in e.name:
                    out[e.name] += (min(e.end, self.t1)
                                    - max(e.start, self.t0)) * 1e-9
        return {k: v / self.chips for k, v in out.items()}

    def idle_by_span(self) -> Dict[str, float]:
        """Idle seconds of the window, by the latest-opened host span that
        holds the gap's midpoint (``host`` where no benchmark span is
        open; the benchmark's spans do not nest)."""
        out: Dict[str, float] = defaultdict(float)
        starts = [s.start for s in self.spans]
        for merged in self.busy.values():
            for g0, g1 in gaps(merged, self.t0, self.t1):
                mid = 0.5 * (g0 + g1)
                i = bisect.bisect_right(starts, mid) - 1
                name = (self.spans[i].name
                        if i >= 0 and self.spans[i].end >= mid else "host")
                out[name] += (g1 - g0) * 1e-9 / self.chips
        return out

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])
        idle = sorted(self.idle_by_span().items(), key=lambda kv: -kv[1])
        return {"device_ops": [[k, v] for k, v in ops[:top]],
                "idle_gaps": [[k, v] for k, v in idle[:top]]}


def paired(reduced: "Reduced", span: str, records: List[dict]):
    """(record, device-busy seconds) for each host span named ``span`` in
    the window, paired in order with ``records`` (one per span)."""
    spans = reduced.spans_named(span)
    return [(r, reduced.busy_in(s.start, s.end))
            for r, s in zip(records, spans)]
