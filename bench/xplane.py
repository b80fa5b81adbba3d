"""Reduction of a profiler trace (``*.xplane.pb``) to device numbers.

What it reads, from a trace of the measured window:

* device operations: the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane,
  one event per operation with its start and duration;
* host spans: the benchmark's own ``bench.<name>`` annotations on the
  ``/host:CPU`` plane, each with the stats it was opened with (the
  ``call`` index of an engine call).

Host and device events share the trace's clock, so a device operation can
be attributed to the engine call that was running when it started, and an
idle gap of the device to what the host was doing in it.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
# when spans nest, a gap is named by the innermost: engine calls run inside
# the runtime's step
SPAN_ORDER = ("bench.prefill_chunk", "bench.decode_stage", "bench.step")
HOST_OTHER = "host-other"

Interval = Tuple[float, float]


class Trace:
    """Device operations and benchmark host spans of one trace."""

    def __init__(self, ops: Dict[str, List[Tuple[float, float, str]]],
                 spans: List[Tuple[float, float, str, Dict]]):
        self.ops = ops            # device plane -> [(start_ns, end_ns, name)]
        self.spans = spans        # [(start_ns, end_ns, name, stats)]

    @classmethod
    def load(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        ops: Dict[str, List[Tuple[float, float, str]]] = {}
        spans: List[Tuple[float, float, str, Dict]] = []
        for plane in data.planes:
            if DEVICE_PLANE.match(plane.name):
                evs = []
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        evs += [(e.start_ns, e.start_ns + e.duration_ns,
                                 e.name) for e in line.events]
                ops[plane.name] = sorted(evs)
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(SPAN_PREFIX):
                            spans.append((e.start_ns,
                                          e.start_ns + e.duration_ns, e.name,
                                          {k: v for k, v in e.stats}))
        return cls(ops, sorted(spans))

    def extent(self) -> Optional[Interval]:
        """First and last instant any event of the trace covers."""
        pts = [t for evs in self.ops.values() for s, e, _ in evs
               for t in (s, e)]
        pts += [t for s, e, _, _ in self.spans for t in (s, e)]
        return (min(pts), max(pts)) if pts else None


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping cover of ``intervals``."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ops: Sequence[Tuple[float, float, str]]) -> float:
    """Time in which at least one operation ran."""
    return sum(e - s for s, e in union([(s, e) for s, e, _ in ops]))


def gaps(ops: Sequence[Tuple[float, float, str]],
         window: Interval) -> List[Interval]:
    """Idle intervals of the device inside ``window``."""
    out, t = [], window[0]
    for s, e in union([(s, e) for s, e, _ in ops]):
        if s > t:
            out.append((t, min(s, window[1])))
        t = max(t, e)
    if t < window[1]:
        out.append((t, window[1]))
    return [(s, e) for s, e in out if e > s]


def op_kind(name: str) -> str:
    """``%fusion.568 = bf16[...] fusion(...)`` -> ``fusion``."""
    head = name.split(" = ")[0].lstrip("%").strip()
    return re.sub(r"(\.\d+)+$", "", head) or name


def top_ops(ops: Sequence[Tuple[float, float, str]],
            n: int = 10) -> List[List]:
    """[[operation kind, seconds]] of the ``n`` kinds that took longest."""
    tot: Dict[str, float] = defaultdict(float)
    for s, e, name in ops:
        tot[op_kind(name)] += e - s
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


class SpanIndex:
    """Which benchmark span is running at an instant.  Spans of one name
    come from one thread, one after another, so they never overlap."""

    def __init__(self, spans: Sequence[Tuple[float, float, str, Dict]]):
        self.by_name = {}
        for name in SPAN_ORDER:
            ss = sorted((s, e) for s, e, n, _ in spans if n == name)
            self.by_name[name] = ([s for s, _ in ss], [e for _, e in ss])

    def at(self, t: float) -> str:
        """Name of the innermost span running at ``t``."""
        for name in SPAN_ORDER:
            starts, ends = self.by_name[name]
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and t < ends[i]:
                return name
        return HOST_OTHER


def idle_by_host(trace: Trace, plane: str, window: Interval,
                 n: int = 10) -> List[List]:
    """[[host activity, seconds]] of the device's idle time in ``window``,
    each gap named by the span running at its midpoint, longest first."""
    index = SpanIndex(trace.spans)
    tot: Dict[str, float] = defaultdict(float)
    for s, e in gaps(trace.ops[plane], window):
        tot[index.at((s + e) / 2)] += e - s
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def kernel_ns_by_call(trace: Trace, plane: str, kernel: str,
                      span: str) -> Dict[int, float]:
    """Device time of operations of kind ``kernel`` (see ``op_kind``),
    summed per ``call`` of the ``span`` that was open when each started.
    Operations outside every such span are not counted."""
    calls = [(s, e, st.get("call")) for s, e, name, st in trace.spans
             if name == span and st.get("call") is not None]
    starts = [c[0] for c in calls]
    out: Dict[int, float] = defaultdict(float)
    for s, e, name in trace.ops.get(plane, []):
        if op_kind(name) != kernel:
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < calls[i][1]:
            out[int(calls[i][2])] += e - s
    return dict(out)
