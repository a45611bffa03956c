"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
benchmark reads: device busy time, the device time of named programs,
the operations that took most time, and idle gaps by what the host was
doing.

The harness wraps its window in a host span ``window`` and its calls into
the program in spans ``restart``, ``decode``, ``resize`` and ``host``
(``jax.profiler.TraceAnnotation``).  Device planes are the
``/device:TPU:<n>`` planes; busy time is the union of the intervals of
their ``XLA Ops`` events, and a program's time is the sum of its
``XLA Modules`` events.  Host and device events are on one clock.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_SPANS = ("restart", "decode", "resize", "host")
WINDOW = "window"

Interval = Tuple[float, float]   # (start_ns, end_ns)


@dataclass
class Reduced:
    window_s: float
    busy_s: Dict[int, float]                 # device id -> busy seconds
    module_s: Dict[str, float]               # program name -> device s
    module_calls: Dict[str, int]
    top_ops: List[Tuple[str, float]]         # op name -> device s, top 10
    idle_gaps: List[Tuple[str, float]]       # longest gaps, by host span
    idle_by_span: Dict[str, float] = field(default_factory=dict)

    def busy_mean_s(self, devices: Sequence[int]) -> float:
        return sum(self.busy_s.get(d, 0.0) for d in devices) / len(devices)

    def module_time_s(self, pattern: str) -> Optional[float]:
        """Device seconds of the programs whose name matches ``pattern``;
        None where none ran."""
        rx = re.compile(pattern)
        hits = [t for name, t in self.module_s.items() if rx.search(name)]
        return sum(hits) if hits else None


def op_name(name: str) -> str:
    """``fusion.12`` from an op event named by its HLO text
    (``%fusion.12 = bf16[...] fusion(...)``)."""
    if name.startswith("%") and " = " in name:
        return name[1:name.index(" = ")]
    return name


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi) around a union of busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _label(gap: Interval, spans: List[Tuple[str, Interval]],
           starts: List[float]) -> str:
    """The host span that overlaps the gap most; ``idle`` where none.
    ``spans`` are sorted by start and do not nest."""
    best, name = 0.0, "idle"
    i = bisect.bisect_left(starts, gap[1]) - 1
    while i >= 0:
        n, (s, e) = spans[i]
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best:
            best, name = ov, n
        if e <= gap[0] and s < gap[0]:
            break
        i -= 1
    return name


def reduce_planes(planes) -> Reduced:
    """Reduce ``jax.profiler.ProfileData(...).planes``."""
    window: Optional[Interval] = None
    spans: List[Tuple[str, Interval]] = []
    ops: Dict[int, List[Interval]] = {}
    named: List[Tuple[str, float, float]] = []
    modules: Dict[str, List[Interval]] = {}
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW and window is None:
                        window = (ev.start_ns, ev.end_ns)
                    elif ev.name in HOST_SPANS:
                        spans.append((ev.name, (ev.start_ns, ev.end_ns)))
        elif m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        ops.setdefault(dev, []).append(
                            (ev.start_ns, ev.end_ns))
                        named.append((op_name(ev.name), ev.start_ns,
                                      ev.end_ns))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        modules.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.end_ns))
    if window is None:
        raise ValueError("trace has no host span named 'window'")
    lo, hi = window
    busy_s, all_gaps = {}, []
    for dev, iv in ops.items():
        u = clip(union(iv), lo, hi)
        busy_s[dev] = sum(e - s for s, e in u) * 1e-9
        all_gaps += gaps(u, lo, hi)
    spans.sort(key=lambda x: x[1][0])
    starts = [iv[0] for _, iv in spans]
    labelled = [(_label(g, spans, starts), (g[1] - g[0]) * 1e-9)
                for g in all_gaps]
    by_span: Dict[str, float] = {}
    for n, t in labelled:
        by_span[n] = by_span.get(n, 0.0) + t
    module_s = {n: sum(e - s for s, e in clip(iv, lo, hi)) * 1e-9
                for n, iv in modules.items()}
    calls = {n: len(clip(iv, lo, hi)) for n, iv in modules.items()}
    op_time: Dict[str, float] = {}
    for n, s, e in named:
        if e > lo and s < hi:
            op_time[n] = op_time.get(n, 0.0) + min(e, hi) - max(s, lo)
    top = sorted(op_time.items(), key=lambda x: -x[1])[:10]
    return Reduced(
        window_s=(hi - lo) * 1e-9, busy_s=busy_s, module_s=module_s,
        module_calls=calls,
        top_ops=[(n, t * 1e-9) for n, t in top],
        idle_gaps=sorted(labelled, key=lambda x: -x[1])[:10],
        idle_by_span=by_span)


def reduce_file(path: str) -> Reduced:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(str(path)).planes)
