"""Reduce a profiler trace to the events the per-layer metrics read.

A trace (``*.xplane.pb`` as ``jax.profiler`` writes it) is read once into
plain lists of ``(name, start_ns, duration_ns)``:

- per device plane (``/device:TPU:<i>``): the ``XLA Ops`` line (every
  operation executed, a loop op spanning its body) and the ``XLA Modules``
  line (every program execution);
- on the host: the benchmark's own spans (``jax.profiler.TraceAnnotation``
  names starting with ``bench.``), on whatever thread recorded them.

Device and host events share the trace's clock. Op names are shortened to
the HLO instruction name (``%fusion.90``, ``%diffuse_evaporate.13``) and
module names lose their hash suffix (``jit_ants_evaluation``).
"""
from __future__ import annotations

import bisect
import glob
import os
import statistics
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, duration_ns)
SPAN_PREFIX = "bench."


def short_op(name: str) -> str:
    return name.split(" = ", 1)[0].strip()


def short_module(name: str) -> str:
    return name.split("(", 1)[0].strip()


class Trace:
    """Device events per chip and the benchmark's host spans."""

    def __init__(self, devices: Dict[str, Dict[str, List[Event]]],
                 spans: List[Event]):
        self.devices = devices               # plane -> {"ops", "modules"}
        self.spans = sorted(spans, key=lambda e: e[1])

    @classmethod
    def from_xplane(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        devices, spans = {}, []
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                lines = {ln.name: ln for ln in plane.lines}
                devices[plane.name] = {
                    "ops": [(short_op(e.name), e.start_ns, e.duration_ns)
                            for e in lines["XLA Ops"].events]
                    if "XLA Ops" in lines else [],
                    "modules": [(short_module(e.name), e.start_ns,
                                 e.duration_ns)
                                for e in lines["XLA Modules"].events]
                    if "XLA Modules" in lines else [],
                }
            elif plane.name == "/host:CPU":
                for ln in plane.lines:
                    spans.extend((e.name, e.start_ns, e.duration_ns)
                                 for e in ln.events
                                 if e.name.startswith(SPAN_PREFIX))
        return cls(devices, spans)

    @classmethod
    def from_dir(cls, directory: str) -> "Trace":
        found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                                 recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {directory}")
        return cls.from_xplane(found[-1])

    def span(self, name: str) -> Optional[Tuple[float, float]]:
        """(start, end) of the first host span called ``name``."""
        for n, s, d in self.spans:
            if n == name:
                return s, s + d
        return None


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    """Events cut to the window [lo, hi); those outside it dropped."""
    out = []
    for n, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((n, a, b - a))
    return out


def intervals(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """Union of the events' [start, end) intervals, sorted, disjoint."""
    merged: List[List[float]] = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        e = s + d
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(a, b) for a, b in merged]


def busy_ns(events: Iterable[Event], lo: float, hi: float) -> float:
    return sum(b - a for a, b in intervals(clip(events, lo, hi)))


def idle_gaps(events: Iterable[Event], lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """The window's stretches in which no event ran."""
    gaps, t = [], lo
    for a, b in intervals(clip(events, lo, hi)):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def by_name(events: Iterable[Event], prefix: str) -> Tuple[float, int]:
    """(total duration, count) of events whose name starts with prefix."""
    total, count = 0.0, 0
    for n, _, d in events:
        if n.startswith(prefix):
            total += d
            count += 1
    return total, count


def within(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    """Events that start and end inside [lo, hi]."""
    return [e for e in events if e[1] >= lo and e[1] + e[2] <= hi]


def gaps_between(events: Sequence[Event]) -> List[float]:
    """Gaps between consecutive events (end of one to start of the next)."""
    ev = sorted(events, key=lambda e: e[1])
    return [max(0.0, b[1] - (a[1] + a[2])) for a, b in zip(ev, ev[1:])]


def median(values: Sequence[float]) -> Optional[float]:
    return statistics.median(values) if values else None


def attribute(gaps: Iterable[Tuple[float, float]], spans: Sequence[Event],
              outside: str = "no benchmark span") -> Dict[str, float]:
    """Idle time by the innermost benchmark span covering each gap's
    midpoint (the shortest span that covers it)."""
    out: Dict[str, float] = {}
    starts = [s for _, s, _ in spans]
    for a, b in gaps:
        mid = (a + b) / 2
        best = None
        for n, s, d in spans[:bisect.bisect_right(starts, mid)]:
            if s <= mid <= s + d and (best is None or d < best[1]):
                best = (n, d)
        name = best[0] if best else outside
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def top(totals: Dict[str, float], k: int = 10) -> List[list]:
    return [[n, v] for n, v in sorted(totals.items(),
                                       key=lambda kv: -kv[1])[:k]]


def op_totals(events: Iterable[Event], skip: Sequence[str] = ()
              ) -> Dict[str, float]:
    """Device time by operation name; names in ``skip`` (loop ops that
    span their bodies) are left out so time is not counted twice."""
    out: Dict[str, float] = {}
    for n, _, d in events:
        if not any(n.startswith(p) for p in skip):
            out[n] = out.get(n, 0.0) + d
    return out
