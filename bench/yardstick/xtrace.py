"""Reduction of a profiler trace to device busy time, op totals and gaps.

``load`` reads the ``.xplane.pb`` the JAX profiler writes: the device's
operations (the ``XLA Ops`` line of each ``/device:`` plane) and the
host's spans (``bench.*`` annotations). Everything after it works on
plain ``(name, start, end)`` tuples in seconds, so it can be checked on a
small synthetic trace.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
Event = Tuple[str, float, float]


SPAN_PREFIX = "bench."


def load(path: str) -> Dict[str, object]:
    """{"devices": {plane: [op events]}, "spans": [host span events]},
    times in seconds on the trace's clock."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            ops = lines.get("XLA Ops")
            if ops is None:
                continue
            devices[plane.name] = [
                (e.name, e.start_ns * 1e-9,
                 (e.start_ns + e.duration_ns) * 1e-9) for e in ops.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9))
    spans.sort(key=lambda s: s[1])
    return {"devices": devices, "spans": spans}


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping intervals; sorted and disjoint."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


class Busy:
    """Disjoint busy intervals with fast overlap queries."""

    def __init__(self, intervals: Sequence[Interval]):
        self.iv = union(intervals)
        self.starts = [a for a, _ in self.iv]

    def within(self, a: float, b: float) -> float:
        """Seconds busy inside [a, b]."""
        if b <= a:
            return 0.0
        i = max(bisect.bisect_right(self.starts, a) - 1, 0)
        tot = 0.0
        while i < len(self.iv) and self.iv[i][0] < b:
            s, e = self.iv[i]
            tot += max(0.0, min(e, b) - max(s, a))
            i += 1
        return tot

    def gaps(self, a: float, b: float) -> List[Interval]:
        """Idle intervals inside [a, b]."""
        out, t = [], a
        for s, e in self.iv:
            if e <= a:
                continue
            if s >= b:
                break
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if t < b:
            out.append((t, b))
        return out


CONTAINERS = (" while(", " conditional(", " call(")
NAME_CHARS = 120


def top_ops(events: Sequence[Event], a: float, b: float, n: int = 10
            ) -> List[Tuple[str, float]]:
    """The n ops with most device time inside [a, b], by HLO text cut to
    NAME_CHARS. Loops and calls are left out: the ops inside them are
    events of their own."""
    tot: Dict[str, float] = defaultdict(float)
    for name, s, e in events:
        d = min(e, b) - max(s, a)
        if d > 0 and not any(c in name for c in CONTAINERS):
            tot[name[:NAME_CHARS]] += d
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def innermost(spans: Sequence[Event], t: float) -> Optional[str]:
    """Name of the latest-starting span that covers time t."""
    best = None
    for name, s, e in spans:
        if s > t:
            break
        if e >= t and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else None


def named_gaps(busy: Busy, spans: Sequence[Event], a: float, b: float,
               n: int = 10) -> List[Tuple[str, float]]:
    """The n longest idle gaps in [a, b], each named by the host span that
    covers its middle ("host: none" where no span does)."""
    gaps = sorted(busy.gaps(a, b), key=lambda g: g[0] - g[1])[:n]
    return [(f"host: {innermost(spans, (s + e) / 2) or 'none'}", e - s)
            for s, e in gaps]
