"""Reduction of a JAX profiler trace to device metrics.

The benchmark traces its measured window with ``jax.profiler`` and
wraps each call into the program in a ``TraceAnnotation`` named
``bench.*`` (``bench.window`` around the whole window), so host spans
and device operations share the profiler's clock. ``load`` flattens the
``.xplane.pb`` into ``Event`` tuples; ``Trace`` reduces them:

- busy time: the union of the intervals of the operations on a device's
  op line, inside the window, averaged over the devices used;
- the device operations that took most time;
- the idle gaps, each attributed to the ``bench.*`` host span that
  overlaps it (what the host was doing while the device waited).
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

__all__ = ["Event", "Trace", "load", "op_name", "union_ns", "WINDOW_SPAN"]

WINDOW_SPAN = "bench.window"
HOST_PREFIX = "bench."
DEVICE_PLANE_PREFIX = "/device:"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
NO_SPAN = "host outside any bench span"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load(logdir: str) -> List[Event]:
    """Every event of the one ``.xplane.pb`` under ``logdir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane trace under {logdir}, "
                           f"found {len(paths)}")
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


def union_ns(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted ``(start, end)`` intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def op_name(name: str) -> str:
    """An XLA op event's name without its HLO text: ``%fusion.8 = s32[...]
    fusion(...)`` reads ``%fusion.8``."""
    return name.split(" = ", 1)[0]


def _leaves(events: List[Event]) -> List[Event]:
    """The events of one line that enclose no other (a ``while`` op spans
    the ops of its body on the same line)."""
    evs = sorted(events, key=lambda e: (e.plane, e.start_ns, -e.dur_ns))
    out = []
    for i, e in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if (nxt is not None and nxt.plane == e.plane
                and nxt.start_ns < e.end_ns and nxt.end_ns <= e.end_ns):
            continue
        out.append(e)
    return out


def _clip(s: float, e: float, lo: float, hi: float) -> Optional[Tuple[float, float]]:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


class Trace:
    def __init__(self, events: List[Event]):
        wins = [e for e in events if e.name == WINDOW_SPAN
                and not e.plane.startswith(DEVICE_PLANE_PREFIX)]
        if len(wins) != 1:
            raise RuntimeError(f"expected one {WINDOW_SPAN} span in the "
                               f"trace, found {len(wins)}")
        self.lo, self.hi = wins[0].start_ns, wins[0].end_ns
        self.events = events
        self.devices = sorted({e.plane for e in events
                               if e.plane.startswith(DEVICE_PLANE_PREFIX)
                               and e.line == OP_LINE})

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def ops(self, device: Optional[str] = None) -> List[Event]:
        """Device operations inside the window (all devices by default)."""
        return [e for e in self.events
                if e.line == OP_LINE and e.plane.startswith(DEVICE_PLANE_PREFIX)
                and (device is None or e.plane == device)
                and e.end_ns > self.lo and e.start_ns < self.hi]

    def modules(self) -> List[Event]:
        return [e for e in self.events
                if e.line == MODULE_LINE
                and e.plane.startswith(DEVICE_PLANE_PREFIX)
                and e.end_ns > self.lo and e.start_ns < self.hi]

    def busy(self, device: str) -> List[Tuple[float, float]]:
        spans = (_clip(e.start_ns, e.end_ns, self.lo, self.hi)
                 for e in self.ops(device))
        return union_ns(s for s in spans if s is not None)

    @property
    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over the devices
        (0 when the trace shows no device)."""
        if not self.devices:
            return 0.0
        tot = sum(e - s for d in self.devices for s, e in self.busy(d))
        return tot * 1e-9 / len(self.devices)

    @property
    def idle_share(self) -> Optional[float]:
        """Idle share of the window in percent; None without a device."""
        if not self.devices:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def op_time_s(self, match) -> float:
        """Device seconds of the operations whose name ``match`` accepts,
        summed over devices, clipped to the window."""
        tot = 0.0
        for e in self.ops():
            if match(e.name):
                c = _clip(e.start_ns, e.end_ns, self.lo, self.hi)
                tot += (c[1] - c[0]) if c else 0.0
        return tot * 1e-9

    def top_ops(self, k: int = 10) -> List[List]:
        """The ``k`` device operations (by op name, containers such as a
        ``while`` left out) that ran longest in the window, in seconds
        summed over devices."""
        acc: Dict[str, float] = {}
        for e in _leaves(self.ops()):
            c = _clip(e.start_ns, e.end_ns, self.lo, self.hi)
            if c:
                n = op_name(e.name)
                acc[n] = acc.get(n, 0.0) + (c[1] - c[0]) * 1e-9
        return [[n, s] for n, s in sorted(acc.items(), key=lambda x: -x[1])[:k]]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """Idle device time by what the host was doing: the ``bench.*``
        span covering each part of each gap, summed by span name over
        the devices (averaged), largest first. The ``bench.*`` spans do
        not nest, so the one that starts last before a gap is the first
        that can cover it."""
        host = [(e.start_ns, e.end_ns, e.name) for e in self.events
                if e.name.startswith(HOST_PREFIX) and e.name != WINDOW_SPAN
                and not e.plane.startswith(DEVICE_PLANE_PREFIX)]
        host.sort()
        starts = [h[0] for h in host]
        acc: Dict[str, float] = {}
        for d in self.devices or []:
            cur = self.lo
            gaps = []
            for s, e in self.busy(d) + [(self.hi, self.hi)]:
                if s > cur:
                    gaps.append((cur, s))
                cur = max(cur, e)
            for gs, ge in gaps:
                covered = 0.0
                first = max(0, bisect.bisect_right(starts, gs) - 1)
                for hs, he, name in host[first:]:
                    if hs >= ge:
                        break
                    c = _clip(hs, he, gs, ge)
                    if c:
                        acc[name] = acc.get(name, 0.0) + (c[1] - c[0])
                        covered += c[1] - c[0]
                rest = (ge - gs) - covered
                if rest > 0:
                    acc[NO_SPAN] = acc.get(NO_SPAN, 0.0) + rest
        n = max(len(self.devices), 1)
        return [[name, ns * 1e-9 / n] for name, ns in
                sorted(acc.items(), key=lambda x: -x[1])[:k]]

    def host_spans(self, name: str) -> List[Event]:
        return [e for e in self.events if e.name == name
                and not e.plane.startswith(DEVICE_PLANE_PREFIX)
                and e.end_ns > self.lo and e.start_ns < self.hi]
