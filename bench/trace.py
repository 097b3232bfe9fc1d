"""Reduction of a JAX profiler trace (``.xplane.pb``) to device time.

``load`` reads the device operations of every TPU in the trace and the
host's Python events; the pure functions below reduce them over a
window of the trace clock:

- ``busy_ns``: the union of the intervals in which any operation ran on
  one device (overlapping operations count once);
- ``per_op_ns``: device time per operation name, clipped to the window,
  over leaf operations only (a ``while`` loop's event spans the ops of
  its body, which are counted themselves);
- ``idle_gaps``: the stretches of the window in which the device ran
  nothing, longest first, each labelled by the host event that covered
  its middle (what the host was doing while the device waited).

Times are integers of nanoseconds on the trace's own clock.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


@dataclasses.dataclass
class Event:
    """One trace event: ``[start, end)`` in ns, with its name."""

    start: int
    end: int
    name: str


@dataclasses.dataclass
class Trace:
    """Device operations per device plane, and host events."""

    devices: Dict[str, List[Event]]
    host: List[Event]

    def host_named(self, name: str) -> List[Event]:
        """Host events called ``name``, in start order."""
        return sorted((e for e in self.host if e.name == name),
                      key=lambda e: e.start)


def load(path: str) -> Trace:
    """Read ``path`` (an ``.xplane.pb``) with JAX's own reader."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    start = int(ev.start_ns)
                    ops.append(Event(start, start + int(ev.duration_ns),
                                     op_name(ev.name)))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    start = int(ev.start_ns)
                    host.append(Event(start, start + int(ev.duration_ns),
                                      ev.name))
    return Trace(devices=devices, host=host)


def op_name(hlo: str) -> str:
    """The instruction name of a device op event (``%fusion.12 = bf16[..]
    fusion(...)`` -> ``fusion.12``)."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def leaves(events: Sequence[Event]) -> List[Event]:
    """The events that contain no other event (of the same list)."""
    order = sorted(events, key=lambda e: (e.start, -e.end))
    parent = [False] * len(order)
    stack: List[int] = []
    for i, e in enumerate(order):
        while stack and order[stack[-1]].end <= e.start:
            stack.pop()
        if stack and e.end <= order[stack[-1]].end:
            parent[stack[-1]] = True
        stack.append(i)
    return [e for e, p in zip(order, parent) if not p]


def clip(intervals: Iterable[Interval], w0: int, w1: int) -> List[Interval]:
    """Intervals cut to ``[w0, w1)``; empty ones dropped."""
    out = []
    for a, b in intervals:
        a, b = max(a, w0), min(b, w1)
        if b > a:
            out.append((a, b))
    return out


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """The union of ``intervals`` as sorted, disjoint intervals."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def busy_ns(events: Sequence[Event], w0: int, w1: int) -> int:
    """Nanoseconds of ``[w0, w1)`` in which any of ``events`` ran."""
    return sum(b - a for a, b in merge(
        clip(((e.start, e.end) for e in events), w0, w1)))


def per_op_ns(events: Sequence[Event], w0: int, w1: int) -> Dict[str, int]:
    """Device time per operation name within ``[w0, w1)``, leaf
    operations only."""
    out: Dict[str, int] = {}
    for e in leaves(events):
        a, b = max(e.start, w0), min(e.end, w1)
        if b > a:
            out[e.name] = out.get(e.name, 0) + b - a
    return out


def idle_gaps(events: Sequence[Event], w0: int, w1: int) -> List[Interval]:
    """The idle stretches of ``[w0, w1)``, longest first (ties by start)."""
    gaps, t = [], w0
    for a, b in merge(clip(((e.start, e.end) for e in events), w0, w1)):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if w1 > t:
        gaps.append((t, w1))
    return sorted(gaps, key=lambda g: (-(g[1] - g[0]), g[0]))


def label_at(host: Sequence[Event], t: int,
             default: str = "no host event") -> str:
    """Name of the shortest host event that covers time ``t`` — the
    innermost thing the host was doing then."""
    best: Optional[Event] = None
    for e in host:
        if e.start <= t < e.end and (best is None or
                                     e.end - e.start < best.end - best.start):
            best = e
    return best.name if best is not None else default
