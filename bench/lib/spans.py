"""The serving engine's host spans against the device's idle time.

The engine brackets each phase of its tick with a profiler annotation
(``serve.admit``, ``serve.prefill``, ``serve.chunk``, ``serve.decode``,
``serve.readback``, ``serve.emit``, ``serve.telemetry``), so the phases
reach ``Reduced.host`` on the device's clock. The readers here measure how
much of the traced window's device-idle time lies inside a set of them.
A program without those spans gives nothing to read.
"""

from __future__ import annotations

from typing import Iterable, List, Optional, Tuple

from .trace import union

STEP = ("serve.decode", "serve.readback", "serve.emit", "serve.telemetry")
JOIN = ("serve.admit", "serve.prefill")

Intervals = List[Tuple[float, float]]


def idle_intervals(red) -> Intervals:
    """The intervals of the window ``[t0, t1]`` in which no device
    operation ran (trace clock, ns), in order."""
    out, prev = [], red.t0
    for a, b in red.busy_iv:
        if a > prev:
            out.append((prev, a))
        prev = max(prev, b)
    if red.t1 > prev:
        out.append((prev, red.t1))
    return out


def overlap_ns(a: Intervals, b: Intervals) -> float:
    """Length of the intersection of two ordered lists of disjoint
    intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def spans(red, names: Iterable[str]) -> Intervals:
    """The union of the host spans with these names that reach into the
    window."""
    names = set(names)
    return union([
        (s, s + d) for s, d, name, *_ in red.host
        if name in names and s < red.t1 and s + d > red.t0
    ])


def idle_inside_s(red, names: Iterable[str]) -> Optional[float]:
    """Seconds of device-idle time in the window that lie inside some span
    with one of these names (nested or overlapping spans count once), or
    None where no such span reaches into the window."""
    iv = spans(red, names)
    return overlap_ns(iv, idle_intervals(red)) * 1e-9 if iv else None


def starts(red, name: str) -> int:
    """Host spans named ``name`` that start inside the window."""
    return sum(1 for s, _, n, *_ in red.host if n == name and red.t0 <= s < red.t1)
