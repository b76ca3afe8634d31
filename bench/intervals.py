"""Interval arithmetic shared by the trace reduction and the span readers."""
from __future__ import annotations

from typing import Iterable, List, Tuple

Interval = Tuple[float, float]


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, non-overlapping union of half-open intervals."""
    out: List[List[float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def covered(intervals: Iterable[Interval], lo: float, hi: float) -> float:
    """Length of [lo, hi) that the union of ``intervals`` covers."""
    return sum(b - a for a, b in merge(clip(intervals, lo, hi)))


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi) that the merged ``busy`` intervals leave."""
    out, t = [], lo
    for a, b in merge(clip(busy, lo, hi)):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out
