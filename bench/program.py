"""What the per-layer readers take from the program's own instruments:
histograms of its metrics registry (window deltas) and its annotations on
the profile (``repro.<name>``, on the device trace's clock).

A program without an instrument reads as None here, so the readers
report nothing for it and raise nothing.
"""
from __future__ import annotations

from typing import Dict, List, Optional

from bench import intervals as iv
from bench.xtrace import DeviceTrace, Event

PREFIX = "repro."
# a collection pauses whatever the thread was doing: it names the time it
# takes, and never makes the annotation around it a container
GC = PREFIX + "gc"


def hist(run, name: str, **labels) -> Optional[Dict]:
    """The window's delta of one histogram, or None where the program has
    none or it saw nothing."""
    h = run.registry["histograms"].get(
        (name, tuple(sorted((k, str(v)) for k, v in labels.items()))))
    if not h or h["count"] <= 0:
        return None
    return h


def mean_ms(run, name: str, **labels) -> Optional[float]:
    """Mean of one histogram's observations in the window, in ms."""
    h = hist(run, name, **labels)
    return None if h is None else 1e3 * h["sum"] / h["count"]


def seconds(run, name: str) -> Optional[float]:
    """Summed observations of every histogram named ``name`` (any labels)
    in the window; None where the program has none."""
    rows = [h for (n, _), h in run.registry["histograms"].items()
            if n == name]
    return sum(h["sum"] for h in rows) if rows else None


def window_share(run, name: str) -> Optional[float]:
    """``name``'s summed seconds over the window, in %."""
    s = seconds(run, name)
    if s is None or run.window.seconds <= 0:
        return None
    return 100.0 * s / run.window.seconds


def annotations(tr: DeviceTrace) -> List[Event]:
    """The program's annotations that overlap the traced window."""
    lo, hi = tr.window
    return [e for e in tr.host
            if e[0].startswith(PREFIX) and e[2] > lo and e[1] < hi]


def _sweep(events: List[Event], gaps: List[iv.Interval]):
    """Walk the window in time order; yields ``(a, b, idle, active)`` for
    each stretch in which the same annotations are open (``active``: their
    indices) and the device is idle or busy throughout, and marks
    (through the returned set) the names that hold another annotation."""
    marks = []
    for i, (_, a, b) in enumerate(events):
        # at one instant: ends before starts, outer starts before inner
        marks.append((a, 1, -(b - a), i))
        marks.append((b, 0, 0.0, i))
    for a, b in gaps:
        marks.append((a, 1, -float("inf"), -1))
        marks.append((b, 0, 0.0, -1))
    marks.sort()
    active: Dict[int, float] = {}
    holders = set()
    idle, t = 0, None
    out = []
    for x, starts, _, i in marks:
        if t is not None and x > t:
            out.append((t, x, idle > 0, dict(active)))
        t = x
        if i < 0:
            idle += 1 if starts else -1
        elif starts:
            name, a, b = events[i]
            if name != GC:
                holders.update(events[j][0] for j, end in active.items()
                               if end >= b)
            active[i] = b
        else:
            active.pop(i, None)
    return out, holders


def idle_by_name(tr: DeviceTrace, dev: int = 0
                 ) -> Optional[Dict[Optional[str], float]]:
    """Seconds of the window in which device ``dev`` was idle, by the
    innermost program annotation open then: its name where it is a leaf
    (a name no annotation of which holds another, ``repro.gc`` aside),
    else None, as for time under a container alone or under no program
    annotation.  None where the profile holds no program annotation.

    Annotations of every thread share one timeline here, as
    ``DeviceTrace.host`` keeps them; the reading is meant for a program
    whose annotated path runs on one thread, as the search loop does."""
    events = annotations(tr)
    if not events or not tr.devices:
        return None
    gaps = iv.gaps(tr.busy_intervals(dev), *tr.window)
    stretches, holders = _sweep(events, gaps)
    out: Dict[Optional[str], float] = {}
    lo, hi = tr.window
    for a, b, idle, active in stretches:
        a, b = max(a, lo), min(b, hi)
        if not idle or b <= a:
            continue
        name = None
        if active:
            i = min(active, key=lambda j: events[j][2] - events[j][1])
            if events[i][0] not in holders:
                name = events[i][0]
        out[name] = out.get(name, 0.0) + (b - a)
    return out
