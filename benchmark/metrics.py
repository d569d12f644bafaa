"""End-to-end arithmetic: from the log of changes due and frames delivered
to the numbers a user of the stream would see. No clock is read here."""

from __future__ import annotations

import bisect
import math
import statistics
from typing import List, Optional, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile of a non-empty sample."""
    v = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(v)))
    return v[rank - 1]


def shown_times(changes: Sequence[Tuple[int, float]],
                frames: Sequence[Tuple[int, float]]) -> List[Optional[float]]:
    """For each change (content index, due time): when the client had the
    last stripe of the first frame whose content is at or past it.

    ``frames`` are (content index, completion time) of delivered frames.
    A frame completes at its own time whatever arrived before it, so the
    answer for a change is the earliest completion among frames at or past
    it; a change coalesced into a later frame waits for that frame."""
    order = sorted(frames, key=lambda f: f[1])
    reach: List[int] = []          # running maximum of content, by time
    times: List[float] = []
    top = -1
    for content, t in order:
        if content > top:
            top = content
            reach.append(top)
            times.append(t)
    out: List[Optional[float]] = []
    for index, _due in changes:
        i = bisect.bisect_left(reach, index)
        out.append(times[i] if i < len(times) else None)
    return out


def latencies_ms(changes: Sequence[Tuple[int, float]],
                 frames: Sequence[Tuple[int, float]],
                 window_s: float) -> Tuple[List[float], int]:
    """(latency of every change in ms, how many were never shown). A change
    never shown counts at the window's length."""
    lat: List[float] = []
    never = 0
    for (index, due), shown in zip(changes, shown_times(changes, frames)):
        if shown is None:
            never += 1
            lat.append(window_s * 1000.0)
        else:
            lat.append(max(0.0, shown - due) * 1000.0)
    return lat, never


def delivered(frames: Sequence[Tuple[float, int]], t0: float,
              t1: float) -> Tuple[int, int]:
    """(complete frames, video payload bytes) the client received in
    [t0, t1). ``frames`` are (completion time, payload bytes)."""
    inside = [(t, b) for t, b in frames if t0 <= t < t1]
    return len(inside), sum(b for _t, b in inside)


def spread(values: Sequence[float]) -> float:
    """Distance between the quartiles as a share of the median: the
    contract's measure of how widely runs spread."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else float("inf")
