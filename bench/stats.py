"""Metric arithmetic over the stamps of one run.  Plain Python, so that
every PR computes the same number in the same way."""
from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence, Tuple


def percentile(values: Iterable[float], p: float) -> Optional[float]:
    """The ``p``-th percentile by linear interpolation between closest
    ranks (numpy's default method).  None for no values."""
    xs = sorted(values)
    if not xs:
        return None
    k = (len(xs) - 1) * p / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def censored_waits(starts: Sequence[float], ends: Sequence[Optional[float]],
                   close: float) -> List[float]:
    """Wait of every request from its start to its end.  A request with
    no end by ``close`` enters with the time it has waited so far, so a
    stall cannot drop out of the tail."""
    return [(e if e is not None and e <= close else close) - s
            for s, e in zip(starts, ends)]


def gaps_within(stamps: Sequence[float], t0: float,
                t1: float) -> List[float]:
    """Gaps between consecutive stamps when both lie in ``[t0, t1]``."""
    return [b - a for a, b in zip(stamps, stamps[1:])
            if t0 <= a and b <= t1]


def count_within(stamps: Iterable[float], t0: float, t1: float) -> int:
    return sum(1 for t in stamps if t0 <= t <= t1)


def union_length(intervals: Iterable[Tuple[float, float]]) -> float:
    """Total length covered by possibly overlapping intervals."""
    total, end = 0.0, -math.inf
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total
