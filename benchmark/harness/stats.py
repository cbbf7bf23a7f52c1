"""Percentile, spread and lateness arithmetic (no JAX, no numpy needed)."""
from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by the nearest-rank rule on the
    sorted sample: the smallest value with at least ``q`` percent of the
    sample at or below it.  ``inf`` entries (requests that missed) sort
    last and are returned as they are, so a tail is the tail of all
    requests."""
    if not values:
        raise ValueError("percentile of an empty sample")
    s = sorted(values)
    k = max(1, math.ceil(q / 100.0 * len(s)))
    return s[k - 1]


def iqr_spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, quartiles as ``statistics.quantiles(values, n=4)`` gives
    them — the spread the bounds are set from."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def union_seconds(intervals) -> float:
    """Total length covered by ``(start, end)`` intervals, overlaps
    counted once."""
    total = 0.0
    end = -math.inf
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def gaps(intervals, lo: float, hi: float):
    """The uncovered ``(start, end)`` stretches of ``[lo, hi]``."""
    out = []
    cur = lo
    for a, b in sorted(intervals):
        if a > cur:
            out.append((cur, min(a, hi)))
        cur = max(cur, b)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]
