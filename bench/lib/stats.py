"""Percentiles and interval arithmetic used by the metrics."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """q-th percentile (0..100) by linear interpolation between order
    statistics (numpy's default "linear" method). Infinite values sort
    last, so failed requests count as the slowest."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no values")
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    if v[hi] == math.inf:
        return math.inf if pos > lo or v[lo] == math.inf else v[lo]
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def union_intervals(intervals, lo: float, hi: float) -> list:
    """Merge [start, end) intervals clipped to [lo, hi)."""
    clipped = sorted((max(s, lo), min(e, hi)) for s, e in intervals
                     if e > lo and s < hi)
    out: list = []
    for s, e in clipped:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_seconds(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union_intervals(intervals, lo, hi))


def idle_gaps(intervals, lo: float, hi: float) -> list:
    """[start, end) gaps in [lo, hi) that no interval covers."""
    gaps, t = [], lo
    for s, e in union_intervals(intervals, lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return gaps
