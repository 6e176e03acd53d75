"""Metric arithmetic: percentiles and spreads, on plain lists."""

from __future__ import annotations

import math
import statistics


def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation between the
    closest ranks; None for no values."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    if len(xs) == 1:
        return xs[0]
    k = (len(xs) - 1) * q / 100.0
    lo = math.floor(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def spread(values):
    """Distance between the first and third quartile as a share of the
    median (statistics.quantiles, n=4): the contract's spread."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med


def union_seconds(intervals):
    """Total length of the union of (start, end) intervals."""
    busy, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            busy += e - s
            end = e
        elif e > end:
            busy += e - end
            end = e
    return busy
