"""The few statistics the benchmark reports, written out so that every PR
computes the same number the same way."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (no interpolation): the smallest sample with
    at least ``q`` percent of the samples at or below it."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("percentile of no samples")
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return float(ordered[rank - 1])


def median(values) -> float:
    return float(statistics.median(values))


def iqr_share(values) -> float:
    """The spread the bounds are set from: distance between the first and
    third quartile (``statistics.quantiles(values, n=4)``) over the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def merge_intervals(intervals):
    """Union of closed intervals as a sorted list of disjoint ones."""
    out = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1][1] = end
        else:
            out.append([start, end])
    return out


def total(intervals) -> float:
    return float(sum(end - start for start, end in intervals))


def subtract(intervals, cover):
    """The part of merged ``intervals`` not covered by merged ``cover``."""
    out = []
    j = 0
    for start, end in intervals:
        cur = start
        while j < len(cover) and cover[j][1] <= cur:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < end:
            if cover[k][0] > cur:
                out.append([cur, cover[k][0]])
            cur = max(cur, cover[k][1])
            k += 1
        if cur < end:
            out.append([cur, end])
    return out


def clip(intervals, lo: float, hi: float):
    return [[max(s, lo), min(e, hi)] for s, e in intervals if e > lo and s < hi]
