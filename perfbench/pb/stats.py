"""Metric arithmetic: percentiles, spreads, failure accounting."""
from __future__ import annotations

import math
import statistics
from typing import List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100), linear between order statistics."""
    if not values:
        raise ValueError("percentile of no values")
    v = sorted(values)
    if len(v) == 1:
        return float(v[0])
    pos = (len(v) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(v) - 1)
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))


def tail_supported(n: int, q: float, beyond: int = 10) -> bool:
    """Whether n samples leave at least ``beyond`` of them past the q-th
    percentile — below that a tail is a maximum under another name."""
    return n * (100.0 - q) / 100.0 >= beyond


def latency_with_missing(
    latencies: Sequence[Optional[float]], missing_value: float
) -> List[float]:
    """A request that failed or never finished misses every limit: it
    enters the tail at ``missing_value`` (the longest it could have
    waited), never dropped from the sample."""
    return [missing_value if x is None else float(x) for x in latencies]


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, by ``statistics.quantiles(values, n=4)``."""
    q = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q[2] - q[0]) / abs(med) if med else float("inf")


def union_seconds(intervals: Sequence[Sequence[float]]) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total = 0.0
    end = -math.inf
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total
