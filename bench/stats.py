"""Percentile and rate arithmetic for the end-to-end metrics."""
from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], p: float) -> float:
    """Nearest-rank p-th percentile: the smallest sample with at least
    p% of the samples at or below it. A failed request enters as
    `math.inf`, so it counts as missing any limit."""
    if not values:
        raise ValueError("percentile of no samples")
    if not 0 < p <= 100:
        raise ValueError(f"percentile {p} outside (0, 100]")
    s = sorted(values)
    return s[max(math.ceil(p / 100 * len(s)), 1) - 1]


def rate(count: int, seconds: float) -> float:
    """Events per second over a window."""
    if seconds <= 0:
        raise ValueError("rate over an empty window")
    return count / seconds


def spread(values: Sequence[float]) -> float:
    """Interquartile distance over the median (Python's default
    `statistics.quantiles`), the run-to-run spread a bound is set from."""
    import statistics
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
