"""Percentile arithmetic of the benchmark.  A request that failed, was
refused or never finished enters a latency sample as +inf: it misses
every limit, and a tail that reaches into the misses reads inf."""

from __future__ import annotations

import math
from typing import Iterable, List, Optional

MISS = math.inf


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics (numpy's default).  None for an empty sample; inf as soon
    as the interpolation touches a miss."""
    xs: List[float] = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    if math.isinf(xs[hi]) and pos > lo:
        return MISS
    if math.isinf(xs[lo]):
        return MISS
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def mean(values: Iterable[float]) -> Optional[float]:
    xs = list(values)
    return sum(xs) / len(xs) if xs else None
