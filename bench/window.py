"""Window arithmetic: every end-to-end metric is taken over all the work
and all the time of the measured window."""
from __future__ import annotations

import math


def p95(values) -> float:
    """95th percentile by nearest rank: the smallest value that at least
    95 % of the values do not exceed."""
    v = sorted(values)
    if not v:
        raise ValueError("p95 of no values")
    return v[max(math.ceil(0.95 * len(v)) - 1, 0)]


def rate(work: float, seconds: float) -> float:
    if seconds <= 0:
        raise ValueError(f"window of {seconds} s")
    return work / seconds
