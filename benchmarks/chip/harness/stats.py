"""Percentile arithmetic and the spread rule, kept with the benchmark."""
from __future__ import annotations

import statistics
from typing import Sequence


def percentile(xs: Sequence[float], q: float) -> float:
    """Linear-interpolation percentile (q in [0, 1]) of a non-empty sample."""
    if not xs:
        raise ValueError("percentile of an empty sample")
    s = sorted(xs)
    k = (len(s) - 1) * q
    lo = int(k)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (k - lo)


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile over the median, as
    ``statistics.quantiles(values, n=4)`` gives them."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
