"""Percentiles and spreads used by the end-to-end metrics and the bounds."""
from __future__ import annotations

import math
import statistics
from typing import Iterable, List


def percentile(values: Iterable[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics (numpy's default 'linear' method). ``inf`` entries
    (failed requests) sort last, so a failure counts as a miss of any
    latency limit. Empty input is an error: a tail of nothing is not 0."""
    xs: List[float] = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    if xs[hi] == math.inf:
        return math.inf if pos > lo or xs[lo] == math.inf else xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def spread(values: Iterable[float]) -> float:
    """Distance between the first and third quartile, as Python's
    ``statistics.quantiles(values, n=4)`` gives them, over the median."""
    xs = [float(v) for v in values]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    return (q3 - q1) / statistics.median(xs)
