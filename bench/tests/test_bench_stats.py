"""Percentiles (failures count as misses) and spreads."""
import math
import os
import statistics
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lib import stats as S  # noqa: E402


@pytest.mark.parametrize("q", [0, 50, 90, 95, 99, 100])
def test_percentile_matches_numpy_linear(q):
    xs = np.random.default_rng(0).exponential(size=101)
    assert S.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_failed_requests_count_as_misses():
    ok = [0.1] * 95
    assert S.percentile(ok + [math.inf] * 4, 95) == pytest.approx(0.1)
    assert S.percentile(ok + [math.inf] * 5, 95) == math.inf
    assert S.percentile([1.0, math.inf], 50) == math.inf


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        S.percentile([], 95)


def test_spread_is_iqr_over_median():
    xs = [10.0, 11.0, 9.0, 10.5, 9.5, 10.2]
    q1, med, q3 = statistics.quantiles(xs, n=4)
    assert S.spread(xs) == pytest.approx((q3 - q1) / statistics.median(xs))
