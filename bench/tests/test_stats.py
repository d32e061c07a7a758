"""Percentile and idle-union arithmetic."""

import math

import numpy as np
import pytest

from bench.lib import stats


@pytest.mark.parametrize("q", [0, 10, 50, 90, 95, 99, 100])
def test_percentile_matches_numpy_linear(q):
    v = list(np.random.default_rng(3).lognormal(0, 1, 137))
    assert stats.percentile(v, q) == pytest.approx(np.percentile(v, q))


def test_percentile_counts_failures_as_slowest():
    v = [1.0] * 9 + [math.inf]
    assert stats.percentile(v, 50) == 1.0
    assert stats.percentile(v, 95) == math.inf


def test_union_busy_and_gaps():
    iv = [(0.5, 1.0), (0.8, 1.5), (2.0, 2.5), (-1.0, 0.1), (3.5, 9.0)]
    assert stats.union_intervals(iv, 0.0, 4.0) == \
        [[0.0, 0.1], [0.5, 1.5], [2.0, 2.5], [3.5, 4.0]]
    assert stats.busy_seconds(iv, 0.0, 4.0) == pytest.approx(2.1)
    gaps = stats.idle_gaps(iv, 0.0, 4.0)
    assert gaps == [(0.1, 0.5), (1.5, 2.0), (2.5, 3.5)]
    assert sum(b - a for a, b in gaps) + 2.1 == pytest.approx(4.0)
