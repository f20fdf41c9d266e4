"""Percentile arithmetic, with failed requests as misses (+inf)."""

import math

import pytest

from chipbench import stats


def test_matches_linear_interpolation():
    xs = [1.0, 2.0, 3.0, 4.0, 10.0]
    assert stats.percentile(xs, 50) == 3.0
    assert stats.percentile(xs, 0) == 1.0
    assert stats.percentile(xs, 100) == 10.0
    assert stats.percentile(xs, 90) == pytest.approx(4.0 + 0.6 * 6.0)


def test_empty_and_single():
    assert stats.percentile([], 50) is None
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.mean([]) is None


@pytest.mark.parametrize("q,expect_inf", [(50, False), (80, False),
                                          (90, True), (95, True)])
def test_misses_enter_the_tail(q, expect_inf):
    # 10 requests, one failed: the tail reads inf as soon as it touches it.
    xs = [float(i) for i in range(1, 10)] + [stats.MISS]
    got = stats.percentile(xs, q)
    assert math.isinf(got) == expect_inf


def test_all_missed():
    assert math.isinf(stats.percentile([stats.MISS, stats.MISS], 50))
