import math

import pytest

from benchmark.harness import stats


def test_geomean_weights_each_class_equally():
    assert stats.geomean([2.0, 8.0]) == pytest.approx(4.0)
    assert stats.geomean([2.2, 4.1, 12.7]) == pytest.approx((2.2 * 4.1 * 12.7) ** (1 / 3))
    with pytest.raises(ValueError):
        stats.geomean([1.0, 0.0])
    with pytest.raises(ValueError):
        stats.geomean([])


def test_median_of_even_and_odd():
    assert stats.median([3, 1, 2]) == 2
    assert stats.median([4, 1, 2, 3]) == 2.5


@pytest.mark.parametrize("n,q,want", [(100, 0.95, 95), (20, 0.95, 19), (10, 0.95, 10),
                                      (1, 0.95, 1), (40, 0.5, 20)])
def test_percentile_is_nearest_rank(n, q, want):
    assert stats.percentile(range(1, n + 1), q) == want


def test_a_failed_statement_misses_the_percentile():
    # 19 good and 2 failed: the 95th percentile is a failure
    assert math.isinf(stats.percentile([0.1] * 19 + [math.inf] * 2, 0.95))
    assert stats.percentile([0.1] * 39 + [math.inf], 0.95) == 0.1


def test_share():
    assert stats.share(1, 4) == 25.0
    assert stats.share(0, 0) is None
