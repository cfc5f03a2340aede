"""Order statistics as the benchmark reports them, by hand."""

import pytest

from benchmark.harness import stats


def test_percentile_interpolates_between_order_statistics():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(xs, 0) == 10.0
    assert stats.percentile(xs, 50) == 30.0
    assert stats.percentile(xs, 100) == 50.0
    assert stats.percentile(xs, 95) == pytest.approx(48.0)
    assert stats.median([3.0, 1.0, 2.0, 4.0]) == 2.5
    with pytest.raises(ValueError):
        stats.percentile([], 50)


@pytest.mark.parametrize("n, want", [
    (9, None), (20, 50.0), (40, 75.0), (199, 90.0),
    (200, 95.0), (1000, 99.0), (10000, 99.9)])
def test_highest_percentile_with_ten_samples_beyond(n, want):
    assert stats.supported_percentile(n) == want


def test_summary_states_what_the_sample_supports():
    few = stats.summarize([float(i) for i in range(50)])
    assert few["n"] == 50 and few["supported"] == 75.0
    assert few["p95"] == pytest.approx(46.55) and few["mean"] == 24.5
    many = stats.summarize([float(i) for i in range(400)])
    assert many["supported"] == 95.0
    assert many["p50"] == pytest.approx(199.5)
    empty = stats.summarize([])
    assert empty["n"] == 0 and empty["p95"] is None


def test_spread_is_quartile_distance_over_median():
    # statistics.quantiles(n=4) of 1..7 (exclusive method): q1=2, q3=6.
    assert stats.spread([1, 2, 3, 4, 5, 6, 7]) == pytest.approx(4 / 4)
