"""Percentile and sample-count rules, and the window-boundary rate."""

import pytest

from benchmark.harness.stats import (
    percentile,
    samples_beyond,
    whole_window_rate,
)


@pytest.mark.parametrize("values,p,want", [
    ([5.0], 50, 5.0),
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    (list(range(1, 101)), 95, 95.05),  # numpy.percentile(range(1,101), 95)
    ([3, 1, 2], 100, 3.0),
    ([3, 1, 2], 0, 1.0),
])
def test_percentile_interpolates_between_ranks(values, p, want):
    assert percentile(values, p) == pytest.approx(want)


def test_percentile_of_nothing_is_an_error_not_zero():
    with pytest.raises(ValueError):
        percentile([], 50)


@pytest.mark.parametrize("n,p,want", [(200, 95, 10), (199, 95, 9),
                                      (130, 95, 6), (20, 50, 10), (0, 95, 0)])
def test_samples_beyond_a_percentile(n, p, want):
    assert samples_beyond(n, p) == want


def test_rate_counts_only_whole_windows_inside_the_run():
    # boundaries every 20 s from the warm-up's last boundary at t=100
    b = [(60.0, 64), (100.0, 128), (120.0, 192), (140.0, 256), (161.0, 320)]
    rate, windows, blocks, span = whole_window_rate(b, 100.0, 45.0)
    assert (windows, blocks, span) == (2, 128, 40.0)
    assert rate == pytest.approx(128 / 40.0)
    # the window that closes at 161 > 100 + 60 is not counted either
    assert whole_window_rate(b, 100.0, 60.0)[1] == 2
    assert whole_window_rate(b, 100.0, 61.0)[1] == 3


def test_rate_is_none_when_no_whole_window_fits():
    b = [(100.0, 128), (150.0, 192)]
    assert whole_window_rate(b, 100.0, 45.0) == (None, 0, 0, 0.0)


def test_rate_uses_the_blocks_applied_not_a_nominal_window():
    # the last window of a pass may be short (a store of 100 blocks, window 64)
    b = [(10.0, 64), (20.0, 100), (30.0, 164)]
    rate, windows, blocks, _ = whole_window_rate(b, 10.0, 25.0)
    assert (windows, blocks) == (2, 100) and rate == pytest.approx(5.0)


def test_rate_needs_the_opening_boundary():
    with pytest.raises(ValueError):
        whole_window_rate([(1.0, 64)], 0.5, 10.0)

