"""The arithmetic of the end-to-end metrics. Pure functions, tested in
benchmark/tests/test_stats.py."""

from __future__ import annotations

import math


def percentile(values, p: float) -> float:
    """p-th percentile (0..100) with linear interpolation between the two
    nearest ranks, as numpy's default. Raises on an empty sample: a metric
    with nothing behind it is not reported as 0."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    k = (len(xs) - 1) * p / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def samples_beyond(n: int, p: float) -> int:
    """How many of n samples lie beyond the p-th percentile. The guide wants
    ten or more behind a reported tail; the harness prints this count beside
    every percentile and never shortens the work to raise it."""
    return int(math.floor(n * (100.0 - p) / 100.0 + 1e-9))


def whole_window_rate(boundaries, t_start: float, seconds: float):
    """Blocks per second over the whole windows that end inside the run.

    `boundaries` is [(t, blocks_applied_so_far)] at each apply of a window's
    last block; t_start is the boundary that opened the measured window (its
    own entry is in the list). Only windows whose closing boundary lies in
    (t_start, t_start + seconds] count: never a partial window. Returns
    (rate, windows, blocks, span_s); rate is None when no whole window fits.
    """
    base = None
    inside = []
    for t, blocks in boundaries:
        if abs(t - t_start) < 1e-12:
            base = blocks
        elif t_start < t <= t_start + seconds:
            inside.append((t, blocks))
    if base is None:
        raise ValueError("t_start is not one of the boundaries")
    if not inside:
        return None, 0, 0, 0.0
    t_last, b_last = inside[-1]
    span = t_last - t_start
    return (b_last - base) / span, len(inside), b_last - base, span

