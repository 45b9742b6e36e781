"""Percentiles, whole-window rates and spreads: the benchmark's arithmetic."""

import math
import statistics


def percentile(values, q):
    """The q-th percentile (0-100) by nearest rank: the smallest value with
    at least q% of the samples at or below it. A value of `math.inf` (a
    request that never finished) sorts last, so a stalled run moves the tail."""
    if not values:
        return None
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def window_rate(total, seconds):
    """All the work of the window over all of its seconds."""
    if seconds <= 0:
        raise ValueError("a window has a positive length")
    return total / seconds


def spread(values):
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of `statistics.quantiles(values, n=4)`: the
    measure the bounds in BENCHMARK.json are set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
