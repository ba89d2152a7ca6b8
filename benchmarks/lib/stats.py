"""Percentiles and spreads over raw samples (never histogram buckets)."""

import statistics


def percentile(samples, q):
    """The q-th percentile (0..100) by linear interpolation between the
    order statistics, numpy's default rule; None for no samples."""
    xs = sorted(samples)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    rank = (len(xs) - 1) * q / 100.0
    lo = int(rank)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (rank - lo))


def median(samples):
    return percentile(samples, 50)


def iqr_share(samples):
    """Distance between the first and third quartile as a share of the
    median, with ``statistics.quantiles(values, n=4)`` — the spread the
    bounds in BENCHMARK.json are set from."""
    q1, _q2, q3 = statistics.quantiles(samples, n=4)
    return (q3 - q1) / statistics.median(samples)
