"""The benchmark's own arithmetic on samples: percentiles with the
sample-count rule, and the spread a bound is set from."""

import math
import statistics


class TooFewSamples(ValueError):
    """A percentile was asked of a sample that cannot carry it."""


def percentile(values, q):
    """The ``q``-th percentile (0 < q < 100) by linear interpolation between
    order statistics (numpy's default method), of at least one value."""
    ordered = sorted(values)
    if not ordered:
        raise TooFewSamples("percentile of no samples")
    rank = (len(ordered) - 1) * q / 100.0
    lo = math.floor(rank)
    hi = min(lo + 1, len(ordered) - 1)
    if rank == lo or ordered[lo] == ordered[hi]:
        return ordered[lo]
    if math.isinf(ordered[hi]):
        return ordered[hi]
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (rank - lo)


def tail(values, q=95.0, beyond=10):
    """The ``q``-th percentile, refused unless at least ``beyond`` samples
    lie beyond it: a p95 needs 200 samples, a p99 a thousand."""
    need = math.ceil(round(beyond / (1.0 - q / 100.0), 6))
    if len(values) < need:
        raise TooFewSamples(
            "p%g needs %d samples (%d beyond it), got %d"
            % (q, need, beyond, len(values)))
    return percentile(values, q)


def spread(values):
    """Distance between the first and the third quartile as a share of the
    median, quartiles as ``statistics.quantiles(values, n=4)`` gives them:
    what a bound is set from (about five times the widest over the cells)."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
