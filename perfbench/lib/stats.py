"""The benchmark's own arithmetic on samples: nearest-rank percentile and
the quartile spread the bounds are set from."""

from __future__ import annotations

import math
import statistics


def percentile(values, p: float) -> float:
    """Nearest-rank percentile: the smallest value with at least p percent
    of the sample at or below it. p in (0, 100]."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    rank = max(1, math.ceil(p / 100.0 * len(xs)))
    return xs[min(rank, len(xs)) - 1]


def median(values) -> float:
    return statistics.median(values)


def iqr_share(values) -> float:
    """(Q3 - Q1) / median with Python's exclusive quartiles: the spread a
    bound is set from."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)
