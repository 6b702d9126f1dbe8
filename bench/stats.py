"""The benchmark's statistics, kept apart so every cell computes them the
same way."""
import statistics
from typing import Optional, Sequence


def percentile(xs: Sequence[float], p: float) -> Optional[float]:
    """The p-th percentile, linear between the closest ranks (numpy's
    default); None for an empty sample."""
    xs = sorted(xs)
    if not xs:
        return None
    k = (len(xs) - 1) * p / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def spread(xs: Sequence[float]) -> float:
    """Distance between the first and third quartile over the median, as
    ``statistics.quantiles(values, n=4)`` gives them."""
    q1, q2, q3 = statistics.quantiles(xs, n=4)
    return (q3 - q1) / q2
