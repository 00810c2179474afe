"""Arithmetic on samples. Every per-run number is taken over ALL the
readings of the window, never over a sub-sample."""

import math
from typing import Iterable, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) by linear interpolation between
    the two nearest order statistics (numpy's default rule, written out
    so that the yardstick does not move with a library). Nothing to read
    gives None."""
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside 0..100")
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def rate(amounts: Iterable[float], window_s: float) -> Optional[float]:
    """All the work of the window over all the time of the window."""
    if window_s <= 0.0:
        return None
    return float(sum(amounts)) / float(window_s)


def token_gaps(times: Sequence[float]) -> list:
    """Gaps between one request's consecutive output tokens."""
    return [b - a for a, b in zip(times, times[1:])]


def spread(values: Sequence[float]) -> float:
    """Run-to-run spread as the builder's contract defines it: the
    distance between the first and third quartile (Python's
    ``statistics.quantiles(values, n=4)``) as a share of the median."""
    import statistics
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
