"""Percentiles by the benchmark's rule, and window accounting."""
from __future__ import annotations

import math


def percentile(samples, q: float) -> float:
    """Linear-interpolated q-th percentile (q in 0..100) of `samples`."""
    xs = sorted(samples)
    if not xs:
        raise ValueError("percentile of no samples")
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def median(samples) -> float:
    return percentile(samples, 50.0)


def highest_supported_percentile(n: int, ladder=(99.9, 99.0, 95.0, 90.0,
                                                 75.0)) -> float:
    """The highest percentile of the ladder with at least ten samples
    beyond it among n (choosing-metrics section 1); 50 if none has."""
    for q in ladder:
        if n * (1.0 - q / 100.0) >= 10.0:
            return q
    return 50.0


def supported(n: int, q: float, min_beyond: int = 10) -> bool:
    """Whether n samples hold `min_beyond` readings on the thinner side
    of their q-th percentile (a median wants 20 samples, a 95th
    percentile 200)."""
    return n * min(q, 100.0 - q) / 100.0 >= min_beyond


def tail(samples, q: float, min_beyond: int = 10):
    """The q-th percentile, or None where the sample does not support
    it: a name may not change meaning from run to run, so a percentile
    that rests on fewer than `min_beyond` readings beyond it is not
    printed at all. A cell is sized so that its window holds enough."""
    if not supported(len(samples), q, min_beyond):
        return None
    return percentile(samples, q)


def whole_intervals(stamps, t_open: float, seconds: float):
    """Indices k >= 1 of the intervals (stamps[k-1], stamps[k]] that
    start at or after t_open and END inside (t_open, t_open + seconds].
    `stamps` are the ends of consecutive flush intervals, ascending."""
    t_close = t_open + seconds
    return [k for k in range(1, len(stamps))
            if stamps[k - 1] >= t_open - 1e-9 and stamps[k] <= t_close]
