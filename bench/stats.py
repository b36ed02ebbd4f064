"""Order statistics the benchmark reports, kept with it so that every run
computes them the same way."""
from __future__ import annotations

import math
import statistics
from typing import Iterable, Optional, Sequence


def percentile(values: Sequence[float], p: float) -> float:
    """Nearest-rank percentile (the ceil(p/100 * n)-th smallest value).

    A job that failed, was refused or never came is passed as `math.inf`:
    it sits above every completed job, so the tail shows it."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    k = max(1, math.ceil(p / 100.0 * len(xs)))
    return xs[k - 1]


def spread(values: Iterable[float]) -> Optional[float]:
    """Interquartile range over the median (`statistics.quantiles`, n=4)."""
    xs = list(values)
    if len(xs) < 2:
        return None
    q1, q2, q3 = statistics.quantiles(xs, n=4)
    return (q3 - q1) / q2 if q2 else None
