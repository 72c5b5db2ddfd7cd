"""Statistics of a run: quantile estimators and the spread the bounds are set from."""

from __future__ import annotations

import math
import statistics
from typing import Sequence


def _log_beta(a: float, b: float) -> float:
    return math.lgamma(a) + math.lgamma(b) - math.lgamma(a + b)


def _betainc(a: float, b: float, x: float) -> float:
    """Regularized incomplete beta function I_x(a, b) by the continued
    fraction of Numerical Recipes (betacf), good to ~1e-12 here."""
    if x <= 0.0:
        return 0.0
    if x >= 1.0:
        return 1.0
    if x > (a + 1.0) / (a + b + 2.0):
        return 1.0 - _betainc(b, a, 1.0 - x)
    front = math.exp(a * math.log(x) + b * math.log1p(-x) - _log_beta(a, b)) / a
    tiny = 1e-300
    c, d = 1.0, 1.0 - (a + b) * x / (a + 1.0)
    d = 1.0 / (d if abs(d) > tiny else tiny)
    h = d
    for m in range(1, 400):
        m2 = 2 * m
        for num in (
            m * (b - m) * x / ((a + m2 - 1.0) * (a + m2)),
            -(a + m) * (a + b + m) * x / ((a + m2) * (a + m2 + 1.0)),
        ):
            d = 1.0 + num * d
            d = 1.0 / (d if abs(d) > tiny else tiny)
            c = 1.0 + num / c
            c = c if abs(c) > tiny else tiny
            h *= d * c
        if abs(d * c - 1.0) < 1e-14:
            break
    return front * h


def harrell_davis(values: Sequence[float], q: float) -> float:
    """Harrell-Davis estimate of the ``q`` quantile: the mean of all order
    statistics weighted by the Beta((n+1)q, (n+1)(1-q)) mass of their
    rank interval. Still the q quantile, but one sample changing sides
    moves it by a fraction of a gap and not by a whole one."""
    xs = sorted(float(v) for v in values)
    n = len(xs)
    if n == 0:
        raise ValueError("no samples")
    if n == 1:
        return xs[0]
    a, b = (n + 1) * q, (n + 1) * (1.0 - q)
    prev, total = 0.0, 0.0
    for i, x in enumerate(xs, start=1):
        cur = _betainc(a, b, i / n)
        total += (cur - prev) * x
        prev = cur
    return total


def percentile(values: Sequence[float], q: float) -> float:
    """Plain linear-interpolated order statistic (numpy's default)."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("no samples")
    pos = q * (len(xs) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def quartile_spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of ``statistics.quantiles(values, n=4)``:
    the spread the benchmark's bounds are set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
