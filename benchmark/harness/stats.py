"""Order statistics as the benchmark reports them.

A timing is summarised by its mean and percentiles, with the sample
count and the highest percentile that has at least ten samples beyond it
(choosing-metrics guide, section 1) stated beside them.
"""

from __future__ import annotations

import math
import statistics

CANDIDATE_PERCENTILES = (50.0, 75.0, 90.0, 95.0, 99.0, 99.9)
SAMPLES_BEYOND = 10


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    the two nearest order statistics.  Raises on an empty sample."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile must be in [0, 100], got {q}")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return percentile(values, 50.0)


def samples_beyond(n: int, q: float) -> int:
    """How many of ``n`` samples lie beyond the ``q``-th percentile."""
    return int(math.floor(n * (1.0 - q / 100.0) + 1e-9))


def supported_percentile(n: int, beyond: int = SAMPLES_BEYOND,
                         candidates=CANDIDATE_PERCENTILES):
    """The highest candidate percentile with at least ``beyond`` of the
    ``n`` samples beyond it, or None when not even the lowest has."""
    best = None
    for q in sorted(candidates):
        if samples_beyond(n, q) >= beyond:
            best = q
    return best


REPORTED_PERCENTILES = (50.0, 75.0, 90.0, 95.0, 99.0)


def summarize(values) -> dict:
    """Count, mean and the reported percentiles of a sample (``p50`` ..
    ``p99``), and ``supported``: the highest percentile the sample
    supports by the ten-beyond rule.  A metric names the statistic it
    reports; one above ``supported`` rests on a handful of samples."""
    xs = [float(v) for v in values]
    out = {"n": len(xs), "supported": supported_percentile(len(xs))}
    for q in REPORTED_PERCENTILES:
        out[f"p{q:g}"] = percentile(xs, q) if xs else None
    out["mean"] = sum(xs) / len(xs) if xs else None
    return out


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, with ``statistics.quantiles(values, n=4)`` as the driver
    takes it."""
    xs = [float(v) for v in values]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    return (q3 - q1) / statistics.median(xs)
