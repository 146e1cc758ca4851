"""The benchmark's arithmetic: kept here so that every PR computes a metric the same way."""

import math


def geomean(values):
    """Geometric mean of positive numbers (TPC-H's power weighting: each counts equally)."""
    vals = [float(v) for v in values]
    if not vals or any(v <= 0 for v in vals):
        raise ValueError(f"geomean needs positive numbers, got {vals}")
    return math.exp(sum(math.log(v) for v in vals) / len(vals))


def median(values):
    vals = sorted(float(v) for v in values)
    if not vals:
        raise ValueError("median of nothing")
    mid = len(vals) // 2
    return vals[mid] if len(vals) % 2 else (vals[mid - 1] + vals[mid]) / 2.0


def percentile(values, q):
    """Nearest-rank percentile: the smallest sample with at least ``q`` of the samples at
    or below it.  ``math.inf`` (a failed statement) sorts last, so a failure counts as
    missing any limit."""
    vals = sorted(float(v) for v in values)
    if not vals:
        raise ValueError("percentile of nothing")
    rank = max(math.ceil(q * len(vals)), 1)
    return vals[rank - 1]


def share(part, whole):
    """``part`` of ``whole`` in per cent, or None where there is nothing to take a share of."""
    return None if not whole else 100.0 * part / whole
