"""The comparison that decides ``correct``: an answer as the client received it against
the reference's frame, positionally and in ORDER BY order.

Each column falls into one of three kinds by the REFERENCE's dtype and the statement's
``AVG_DECIMALS``: exact (strings, integers, dates: any differing cell is a mismatch),
avg (a decimal average, right to half a unit of its last place) and float (exact
decimal sums against float64 arithmetic: relative error).  The numbers come back so
that every run can print them beside their limits.
"""

import numpy as np
import pandas as pd

# the limits, set from readings on the chip (PERF.md section 2 has them)
LIMITS = {"exact_mismatches": 0, "max_rel_err": 1e-9, "avg_err_units": 0.5 + 1e-6}


def _days(values):
    return (pd.to_datetime(list(values), format="%Y-%m-%d").to_numpy()
            .astype("datetime64[D]").astype(np.int64))


def _exact(got, want):
    """Both columns in one exactly comparable form, or None where they have none."""
    want = np.asarray(want)
    if want.dtype.kind == "M":
        return _days(got), want.astype("datetime64[D]").astype(np.int64)
    if want.dtype.kind in "iu":
        try:
            return np.asarray(list(got), dtype=np.int64), want.astype(np.int64)
        except (TypeError, ValueError):
            return None
    return np.asarray([str(v) for v in got], dtype=object), \
        np.asarray([str(v) for v in want], dtype=object)


def compare(got, want, avg_decimals=None):
    """``got``: pandas frame of the client's rows; ``want``: the reference's frame, its
    columns in the statement's SELECT order.  Returns the numbers compared."""
    avg_decimals = avg_decimals or {}
    out = {"exact_mismatches": 0, "max_rel_err": 0.0, "avg_err_units": 0.0}
    if got.shape != want.shape:
        out["exact_mismatches"] = max(int(abs(got.size - want.size)), 1)
        out["shape"] = [list(got.shape), list(want.shape)]
        return out
    for j, name in enumerate(want.columns):
        g, w = got.iloc[:, j], want.iloc[:, j]
        if name in avg_decimals or w.dtype.kind == "f":
            try:
                gf = np.asarray(g, dtype=np.float64)
            except (TypeError, ValueError):
                out["exact_mismatches"] += len(w)
                continue
            wf = np.asarray(w, dtype=np.float64)
            bad = ~np.isfinite(gf)
            if bad.any():
                out["exact_mismatches"] += int(bad.sum())
                gf = np.where(bad, wf, gf)
            if not len(wf):
                continue
            if name in avg_decimals:
                units = np.abs(gf - wf) * 10.0 ** avg_decimals[name]
                out["avg_err_units"] = max(out["avg_err_units"], float(units.max()))
            else:
                rel = np.abs(gf - wf) / np.maximum(np.abs(wf), 1.0)
                out["max_rel_err"] = max(out["max_rel_err"], float(rel.max()))
        else:
            pair = _exact(g, w)
            if pair is None:
                out["exact_mismatches"] += len(w)
            else:
                out["exact_mismatches"] += int((pair[0] != pair[1]).sum())
    return out


def within_limits(numbers):
    return all(numbers[k] <= LIMITS[k] for k in LIMITS)


def worst(records):
    """The largest of each number over many comparisons."""
    out = {k: 0 for k in LIMITS}
    for r in records:
        for k in LIMITS:
            out[k] = max(out[k], r[k])
    return out
