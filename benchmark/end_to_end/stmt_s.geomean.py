"""Geometric mean over the cell's statement classes of each class's median seconds in
the window (TPC-H's power weighting: q3 cannot hide behind q9).  A class with no
completed statement makes the metric missing: the run then fails its contract."""

from benchmark.harness import stats


def read(ctx):
    medians = []
    for name in ctx.cell.statements:
        seconds = [r["seconds"] for r in ctx.completed(name)]
        if not seconds:
            return None
        medians.append(stats.median(seconds))
    return stats.geomean(medians)
