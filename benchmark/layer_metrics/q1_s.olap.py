"""Median seconds of q1 in the window, kept beside the geomean as ``q3_s.olap`` is."""

from benchmark.harness import stats


def read(ctx):
    seconds = [r["seconds"] for r in ctx.completed("q1")]
    return stats.median(seconds) if seconds else None
