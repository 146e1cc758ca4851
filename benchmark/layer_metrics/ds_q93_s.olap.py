"""Median seconds of ds_q93 in the window: the classes of a round weigh differently in the
round's seconds, so each is kept beside the geomean that weighs them equally."""

from benchmark.harness import stats


def read(ctx):
    seconds = [r["seconds"] for r in ctx.completed("ds_q93")]
    return stats.median(seconds) if seconds else None
