"""Median seconds of ds_q51 in the window (host clock, the client's): with one statement
class it is the cell's ``stmt_s.geomean``, kept under the statement's name as the other
cells keep theirs."""

from benchmark.harness import stats


def read(ctx):
    seconds = [r["seconds"] for r in ctx.completed("ds_q51")]
    return stats.median(seconds) if seconds else None
