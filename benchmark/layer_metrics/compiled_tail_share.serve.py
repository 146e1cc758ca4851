"""Group-by finalizes and Sorts/TopNs that ran as ONE compiled program, over all that ran
(``tail_compiled`` over ``tail_compiled`` + ``tail_eager``, window deltas; PR 39): 100 when
no statement of the window fell back to the eager/host path (a host-resident page, an
unrankable sort key, a sum that needs the host-exact finalize).  None on a program without
the counters, and in a window in which no group-by or sort ran."""

from benchmark.harness import stats


def read(ctx):
    if "tail_compiled" not in ctx.counters:
        return None
    compiled = ctx.counters["tail_compiled"]
    return stats.share(compiled, compiled + ctx.counters.get("tail_eager", 0))
