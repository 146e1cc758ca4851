"""Megabytes (10^6 B) a statement's operators routed out of their working set
(``spilled_bytes`` window delta over statements completed: the partitioned group-by's
and the spilled joins' chunks, whatever tier they landed in).  0 = every state stayed
in HBM beside the page cache.  None on a program without the counter."""


def read(ctx):
    done = len(ctx.completed())
    if "spilled_bytes" not in ctx.counters or not done:
        return None
    return ctx.counters["spilled_bytes"] / done / 1e6
