"""Launches of the connectors' page generators a statement (``generator_dispatches``,
window delta over statements completed): one a split of every scan the page cache did not
serve.  They are device programs that ``dispatches_per_stmt.olap`` does not count (the
connector owns them).  None on a program without the counter (before PR 38)."""


def read(ctx):
    done = len(ctx.completed())
    if "generator_dispatches" not in ctx.counters or not done:
        return None
    return ctx.counters["generator_dispatches"] / done
