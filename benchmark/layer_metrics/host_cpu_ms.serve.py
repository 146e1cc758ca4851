"""CPU milliseconds of a statement's own thread under its root span (``host_cpu_s``,
window delta over statements completed, x 1e3): the serving cells' reading of
``host_cpu_s_per_stmt.olap``.  None on a program without the counter (before PR 38)."""


def read(ctx):
    done = len(ctx.completed())
    if "host_cpu_s" not in ctx.counters or not done:
        return None
    return ctx.counters["host_cpu_s"] / done * 1e3
