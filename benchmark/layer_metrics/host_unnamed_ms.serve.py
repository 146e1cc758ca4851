"""Milliseconds of a statement's root span that no span names (``wall_unattributed_s``,
window delta over statements completed, x 1e3): the serving cells' reading of
``host_unnamed_s_per_stmt.olap``.  Beside ``host_cpu_ms.serve`` it tells a thread that
computes from one that waits (the interpreter lock of concurrent clients).  None on a
program without the counter."""


def read(ctx):
    done = len(ctx.completed())
    if "wall_unattributed_s" not in ctx.counters or not done:
        return None
    return ctx.counters["wall_unattributed_s"] / done * 1e3
