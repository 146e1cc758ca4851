"""Lanes a statement's window kernels were handed (``window_lanes``, window delta over
statements completed): the static capacity of each page ``LocalExecutor._run_window``
ran a kernel over, live rows or not (a hash group-by's page may be its table's slots).
Every lane is sorted, scanned and scattered back; lower is better.  None on a program
without the counter."""


def read(ctx):
    done = len(ctx.completed())
    if "window_lanes" not in ctx.counters or not done:
        return None
    return ctx.counters["window_lanes"] / done
