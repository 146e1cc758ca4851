"""Lanes a statement's window kernels SORTED (``window_sort_lanes``, window delta over
statements completed): a kernel's lanes times the stable sort passes of
``ops/window.window_order`` (one a partition or order key, and one for the validity
mask).  Beside ``window_lanes_per_stmt.olap`` it says how many passes a lane cost;
lower is better.  None on a program without the counter."""


def read(ctx):
    done = len(ctx.completed())
    if "window_sort_lanes" not in ctx.counters or not done:
        return None
    return ctx.counters["window_sort_lanes"] / done
