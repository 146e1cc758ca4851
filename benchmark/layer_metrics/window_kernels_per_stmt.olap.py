"""Window kernels a statement dispatched (``window_kernels``, window delta over
statements completed): one a Window node that ``LocalExecutor._run_window`` ran, each
over one materialised page.  A replayed q51 runs 3 (each channel's cumulative sum, the
two running maxima over the joined rows in one kernel); the second copy of each CTE is a
build side and stays inside the compiled stream.  0 would be a window that was not
computed again.  None on a program without the counter."""


def read(ctx):
    done = len(ctx.completed())
    if "window_kernels" not in ctx.counters or not done:
        return None
    return ctx.counters["window_kernels"] / done
