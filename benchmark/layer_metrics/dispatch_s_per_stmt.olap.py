"""Host seconds a statement spends around enqueueing device programs
(``wall_dispatch_s`` window delta over statements completed); never a device number."""


def read(ctx):
    done = len(ctx.completed())
    if "wall_dispatch_s" not in ctx.counters or not done:
        return None
    return ctx.counters["wall_dispatch_s"] / done
