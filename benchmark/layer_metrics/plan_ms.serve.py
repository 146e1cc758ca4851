"""Milliseconds of the ``plan`` bucket of a statement's wall breakdown, from the
counters (``wall_plan_s`` window delta over statements completed), so that it reads
in cells with concurrent clients too."""


def read(ctx):
    done = len(ctx.completed())
    if "wall_plan_s" not in ctx.counters or not done:
        return None
    return ctx.counters["wall_plan_s"] / done * 1e3
