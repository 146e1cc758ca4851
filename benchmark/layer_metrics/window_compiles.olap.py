"""Compile REQUESTS inside the window (``counters_total.compiles`` delta; not cache
misses).  Expected 0: anything else means a parameter crossed a capacity bucket."""


def read(ctx):
    return ctx.counters.get("compiles")
