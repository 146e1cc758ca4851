"""Milliseconds a statement waits on a template batcher lane, the driver's gather
window included (``batch_wait_s`` window delta over statements completed)."""


def read(ctx):
    done = len(ctx.completed())
    if "batch_wait_s" not in ctx.counters or not done:
        return None
    return ctx.counters["batch_wait_s"] / done * 1e3
