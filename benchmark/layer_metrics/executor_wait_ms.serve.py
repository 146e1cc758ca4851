"""Milliseconds a statement waits for an executor of the engine's pool
(``executor_wait_s`` window delta over statements completed)."""


def read(ctx):
    done = len(ctx.completed())
    if "executor_wait_s" not in ctx.counters or not done:
        return None
    return ctx.counters["executor_wait_s"] / done * 1e3
