"""Statements completed with a right answer per second of window."""


def read(ctx):
    right = [r for r in ctx.completed() if r.get("ok") is not False]
    return len(right) / ctx.window_s
