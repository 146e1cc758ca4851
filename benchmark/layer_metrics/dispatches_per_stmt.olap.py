"""Device dispatches per completed statement, window delta."""


def read(ctx):
    done = len(ctx.completed())
    return ctx.counters.get("device_dispatches", 0) / done if done else None
