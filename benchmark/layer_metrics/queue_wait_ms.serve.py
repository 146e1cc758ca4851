"""Milliseconds a statement spends between the server accepting its POST and the
engine's root span opening (``queued_s`` window delta over statements completed):
the dispatch pool's queue, the statement lock, session set-up, admission."""


def read(ctx):
    done = len(ctx.completed())
    if "queued_s" not in ctx.counters or not done:
        return None
    return ctx.counters["queued_s"] / done * 1e3
