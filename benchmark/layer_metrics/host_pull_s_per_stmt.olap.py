"""Seconds a statement's host thread is blocked pulling the device's answer
(``wall_host_pull_s`` window delta over statements completed): host clock, the wait
for the device included."""


def read(ctx):
    done = len(ctx.completed())
    if "wall_host_pull_s" not in ctx.counters or not done:
        return None
    return ctx.counters["wall_host_pull_s"] / done
