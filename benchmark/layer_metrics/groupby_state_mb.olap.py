"""Megabytes (10^6 B) of group-by state a statement reserves on the device
(``groupby_state_bytes``: the largest reservation of each of the statement's group-bys,
recorded as host ints where the capacity is chosen; window delta over statements
completed).  None on a program without the counter."""


def read(ctx):
    done = len(ctx.completed())
    if "groupby_state_bytes" not in ctx.counters or not done:
        return None
    return ctx.counters["groupby_state_bytes"] / done / 1e6
