"""Static lanes a statement sends through the probe loop of hashed join tables
(``join_hash_probe_lanes``, window delta over statements completed): what the loop's
cost a lane multiplies.  None on a program without the counter."""


def read(ctx):
    done = len(ctx.completed())
    if "join_hash_probe_lanes" not in ctx.counters or not done:
        return None
    return ctx.counters["join_hash_probe_lanes"] / done
