"""Lanes a statement's hashed join probes GATHERED for (``join_hash_probe_round_lanes``,
window delta over statements completed): the rounds of the open-addressing lookup times the
width each ran at (the whole batch, then what was still unfinished, packed), over the split
joins' match steps.  Beside ``hash_probe_lanes_per_stmt.olap`` it says how many times a
probed lane was gathered for.  None on a program without the counter."""


def read(ctx):
    done = len(ctx.completed())
    if "join_hash_probe_round_lanes" not in ctx.counters or not done:
        return None
    return ctx.counters["join_hash_probe_round_lanes"] / done
