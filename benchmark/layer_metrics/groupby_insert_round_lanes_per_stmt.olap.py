"""Lanes a statement's hash group-by inserts PROBED for (``groupby_insert_round_lanes``,
window delta over statements completed): the rounds of ``ops/hashagg``'s open-addressing
insert loop times the width each ran at, over the insert steps of a hash-mode group-by
(the compacted and the masked insert, a regrow's rehash).  Beside
``groupby_insert_lanes_per_stmt.olap`` it says how many rounds an inserted lane cost:
every round gathers twice, scatters and sets at that width.  None on a program without
the counter."""


def read(ctx):
    done = len(ctx.completed())
    if "groupby_insert_round_lanes" not in ctx.counters or not done:
        return None
    return ctx.counters["groupby_insert_round_lanes"] / done
