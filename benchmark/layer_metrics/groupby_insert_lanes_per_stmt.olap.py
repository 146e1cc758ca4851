"""Static lanes a statement sends through the group-by's hash insert loop
(``groupby_insert_lanes``: every mode that calls ``groupby_insert``, a regrow's rehash
included; window delta over statements completed).  None on a program without the
counter."""


def read(ctx):
    done = len(ctx.completed())
    if "groupby_insert_lanes" not in ctx.counters or not done:
        return None
    return ctx.counters["groupby_insert_lanes"] / done
