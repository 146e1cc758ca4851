"""Group-by overflows that cost a re-scan (``groupby_regrows`` window delta over
statements completed): a hash group-by whose table was sized too small starts again
over its whole input at a larger capacity.  0 is the aim: the first capacity held.
None on a program without the counter."""


def read(ctx):
    done = len(ctx.completed())
    if "groupby_regrows" not in ctx.counters or not done:
        return None
    return ctx.counters["groupby_regrows"] / done
