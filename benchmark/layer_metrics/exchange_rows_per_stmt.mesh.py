"""Rows a statement's all-to-all exchanges delivered (``exchange_rows`` window delta:
the receive cursors and occupancy counts the exchange already pulls, summed over the
statement's exchanges) over statements completed.  None on a program without the
counter."""


def read(ctx):
    done = len(ctx.completed())
    if "exchange_rows" not in ctx.counters or not done:
        return None
    return ctx.counters["exchange_rows"] / done
