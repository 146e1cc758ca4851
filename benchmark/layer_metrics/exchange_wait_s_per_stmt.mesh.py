"""Seconds of a statement's root span inside the mesh exchange's spans (``exchange.route``,
``exchange.merge``: the ``exchange_wait`` bucket of its wall breakdown, folded into
``wall_exchange_wait_s``), window delta over statements completed.  Host clock around
the dispatch of the routing and merging programs, not device time.  None on a program
without the counter."""


def read(ctx):
    done = len(ctx.completed())
    if "wall_exchange_wait_s" not in ctx.counters or not done:
        return None
    return ctx.counters["wall_exchange_wait_s"] / done
