"""Seconds a statement's thread waited on the prefetch queue of its scans
(``wall_scan_wait_s``: the ``scan_wait`` bucket, spans ``scan.wait``; window delta over
statements completed): the consumer had nothing to dispatch until the producer's next page
arrived.  None on a program without the counter (before PR 38)."""


def read(ctx):
    done = len(ctx.completed())
    if "wall_scan_wait_s" not in ctx.counters or not done:
        return None
    return ctx.counters["wall_scan_wait_s"] / done
