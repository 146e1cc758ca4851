"""Milliseconds a statement spends after the engine answered: rows to JSON
(``encode_s``) and the lag until the client's next poll fetches the last page
(``deliver_wait_s``), window delta over statements completed."""


def read(ctx):
    done = len(ctx.completed())
    if "deliver_wait_s" not in ctx.counters or not done:
        return None
    return (ctx.counters["encode_s"] + ctx.counters["deliver_wait_s"]) / done * 1e3
