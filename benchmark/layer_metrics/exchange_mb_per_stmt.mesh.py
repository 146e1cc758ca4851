"""Megabytes (10^6 bytes) of rows a statement's all-to-all exchanges delivered
(``exchange_bytes`` window delta over statements completed): the probe exchanges' routed
rows and the merge exchanges' group entries, each times the width of its routed columns.
Payload, not lanes: the receive tensors' dead lanes are ``probe_recv_fill_share.mesh``'s.
None on a program without the counter."""


def read(ctx):
    done = len(ctx.completed())
    if "exchange_bytes" not in ctx.counters or not done:
        return None
    return ctx.counters["exchange_bytes"] / done / 1e6
