"""How much fuller the fullest chip's receive side is than an even share, in percent:
``exchange_rows_max_shard`` x chips over ``exchange_rows``, less 1 (window deltas; 0 is
even, 300 is everything on one of four chips).  None on a program without the counters
or in a window that exchanged nothing."""


def read(ctx):
    rows = ctx.counters.get("exchange_rows")
    if not rows or "exchange_rows_max_shard" not in ctx.counters:
        return None
    return (ctx.counters["exchange_rows_max_shard"] * ctx.cell.chips / rows - 1.0) * 100.0
