"""Rows the mesh fragments' probe exchanges routed, per cent of the lanes their receive
tensors held (``probe_exchange_rows`` over ``probe_exchange_lanes``, window deltas): 100
where every received lane carried a row, 12.5 where one in eight did.  None on a program
without the counters, or in a window in which no probe exchange ran."""

from benchmark.harness import stats


def read(ctx):
    if "probe_exchange_lanes" not in ctx.counters:
        return None
    return stats.share(ctx.counters.get("probe_exchange_rows", 0),
                       ctx.counters["probe_exchange_lanes"])
