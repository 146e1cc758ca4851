"""Lanes at which split joins gathered their build columns, per cent of the lanes that
entered their match steps (``join_gather_lanes`` over ``join_match_lanes``, window
deltas): 100 where every batch stayed dense, 1.5625 where every batch packed into n/64.
None on a program without the counters, or in a window where no join matched a lane."""

from benchmark.harness import stats


def read(ctx):
    if "join_match_lanes" not in ctx.counters:
        return None
    return stats.share(ctx.counters.get("join_gather_lanes", 0),
                       ctx.counters["join_match_lanes"])
