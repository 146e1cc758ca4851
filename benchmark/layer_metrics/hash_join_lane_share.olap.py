"""Lanes that joins probed through the open-addressing loop of a hashed table, per cent
of all lanes that entered a join's match or probe step (``join_hash_probe_lanes`` over
it plus ``join_direct_probe_lanes``, window deltas): 0 where every join table is
direct-indexed, as in the TPC-H cells.  None on a program without the counters, or in a
window where no join probed a lane."""

from benchmark.harness import stats


def read(ctx):
    if "join_hash_probe_lanes" not in ctx.counters:
        return None
    hashed = ctx.counters["join_hash_probe_lanes"]
    return stats.share(hashed, hashed + ctx.counters.get("join_direct_probe_lanes", 0))
