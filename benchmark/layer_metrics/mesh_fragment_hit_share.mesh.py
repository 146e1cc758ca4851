"""Kept mesh fragments served over fragments looked up (``mesh_fragment_hits`` over hits
plus ``mesh_fragment_compiles``, window deltas), in percent: 100 in a sound window, in
which every fragment of every statement was compiled in set-up.  None on a program
without the counters or in a window that looked none up."""

from benchmark.harness import stats


def read(ctx):
    if "mesh_fragment_hits" not in ctx.counters:
        return None
    hits = ctx.counters["mesh_fragment_hits"]
    looked_up = hits + ctx.counters.get("mesh_fragment_compiles", 0)
    return stats.share(hits, looked_up) if looked_up else None
