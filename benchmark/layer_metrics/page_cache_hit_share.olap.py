"""Page-cache hits over hits plus misses (``buffer_pool.info()``), window delta."""

from benchmark.harness import stats


def read(ctx):
    hits = ctx.pool.get("hits", 0)
    return stats.share(hits, hits + ctx.pool.get("misses", 0))
