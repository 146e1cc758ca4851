"""Plan-template hits over hits plus misses, window delta."""

from benchmark.harness import stats


def read(ctx):
    hits = ctx.counters.get("plan_template_hits", 0)
    return stats.share(hits, hits + ctx.counters.get("plan_template_misses", 0))
