"""95th percentile of statement seconds over all classes; a failed statement counts as
missing any limit (infinitely late)."""

import math

from benchmark.harness import stats


def read(ctx):
    seconds = [r["seconds"] if r["error"] is None else math.inf for r in ctx.records]
    p95 = stats.percentile(seconds, 0.95)
    return None if math.isinf(p95) else p95
