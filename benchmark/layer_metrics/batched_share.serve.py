"""Requests that rode a fused (batched) dispatch over statements completed."""

from benchmark.harness import stats


def read(ctx):
    return stats.share(ctx.counters.get("batched_requests", 0), len(ctx.completed()))
