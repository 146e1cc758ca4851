"""Median over ``point`` statements of client seconds minus the server's root span:
HTTP, polling and the client's own parsing."""

from benchmark.harness import stats


def read(ctx):
    over = ctx.overhead_s.get("point")
    return stats.median(over) * 1e3 if over else None
