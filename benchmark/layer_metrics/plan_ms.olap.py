"""Median ``plan`` bucket of the engine's wall breakdown per statement (host clock;
single-client cells only, where the engine's last-statement trace is this statement's)."""

from benchmark.harness import stats


def read(ctx):
    plans = [r["plan_s"] for r in ctx.completed() if r.get("plan_s") is not None]
    return stats.median(plans) * 1e3 if plans else None
