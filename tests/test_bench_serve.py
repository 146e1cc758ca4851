"""bench_serve.py smoke (round-12 CI satellite, round-14 template phase):
in-process server, tiny load, asserting the JSON-line contract — per-class
p50/p99 for every workload class across the three phases, cache/template
hit rates, the counter-verified zero-dispatch warm repeat hit, and cache-on
results byte-identical to cache-off.

Since round 14 the point/param classes draw per-request DISTINCT constants
(the millions-of-users shape plan templates serve), so the cache-on phase
legitimately dispatches for first-sight bindings — the zero-dispatch
contract is pinned on the REPEAT statement (``warm_hit_zero_dispatches``),
not the whole phase.

The 5x acceptance ratios are NOT asserted here: the 1-core build box's
load makes absolute latency ratios flaky at smoke scale — the ratios are
recorded in the payload (``repeat_p50_speedup``,
``{point,param}_template_qps_speedup``); the serve A/B has not been run on
the chip.
"""

import json

import pytest


@pytest.fixture(scope="module")
def serve_payload():
    import contextlib
    import io

    import bench_serve

    # tiny knobs via module attributes (env was read at import time);
    # module-scoped so the ~30s serve run happens ONCE for both tests
    mp = pytest.MonkeyPatch()
    mp.setattr(bench_serve, "SF", 0.01)
    mp.setattr(bench_serve, "DURATION", 1.2)
    mp.setattr(bench_serve, "CLIENTS", 2)
    mp.setattr(bench_serve, "QPS", 3.0)
    mp.setattr(bench_serve, "BATCH_QPS", 48.0)
    mp.setattr(bench_serve, "BUDGET", 480.0)
    mp.setattr(bench_serve, "RESULT_CACHE", 64 << 20)
    mp.setattr(bench_serve, "PAGE_CACHE", 1 << 30)
    mp.setattr(bench_serve, "WORKERS", 0)
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            bench_serve.main()
    finally:
        mp.undo()
    out = buf.getvalue().strip().splitlines()
    # ONE JSON line on stdout — the bench.py contract
    assert len(out) == 1, out
    yield json.loads(out[0])


def test_json_line_contract(serve_payload):
    p = serve_payload
    assert p["metric"].startswith("serve_sf0.01")
    assert p["unit"] == "qps" and p["value"] > 0
    assert "env" in p
    for half in ("templates_off", "cache_off", "cache_on"):
        phase = p["phases"][half]
        classes = phase["closed"]["classes"]
        for cls in ("repeat", "point", "param", "agg", "tpch"):
            assert cls in classes, (half, classes)
            if classes[cls]["count"]:
                assert classes[cls]["p50_ms"] is not None
                assert classes[cls]["p99_ms"] is not None
        assert phase["open"] is not None  # open loop ran too
        # cache hit rates ride each phase's buffer-pool snapshot
        assert "result_hits" in phase["buffer_pool"]
        assert "hits" in phase["buffer_pool"]
    on = p["phases"]["cache_on"]
    assert on["buffer_pool"]["result_hits"] > 0
    assert on["counters"]["result_cache_hits"] > 0


def test_warm_hits_cost_zero_dispatches_and_match(serve_payload):
    p = serve_payload
    # the acceptance contract, counter-verified in-process by bench_serve
    assert p["warm_hit_zero_dispatches"] is True
    assert p["cache_identical"] is True
    # repeats serve from the result tier; DISTINCT point/param bindings
    # execute (each is its own binding-specific entry), so the phase
    # dispatches — but the repeat statement never does, and the tier is live
    on = p["phases"]["cache_on"]["counters"]
    assert on["result_cache_hits"] > 0, on
    # and the off half actually executed (the A/B is a real A/B)
    off = p["phases"]["cache_off"]["counters"]
    assert off["device_dispatches"] > 0
    assert off["result_cache_hits"] == 0


def test_template_phase_contract(serve_payload):
    p = serve_payload
    # the template A/B ran: substitution baseline shows zero template
    # traffic, the template phase shows hits on the point/param classes
    off = p["phases"]["templates_off"]["counters"]
    assert off["plan_template_hits"] == 0, off
    on = p["phases"]["cache_off"]["counters"]
    assert on["plan_template_hits"] > 0, on
    assert p["template_hit_rate"] > 0
