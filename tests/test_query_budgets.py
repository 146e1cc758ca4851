"""Per-query device-boundary BUDGETS over the warm TPC-H north-star queries.

Warm join queries pay for every host<->device sync and launch, and the round-5 wins
(_finalize_aggs_device, _topn_page_device) traced a ~40MB -> ~660B transfer
reduction that nothing protected: one stray np.asarray in a loop silently
reverts it.  These tests turn the trace notes into committed invariants —
each warm SF1 query must stay within a dispatch-count and host-bytes ceiling
recorded HERE, from a real capture (reference analog: the zero-per-page
scheduler cost of Trino's driver pump, operator/Driver.java:372-481, enforced
instead of assumed).

Round 9: the budgets pin the DEVICE BUFFER POOL ON (TRINO_TPU_PAGE_CACHE set
by the fixture — the production configuration on device backends).  Each
query's cold run populates the pool; the warm budgeted run serves every scan
as ONE resident page, so the per-split consumer dispatches collapse on top
of the round-6 coalescing win.  Ceilings were re-derived with
scripts/query_counters.py on the 8-device CPU mesh (SF1, split_rows=1<<21,
2026-08-03, `--page-cache 6442450944`) and carry ~25-35% headroom over the
measured warm trace:

    measured warm (cache on):  q1 4/285B   q3  6/258B   q9  7/3057B   q18  6/2831B
                               (PR 28: q3 8/262B; PR 30: q18 8/2835B;
                                PR 38: q1 4/315B, q3 8/309B, q9 7/3132B, q18 8/2890B)
    measured warm (cache off): q1 6/285B   q3 10/262B   q9 10/3057B   q18 10/2835B
    measured warm (batch=1):   q1 10/285B  q3 22/278B   q9 29/3077B   q18 20/2851B

The dispatch ceilings now sit BELOW the cache-off trace: losing the pool's
whole-scan hit (a scan source bypassing _scan_pages_source, a put_scan that
stops storing, a key that stops matching across runs) fails this suite just
like losing coalescing or reintroducing a per-split sync would.  Entries are
keyed per (table, splits, columns), and the four queries' scan specs are
pairwise distinct, so the ceilings are test-order independent; 6GB budget
fits the ~2GB SF1 working set with no eviction.  A reintroduced bulk pull
(the device-finalize or device-TopN regressions) overshoots the byte
ceilings by KBs.  Counters are NOT env-dependent beyond the fixture's own
page-cache budget: split geometry is pinned by sf/split_rows and page shapes
are pow2-quantized.

Round 17: the budgets additionally pin warm ``compiles == 0`` (the compile
observatory at the _jit chokepoint — detection is a host-side seen-signature
set lookup, so the dispatch/byte ceilings are UNCHANGED with it enabled).  A
warm compile is the recompile-regression signature: shape churn that used to
ship silently as inflated warm walls now fails this suite by name.  The
observatory's first catch was THIS SUITE's own 2-run structure: with the
page cache on, run 2's whole-scan served page is a new shape class that
recompiles the streams (q1 ~2s, q9 ~4.5s, measured 2026-08-04) — the
budgeted "warm" run is now the THIRD execution, the first that is genuinely
compile-free.  Re-derive with ``scripts/query_counters.py --compiles``.

Re-derive after an intentional executor change (cache-on and off):
    JAX_PLATFORMS=cpu python scripts/query_counters.py --page-cache 6442450944
    JAX_PLATFORMS=cpu python scripts/query_counters.py --page-cache 0
"""

import pytest

from trino_tpu import Engine
from trino_tpu.connectors.tpch import TpchConnector

# the north-star queries (inlined: the ceilings must not drift with an edit of
# chip_smoke.py's texts)
QUERIES = {
    "q1": """
    select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,
           sum(l_extendedprice) as sum_base_price,
           sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
           sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
           avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price,
           avg(l_discount) as avg_disc, count(*) as count_order
    from lineitem where l_shipdate <= date '1998-12-01' - interval '90' day
    group by l_returnflag, l_linestatus order by l_returnflag, l_linestatus""",
    "q3": """
    select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
           o_orderdate, o_shippriority
    from customer, orders, lineitem
    where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
      and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'
      and l_shipdate > date '1995-03-15'
    group by l_orderkey, o_orderdate, o_shippriority
    order by revenue desc, o_orderdate limit 10""",
    "q9": """
    select nation, o_year, sum(amount) as sum_profit from (
      select n_name as nation, extract(year from o_orderdate) as o_year,
        l_extendedprice * (1 - l_discount) - ps_supplycost * l_quantity as amount
      from part, supplier, lineitem, partsupp, orders, nation
      where s_suppkey = l_suppkey and ps_suppkey = l_suppkey and ps_partkey = l_partkey
        and p_partkey = l_partkey and o_orderkey = l_orderkey
        and s_nationkey = n_nationkey and p_name like '%green%') as profit
    group by nation, o_year order by nation, o_year desc""",
    "q18": """
    select c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice, sum(l_quantity)
    from customer, orders, lineitem
    where o_orderkey in (select l_orderkey from lineitem group by l_orderkey
                         having sum(l_quantity) > 300)
      and c_custkey = o_custkey and o_orderkey = l_orderkey
    group by c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
    order by o_totalprice desc, o_orderdate limit 100""",
}

# (max device dispatches, max host bytes pulled) per WARM query with the
# buffer pool on.  Dispatch ceilings enforce the whole-scan cache hit on top
# of coalescing — round-8 ceilings were q1 8, q3 12, q9 15, q18 12; the
# cache-off warm trace (10/10/10 for q3/q9/q18) must now BREACH them, which
# is exactly the protection: a silently dead cache fails the suite.
# PR 28: a split join adds a match step and a pack to a statement whose first
# join is selective, plus one 4-byte count pull ("join.match.count"): warm q3
# 6 -> 8 dispatches (now AT its ceiling), q9 7 (its boundary moved into the
# first join), bytes +4 each; q18's first page stayed dense, so it compiled as
# one step and kept its 6.
# PR 30: q18's semi-join now filters orders inside the first join's build
# (PushSemiJoinThroughJoin), so its first probe is selective and pays what q3
# pays: a match step, a pack and the 4-byte count.  Measured warm: 8 dispatches
# (join.match, jc_fn, agg.hash.prepare, _compact_part, insert_compact,
# agg.finalize, the TopN's stream.page and _compact_part_sized), 2835 bytes
# (2831 + 4).  q18 is now AT its dispatch ceiling, as q3 is; the ceiling stays.
# PR 38: the group-by's overflow, group-count and envelope scalars and the plan
# history's row counters are pulled through _host now (they were bool() / int() /
# jax.device_get syncs that no counter saw): the same round trips, and their
# bytes are counted.  Measured warm: q1 +30 B (1 + 4 + 1 + three 8-byte row
# counters), q3 +47, q9 +75, q18 +55; host_transfers 4 -> 8, 5 -> 10, 6 -> 11,
# 5 -> 10.  Every ceiling still holds them, so none is raised.
# PR 39: a group-by statement's prologue and epilogue are counted programs now
# (they were about 120-285 eager launches that no counter saw): +agg.direct.init
# or agg.hash.init, +sort.rows (the Sort/TopN's whole device part), and the
# packed page of the finalize needs no _compact_part_sized before the sort: +1
# dispatch a statement.  The group count, the envelope flag and the sort's
# count ride pulls that were there (warm q1: host_transfers 8 -> 3), and the
# bytes fall with them.  Measured warm: q3 9 dispatches, q18 9; q1 and q9
# stay inside their ceilings: the two that sat AT theirs move by the one.
BUDGETS = {
    "q1": (6, 400),
    "q3": (9, 400),
    "q9": (9, 3400),    # pre-round-6 trace: 4228 bytes — must stay below it
    "q18": (9, 3200),
}


@pytest.fixture(scope="module")
def sf1(request):
    import os

    # round 9: the budgets are pinned WITH the device buffer pool ON (the
    # production configuration on device backends) — the cold run of each
    # query populates the pool, the warm budgeted run serves every scan as
    # one resident page.  6GB comfortably fits the SF1 working set
    # (~2GB of distinct (table, splits, columns) entries), so no eviction
    # perturbs the counters.
    prev = os.environ.get("TRINO_TPU_PAGE_CACHE")
    os.environ["TRINO_TPU_PAGE_CACHE"] = str(6 * 1024 * 1024 * 1024)
    # round 12: the RESULT cache stays OFF here, pinned explicitly.  The
    # budgets measure the EXECUTE path — with the result tier on, the warm
    # budgeted run would be answered whole from the cache (0 dispatches) and
    # the "counters must be live" assertion below would fail.  Re-derive
    # with the same configuration: scripts/query_counters.py keeps the tier
    # off unless --result-cache is passed.
    prev_rc = os.environ.get("TRINO_TPU_RESULT_CACHE")
    os.environ["TRINO_TPU_RESULT_CACHE"] = "0"
    engine = Engine()
    engine.register_catalog("tpch", TpchConnector(sf=1, split_rows=1 << 21))
    session = engine.create_session("tpch")
    yield engine, session
    # SF1 compiled pipelines + build pages + the buffer pool are
    # device-resident: release them before the next module runs
    engine._invalidate()
    if prev is None:
        os.environ.pop("TRINO_TPU_PAGE_CACHE", None)
    else:
        os.environ["TRINO_TPU_PAGE_CACHE"] = prev
    if prev_rc is None:
        os.environ.pop("TRINO_TPU_RESULT_CACHE", None)
    else:
        os.environ["TRINO_TPU_RESULT_CACHE"] = prev_rc


def _sites_table(c) -> str:
    """Per-site attribution dump for budget-failure messages: a tripped
    ceiling names the exact operator/call-site that regressed (re-derive with
    scripts/query_counters.py --sites)."""
    rows = sorted(c.sites.items(),
                  key=lambda kv: (-kv[1]["dispatches"], -kv[1]["bytes"]))
    return "\n".join(f"  {k}: {v['dispatches']} dispatches, "
                     f"{v['transfers']} transfers, {v['bytes']} bytes"
                     for k, v in rows)


@pytest.mark.parametrize("name", sorted(BUDGETS))
def test_warm_query_stays_within_budget(sf1, name):
    engine, session = sf1
    engine.execute_sql(QUERIES[name], session)  # cold: plan + XLA compile
    cold = engine.last_query_counters
    # round 17: the cold run is where the compiles live — the observatory
    # must actually see them (a detection regression would silently pass
    # the warm zero below)
    assert cold.compiles > 0, cold.as_dict()
    # second run: the first CACHE-HIT execution.  The observatory exposed a
    # fact the 2-run structure had hidden: run 1 (cache miss) compiles the
    # per-split page shapes, and run 2's whole-scan served page is a NEW
    # shape class that compiles AGAIN (~2s q1 / ~4.5s q9 on this box,
    # previously invisible inside "warm" wall).  The budgeted run below is
    # therefore the THIRD execution — the first with zero compiles — and
    # its dispatch/byte path is identical to run 2's (same cache-hit plan).
    engine.execute_sql(QUERIES[name], session)
    engine.execute_sql(QUERIES[name], session)  # warm: the budgeted run
    c = engine.last_query_counters
    max_disp, max_bytes = BUDGETS[name]
    # the counters must actually be live (an accounting regression that stops
    # recording would otherwise pass every ceiling)
    assert c.device_dispatches > 0 and c.host_transfers > 0, c
    # round 17: WARM queries compile NOTHING — every dispatch re-uses a
    # seen signature.  A nonzero count here is the recompile-regression
    # signature (shape churn from non-uniform splits, un-quantized size
    # buckets, a cache that stopped keying) that previously shipped
    # silently inside inflated warm walls.
    assert c.compiles == 0, (
        f"{name}: {c.compiles} warm compiles ({c.compile_s:.3f}s) — a "
        f"recompile crept into the warm path; per-site attribution:\n"
        f"{_sites_table(c)}")
    assert c.device_dispatches <= max_disp, (
        f"{name}: {c.device_dispatches} warm device dispatches > budget "
        f"{max_disp} — a per-page/per-split dispatch crept into the warm "
        f"path; per-site attribution:\n{_sites_table(c)}")
    assert c.host_bytes_pulled <= max_bytes, (
        f"{name}: {c.host_bytes_pulled} warm host bytes > budget {max_bytes} "
        f"— a bulk device->host pull crept into the warm path; per-site "
        f"attribution:\n{_sites_table(c)}")


def test_warm_q3_span_tree(sf1):
    """Round-7 acceptance: the warm SF1 q3 span tree — one root, an execution
    span, one dispatch span per counted dispatch, and prefetch-thread spans
    that parent INTO the tree (explicit cross-thread handoff; they were
    orphans when parenting was thread-local)."""
    import time as _time

    engine, session = sf1
    # page_cache=false for THIS session: a buffer-pool hit serves the scan
    # without ever starting a prefetch producer, and this test exists to
    # pin the prefetch-thread span parenting (the property is
    # non-plan-shaping, so the cached plan is reused either way)
    session = engine.create_session("tpch")
    engine.session_properties.set_property(session, "page_cache", False)
    engine.execute_sql(QUERIES["q3"], session)  # plan cache warm (cheap if
    engine.execute_sql(QUERIES["q3"], session)  # the budget tests ran first)
    c = engine.last_query_counters
    t = engine.last_query_trace
    qid = t["query_id"]
    names = [sp["name"] for sp in t["spans"]]
    roots = [sp for sp in t["spans"] if sp["parent_id"] is None]
    assert len(roots) == 1 and roots[0]["name"] == "query"
    assert "execution" in names
    assert names.count("dispatch") == c.device_dispatches
    # per-site sums == totals (the attribution invariant)
    assert sum(v["dispatches"] for v in c.sites.values()) \
        == c.device_dispatches
    assert sum(v["bytes"] for v in c.sites.values()) == c.host_bytes_pulled
    # prefetch spans land slightly after the query returns (producer-thread
    # close): poll the tracer, then check parents resolve inside the trace
    spans = engine.tracer.spans_for(qid)
    for _ in range(50):
        spans = engine.tracer.spans_for(qid)
        if any(sp.name == "prefetch" for sp in spans):
            break
        _time.sleep(0.02)
    prefetch = [sp for sp in spans if sp.name == "prefetch"]
    assert prefetch, \
        f"no prefetch span in {sorted({s.name for s in spans})}"
    ids = {sp.span_id for sp in spans}
    for sp in prefetch:
        assert sp.parent_id in ids, "prefetch span is an orphan"


def test_explain_analyze_q9_per_operator_attribution(sf1):
    """Round-7 acceptance: EXPLAIN ANALYZE on warm SF1 q9 shows per-operator
    and per-site dispatch/byte attribution whose sums equal the query's
    QueryCounters totals exactly."""
    import re

    engine, session = sf1
    r = engine.execute_sql(f"explain analyze {QUERIES['q9']}", session)
    text = "\n".join(str(row[0]) for row in r.rows())
    c = engine.last_query_counters
    m = re.search(r"Device boundary: (\d+) dispatches, (\d+) host transfers, "
                  r"(\d+) bytes pulled", text)
    assert m, text
    assert (int(m.group(1)), int(m.group(2)), int(m.group(3))) == \
        (c.device_dispatches, c.host_transfers, c.host_bytes_pulled), text
    # per-site lines sum to the totals
    sites = re.findall(r"site (\S+): (\d+) dispatches, (\d+) transfers, "
                       r"(\d+) bytes", text)
    assert sites, text
    assert sum(int(d) for _, d, _t, _b in sites) == c.device_dispatches, text
    assert sum(int(b) for _, _d, _t, b in sites) == c.host_bytes_pulled, text
    # per-operator rows attribute the join/aggregate pipeline itself
    op_rows = re.findall(r"\[boundary: (\d+) dispatches, (\d+) transfers, "
                         r"(\d+) bytes\]", text)
    assert op_rows, text
    assert sum(int(d) for d, _t, _b in op_rows) > 0


def test_warm_wall_breakdown_sums_to_wall(sf1):
    """Round-16 acceptance: warm SF1 q3 and q18 wall-breakdown buckets sum
    to within 5% of the measured wall (by construction: disjoint sweep
    attribution + an explicit unattributed remainder), and the flight
    recorder is ENABLED for every budgeted run in this module — its feed
    adds zero dispatches/pulls, so the ceilings above are UNCHANGED."""
    from trino_tpu.execution.tracing import WALL_BUCKETS

    engine, session = sf1
    assert engine.flight_recorder.enabled  # the budget runs record flights
    for name in ("q3", "q18"):
        engine.execute_sql(QUERIES[name], session)  # cold/warm-up
        engine.execute_sql(QUERIES[name], session)  # warm: the measured run
        t = engine.last_query_trace
        bd = t.get("wall_breakdown")
        assert bd, f"{name}: no wall breakdown on the warm trace"
        total = sum(bd[b] for b in WALL_BUCKETS)
        wall = bd["wall_s"]
        assert wall > 0 and abs(total - wall) <= 0.05 * wall, \
            (name, total, wall, bd)
        # the dominant cost is named, not everything dumped in unattributed
        assert bd["device_dispatch"] > 0, bd
        # the statement's flight record carries the same decomposition
        rec = engine.flight_recorder.get(t["query_id"])
        assert rec is not None and rec["wall_breakdown"] == bd


def test_explain_analyze_shows_device_boundary(engine):
    """EXPLAIN ANALYZE surfaces the per-query counters (sql/planprinter)."""
    r = engine.execute_sql(
        "explain analyze select count(*) from nation")
    text = "\n".join(str(row[0]) for row in r.rows())
    assert "Device boundary:" in text
    assert "dispatches" in text and "bytes pulled" in text
