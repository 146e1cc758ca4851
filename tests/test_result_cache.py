"""Result-cache tier (execution/bufferpool third tier) — round 12.

Covers the acceptance surface of the RESULT cache: a repeated deterministic
statement is answered with ZERO device dispatches / executor checkouts /
host pulls (counter-verified), byte-identical to the executed run; the full
invalidation matrix (INSERT/DDL clear, catalog-version bump, plan-shaping
SET SESSION, volatile functions/connectors, LRU under a tiny budget,
per-entry cap); concurrent pooled executors racing the same statement; the
shared chaos scenarios (store/checkout deny recoverable, errored queries
never cache); and the observability wiring (EXPLAIN ANALYZE line,
/v1/metrics series, system.runtime.queries column).

The tier budget comes from TRINO_TPU_RESULT_CACHE, resolved lazily at first
use — every test sets it via monkeypatch BEFORE building its Engine (the
same pattern as test_page_cache).
"""

import threading

import numpy as np
import pytest

from trino_tpu import Engine
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.execution import faults
from trino_tpu.execution.chaos_matrix import (RESULT_SCENARIOS, leak_report,
                                              run_result_scenario)
from trino_tpu.execution.chaos_matrix import result_signature as _sig

SF, SPLIT_ROWS = 0.01, 1 << 14

Q_AGG = """
select l_returnflag, l_linestatus, sum(l_quantity) s, count(*) c
from lineitem where l_shipdate <= date '1998-09-02'
group by l_returnflag, l_linestatus order by l_returnflag, l_linestatus"""

Q_JOIN = """
select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
       o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
  and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'
  and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate limit 10"""

Q_POINT = "select c_name, c_acctbal from customer where c_custkey = 7"


def _engine(monkeypatch, budget=64 << 20, page_budget=0):
    monkeypatch.setenv("TRINO_TPU_RESULT_CACHE", str(budget))
    monkeypatch.setenv("TRINO_TPU_PAGE_CACHE", str(page_budget))
    e = Engine()
    e.register_catalog("tpch", TpchConnector(sf=SF, split_rows=SPLIT_ROWS))
    return e


def _assert_same(a, b):
    assert _sig(a) == _sig(b)
    for x, y in zip(a.raw_columns, b.raw_columns):
        xa, ya = np.asarray(x), np.asarray(y)
        assert xa.dtype == ya.dtype
        assert np.array_equal(xa, ya, equal_nan=xa.dtype.kind == "f")


@pytest.mark.parametrize("sql", [Q_AGG, Q_JOIN, Q_POINT],
                         ids=["agg", "join", "point"])
def test_warm_hit_zero_boundary_and_byte_identical(monkeypatch, sql):
    e = _engine(monkeypatch)
    s = e.create_session("tpch")
    off = e.create_session("tpch")
    e.session_properties.set_property(off, "result_cache", False)
    r_off = e.execute_sql(sql, off)
    c = e.last_query_counters
    assert c.result_cache_hits == 0 and c.result_cache_misses == 0
    r1 = e.execute_sql(sql, s)  # admissible miss: executes + stores
    c = e.last_query_counters
    assert c.result_cache_misses == 1 and c.result_cache_hits == 0
    r2 = e.execute_sql(sql, s)  # warm: served whole from the tier
    c = e.last_query_counters
    # the zero-dispatch contract, counter-verified: no device work, no host
    # pulls, no splits — the statement never reached the executor path
    assert c.result_cache_hits == 1
    assert c.device_dispatches == 0 and c.host_transfers == 0 \
        and c.host_bytes_pulled == 0, c.as_dict()
    assert c.result_cache_bytes_saved > 0
    # attribution: the hit landed on the result.cache site
    assert c.sites.get("result.cache", {}).get("result_cache_hits") == 1
    _assert_same(r_off, r1)
    _assert_same(r_off, r2)
    e._invalidate()


def test_hit_skips_executor_checkout(monkeypatch):
    e = _engine(monkeypatch)
    s = e.create_session("tpch")
    e.execute_sql(Q_POINT, s)
    n_executors = len(e._all_executors)
    calls = []
    orig = e._checkout_executor

    def counting():
        calls.append(1)
        return orig()

    monkeypatch.setattr(e, "_checkout_executor", counting)
    e.execute_sql(Q_POINT, s)
    assert e.last_query_counters.result_cache_hits == 1
    assert not calls, "a served statement checked out an executor"
    assert len(e._all_executors) == n_executors
    e._invalidate()


def test_insert_and_ddl_invalidate(monkeypatch):
    from trino_tpu.connectors.memory import MemoryConnector

    monkeypatch.setenv("TRINO_TPU_RESULT_CACHE", str(64 << 20))
    monkeypatch.setenv("TRINO_TPU_PAGE_CACHE", "0")
    e = Engine()
    e.register_catalog("mem", MemoryConnector())
    s = e.create_session("mem")
    e.execute_sql("create table t (k bigint, v bigint)", s)
    e.execute_sql("insert into t values (1, 10), (2, 20)", s)
    e.execute_sql("select sum(v) s from t", s)
    e.execute_sql("select sum(v) s from t", s)
    assert e.last_query_counters.result_cache_hits == 1
    assert e.buffer_pool.info()["result_entries"] == 1
    e.execute_sql("insert into t values (3, 70)", s)  # DML clears the pool
    assert e.buffer_pool.info()["result_entries"] == 0
    r = e.execute_sql("select sum(v) s from t", s)
    assert int(r.columns[0][0]) == 100, "stale result served after INSERT"
    e.execute_sql("create table u (x bigint)", s)  # DDL clears too
    assert e.buffer_pool.info()["result_entries"] == 0
    # pool accounting: reservations always equal resident bytes
    bp = e.buffer_pool
    assert bp.memory_pool is None or \
        bp.memory_pool.reserved == bp.info()["bytes"]
    e._invalidate()


class _VersionedTpch(TpchConnector):
    """Cacheable connector with a bumpable plan_version — the growable-
    catalog shape (parquet DML, system dictionaries) without the weight."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.version = 0

    def plan_version(self) -> int:
        return self.version


def test_catalog_version_bump_invalidates(monkeypatch):
    monkeypatch.setenv("TRINO_TPU_RESULT_CACHE", str(64 << 20))
    monkeypatch.setenv("TRINO_TPU_PAGE_CACHE", "0")
    e = Engine()
    conn = _VersionedTpch(sf=SF, split_rows=SPLIT_ROWS)
    e.register_catalog("tpch", conn)
    s = e.create_session("tpch")
    e.execute_sql(Q_POINT, s)
    e.execute_sql(Q_POINT, s)
    assert e.last_query_counters.result_cache_hits == 1
    conn.version += 1
    # the version-stale plan path replans AND drops the catalog's entries:
    # the old entry can neither serve (fingerprint embeds v0) nor pin bytes
    e.execute_sql(Q_POINT, s)
    c = e.last_query_counters
    assert c.result_cache_hits == 0 and c.result_cache_misses == 1
    info = e.buffer_pool.info()
    assert info["result_entries"] == 1  # only the fresh v1 entry
    e.execute_sql(Q_POINT, s)
    assert e.last_query_counters.result_cache_hits == 1
    e._invalidate()


def test_plan_shaping_property_change_misses(monkeypatch):
    e = _engine(monkeypatch)
    s = e.create_session("tpch")
    e.execute_sql(Q_AGG, s)
    e.execute_sql(Q_AGG, s)
    assert e.last_query_counters.result_cache_hits == 1
    # dispatch_batch rides _plan_shape_props, which rides the result key: a
    # SET SESSION that re-plans must also re-execute, never serve the old
    # shape's cached result
    e.session_properties.set_property(s, "dispatch_batch", 1)
    r = e.execute_sql(Q_AGG, s)
    c = e.last_query_counters
    assert c.result_cache_hits == 0 and c.result_cache_misses == 1
    assert len(r) > 0
    e._invalidate()


def test_volatile_functions_and_connectors_excluded(monkeypatch):
    e = _engine(monkeypatch)
    s = e.create_session("tpch")
    vol = "select n_name, now() t from nation"
    e.execute_sql(vol, s)
    e.execute_sql(vol, s)
    c = e.last_query_counters
    assert c.result_cache_hits == 0 and c.result_cache_misses == 0
    # the system catalog is a volatile connector (no CACHEABLE_SCANS):
    # repeated runs execute every time
    q = "select count(*) c from system.queries"
    e.execute_sql(q, s)
    e.execute_sql(q, s)
    c = e.last_query_counters
    assert c.result_cache_hits == 0 and c.result_cache_misses == 0
    assert e.buffer_pool.info()["result_entries"] <= 1  # only the tpch entry
    e._invalidate()


def test_lru_eviction_and_entry_cap_under_tiny_budget(monkeypatch):
    # ~2KB budget: the region/nation singles fit one at a time, so
    # alternating statements must LRU-evict, never raise, and stay inside
    # the labeled pool's ceiling
    monkeypatch.setenv("TRINO_TPU_RESULT_CACHE", "2048")
    monkeypatch.setenv("TRINO_TPU_PAGE_CACHE", "0")
    e = Engine()
    e.register_catalog("tpch", TpchConnector(sf=SF, split_rows=SPLIT_ROWS))
    s = e.create_session("tpch")
    for sql in ("select count(*) c from region group by r_regionkey",
                "select count(*) c from nation group by n_nationkey",
                "select count(*) c from region group by r_regionkey"):
        e.execute_sql(sql, s)
    info = e.buffer_pool.info()
    assert info["result_bytes"] <= 2048
    assert e.buffer_pool.memory_pool.reserved == info["bytes"]
    # an entry past the per-entry cap (budget/4 = 512B) is skipped, not an
    # error — the wide customer scan result is far bigger than that
    r = e.execute_sql("select c_custkey, c_name, c_acctbal from customer", s)
    assert len(r) > 0
    info = e.buffer_pool.info()
    assert info["result_bytes"] <= 2048
    e._invalidate()


def test_concurrent_same_statement_byte_identical_one_store(monkeypatch):
    e = _engine(monkeypatch)
    s0 = e.create_session("tpch")
    ref = e.execute_sql(Q_JOIN, s0)  # plan + first store
    results, errors = [None] * 6, []

    def run(i):
        try:
            results[i] = e.execute_sql(Q_JOIN, e.create_session("tpch"))
        except Exception as ex:  # surface in the main thread
            errors.append(ex)

    ts = [threading.Thread(target=run, args=(i,)) for i in range(6)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errors, errors
    for r in results:
        _assert_same(ref, r)
    info = e.buffer_pool.info()
    # at most one store: every racer either hit or found the entry already
    # present at store time (put_result's in-lock duplicate check)
    assert info["result_entries"] == 1, info
    assert info["result_hits"] >= 1
    assert not leak_report(e)
    e._invalidate()
    assert e.buffer_pool.info()["entries"] == 0
    assert e.buffer_pool.memory_pool.reserved == 0


@pytest.mark.parametrize("scenario", [n for n, _s, _k in RESULT_SCENARIOS])
def test_chaos_result_scenarios(monkeypatch, scenario):
    """The shared chaos matrix rows: store/checkout faults are recoverable
    and byte-identical, no entry is admitted under a store fault, and the
    leak check passes after every scenario."""
    spec, kind = next((s, k) for n, s, k in RESULT_SCENARIOS
                      if n == scenario)
    e = _engine(monkeypatch)
    s = e.create_session("tpch")
    e.execute_sql(Q_AGG, s)  # cold
    base = _sig(e.execute_sql(Q_AGG, s))
    rec = run_result_scenario(e, Q_AGG, s, base, scenario, spec, kind)
    assert rec.get("ok"), rec
    e._invalidate()


def test_store_refused_after_mid_statement_invalidation(monkeypatch):
    """A DML's invalidation landing WHILE a select executes must refuse the
    select's late store: the result may predate the DML, and connectors
    without plan_version have no other staleness defense.  The engine
    captures the pool epoch before executing and presents it at store."""
    e = _engine(monkeypatch)
    s = e.create_session("tpch")
    r = e.execute_sql(Q_POINT, s)
    bp = e.buffer_pool
    key = ("result", "fp-under-test", (), False, False, ())
    epoch = bp.epoch
    bp.clear()  # the concurrent invalidation
    assert bp.put_result(key, r, epoch=epoch) is False
    assert bp.info()["result_entries"] == 0
    # the CURRENT epoch stores fine (and with no epoch = unguarded callers)
    assert bp.put_result(key, r, epoch=bp.epoch) is True
    e._invalidate()


def test_errored_queries_never_cache(monkeypatch):
    e = _engine(monkeypatch)
    s = e.create_session("tpch")
    e.execute_sql(Q_AGG, s)  # plan + compile + store
    e.buffer_pool.clear()
    with faults.injected("point=dispatch,action=error,nth=1"):
        with pytest.raises(faults.InjectedFaultError):
            e.execute_sql(Q_AGG, s)
    assert e.buffer_pool.info()["result_entries"] == 0, \
        "an errored query stored a result"
    assert not leak_report(e)
    # the clean rerun re-executes, stores, and the next run serves it
    e.execute_sql(Q_AGG, s)
    e.execute_sql(Q_AGG, s)
    assert e.last_query_counters.result_cache_hits == 1
    e._invalidate()


def test_cluster_coordinator_serves_from_result_cache(monkeypatch, tmp_path):
    """Coordinator-side gating: ClusterCoordinator.execute_sql consults the
    engine's result tier before scheduling any fragment (no live workers
    here, so the cold run degrades to local — the LOOKUP path is identical
    either way)."""
    from trino_tpu.server.cluster import ClusterCoordinator

    e = _engine(monkeypatch)
    coord = ClusterCoordinator(e, str(tmp_path))
    s = e.create_session("tpch")
    r1 = coord.execute_sql(Q_AGG, s)
    r2 = coord.execute_sql(Q_AGG, s)
    _assert_same(r1, r2)
    assert e.buffer_pool.result_hits >= 1
    assert e.last_query_counters.result_cache_hits == 1
    assert coord.last_query_counters.result_cache_hits == 1
    e._invalidate()


def test_explain_analyze_and_metrics_surfaces(monkeypatch):
    from trino_tpu.server.server import CoordinatorServer
    from trino_tpu.sql.planprinter import format_plan
    from trino_tpu.sql import parser as A
    from trino_tpu.sql.frontend import Planner

    e = _engine(monkeypatch)
    s = e.create_session("tpch")
    e.execute_sql(Q_AGG, s)
    e.execute_sql(Q_AGG, s)
    c = e.last_query_counters
    assert c.result_cache_hits == 1
    plan = Planner(e, s).plan_query(A.parse(Q_AGG))
    text = format_plan(plan, counters=c)
    assert "Result cache: 1 hits" in text, text
    # /v1/metrics result series read straight off the pool (no HTTP needed)
    srv = CoordinatorServer(e)
    body = srv._metrics_text()
    assert "trino_tpu_result_cache_hits_total 1" in body
    assert "trino_tpu_result_cache_entries 1" in body
    # system.runtime.queries marks cache-served statements
    rows = e.execute_sql(
        "select query_id, result_cache_hits from system.queries "
        "where result_cache_hits > 0", s).rows()
    assert rows, "no cache-served statement visible in system.queries"
    e._invalidate()


def test_off_by_default_without_env(monkeypatch):
    monkeypatch.delenv("TRINO_TPU_RESULT_CACHE", raising=False)
    monkeypatch.setenv("TRINO_TPU_PAGE_CACHE", "0")
    e = Engine()
    e.register_catalog("tpch", TpchConnector(sf=SF, split_rows=SPLIT_ROWS))
    s = e.create_session("tpch")
    e.execute_sql(Q_POINT, s)
    e.execute_sql(Q_POINT, s)
    c = e.last_query_counters
    # unset env = tier off on EVERY backend: no lookups, no stores — the
    # warm path keeps executing (the benchmark and the budget suite depend on it)
    assert c.result_cache_hits == 0 and c.result_cache_misses == 0
    assert c.device_dispatches > 0
    assert e.buffer_pool.info()["result_entries"] == 0
    e._invalidate()
