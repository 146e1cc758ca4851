"""The memory-pressure escalation ladder (round 11): tiered spill
(HBM -> host RAM -> disk), accounted, observable, fault-injectable and
leak-checked end to end.

Reference models: the spilling operators + MemoryRevokingScheduler +
FileSingleStreamSpiller (byte-identity of spilled vs in-memory execution),
ClusterMemoryManager's rung ordering (evict before kill), and the resource
groups' admission deferral.  The pressure scenario table lives in
execution/chaos_matrix.py (PRESSURE), shared with scripts/chaos.py so the
pinned contract and the on-device capture artifact cannot drift.
"""

import os
import threading
from functools import partial

import pytest

from trino_tpu import Engine
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.exec.local_executor import LocalExecutor
from trino_tpu.execution import faults
from trino_tpu.execution.bufferpool import DeviceBufferPool
from trino_tpu.execution.chaos_matrix import (PRESSURE, PRESSURE_QUERY,
                                              QUERIES, run_pressure_scenario)
from trino_tpu.execution.chaos_matrix import result_signature as _sig
from trino_tpu.memory import MemoryPool
from trino_tpu.sql.frontend import compile_sql

SF = 0.02
SPLIT_ROWS = 1 << 12


@pytest.fixture(scope="module")
def env():
    engine = Engine()
    engine.register_catalog("tpch",
                            TpchConnector(sf=SF, split_rows=SPLIT_ROWS))
    session = engine.create_session("tpch")
    plan = compile_sql(PRESSURE_QUERY, engine, session)
    # unconstrained baseline: a default-budget executor, same plan object
    base_ex = LocalExecutor(engine.catalogs)
    baseline = _sig(base_ex.execute(plan))
    yield engine, session, plan, baseline
    engine._invalidate()


# ----------------------------------------------------------- pressure matrix
@pytest.mark.parametrize("name", [s[0] for s in PRESSURE])
def test_pressure_scenario(env, name, tmp_path):
    """The chaos pressure matrix (acceptance): every forced tier is
    byte-identical to the unconstrained run, injected spill faults yield
    typed errors, and the extended leak check (spill files, "spill"-tag
    reservations, executor-held spills) passes after every scenario."""
    engine, _session, plan, baseline = env
    cfg, spec, kind = next((c, sp, k) for n, c, sp, k in PRESSURE
                           if n == name)
    scratch = tmp_path / "spill"
    scratch.mkdir()
    rec = run_pressure_scenario(partial(LocalExecutor, engine.catalogs), plan,
                                baseline, name, cfg, spec, kind, str(scratch))
    assert rec["ok"], rec


def test_forced_tiers_report_on_counters(env, tmp_path):
    """Tier forcing is visible, not just correct: the disk-forced run's
    per-query counters carry spilled_bytes attributed to the disk tier and
    zero to the others."""
    engine, _session, plan, _baseline = env
    scratch = tmp_path / "spill"
    scratch.mkdir()
    prev = os.environ.get("TRINO_TPU_SPILL_HOST_BYTES")
    os.environ["TRINO_TPU_SPILL_HOST_BYTES"] = "0"
    os.environ["TRINO_TPU_SPILL_DIR"] = str(scratch)
    try:
        ex = LocalExecutor(engine.catalogs,
                           memory_pool=MemoryPool(max_bytes=1 << 19),
                           buffer_pool=DeviceBufferPool(budget_bytes=0))
        ex.execute(plan)
        c = ex.counters
        assert c.spill_tier_disk > 0
        assert c.spill_tier_hbm == 0 and c.spill_tier_host == 0
        assert c.spilled_bytes == c.spill_tier_disk
        # site attribution: the spill landed under a named site
        assert any("spill.disk" in k for k in c.sites), sorted(c.sites)
        assert not os.listdir(scratch), "spill files survived the query"
    finally:
        os.environ.pop("TRINO_TPU_SPILL_DIR", None)
        if prev is None:
            os.environ.pop("TRINO_TPU_SPILL_HOST_BYTES", None)
        else:
            os.environ["TRINO_TPU_SPILL_HOST_BYTES"] = prev


def test_partitioned_join_spill_tiers_identity(env, tmp_path):
    """The Grace join's build+probe spill walks the same ladder: a tiny pool
    forces the partitioned join, results match the unconstrained run, tier
    stats land on the plan stats, and per-query host-tier reservations
    release (the persistent build side keeps its own "spill-build" tag)."""
    engine, session, _plan, _baseline = env
    os.environ["TRINO_TPU_SPILL_DIR"] = str(tmp_path)
    try:
        sql = """select o_orderpriority, count(*) c from orders, lineitem
                 where o_orderkey = l_orderkey group by o_orderpriority
                 order by o_orderpriority"""
        plan = compile_sql(sql, engine, session)
        full = _sig(LocalExecutor(engine.catalogs).execute(plan))
        ex = LocalExecutor(engine.catalogs,
                           memory_pool=MemoryPool(max_bytes=200_000))
        got = _sig(ex.execute(plan))
        assert got == full
        spilled = [st for st in ex.stats.values()
                   if st.get("spill_partitions")]
        assert spilled and any(st.get("spill_tiers") for st in spilled)
        ex.close_producers()
        tags = ex.memory_pool.info()["by_tag"]
        assert tags.get("spill", 0) == 0, tags
        # the PERSISTENT build spill may keep its disk partitions (it lives
        # with the cached stream, like the build cache; deliberately
        # unaccounted in the pool — plan-lifetime reservations would pin
        # blocked() true forever); evicting the plan's compiled artifacts —
        # the designed eviction path, since jax's global jit caches pin the
        # closure graph past any del/gc — must reclaim its files with it
        ex.forget_plan(plan)
        assert not ex._spills
        assert not [f for f in os.listdir(tmp_path)], \
            "build spill files survived forget_plan"
    finally:
        os.environ.pop("TRINO_TPU_SPILL_DIR", None)


def test_spill_error_mid_partition_cleans_up(env, tmp_path):
    """An error raised MID-SPILL (second disk chunk) unwinds clean: typed
    error, no orphaned file, no stranded reservation — the executor's
    exit-path sweep, not the consumer's finally, is what guarantees it when
    the traceback pins the generator frames."""
    engine, _session, plan, _baseline = env
    os.environ["TRINO_TPU_SPILL_DIR"] = str(tmp_path)
    os.environ["TRINO_TPU_SPILL_HOST_BYTES"] = "0"
    try:
        ex = LocalExecutor(engine.catalogs,
                           memory_pool=MemoryPool(max_bytes=1 << 19))
        with faults.injected(
                "point=spill_write,site=spill.disk,action=error,nth=3"
        ) as fplan:
            with pytest.raises(faults.InjectedFaultError):
                ex.execute(plan)
        assert fplan.total_fires() == 1
        ex.close_producers()
        assert not ex._spills
        assert ex.memory_pool.info()["by_tag"].get("spill", 0) == 0
        assert not os.listdir(tmp_path), "orphaned spill file"
    finally:
        os.environ.pop("TRINO_TPU_SPILL_DIR", None)
        os.environ.pop("TRINO_TPU_SPILL_HOST_BYTES", None)


# ------------------------------------------------------ observability surface
def test_explain_and_metrics_carry_spill_line(tmp_path):
    """Observability satellite: the EXPLAIN ANALYZE rendering grows a Spill
    line (+ per-node tier breakdown) when and only when the query spilled,
    and /v1/metrics exports the per-tier counters + the admission-queue
    counter once a spilling query ran through the engine."""
    import re

    from trino_tpu.server.server import CoordinatorServer
    from trino_tpu.sql.planprinter import format_plan

    os.environ["TRINO_TPU_SPILL_DIR"] = str(tmp_path)
    try:
        engine = Engine()
        engine.register_catalog(
            "tpch", TpchConnector(sf=SF, split_rows=SPLIT_ROWS))
        session = engine.create_session("tpch")
        plan = compile_sql(PRESSURE_QUERY, engine, session)
        # unconstrained: no Spill line
        ex = LocalExecutor(engine.catalogs)
        ex.execute(plan)
        text = format_plan(plan, ex.stats, counters=ex.counters,
                           boundary=ex.boundary)
        assert "Spill:" not in text and "[tiers:" not in text
        # spilled: the line + the per-node tier breakdown render
        ex = LocalExecutor(engine.catalogs,
                           memory_pool=MemoryPool(max_bytes=1 << 19))
        ex.execute(plan)
        text = format_plan(plan, ex.stats, counters=ex.counters,
                           boundary=ex.boundary)
        assert "Spill:" in text and "bytes" in text, text
        assert "[spilled:" in text and "[tiers:" in text, text
        # engine path: shrink the POOLED executors so a plain statement
        # spills, then scrape the metrics endpoint
        engine.execute_sql("select count(*) from nation", session)
        for pooled in engine._all_executors:
            pooled.memory_pool.max_bytes = 1 << 19
        engine.execute_sql(PRESSURE_QUERY, session)
        c = engine.last_query_counters
        assert c.spilled_bytes > 0
        mtext = CoordinatorServer(engine)._metrics_text()
        assert "# TYPE trino_tpu_spilled_bytes_total counter" in mtext
        m = {t: int(v) for t, v in re.findall(
            r'^trino_tpu_spilled_bytes_total\{tier="(\w+)"\} (\d+)$',
            mtext, re.M)}
        assert set(m) == {"hbm", "host", "disk"}
        assert sum(m.values()) >= c.spilled_bytes
        assert re.search(r"^trino_tpu_admission_queued_total \d+$", mtext,
                         re.M)
        engine._invalidate()
    finally:
        os.environ.pop("TRINO_TPU_SPILL_DIR", None)


# ---------------------------------------------------- admission (queue rung)
def test_admission_gate_queues_then_drains():
    """ResourceGroupManager's memory gate: with work running and the gate
    blocked, new submissions QUEUE (and the memory-queued callback fires);
    finish() re-drains once the gate clears; an idle tree always admits
    (no deadlock)."""
    from trino_tpu.execution.resourcegroups import (ResourceGroup,
                                                    ResourceGroupManager)

    blocked = {"v": False}
    mgr = ResourceGroupManager(admission_gate=lambda: not blocked["v"])
    g = mgr.get_or_create("global.alice")
    started, mem_queued = [], []
    # idle tree + blocked gate: still admits (nothing running to drain it)
    blocked["v"] = True
    mgr.submit(g, lambda: started.append("q1"),
               queued_on_memory=lambda: mem_queued.append("q1"))
    assert started == ["q1"] and not mem_queued
    # running + blocked: defer, and attribute the deferral to memory
    mgr.submit(g, lambda: started.append("q2"),
               queued_on_memory=lambda: mem_queued.append("q2"))
    assert started == ["q1"] and mem_queued == ["q2"]
    assert mgr.memory_queued_total == 1
    # finish with the gate still blocked: q1 was the last runner, so the
    # tree is idle and the drain admits q2 anyway (progress guarantee)
    mgr.finish(g)
    assert started == ["q1", "q2"]
    mgr.finish(g)


def test_engine_defers_admission_under_pool_pressure():
    """Engine-level rung: with an executor pool blocked and a query running,
    a second statement queues (admission_queued lands on its counters and
    the engine totals) and completes once the pressure clears."""
    import time

    from trino_tpu.execution.memory_killer import BLOCKED_FRACTION

    engine = Engine()
    engine.register_catalog("tpch",
                            TpchConnector(sf=0.01, split_rows=1 << 11))
    session = engine.create_session("tpch")
    engine.execute_sql("select count(*) from nation", session)  # warm pool
    before = engine.counters_total.admission_queued
    ex = engine._all_executors[0]
    hog = int(ex.memory_pool.max_bytes * (BLOCKED_FRACTION + 0.05))
    assert ex.memory_pool.try_reserve(hog, "test-hog")
    group = engine.resource_groups.get_or_create("global.holder")
    engine.resource_groups.submit(group, lambda: None)  # a "running" query
    try:
        done = {}

        def run():
            done["r"] = engine.execute_sql(
                "select count(*) from nation", session)

        t = threading.Thread(target=run)
        t.start()
        # the statement must be QUEUED, not running: give it a beat
        deadline = time.time() + 5
        while time.time() < deadline \
                and engine.resource_groups.memory_queued_total == 0:
            time.sleep(0.01)
        assert engine.resource_groups.memory_queued_total == 1
        assert "r" not in done
        # pressure clears -> the holder finishes -> the queue drains
        ex.memory_pool.free(hog, "test-hog")
        engine.resource_groups.finish(group)
        t.join(timeout=30)
        assert not t.is_alive() and len(done["r"]) == 1
        assert engine.counters_total.admission_queued == before + 1
        assert engine.last_query_counters.admission_queued == 1
    finally:
        engine._invalidate()


# ------------------------------------------------- cluster rungs (pre-kill)
def test_coordinator_walks_evict_rung_before_kill(tmp_path):
    """The cluster killer's ladder order: a blocked node gets one debounce
    beat, then a cache-evict request, and only on the THIRD consecutive
    blocked pass does the policy pick a victim — with both rungs recorded
    (pressure_events order, per-query rung for the victim)."""
    from trino_tpu.server.cluster import ClusterCoordinator

    coord = ClusterCoordinator(Engine(), str(tmp_path / "spool"))
    coord._announce("w0", "http://127.0.0.1:1")  # unreachable: posts no-op
    w = coord.workers["w0"]
    w.mem_reserved, w.mem_max = 95, 100
    w.mem_by_query = {"hog": 90}
    coord._run_memory_killer()  # streak 1: debounce
    assert coord.oom_kills == 0 and not coord.pressure_events
    coord._run_memory_killer()  # streak 2: evict rung
    assert coord.oom_kills == 0
    assert [e["rung"] for e in coord.pressure_events] == ["evict-cache"]
    coord._run_memory_killer()  # streak 3: kill rung
    assert coord.oom_kills == 1 and coord.last_oom_victim == "hog"
    assert [e["rung"] for e in coord.pressure_events] == \
        ["evict-cache", "kill"]
    assert coord.query_pressure_rung["hog"] == "kill"
    # recovery resets the ladder
    w.mem_reserved = 10
    coord._run_memory_killer()
    assert coord._blocked_streak == 0


def test_worker_sheds_cache_then_refuses(tmp_path):
    """Worker admission rung: a memory-blocked worker evicts its buffer
    pool, counts the denial, and refuses the task (the coordinator
    re-offers elsewhere)."""
    from trino_tpu.server.cluster import WorkerServer, _WorkerBusy

    w = WorkerServer({"tpch": {"connector": "tpch", "sf": 0.01}},
                     str(tmp_path / "spool"))
    w.fragments["f0"] = object()  # never executed: admission refuses first
    w.memory_pool.reserved = int(w.memory_pool.max_bytes * 0.95)
    with pytest.raises(_WorkerBusy):
        w._start_task({"task_id": "t0", "fragment_id": "f0"})
    assert w.admission_denials == 1
    w.memory_pool.reserved = 0


# ----------------------------------------------------------- counters plumb
def test_spill_counters_merge_and_roundtrip():
    """The new fields ride every counter flow: merge, dict round-trip (the
    worker->coordinator wire shape), and snapshot."""
    from trino_tpu.execution.tracing import QueryCounters, record_spill, \
        track_counters

    c = QueryCounters()
    with track_counters(c):
        record_spill("host", 100)
        record_spill("disk", 50)
    assert (c.spilled_bytes, c.spill_tier_host, c.spill_tier_disk) == \
        (150, 100, 50)
    assert any(v.get("spilled_bytes") for v in c.sites.values())
    d = QueryCounters.from_dict(c.as_dict())
    assert d.spilled_bytes == 150 and d.spill_tier_disk == 50
    m = QueryCounters()
    m.merge(c)
    m.merge(d)
    assert m.spilled_bytes == 300 and m.spill_tier_host == 200
    m.admission_queued += 1
    assert QueryCounters.from_dict(m.as_dict()).admission_queued == 1


# ------------------------------------------------------------------ at scale
@pytest.mark.slow
def test_q18_crosses_all_tiers_byte_identical(tmp_path):
    """Acceptance at real shape: TPC-H q18 (SF0.1) with the pool forced down
    and tiny tier budgets crosses hbm AND host AND disk in one query, and
    the result is byte-identical to the unconstrained run."""
    engine = Engine()
    engine.register_catalog("tpch",
                            TpchConnector(sf=0.1, split_rows=1 << 16))
    session = engine.create_session("tpch")
    plan = compile_sql(QUERIES["q18"], engine, session)
    baseline = _sig(LocalExecutor(engine.catalogs).execute(plan))
    os.environ["TRINO_TPU_SPILL_DIR"] = str(tmp_path)
    os.environ["TRINO_TPU_SPILL_HOST_BYTES"] = str(96 << 10)
    try:
        ex = LocalExecutor(engine.catalogs,
                           memory_pool=MemoryPool(max_bytes=1 << 20),
                           buffer_pool=DeviceBufferPool(
                               budget_bytes=128 << 10))
        got = _sig(ex.execute(plan))
        assert got == baseline
        c = ex.counters
        assert c.spill_tier_hbm > 0, c.as_dict()
        assert c.spill_tier_host > 0, c.as_dict()
        assert c.spill_tier_disk > 0, c.as_dict()
        ex.close_producers()
        assert ex.memory_pool.info()["by_tag"].get("spill", 0) == 0
        # the partitioned join's persistent build spill lives with the
        # compiled stream; evicting the plan reclaims its files too
        ex.forget_plan(plan)
        assert not os.listdir(tmp_path)
    finally:
        os.environ.pop("TRINO_TPU_SPILL_DIR", None)
        os.environ.pop("TRINO_TPU_SPILL_HOST_BYTES", None)
        engine._invalidate()
