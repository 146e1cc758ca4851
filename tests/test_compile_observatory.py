"""Compile observatory (round 17): per-compilation attribution at the _jit
chokepoint, compile-aware stall verdicts, and the executable cost census.

What this pins:
- cold/warm detection — a first-seen ABSTRACT arg signature per _jit wrapper
  records one compile (counters.compiles / compile_s, site-attributed); a
  warm re-execution records ZERO (the recompile-regression guard — the SF1
  version lives in tests/test_query_budgets.py);
- wall attribution — the "compile" bucket outranks device_dispatch, so a
  cold statement's wall names compilation instead of inflating the dispatch
  bucket, and buckets still sum to wall by construction;
- compile-aware stall verdicts — a compiling in-flight entry past STALL_S
  but under TRINO_TPU_STALL_COMPILE_S verdicts "compiling" (no stall
  report, no worker degradation); past the compile threshold it is a
  genuine wedge and reports stalled;
- the census — CompileLog ring + recompile-storm detection, surfaced via
  system.runtime.compilations, GET /v1/compiles, /v1/metrics (strict
  Prometheus parse), EXPLAIN ANALYZE's "Compile:" line, and flight records.
"""

import json
import time
import urllib.request

import pytest

from trino_tpu.execution import tracing
from trino_tpu.execution.tracing import (COMPILE_LOG, CompileLog,
                                         QueryCounters, StallWatchdog,
                                         arg_signature, signature_summary)

QUERY = """select l_returnflag, sum(l_quantity) q, count(*) c
           from lineitem where l_shipdate <= date '1998-09-02'
           group by l_returnflag order by l_returnflag"""


# ---------------------------------------------------------------- unit layer
def test_arg_signature_distinguishes_shapes_dtypes_and_statics():
    import numpy as np

    k1 = arg_signature((np.zeros((4,), np.int64),))
    k2 = arg_signature((np.zeros((8,), np.int64),))   # shape differs
    k3 = arg_signature((np.zeros((4,), np.float64),))  # dtype differs
    k4 = arg_signature((np.zeros((4,), np.int64), 7))  # static differs
    k5 = arg_signature((np.zeros((4,), np.int64), 8))
    assert len({k1, k2, k3, k4, k5}) == 5
    k1b = arg_signature((np.ones((4,), np.int64),))  # values don't matter
    assert k1 == k1b
    # the printable form renders lazily FROM the key (cold path only)
    assert "int64[4]" in signature_summary(k1)
    assert "7" in signature_summary(k4)
    # pytree STRUCTURE is part of the key (same leaves, different nesting)
    ka = arg_signature(((np.zeros((2,)), np.zeros((2,))),))
    kb = arg_signature((np.zeros((2,)), np.zeros((2,))))
    assert ka != kb


def test_counters_carry_compiles_and_roundtrip():
    a = QueryCounters()
    a.compiles = 2
    a.compile_s = 1.25
    a.sites["Agg#0/step"] = {"dispatches": 1, "transfers": 0, "bytes": 0,
                             "compiles": 2, "compile_s": 1.25}
    b = QueryCounters.from_dict(a.as_dict())
    assert b.compiles == 2 and b.compile_s == pytest.approx(1.25)
    assert b.sites["Agg#0/step"]["compile_s"] == pytest.approx(1.25)
    b.merge(a)
    assert b.compiles == 4 and b.compile_s == pytest.approx(2.5)


def test_jit_wrapper_detects_first_seen_signatures():
    """Two distinct shapes through ONE wrapper = two compiles; repeats of a
    seen shape = zero more.  Detection is a host-side set lookup — the
    dispatch count keeps counting every invocation."""
    import jax.numpy as jnp

    from trino_tpu.exec.boundary import _jit

    f = _jit(lambda x: x * 2 + 1, site="obs.test")
    c = QueryCounters()
    with tracing.track_counters(c):
        f(jnp.arange(8))
        f(jnp.arange(8))   # warm
        f(jnp.arange(16))  # new shape -> compile
        f(jnp.arange(16))  # warm
    assert c.compiles == 2, c.as_dict()
    assert c.device_dispatches == 4
    assert c.compile_s > 0
    assert c.sites["obs.test"]["compiles"] == 2


def test_failed_first_seen_dispatch_does_not_poison_seen():
    """A first-seen dispatch that RAISES (injected fault, transient device
    error) records no compile and leaves the signature unseen — the retry
    is the run that really compiles, and it must still be flagged
    `compiling` or a tight STALL_S would read the legit compile as a wedge
    (the footgun this round retires)."""
    import jax.numpy as jnp

    from trino_tpu.exec.boundary import _jit

    f = _jit(lambda x: x + 1, site="obs.fail")
    c = QueryCounters()
    fired = {"n": 0}

    def hook(label):
        if label == "obs.fail" and fired["n"] == 0:
            fired["n"] += 1
            raise RuntimeError("injected")

    tracing.DISPATCH_TEST_HOOK = hook
    try:
        with tracing.track_counters(c):
            with pytest.raises(RuntimeError):
                f(jnp.arange(4))
            assert c.compiles == 0  # failure: nothing recorded, not seen
            f(jnp.arange(4))  # the retry pays (and records) THE compile
            assert c.compiles == 1
            f(jnp.arange(4))  # now genuinely warm
            assert c.compiles == 1
    finally:
        tracing.DISPATCH_TEST_HOOK = None


def test_compile_log_storm_detection(caplog):
    import logging

    cl = CompileLog(max_records=16, storm_sigs=3)
    with caplog.at_level(logging.WARNING, logger="trino_tpu.stall"):
        for i in range(5):
            cl.record(site="probe.step", label="HashJoin#2/probe.step",
                      query_id="q1", signature=f"int64[{i}]",
                      sig_key=f"s{i}", duration_s=0.01)
        # a second site under threshold never storms
        cl.record(site="other", label="Agg#0/other", query_id="q1",
                  signature="int64[1]", sig_key="t0", duration_s=0.01)
    info = cl.info()
    assert info["compiles_total"] == 6
    assert info["storms_total"] == 1
    assert info["stormed_labels"] == ["HashJoin#2/probe.step"]
    storms = [r for r in caplog.records if "recompile storm" in r.message]
    assert len(storms) == 1  # warned ONCE per storm, not per compile
    assert "HashJoin#2/probe.step" in storms[0].getMessage()
    # a DIFFERENT statement's compiles at the same site count in their own
    # key (storms are per execution — cross-query shape diversity through
    # module-level wrappers is legitimate, not churn)
    cl.record(site="probe.step", label="HashJoin#2/probe.step",
              query_id="q2", signature="int64[0]", sig_key="s0",
              duration_s=0.01)
    assert cl.info()["storms_total"] == 1
    assert len(cl.for_query("q2")) == 1
    # the histogram rides the compile bucket scale
    assert cl.latency.total == 7


def test_watchdog_compile_aware_verdicts():
    """Fake clock: a compiling entry past stall_s but under compile_stall_s
    verdicts "compiling" with NO stall report; past compile_stall_s it is a
    genuine wedge; a non-compiling entry stalls at stall_s as before."""
    reg = tracing.InflightRegistry()
    got = []
    wd = StallWatchdog(registry=reg, stall_s=5.0, compile_stall_s=200.0,
                       kill_s=0, on_stall=got.append)
    with tracing.track_inflight(reg), tracing.query_scope("q7"):
        tok = reg.enter("dispatch", "agg.step", compiling=True)
        try:
            now = time.monotonic() + 100.0  # 100s old: over stall, under compile
            assert wd.verdict(now=now) == ("compiling", 1)
            assert wd.check(now=now) is None and got == []
            assert wd.compiling_now == 1 and wd.stalled_now == 0
            now = time.monotonic() + 300.0  # past compile threshold: wedged
            assert wd.verdict(now=now) == ("stalled", 1)
            report = wd.check(now=now)
            assert report is not None and got == [report]
            assert report["stalled"][0]["compiling"] is True
        finally:
            reg.exit(tok)
        # non-compiling entry: stalls at stall_s exactly as before round 17
        tok = reg.enter("dispatch", "probe.step")
        try:
            now = time.monotonic() + 10.0
            assert wd.verdict(now=now) == ("stalled", 1)
        finally:
            reg.exit(tok)
    assert wd.verdict()[0] == "ok"


def test_watchdog_compile_threshold_defaults_to_10x():
    wd = StallWatchdog(registry=tracing.InflightRegistry(), stall_s=3.0)
    assert wd.compile_stall_s == pytest.approx(30.0)


def test_coordinator_does_not_degrade_compiling_worker(tmp_path):
    """The acceptance bit the round-8 footgun was about: a worker whose
    health verdict is "compiling" keeps receiving work (not degraded, stays
    in live_workers); "stalled" still gates it out."""
    from trino_tpu import Engine
    from trino_tpu.server.cluster import ClusterCoordinator

    coord = ClusterCoordinator(Engine(), spool_dir=str(tmp_path))
    # no coord.start(): _announce + live_workers are plain methods
    coord._announce("w1", "http://127.0.0.1:1", health="compiling")
    coord._announce("w2", "http://127.0.0.1:2", health="stalled")
    coord._announce("w3", "http://127.0.0.1:3", health="ok")
    by_id = {w.node_id: w for w in coord.workers.values()}
    assert not by_id["w1"].degraded
    assert by_id["w2"].degraded
    assert {w.node_id for w in coord.live_workers()} == {"w1", "w3"}


# -------------------------------------------------------------- engine layer
@pytest.fixture(scope="module")
def obs_engine(tpch_sf001):
    """A FRESH engine: the module needs genuinely cold executions (the
    shared session `engine` fixture is warm from other modules)."""
    from trino_tpu import Engine

    e = Engine()
    e.register_catalog("tpch", tpch_sf001)
    yield e
    e._invalidate()


def test_cold_then_warm_compile_split_and_wall_attribution(obs_engine):
    """Acceptance (test scale; SF1 lives in test_query_budgets): the cold
    run records compiles and its wall_breakdown charges more to `compile`
    than to `device_dispatch`; the warm run records ZERO compiles and no
    compile bucket; buckets sum to wall within the structural 5%."""
    from trino_tpu.execution.tracing import WALL_BUCKETS

    s = obs_engine.create_session("tpch")
    obs_engine.execute_sql(QUERY, s)
    cold = obs_engine.last_query_counters
    cold_bd = obs_engine.last_query_trace.get("wall_breakdown")
    assert cold.compiles > 0 and cold.compile_s > 0
    assert cold_bd and cold_bd["compile"] > 0
    # compilation, not execution, is the named cost of a cold statement
    assert cold_bd["compile"] > cold_bd["device_dispatch"]
    total = sum(cold_bd[b] for b in WALL_BUCKETS)
    assert abs(total - cold_bd["wall_s"]) <= 0.05 * cold_bd["wall_s"]
    # per-site sums equal the totals (the attribution invariant extends)
    assert sum(v.get("compiles", 0) for v in cold.sites.values()) \
        == cold.compiles
    obs_engine.execute_sql(QUERY, s)
    warm = obs_engine.last_query_counters
    warm_bd = obs_engine.last_query_trace.get("wall_breakdown")
    assert warm.compiles == 0 and warm.compile_s == 0.0
    assert warm_bd["compile"] == 0.0
    total = sum(warm_bd[b] for b in WALL_BUCKETS)
    assert abs(total - warm_bd["wall_s"]) <= 0.05 * warm_bd["wall_s"]


def test_flight_record_carries_compile_census(obs_engine):
    s = obs_engine.create_session("tpch")
    sql = "select count(*) from orders where o_orderkey > 7"
    obs_engine.execute_sql(sql, s)
    qid = obs_engine.last_query_trace["query_id"]
    n = obs_engine.last_query_counters.compiles
    assert n > 0
    rec = obs_engine.flight_recorder.get(qid)
    assert rec is not None
    assert rec["compiles"] == n
    assert rec["compile_s"] > 0
    events = rec["compile_events"]
    assert events and all(e["query_id"] == qid for e in events)
    assert sum(1 for _ in events) == n
    assert all(e.get("signature") for e in events)


def test_explain_analyze_compile_line(obs_engine):
    """EXPLAIN ANALYZE runs a throwaway executor (fresh _jit wrappers), so
    its counters always include the run's compiles — the "Compile:" line is
    deterministic there."""
    import re

    s = obs_engine.create_session("tpch")
    r = obs_engine.execute_sql(
        "explain analyze select count(*) from nation", s)
    text = "\n".join(str(row[0]) for row in r.rows())
    m = re.search(r"Compile: (\d+) compilations, ([0-9.]+)s", text)
    assert m, text
    assert int(m.group(1)) > 0


def test_system_runtime_compilations_table(obs_engine):
    s = obs_engine.create_session("tpch")
    obs_engine.execute_sql(QUERY, s)  # ensure census rows exist
    r = obs_engine.execute_sql(
        "select site, label, query_id, signature, duration_s "
        "from system.compilations", s)
    rows = r.rows()
    assert rows
    sites = {row[0] for row in rows}
    assert any(site for site in sites)
    # rows mirror the engine's census ring (the scan itself may compile and
    # append, so subset — every retained record has a positive duration)
    assert all(row[4] is None or row[4] >= 0 for row in rows)
    labels = {row[1] for row in rows}
    assert any("/" in (l or "") for l in labels)  # "<Op>#<k>/<site>" form


# ---------------------------------------------------------------- HTTP layer
@pytest.fixture()
def obs_server(obs_engine):
    from trino_tpu.server.server import CoordinatorServer

    srv = CoordinatorServer(obs_engine, port=0)
    srv.start()
    yield srv
    srv.stop()


def test_v1_compiles_endpoint(obs_server, obs_engine):
    s = obs_engine.create_session("tpch")
    obs_engine.execute_sql(QUERY, s)
    payload = json.loads(urllib.request.urlopen(
        obs_server.url + "/v1/compiles", timeout=10).read().decode())
    assert payload["info"]["compiles_total"] > 0
    assert payload["info"]["storm_threshold_sigs"] > 0
    recs = payload["records"]
    assert recs
    for r in recs[:5]:
        assert {"site", "label", "query_id", "signature", "duration_s",
                "exe_bytes", "at"} <= set(r)


def test_metrics_compile_series_strict_parse(obs_server, obs_engine):
    from test_profiling import _parse_prometheus

    s = obs_engine.create_session("tpch")
    obs_engine.execute_sql("select count(*) from region", s)
    body = urllib.request.urlopen(
        obs_server.url + "/v1/metrics", timeout=10).read().decode()
    parsed = _parse_prometheus(body)
    assert parsed["types"]["trino_tpu_compiles_total"] == "counter"
    assert parsed["samples"]["trino_tpu_compiles_total"][0][1] > 0
    assert parsed["types"]["trino_tpu_recompile_storms_total"] == "counter"
    assert parsed["types"]["trino_tpu_compiling_dispatches"] == "gauge"
    assert parsed["samples"]["trino_tpu_compiling_dispatches"][0][1] == 0
    assert parsed["types"]["trino_tpu_compile_seconds"] == "histogram"
    buckets = parsed["samples"]["trino_tpu_compile_seconds_bucket"]
    assert buckets[-1][0].get("le") == "+Inf"
    counts = [v for _, v in buckets]
    assert counts == sorted(counts)  # cumulative
    assert counts[-1] == parsed["samples"][
        "trino_tpu_compile_seconds_count"][0][1]
    assert parsed["samples"]["trino_tpu_compile_seconds_sum"][0][1] > 0


def test_status_health_reports_compiling(obs_server, obs_engine):
    """/v1/status health flips to "compiling" (NOT "stalled", no stall
    report) while a compiling in-flight entry ages past STALL_S but under
    the compile threshold — live, via the registry, no watchdog thread."""
    wd = obs_engine.stall_watchdog
    saved = (wd.stall_s, wd.compile_stall_s)
    wd.stall_s, wd.compile_stall_s = 0.05, 60.0
    tok = obs_engine.inflight.enter("dispatch", "obs.compile",
                                    compiling=True)
    try:
        time.sleep(0.1)
        st = json.loads(urllib.request.urlopen(
            obs_server.url + "/v1/status", timeout=10).read().decode())
        assert st["health"]["status"] == "compiling"
        assert st["health"]["compiling"] >= 1
        assert st["health"]["stalled"] == 0
        entries = [e for e in st["inflight"] if e["site"] == "obs.compile"]
        assert entries and entries[0]["compiling"] is True
    finally:
        obs_engine.inflight.exit(tok)
        wd.stall_s, wd.compile_stall_s = saved
    assert obs_engine.health()["status"] == "ok"


def test_query_log_compile_columns(obs_engine):
    s = obs_engine.create_session("tpch")
    obs_engine.execute_sql(QUERY, s)
    qid = obs_engine.last_query_trace["query_id"]
    r = obs_engine.execute_sql(
        "select query_id, compiles, compile_s from system.query_log", s)
    rows = {row[0]: row for row in r.rows()}
    assert qid in rows
    # the module's first QUERY execution was cold: its record carries the
    # compiles it paid; this (warm) re-execution's record will carry 0
    assert rows[qid][1] is not None


# ------------------------------------------ device program names (PR 25)
@pytest.mark.parametrize("site,module", [
    ("join.probe", "jit_join_probe"),
    ("agg.sorted.select", "jit_agg_sorted_select"),
    (None, "jit_named_step"),
])
def test_jit_site_is_the_device_programs_name(site, module):
    """The XLA module is named after the STATIC site and its ops carry the
    site as a scope; the operator label of the plan is in neither, so two
    plans lower one site to the same text (one compile-cache key)."""
    import jax.numpy as jnp

    from trino_tpu.exec.boundary import _jit

    def named_step(x, k=2):
        return jnp.cumsum(x) * k

    def make():
        if site is None:
            return _jit(named_step)
        return _jit(lambda x, k=2: jnp.cumsum(x) * k, site=site)

    x = jnp.arange(8.0)
    texts = []
    for op in ("Aggregate#3", "Join#5"):
        run = make()
        with tracing.operator_scope(op):
            lowered = run.lower(x)
            assert float(run(x)[-1]) == 56.0
        text = lowered.as_text()
        assert f"module @{module} " in text
        assert op not in lowered.as_text(debug_info=True)
        scope = site or "named_step"
        assert f"/{scope}/" in lowered.as_text(debug_info=True)
        assert f"/{scope}/" in lowered.compile().as_text()
        texts.append(text)
    assert texts[0] == texts[1]
    # callers run the step eagerly through __wrapped__: the python function
    assert run.__wrapped__(x)[-1] == 56.0
    assert run.__wrapped__.__name__ in ("<lambda>", "named_step")


@pytest.mark.parametrize("connector", ["tpch", "tpcds"])
def test_generator_programs_are_named_after_their_table(connector):
    import importlib

    mod = importlib.import_module(f"trino_tpu.connectors.{connector}")
    conn = getattr(mod, connector.capitalize() + "Connector")(sf=0.01)
    table = "nation" if connector == "tpch" else "reason"
    split = conn.splits(table)[0]
    page = conn.generate(split)
    assert page.capacity == split.hi - split.lo
    program = mod._GENERATE_PROGRAMS[table]
    assert set(mod._GENERATE_PROGRAMS) >= {table}
    if connector == "tpch":
        lowered = program.lower(conn.sf, split.lo, split.hi - split.lo,
                                conn.table_bound(table),
                                tuple(mod.TPCH_SCHEMAS[table].names))
    else:
        lowered = program.lower(conn.sf, split.lo, split.hi - split.lo,
                                tuple(mod.SCHEMAS[table].names), 0)
    assert f"module @jit_generate_{table} " in lowered.as_text()
    assert f"/generate.{table}/" in lowered.as_text(debug_info=True)


def test_compiles_are_requests_and_cache_misses_are_compilations(tmp_path):
    """``compiles`` counts first-seen signatures at a wrapper, whoever serves
    the executable; ``compile_cache_misses`` counts what XLA really compiled:
    over a filled persistent cache a new wrapper is one request, no miss."""
    import jax
    import jax.numpy as jnp
    from jax._src import compilation_cache

    from trino_tpu.exec.boundary import _jit

    def make():
        return _jit(lambda x: jnp.cumsum(jnp.sin(x)) * 3.0,
                    site="cache.miss.probe")

    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    compilation_cache.reset_cache()
    try:
        x = jnp.arange(1000.0)
        readings = []
        for _ in range(2):
            run, c = make(), QueryCounters()
            with tracing.track_counters(c):
                run(x)
                run(x)  # the same signature again: neither
            readings.append((c.compiles, c.compile_cache_misses,
                             c.device_dispatches))
            jax.clear_caches()  # the in-process executables go, the files stay
        assert readings == [(1, 1, 2), (1, 0, 2)]
        assert any("cache_miss_probe" in f.name for f in tmp_path.iterdir())
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        compilation_cache.reset_cache()
    a = QueryCounters()
    a.compile_cache_misses = 2
    b = QueryCounters.from_dict(a.as_dict())
    b.merge(a)
    assert b.compile_cache_misses == 4 and b.snapshot().as_dict() == b.as_dict()
