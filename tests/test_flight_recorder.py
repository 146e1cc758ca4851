"""Flight recorder, stitched traces, and wall-clock decomposition (round 16).

The tentpole's three pieces and their contracts:

- ``execution/flightrecorder.FlightRecorder`` — one record per completed OR
  errored statement (counters, span tree, wall breakdown, plan-actuals),
  in-memory ring always, on-disk JSONL ring under TRINO_TPU_FLIGHT_DIR with
  byte-budget eviction, readable from a DEAD process's directory; appended
  under cache-store guard discipline (a recorder failure never fails the
  query; zero device work — test_query_budgets pins the ceilings with the
  recorder ENABLED).
- stitched distributed traces — the coordinator propagates the query's trace
  id + root-span id through /v1/task, worker task spans ship back and
  re-parent under the query root: ONE OTLP tree per distributed query.
- ``tracing.wall_breakdown`` — the span tree decomposed into named wall
  buckets (plan / split generation / h2d / device dispatch / host pull /
  exchange wait / admission queue / retry backoff / unattributed) that sum
  to the reported wall by construction.
"""

import json
import os
import time
import urllib.error
import urllib.request

import pytest

from trino_tpu.execution.flightrecorder import (FlightRecorder,
                                                pressure_rung,
                                                read_flight_dir)
from trino_tpu.execution.tracing import (WALL_BUCKETS, format_wall_breakdown,
                                         wall_breakdown)

QUERY = """select l_returnflag, sum(l_quantity) q, count(*) c
           from lineitem where l_shipdate <= date '1998-09-02'
           group by l_returnflag order by l_returnflag"""


# ---------------------------------------------------------------- unit layer
def _span(name, start, end, span_id=None, parent=None, trace="q"):
    return {"name": name, "trace_id": trace, "span_id": span_id or id(name),
            "parent_id": parent, "start_s": start, "end_s": end,
            "attributes": {}, "status": "OK"}


def test_wall_breakdown_buckets_and_sum():
    """Disjoint sweep attribution: overlapped background staging never
    double-counts against foreground dispatch, and every bucket plus the
    unattributed remainder sums to the wall exactly."""
    spans = [
        _span("query", 0.0, 10.0, span_id=1),
        _span("planner", 0.5, 1.5, span_id=2, parent=1),
        _span("dispatch", 2.0, 5.0, span_id=3, parent=1),
        # h2d prefetch fully overlapping the dispatch: the slice charges to
        # the dispatch (foreground), the non-overlapped tail to h2d
        _span("prefetch", 4.0, 6.0, span_id=4, parent=1),
        _span("host_pull", 7.0, 8.0, span_id=5, parent=1),
    ]
    bd = wall_breakdown(spans, queued_s=0.25)
    assert bd["plan"] == pytest.approx(1.0)
    assert bd["device_dispatch"] == pytest.approx(3.0)
    assert bd["h2d"] == pytest.approx(1.0)  # only the 5.0-6.0 tail
    assert bd["host_pull"] == pytest.approx(1.0)
    assert bd["admission_queue"] == pytest.approx(0.25)
    assert bd["unattributed"] == pytest.approx(4.0)
    assert bd["wall_s"] == pytest.approx(10.25)
    total = sum(bd[b] for b in WALL_BUCKETS)
    assert total == pytest.approx(bd["wall_s"], rel=1e-6)
    # explicit-window form (EXPLAIN ANALYZE): clipped + summed the same way
    bd2 = wall_breakdown(spans, window=(2.0, 6.0))
    assert bd2["device_dispatch"] == pytest.approx(3.0)
    assert bd2["plan"] == 0.0
    assert bd2["wall_s"] == pytest.approx(4.0)
    # no closed root span and no window -> no breakdown (never fabricated)
    assert wall_breakdown([_span("dispatch", 0, 1)]) is None
    line = format_wall_breakdown(bd)
    assert line.startswith("Wall breakdown:") and "device_dispatch" in line


def test_pressure_rung_derivation():
    assert pressure_rung(None) is None
    assert pressure_rung({"admission_queued": 1}) == "admission-queue"
    assert pressure_rung({"spill_tier_hbm": 10}) == "spill-hbm"
    assert pressure_rung({"spill_tier_hbm": 1, "spill_tier_disk": 2}) \
        == "spill-disk"


def test_recorder_ring_eviction_and_dead_process_readback(tmp_path):
    """Tiny byte budget: the disk ring stays bounded, oldest records evict,
    the newest survives even when one record alone exceeds the budget — and
    a FRESH reader (the dead-process post-mortem path) sees exactly what is
    on disk, skipping a torn tail."""
    d = str(tmp_path / "flight")
    fr = FlightRecorder(flight_dir=d, disk_budget=4000, max_records=16)
    pad = "x" * 300  # ~400B/record -> eviction after ~10
    for i in range(40):
        fr.record_query({"query_id": f"q{i}", "state": "FINISHED",
                         "sql": pad, "wall_s": 0.1})
    assert fr.disk_evictions > 0
    # bounded: budget + one active segment of slack
    assert fr.disk_bytes() <= 4000 + 4000 // 8 + 600
    recs = read_flight_dir(d)
    assert recs, "nothing readable from the ring"
    ids = [r["query_id"] for r in recs]
    assert "q39" in ids and "q0" not in ids  # newest kept, oldest evicted
    assert ids == sorted(ids, key=lambda q: int(q[1:]))  # oldest-first order
    # torn tail (process died mid-write): skipped, records before it survive
    segs = sorted(p for p in os.listdir(d) if p.endswith(".jsonl"))
    with open(os.path.join(d, segs[-1]), "ab") as f:
        f.write(b'{"query_id": "torn...')
    recs2 = read_flight_dir(d)
    assert [r["query_id"] for r in recs2] == ids
    # in-memory ring independently bounded
    assert len(fr.snapshot()) == 16


def test_record_shape_success_and_error(engine):
    """Completed AND errored statements both land, typed: the errored
    record carries the state machine's error and still has counters/trace."""
    s = engine.create_session("tpch")
    engine.execute_sql(QUERY, s)
    qid = engine.last_query_trace["query_id"]
    rec = engine.flight_recorder.get(qid)
    assert rec is not None and rec["kind"] == "query"
    assert rec["state"] == "FINISHED" and rec["error"] is None
    assert rec["counters"]["device_dispatches"] > 0
    assert rec["counters"]["sites"]
    assert rec["trace"]["spans"] and rec["trace"]["root_span_s"] > 0
    assert rec["sql"].startswith("select")  # normalized text
    bd = rec["wall_breakdown"]
    assert bd and abs(sum(bd[b] for b in WALL_BUCKETS) - bd["wall_s"]) \
        <= 0.05 * bd["wall_s"]
    # errored statement: recorded, typed, state FAILED
    before = engine.flight_recorder.records_total
    with pytest.raises(Exception):
        engine.execute_sql("select no_such_column from lineitem", s)
    recs = engine.flight_recorder.snapshot(kind="query")
    assert engine.flight_recorder.records_total == before + 1
    err = recs[-1]
    assert err["state"] == "FAILED"
    assert err["error"] and "no_such_column" in err["error"]


def test_recorder_failure_never_fails_query(engine):
    """Guard discipline: a recorder that raises (full disk, broken encoder)
    must leave the statement successful — same contract as cache stores."""
    fr = engine.flight_recorder
    orig = fr.record_query
    calls = []

    def boom(rec):
        calls.append(rec)
        raise RuntimeError("disk full")

    fr.record_query = boom
    try:
        res = engine.execute_sql("select count(*) from nation",
                                 engine.create_session("tpch"))
        assert res.rows()[0][0] == 25
        assert calls, "recorder was never consulted"
    finally:
        fr.record_query = orig
    # the recorder's own internal guard counts failures instead of raising
    bad = FlightRecorder(flight_dir="/nonexistent/\0bad", disk_budget=100,
                         max_records=4)
    assert bad.record_query({"query_id": "q", "state": "FINISHED"}) is None
    assert bad.failures == 1


def test_chaos_fatal_injection_record_and_leak_clean():
    """Acceptance: the flight record for an ERRORED (chaos ``fatal``) query
    is present, typed, and the engine passes the chaos leak check after."""
    from trino_tpu import Engine
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.execution import faults
    from trino_tpu.execution.chaos_matrix import leak_report
    from trino_tpu.execution.faults import FatalInjectedFaultError

    e = Engine()
    e.register_catalog("tpch", TpchConnector(sf=0.01, split_rows=1 << 11))
    s = e.create_session("tpch")
    e.execute_sql(QUERY, s)  # warm: the fault hits a compiled dispatch
    with faults.injected("point=dispatch,action=fatal,nth=1"):
        with pytest.raises(FatalInjectedFaultError):
            e.execute_sql(QUERY, s)
    rec = e.flight_recorder.snapshot(kind="query")[-1]
    assert rec["state"] == "FAILED"
    # typed: the record names the injected fault's point/site/rule, the
    # same text the raised FatalInjectedFaultError carried
    assert "injected fatal at dispatch" in (rec["error"] or "")
    assert rec["counters"]["faults_injected"] == 1
    leaks = leak_report(e)
    assert not leaks, leaks
    e._invalidate()


def test_stall_reports_fold_into_recorder(engine):
    """Satellite: StallWatchdog reports append as flight EVENTS (kind=stall)
    through the engine's on_stall hook."""
    report = {"detected_at_s": time.time(), "threshold_s": 1.0,
              "stalled": [{"label": "HashJoin#2/probe.step",
                           "elapsed_s": 9.9}], "inflight_depth": 1}
    before = len(engine.flight_recorder.snapshot(kind="stall"))
    engine._on_stall(dict(report))
    stalls = engine.flight_recorder.snapshot(kind="stall")
    assert len(stalls) == before + 1
    assert stalls[-1]["stalled"][0]["label"] == "HashJoin#2/probe.step"
    assert engine.last_stall_report["threshold_s"] == 1.0


# ------------------------------------------------------------- HTTP surfaces
@pytest.fixture()
def flight_server(engine):
    from trino_tpu.server.server import CoordinatorServer

    srv = CoordinatorServer(engine, port=0)
    srv.start()
    yield srv
    srv.stop()


def test_trace_endpoint_serves_completed_statements_from_recorder(
        flight_server, engine):
    """Satellite: /v1/query/{id}/trace resolves AFTER later statements land
    — served from the flight recorder, not the live-tracer slot (proven by
    clearing the tracer's finished ring before the fetch)."""
    s = engine.create_session("tpch")
    engine.execute_sql(QUERY, s)
    qid = engine.last_query_trace["query_id"]
    engine.execute_sql("select count(*) from region", s)  # a later statement
    engine.tracer.clear()  # the live tracer can no longer serve it
    payload = json.loads(urllib.request.urlopen(
        flight_server.url + f"/v1/query/{qid}/trace", timeout=10)
        .read().decode())
    spans = payload["resourceSpans"][0]["scopeSpans"][0]["spans"]
    names = {sp["name"] for sp in spans}
    assert "query" in names and "dispatch" in names
    roots = [sp for sp in spans if sp["parentSpanId"] == ""]
    assert len(roots) == 1 and roots[0]["name"] == "query"


def test_flight_http_endpoints_and_query_log(flight_server, engine):
    s = engine.create_session("tpch")
    engine.execute_sql(QUERY, s)
    qid = engine.last_query_trace["query_id"]
    idx = json.loads(urllib.request.urlopen(
        flight_server.url + "/v1/flight", timeout=10).read().decode())
    assert idx["info"]["enabled"] and idx["info"]["records"] > 0
    assert any(r["query_id"] == qid for r in idx["records"])
    rec = json.loads(urllib.request.urlopen(
        flight_server.url + f"/v1/flight/{qid}", timeout=10).read().decode())
    assert rec["state"] == "FINISHED" and rec["wall_breakdown"]
    with pytest.raises(urllib.error.HTTPError) as exc:
        urllib.request.urlopen(flight_server.url + "/v1/flight/nope",
                               timeout=10)
    assert exc.value.code == 404
    # system.runtime.query_log: the SQL twin — per-statement counters and
    # flattened breakdown buckets
    r = engine.execute_sql(
        "select query_id, state, device_dispatches, device_dispatch_s, "
        "unattributed_s from system.query_log", s)
    rows = r.rows()
    mine = [row for row in rows if row[0] == qid]
    assert mine, rows[:5]
    assert mine[0][1] == "FINISHED" and mine[0][2] > 0
    assert mine[0][3] is not None and mine[0][4] is not None


def test_metrics_flight_series(flight_server, engine):
    """Satellite: recorder records/bytes gauges + stitched-span counters
    pass the strict Prometheus parse."""
    from test_profiling import _parse_prometheus

    engine.execute_sql("select count(*) from nation",
                       engine.create_session("tpch"))
    body = urllib.request.urlopen(
        flight_server.url + "/v1/metrics", timeout=10).read().decode()
    parsed = _parse_prometheus(body)
    assert parsed["types"]["trino_tpu_flight_records"] == "gauge"
    assert parsed["samples"]["trino_tpu_flight_records"][0][1] > 0
    assert parsed["types"]["trino_tpu_flight_disk_bytes"] == "gauge"
    assert parsed["types"]["trino_tpu_flight_records_total"] == "counter"
    assert parsed["samples"]["trino_tpu_flight_records_total"][0][1] > 0
    assert parsed["types"]["trino_tpu_flight_spans_total"] == "counter"
    assert parsed["samples"]["trino_tpu_flight_spans_total"][0][1] > 0
    assert parsed["types"]["trino_tpu_flight_worker_spans_total"] == "counter"
    assert parsed["types"]["trino_tpu_flight_record_failures_total"] \
        == "counter"


# ---------------------------------------------------------- stitched cluster
def test_in_process_cluster_one_stitched_trace(tmp_path):
    """Acceptance: a distributed query produces ONE stitched OTLP trace —
    every worker task span carries the query's trace id and parents under
    the coordinator's root span; the flight record carries the whole tree."""
    from trino_tpu import Engine
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.execution.tracing import spans_to_otlp
    from trino_tpu.server.cluster import ClusterCoordinator, WorkerServer

    CATALOGS = {"tpch": {"connector": "tpch", "sf": 0.01,
                         "split_rows": 1 << 11}}
    e = Engine()
    e.register_catalog("tpch", TpchConnector(sf=0.01, split_rows=1 << 11))
    coord = ClusterCoordinator(e, str(tmp_path / "spool"),
                               heartbeat_interval=0.2)
    url = coord.start()
    w = WorkerServer(CATALOGS, str(tmp_path / "spool"), coordinator_url=url,
                     node_id="inproc")
    w.start()
    try:
        coord.wait_for_workers(1, timeout=60)
        expected = e.execute_sql(QUERY).rows()
        got = coord.execute_sql(QUERY).rows()
        assert got == expected
        assert coord.local_fallbacks == 0, coord.last_fallback_error
        t = coord.last_query_trace
        qid = t["query_id"]
        spans = t["spans"]
        # ONE trace id across coordinator and workers
        assert {sp["trace_id"] for sp in spans} == {qid}
        roots = [sp for sp in spans if sp["parent_id"] is None]
        assert len(roots) == 1 and roots[0]["name"] == "query"
        # worker task spans present and parented DIRECTLY under the root
        tasks = [sp for sp in spans if sp["name"] == "task"]
        assert tasks, "no worker task spans stitched"
        assert all(sp["parent_id"] == roots[0]["span_id"] for sp in tasks)
        # parent integrity: no orphans anywhere in the stitched tree
        ids = {sp["span_id"] for sp in spans}
        for sp in spans:
            if sp["parent_id"] is not None:
                assert sp["parent_id"] in ids, sp
        assert coord.stitched_spans_total >= len(tasks)
        # the OTLP rendering keeps it one tree under one traceId
        otlp = spans_to_otlp(spans)
        ospans = otlp["resourceSpans"][0]["scopeSpans"][0]["spans"]
        assert len({sp["traceId"] for sp in ospans}) == 1
        # flight record: distributed, stitched span count stamped
        rec = e.flight_recorder.get(qid)
        assert rec is not None and rec.get("distributed")
        assert rec["worker_spans"] >= len(tasks)
        assert rec["trace"]["spans"]
        bd = rec["wall_breakdown"]
        assert bd and abs(sum(bd[b] for b in WALL_BUCKETS) - bd["wall_s"]) \
            <= 0.05 * bd["wall_s"]
        # legacy surface still carries the worker half
        names = {sp["name"] for sp in coord.last_query_worker_spans}
        assert "task" in names and "dispatch" in names
    finally:
        w.stop()
        coord.stop()
        e._invalidate()
