"""Query-level profiling: span trees with per-operator device-boundary
attribution, cluster counter flow surfaces, and dispatch-latency histograms.

What round 7 added on top of the round-6 QueryCounters:
- every ``_jit`` dispatch / ``_host`` pull carries a call-site tag and lands
  under the active operator scope -> ``counters.sites`` and the executor's
  per-node ``boundary`` dict (EXPLAIN ANALYZE attribution);
- the engine's Tracer is ACTIVATED per statement, so executor internals emit
  dispatch spans, prefetch-thread spans (explicit cross-thread parent), and
  exchange-segment spans under the query's root span
  (``engine.last_query_trace``, ``GET /v1/query/{id}/trace`` OTLP JSON);
- dispatch wall times feed fixed-bucket histograms (per query + engine
  totals) exported as a proper Prometheus histogram in ``/v1/metrics``.

The SF1 acceptance tests (warm q3 span tree, warm q9 EXPLAIN ANALYZE
attribution) live in tests/test_query_budgets.py with the other SF1 runs;
this module covers the same invariants at test scale plus the HTTP and
format surfaces.
"""

import json
import re
import threading
import time
import urllib.request

import pytest

from trino_tpu.execution.tracing import (LATENCY_BUCKETS_S, LatencyHistogram,
                                         QueryCounters, Tracer, span_dict,
                                         spans_to_otlp)


# ---------------------------------------------------------------- unit layer
def test_tracer_explicit_parent_across_threads():
    """Satellite: thread-local parenting orphaned background-thread spans;
    ``parent=`` carries the query-thread span across explicitly."""
    tr = Tracer()
    out = {}
    with tr.span("root", trace_id="q") as root:
        parent = tr.current()
        assert parent is root

        def worker():
            with tr.span("bg", parent=parent) as s:
                out["trace_id"] = s.trace_id
                out["parent_id"] = s.parent_id

        t = threading.Thread(target=worker)
        t.start()
        t.join()
        # without parent=, the background thread has NO current span -> orphan
        def orphan():
            with tr.span("orphan") as s:
                out["orphan_parent"] = s.parent_id

        t2 = threading.Thread(target=orphan)
        t2.start()
        t2.join()
    assert out["parent_id"] == root.span_id
    assert out["trace_id"] == "q"  # trace id inherited through the parent
    assert out["orphan_parent"] is None
    names = {s.name for s in tr.spans_for("q")}
    assert names == {"root", "bg"}


def test_latency_histogram_buckets_and_quantiles():
    h = LatencyHistogram()
    for v in (0.0002, 0.0002, 0.003, 0.2, 20.0):
        h.record(v)
    d = h.as_dict()
    assert d["count"] == 5 and sum(d["buckets"]) == 5
    assert d["buckets"][-1] == 1  # 20s -> +Inf bucket
    assert h.quantile(0.5) <= 0.005
    assert h.quantile(0.99) == LATENCY_BUCKETS_S[-1]
    # merge_dict (the cluster wire form) preserves totals
    h2 = LatencyHistogram()
    h2.merge_dict(d)
    assert h2.as_dict() == d


def test_counters_dict_roundtrip_and_merge():
    a = QueryCounters()
    a.device_dispatches = 3
    a.host_transfers = 2
    a.host_bytes_pulled = 100
    a.sites["Agg#0/step"] = {"dispatches": 3, "transfers": 0, "bytes": 0}
    a.sites["Sort#1/sort.pull"] = {"dispatches": 0, "transfers": 2,
                                   "bytes": 100}
    a.dispatch_latency.record(0.01)
    b = QueryCounters.from_dict(a.as_dict())
    assert b.as_dict() == a.as_dict()
    b.merge_dict(a.as_dict())
    assert b.device_dispatches == 6
    assert b.sites["Agg#0/step"]["dispatches"] == 6
    assert b.dispatch_latency.total == 2


def test_spans_to_otlp_shape():
    tr = Tracer()
    with tr.span("query", trace_id="qx", sql="select 1"):
        with tr.span("execution"):
            tr.add_completed("dispatch", 0.005, site="stream.page")
    payload = spans_to_otlp(tr.spans_for("qx"))
    spans = payload["resourceSpans"][0]["scopeSpans"][0]["spans"]
    assert {s["name"] for s in spans} == {"query", "execution", "dispatch"}
    by_name = {s["name"]: s for s in spans}
    assert by_name["query"]["parentSpanId"] == ""
    assert by_name["execution"]["parentSpanId"] == \
        by_name["query"]["spanId"]
    assert by_name["dispatch"]["parentSpanId"] == \
        by_name["execution"]["spanId"]
    for s in spans:
        assert re.fullmatch(r"[0-9a-f]{32}", s["traceId"])
        assert re.fullmatch(r"[0-9a-f]{16}", s["spanId"])
        assert int(s["endTimeUnixNano"]) >= int(s["startTimeUnixNano"])
    # dicts (the worker-span wire form) render identically to Span objects
    again = spans_to_otlp([span_dict(s) for s in tr.spans_for("qx")])
    assert again == payload


# ---------------------------------------------------------------- engine layer
QUERY = """select l_returnflag, sum(l_quantity) q, count(*) c
           from lineitem where l_shipdate <= date '1998-09-02'
           group by l_returnflag order by l_returnflag"""


def test_per_site_sums_equal_totals(engine):
    s = engine.create_session("tpch")
    engine.execute_sql(QUERY, s)
    engine.execute_sql(QUERY, s)  # warm
    c = engine.last_query_counters
    assert c.device_dispatches > 0 and c.sites
    assert sum(v["dispatches"] for v in c.sites.values()) \
        == c.device_dispatches
    assert sum(v["transfers"] for v in c.sites.values()) == c.host_transfers
    assert sum(v["bytes"] for v in c.sites.values()) == c.host_bytes_pulled
    # every dispatch was timed into the per-query histogram
    assert c.dispatch_latency.total == c.device_dispatches
    # attribution keys carry the operator scope ("<Op>#<k>/<site>")
    assert any("/" in k and "#" in k.split("/")[0] for k in c.sites)


def test_span_tree_shape_and_parent_integrity(engine):
    s = engine.create_session("tpch")
    # a unique alias makes a fresh plan-cache key: this run is genuinely COLD
    # even on the shared module engine, so the planner span must appear
    engine.execute_sql(QUERY.replace("sum(l_quantity) q", "sum(l_quantity) q0"),
                       s)
    cold = engine.last_query_trace
    cold_names = [sp["name"] for sp in cold["spans"]]
    assert "planner" in cold_names and "query" in cold_names
    engine.execute_sql(QUERY, s)  # ensure the shared-key plan exists
    engine.execute_sql(QUERY, s)  # warm: cached plan, execution span present
    t = engine.last_query_trace
    names = [sp["name"] for sp in t["spans"]]
    assert names.count("query") == 1
    assert "execution" in names
    assert names.count("dispatch") == engine.last_query_counters \
        .device_dispatches
    ids = {sp["span_id"] for sp in t["spans"]}
    roots = [sp for sp in t["spans"] if sp["parent_id"] is None]
    assert len(roots) == 1 and roots[0]["name"] == "query"
    for sp in t["spans"]:
        if sp["parent_id"] is not None:
            assert sp["parent_id"] in ids, sp
        assert sp["end_s"] is not None
    assert t["root_span_s"] > 0


def test_prefetch_spans_parent_across_thread():
    """The coalescing prefetch producer runs on a background thread; its span
    must still parent into the query's tree (explicit parent handoff)."""
    from trino_tpu import Engine
    from trino_tpu.connectors.tpch import TpchConnector

    e = Engine()
    # small splits -> multi-split scan -> the dispatch-coalescing double
    # buffer engages its producer thread
    e.register_catalog("tpch", TpchConnector(sf=0.01, split_rows=1 << 11))
    s = e.create_session("tpch")
    e.execute_sql(QUERY, s)
    e.execute_sql(QUERY, s)
    qid = e.last_query_trace["query_id"]
    # the producer's span closes on ITS thread right after the consumer
    # drains; allow it a beat to land in the tracer
    spans = []
    for _ in range(50):
        spans = e.tracer.spans_for(qid)
        if any(sp.name == "prefetch" for sp in spans):
            break
        time.sleep(0.02)
    prefetch = [sp for sp in spans if sp.name == "prefetch"]
    assert prefetch, [sp.name for sp in spans]
    ids = {sp.span_id for sp in spans}
    for sp in prefetch:
        assert sp.parent_id in ids  # NOT an orphan
        assert sp.attributes.get("pages", 0) > 0
    e._invalidate()


def test_explain_analyze_per_operator_attribution(engine):
    """Per-node [boundary: ...] rows and per-site lines sum to the query's
    counter totals (the small-scale version of the SF1 q9 acceptance test in
    test_query_budgets.py)."""
    r = engine.execute_sql(f"explain analyze {QUERY}",
                           engine.create_session("tpch"))
    text = "\n".join(str(row[0]) for row in r.rows())
    c = engine.last_query_counters
    assert "Device boundary:" in text
    m = re.search(r"Device boundary: (\d+) dispatches, (\d+) host transfers, "
                  r"(\d+) bytes pulled", text)
    assert m, text
    assert (int(m.group(1)), int(m.group(2)), int(m.group(3))) == \
        (c.device_dispatches, c.host_transfers, c.host_bytes_pulled)
    sites = re.findall(r"site (\S+): (\d+) dispatches, (\d+) transfers, "
                       r"(\d+) bytes", text)
    assert sites, text
    assert sum(int(d) for _, d, _t, _b in sites) == c.device_dispatches
    assert sum(int(b) for _, _d, _t, b in sites) == c.host_bytes_pulled
    # per-operator rows on the plan nodes themselves
    op_rows = re.findall(r"\[boundary: (\d+) dispatches, (\d+) transfers, "
                         r"(\d+) bytes\]", text)
    assert op_rows, text


def test_query_completed_event_carries_boundary_profile(engine):
    from trino_tpu.execution.eventlistener import EventListener

    got = []

    class L(EventListener):
        def query_completed(self, event):
            got.append(event)

    listener = L()
    engine.event_listeners.add(listener)
    try:
        s = engine.create_session("tpch")
        engine.execute_sql("select count(*) from nation", s)
        ev = got[-1]
        assert ev.counters is not None
        assert ev.counters["device_dispatches"] > 0
        assert ev.counters["sites"]
        assert ev.root_span_s is not None and ev.root_span_s > 0
        # a statement that executes no plan leaves counters unset
        engine.execute_sql("set session dispatch_batch = 2", s)
        assert got[-1].counters is None
        assert got[-1].root_span_s is not None
    finally:
        engine.event_listeners.listeners.remove(listener)


# ---------------------------------------------------------------- HTTP layer
def _parse_prometheus(body: str) -> dict:
    """Strict-ish Prometheus text-format parse: every sample line must match
    the exposition grammar, every sampled metric must have a # TYPE, label
    values must be quoted/escaped.  Returns {metric: [(labels, value)]}."""
    types, helps, samples = {}, {}, {}
    sample_re = re.compile(
        r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'
        r'(?:\{((?:[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*",?)*)\})?'
        r' (-?(?:\d+\.?\d*(?:[eE][+-]?\d+)?|NaN|[+-]Inf))$')
    for line in body.splitlines():
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            _, _, rest = line.partition("# TYPE ")
            name, kind = rest.split()
            types[name] = kind
            continue
        if line.startswith("# HELP "):
            _, _, rest = line.partition("# HELP ")
            helps[rest.split()[0]] = rest
            continue
        assert not line.startswith("#"), f"unparseable comment: {line!r}"
        m = sample_re.match(line)
        assert m, f"unparseable sample line: {line!r}"
        name = m.group(1)
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        assert name in types or base in types, \
            f"sample {name} has no # TYPE"
        labels = dict(re.findall(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"',
                                 m.group(2) or ""))
        samples.setdefault(name, []).append((labels, float(m.group(3))))
    return {"types": types, "helps": helps, "samples": samples}


@pytest.fixture()
def profiling_server(engine):
    from trino_tpu.server.server import CoordinatorServer

    srv = CoordinatorServer(engine, port=0)
    srv.start()
    yield srv
    srv.stop()


def test_metrics_histogram_passes_format_check(profiling_server, engine):
    from trino_tpu.server import Client

    c = Client(profiling_server.url, catalog="tpch")
    c.execute("select count(*) from nation")
    body = urllib.request.urlopen(
        profiling_server.url + "/v1/metrics", timeout=10).read().decode()
    parsed = _parse_prometheus(body)
    # HELP/TYPE metadata present (satellite: bare counter lines rejected by
    # stricter scrapers)
    assert parsed["types"]["trino_tpu_queries_total"] == "counter"
    assert "trino_tpu_device_dispatches_total" in parsed["helps"]
    # the dispatch-latency histogram: TYPE histogram, cumulative buckets
    # ending at +Inf == _count, _sum present
    assert parsed["types"]["trino_tpu_dispatch_latency_seconds"] == \
        "histogram"
    buckets = parsed["samples"]["trino_tpu_dispatch_latency_seconds_bucket"]
    assert buckets[-1][0]["le"] == "+Inf"
    values = [v for _, v in buckets]
    assert values == sorted(values), "histogram buckets must be cumulative"
    count = parsed["samples"]["trino_tpu_dispatch_latency_seconds_count"][0][1]
    assert buckets[-1][1] == count and count > 0
    assert parsed["samples"]["trino_tpu_dispatch_latency_seconds_sum"][0][1] \
        >= 0
    # per-site series carry escaped label values
    assert any(s[0].get("site")
               for s in parsed["samples"]
               .get("trino_tpu_site_dispatches_total", []))


def test_label_escaping():
    from trino_tpu.server.server import CoordinatorServer

    esc = CoordinatorServer._escape_label
    assert esc('a"b\\c\nd') == 'a\\"b\\\\c\\nd'


def test_trace_endpoint_round_trip(profiling_server, engine):
    from trino_tpu.server import Client

    c = Client(profiling_server.url, catalog="tpch")
    c.execute("select count(*) from region")
    # find the server-side query id (the most recent FINISHED one)
    qs = [q for q in profiling_server.queries.values()
          if q.state == "FINISHED"]
    qid = sorted(qs, key=lambda q: q.created_at)[-1].query_id
    payload = json.loads(urllib.request.urlopen(
        profiling_server.url + f"/v1/query/{qid}/trace",
        timeout=10).read().decode())
    spans = payload["resourceSpans"][0]["scopeSpans"][0]["spans"]
    names = {s["name"] for s in spans}
    assert "query" in names and "dispatch" in names
    roots = [s for s in spans if s["parentSpanId"] == ""]
    assert len(roots) == 1 and roots[0]["name"] == "query"
    # unknown id -> 404
    with pytest.raises(urllib.error.HTTPError) as exc:
        urllib.request.urlopen(
            profiling_server.url + "/v1/query/nope/trace", timeout=10)
    assert exc.value.code == 404


def test_engine_query_id_trace_lookup(profiling_server, engine):
    """The trace endpoint also resolves ENGINE query ids (query_N) straight
    from the live tracer — the embedded-engine escape hatch."""
    s = engine.create_session("tpch")
    engine.execute_sql("select count(*) from region", s)
    qid = engine.last_query_trace["query_id"]
    payload = json.loads(urllib.request.urlopen(
        profiling_server.url + f"/v1/query/{qid}/trace",
        timeout=10).read().decode())
    spans = payload["resourceSpans"][0]["scopeSpans"][0]["spans"]
    assert any(s["name"] == "query" for s in spans)


# ------------------------------------------------- in-flight registry (round 8)
def test_inflight_registry_entry_lifecycle():
    """Entries carry the same "<Op>#<k>/<site>" label the counters' site
    table uses, plus query id / thread / start time, and retire on exit."""
    from trino_tpu.execution import tracing

    reg = tracing.InflightRegistry()
    with tracing.track_inflight(reg), tracing.query_scope("query_77"):
        assert reg.depth() == 0
        with tracing.operator_scope("Aggregate#3", None):
            with tracing.inflight("dispatch", site="dstep"):
                snap = reg.snapshot()
                assert len(snap) == 1 and reg.depth() == 1
                (e,) = snap
                assert e["label"] == "Aggregate#3/dstep"
                assert e["kind"] == "dispatch" and e["site"] == "dstep"
                assert e["op"] == "Aggregate#3"
                assert e["query_id"] == "query_77"
                assert e["thread_id"] == threading.get_ident()
                assert e["elapsed_s"] >= 0
    assert reg.depth() == 0
    # without an op scope the label degrades to the bare site
    tok = reg.enter("host_pull", "agg.pull")
    assert reg.snapshot()[0]["label"] == "agg.pull"
    reg.exit(tok)
    assert reg.depth() == 0


def test_stall_watchdog_fake_clock_report_shape():
    """Fake-clock stall detection: an entry 'aged' past the threshold yields
    a structured report (label, query id, elapsed, stuck thread's stack,
    extra memory info) and a live 'stalled' verdict; it clears on exit."""
    from trino_tpu.execution import tracing

    reg = tracing.InflightRegistry()
    got = []
    wd = tracing.StallWatchdog(registry=reg, stall_s=5.0, kill_s=0,
                               on_stall=got.append,
                               extra_info=lambda: {"memory": [{"pool": "p0"}]})
    assert wd.enabled
    with tracing.track_inflight(reg), tracing.query_scope("query_42"):
        with tracing.operator_scope("HashJoin#2", None):
            with tracing.inflight("dispatch", site="probe.step"):
                now = time.monotonic() + 100.0  # fake clock: entry is 100s old
                report = wd.check(now=now)
                assert report is not None and wd.last_report is report
                assert wd.stalled_now == 1 and got == [report]
                assert wd.verdict(now=now) == ("stalled", 1)
                assert report["threshold_s"] == 5.0
                assert report["inflight_depth"] == 1
                assert report["memory"] == [{"pool": "p0"}]
                (e,) = report["stalled"]
                assert e["label"] == "HashJoin#2/probe.step"
                assert e["query_id"] == "query_42"
                assert e["elapsed_s"] >= 100
                # the stuck thread's live stack is in the report (it is THIS
                # thread, so our own frame must appear)
                assert e["stack"] and "test_stall_watchdog" in e["stack"]
    # entry retired -> healthy again, gauge drops
    assert wd.check(now=time.monotonic() + 100.0) is None
    assert wd.stalled_now == 0
    assert wd.verdict()[0] == "ok"
    # a disabled watchdog (no threshold) never reports
    off = tracing.StallWatchdog(registry=reg, stall_s=0)
    assert not off.enabled and off.check() is None and off.verdict() == ("ok", 0)


def test_slow_dispatch_stall_report_and_status_flip(profiling_server, engine):
    """Acceptance: a deliberately-slowed dispatch (test hook) produces a
    stall report naming the correct "<Op>#<k>/<site>" within one watchdog
    period, and /v1/status reads "stalled" WHILE the dispatch hangs."""
    from trino_tpu.execution import tracing

    wd = engine.stall_watchdog
    saved = (wd.stall_s, wd.poll_s)
    wd.stall_s, wd.poll_s = 0.05, 0.01
    engine.last_stall_report = None
    status_seen = []

    def hook(site):
        # slow only the first two dispatches (enough for >1 watchdog period)
        # and snapshot /v1/status from INSIDE the stall
        if len(status_seen) < 2:
            time.sleep(0.2)
            status_seen.append(json.loads(urllib.request.urlopen(
                profiling_server.url + "/v1/status", timeout=10)
                .read().decode()))

    try:
        s = engine.create_session("tpch")
        # prewarm BEFORE arming the hook: the slowed dispatches must be
        # warm (seen signatures) — a first-seen dispatch is flagged
        # `compiling` and the round-17 compile-aware watchdog would verdict
        # "compiling" instead of producing the stall report this test pins
        engine.execute_sql(QUERY, s)
        wd.start()
        tracing.DISPATCH_TEST_HOOK = hook
        engine.execute_sql(QUERY, s)
    finally:
        tracing.DISPATCH_TEST_HOOK = None
        wd.stop()
        wd.stall_s, wd.poll_s = saved
    report = engine.last_stall_report
    assert report is not None, "watchdog never reported"
    labels = [e["label"] for e in report["stalled"]]
    # the stuck site carries full operator attribution: "<Op>#<k>/<site>"
    assert any("#" in lbl.split("/")[0] and "/" in lbl for lbl in labels), \
        labels
    assert any(e["stack"] for e in report["stalled"])
    assert report.get("memory"), report.keys()
    # the live status surface flipped while the dispatch hung
    st = status_seen[0]
    assert st["health"]["status"] == "stalled"
    assert st["health"]["stalled"] >= 1
    assert any(f["kind"] == "dispatch" for f in st["inflight"])
    # the executing query is visible as RUNNING with its in-flight entries
    running = [q for q in st["queries"] if q["state"] == "RUNNING"]
    assert running and any(q["inflight"] for q in running)
    # after the query finishes the verdict clears (watchdog still enabled at
    # the lowered threshold inside the finally's restore window is fine —
    # recompute against the restored config)
    assert engine.health()["status"] == "ok"


def test_status_endpoint_shape(profiling_server, engine):
    from trino_tpu.server import Client

    Client(profiling_server.url, catalog="tpch").execute(
        "select count(*) from nation")
    st = json.loads(urllib.request.urlopen(
        profiling_server.url + "/v1/status", timeout=10).read().decode())
    assert st["health"]["status"] == "ok"
    assert st["health"]["watchdog"]["enabled"] in (True, False)
    assert isinstance(st["inflight"], list)
    assert isinstance(st["queries"], list)
    # memory pools expose the MemoryPool snapshot dict, labeled
    assert st["memory"], "no executor pools surfaced"
    assert {"pool", "reserved", "max_bytes", "free"} <= set(st["memory"][0])


def test_metrics_stall_memory_and_resource_group_gauges(profiling_server,
                                                        engine):
    """Round-8 satellite: MemoryPool snapshots + resource-group queue depths
    + the stalled/in-flight gauges reach /v1/metrics as labeled gauges."""
    from trino_tpu.server import Client

    Client(profiling_server.url, catalog="tpch").execute(
        "select count(*) from region")
    body = urllib.request.urlopen(
        profiling_server.url + "/v1/metrics", timeout=10).read().decode()
    parsed = _parse_prometheus(body)
    assert parsed["types"]["trino_tpu_stalled_dispatches"] == "gauge"
    assert parsed["samples"]["trino_tpu_stalled_dispatches"][0][1] == 0
    assert parsed["types"]["trino_tpu_inflight_entries"] == "gauge"
    assert parsed["types"]["trino_tpu_memory_reserved_bytes"] == "gauge"
    pools = parsed["samples"]["trino_tpu_memory_reserved_bytes"]
    assert pools and all(lbl.get("pool") for lbl, _ in pools)
    assert parsed["samples"]["trino_tpu_memory_max_bytes"][0][1] > 0
    assert parsed["types"]["trino_tpu_resource_group_running"] == "gauge"
    groups = parsed["samples"]["trino_tpu_resource_group_queued"]
    assert groups and all(lbl.get("group") for lbl, _ in groups)
    # round-16 satellite: the flight-recorder series ride the same strict
    # exposition (records/bytes gauges, lifetime + stitched-span counters)
    assert parsed["types"]["trino_tpu_flight_records"] == "gauge"
    assert parsed["samples"]["trino_tpu_flight_records"][0][1] > 0
    assert parsed["types"]["trino_tpu_flight_spans_total"] == "counter"
    assert parsed["types"]["trino_tpu_flight_worker_spans_total"] == "counter"


def test_runtime_queries_boundary_columns(engine):
    """Round-8 satellite: system.runtime.queries exposes device_dispatches /
    host_bytes_pulled / elapsed_s so a SQL client sees spend without curling
    /v1/metrics."""
    s = engine.create_session("tpch")
    engine.execute_sql("select count(*) from nation", s)
    r = engine.execute_sql(
        "select query_id, state, device_dispatches, host_bytes_pulled, "
        "elapsed_s from system.queries", s)
    rows = r.rows()
    assert rows
    finished = [row for row in rows if row[1] == "FINISHED"
                and row[2] is not None]
    assert finished, rows
    qid, _, dd, hb, elapsed = finished[-1]
    assert dd > 0 and hb > 0 and elapsed > 0


# ------------------------------------------- the statement timeline (PR 25)
def _otlp_spans(url, qid):
    payload = json.loads(urllib.request.urlopen(
        url + f"/v1/query/{qid}/trace", timeout=10).read().decode())
    return payload["resourceSpans"][0]["scopeSpans"][0]["spans"]


def _otlp_s(span):
    return (int(span["endTimeUnixNano"])
            - int(span["startTimeUnixNano"])) / 1e9


def _otlp_attr(span, key):
    return next((list(a["value"].values())[0] for a in span["attributes"]
                 if a["key"] == key), None)


def test_server_phases_are_spans_of_one_trace_and_seconds_counters(
        engine, monkeypatch):
    """One dispatch thread, three statements posted at once: the later ones'
    ``server.queued`` covers the first's run; every phase of a statement is
    a span under the ENGINE's trace id and root span; the totals' seconds
    equal the spans'."""
    from trino_tpu.execution import tracing
    from trino_tpu.server.client import Client
    from trino_tpu.server.server import CoordinatorServer

    s = engine.create_session("tpch")
    engine.execute_sql(QUERY, s)
    engine.execute_sql(QUERY, s)  # warm: the runs below compile nothing
    monkeypatch.setattr(tracing, "DISPATCH_TEST_HOOK",
                        lambda label: time.sleep(0.1))
    srv = CoordinatorServer(engine, port=0, dispatch_threads=1)
    srv.start()
    try:
        before = engine.counters_total.as_dict()
        client = Client(srv.url, catalog="tpch", poll_interval=0.01)
        posted = [client._request(srv.url + "/v1/statement", "POST",
                                  QUERY.encode()) for _ in range(3)]

        def drain(out):
            while "nextUri" in out:
                time.sleep(0.01)
                out = client._request(out["nextUri"])
            assert "error" not in out, out

        threads = [threading.Thread(target=drain, args=(p,)) for p in posted]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        trees, deadline = {}, time.time() + 10
        for p in posted:  # server.encode is recorded just after FINISHED
            while True:
                spans = _otlp_spans(srv.url, p["id"])
                if {"server.encode", "server.deliver"} \
                        <= {sp["name"] for sp in spans}:
                    break
                assert time.time() < deadline, [sp["name"] for sp in spans]
                time.sleep(0.01)
            trees[p["id"]] = spans
            # the engine's id answers with the same tree
            qid = srv.queries[p["id"]].trace["query_id"]
            assert qid.startswith("query_") and qid != p["id"]
            assert len(_otlp_spans(srv.url, qid)) == len(spans)
        after = engine.counters_total.as_dict()
    finally:
        srv.stop()
    sums = {"server.queued": 0.0, "server.encode": 0.0, "server.deliver": 0.0}
    runs = []
    for sid, spans in trees.items():
        by_name = {sp["name"]: sp for sp in spans}
        assert {"server.queued", "query", "executor.checkout",
                "server.encode", "server.deliver"} <= set(by_name)
        assert len({sp["traceId"] for sp in spans}) == 1
        queries = [sp for sp in spans if sp["name"] == "query"]
        roots = [sp for sp in spans if sp["parentSpanId"] == ""]
        assert len(queries) == 1 and roots == queries
        for name in sums:
            assert by_name[name]["parentSpanId"] == queries[0]["spanId"]
            assert _otlp_attr(by_name[name], "server_query_id") == sid
            sums[name] += _otlp_s(by_name[name])
        runs.append((int(queries[0]["startTimeUnixNano"]),
                     _otlp_s(queries[0]), _otlp_s(by_name["server.queued"])))
    runs.sort()
    first_run = runs[0][1]
    assert first_run >= 0.25  # the hook's sleeps
    assert all(queued >= 0.9 * first_run for _, _, queued in runs[1:]), runs
    for name, field in (("server.queued", "queued_s"),
                        ("server.encode", "encode_s"),
                        ("server.deliver", "deliver_wait_s")):
        assert after[field] - before[field] == pytest.approx(
            sums[name], abs=1e-4), (name, sums)


def test_batcher_wait_is_the_queued_members_not_the_leaders(monkeypatch):
    """The leader parked in LEADER_EXIT_HOOK: every queued member's
    ``batch_wait_s`` (and ``batcher.wait`` span) covers the parked time, the
    leader's is 0."""
    from trino_tpu.execution import batcher as BA
    from trino_tpu.execution import tracing

    parked, ready = 0.15, threading.Event()
    monkeypatch.setattr(BA, "LEADER_EXIT_HOOK",
                        lambda key: (ready.wait(30), time.sleep(parked)))
    bt = BA.TemplateBatcher(window_ms=1.0, max_batch=8, enabled=True)
    tr, out = Tracer(), {}

    def run(name):
        with tracing.activate_tracer(tr), tracing.statement_waits() as w, \
                tr.span("query", trace_id=name):
            bt.execute("k", (name,), lambda rt: ("serial", rt),
                       lambda rts: [("batched", rt) for rt in rts])
        out[name] = dict(w)

    lead = threading.Thread(target=run, args=("leader",))
    lead.start()
    while "k" not in bt._lanes:
        time.sleep(0.001)
    members = [threading.Thread(target=run, args=(f"m{i}",))
               for i in range(2)]
    for t in members:
        t.start()
    while len(bt._lanes["k"].queue) < 2:
        time.sleep(0.001)
    ready.set()
    for t in [lead] + members:
        t.join(30)
    assert out["leader"].get("batch_wait_s", 0.0) == 0.0
    assert not [s for s in tr.spans_for("leader") if s.name == "batcher.wait"]
    for name in ("m0", "m1"):
        assert out[name]["batch_wait_s"] >= parked
        spans = [s for s in tr.spans_for(name) if s.name == "batcher.wait"]
        assert sum(s.duration_s for s in spans) == pytest.approx(
            out[name]["batch_wait_s"], abs=1e-4)


def test_executor_checkout_wait_is_counted(engine):
    """Every executor of the pool held: a statement's ``executor_wait_s``
    (snapshot and totals) grows by the time it waited for one."""
    s = engine.create_session("tpch")
    engine.execute_sql(QUERY, s)  # the pool and its semaphore exist
    held = [engine._checkout_executor()
            for _ in range(engine.MAX_CONCURRENT_EXECUTORS)]
    before = engine.counters_total.executor_wait_s
    out = {}

    def run():
        engine.execute_sql(QUERY, s)
        out["snap"] = engine._thread_accounting.snap
        out["trace"] = engine._thread_accounting.trace

    t = threading.Thread(target=run)
    try:
        t.start()
        time.sleep(0.2)
    finally:
        for ex in held:
            engine._release_executor(ex)
    t.join(60)
    assert out["snap"].executor_wait_s >= 0.19
    assert engine.counters_total.executor_wait_s - before == pytest.approx(
        out["snap"].executor_wait_s)
    spans = [sp for sp in out["trace"]["spans"]
             if sp["name"] == "executor.checkout"]
    assert sum(sp["duration_s"] for sp in spans) == pytest.approx(
        out["snap"].executor_wait_s, abs=1e-4)


def test_wall_buckets_fold_into_each_concurrent_statements_counters(engine):
    """Two statements at once: each one's ``wall_*_s`` counters are ITS
    breakdown (they sum to its root span), and the totals grow by both."""
    s = engine.create_session("tpch")
    engine.execute_sql(QUERY, s)
    engine.execute_sql(QUERY, s)  # warm: no compile bucket
    fields = ("wall_plan_s", "wall_split_generation_s", "wall_h2d_s",
              "wall_dispatch_s", "wall_host_pull_s", "wall_unattributed_s")
    before = engine.counters_total.as_dict()
    out = []

    def run():
        engine.execute_sql(QUERY, s)
        out.append((engine._thread_accounting.snap,
                    engine._thread_accounting.trace))

    threads = [threading.Thread(target=run) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert len(out) == 2 and out[0][1]["query_id"] != out[1][1]["query_id"]
    for snap, trace in out:
        # a second executor of the pool may trace its programs anew: the
        # compile bucket is not folded (compile_s has the XLA seconds)
        folded = sum(getattr(snap, f) for f in fields) \
            + trace["wall_breakdown"]["compile"]
        assert folded == pytest.approx(trace["root_span_s"], rel=1e-3)
        assert snap.wall_dispatch_s > 0 and snap.queued_s > 0
    after = engine.counters_total.as_dict()
    for f in fields:
        assert after[f] - before[f] == pytest.approx(
            sum(getattr(snap, f) for snap, _ in out), abs=1e-6)


def test_spans_are_annotations_on_the_profilers_clock(engine, tmp_path):
    """Under a profiler session the statement's spans and dispatches are
    ``trino_tpu:<name>`` events of the host plane, carrying its query id."""
    import glob

    import jax

    s = engine.create_session("tpch")
    engine.execute_sql(QUERY, s)  # warm
    with jax.profiler.trace(str(tmp_path)):
        engine.execute_sql(QUERY, s)
    qid = engine.last_query_trace["query_id"]
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[0]
    data = jax.profiler.ProfileData.from_file(path)
    seen = {}
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("trino_tpu:"):
                        seen.setdefault(ev.name, set()).add(
                            dict(ev.stats).get("query_id"))
    for name in ("trino_tpu:query", "trino_tpu:dispatch",
                 "trino_tpu:host_pull", "trino_tpu:executor.checkout"):
        assert qid in seen.get(name, ()), (name, seen)


@pytest.mark.parametrize("max_finished", [6, 1000])
def test_tracer_indexes_by_trace_and_evicts_whole_traces(max_finished):
    """``spans_for`` returns a trace's spans in finishing order however the
    traces interleave, and the bound drops whole traces, oldest first."""
    tr = Tracer(max_finished=max_finished)
    with tr.span("query", trace_id="a") as ra:
        tr.add_completed("dispatch", 0.001)
        with tr.span("query", trace_id="b"):  # another trace in between
            tr.add_completed("dispatch", 0.001)
        tr.add_completed("dispatch", 0.002)
    for name in ("c", "d"):
        with tr.span("query", trace_id=name):
            tr.add_completed("dispatch", 0.001)
            tr.add_completed("host_pull", 0.001, site="x")
    if max_finished == 1000:
        assert [s.name for s in tr.spans_for("a")] \
            == ["dispatch", "dispatch", "query"]
        assert [s.name for s in tr.spans_for("b")] == ["dispatch", "query"]
        assert tr._count == 11
    else:
        # 11 spans against a bound of 6: traces "a" and "b" went, each whole
        assert tr.spans_for("a") == [] and tr.spans_for("b") == []
        assert [s.name for s in tr.spans_for("c")] \
            == ["dispatch", "host_pull", "query"]
        assert len(tr.spans_for("d")) == 3 and tr._count == 6
    # an explicit end and parent: the server's after-the-fact phases
    late = tr.add_completed("server.encode", 0.5, parent=ra, end_s=100.0)
    assert (late.trace_id, late.parent_id) == ("a", ra.span_id)
    assert (late.start_s, late.end_s) == (99.5, 100.0)
    assert late in tr.spans_for("a")
    tr.clear()
    assert tr._count == 0 and tr.spans_for("c") == []
