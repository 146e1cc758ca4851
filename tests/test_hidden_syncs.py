"""Every wait of the host on the device, and every launch, passes a named chokepoint
(PR 38).

A dynamic census, because a static lint cannot tell ``bool(x)`` of a device scalar from
``bool(x)`` of a host one: ``ArrayImpl._value`` is the one place a jax array becomes a
host value (``bool()``, ``int()``, ``float()``, ``.item()``, ``np.asarray``,
``jax.device_get`` all read it), so it is wrapped, and a read whose stack holds a frame
under ``trino_tpu/`` but not ``exec.boundary._host`` is a hidden sync: no ``host_pull``
span, no ``host_transfers`` count, no in-flight entry for the stall watchdog, no fault
point.  (The CPU backend ignores ``jax.transfer_guard_device_to_host``, so the guard
cannot do this here.)  A warm replay of the benchmark's statements makes none.

Beside it: the connector's generator launches are counted and compile-captured where
the executor calls them (``_generate``), the prefetch queue's wait is a span and a
bucket (``scan.wait`` / ``scan_wait``), the buckets still sum to the wall, and the
unattributed remainder says under which container span it sits.
"""

import pathlib
import sys
import threading
import time

import pytest
from jax._src import array as jax_array

import trino_tpu
from benchmark.statements import ds_q51, ds_q93, q1, q3, q9, q18
from trino_tpu import Engine
from trino_tpu.connectors.tpcds import TpcdsConnector
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.exec import boundary
from trino_tpu.execution import faults, tracing
from trino_tpu.execution.bufferpool import DeviceBufferPool
from trino_tpu.execution.tracing import WALL_BUCKETS
from trino_tpu.parallel.mesh import worker_mesh

ROOT = str(pathlib.Path(trino_tpu.__file__).resolve().parent)
HOST_FILE = str(pathlib.Path(boundary.__file__).resolve())


class avg_of_sums:
    """(PR 44) A group-by over a group-by's one page: the outer one reads its keys'
    bounds off that page, through ``_host`` like every other read.  (TPC-DS q65 has the
    shape, but its multi-match join reads a count outside ``_host``: ROADMAP.)"""

    VALIDATION = {}

    @staticmethod
    def render(p):
        return ("select l_returnflag, k, avg(q) a from (select l_returnflag, "
                "l_suppkey % 5 k, l_linestatus, sum(l_quantity) q from lineitem "
                "group by l_returnflag, l_suppkey % 5, l_linestatus) x "
                "group by l_returnflag, k order by l_returnflag, k"), None


STATEMENTS = {"q1": q1, "q3": q3, "q18": q18, "q9": q9, "ds_q93": ds_q93,
              "ds_q51": ds_q51, "avg_of_sums": avg_of_sums}
# lineitem in 13 splits at SF0.01, so that a scan is prefetched and coalesced
SPLIT_ROWS = 1 << 13


def sql_of(name):
    statement = STATEMENTS[name]
    return statement.render(statement.VALIDATION)[0]


class Census:
    """While open, every device-to-host read made from under ``trino_tpu/`` outside
    ``_host`` is listed in ``hidden`` as "file:line function"."""

    def __init__(self):
        self.hidden = []
        self._prop = jax_array.ArrayImpl.__dict__["_value"]

    def _read(self, array):
        if array._npy_value is None:  # a value the host does not hold yet
            frames, f = [], sys._getframe(1)
            while f is not None:
                frames.append(f)
                f = f.f_back
            ours = [f for f in frames if f.f_code.co_filename.startswith(ROOT)]
            if ours and not any(f.f_code.co_name == "_host"
                                and f.f_code.co_filename == HOST_FILE for f in ours):
                f = ours[0]  # the innermost frame of the program
                self.hidden.append(
                    f"{f.f_code.co_filename[len(ROOT) + 1:]}:{f.f_lineno} "
                    f"{f.f_code.co_name}")
        return self._prop.fget(array)

    def __enter__(self):
        jax_array.ArrayImpl._value = property(self._read)
        return self

    def __exit__(self, *exc):
        jax_array.ArrayImpl._value = self._prop


@pytest.fixture(scope="module")
def engines():
    """name -> (engine, catalog): ``Engine()`` over TPC-H and TPC-DS at SF0.01, and an
    engine on ``worker_mesh(4)`` of the suite's host devices with a page cache, as the
    mesh cell has."""
    plain = Engine()
    plain.register_catalog("tpch", TpchConnector(sf=0.01, split_rows=SPLIT_ROWS))
    plain.register_catalog("tpcds", TpcdsConnector(sf=0.01, split_rows=1 << 15))
    mesh = Engine(mesh=worker_mesh(4))
    mesh.buffer_pool = DeviceBufferPool(budget_bytes=1 << 30)
    mesh.register_catalog("tpch", TpchConnector(sf=0.01, split_rows=SPLIT_ROWS))
    yield {"plain": plain, "mesh": mesh}
    for engine in (plain, mesh):
        engine._invalidate()


CASES = [("plain", "tpch", "q1"), ("plain", "tpch", "q3"), ("plain", "tpch", "q18"),
         ("plain", "tpch", "q9"), ("plain", "tpcds", "ds_q93"),
         # (PR 42) the window operator adds no read to those its child already makes
         ("plain", "tpcds", "ds_q51"),
         # (PR 44) a group-by over a group-by reads its keys' bounds through `_host`
         ("plain", "tpch", "avg_of_sums"),
         ("mesh", "tpch", "q1"), ("mesh", "tpch", "q3")]


@pytest.mark.parametrize("where,catalog,name", CASES,
                         ids=[f"{w}-{n}" for w, _, n in CASES])
def test_a_warm_replay_reads_no_device_value_outside_host(engines, where, catalog, name):
    engine = engines[where]
    session = engine.create_session(catalog)
    for _ in range(3):  # cold, the advisor's re-plan or the learned bucket, warm
        engine.execute_sql(sql_of(name), session)
    with Census() as census:
        engine.execute_sql(sql_of(name), session)
    assert census.hidden == [], census.hidden
    counters = engine.last_query_counters
    assert counters.compiles == 0, counters.compiles  # it WAS a warm replay
    assert counters.host_transfers > 0
    # only one of them groups a blocking child's one page, and it reads the bounds once
    bounds = [v for k, v in counters.sites.items() if k.endswith("/agg.key_bounds")]
    assert [(v["dispatches"], v["transfers"]) for v in bounds] == \
        ([(1, 1)] if name == "avg_of_sums" else [])
    assert counters.groupby_observed_direct == len(bounds)


def test_a_planted_bool_of_a_device_scalar_is_caught(engines, monkeypatch):
    """The census is not blind: the sync this PR took out, planted again (PR 39: where
    the group-by's finalize reads its one pull, the count now rides with the flag)."""
    real = tracing.record_compaction

    def planted(lanes_in, lanes_out):
        import jax.numpy as jnp

        bool(jnp.zeros(()) > 0)  # what `if not bool(state.overflow)` was
        return real(lanes_in, lanes_out)

    monkeypatch.setattr(tracing, "record_compaction", planted)
    engine = engines["plain"]
    session = engine.create_session("tpch")
    with Census() as census:
        engine.execute_sql(sql_of("q1"), session)
    # named by the program's innermost frame: the line that called the planted sync
    assert len(census.hidden) == 1 and "_finalize_groups" in census.hidden[0], census.hidden


def test_the_group_bys_syncs_are_host_pull_spans_with_their_sites(engines):
    engine = engines["plain"]
    session = engine.create_session("tpch")
    for _ in range(2):  # (a replay, whichever test of the module met the engine first)
        engine.execute_sql(sql_of("q1"), session)
    pulls = [s["attributes"].get("site") for s in engine.last_query_trace["spans"]
             if s["name"] == "host_pull"]
    # PR 39: a replay reads the flag, the group count and the envelope flag in ONE
    # pull behind the finalize's program, and its history record holds host ints only
    assert pulls == ["agg.direct.overflow", "sort.pull", "page"], pulls
    sites = engine.last_query_counters.sites
    assert sum(v["transfers"] for v in sites.values()) \
        == engine.last_query_counters.host_transfers == len(pulls)
    for _ in range(3):  # until the plan has proven a capacity, every page reads the flag
        engine.execute_sql(sql_of("q3"), session)
    pulls = [s["attributes"].get("site") for s in engine.last_query_trace["spans"]
             if s["name"] == "host_pull"]
    assert pulls.count("agg.hash.overflow") == 2, pulls  # drain's chunk, then the loop's end


def cached_engine():
    engine = Engine()
    engine.buffer_pool = DeviceBufferPool(budget_bytes=1 << 30)
    conn = TpchConnector(sf=0.01, split_rows=SPLIT_ROWS)
    engine.register_catalog("tpch", conn)
    return engine, conn


def test_generator_dispatches_are_the_splits_of_a_missed_scan_and_zero_on_a_hit():
    engine, conn = cached_engine()
    session = engine.create_session("tpch")
    splits = len(conn.splits("lineitem"))
    assert splits > 2
    engine.execute_sql(sql_of("q1"), session)  # the page cache misses: every split
    cold = engine.last_query_counters
    assert cold.page_cache_misses == 1 and cold.generator_dispatches == splits
    spans = engine.tracer.spans_for(engine.last_query_trace["query_id"])
    warm = {s.span_id for s in spans if s.name == "generate.warm"}  # the connector's
    # warm thread launched once more, for the compile: a span, not a count
    generated = [s for s in spans if s.name == "generate" and s.parent_id not in warm]
    assert len(generated) == splits
    assert {s.attributes["site"] for s in generated} == {"generate.lineitem"}
    # the producer's launches hang under its prefetch span, the warm-up pair under the
    # statement's thread; not one is an orphan
    ids = {s.span_id for s in spans}
    assert all(s.parent_id in ids for s in generated)
    prefetch = [s for s in spans if s.name == "prefetch"]
    assert prefetch and all("put_wait_s" in s.attributes and "cpu_s" in s.attributes
                            for s in prefetch)
    assert sum(s.parent_id == prefetch[0].span_id for s in generated) == splits - 2
    # NOT a device dispatch of the executor: its ceilings keep their meaning
    assert sum(v["dispatches"] for v in cold.sites.values()) == cold.device_dispatches
    engine.execute_sql(sql_of("q1"), session)  # served from the resident page
    warm = engine.last_query_counters
    assert warm.page_cache_hits == 1 and warm.generator_dispatches == 0
    assert engine.counters_total.generator_dispatches >= splits


def test_explain_analyze_prints_the_generator_launches_and_where_the_remainder_sits():
    engine = Engine()
    conn = TpchConnector(sf=0.01, split_rows=SPLIT_ROWS)
    engine.register_catalog("tpch", conn)
    session = engine.create_session("tpch")
    engine.execute_sql(sql_of("q1"), session)
    res = engine.execute_sql("explain analyze " + sql_of("q1"), session)
    text = "\n".join(str(r[0]) for r in res.rows())
    splits = len(conn.splits("lineitem"))
    assert f", {splits} generator launches" in text, text
    line = next(ln for ln in text.split("\n") if ln.startswith("Wall breakdown:"))
    # the wait for the device is asserted by its counter and its span: the line prints
    # a bucket only when it does not round to nothing, which the host's speed decides
    assert engine.last_query_counters.host_transfers > 0
    pulls = [s for s in engine.tracer.spans_for(engine.last_query_trace["query_id"])
             if s.name == "host_pull"]
    assert any(s.attributes.get("site") == "agg.direct.overflow" for s in pulls), pulls
    if "[" in line:  # the remainder is over 5 % of the wall: its containers are named
        assert "aggregate.direct" in line or "execution" in line, line


def test_a_generators_first_launch_is_a_compile_event_of_its_site():
    # a length no other test generates at, so that this process has not compiled it
    engine = Engine()
    engine.register_catalog("tpch", TpchConnector(sf=0.01, split_rows=(1 << 12) + 24))
    session = engine.create_session("tpch")
    def events():
        return [r for r in tracing.COMPILE_LOG.snapshot()
                if r.get("site") == "generate.orders"
                and r.get("query_id") == engine.last_query_trace["query_id"]]

    engine.execute_sql("select count(*), sum(o_totalprice) from orders", session)
    # the first launch is the connector's warm thread's (TpchConnector.warm_scan), through
    # the executor's chokepoint: it may end a moment after the statement
    for _ in range(100):
        if events():
            break
        time.sleep(0.02)
    assert events() and all(r["duration_s"] > 0 for r in events()), events()
    spans = engine.tracer.spans_for(engine.last_query_trace["query_id"])
    assert any(s.name == "compile" and s.attributes.get("site") == "generate.orders"
               for s in spans)
    # the warm launch is no scan source's: the count is the scan's splits
    assert engine.last_query_counters.generator_dispatches \
        == len(engine.catalogs["tpch"].splits("orders"))
    engine.execute_sql("select count(*), sum(o_totalprice) from orders", session)
    assert engine.last_query_counters.generator_dispatches > 0
    assert engine.last_query_counters.compiles == 0 and not events()


def test_scan_wait_and_every_bucket_sum_to_the_wall_and_the_remainder_is_placed():
    engine = Engine()
    engine.register_catalog("tpch", TpchConnector(sf=0.01, split_rows=SPLIT_ROWS))
    session = engine.create_session("tpch")
    for _ in range(2):
        engine.execute_sql(sql_of("q1"), session)
    trace = engine.last_query_trace
    bd = trace["wall_breakdown"]
    waits = [s for s in trace["spans"] if s["name"] == "scan.wait"]
    assert waits and all(s["attributes"]["table"] == "lineitem" for s in waits)
    assert "scan_wait" in WALL_BUCKETS and bd["scan_wait"] >= 0.0
    assert sum(bd[b] for b in WALL_BUCKETS) == pytest.approx(bd["wall_s"], abs=1e-4)
    where = bd["unattributed_by"]
    assert sum(where.values()) == pytest.approx(bd["unattributed"], abs=1e-4)
    assert set(where) <= {"query", "execution", "aggregate.direct",
                          "executor.checkout"}, where
    counters = engine.last_query_counters
    assert counters.wall_scan_wait_s == pytest.approx(bd["scan_wait"])
    assert counters.wall_unattributed_s == pytest.approx(bd["unattributed"])
    # CPU seconds of the statement's thread, beside its wall
    root = next(s for s in trace["spans"] if s["name"] == "query")
    assert 0.0 < counters.host_cpu_s <= root["duration_s"] * 1.5 + 0.05
    assert root["attributes"]["cpu_s"] == pytest.approx(counters.host_cpu_s, abs=1e-5)


def test_the_sweep_places_the_remainder_under_the_innermost_open_container():
    """Hand-made spans: the arithmetic of ``unattributed_by``, with a wait that
    outranks the generation it overlaps."""
    def span(name, start, end, sid, parent=None, **attributes):
        return {"name": name, "trace_id": "t", "span_id": sid, "parent_id": parent,
                "start_s": start, "end_s": end, "attributes": attributes}

    spans = [span("query", 0.0, 10.0, 1),
             span("execution", 1.0, 9.0, 2, 1),
             span("aggregate.hash", 2.0, 8.0, 3, 2),
             span("prefetch", 2.0, 7.0, 4, 3),        # h2d, the lowest priority
             span("generate", 2.0, 3.0, 5, 4),        # split_generation
             span("scan.wait", 2.5, 3.5, 6, 3),       # outranks both
             span("host_pull", 7.0, 7.5, 7, 3),
             span("h2d", 8.0, 9.0, 8, 2)]             # no such span is opened: no bucket
    bd = tracing.wall_breakdown(spans)
    assert bd["scan_wait"] == pytest.approx(1.0)
    assert bd["split_generation"] == pytest.approx(0.5)   # 2.0-2.5
    assert bd["h2d"] == pytest.approx(3.5)                # 3.5-7.0
    assert bd["host_pull"] == pytest.approx(0.5)
    assert bd["unattributed"] == pytest.approx(4.5)
    assert bd["unattributed_by"] == pytest.approx(
        {"query": 2.0, "execution": 2.0, "aggregate.hash": 0.5})
    assert sum(bd[b] for b in WALL_BUCKETS) == pytest.approx(bd["wall_s"])
    # backoff sleeps come out of the remainder and out of its placement alike
    bd = tracing.wall_breakdown(spans, retry_backoff_s=1.0)
    assert bd["retry_backoff"] == 1.0 and bd["unattributed"] == pytest.approx(3.5)
    assert sum(bd["unattributed_by"].values()) == pytest.approx(3.5)
    line = tracing.format_wall_breakdown(bd)
    assert "scan_wait 1000.0ms" in line and "[" in line and "execution 2000.0ms" in line


def test_a_hang_at_the_direct_group_bys_sync_shows_in_the_inflight_registry(engines):
    """What ``bool(state.overflow)`` could not show the stall watchdog."""
    engine = engines["plain"]
    session = engine.create_session("tpch")
    engine.execute_sql(sql_of("q1"), session)
    seen = []
    done = threading.Event()

    def watch():
        while not done.is_set():
            for entry in tracing.INFLIGHT.snapshot():
                if entry["kind"] == "host_pull" \
                        and entry["site"] == "agg.direct.overflow":
                    seen.append(entry)
                    return
            time.sleep(0.01)

    watcher = threading.Thread(target=watch)
    watcher.start()
    try:
        with faults.injected("point=host_pull,site=agg.direct.overflow,"
                             "action=delay,s=1.5,times=1"):
            engine.execute_sql(sql_of("q1"), session)
    finally:
        done.set()
        watcher.join()
    assert seen and seen[0]["label"].endswith("agg.direct.overflow"), seen
    assert seen[0]["query_id"] == engine.last_query_trace["query_id"]
    assert engine.last_query_counters.faults_injected == 1


def test_the_served_surfaces_carry_the_new_series_and_the_remainders_placement():
    """``/v1/metrics`` and ``GET /v1/query/{id}``, once each."""
    import json
    import urllib.request

    from trino_tpu.server.client import Client
    from trino_tpu.server.server import CoordinatorServer

    engine = Engine()
    engine.register_catalog("tpch", TpchConnector(sf=0.01, split_rows=SPLIT_ROWS))
    server = CoordinatorServer(engine, port=0)
    server.start()
    try:
        Client(server.url, catalog="tpch").execute(sql_of("q1"))
        body = urllib.request.urlopen(server.url + "/v1/metrics").read().decode()
        values = {}
        for series in ('trino_tpu_wall_seconds_total{bucket="scan_wait"}',
                       "trino_tpu_generator_dispatches_total",
                       "trino_tpu_host_cpu_seconds_total"):
            lines = [ln for ln in body.splitlines() if ln.startswith(series + " ")]
            assert len(lines) == 1, (series, lines)
            values[series] = float(lines[0].split()[-1])
        assert values["trino_tpu_generator_dispatches_total"] \
            == engine.counters_total.generator_dispatches > 0
        assert values["trino_tpu_host_cpu_seconds_total"] > 0
        qid = list(server.queries)[-1]
        info = json.loads(urllib.request.urlopen(f"{server.url}/v1/query/{qid}").read())
        bd = info["wallBreakdown"]
        assert sum(bd["unattributed_by"].values()) == pytest.approx(bd["unattributed"],
                                                                     abs=1e-4)
        assert sum(bd[b] for b in WALL_BUCKETS) == pytest.approx(bd["wall_s"], abs=1e-4)
    finally:
        server.stop()
