"""The mesh executor that outlives its statement (PR 32), on ``worker_mesh(4)`` of the
suite's eight host devices at SF0.01.

An engine made with ``Engine(mesh=...)`` runs every statement that names neither
``distributed`` nor ``mesh`` (the served path) on ONE kept DistributedExecutor: a replay
of a text asks for no compile, executes no build side and generates no row (its scans are
row-sharded entries of the engine's page cache).  Answers are checked against the pandas
references of ``benchmark/statements`` (which import nothing of ``trino_tpu``) and, to
the last digit, against ``Engine()``'s.
"""

import ast
import pathlib
import threading

import pandas as pd
import pytest

from benchmark.harness import compare
from benchmark.harness.hosttables import HostTables
from benchmark.statements import q1, q3
from trino_tpu import Engine
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.execution.bufferpool import DeviceBufferPool
from trino_tpu.parallel.mesh import worker_mesh
from trino_tpu.server.client import Client
from trino_tpu.server.server import CoordinatorServer

STATEMENTS = {"q1": q1, "q3": q3}
# lineitem in 13 splits of 1,170 orders, padded to 16: four batches a scan on four workers
SPLIT_ROWS = 1 << 13


class VersionedTpch(TpchConnector):
    """A generator whose visible data a test can declare changed, as a connector with
    DML does through ``plan_version``."""

    version = 0

    def plan_version(self):
        return self.version


def sql_of(name):
    statement = STATEMENTS[name]
    return statement.render(statement.VALIDATION)[0]


def mesh_engine(conn, mesh):
    engine = Engine(mesh=mesh)
    # the CPU backend's page cache is off unless given a budget; the chip's is a
    # quarter of HBM
    engine.buffer_pool = DeviceBufferPool(budget_bytes=1 << 30)
    engine.register_catalog("tpch", conn)
    return engine


@pytest.fixture(scope="module")
def deployment():
    conn = VersionedTpch(sf=0.01, split_rows=SPLIT_ROWS)
    mesh = worker_mesh(4)
    engine, plain = mesh_engine(conn, mesh), Engine()
    plain.register_catalog("tpch", conn)
    wanted = {}
    for statement in STATEMENTS.values():
        for table, cols in statement.TABLES.items():
            wanted.setdefault(table, []).extend(cols)
    servers = [CoordinatorServer(e, port=0) for e in (engine, plain)]
    for server in servers:
        server.start()
    yield {"conn": conn, "mesh": mesh, "engine": engine, "plain": plain,
           "tables": HostTables(conn, wanted), "urls": [s.url for s in servers]}
    for server in servers:
        server.stop()


def assert_reference(deployment, name, columns, rows):
    statement = STATEMENTS[name]
    want = statement.reference(deployment["tables"], statement.VALIDATION)
    numbers = compare.compare(pd.DataFrame(rows, columns=columns), want,
                              getattr(statement, "AVG_DECIMALS", None))
    assert compare.within_limits(numbers), numbers


def run(engine, name):
    """One execution with no keyword from the caller; its rows and its own counters."""
    res = engine.execute_sql(sql_of(name), engine.create_session("tpch"))
    return res.rows(), engine.last_query_counters


@pytest.mark.parametrize("name", list(STATEMENTS))
def test_served_statements_run_on_the_engines_mesh(deployment, name):
    """(a) over HTTP: the reference's answer with shard records; ``Engine()``'s has none.
    (f) the exact-decimal control: both engines' answers are equal to the last digit."""
    answers = []
    for url, engine in zip(deployment["urls"], (deployment["engine"], deployment["plain"])):
        res = Client(url, catalog="tpch").execute(sql_of(name))
        assert_reference(deployment, name, res.column_names, res.rows)
        answers.append((res.rows, len(engine.last_query_counters.shard_stats)))
    assert answers[0][0] == answers[1][0]
    assert answers[0][1] > 0 and answers[1][1] == 0
    assert deployment["engine"].last_query_counters.exchange_rows > 0


@pytest.mark.parametrize("name", list(STATEMENTS))
def test_a_replay_compiles_builds_and_generates_nothing(deployment, name):
    """(b) the second and third execution of a text."""
    engine = deployment["engine"]
    first, _ = run(engine, name)
    for _ in range(2):
        rows, c = run(engine, name)
        assert rows == first
        assert (c.compiles, c.compile_cache_misses, c.rows_generated, c.join_build_rows,
                c.mesh_fragment_compiles) == (0, 0, 0, 0, 0), c.as_dict()
        assert c.mesh_fragment_hits > 0 and c.page_cache_hits > 0 and c.page_cache_misses == 0
        assert c.device_dispatches > 0
    (ex,) = engine._mesh_executors.values()
    assert ex.mesh is deployment["mesh"] and ex.buffer_pool is engine.buffer_pool
    per_table = engine.buffer_pool.info()["per_table"]
    assert per_table["tpch.lineitem"]["bytes"] > 0


def test_explain_analyze_reports_the_exchange_and_keeps_no_plan(deployment):
    engine = deployment["engine"]
    run(engine, "q1")
    (ex,) = engine._mesh_executors.values()
    kept = len(ex._kept)
    text = "\n".join(r[0] for r in engine.execute_sql(
        "explain analyze " + sql_of("q1"), engine.create_session("tpch")).rows())
    assert "Fragment execution (distributed run):" in text and "[mesh] Aggregate" in text
    assert "Exchange: " in text and " rows routed, fullest shard " in text
    assert len(ex._kept) == kept  # the EXPLAIN's own plan is forgotten again


def test_plan_version_moving_drops_fragments_and_resident_shards(deployment):
    """(c) a connector whose data changed: the stale plan's fragments go, the resident
    shards of the old version go, and the next answer is computed again, right."""
    engine, conn = deployment["engine"], deployment["conn"]
    before, _ = run(engine, "q1")
    (ex,) = engine._mesh_executors.values()
    kept = len(ex._kept)
    conn.version += 1
    try:
        rows, c = run(engine, "q1")
    finally:
        conn.version -= 1
    assert rows == before
    assert c.mesh_fragment_compiles > 0 and c.rows_generated > 0 and c.page_cache_misses > 0
    assert len(ex._kept) == kept  # the stale plan's entries were dropped, not leaked
    engine._invalidate()  # back to version 0: nothing cached under it may survive


def test_dml_on_a_scanned_table_drops_the_executor_and_the_next_answer_is_right(deployment):
    """(c) DML: a host-fed (memory) table scanned on the mesh, a row inserted."""
    from trino_tpu.connectors.memory import MemoryConnector

    engine = mesh_engine(deployment["conn"], deployment["mesh"])
    engine.register_catalog("mem", MemoryConnector())
    s = engine.create_session("mem")
    engine.execute_sql("create table t as select o_orderkey k, o_custkey v "
                       "from tpch.default.orders where o_orderkey < 200", s)
    q = "select count(*) n, sum(v) s from t"
    (n0, s0), = engine.execute_sql(q, s).rows()
    assert engine.last_query_counters.shard_stats or engine.last_query_counters.device_dispatches
    run(engine, "q1")
    assert engine._mesh_executors and engine.buffer_pool.info()["page_entries"] > 0
    engine.execute_sql("insert into t values (1000001, 7)", s)
    assert not engine._mesh_executors and engine.buffer_pool.info()["entries"] == 0
    assert engine.execute_sql(q, s).rows() == [(n0 + 1, s0 + 7)]
    rows, c = run(engine, "q1")
    assert rows == run(deployment["engine"], "q1")[0]
    assert c.rows_generated > 0 and c.mesh_fragment_compiles > 0


def test_a_short_bucket_climbs_the_ladder_from_the_kept_rung(deployment):
    """(d) ``test_probe_bucket_overflow_retries``' shape, twice in a row on one kept
    executor: every probe row routes to one worker, so rung 0's bucket is short; the
    second run starts at the rung that held, and would still climb from there."""
    from trino_tpu.exec.distributed import _EXCHANGE_LADDER, DistributedExecutor
    from trino_tpu.sql.frontend import compile_sql

    plain = deployment["plain"]
    sql = ("select count(*) c from (select 1 k, l_quantity from lineitem) l "
           "join (select 1 k, n_nationkey from nation) n on l.k = n.k")
    s = plain.create_session("tpch")
    want = plain.execute_sql(sql, s).rows()
    ex = DistributedExecutor(plain.catalogs, mesh=deployment["mesh"], partition_threshold=8)
    plan = compile_sql(sql, plain, s)
    assert ex.execute(plan).rows() == want
    first = ex.counters.snapshot()
    rungs = [v[1][0] for k, v in ex._kept.items() if k[1:] == ("rung",)]
    assert max(rungs) > 0 and first.mesh_fragment_compiles >= 2
    assert ex.execute(plan).rows() == want
    second = ex.counters.snapshot()
    assert second.compiles == 0 and second.mesh_fragment_compiles == 0
    assert second.device_dispatches < first.device_dispatches
    # the kept rung is a start, not a cap: pushed back below what holds, it climbs again
    for k, v in ex._kept.items():
        if k[1:] == ("rung",):
            v[1][0] = 0
    assert ex.execute(plan).rows() == want
    assert max(v[1][0] for k, v in ex._kept.items() if k[1:] == ("rung",)) == max(rungs)
    assert len(_EXCHANGE_LADDER) > max(rungs)


# PR 33: a partitioned join's probe exchange sends what is live.  SF1's orders pass the
# default partition threshold by themselves; at SF0.01 the threshold is lowered on the kept
# executor, so that q3 has the deployment's shape: orders PARTITIONED, customer broadcast
PARTITION_THRESHOLD = 1024
W = 4


def probe_lanes(deployment, bucket):
    """W x bucket a chip a batch, over the chips and the batches of lineitem's scan."""
    splits = deployment["conn"].splits("lineitem", n_hint=W)
    return W * bucket * W * (len(splits) // W)


def rung0_bucket(deployment):
    """2n / W, n the lanes of a split of lineitem: seven an order of its range."""
    split = deployment["conn"].splits("lineitem", n_hint=W)[0]
    return -(-(split.hi - split.lo) * 7 * 2 // W)


@pytest.fixture(scope="module")
def narrowing(deployment):
    """q3 three times on a kept engine of its own: each run's rows and counters, and the
    engine after them."""
    engine = mesh_engine(deployment["conn"], deployment["mesh"])
    with engine._mesh_executor(None) as ex:
        ex.partition_threshold = PARTITION_THRESHOLD
    return [run(engine, "q3") for _ in range(3)] + [engine]


@pytest.mark.parametrize("nth, compiles, narrow", [(0, True, False), (1, True, True),
                                                   (2, False, True)])
def test_q3s_probe_bucket_follows_the_rows_it_carried(deployment, narrowing, nth, compiles,
                                                      narrow):
    """(a) the first run learns, the second compiles the fragment at the learned bucket, the
    third compiles nothing; (d) what the counters say of each; the same answer from all."""
    rows, c = narrowing[nth]
    assert rows == run(deployment["plain"], "q3")[0]
    assert_reference(deployment, "q3", ["l_orderkey", "revenue", "o_orderdate",
                                        "o_shippriority"], rows)
    assert (c.compiles > 0, c.mesh_fragment_compiles > 0) == (compiles, compiles), c.as_dict()
    assert rung0_bucket(deployment) >= 2 * 1024  # narrowing pays: half or less
    bucket = 1024 if narrow else rung0_bucket(deployment)
    assert c.probe_exchange_lanes == probe_lanes(deployment, bucket)
    lineitem = deployment["tables"].columns("lineitem")
    cutoff = (pd.Timestamp(q3.VALIDATION["date"]) - pd.Timestamp("1970-01-01")).days
    assert c.probe_exchange_rows == int((lineitem["l_shipdate"] > cutoff).sum())
    # the group-by's merge is the exchange it was: the probe's rows are not folded into it
    _, served = run(deployment["engine"], "q3")
    assert c.exchange_rows == served.exchange_rows > 0
    assert c.exchange_rows_max_shard == served.exchange_rows_max_shard
    assert served.probe_exchange_lanes == 0  # under the default threshold both joins broadcast


def test_explain_analyze_and_metrics_report_the_probe_exchange(narrowing):
    import re
    import urllib.request

    engine = narrowing[-1]
    text = "\n".join(r[0] for r in engine.execute_sql(
        "explain analyze " + sql_of("q3"), engine.create_session("tpch")).rows())
    c = engine.last_query_counters
    m = re.search(r"Exchange: .*; probe exchanges: (\d+) rows in (\d+) receive lanes", text)
    assert m, text
    assert tuple(map(int, m.groups())) == (c.probe_exchange_rows, c.probe_exchange_lanes)
    server = CoordinatorServer(engine, port=0)
    server.start()
    try:
        body = urllib.request.urlopen(server.url + "/v1/metrics", timeout=10).read().decode()
    finally:
        server.stop()
    total = engine.counters_total
    assert f"trino_tpu_probe_exchange_rows_total {total.probe_exchange_rows}\n" in body
    assert f"trino_tpu_probe_exchange_lanes_total {total.probe_exchange_lanes}\n" in body
    assert total.probe_exchange_lanes > c.probe_exchange_lanes > 0


def dense_probe(deployment):
    """orders onto customer, PARTITIONED, no filter under the probe: every lane of orders'
    first split holds a row.  (plan, the answer of ``Engine()``, a fresh executor)"""
    from trino_tpu.exec.distributed import DistributedExecutor
    from trino_tpu.sql.frontend import compile_sql

    plain = deployment["plain"]
    sql = ("select count(*) c, sum(o_totalprice) s from orders join customer "
           "on o_custkey = c_custkey")
    s = plain.create_session("tpch")
    s.properties["join_distribution_type"] = "PARTITIONED"  # the optimizer would broadcast
    ex = DistributedExecutor(plain.catalogs, mesh=deployment["mesh"])
    return compile_sql(sql, plain, s), plain.execute_sql(sql, s).rows(), ex


def test_a_dense_probe_keeps_its_program(deployment):
    """(c) the learned bucket is over half of what ran: nothing is kept, nothing compiles."""
    plan, want, ex = dense_probe(deployment)
    assert ex.execute(plan).rows() == want
    first = ex.counters.snapshot()
    assert first.probe_exchange_lanes > 0 and first.mesh_fragment_compiles > 0
    assert not [k for k in ex._kept if k[1:] == ("probe_need",)]
    assert ex.execute(plan).rows() == want
    second = ex.counters.snapshot()
    assert (second.compiles, second.mesh_fragment_compiles) == (0, 0), second.as_dict()
    assert (second.probe_exchange_rows, second.probe_exchange_lanes) == \
        (first.probe_exchange_rows, first.probe_exchange_lanes)


def test_a_learned_bucket_that_falls_short_is_forgotten_and_the_ladder_answers(deployment):
    """(b) a kept ``need`` pushed below the truth (the data changed under a kept plan): the
    narrow run drops rows, says so, forgets ``need`` and climbs the ladder."""
    plan, want, ex = dense_probe(deployment)
    assert ex.execute(plan).rows() == want
    (join,) = [v[0] for k, v in ex._kept.items() if k[1:] == ("ptable",)]
    ex._kept[(id(join), "probe_need")] = (join, 10)
    assert ex.execute(plan).rows() == want
    c = ex.counters.snapshot()
    assert c.mesh_fragment_compiles >= 2  # the narrow fragment, then the next rung's
    assert max(v[1][0] for k, v in ex._kept.items() if k[1:] == ("rung",)) > 0
    # the rung that held runs at the always-safe bucket n, and what IT counted is kept: the
    # truth, which asks for more than the 1,024 slots that fell short
    assert ex._kept[(id(join), "probe_need")][1] > 1024
    for compiled in (True, False):
        assert ex.execute(plan).rows() == want
        assert (ex.counters.snapshot().mesh_fragment_compiles > 0) == compiled


def test_two_concurrent_mesh_statements_both_answer_right(deployment):
    """(e) the executor's caches are single-statement state: the second statement waits."""
    engine, url = deployment["engine"], deployment["urls"][0]
    out, errors = [], []

    def work(name):
        try:
            res = Client(url, catalog="tpch").execute(sql_of(name))
            out.append((name, res.column_names, res.rows))
        except Exception as e:  # reported below, on the test's thread
            errors.append(e)

    threads = [threading.Thread(target=work, args=(n,)) for n in ("q1", "q3", "q1", "q3")]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    assert len(out) == 4
    for name, columns, rows in out:
        assert_reference(deployment, name, columns, rows)
    assert len(engine._mesh_executors) == 1
    assert engine.counters_total.executor_wait_s > 0


def test_engine_without_a_mesh_is_the_one_chip_engine(deployment):
    plain = deployment["plain"]
    assert plain.mesh is None and not plain._mesh_executors
    run(plain, "q1")
    assert not plain._mesh_executors
    assert plain.last_query_counters.mesh_fragment_hits == 0


def test_every_jit_of_the_mesh_executor_names_its_site():
    """The device plane's modules are ``jit_dist_*``: no bare ``_jit(fn)`` is left."""
    path = pathlib.Path(__file__).resolve().parent.parent / "trino_tpu" / "exec" / "distributed.py"
    calls = [n for n in ast.walk(ast.parse(path.read_text()))
             if isinstance(n, ast.Call) and getattr(n.func, "id", None) == "_jit"]
    assert len(calls) >= 15
    for call in calls:
        site = next((k.value for k in call.keywords if k.arg == "site"), None)
        assert site is not None, f"distributed.py:{call.lineno}: _jit without site="
        if isinstance(site, ast.Constant):
            assert site.value.startswith("dist."), (call.lineno, site.value)
