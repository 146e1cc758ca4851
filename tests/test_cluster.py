"""Multi-process control plane: coordinator + worker PROCESSES over HTTP with
a spooled filesystem exchange (reference test model: DistributedQueryRunner
boots a real coordinator + N workers and runs real exchanges,
testing/trino-testing/.../DistributedQueryRunner.java:108 — here the workers
are genuine OS processes, crossing the same process boundary the reference's
HTTP tasks cross)."""

import json
import os
import pathlib
import subprocess
import sys
import time

import pytest

from trino_tpu import Engine
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.server.cluster import ClusterCoordinator, WorkerServer

CATALOGS = {"tpch": {"connector": "tpch", "sf": 0.01, "split_rows": 1 << 11}}

Q = """select l_returnflag, l_linestatus, sum(l_quantity) qty, count(*) c
       from lineitem where l_shipdate <= date '1998-09-02'
       group by l_returnflag, l_linestatus order by l_returnflag, l_linestatus"""


def _engine():
    e = Engine()
    e.register_catalog("tpch", TpchConnector(sf=0.01, split_rows=1 << 11))
    return e


def _spawn_worker(tmp_path, coord_url, node_id):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo_root = str(pathlib.Path(__file__).resolve().parents[1])
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.Popen(
        [sys.executable, "-m", "trino_tpu.server.cluster",
         "--coordinator", coord_url, "--catalogs", json.dumps(CATALOGS),
         "--spool", str(tmp_path / "spool"), "--node-id", node_id],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL)


@pytest.mark.slow
def test_two_process_cluster(tmp_path):
    """Worker registration + fragment dispatch + spooled exchange across two
    real worker processes; result matches single-process execution."""
    e = _engine()
    coord = ClusterCoordinator(e, str(tmp_path / "spool"),
                               heartbeat_interval=0.3)
    url = coord.start()
    w1 = w2 = None
    try:
        w1 = _spawn_worker(tmp_path, url, "w1")
        w2 = _spawn_worker(tmp_path, url, "w2")
        coord.wait_for_workers(2, timeout=60)
        expected = e.execute_sql(Q).rows()
        got = coord.execute_sql(Q).rows()
        assert got == expected
        nodes = {w.node_id for w in coord.live_workers()}
        assert nodes == {"w1", "w2"}
    finally:
        coord.stop()
        for w in (w1, w2):
            if w is not None:
                w.terminate()
                w.wait(timeout=10)


@pytest.mark.slow
def test_worker_death_reassigns_tasks(tmp_path):
    """Heartbeat failure detection + task reassignment: killing one worker
    mid-cluster must not fail the query (reference: HeartbeatFailureDetector
    gating + FTE task retries on another node)."""
    e = _engine()
    coord = ClusterCoordinator(e, str(tmp_path / "spool"),
                               heartbeat_interval=0.2, max_misses=2)
    url = coord.start()
    w1 = w2 = None
    try:
        w1 = _spawn_worker(tmp_path, url, "w1")
        w2 = _spawn_worker(tmp_path, url, "w2")
        coord.wait_for_workers(2, timeout=60)
        expected = e.execute_sql(Q).rows()
        # kill w2 before dispatch: tasks headed its way must reroute to w1
        w2.kill()
        w2.wait(timeout=10)
        time.sleep(0.6)  # let the failure detector notice
        got = coord.execute_sql(Q).rows()
        assert got == expected
        alive = {w.node_id for w in coord.live_workers()}
        assert alive == {"w1"}
    finally:
        coord.stop()
        for w in (w1, w2):
            if w is not None and w.poll() is None:
                w.terminate()
                w.wait(timeout=10)


Q3 = """select l_orderkey, sum(l_extendedprice * (1 - l_discount)) revenue,
               o_orderdate, o_shippriority
        from customer, orders, lineitem
        where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
          and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'
          and l_shipdate > date '1995-03-15'
        group by l_orderkey, o_orderdate, o_shippriority
        order by revenue desc, o_orderdate limit 10"""

Q9 = """select nation, o_year, sum(amount) as sum_profit from (
          select n_name as nation, extract(year from o_orderdate) as o_year,
            l_extendedprice * (1 - l_discount) - ps_supplycost * l_quantity as amount
          from part, supplier, lineitem, partsupp, orders, nation
          where s_suppkey = l_suppkey and ps_suppkey = l_suppkey
            and ps_partkey = l_partkey and p_partkey = l_partkey
            and o_orderkey = l_orderkey and s_nationkey = n_nationkey
            and p_name like '%green%') as profit
        group by nation, o_year order by nation, o_year desc"""


@pytest.mark.slow
def test_cluster_join_queries_across_processes(tmp_path):
    """Q3 and Q9 run END-TO-END through the cluster plane across two real
    worker processes: join fragments fan out by probe splits, aggregates
    consume spooled join output, the remainder finishes on the coordinator
    (round-2 VERDICT #2 done-criterion)."""
    e = _engine()
    coord = ClusterCoordinator(e, str(tmp_path / "spool"),
                               heartbeat_interval=0.3)
    url = coord.start()
    w1 = w2 = None
    try:
        w1 = _spawn_worker(tmp_path, url, "w1")
        w2 = _spawn_worker(tmp_path, url, "w2")
        coord.wait_for_workers(2, timeout=60)
        for q in (Q3, Q9):
            expected = e.execute_sql(q).rows()
            got = coord.execute_sql(q).rows()
            assert got == expected
    finally:
        coord.stop()
        for w in (w1, w2):
            if w is not None:
                w.terminate()
                w.wait(timeout=10)


@pytest.mark.slow
def test_cluster_mid_query_worker_kill(tmp_path):
    """A worker dies MID-QUERY while running join-fragment tasks: the
    coordinator reassigns its tasks to the survivor and the result still
    matches local (round-2 VERDICT #2 done-criterion)."""
    import threading

    e = _engine()
    coord = ClusterCoordinator(e, str(tmp_path / "spool"),
                               heartbeat_interval=0.2, max_misses=2,
                               task_timeout=30.0)
    url = coord.start()
    w1 = w2 = None
    try:
        w1 = _spawn_worker(tmp_path, url, "w1")
        w2 = _spawn_worker(tmp_path, url, "w2")
        coord.wait_for_workers(2, timeout=60)
        expected = e.execute_sql(Q3).rows()
        result: dict = {}

        def run():
            try:
                result["rows"] = coord.execute_sql(Q3).rows()
            except Exception as ex:  # pragma: no cover - surfaced in assert
                result["error"] = ex

        t = threading.Thread(target=run)
        t.start()
        time.sleep(1.0)  # let dispatch begin (workers are mid-fragment)
        w2.kill()
        w2.wait(timeout=10)
        t.join(timeout=300)
        assert not t.is_alive(), "query wedged after worker death"
        assert "error" not in result, result.get("error")
        assert result["rows"] == expected
    finally:
        coord.stop()
        for w in (w1, w2):
            if w is not None and w.poll() is None:
                w.terminate()
                w.wait(timeout=10)


def test_task_endpoints_require_hmac(tmp_path):
    """The fragment/task envelope is pickled — an unauthenticated body must be
    rejected BEFORE deserialization (reference: internal-communication shared
    secret).  Signed traffic passes end-to-end."""
    import pickle
    import urllib.error
    import urllib.request

    e = _engine()
    coord = ClusterCoordinator(e, str(tmp_path / "spool"),
                               heartbeat_interval=0.2, secret="s3cret")
    url = coord.start()
    w = WorkerServer(CATALOGS, str(tmp_path / "spool"), coordinator_url=url,
                     node_id="sec", secret="s3cret")
    w.start()
    try:
        coord.wait_for_workers(1, timeout=60)
        blob = pickle.dumps({"fragment_id": "x", "plan": None})
        # unsigned and mis-signed POSTs bounce with 403
        for headers in ({}, {"X-Trino-Internal-Signature": "0" * 64}):
            req = urllib.request.Request(f"{w.url}/v1/fragment", data=blob,
                                         headers=headers)
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(req, timeout=5)
            assert exc.value.code == 403
        # the coordinator signs with the shared secret: full query runs
        assert coord.execute_sql(Q).rows() == e.execute_sql(Q).rows()
    finally:
        w.stop()
        coord.stop()


def test_worker_refuses_unauthenticated_nonloopback(tmp_path, monkeypatch):
    monkeypatch.delenv("TRINO_TPU_CLUSTER_SECRET", raising=False)
    with pytest.raises(ValueError, match="loopback"):
        WorkerServer(CATALOGS, str(tmp_path / "spool"), host="0.0.0.0")


def test_in_process_worker_roundtrip(tmp_path):
    """WorkerServer driven in-process (fast path for CI): announce, dispatch,
    status poll, spooled commit."""
    e = _engine()
    coord = ClusterCoordinator(e, str(tmp_path / "spool"),
                               heartbeat_interval=0.2)
    url = coord.start()
    w = WorkerServer(CATALOGS, str(tmp_path / "spool"), coordinator_url=url,
                     node_id="inproc")
    w.start()
    try:
        coord.wait_for_workers(1, timeout=60)
        expected = e.execute_sql(Q).rows()
        got = coord.execute_sql(Q).rows()
        assert got == expected
    finally:
        w.stop()
        coord.stop()


def test_distributed_query_merges_worker_counters(tmp_path):
    """Round-7 acceptance: a distributed run reports MERGED coordinator +
    worker device-boundary counters.  Worker tasks record their own
    QueryCounters, ship them on the task status response, and the coordinator
    folds every harvested snapshot (plus its own local spend) into
    last_query_counters and the engine totals — so distributed queries are no
    longer invisible to the budget surfaces."""
    e = _engine()
    coord = ClusterCoordinator(e, str(tmp_path / "spool"),
                               heartbeat_interval=0.2)
    url = coord.start()
    w = WorkerServer(CATALOGS, str(tmp_path / "spool"), coordinator_url=url,
                     node_id="inproc")
    w.start()
    try:
        coord.wait_for_workers(1, timeout=60)
        expected = e.execute_sql(Q9).rows()
        before = e.counters_total.device_dispatches
        got = coord.execute_sql(Q9).rows()
        assert got == expected
        assert coord.local_fallbacks == 0, coord.last_fallback_error
        merged = coord.last_query_counters
        workers = coord._qc_workers
        # the worker half actually arrived (not just coordinator-local spend)
        assert workers.device_dispatches > 0, "no worker counters harvested"
        assert workers.host_bytes_pulled > 0
        # merged totals = coordinator-local + harvested worker snapshots
        # (the merge is constructed that way; assert both halves are present
        # and the engine totals advanced by the merged amount)
        assert merged.device_dispatches >= workers.device_dispatches
        assert e.counters_total.device_dispatches - before \
            == merged.device_dispatches
        # worker sites flow through the merge with their fte/stream tags
        assert any(k.startswith(("fte.", "step", "dist."))
                   or "/" in k for k in merged.sites), merged.sites
        # worker span trees ride back too (task root + dispatch children)
        names = {s["name"] for s in coord.last_query_worker_spans}
        assert "task" in names and "dispatch" in names, names
        # per-site sums still equal the merged totals after the cluster merge
        assert sum(v["dispatches"] for v in merged.sites.values()) \
            == merged.device_dispatches
    finally:
        w.stop()
        coord.stop()


def test_stalled_worker_marked_degraded_and_unscheduled(tmp_path):
    """Round-8 acceptance: a worker whose stall watchdog reports a wedged
    in-flight dispatch keeps answering HTTP (alive, harvestable, streams
    drain/retry as before — the speculation and stream-RETRY paths covered
    by the other tests in this module are untouched) but is marked DEGRADED:
    the coordinator stops scheduling new tasks to it, the query completes
    entirely on the healthy worker, and scheduling resumes once the stall
    clears."""
    e = _engine()
    coord = ClusterCoordinator(e, str(tmp_path / "spool"),
                               heartbeat_interval=0.1)
    url = coord.start()
    # realistic threshold: a genuine cold compile on this box takes seconds
    # and must NOT read as a stall; the wedge below is injected as an entry
    # aged far past it (the same record a _jit stuck on a dead device holds)
    wa = WorkerServer(CATALOGS, str(tmp_path / "spool"), coordinator_url=url,
                      node_id="wa", stall_s=30.0)
    wb = WorkerServer(CATALOGS, str(tmp_path / "spool"), coordinator_url=url,
                      node_id="wb", stall_s=30.0)
    wa.start()
    wb.start()
    try:
        coord.wait_for_workers(2, timeout=60)
        expected = e.execute_sql(Q).rows()
        # wedge wa: an in-flight dispatch entry an hour old on ITS registry
        tok = wa.inflight.enter("dispatch", site="probe.step")
        wa.inflight._entries[tok].start_monotonic -= 3600.0
        deadline = time.time() + 30
        while time.time() < deadline:
            with coord._lock:
                w = coord.workers.get("wa")
                if w is not None and w.degraded:
                    break
            time.sleep(0.05)
        with coord._lock:
            assert coord.workers["wa"].degraded, "wa never marked degraded"
            assert coord.workers["wa"].alive, "degraded != dead"
            assert coord.workers["wa"].health == "stalled"
        assert {w.node_id for w in coord.live_workers()} == {"wb"}
        # the query schedules ONLY onto the healthy worker and still succeeds
        got = coord.execute_sql(Q).rows()
        assert got == expected
        assert coord.local_fallbacks == 0, coord.last_fallback_error
        assert not wa.tasks, f"degraded worker received tasks: {list(wa.tasks)}"
        assert wb.tasks, "healthy worker ran nothing"
        # stall clears -> verdict recovers -> wa returns to scheduling
        wa.inflight.exit(tok)
        deadline = time.time() + 30
        while time.time() < deadline:
            with coord._lock:
                if not coord.workers["wa"].degraded:
                    break
            time.sleep(0.05)
        assert {w.node_id for w in coord.live_workers()} == {"wa", "wb"}
    finally:
        wa.stop()
        wb.stop()
        coord.stop()


def test_speculative_execution_of_stragglers(tmp_path, monkeypatch):
    """Once every task is dispatched, a straggler re-dispatches to another
    worker; first-commit-wins dedup makes the duplicate harmless and the
    query finishes at the fast worker's pace (reference: the FTE scheduler's
    SPECULATIVE task class, TaskExecutionClass.java)."""
    e = _engine()
    coord = ClusterCoordinator(e, str(tmp_path / "spool"),
                               heartbeat_interval=0.2,
                               speculative_factor=2.0, task_timeout=60.0)
    url = coord.start()
    w1 = WorkerServer(CATALOGS, str(tmp_path / "spool"), coordinator_url=url,
                      node_id="fast")
    w2 = WorkerServer(CATALOGS, str(tmp_path / "spool"), coordinator_url=url,
                      node_id="slow")
    w1.start()
    w2.start()
    try:
        coord.wait_for_workers(2, timeout=30)
        expected = e.execute_sql(Q).rows()
        coord.execute_sql(Q)  # warm both workers' compile caches
        # 20s straggler cost: big enough that "the query finished in well
        # under one straggler" stays unambiguous on a loaded 1-core box
        # (wall-clock margins below that were flaky under background load)
        # EVERY executor of the slow worker: it keeps a pool of them, and a
        # second one exists whenever two of its tasks overlapped in the warm-up
        from trino_tpu.exec.local_executor import LocalExecutor

        orig = LocalExecutor._agg_compiled

        def straggle(self, node):
            if self.memory_pool is w2.memory_pool:
                time.sleep(20)
            return orig(self, node)

        monkeypatch.setattr(LocalExecutor, "_agg_compiled", straggle)
        t0 = time.time()
        got = coord.execute_sql(Q).rows()
        elapsed = time.time() - t0
        assert got == expected
        assert coord.speculative_tasks >= 1, "no speculation happened"
        assert elapsed < 19.0, \
            f"query waited out the straggler ({elapsed:.1f}s)"
    finally:
        w1.stop()
        w2.stop()
        coord.stop()


def test_fte_memory_failure_bisects_task(tmp_path):
    """A device-memory failure inside a partial-aggregation task bisects its
    split set and merges the halves (the memory-growth retry analog:
    ExponentialGrowthPartitionMemoryEstimator)."""
    from trino_tpu.exec import fte as F

    e = _engine()
    s = e.create_session("tpch")
    from trino_tpu.sql.frontend import compile_sql

    plan = compile_sql(Q, e, s)
    expected = e.execute_sql(Q, s).rows()
    ex = F.FaultTolerantExecutor(e.catalogs, str(tmp_path / "spool"))
    calls = []
    orig = F._partial_once

    def flaky(node, stream, key_types, acc_specs, step, splits, tick=None):
        calls.append(len(splits))
        if len(splits) > 1:
            raise MemoryError("synthetic RESOURCE_EXHAUSTED")
        return orig(node, stream, key_types, acc_specs, step, splits, tick)

    F._partial_once = flaky
    try:
        got = ex.execute(plan).rows()
    finally:
        F._partial_once = orig
    assert got == expected
    assert any(c > 1 for c in calls) and any(c == 1 for c in calls), \
        "bisection never recursed"


def test_graceful_shutdown_drains_and_leaves(tmp_path):
    """Graceful shutdown (reference: GracefulShutdownHandler): the worker
    finishes running tasks, refuses new ones with 503, reports
    shutting_down, and leaves the cluster; queries keep succeeding on the
    remaining worker."""
    import urllib.request

    e = _engine()
    coord = ClusterCoordinator(e, str(tmp_path / "spool"),
                               heartbeat_interval=0.1)
    url = coord.start()
    w1 = WorkerServer(CATALOGS, str(tmp_path / "spool"), coordinator_url=url,
                      node_id="w1", announce_interval=0.1)
    w2 = WorkerServer(CATALOGS, str(tmp_path / "spool"), coordinator_url=url,
                      node_id="w2", announce_interval=0.1)
    w1.start()
    w2.start()
    try:
        coord.wait_for_workers(2, timeout=60)
        expected = e.execute_sql(Q).rows()
        assert coord.execute_sql(Q).rows() == expected

        w1.shutdown_gracefully()
        try:
            info = json.loads(urllib.request.urlopen(
                f"{w1.url}/v1/info", timeout=5).read())
            assert info["state"] == "shutting_down"
        except urllib.error.HTTPError:
            raise  # a BROKEN info endpoint must not pass
        except (OSError, urllib.error.URLError):
            pass  # drain was idle-fast: the server already exited — the
            # coordinator-side assertions below are the real contract
        # the coordinator drains w1 out of scheduling within an announce tick
        deadline = time.time() + 30
        while time.time() < deadline:
            live = {w.node_id for w in coord.live_workers()}
            if live == {"w2"}:
                break
            time.sleep(0.05)
        assert {w.node_id for w in coord.live_workers()} == {"w2"}
        # queries still work on the remaining worker
        assert coord.execute_sql(Q).rows() == expected
        # the drained worker eventually leaves entirely (announce "gone")
        deadline = time.time() + 30
        while time.time() < deadline:
            with coord._lock:
                if "w1" not in coord.workers:
                    break
            time.sleep(0.05)
        with coord._lock:
            assert "w1" not in coord.workers
    finally:
        w2.stop()
        coord.stop()


def test_task_admission_backpressure(tmp_path):
    """A worker at max_concurrent_tasks refuses with 429; the coordinator
    re-offers instead of burning retry attempts, and the query completes."""
    e = _engine()
    coord = ClusterCoordinator(e, str(tmp_path / "spool"),
                               heartbeat_interval=0.2, splits_per_task=1,
                               max_attempts=2)
    url = coord.start()
    w = WorkerServer(CATALOGS, str(tmp_path / "spool"), coordinator_url=url,
                     node_id="slow", announce_interval=0.1)
    w.max_concurrent_tasks = 1  # every concurrent dispatch beyond 1 -> 429
    w.start()
    try:
        coord.wait_for_workers(1, timeout=60)
        expected = e.execute_sql(Q).rows()
        assert coord.execute_sql(Q).rows() == expected
    finally:
        w.stop()
        coord.stop()


@pytest.mark.slow
def test_cluster_tpcds_star(tmp_path):
    """The OS-process control plane schedules a TPC-DS star query: worker
    build_catalogs instantiates the TPC-DS connector, split tasks fan out
    over store_sales, and the coordinator merges partials (round 4: the
    cluster plane is no longer TPC-H-only)."""
    from trino_tpu.connectors.tpcds import TpcdsConnector

    cats = {"tpcds": {"connector": "tpcds", "sf": 0.01,
                      "split_rows": 1 << 12}}
    e = Engine()
    e.register_catalog("tpcds", TpcdsConnector(sf=0.01, split_rows=1 << 12))
    coord = ClusterCoordinator(e, str(tmp_path / "spool"),
                               heartbeat_interval=0.3)
    url = coord.start()
    w1 = w2 = None
    sql = ("select i_category, sum(ss_ext_sales_price) rev, count(*) c "
           "from store_sales, item, date_dim "
           "where ss_item_sk = i_item_sk and ss_sold_date_sk = d_date_sk "
           "and d_year = 2000 group by i_category "
           "order by rev desc, i_category")
    try:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        repo_root = str(pathlib.Path(__file__).resolve().parents[1])
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
        procs = []
        for nid in ("dsw1", "dsw2"):
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "trino_tpu.server.cluster",
                 "--coordinator", url, "--catalogs", json.dumps(cats),
                 "--spool", str(tmp_path / "spool"), "--node-id", nid],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL))
        w1, w2 = procs
        coord.wait_for_workers(2, timeout=60)
        expected = e.execute_sql(sql).rows()
        got = coord.execute_sql(sql).rows()
        assert got == expected and len(got) > 3
    finally:
        coord.stop()
        for w in (w1, w2):
            if w is not None:
                w.terminate()
                w.wait(timeout=10)
