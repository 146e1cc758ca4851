"""Serving benchmark: the engine under CONCURRENT statement load.

The north star is "heavy traffic from millions of users" — many concurrent
small/medium statements against the coordinator HTTP protocol, not one big
scan — and this is the harness that measures it (ROADMAP item 4;
"Accelerating Presto with GPUs", arxiv 2606.24647: accelerator engines win
or lose on concurrent utilization, not single-query wall).

Two load modes against a live CoordinatorServer (the /v1/statement
protocol, nextUri paging, real HTTP):

- **closed loop** — SERVE_CLIENTS threads, each issuing its next statement
  the moment the previous one completes (throughput under a fixed
  concurrency; the classic dashboard-fleet shape);
- **open loop** — a Poisson-free fixed-rate arrival schedule at SERVE_QPS,
  each request timed from its SCHEDULED arrival (so queueing delay counts,
  the latency a user actually sees when the engine falls behind).

The mixed workload has five classes (warm TPC-H + point lookups with
per-request DISTINCT constants + protocol-parameterized EXECUTE + short
aggregations + one repeated dashboard statement).  The point/param classes
share one statement shape (_POINT_SQL, a customer point lookup): ``point``
inlines a fresh constant per request (stride 97 over the customer keys —
exercises AUTO-parameterization) and ``param`` binds one per request via
protocol parameters (stride 61) — every request a distinct binding,
identical up to constants, which is exactly the shape plan templates (and
the round-21 template batcher) serve.  The matrix runs THREE times — plan
templates OFF (substitution baseline), templates ON with result cache OFF
(isolates the round-13 template win), then result cache ON — so the JSON
line prices exactly what each tier buys:
per-class p50/p99, achieved qps, buffer-pool/result-cache hit rates,
admission/resource-group queueing, and (SERVE_WORKERS > 0) worker
fair-scheduler preemption counts.  The cache-on half also verifies the
acceptance contract in-process: the repeated statement's warm hit must show
``device_dispatches == 0`` on its counters and byte-identical results vs
the cache-off engine.

After the three-phase matrix, a round-21 template-batch A/B runs the
point+param classes OPEN-LOOP at SERVE_BATCH_QPS (well above
single-statement throughput, so the gather window actually fills) with the
template batcher OFF then ON — latency still measured from SCHEDULED
arrival, so gather-window queueing counts against p50/p99 — and the
payload carries the per-class and total open-loop qps speedups, the
``batched_requests`` counter delta, and batched-vs-serial byte identity.
The A/B drives the ENGINE in-process (``open_loop_inproc``), not the HTTP
protocol: on a small box the polling HTTP harness saturates near ~55 qps
with ZERO dispatches (the cache-on phase measures exactly that ceiling),
which would mask the fused path entirely — and both halves differ only in
the batcher flag, so the protocol layer cancels out of the ratio anyway.

Prints ONE JSON line — always, even on timeout/failure (finally block;
SIGTERM/SIGALRM raise through it) — env-stamped, same contract as bench.py.

Env knobs:
    SERVE_SF            TPC-H scale factor (default 0.1)
    SERVE_DURATION      seconds per load phase (default 20)
    SERVE_CLIENTS       closed-loop concurrency (default 4)
    SERVE_QPS           open-loop arrival rate (default 8; 0 skips open loop)
    SERVE_BATCH_QPS     in-process open-loop arrival rate for the
                        template-batch A/B phases (default 256; 0 skips
                        them — pick it well above the serial engine's
                        point-lookup throughput or neither half saturates)
    SERVE_BATCH_MAX     window cap for the A/B's ON half (default 16 —
                        deeper windows LOSE on CPU where the vmapped
                        program pays real per-lane compute; raise it on a
                        device where a dispatch is a round-trip)
    SERVE_BATCH_WINDOW_MS  gather-window for the ON half (default 0 =
                        pure continuous batching: fuse whatever queued
                        behind the running window, no artificial delay —
                        measured fastest on CPU; the engine-wide
                        TRINO_TPU_BATCH_WINDOW_MS default stays 2)
    SERVE_BUDGET        global wall-clock budget seconds (default 900)
    SERVE_RESULT_CACHE  result-tier bytes for the ON half (default 256MB)
    SERVE_PAGE_CACHE    page-tier bytes for BOTH halves (default 1GB)
    SERVE_CLASSES       comma list restricting the schedule to named classes
                        (e.g. "point,param" isolates the template A/B from
                        cross-class contention; default: all)
    SERVE_WORKERS       in-process cluster workers (default 0 = single node;
                        >0 routes statements through a ClusterCoordinator so
                        worker fair-scheduler preemption becomes measurable)
"""

import json
import os
import signal
import sys
import threading
import time

import jax

jax.config.update("jax_enable_x64", True)

SF = float(os.environ.get("SERVE_SF", "0.1"))
DURATION = float(os.environ.get("SERVE_DURATION", "20"))
CLIENTS = int(os.environ.get("SERVE_CLIENTS", "4"))
QPS = float(os.environ.get("SERVE_QPS", "8"))
BATCH_QPS = float(os.environ.get("SERVE_BATCH_QPS", "256"))
BATCH_MAX = int(os.environ.get("SERVE_BATCH_MAX", "16"))
BATCH_WINDOW_MS = float(os.environ.get("SERVE_BATCH_WINDOW_MS", "0"))
BUDGET = float(os.environ.get("SERVE_BUDGET", "900"))
RESULT_CACHE = int(os.environ.get("SERVE_RESULT_CACHE", str(256 << 20)))
PAGE_CACHE = int(os.environ.get("SERVE_PAGE_CACHE", str(1 << 30)))
WORKERS = int(os.environ.get("SERVE_WORKERS", "0"))
# optional class filter ("point,param"): isolates one workload class for the
# template A/B — under the mixed cycle on a small box, per-class latency is
# dominated by cross-class contention, not the path under measurement
CLASSES = [c.strip() for c in os.environ.get("SERVE_CLASSES", "").split(",")
           if c.strip()]

# TPC-H q1/q3 inlined (importing bench.py re-points the process-wide XLA
# compile cache — the same reason test_query_budgets inlines them)
_Q1 = """
select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,
       sum(l_extendedprice) as sum_base_price,
       sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
       sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge,
       avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price,
       avg(l_discount) as avg_disc, count(*) as count_order
from lineitem where l_shipdate <= date '1998-12-01' - interval '90' day
group by l_returnflag, l_linestatus order by l_returnflag, l_linestatus"""
_Q3 = """
select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
       o_orderdate, o_shippriority
from customer, orders, lineitem
where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
  and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'
  and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate limit 10"""


_POINT_SQL = "select c_name, c_acctbal, c_mktsegment from customer " \
             "where c_custkey = "
_CUSTOMERS = max(int(150000 * SF) - 1, 100)


def workload():
    """-> (classes: {name: [gen...]}, schedule: [(class, gen)...]) where each
    ``gen(i) -> (sql, params|None)`` produces the i-th request.  The schedule
    is a deterministic weighted cycle — repeat-heavy (the dashboard shape the
    result cache exists for), with per-request DISTINCT constants on the
    point/param classes (the millions-of-users shape plan templates exist
    for: every request is a fresh SQL text, identical up to constants).

    - ``point``: ad-hoc SELECT with an inline per-request constant —
      exercises AUTO-parameterization (template hit without client opt-in);
    - ``param``: the same statement with a ``?`` marker and the constant
      bound via protocol parameters (X-Trino-Execute-Parameters)."""

    def fixed(sql):
        return lambda i, sql=sql: (sql, None)

    def point(i):
        return (_POINT_SQL + str(1 + (i * 97) % _CUSTOMERS), None)

    def param(i):
        return (_POINT_SQL + "?", [1 + (i * 61) % _CUSTOMERS])

    classes = {
        # THE repeated statement: identical text every time — result-tier bait
        "repeat": [fixed(_Q3)],
        "point": [point],
        "param": [param],
        "agg": [
            fixed("select l_returnflag, count(*) c, sum(l_quantity) q "
                  "from lineitem group by l_returnflag order by l_returnflag"),
            fixed("select o_orderpriority, count(*) c from orders "
                  "group by o_orderpriority order by o_orderpriority"),
        ],
        "tpch": [fixed(_Q1)],
    }
    schedule = []
    # 12-slot cycle: 4x repeat, 3x point, 2x param, 2x agg, 1x tpch
    weights = (("repeat", 4), ("point", 3), ("param", 2), ("agg", 2),
               ("tpch", 1))
    idx = {c: 0 for c in classes}
    for name, w in weights:
        if CLASSES and name not in CLASSES:
            continue
        for _ in range(w):
            gens = classes[name]
            schedule.append((name, gens[idx[name] % len(gens)]))
            idx[name] += 1
    return classes, schedule


def _quantile(sorted_vals, q):
    if not sorted_vals:
        return None
    i = min(int(q * len(sorted_vals)), len(sorted_vals) - 1)
    return sorted_vals[i]


def _class_stats(samples):
    """samples: {class: [latency_s...]} -> per-class p50/p99/mean/count."""
    out = {}
    for cls, vals in sorted(samples.items()):
        v = sorted(vals)
        out[cls] = {
            "count": len(v),
            "p50_ms": None if not v else round(_quantile(v, 0.50) * 1e3, 2),
            "p99_ms": None if not v else round(_quantile(v, 0.99) * 1e3, 2),
            "mean_ms": None if not v else round(sum(v) / len(v) * 1e3, 2),
        }
    return out


class _Sampler(threading.Thread):
    """Polls the engine's admission surfaces during a load phase: peak
    resource-group queue depth / running count and peak in-flight registry
    depth — the queueing behavior the payload reports."""

    def __init__(self, engine, interval=0.05):
        super().__init__(daemon=True, name="serve-sampler")
        self.engine = engine
        self.interval = interval
        self.max_queued = 0
        self.max_running = 0
        self.max_inflight = 0
        # NOT named _stop: threading.Thread has a private _stop METHOD that
        # join() calls — shadowing it with an Event breaks join()
        self._halt = threading.Event()

    def run(self):
        while not self._halt.is_set():
            try:
                for g in self.engine.resource_groups.info():
                    self.max_queued = max(self.max_queued, g["queued"])
                    self.max_running = max(self.max_running, g["running"])
                self.max_inflight = max(self.max_inflight,
                                        self.engine.inflight.depth())
            except Exception:
                pass
            self._halt.wait(self.interval)

    def stop(self):
        self._halt.set()
        self.join(timeout=2)
        return {"max_group_queued": self.max_queued,
                "max_group_running": self.max_running,
                "max_inflight": self.max_inflight}


_COUNTER_KEYS = ("device_dispatches", "host_transfers", "host_bytes_pulled",
                 "result_cache_hits", "result_cache_misses",
                 "result_cache_bytes_saved", "page_cache_hits",
                 "page_cache_misses", "admission_queued", "task_retries",
                 "plan_template_hits", "plan_template_misses",
                 "batched_requests")


def _counters_snapshot(engine):
    d = engine.counters_total.as_dict()
    return {k: d.get(k, 0) for k in _COUNTER_KEYS}


def _counters_delta(before, after):
    return {k: after[k] - before[k] for k in _COUNTER_KEYS}


def closed_loop(url, schedule, duration, clients, deadline):
    """Fixed-concurrency load: each client issues its next statement as soon
    as the previous completes; returns (per-class latencies, errors, wall)."""
    from trino_tpu.server.client import Client

    samples = {cls: [] for cls, _ in schedule}
    errors = [0]
    lock = threading.Lock()
    stop_at = min(time.monotonic() + duration, deadline)

    def run(offset):
        client = Client(url, catalog="tpch", poll_interval=0.002)
        i = offset  # stagger clients through the cycle so classes interleave
        while time.monotonic() < stop_at:
            cls, gen = schedule[i % len(schedule)]
            sql, params = gen(i)
            i += 1
            t0 = time.perf_counter()
            try:
                client.execute(sql, timeout=120, params=params)
            except Exception:
                with lock:
                    errors[0] += 1
                continue
            dt = time.perf_counter() - t0
            with lock:
                samples[cls].append(dt)

    t_start = time.monotonic()
    threads = [threading.Thread(target=run, args=(k * 3,), daemon=True)
               for k in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t_start
    total = sum(len(v) for v in samples.values())
    return {"wall_s": round(wall, 2),
            "total": {"count": total, "errors": errors[0],
                      "qps": round(total / wall, 2) if wall else None},
            "classes": _class_stats(samples)}


def open_loop(url, schedule, duration, qps, deadline):
    """Fixed-rate arrivals: latency counts from the SCHEDULED arrival time,
    so a backed-up engine shows its queueing delay instead of hiding it
    (the coordinated-omission correction)."""
    from concurrent.futures import ThreadPoolExecutor

    from trino_tpu.server.client import Client

    samples = {cls: [] for cls, _ in schedule}
    errors = [0]
    lock = threading.Lock()
    n = max(int(min(duration, max(deadline - time.monotonic(), 0)) * qps), 1)
    t0 = time.monotonic()

    def fire(i, cls, sql, params, scheduled):
        client = Client(url, catalog="tpch", poll_interval=0.002)
        try:
            client.execute(sql, timeout=120, params=params)
        except Exception:
            with lock:
                errors[0] += 1
            return
        dt = time.monotonic() - scheduled
        with lock:
            samples[cls].append(dt)

    with ThreadPoolExecutor(max_workers=32,
                            thread_name_prefix="serve-open") as pool:
        futures = []
        for i in range(n):
            scheduled = t0 + i / qps
            delay = scheduled - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            if time.monotonic() > deadline:
                break
            cls, gen = schedule[i % len(schedule)]
            sql, params = gen(i)
            futures.append(pool.submit(fire, i, cls, sql, params, scheduled))
        for f in futures:
            f.result()
    wall = time.monotonic() - t0
    total = sum(len(v) for v in samples.values())
    return {"wall_s": round(wall, 2), "target_qps": qps,
            "total": {"count": total, "errors": errors[0],
                      "achieved_qps": round(total / wall, 2) if wall else None},
            "classes": _class_stats(samples)}


def open_loop_inproc(engine, schedule, duration, qps, deadline):
    """open_loop minus the HTTP harness: fixed-rate arrivals fired straight
    at ``engine.execute_sql`` with protocol parameters, latency from the
    SCHEDULED arrival.  The template-batch A/B uses this so the measured
    ratio is the fused serving path, not the polling client's ceiling."""
    from concurrent.futures import ThreadPoolExecutor

    samples = {cls: [] for cls, _ in schedule}
    errors = [0]
    lock = threading.Lock()
    n = max(int(min(duration, max(deadline - time.monotonic(), 0)) * qps), 1)
    t0 = time.monotonic()

    def fire(i, cls, sql, params, scheduled):
        sess = engine.create_session("tpch")
        try:
            engine.execute_sql(sql, sess, parameters=params)
        except Exception:
            with lock:
                errors[0] += 1
            return
        dt = time.monotonic() - scheduled
        with lock:
            samples[cls].append(dt)

    with ThreadPoolExecutor(max_workers=32,
                            thread_name_prefix="serve-inproc") as pool:
        futures = []
        for i in range(n):
            scheduled = t0 + i / qps
            delay = scheduled - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            if time.monotonic() > deadline:
                break
            cls, gen = schedule[i % len(schedule)]
            sql, params = gen(i)
            futures.append(pool.submit(fire, i, cls, sql, params, scheduled))
        for f in futures:
            f.result()
    wall = time.monotonic() - t0
    total = sum(len(v) for v in samples.values())
    return {"wall_s": round(wall, 2), "target_qps": qps,
            "total": {"count": total, "errors": errors[0],
                      "achieved_qps": round(total / wall, 2) if wall else None},
            "classes": _class_stats(samples)}


def build_node(conn, result_cache_bytes, spool_root, templates=True):
    """One engine + coordinator server (+ optional in-process cluster).
    Returns (engine, server, cluster_parts | None).  ``templates=False``
    disables the plan-template path (the substitution-baseline half of the
    round-13 A/B)."""
    from trino_tpu import Engine
    from trino_tpu.execution.bufferpool import DeviceBufferPool
    from trino_tpu.server.server import CoordinatorServer

    engine = Engine()
    # explicit pool budgets (never via env: three phases in one process)
    engine.buffer_pool = DeviceBufferPool(
        budget_bytes=PAGE_CACHE, result_budget_bytes=result_cache_bytes)
    engine.plan_templates_enabled = templates
    engine.register_catalog("tpch", conn)
    cluster = None
    facade = engine
    if WORKERS > 0:
        from trino_tpu.server.cluster import ClusterCoordinator, WorkerServer

        coord = ClusterCoordinator(engine, spool_root)
        coord_url = coord.start()
        workers = []
        for i in range(WORKERS):
            w = WorkerServer({"tpch": {"connector": "tpch", "sf": SF}},
                             spool_root, coordinator_url=coord_url,
                             node_id=f"serve-w{i}")
            w.start()
            workers.append(w)
        coord.wait_for_workers(WORKERS)
        cluster = {"coordinator": coord, "workers": workers}

        class _ClusterFacade:
            """Statement routing through the cluster coordinator; every
            other engine surface (metrics, sessions, pools) passes through."""

            def __init__(self, coordinator, eng):
                self._coord = coordinator
                self._engine = eng

            def execute_sql(self, sql, session=None, parameters=None, **_kw):
                if parameters is not None:
                    # parameterized statements run on the coordinator's own
                    # engine (the template path is local; the cluster task
                    # protocol does not ship bindings)
                    return self._engine.execute_sql(sql, session,
                                                    parameters=parameters)
                return self._coord.execute_sql(sql, session)

            def __getattr__(self, name):
                return getattr(self._engine, name)

        facade = _ClusterFacade(coord, engine)
    server = CoordinatorServer(facade, port=0,
                               dispatch_threads=max(8, CLIENTS + 2))
    server.start()
    return engine, server, cluster


def run_phase(engine, server, schedule, deadline):
    """Warmup + closed loop + open loop + counter/admission deltas."""
    from trino_tpu.server.client import Client

    client = Client(server.url, catalog="tpch", poll_interval=0.002)
    seen = set()
    for _cls, gen in schedule:  # warmup: one pass compiles + populates
        sql, params = gen(0)
        k = (sql, None if params is None else tuple(params))
        if k not in seen:
            seen.add(k)
            client.execute(sql, timeout=600, params=params)
    before = _counters_snapshot(engine)
    sampler = _Sampler(engine)
    sampler.start()
    closed = closed_loop(server.url, schedule, DURATION, CLIENTS, deadline)
    open_ = None
    if QPS > 0 and time.monotonic() < deadline:
        open_ = open_loop(server.url, schedule, DURATION, QPS, deadline)
    admission = sampler.stop()
    bp = engine.buffer_pool.info()
    bp.pop("per_table", None)
    return {"closed": closed, "open": open_,
            "counters": _counters_delta(before, _counters_snapshot(engine)),
            "admission": admission, "buffer_pool": bp}


def main():
    # two Engines live in this process (the off/on halves) — an armed
    # TRINO_TPU_STALL_S (exported for bench.py) would start TWO
    # watchdogs over the shared process-global in-flight registry and
    # cross-report (CLAUDE.md round-8: one armed Engine per process)
    os.environ.pop("TRINO_TPU_STALL_S", None)
    deadline = time.monotonic() + BUDGET

    def _bail(signum, frame):
        raise SystemExit(f"signal {signum}")

    signal.signal(signal.SIGTERM, _bail)
    signal.signal(signal.SIGALRM, _bail)
    signal.alarm(int(BUDGET + 60))

    payload = {"metric": f"serve_sf{SF:g}_bench_failed", "value": 0,
               "unit": "qps", "vs_baseline": 0}
    servers = []
    try:
        from trino_tpu.connectors.tpch import TpchConnector
        from trino_tpu.execution.chaos_matrix import result_signature as _sig

        conn = TpchConnector(sf=SF, split_rows=1 << 16)
        classes, schedule = workload()
        import tempfile

        spool_root = tempfile.mkdtemp(prefix="trino_tpu_serve_")
        phases = {}
        engines = {}
        # three phases: templates_off (result cache off, plan templates off —
        # the substitution baseline), cache_off (templates on, result cache
        # off — isolates the round-13 template win), cache_on (everything)
        matrix = (("templates_off", 0, False), ("cache_off", 0, True),
                  ("cache_on", RESULT_CACHE, True))
        for label, budget, templates in matrix:
            if time.monotonic() > deadline - 10:
                print(f"bench_serve: budget exhausted before {label}",
                      file=sys.stderr)
                break
            engine, server, cluster = build_node(conn, budget, spool_root,
                                                 templates=templates)
            servers.append(server)
            engines[label] = engine
            phases[label] = run_phase(engine, server, schedule, deadline)
            if cluster is not None:
                phases[label]["scheduler"] = {
                    "preemptions": sum(w.scheduler.preemptions
                                       for w in cluster["workers"]),
                    "workers": WORKERS}
            print(f"bench_serve: {label} done "
                  f"({phases[label]['closed']['total']})", file=sys.stderr)
        # -- round-21 template-batch A/B: point+param open-loop at a rate ---
        # well above single-statement throughput, batcher off vs on.  A
        # fresh engine pair (templates on, result cache off) so the only
        # difference is the fused path; latency still counts from SCHEDULED
        # arrival, so the gather window's queueing is in the percentiles.
        batch_sched = [("point", classes["point"][0]),
                       ("param", classes["param"][0])]
        batch_engines = {}
        for label, batching in (("batch_off", False), ("batch_on", True)):
            if BATCH_QPS <= 0 or time.monotonic() > deadline - 10:
                if BATCH_QPS > 0:
                    print(f"bench_serve: budget exhausted before {label}",
                          file=sys.stderr)
                break
            engine, server, _cluster = build_node(conn, 0, spool_root,
                                                  templates=True)
            servers.append(server)
            engine.template_batcher.enabled = batching
            engine.template_batcher.max_batch = BATCH_MAX
            engine.template_batcher.window_s = BATCH_WINDOW_MS / 1000.0
            batch_engines[label] = engine
            for k in (0, 1):  # two distinct bindings confirm the template
                for _cls, gen in batch_sched:
                    sql, params = gen(k)
                    sess = engine.create_session("tpch")
                    engine.execute_sql(sql, sess, parameters=params)
            # unmeasured pre-storm: compiles the pow2 rung ladder (the ON
            # half's analog of the serial half's already-warm plan — both
            # phases measure warm execution, not compilation)
            open_loop_inproc(engine, batch_sched, min(2.0, DURATION / 4),
                            BATCH_QPS, deadline)
            before = _counters_snapshot(engine)
            res = open_loop_inproc(engine, batch_sched, DURATION, BATCH_QPS,
                                   deadline)
            phases[label] = {
                "open": res,
                "counters": _counters_delta(before,
                                            _counters_snapshot(engine)),
                "batcher": engine.template_batcher.info()}
            print(f"bench_serve: {label} done ({res['total']})",
                  file=sys.stderr)
        if "batch_off" in phases and "batch_on" in phases:
            def _open_qps(label, cls_):
                ph = phases[label]["open"]
                n = ph["classes"].get(cls_, {}).get("count") or 0
                w = ph["wall_s"]
                return (n / w) if (n and w) else None

            for cls_ in ("point", "param"):
                off_q, on_q = _open_qps("batch_off", cls_), \
                    _open_qps("batch_on", cls_)
                if off_q and on_q:
                    payload[f"{cls_}_batch_qps_speedup"] = round(
                        on_q / off_q, 2)
            off_t = phases["batch_off"]["open"]["total"]["achieved_qps"]
            on_t = phases["batch_on"]["open"]["total"]["achieved_qps"]
            if off_t and on_t:
                payload["batch_open_qps_speedup"] = round(on_t / off_t, 2)
            payload["batched_requests"] = phases["batch_on"]["counters"] \
                .get("batched_requests", 0)
            # byte identity: the batched engine's answers vs the serial
            # engine's, same requests (the load phase already counter-
            # verified that fused batches actually served traffic)
            identical = True
            for i in range(4):
                for _cls, gen in batch_sched:
                    sql, params = gen(i)
                    s_on = batch_engines["batch_on"].create_session("tpch")
                    s_off = batch_engines["batch_off"].create_session("tpch")
                    if _sig(batch_engines["batch_on"].execute_sql(
                            sql, s_on, parameters=params)) != \
                            _sig(batch_engines["batch_off"].execute_sql(
                                sql, s_off, parameters=params)):
                        identical = False
                        print(f"bench_serve: MISMATCH batch on/off: "
                              f"{sql[:60]}", file=sys.stderr)
            payload["batch_identical"] = identical

        payload["phases"] = phases
        payload["sf"], payload["clients"] = SF, CLIENTS
        payload["duration_s"], payload["qps_target"] = DURATION, QPS
        payload["batch_qps_target"] = BATCH_QPS
        payload["batch_max"] = BATCH_MAX
        payload["batch_window_ms"] = BATCH_WINDOW_MS
        payload["workers"] = WORKERS

        # -- round-13 template A/B: substitution baseline vs templates ------
        if "templates_off" in phases and "cache_off" in phases:
            def _cls_stat(label, cls_, stat):
                return (phases[label]["closed"]["classes"]
                        .get(cls_, {}).get(stat))

            for cls_ in ("point", "param"):
                coff = _cls_stat("templates_off", cls_, "count")
                con = _cls_stat("cache_off", cls_, "count")
                woff = phases["templates_off"]["closed"]["wall_s"]
                won = phases["cache_off"]["closed"]["wall_s"]
                if coff and con and woff and won:
                    payload[f"{cls_}_template_qps_speedup"] = round(
                        (con / won) / (coff / woff), 2)
                p_off = _cls_stat("templates_off", cls_, "p50_ms")
                p_on = _cls_stat("cache_off", cls_, "p50_ms")
                if p_off and p_on:
                    payload[f"{cls_}_template_p50_speedup"] = round(
                        p_off / p_on, 2)
            ctr = phases["cache_off"]["counters"]
            served = sum(_cls_stat("cache_off", c_, "count") or 0
                         for c_ in ("point", "param"))
            if served:
                payload["template_hit_rate"] = round(
                    ctr.get("plan_template_hits", 0) / served, 3)

        # -- acceptance verification (in-process, both engines live) --------
        if "cache_on" in engines and "cache_off" in engines:
            eng_on, eng_off = engines["cache_on"], engines["cache_off"]
            repeat_sql = classes["repeat"][0](0)[0]
            # byte identity: every distinct statement, cache-on vs cache-off
            identical = True
            for _cls, gen in schedule:
                sql, params = gen(0)
                s_on = eng_on.create_session("tpch")
                s_off = eng_off.create_session("tpch")
                if _sig(eng_on.execute_sql(sql, s_on, parameters=params)) != \
                        _sig(eng_off.execute_sql(sql, s_off,
                                                 parameters=params)):
                    identical = False
                    print(f"bench_serve: MISMATCH cache on/off: {sql[:60]}",
                          file=sys.stderr)
            payload["cache_identical"] = identical
            # counter-verified zero-dispatch warm hit
            s = eng_on.create_session("tpch")
            eng_on.execute_sql(repeat_sql, s)
            eng_on.execute_sql(repeat_sql, s)
            c = eng_on.last_query_counters
            payload["warm_hit_zero_dispatches"] = bool(
                c.result_cache_hits >= 1 and c.device_dispatches == 0
                and c.host_transfers == 0)
            # the headline ratio: repeated-statement p50, off vs on
            off_p50 = phases["cache_off"]["closed"]["classes"] \
                .get("repeat", {}).get("p50_ms")
            on_p50 = phases["cache_on"]["closed"]["classes"] \
                .get("repeat", {}).get("p50_ms")
            if off_p50 and on_p50:
                payload["repeat_p50_speedup"] = round(off_p50 / on_p50, 2)
            on = phases["cache_on"]["closed"]["total"]
            payload["metric"] = f"serve_sf{SF:g}_mixed_closed_qps"
            payload["value"] = on.get("qps") or 0
            payload["vs_baseline"] = payload.get("repeat_p50_speedup", 0)
    except BaseException as e:
        import traceback

        print(f"bench_serve: fatal: {type(e).__name__}: {e}", file=sys.stderr)
        traceback.print_exc(file=sys.stderr)
    finally:
        signal.signal(signal.SIGTERM, signal.SIG_IGN)
        signal.signal(signal.SIGALRM, signal.SIG_IGN)
        signal.alarm(0)
        for srv in servers:
            try:
                srv.stop()
            except Exception:
                pass
        try:
            from benchenv import env_info

            payload["env"] = env_info()
        except Exception:
            pass
        print(json.dumps(payload), flush=True)


if __name__ == "__main__":
    main()
