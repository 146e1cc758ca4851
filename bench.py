"""Benchmark: the TPC-H north-star suite (Q1/Q3/Q9/Q18) on the local accelerator
vs a vectorized CPU (numpy/pandas) evaluation of the same queries on the same data.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} — ALWAYS, even on
timeout/failure (from a finally: block; SIGTERM/SIGALRM raise through it).  The exit
code is non-zero when any query raised or the run was cut short, and when no TPU is
found (unless JAX_PLATFORMS=cpu asks for a CPU smoke run): the JSON line still prints.

Protocol mirrors the reference's benchto macro setup (prewarm + timed runs,
SURVEY.md §6: testing/trino-benchto-benchmarks/.../tpch.yaml), adapted to survive a
cold XLA-compile cache: a global wall-clock budget (env BENCH_BUDGET seconds,
default 900) degrades the suite — fewer timed runs, then fewer queries — instead of
overrunning.  Each query completes engine+baseline as a unit, so whatever finished
when the budget ran out still yields a coherent metric.

value = summed TPC-H input rows / summed median wall-clock (rows/sec on one chip);
vs_baseline = geometric-mean per-query speedup over the CPU baseline.
BENCH_SF overrides the scale factor (default 1); BENCH_QUERIES picks a subset
(comma-separated, e.g. "q1,q3").

``--distributed`` benches the worker-mesh executor instead (rows/sec/chip
across the mesh; forces the virtual 8-device mesh on CPU) and embeds the
round-18 device-vs-spool exchange-byte A/B per query.

``--baseline BENCH_xxx.json`` diffs this run's per_query wall/dispatch/bytes
against a prior capture and prints a regression verdict line to stderr
(>20% wall growth or any budget-counter growth flags); the diff also embeds
in the JSON payload under "baseline".  BENCH_STATUS_PORT starts an HTTP
status server on the engine (GET /v1/status: in-flight registry, stall
report, running queries) so an external watcher can capture a post-mortem
artifact if the run stalls mid-bench; pair it with TRINO_TPU_STALL_S to arm
the engine's stall watchdog.
"""

import json
import os
import signal
import sys
import time

# --distributed benches the worker-mesh executor: it needs >1 device, which
# on the CPU backend means forcing the virtual 8-device mesh BEFORE jax
# imports (same dance as tests/conftest.py; a no-op on a real multi-chip
# backend, where jax.devices() reports the hardware)
if "--distributed" in sys.argv and "host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402

SF = float(os.environ.get("BENCH_SF", "1"))
RUNS = int(os.environ.get("BENCH_RUNS", "3"))
BUDGET = float(os.environ.get("BENCH_BUDGET", "900"))

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
    "q4": """
    select o_orderpriority, count(*) as order_count from orders
    where o_orderdate >= date '1993-07-01'
      and o_orderdate < date '1993-07-01' + interval '3' month
      and exists (select 1 from lineitem where l_orderkey = o_orderkey
                  and l_commitdate < l_receiptdate)
    group by o_orderpriority order by o_orderpriority""",
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

# TPC-H input rows touched per query (the tables each query scans)
QUERY_TABLES = {
    "q1": ["lineitem"],
    "q3": ["customer", "orders", "lineitem"],
    "q4": ["orders", "lineitem"],
    "q9": ["part", "supplier", "lineitem", "partsupp", "orders", "nation"],
    "q18": ["customer", "orders", "lineitem"],
}

# columns the CPU baseline actually reads, per table — pulling full tables to
# host (16 lineitem columns, string decode via to_pylist) dominated the round-1
# bench wall-clock; the baseline only needs these
BASELINE_COLUMNS = {
    "lineitem": ["l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
                 "l_discount", "l_tax", "l_shipdate", "l_orderkey", "l_partkey",
                 "l_suppkey", "l_commitdate", "l_receiptdate"],
    "customer": ["c_custkey", "c_mktsegment", "c_name"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority",
               "o_totalprice", "o_orderpriority"],
    "part": ["p_partkey", "p_name"],
    "supplier": ["s_suppkey", "s_nationkey"],
    "partsupp": ["ps_partkey", "ps_suppkey", "ps_supplycost"],
    "nation": ["n_nationkey", "n_name"],
}


class _HostTables:
    """Lazy, cached host-side copies of the baseline's input columns (transfer
    time is NOT part of either measurement)."""

    def __init__(self, conn):
        self.conn = conn
        self._cache: dict = {}

    def __getitem__(self, t):
        import pandas as pd

        if t in self._cache:
            return self._cache[t]
        conn = self.conn
        dicts = conn.dictionaries(t)
        names = BASELINE_COLUMNS[t]
        # ONE generate per split for every baseline column (one compiled
        # program per table, not one per column)
        parts: dict = {name: [] for name in names}
        for sp in conn.splits(t):
            page = conn.generate(sp, list(names))
            valid = np.asarray(page.valid_mask())
            for name in names:
                parts[name].append(np.asarray(page.column(name))[valid])
        cols = {}
        for name in names:
            arr = np.concatenate(parts[name])
            d = dicts.get(name)
            if d is not None:
                arr = d.decode(arr)
            cols[name] = arr
        df = pd.DataFrame(cols)
        self._cache[t] = df
        return df


def cpu_q1(T):
    df = T["lineitem"]
    cutoff = (np.datetime64("1998-12-01") - np.timedelta64(90, "D")
              - np.datetime64("1970-01-01")).astype(np.int64)
    m = df[df["l_shipdate"].to_numpy() <= cutoff]
    disc = m["l_discount"].to_numpy() / 100.0
    tax = m["l_tax"].to_numpy() / 100.0
    price = m["l_extendedprice"].to_numpy() / 100.0
    g = m.assign(dp=price * (1 - disc), ch=price * (1 - disc) * (1 + tax),
                 qty=m["l_quantity"].to_numpy() / 100.0, pr=price, dc=disc)
    r = g.groupby(["l_returnflag", "l_linestatus"]).agg(
        sum_qty=("qty", "sum"), sum_base=("pr", "sum"), sum_dp=("dp", "sum"),
        sum_ch=("ch", "sum"), avg_qty=("qty", "mean"), avg_pr=("pr", "mean"),
        avg_dc=("dc", "mean"), cnt=("dp", "size")).reset_index()
    return r.sort_values(["l_returnflag", "l_linestatus"])


def cpu_q3(T):
    c = T["customer"]; o = T["orders"]; l = T["lineitem"]
    cutoff = (np.datetime64("1995-03-15") - np.datetime64("1970-01-01")).astype(np.int64)
    c2 = c[c["c_mktsegment"] == "BUILDING"][["c_custkey"]]
    o2 = o[o["o_orderdate"].to_numpy() < cutoff][
        ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"]]
    l2 = l[l["l_shipdate"].to_numpy() > cutoff][
        ["l_orderkey", "l_extendedprice", "l_discount"]]
    j = o2.merge(c2, left_on="o_custkey", right_on="c_custkey")
    j = l2.merge(j, left_on="l_orderkey", right_on="o_orderkey")
    rev = (j["l_extendedprice"].to_numpy() / 100.0) * (1 - j["l_discount"].to_numpy() / 100.0)
    j = j.assign(revenue=rev)
    r = j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"])["revenue"].sum().reset_index()
    return r.sort_values(["revenue", "o_orderdate"], ascending=[False, True]).head(10)


def cpu_q4(T):
    o = T["orders"]; l = T["lineitem"]
    lo = (np.datetime64("1993-07-01") - np.datetime64("1970-01-01")).astype(np.int64)
    hi = (np.datetime64("1993-10-01") - np.datetime64("1970-01-01")).astype(np.int64)
    od = o["o_orderdate"].to_numpy()
    o2 = o[(od >= lo) & (od < hi)]
    late = l[l["l_commitdate"].to_numpy() < l["l_receiptdate"].to_numpy()]
    keys = np.unique(late["l_orderkey"].to_numpy())
    m = o2[np.isin(o2["o_orderkey"].to_numpy(), keys)]
    r = m.groupby("o_orderpriority").size().reset_index(name="order_count")
    return r.sort_values("o_orderpriority")


def cpu_q9(T):
    p = T["part"]; s = T["supplier"]; l = T["lineitem"]
    ps = T["partsupp"]; o = T["orders"]; n = T["nation"]
    p2 = p[p["p_name"].astype(str).str.contains("green")][["p_partkey"]]
    j = l.merge(p2, left_on="l_partkey", right_on="p_partkey")
    j = j.merge(s[["s_suppkey", "s_nationkey"]], left_on="l_suppkey", right_on="s_suppkey")
    j = j.merge(ps[["ps_partkey", "ps_suppkey", "ps_supplycost"]],
                left_on=["l_partkey", "l_suppkey"], right_on=["ps_partkey", "ps_suppkey"])
    j = j.merge(o[["o_orderkey", "o_orderdate"]], left_on="l_orderkey", right_on="o_orderkey")
    j = j.merge(n[["n_nationkey", "n_name"]], left_on="s_nationkey", right_on="n_nationkey")
    amount = (j["l_extendedprice"].to_numpy() / 100.0) * (1 - j["l_discount"].to_numpy() / 100.0) \
        - (j["ps_supplycost"].to_numpy() / 100.0) * (j["l_quantity"].to_numpy() / 100.0)
    year = (j["o_orderdate"].to_numpy().astype("datetime64[D]")).astype("datetime64[Y]").astype(int) + 1970
    j = j.assign(amount=amount, o_year=year)
    r = j.groupby(["n_name", "o_year"])["amount"].sum().reset_index()
    return r.sort_values(["n_name", "o_year"], ascending=[True, False])


def cpu_q18(T):
    c = T["customer"]; o = T["orders"]; l = T["lineitem"]
    qty = l.groupby("l_orderkey")["l_quantity"].sum()
    big = qty[qty > 30000].index  # l_quantity is a scaled decimal (x100)
    o2 = o[o["o_orderkey"].isin(big)]
    j = o2.merge(c[["c_custkey", "c_name"]], left_on="o_custkey", right_on="c_custkey")
    j = j.merge(l[["l_orderkey", "l_quantity"]], left_on="o_orderkey", right_on="l_orderkey")
    r = j.groupby(["c_name", "c_custkey", "o_orderkey", "o_orderdate", "o_totalprice"])[
        "l_quantity"].sum().reset_index()
    return r.sort_values(["o_totalprice", "o_orderdate"],
                         ascending=[False, True]).head(100)


CPU_QUERIES = {"q1": cpu_q1, "q3": cpu_q3, "q4": cpu_q4, "q9": cpu_q9,
               "q18": cpu_q18}


class _BudgetExceeded(Exception):
    pass


# regression thresholds for --baseline: wall growth beyond the ratio flags;
# any growth in these per-query budget counters flags (they are supposed to
# be DETERMINISTIC warm-path quantities — growth means a real code change)
WALL_REGRESSION_RATIO = 1.2
BUDGET_COUNTERS = ("device_dispatches", "host_transfers", "host_bytes_pulled")
# cache-effectiveness counters diffed for VISIBILITY, never flagged: hit
# deltas between captures are configuration (budgets, order), not
# regressions — but a result-cache hit appearing here at all means the tier
# leaked into an execute-path measurement (see the RESULT-CACHE pin in main)
CACHE_COUNTERS = ("page_cache_hits", "page_cache_misses",
                  "result_cache_hits", "result_cache_misses")
# round 17: compile census, diffed for VISIBILITY, never flagged — cold
# compile counts/seconds move with XLA versions and cache state, but a WARM
# compile appearing at all is the recompile-regression signature the budget
# suite pins (warm compiles == 0), so the diff shows it without verdicting
COMPILE_COUNTERS = ("compiles", "compile_s",
                    "cold_compiles", "cold_compile_s")
# round 19: adaptive decisions, diffed for VISIBILITY, never flagged — a
# replan appearing between captures is the advisor doing its job (history
# accumulated), not a regression; the warm-path cost of a BAD correction
# shows up in the flagged budget counters above, which is where it belongs
ADAPTIVE_COUNTERS = ("adaptive_replans", "adaptive_holds")


def _baseline_diff(base_pq: dict, now_pq: dict) -> dict:
    """Per-query diff of this run vs a prior capture's per_query payload.
    Returns {"queries": {q: {...}}, "missing": [...], "regressions":
    [summary...]} — a query regresses on >20% wall growth, ANY budget-counter
    growth, or by DISAPPEARING from this run (a query that no longer finishes
    is the worst regression of all)."""
    queries, regressions = {}, []
    missing = sorted(set(base_pq) - set(now_pq))
    for q in missing:
        regressions.append(f"{q}: missing from this run "
                           "(present in baseline — crashed or timed out?)")
    for q in sorted(set(base_pq) & set(now_pq)):
        b, n = base_pq[q], now_pq[q]
        d: dict = {}
        flags = []
        bw, nw = b.get("engine_warm_s"), n.get("engine_warm_s")
        if bw and nw:
            d["wall_s"] = {"base": bw, "now": nw,
                           "ratio": round(nw / bw, 3)}
            if nw > WALL_REGRESSION_RATIO * bw:
                flags.append(f"wall +{(nw / bw - 1) * 100:.0f}% "
                             f"({bw:.3f}s -> {nw:.3f}s)")
        for k in BUDGET_COUNTERS:
            bv, nv = b.get(k), n.get(k)
            if bv is None or nv is None:
                continue
            d[k] = {"base": bv, "now": nv}
            if nv > bv:
                flags.append(f"{k} {bv} -> {nv}")
        for k in CACHE_COUNTERS + COMPILE_COUNTERS + ADAPTIVE_COUNTERS:
            bv, nv = b.get(k), n.get(k)
            if bv is None and nv is None:
                continue
            d[k] = {"base": bv, "now": nv}
        # wall-breakdown buckets (round 16): diffed for VISIBILITY, never
        # flagged — a regressed capture should show WHICH bucket moved
        # (dispatch vs host_pull vs unattributed), but bucket drift between
        # captures is timing, not by itself a verdict
        bbd, nbd = b.get("wall_breakdown") or {}, n.get("wall_breakdown") or {}
        if bbd or nbd:
            d["wall_breakdown"] = {
                k: {"base": bbd.get(k), "now": nbd.get(k)}
                for k in sorted(set(bbd) | set(nbd))
                if (bbd.get(k) or 0) > 0.0005 or (nbd.get(k) or 0) > 0.0005}
        d["flags"] = flags
        queries[q] = d
        if flags:
            regressions.append(f"{q}: " + "; ".join(flags))
    return {"queries": queries, "missing": missing,
            "regressions": regressions}


def _bench_distributed(engine, conn, session, names, remaining, payload,
                       failures):
    """The --distributed bench: Q1/Q3/Q9/Q18 through DistributedExecutor on
    the worker mesh (virtual 8-device CPU mesh locally, the real chips on
    device).  value = rows/sec/CHIP (total input rows / summed warm median /
    mesh size).  Each query also runs one cold+warm pair with the host-spool
    exchange (TRINO_TPU_DEVICE_EXCHANGE=0 equivalent) so the capture carries
    the round-18 A/B: per_query dist_site_bytes (device) vs
    spool_site_bytes."""
    from trino_tpu.exec.distributed import DistributedExecutor
    from trino_tpu.parallel.mesh import worker_mesh
    from trino_tpu.sql.frontend import compile_sql

    n_dev = jax.device_count()
    if n_dev < 2:
        payload["metric"] = f"tpch_sf{SF:g}_distributed_skipped"
        payload["detail"] = f"single-device backend ({n_dev})"
        return
    workers = min(n_dev, 8)
    mesh = worker_mesh(workers)
    payload["workers"] = workers

    def _dist_bytes(c):
        return sum(v["bytes"] for k, v in c.sites.items() if "dist." in k)

    engine_times: dict = {}
    row_counts: dict = {}
    per_query: dict = {}
    for name in names:
        if remaining() < 30:
            print(f"bench: budget exhausted before {name}", file=sys.stderr)
            break
        try:
            plan = compile_sql(QUERIES[name], engine, session)
            ex = DistributedExecutor(engine.catalogs, mesh=mesh)
            t0 = time.perf_counter()
            ex.execute(plan)  # prewarm = cold compile
            cold_s = time.perf_counter() - t0
            times = []
            for _ in range(RUNS):
                if times and remaining() < 3 * times[0]:
                    break
                t0 = time.perf_counter()
                ex.execute(plan)
                times.append(time.perf_counter() - t0)
            med = sorted(times)[len(times) // 2]
            c = ex.counters  # the last WARM run's counters
            pq = {"engine_warm_s": round(med, 3),
                  "engine_cold_s": round(cold_s, 3),
                  "dist_site_bytes": _dist_bytes(c), **c.as_dict()}
            # round 20: shard-skew summary — worst max/mean load ratio and
            # summed imbalance wall over the warm run's ShardStats (the raw
            # records ride along in as_dict's shard_stats)
            if c.shard_stats:
                worst = max(c.shard_stats,
                            key=lambda r: float(r.get("ratio") or 1.0))
                pq["skew"] = {
                    "worst_ratio": round(
                        float(worst.get("ratio") or 1.0), 2),
                    "worst_site": worst.get("site"),
                    "worst_worker": int(worst.get("worker") or 0),
                    "imbalance_s": round(
                        sum(float(r.get("imbalance_s") or 0.0)
                            for r in c.shard_stats), 4),
                    "records": len(c.shard_stats)}
            # spool half of the A/B (one cold + one warm, budget permitting):
            # the host-materializing exchange this round replaced
            if remaining() > 30 + 2 * cold_s:
                sp = DistributedExecutor(engine.catalogs, mesh=mesh,
                                         device_exchange=False)
                sp.execute(plan)
                t0 = time.perf_counter()
                sp.execute(plan)
                pq["spool_warm_s"] = round(time.perf_counter() - t0, 3)
                pq["spool_site_bytes"] = _dist_bytes(sp.counters)
            engine_times[name] = med
            per_query[name] = pq
            for t in QUERY_TABLES[name]:
                row_counts.setdefault(t, conn.row_count(t))
            print(f"bench: {name} mesh({workers}) cold={cold_s:.2f}s "
                  f"warm={med:.3f}s dist_bytes={pq['dist_site_bytes']}"
                  + (f" spool_bytes={pq['spool_site_bytes']}"
                     if "spool_site_bytes" in pq else "")
                  + f" ({remaining():.0f}s left)", file=sys.stderr)
        except _BudgetExceeded:
            raise
        except Exception as e:
            failures.append(name)
            print(f"bench: {name} failed: {type(e).__name__}: {e}",
                  file=sys.stderr)
    done = sorted(engine_times)
    if done:
        total_rows = sum(sum(row_counts[t] for t in QUERY_TABLES[q])
                         for q in done)
        total_t = sum(engine_times.values())
        payload.update({
            "metric": (f"tpch_sf{SF:g}_dist{workers}w_{'_'.join(done)}"
                       "_rows_per_sec_per_chip"),
            "value": round(total_rows / total_t / workers),
            "unit": "rows/s",
            "per_query": per_query,
        })


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--baseline", default=None, metavar="BENCH_JSON",
                    help="prior bench JSON to diff per_query wall/dispatch/"
                         "bytes against (prints a regression verdict line)")
    ap.add_argument("--no-page-cache", action="store_true",
                    help="force the device buffer pool OFF for this run "
                         "(TRINO_TPU_PAGE_CACHE=0) — the cache-off half of "
                         "an A/B pair; per_query embeds page_cache_hits/"
                         "misses/bytes_saved either way, so diffing two runs "
                         "quantifies exactly what the pool saved")
    ap.add_argument("--distributed", action="store_true",
                    help="bench the worker-mesh DistributedExecutor instead "
                         "of the local engine: rows/sec/CHIP across the mesh "
                         "plus the device-vs-spool exchange-byte A/B "
                         "(round 18); on CPU this forces the virtual "
                         "8-device mesh")
    args = ap.parse_args(argv)
    if args.no_page_cache:
        os.environ["TRINO_TPU_PAGE_CACHE"] = "0"
    # the RESULT cache (round 12) stays off unless a capture explicitly sets
    # the env: this benchmark measures the EXECUTE path, and with the tier
    # on every warm timed run would be answered from the cache in ~0 time
    # (bench_serve.py is where that is measured on purpose)
    os.environ.setdefault("TRINO_TPU_RESULT_CACHE", "0")

    deadline = time.monotonic() + BUDGET
    remaining = lambda: deadline - time.monotonic()

    # a terminated process prints nothing — round 1's rc=124 scored null.  Turn
    # SIGTERM (driver timeout) and SIGALRM (own hard stop, slightly past the
    # budget to catch a single hung compile) into an exception that unwinds to
    # the finally: below.  A signal arriving inside one long C-level XLA call
    # is only delivered when the interpreter resumes — hence the deadline
    # checks between runs, which keep any single call's overrun small.
    def _bail(signum, frame):
        raise _BudgetExceeded(f"signal {signum}")

    signal.signal(signal.SIGTERM, _bail)
    signal.signal(signal.SIGALRM, _bail)
    signal.alarm(int(BUDGET + 60))

    engine_times: dict = {}
    cpu_times: dict = {}
    row_counts: dict = {}
    query_counters: dict = {}
    failures: list = []  # anything here makes the exit code non-zero
    payload = {"metric": f"tpch_sf{SF:g}_bench_failed", "value": 0,
               "unit": "rows/s", "vs_baseline": 0}

    try:
        # this process is the only one to touch JAX: a child that probed the
        # device would hold the chip this process then needs.  No TPU and no
        # explicit JAX_PLATFORMS=cpu is a failure, never a CPU run under a
        # device metric's name.
        platform = jax.devices()[0].platform
        if platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
            raise RuntimeError(
                f"no TPU (backend {platform!r}); set JAX_PLATFORMS=cpu for a "
                "CPU smoke run")

        from trino_tpu import Engine
        from trino_tpu.connectors.tpch import TpchConnector

        conn = TpchConnector(sf=SF, split_rows=1 << 21)
        engine = Engine()
        engine.register_catalog("tpch", conn)
        session = engine.create_session("tpch")
        T = _HostTables(conn)

        # optional status sidecar (BENCH_STATUS_PORT): /v1/status serves the
        # live in-flight registry + engine.last_stall_report so a watcher
        # can archive a post-mortem if the run stalls mid-capture (the
        # engine's stall watchdog arms via TRINO_TPU_STALL_S)
        status_port = os.environ.get("BENCH_STATUS_PORT")
        if status_port:
            try:
                from trino_tpu.server.server import CoordinatorServer

                srv = CoordinatorServer(engine, port=int(status_port))
                srv.start()
                print(f"bench: status server at {srv.url}/v1/status",
                      file=sys.stderr)
            except Exception as se:
                print(f"bench: status server failed: {se}", file=sys.stderr)

        names = [q.strip() for q in
                 os.environ.get("BENCH_QUERIES", "q1,q3,q4,q9,q18").split(",")
                 if q.strip() in QUERIES]
        if args.distributed:
            # mesh bench: its own loop + payload (no pandas baseline — the
            # comparison that matters there is device-vs-spool exchange A/B)
            _bench_distributed(engine, conn, session,
                               [n for n in names if n != "q4"],
                               remaining, payload, failures)
            names = []  # skip the local loop; the finally prints the payload
        for name in names:
            if remaining() < 30:
                print(f"bench: budget exhausted before {name}", file=sys.stderr)
                break
            sql = QUERIES[name]
            try:
                t0 = time.perf_counter()
                engine.execute_sql(sql, session)  # prewarm = the cold compile run
                cold_s = time.perf_counter() - t0
                # cold-run compile census (round 17): how many XLA
                # compilations the cold run paid and what they cost — the
                # cold-vs-warm split per_query carries (warm compiles ride
                # the counters snapshot below and must be ZERO)
                try:
                    cc = engine.last_query_counters
                    cold_compiles = cc.compiles
                    cold_compile_s = round(cc.compile_s, 4)
                except Exception:
                    cold_compiles = cold_compile_s = None
                # timed engine runs: as many of RUNS as the budget allows, min 1
                times = []
                for i in range(RUNS):
                    if times and remaining() < 3 * times[0]:
                        break
                    t0 = time.perf_counter()
                    engine.execute_sql(sql, session)
                    times.append(time.perf_counter() - t0)
                med = sorted(times)[len(times) // 2]
                # device-boundary counters of the LAST warm run: the
                # dispatch/transfer budget this query actually spent
                # (engine.last_query_counters — execution/tracing), including
                # the per-site attribution + dispatch-latency histogram, plus
                # a span-tree summary (engine.last_query_trace) — enough to
                # tell "stalled device" (p99 blown, counts stalled) from
                # "slow plan" straight from the bench record
                try:
                    qc = engine.last_query_counters
                    query_counters[name] = qc.as_dict()
                    # the cold/warm compile split: as_dict already carries
                    # the WARM run's compiles/compile_s (expected 0/0.0)
                    query_counters[name]["cold_compiles"] = cold_compiles
                    query_counters[name]["cold_compile_s"] = cold_compile_s
                    tr = engine.last_query_trace or {}
                    query_counters[name]["trace"] = {
                        "spans": len(tr.get("spans", ())),
                        "root_span_s": tr.get("root_span_s"),
                        "dispatch_p50_s": qc.dispatch_latency.quantile(0.5),
                        "dispatch_p99_s": qc.dispatch_latency.quantile(0.99),
                    }
                    # round 16: the warm run's wall decomposed into named
                    # buckets (device dispatch vs host pull vs generation vs
                    # unattributed) — "where did the time go" rides every
                    # capture, and --baseline diffs WHICH bucket moved
                    bd = tr.get("wall_breakdown")
                    if bd:
                        query_counters[name]["wall_breakdown"] = bd
                except Exception:
                    pass
                print(f"bench: {name} engine cold={cold_s:.2f}s warm={med:.3f}s "
                      f"({len(times)} runs, {remaining():.0f}s left)", file=sys.stderr)

                # CPU baseline for the same query (host pull cached per table)
                fn = CPU_QUERIES[name]
                fn(T)  # warm (also triggers the host pull)
                ctimes = []
                for i in range(RUNS):
                    if ctimes and remaining() < 3 * ctimes[0]:
                        break
                    t0 = time.perf_counter()
                    fn(T)
                    ctimes.append(time.perf_counter() - t0)
                cmed = sorted(ctimes)[len(ctimes) // 2]
                print(f"bench: {name} cpu warm={cmed:.3f}s ({len(ctimes)} runs, "
                      f"{remaining():.0f}s left)", file=sys.stderr)

                engine_times[name] = med
                cpu_times[name] = cmed
                for t in QUERY_TABLES[name]:
                    row_counts.setdefault(t, conn.row_count(t))
            except _BudgetExceeded:
                raise
            except Exception as e:  # one pathological query must not zero the bench
                failures.append(name)
                print(f"bench: {name} failed: {type(e).__name__}: {e}",
                      file=sys.stderr)
    except _BudgetExceeded as e:
        import traceback

        failures.append("budget")
        print(f"bench: stopped by {e} at:", file=sys.stderr)
        traceback.print_exc(file=sys.stderr)
    except Exception as e:
        import traceback

        failures.append("fatal")
        print(f"bench: fatal: {type(e).__name__}: {e}", file=sys.stderr)
        traceback.print_exc(file=sys.stderr)
    finally:
        # the JSON emission itself must be uninterruptible: a driver SIGTERM
        # landing inside this block would otherwise raise mid-print and void
        # the "always prints one line" guarantee
        signal.signal(signal.SIGTERM, signal.SIG_IGN)
        signal.signal(signal.SIGALRM, signal.SIG_IGN)
        signal.alarm(0)
        done = sorted(engine_times)
        if done:
            total_rows = sum(sum(row_counts[t] for t in QUERY_TABLES[q]) for q in done)
            total_t = sum(engine_times.values())
            speedups = [cpu_times[q] / engine_times[q] for q in done]
            geomean = float(np.exp(np.mean(np.log(speedups))))
            payload = {
                "metric": f"tpch_sf{SF:g}_{'_'.join(done)}_rows_per_sec_per_chip",
                "value": round(total_rows / total_t),
                "unit": "rows/s",
                "vs_baseline": round(geomean, 3),
            }
            # per-query breakdown: both sides timed in THIS process (the
            # pandas baseline is recomputed alongside the engine run, never
            # copied from an earlier capture) plus each query's warm
            # device-boundary counters
            payload["per_query"] = {
                q: {"engine_warm_s": round(engine_times[q], 3),
                    "cpu_warm_s": round(cpu_times[q], 3),
                    **query_counters.get(q, {})} for q in done}
        if args.baseline:
            # BENCH trajectory comparison: diff against a prior capture and
            # print a one-line verdict (stderr; stdout stays one JSON line)
            try:
                with open(args.baseline) as f:
                    base = json.load(f)
                diff = _baseline_diff(base.get("per_query") or {},
                                      payload.get("per_query") or {})
                payload["baseline"] = {"path": args.baseline, **diff}
                if diff["regressions"]:
                    print(f"bench: baseline REGRESSION vs {args.baseline} — "
                          + " | ".join(diff["regressions"]), file=sys.stderr)
                else:
                    print(f"bench: baseline OK vs {args.baseline} "
                          f"({len(diff['queries'])} queries compared)",
                          file=sys.stderr)
            except Exception as be:
                print(f"bench: baseline diff failed: {type(be).__name__}: "
                      f"{be}", file=sys.stderr)
        try:
            from benchenv import env_info

            payload["env"] = env_info()
        except Exception:
            pass
        try:
            # buffer-pool end-state: entries/bytes/hit totals (per_query
            # already carries each query's page_cache_* counters via as_dict)
            bp = getattr(engine, "buffer_pool", None)
            if bp is not None:
                bi = bp.info()
                bi.pop("per_table", None)  # one JSON line: keep it flat-ish
                payload["page_cache"] = bi
        except Exception:
            pass
        if failures:
            payload["failed"] = failures
        print(json.dumps(payload), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
