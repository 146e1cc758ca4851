"""The chaos matrix, shared between its two consumers.

tests/test_chaos.py (the pinned clean-failure contract) and scripts/chaos.py
(the standalone on-device capture harness) run the SAME scenarios with the
SAME result-signature and leak-check semantics — so the scenario table and
those helpers live here, once.  An edit here changes the test suite and the
capture artifact together instead of silently diverging them.

Host-only module: no jax import, safe to load before backend selection.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from . import tracing

# the budget-suite north-star queries (inlined from the TPC-H spec for the
# same reason test_query_budgets inlines them: the matrix must not drift with
# a generator/benchmark edit)
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

# (name, spec, kind, clear_pool, cache_on).  kind "recover" asserts
# byte-identical results, "fail" asserts the typed error.  clear_pool empties
# the buffer pool first (store scenarios never fire against a warm pool —
# a warm pool never stores); cache_on=False runs the page_cache=false session
# for the generate/h2d classes (a warm pool hit never generates).
SCENARIOS = [
    ("cache-checkout-deny", "point=cache_checkout,action=deny,every=1",
     "recover", False, True),
    ("cache-store-error", "point=cache_store,action=error,every=1",
     "recover", True, True),
    ("reserve-deny", "point=reserve,action=deny,nth=1", "recover", False,
     True),
    ("dispatch-delay", "point=dispatch,action=delay,s=0.001,every=2",
     "recover", False, True),
    ("dispatch-error", "point=dispatch,action=error,nth=3", "fail", False,
     True),
    ("generate-error", "point=generate,action=error,nth=2", "fail", False,
     False),
    ("host-pull-fatal", "point=host_pull,action=fatal,nth=1", "fail", False,
     True),
    ("h2d-error", "point=h2d,action=error,nth=2", "fail", False, False),
]

# the test suite's parametrization views: recovery must be invisible
# (name -> (spec, clear_pool)), local failure must be typed-clean
# (name -> (spec, cache_on))
RECOVERABLE = {name: (spec, clear_pool)
               for name, spec, kind, clear_pool, _cache_on in SCENARIOS
               if kind == "recover"}
FAILING = {name: (spec, cache_on)
           for name, spec, kind, _clear_pool, cache_on in SCENARIOS
           if kind == "fail"}


# -- result-cache matrix (round 12: the buffer pool's result tier) ------------
#
# Separate table from SCENARIOS on purpose: these need an engine whose
# RESULT tier is enabled, and enabling it for the MAIN matrix would let warm
# statements be answered from the cache — the dispatch/generate fault
# classes would then never fire and the suite would fail vacuously.  Every
# consumer (tests/test_result_cache.py, scripts/chaos.py) runs these on a
# result-enabled engine via run_result_scenario below.
#
# (name, spec, kind): "recover" pins byte-identical results + >=1 fire +
# leak check; the "errored queries never cache" contract is pinned by the
# dedicated failing test (a typed dispatch error must leave no entry).
RESULT_SCENARIOS = [
    ("result-checkout-deny",
     "point=cache_checkout,site=result,action=deny,every=1", "recover"),
    ("result-store-deny",
     "point=cache_store,site=result,action=deny,every=1", "recover"),
    ("result-store-error",
     "point=cache_store,site=result,action=error,nth=1", "recover"),
]


def run_result_scenario(engine, sql, session, baseline_sig, name, spec,
                        kind) -> dict:
    """One result-cache chaos scenario: arm ``spec``, run ``sql`` on a
    result-enabled engine, pin byte-identity vs ``baseline_sig``, at least
    one fire, the post-scenario leak check, and (store scenarios) that no
    entry was admitted under the fault.  Returns {"ok": bool, ...} — shared
    by tests/test_result_cache.py and scripts/chaos.py."""
    from . import faults

    rec = {"scenario": name, "kind": kind}
    try:
        # store scenarios must actually attempt a store; checkout scenarios
        # must have an entry to be denied — one clean warm pass arranges
        # both, then the store classes clear just the result tier
        engine.execute_sql(sql, session)
        if "store" in name:
            engine.buffer_pool.clear()
        with faults.injected(spec) as plan:
            got = result_signature(engine.execute_sql(sql, session))
        rec["ok"] = got == baseline_sig
        if not rec["ok"]:
            rec["detail"] = "result diverged"
        rec["fires"] = plan.total_fires()
        if rec["fires"] < 1:
            rec["ok"] = False
            rec["detail"] = "scenario never fired"
        if "store" in name and rec.get("ok") \
                and engine.buffer_pool.info()["result_entries"]:
            rec["ok"] = False
            rec["detail"] = "entry admitted under a store fault"
        leaks = leak_report(engine)
        if leaks:
            rec["ok"] = False
            rec["leaks"] = leaks
        if rec.get("ok"):
            # fault-free rerun: the denied/errored store left no partial
            # state, and the next clean pass re-populates and still matches
            again = result_signature(engine.execute_sql(sql, session))
            if again != baseline_sig:
                rec["ok"] = False
                rec["detail"] = "post-fault rerun diverged"
    except Exception as e:  # scenario harness failure
        rec["ok"] = False
        rec["detail"] = f"{type(e).__name__}: {e}"
    return rec


# -- distributed-exchange matrix (round 18: the device-resident mesh path) ----
#
# The exchange_write/exchange_read fault points used to fire only on the HTTP
# SpoolingExchange; the mesh exchange (exec/distributed.py) now reports to the
# same points at its dist.* sites.  The mesh contract is stricter than HTTP's:
# rows live in carried device buffers inside one shard_map program, so a
# RETURNED action (drop/deny) cannot silently lose or defer them — every
# returned action raises typed (InjectedFaultError), and only the non-raising
# actions (delay) are recoverable.  (name, query, spec, kind): "window" routes
# every orders row through _exchange_collect (dist.exchange.route/.read),
# "agg" takes the final-aggregation merge exchange (dist.agg.merge/.groups);
# "recover" pins byte-identity vs the undistributed baseline, "fail" pins the
# typed error; every scenario ends with the standard leak check + a
# fault-free rerun.
DIST_SCENARIOS = [
    ("dist-route-delay", "window",
     "point=exchange_write,site=dist.*,action=delay,s=0.001,every=1",
     "recover"),
    ("dist-route-error", "window",
     "point=exchange_write,site=dist.exchange.route,action=error,nth=1",
     "fail"),
    ("dist-route-drop", "window",
     "point=exchange_write,site=dist.exchange.route,action=drop,nth=1",
     "fail"),
    ("dist-read-error", "window",
     "point=exchange_read,site=dist.exchange.read,action=error,nth=1",
     "fail"),
    ("dist-merge-deny", "agg",
     "point=exchange_write,site=dist.agg.merge,action=deny,nth=1", "fail"),
    ("dist-groups-error", "agg",
     "point=exchange_read,site=dist.agg.groups,action=error,nth=1", "fail"),
]

# the distributed-exchange queries: a partitioned window (the
# _exchange_collect receive-buffer path) and a distributed group-by (the
# _merge_states hash exchange + compacted groups read)
DIST_QUERIES = {
    "window": """
        select o_custkey, o_orderkey,
               row_number() over (partition by o_custkey
                   order by o_totalprice desc, o_orderkey) rk
        from orders order by o_custkey, o_orderkey limit 29""",
    "agg": """
        select o_custkey, count(*) n, sum(o_totalprice) s from orders
        group by o_custkey order by n desc, o_custkey limit 17""",
}


def run_dist_scenario(engine, sql, session, mesh, baseline_sig, name, spec,
                      kind) -> dict:
    """One distributed-exchange chaos scenario: arm ``spec``, run ``sql`` on
    the worker mesh, pin the outcome (byte-identity for "recover", the typed
    error for "fail"), at least one fire, the standard leak check, and a
    fault-free distributed rerun.  Returns {"ok": bool, ...} — shared by
    tests/test_chaos.py and scripts/chaos.py."""
    from . import faults
    from .faults import InjectedFaultError

    rec = {"scenario": name, "kind": kind}
    try:
        with faults.injected(spec) as plan:
            if kind == "fail":
                try:
                    engine.execute_sql(sql, session, distributed=True,
                                       mesh=mesh)
                    rec["ok"] = False
                    rec["detail"] = "no error raised"
                except InjectedFaultError as e:
                    rec["ok"] = True
                    rec["error_type"] = type(e).__name__
            else:
                got = result_signature(engine.execute_sql(
                    sql, session, distributed=True, mesh=mesh))
                rec["ok"] = got == baseline_sig
                if not rec["ok"]:
                    rec["detail"] = "result diverged"
        rec["fires"] = plan.total_fires()
        if rec["fires"] < 1:
            rec["ok"] = False
            rec["detail"] = "scenario never fired"
        leaks = leak_report(engine)
        if leaks:
            rec["ok"] = False
            rec["leaks"] = leaks
        if rec.get("ok"):
            # fault-free rerun: the raised exchange left no partial carried
            # state behind (executors are per-statement; buffers die with
            # the shard_map program)
            again = result_signature(engine.execute_sql(
                sql, session, distributed=True, mesh=mesh))
            if again != baseline_sig:
                rec["ok"] = False
                rec["detail"] = "post-fault rerun diverged"
    except Exception as e:  # scenario harness failure
        rec["ok"] = False
        rec["detail"] = f"{type(e).__name__}: {e}"
    return rec


# -- memory-pressure matrix (round 11: the tiered-spill ladder) ---------------
#
# Each scenario runs the plan on a FRESH tiny-budget executor whose pool
# forces the Grace/spill paths, with a per-scenario tier configuration and an
# optional armed fault.  (name, cfg, spec, kind):
#
#   cfg["pool_bytes"]  executor MemoryPool capacity (small -> Grace + spill)
#   cfg["page_cache"]  DeviceBufferPool budget: >0 enables the HBM spill
#                      tier, 0 disables it (host tier next)
#   cfg["spill_host"]  TRINO_TPU_SPILL_HOST_BYTES for the scenario (0 forces
#                      disk; None = pool-limited only)
#   cfg["expect_tier"] a tier whose per-query counter must be nonzero (the
#                      forcing actually forced; None = don't care)
#
# "recover" pins byte-identical results vs the unconstrained baseline;
# "fail" pins a typed error (InjectedFaultError / SpillCapacityError).
# After EVERY scenario the extended leak check must pass: no live spill
# file, "spill"-tag reservations back to zero in both the executor pool and
# the scenario buffer pool, no executor-held spill registration.
_POOL = 1 << 19  # 512KB: forces Grace agg + partitioned join at SF<=0.1
PRESSURE = [
    ("tier-hbm", {"pool_bytes": _POOL, "page_cache": 256 << 20,
                  "spill_host": None, "expect_tier": "hbm"}, None, "recover"),
    ("tier-host", {"pool_bytes": _POOL, "page_cache": 0,
                   "spill_host": None, "expect_tier": "host"}, None,
     "recover"),
    ("tier-disk", {"pool_bytes": _POOL, "page_cache": 0,
                   "spill_host": 0, "expect_tier": "disk"}, None, "recover"),
    ("tier-mixed", {"pool_bytes": _POOL, "page_cache": 1 << 16,
                    "spill_host": 1 << 16, "expect_tier": "disk"}, None,
     "recover"),
    ("hbm-deny-overflows", {"pool_bytes": _POOL, "page_cache": 256 << 20,
                            "spill_host": None, "expect_tier": None},
     "point=spill_write,site=spill.hbm,action=deny,every=1", "recover"),
    ("spill-write-error", {"pool_bytes": _POOL, "page_cache": 0,
                           "spill_host": 0, "expect_tier": None},
     "point=spill_write,site=spill.disk,action=error,nth=2", "fail"),
    ("disk-full", {"pool_bytes": _POOL, "page_cache": 0, "spill_host": 0,
                   "expect_tier": None},
     "point=spill_write,site=spill.disk,action=disk_full,nth=1", "fail"),
    ("read-deny", {"pool_bytes": _POOL, "page_cache": 0,
                   "spill_host": None, "expect_tier": None},
     "point=spill_read,action=deny,nth=1", "fail"),
]

# the pressure query: a q18-style wide GROUP BY (one group per orderkey, the
# shape whose device group table blows the tiny pool) — the full q18 runs in
# the slow/capture matrices via QUERIES["q18"]
PRESSURE_QUERY = """
    select o_orderkey, count(*) n from orders
    group by o_orderkey order by n desc, o_orderkey limit 13"""


def run_pressure_scenario(new_executor, plan, baseline_sig, name, cfg, spec,
                          kind, scratch_dir) -> dict:
    """One pressure scenario against a compiled ``plan``: fresh tiny-budget
    executor per cfg (``new_executor(memory_pool=, buffer_pool=)`` makes it:
    this layer sits under the executors and imports none), fault armed,
    outcome + extended leak check folded into the returned record
    ({"ok": bool, ...}) — shared by tests/test_spill_tiers.py and
    scripts/chaos.py so the pinned contract and the on-device capture cannot
    drift."""
    import contextlib
    import os

    from ..exec import spill as spill_mod
    from ..exec.spill import SpillCapacityError
    from ..execution.bufferpool import DeviceBufferPool
    from ..memory import MemoryPool
    from . import faults
    from .faults import InjectedFaultError

    rec = {"scenario": name, "kind": kind}
    prev = {k: os.environ.get(k)
            for k in ("TRINO_TPU_SPILL_HOST_BYTES", "TRINO_TPU_SPILL_DIR")}
    os.environ["TRINO_TPU_SPILL_DIR"] = scratch_dir
    if cfg.get("spill_host") is None:
        os.environ.pop("TRINO_TPU_SPILL_HOST_BYTES", None)
    else:
        os.environ["TRINO_TPU_SPILL_HOST_BYTES"] = str(cfg["spill_host"])
    bp = DeviceBufferPool(budget_bytes=cfg.get("page_cache", 0))
    ex = new_executor(memory_pool=MemoryPool(max_bytes=cfg["pool_bytes"]),
                      buffer_pool=bp)
    try:
        ctx = faults.injected(spec) if spec else contextlib.nullcontext()
        with ctx as plan_f:
            if kind == "fail":
                try:
                    ex.execute(plan)
                    rec["ok"] = False
                    rec["detail"] = "no error raised"
                except (InjectedFaultError, SpillCapacityError) as e:
                    rec["ok"] = True
                    rec["error_type"] = type(e).__name__
            else:
                got = result_signature(ex.execute(plan))
                rec["ok"] = got == baseline_sig
                if not rec["ok"]:
                    rec["detail"] = "result diverged"
        if spec:
            rec["fires"] = plan_f.total_fires()
            if rec["fires"] < 1:
                rec["ok"] = False
                rec["detail"] = "scenario never fired"
        c = ex.counters
        rec["tiers"] = {t: getattr(c, f"spill_tier_{t}")
                        for t in ("hbm", "host", "disk")}
        expect = cfg.get("expect_tier")
        if kind == "recover" and expect and not rec["tiers"].get(expect):
            rec["ok"] = False
            rec["detail"] = f"tier {expect} never engaged: {rec['tiers']}"
        ex.close_producers()  # the exit-path sweep (error unwinds included)
        # a join-bearing plan (the real-q18 capture runs) leaves a
        # PERSISTENT build spill with the compiled stream by design; this
        # scenario executor is throwaway, so evict through the designed
        # path first — then every check below may stay strict
        ex.forget_plan(plan)
        leaks = []
        if ex._spills:
            leaks.append("executor-held-spills")
        n = ex.memory_pool.info()["by_tag"].get("spill", 0)
        if n:
            leaks.append(f"spill-reservation:{n}")
        if bp.memory_pool is not None:
            nb = bp.memory_pool.info()["by_tag"].get(
                DeviceBufferPool.SPILL_TAG, 0)
            if nb:
                leaks.append(f"hbm-spill-reservation:{nb}")
        files = spill_mod.live_spill_files()
        if files:
            leaks.append(f"live-spill-files:{len(files)}")
        leftover = [f for f in os.listdir(scratch_dir)] \
            if os.path.isdir(scratch_dir) else []
        if leftover:
            leaks.append(f"orphaned-spill-files:{leftover}")
        if leaks:
            rec["ok"] = False
            rec["leaks"] = leaks
    except Exception as e:  # scenario harness failure
        rec["ok"] = False
        rec["detail"] = f"{type(e).__name__}: {e}"
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return rec


def result_signature(result):
    """Byte-level result signature (dtype + raw bytes per column; object
    columns — decoded strings — by value)."""
    out = []
    for c in result.columns:
        a = np.asarray(c)
        out.append((str(a.dtype),
                    tuple(a.tolist()) if a.dtype == object else a.tobytes()))
    return tuple(out)


def settle(timeout: float = 8.0) -> list:
    """Poll until no prefetch-producer thread is alive and the in-flight
    registry is empty; returns the leftovers (empty = clean)."""
    deadline = time.time() + timeout
    while True:
        leftovers = [t.name for t in threading.enumerate()
                     if t.name.startswith("prefetch-producer")
                     and t.is_alive()]
        if tracing.INFLIGHT.depth() > 0:
            leftovers += [e["label"] for e in tracing.INFLIGHT.snapshot()]
        if not leftovers or time.time() >= deadline:
            return leftovers
        time.sleep(0.05)


def leak_report(engine, timeout: float = 8.0) -> list:
    """The post-scenario contract, as a list of violations (empty = clean):
    no surviving prefetch-producer thread, zero residual in-flight entries,
    no executor holding a live producer registration, buffer-pool
    reservations exactly equal to its resident bytes (an orphaned
    reservation — store failed after reserving — or an unaccounted partial
    page breaks the equality), and (round 11) spill hygiene: no live spill
    file, no executor-held per-query spill, every "spill"-tagged
    reservation released.  Persistent join-build spills ("spill-build" tag)
    legitimately survive with their cached streams and are exempt."""
    leftovers = settle(timeout)
    for ex in getattr(engine, "_all_executors", []):
        if ex._producers:
            leftovers.append("executor-held-producers")
        if [sp for sp in getattr(ex, "_spills", ())
                if not getattr(sp, "persistent", False)]:
            leftovers.append("executor-held-spills")
        pool = getattr(ex, "memory_pool", None)
        if pool is not None:
            n = pool.info()["by_tag"].get("spill", 0)
            if n:
                leftovers.append(f"spill-reservation:{n}")
    bp = engine.buffer_pool
    pool = bp.memory_pool
    if pool is not None and pool.reserved != bp.info()["bytes"]:
        # the equality also catches an unreleased HBM-tier spill
        # reservation: spill bytes never become resident cache entries
        leftovers.append(f"pool-reservation-mismatch:{pool.reserved}!="
                         f"{bp.info()['bytes']}")
    from ..exec.spill import live_spill_files

    files = live_spill_files()
    if files:
        leftovers.append(f"live-spill-files:{len(files)}")
    return leftovers
