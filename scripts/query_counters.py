#!/usr/bin/env python
"""Per-query device-boundary counter trace: the tool that derives (and
re-derives) the budget numbers pinned in tests/test_query_budgets.py.

Runs the TPC-H north-star queries (chip_smoke.py's QUERIES) through the engine
twice — cold (plan + XLA compile) and warm (cached plan, compiled pipelines)
— and prints one JSON line per query with the QueryCounters snapshot of each
run: device_dispatches, host_transfers, host_bytes_pulled.

The WARM numbers are the budget: a warm query's dispatch count is its
host->device launch bill and its pulled bytes are its transfer bill.  To re-derive the test ceilings after an executor change:

    JAX_PLATFORMS=cpu python scripts/query_counters.py

and copy the warm numbers (with the headroom noted in the test) into
tests/test_query_budgets.py.  TRACE_SF / TRACE_QUERIES / TRACE_SPLIT_ROWS
override the scale factor (default 1, matching the tests), query subset, and
split size (default 1<<21, matching chip_smoke.py).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if "--distributed" in sys.argv and "host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    # the mesh trace needs the virtual 8-device CPU mesh, and the flag must
    # land BEFORE jax import (as in tests/conftest.py)
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)


def main():
    import argparse

    from chip_smoke import QUERIES
    from trino_tpu import Engine
    from trino_tpu.connectors.tpch import TpchConnector

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=None, metavar="N",
                    help="also trace with dispatch_batch=N and print batch=1 "
                         "vs batch=N side by side (coalescing A/B; default: "
                         "trace only the session default)")
    ap.add_argument("--page-cache", type=int, default=None, metavar="BYTES",
                    help="device buffer-pool budget for this trace "
                         "(TRINO_TPU_PAGE_CACHE; 0 = off).  The round-9 "
                         "budget ceilings derive with the cache ON — run "
                         "once with the budget the test fixture sets and "
                         "once with 0 for the A/B the docstring records")
    ap.add_argument("--result-cache", type=int, default=0, metavar="BYTES",
                    help="result-cache tier budget (TRINO_TPU_RESULT_CACHE) "
                         "for this trace.  DEFAULT 0 — the budget ceilings "
                         "in tests/test_query_budgets.py pin the EXECUTE "
                         "path and their fixture forces the tier off; a "
                         "warm run with the tier on costs 0 dispatches "
                         "(not what this tool measures)")
    ap.add_argument("--prepared", action="store_true",
                    help="trace the PREPARE/EXECUTE point-lookup class "
                         "instead of the TPC-H set: cold (template "
                         "creation) then warm EXECUTEs with fresh bindings, "
                         "against the substitution baseline (plan templates "
                         "disabled).  The warm template numbers are the "
                         "point-class ceilings — re-derive them here after "
                         "any template-path change")
    ap.add_argument("--serve-batch", action="store_true",
                    help="trace the round-21 template batcher: fused "
                         "windows of {1,4,16} concurrent EXECUTEs of one "
                         "point-lookup template, printing total and "
                         "PER-REQUEST warm dispatch counts per batch size "
                         "(the fused window must land within 2x of ONE "
                         "request's serial bill — the acceptance ratio).  "
                         "Fusion is manufactured deterministically (the "
                         "lane is held busy while the window enqueues), "
                         "not raced against the wall-clock gather window")
    ap.add_argument("--distributed", action="store_true",
                    help="trace the WORKER-MESH path instead of the local "
                         "executor: each query runs on the 8-device CPU "
                         "mesh (virtual workers; the flag forces the device "
                         "count before jax imports) cold+warm.  The warm "
                         "numbers are the tests/test_distributed_budgets.py "
                         "ceilings")
    ap.add_argument("--sites", action="store_true",
                    help="print each warm query's per-site attribution table "
                         "(operator/call-site -> dispatches, transfers, "
                         "bytes) — the breakdown the budget-test docstrings "
                         "cite when a ceiling regresses")
    ap.add_argument("--breakdown", action="store_true",
                    help="print each warm query's wall-clock decomposition "
                         "(execution/tracing.wall_breakdown over the span "
                         "tree: plan / split generation / h2d / device "
                         "dispatch / host pull / unattributed) — the same "
                         "re-derivation contract as --sites/--history: the "
                         "breakdown is computed from spans the run already "
                         "emitted, zero extra dispatches/pulls")
    ap.add_argument("--compiles", action="store_true",
                    help="print each query's compile census (cold-vs-warm "
                         "compile counts/seconds plus the per-site compile "
                         "table from the attribution) — the re-derivation "
                         "contract matches --sites/--breakdown: detection "
                         "is a host-side set lookup, zero extra dispatches/"
                         "pulls, and the WARM row must show 0 compiles "
                         "(the recompile-regression guard "
                         "tests/test_query_budgets.py pins)")
    ap.add_argument("--adaptive", action="store_true",
                    help="print the adaptive advisor's per-statement "
                         "decision trace after the runs (state, frozen "
                         "corrections, win-vs-price reasons) — the warm run "
                         "is execution 2, so a material misestimate recorded "
                         "cold is exactly what the advisor judges here.  "
                         "Consult/observe are host-only: the counters "
                         "printed alongside are unchanged by the advisor "
                         "(the budget suite pins that)")
    ap.add_argument("--skew", action="store_true",
                    help="print each warm query's per-shard attribution "
                         "(site -> per-worker rows, max/mean ratio, argmax "
                         "worker, imbalance wall) from the ShardStats the "
                         "run already recorded — meaningful with "
                         "--distributed (local statements carry no shard "
                         "records).  Same re-derivation contract as "
                         "--sites: the skew derivation consumes host ints "
                         "already pulled at the existing dist.* sites, "
                         "zero new pulls, counters unchanged")
    ap.add_argument("--history", action="store_true",
                    help="print each warm query's est-vs-actual table from "
                         "the plan-actuals history (node path -> CBO "
                         "estimate, actual rows, over/under factor) — the "
                         "same re-derivation contract as --sites: the "
                         "history feed adds ZERO dispatches/pulls, so the "
                         "counters printed alongside are unchanged by it")
    args = ap.parse_args()

    if args.page_cache is not None:
        os.environ["TRINO_TPU_PAGE_CACHE"] = str(args.page_cache)
    os.environ["TRINO_TPU_RESULT_CACHE"] = str(args.result_cache)
    sf = float(os.environ.get("TRACE_SF", "1"))
    split_rows = int(os.environ.get("TRACE_SPLIT_ROWS", str(1 << 21)))
    names = [q.strip() for q in
             os.environ.get("TRACE_QUERIES", ",".join(QUERIES)).split(",")
             if q.strip() in QUERIES]

    engine = Engine()
    engine.register_catalog("tpch", TpchConnector(sf=sf, split_rows=split_rows))

    if args.prepared:
        _trace_prepared(engine, sf, split_rows)
        return
    if args.serve_batch:
        _trace_serve_batch(engine, sf, split_rows)
        return
    if args.distributed:
        _trace_distributed(engine, sf, split_rows, names, QUERIES,
                           args.sites, args.skew)
        return

    def trace(session, name):
        out = {}
        for phase in ("cold", "warm"):
            t0 = time.perf_counter()
            engine.execute_sql(QUERIES[name], session)
            counters = engine.last_query_counters.as_dict()
            sites = counters.pop("sites", {})
            counters.pop("dispatch_latency", None)  # histogram: JSON noise here
            out[phase] = {"wall_s": round(time.perf_counter() - t0, 3),
                          **counters}
            if args.sites and phase == "warm":
                print(f"# {name} warm per-site attribution "
                      "(dispatches/transfers/bytes):", flush=True)
                for key in sorted(sites, key=lambda k: (
                        -sites[k]["dispatches"], -sites[k]["bytes"], k)):
                    s = sites[key]
                    print(f"#   {key:<44} {s['dispatches']:>4} "
                          f"{s['transfers']:>4} {s['bytes']:>8}", flush=True)
            if args.compiles:
                n = out[phase].get("compiles", 0)
                cs = out[phase].get("compile_s", 0.0)
                print(f"# {name} {phase} compiles: {n} "
                      f"({cs * 1000:.1f} ms)", flush=True)
                comp = {k: v for k, v in sites.items() if v.get("compiles")}
                for key in sorted(comp, key=lambda k: (
                        -comp[k].get("compile_s", 0.0), k)):
                    s = comp[key]
                    print(f"#   {key:<44} {s.get('compiles', 0):>4} "
                          f"{s.get('compile_s', 0.0) * 1000:>9.1f} ms",
                          flush=True)
            if args.breakdown and phase == "warm":
                from trino_tpu.execution.tracing import WALL_BUCKETS
                bd = (engine.last_query_trace or {}).get("wall_breakdown") \
                    or {}
                print(f"# {name} warm wall breakdown "
                      f"(total {bd.get('wall_s', 0.0) * 1000:.1f} ms):",
                      flush=True)
                for b in WALL_BUCKETS:
                    v = bd.get(b) or 0.0
                    if v <= 0:
                        continue
                    wall = bd.get("wall_s") or 1.0
                    print(f"#   {b:<18} {v * 1000:>9.2f} ms "
                          f"{v / wall * 100:>5.1f}%", flush=True)
            if args.history and phase == "warm":
                actuals = engine.last_plan_actuals or {}
                print(f"# {name} warm est-vs-actual "
                      f"(plan {actuals.get('fingerprint', '?')}):",
                      flush=True)
                from trino_tpu.execution.history import misestimate
                for path, r in sorted((actuals.get("nodes") or {}).items()):
                    est = r.get("est_rows")
                    actual = r.get("actual_rows", 0)
                    if est is None:
                        drift = "no estimate"
                    else:
                        ratio, direction = misestimate(est, actual)
                        drift = "on estimate" if direction == "exact" \
                            else f"{ratio:.1f}x {direction}"
                    print(f"#   {path:<32} est "
                          f"{'-' if est is None else format(int(est), ',')}"
                          f"{'':<2} actual {actual:,}  {drift}", flush=True)
        return out

    if args.batch is None:
        session = engine.create_session("tpch")
        for name in names:
            print(json.dumps({"query": name, "sf": sf,
                              "split_rows": split_rows, **trace(session, name)}),
                  flush=True)
        if args.adaptive:
            _print_adaptive(engine)
        return

    # side-by-side: batch=1 (exact per-split) vs --batch N.  Separate sessions:
    # dispatch_batch is plan-shaping, so each mode keys (and compiles) its own
    # plan; the warm dispatch delta is the coalescing win the budget test pins.
    s1 = engine.create_session("tpch")
    engine.session_properties.set_property(s1, "dispatch_batch", 1)
    sn = engine.create_session("tpch")
    engine.session_properties.set_property(sn, "dispatch_batch", args.batch)
    for name in names:
        r1 = trace(s1, name)
        rn = trace(sn, name)
        print(json.dumps({"query": name, "sf": sf, "split_rows": split_rows,
                          "batch1": r1, f"batch{args.batch}": rn}), flush=True)
        w1, wn = r1["warm"], rn["warm"]
        print(f"# {name}: warm dispatches {w1['device_dispatches']} -> "
              f"{wn['device_dispatches']} "
              f"({wn['coalesced_splits']} splits coalesced), "
              f"bytes {w1['host_bytes_pulled']} -> {wn['host_bytes_pulled']}",
              flush=True)


def _print_adaptive(engine):
    """Decision trace (--adaptive): one block per statement the advisor has
    state for — what it decided and the win-vs-price arithmetic behind it."""
    adv = getattr(engine, "adaptive_advisor", None)
    info = adv.info() if adv is not None else {}
    print(f"# adaptive decisions ({info.get('replans_total', 0)} replans, "
          f"{info.get('holds_total', 0)} holds, "
          f"{info.get('demotions_total', 0)} demotions, "
          f"{info.get('confirms_total', 0)} confirms):", flush=True)
    for row in (adv.decision_trace() if adv is not None else []):
        sql = " ".join((row.get("sql") or "?").split())
        if len(sql) > 72:
            sql = sql[:69] + "..."
        verdict = row.get("last_verdict") or "no verdict yet"
        print(f"#   [{row['state']:<9}] {verdict:<7} {sql}", flush=True)
        for r in (row.get("reasons") or []):
            print(f"#       {r}", flush=True)


def _trace_distributed(engine, sf, split_rows, names, QUERIES, show_sites,
                       show_skew=False):
    """Worker-mesh trace: cold+warm counters per query.  The warm rows —
    total dist.* site bytes and the per-site table — are what
    tests/test_distributed_budgets.py pins."""
    from trino_tpu.exec.distributed import DistributedExecutor
    from trino_tpu.parallel.mesh import worker_mesh
    from trino_tpu.sql.frontend import compile_sql

    mesh = worker_mesh(min(jax.device_count(), 8))
    session = engine.create_session("tpch")
    for name in names:
        plan = compile_sql(QUERIES[name], engine, session)
        rec = {"query": name, "sf": sf, "split_rows": split_rows,
               "workers": int(mesh.devices.size)}
        ex = DistributedExecutor(engine.catalogs, mesh=mesh)
        out = {}
        for phase in ("cold", "warm"):
            t0 = time.perf_counter()
            ex.execute(plan)
            counters = ex.counters.as_dict()
            sites = counters.pop("sites", {})
            counters.pop("dispatch_latency", None)
            shard = counters.pop("shard_stats", [])
            dist = {k: v for k, v in sites.items() if "dist." in k}
            out[phase] = {
                "wall_s": round(time.perf_counter() - t0, 3),
                "dist_site_bytes": sum(v["bytes"] for v in dist.values()),
                **{k: v for k, v in counters.items() if v}}
            if show_sites and phase == "warm":
                print(f"# {name} warm dist sites "
                      "(dispatches/transfers/bytes):", flush=True)
                for key in sorted(dist, key=lambda k: (
                        -dist[k]["bytes"], k)):
                    s = dist[key]
                    print(f"#   {key:<44} {s['dispatches']:>4} "
                          f"{s['transfers']:>4} {s['bytes']:>9}",
                          flush=True)
            if show_skew and phase == "warm":
                print(f"# {name} warm shard skew "
                      "(site/kind -> per-worker rows, ratio):",
                      flush=True)
                for s in shard:
                    rows = ",".join(str(int(v))
                                    for v in (s.get("rows") or [])[:16])
                    print(f"#   {s.get('site', '?'):<28} "
                          f"{s.get('kind', '?'):<10} "
                          f"{s.get('op') or '-':<12} "
                          f"{s.get('ratio', 1.0):>5.1f}x "
                          f"worker {s.get('worker', 0):<3} "
                          f"{s.get('imbalance_s', 0.0) * 1000:>7.1f} ms "
                          f"[{rows}]", flush=True)
        rec.update(out)
        print(json.dumps(rec), flush=True)


def _trace_serve_batch(engine, sf, split_rows):
    """--serve-batch: dispatches-per-request through the template batcher at
    fused window sizes {1, 4, 16}.  Each window runs twice; the SECOND
    (warm — serial path and bindings-jit both compiled) run's counter delta
    is the number that matters: the fused window of N must bill within 2x
    of ONE serial request, not N times it.

    Fusion is deterministic, not raced: the template's lane is marked busy
    by hand, the N requests enqueue as members, and a manual handoff
    promotes the first to driver — the same state the real gather window
    produces, minus the wall clock."""
    import threading

    bt = engine.template_batcher
    bt.enabled = True
    bt.window_s = 0.2  # generous: members are already enqueued at handoff
    point = ("select c_name, c_acctbal, c_mktsegment from customer "
             "where c_custkey = ?")
    ncust = max(int(150000 * sf) - 1, 100)
    session = engine.create_session("tpch")
    # create + CONFIRM the template through the real protocol path (the
    # batcher only fuses confirmed templates), and warm the serial jits
    engine.execute_sql(point, session, parameters=[42])
    engine.execute_sql(point, session, parameters=[97])

    def run_window(n):
        keys = [1 + (i * 61) % ncust for i in range(n)]
        errs: list = []

        def fire(k):
            s = engine.create_session("tpch")
            try:
                engine.execute_sql(point, s, parameters=[int(k)])
            except Exception as e:  # surfaced after join
                errs.append(e)

        before = engine.counters_total.as_dict()
        t0 = time.perf_counter()
        if n == 1:
            fire(keys[0])
        else:
            lane = next(iter(bt._lanes.values()))
            with bt._lock:
                lane.busy = True
            threads = [threading.Thread(target=fire, args=(k,))
                       for k in keys]
            for t in threads:
                t.start()
            t_wait = time.monotonic()
            while time.monotonic() - t_wait < 30:
                with bt._lock:
                    if len(lane.queue) >= n:
                        break
                time.sleep(0.001)
            bt._handoff(lane)
            for t in threads:
                t.join()
        wall = time.perf_counter() - t0
        if errs:
            raise errs[0]
        after = engine.counters_total.as_dict()
        return {
            "wall_s": round(wall, 4),
            "device_dispatches": after["device_dispatches"]
            - before["device_dispatches"],
            "host_bytes_pulled": after["host_bytes_pulled"]
            - before["host_bytes_pulled"],
            "batched_requests": after.get("batched_requests", 0)
            - before.get("batched_requests", 0)}

    serial_d = None
    for n in (1, 4, 16):
        cold = run_window(n)   # first fused run compiles the rung's jit
        warm = run_window(n)
        rec = {"batch": n, "sf": sf, "split_rows": split_rows,
               "cold": cold, "warm": warm,
               "per_request_dispatches": round(
                   warm["device_dispatches"] / n, 2)}
        print(json.dumps(rec), flush=True)
        if n == 1:
            serial_d = warm["device_dispatches"]
        ratio = (warm["device_dispatches"] / serial_d) if serial_d else None
        print(f"# batch={n}: warm {warm['device_dispatches']} dispatches "
              f"({rec['per_request_dispatches']}/request, "
              f"{'-' if ratio is None else format(ratio, '.2f')}x one "
              f"request's bill), {warm['batched_requests']} "
              f"batched_requests", flush=True)


def _trace_prepared(engine, sf, split_rows):
    """PREPARE/EXECUTE point-class trace: per phase, wall + counters (the
    warm rows are the template-path budget; the baseline engine shows what
    the substitution path pays for the same statements)."""
    from trino_tpu import Engine
    from trino_tpu.connectors.tpch import TpchConnector

    baseline = Engine()
    baseline.plan_templates_enabled = False
    baseline.register_catalog(
        "tpch", TpchConnector(sf=sf, split_rows=split_rows))

    point = ("select c_name, c_acctbal, c_mktsegment from customer "
             "where c_custkey = ?")
    for label, eng in (("template", engine), ("substitution", baseline)):
        session = eng.create_session("tpch")
        eng.execute_sql(f"prepare point from {point}", session)
        out = {}
        for phase, key in (("cold", 42), ("warm", 4242), ("warm2", 97)):
            t0 = time.perf_counter()
            eng.execute_sql(f"execute point using {key}", session)
            counters = eng.last_query_counters.as_dict()
            counters.pop("sites", None)
            counters.pop("dispatch_latency", None)
            out[phase] = {"wall_s": round(time.perf_counter() - t0, 4),
                          **{k: v for k, v in counters.items() if v}}
        print(json.dumps({"mode": label, "sf": sf,
                          "split_rows": split_rows, **out}), flush=True)
        w = out["warm2"]
        print(f"# {label}: warm wall {w['wall_s'] * 1000:.1f} ms, "
              f"{w.get('device_dispatches', 0)} dispatches, "
              f"{w.get('plan_template_hits', 0)} template hits", flush=True)


if __name__ == "__main__":
    main()
