#!/usr/bin/env python
"""Standalone chaos matrix: the tests/test_chaos.py scenarios as a capture
artifact.  Prints ONE JSON line — always, even on crash (finally block) —
with per-scenario outcomes and the leak-check verdicts, so a chaos pass on
real hardware is one command (not yet run on the chip).

Env knobs:
    CHAOS_SF       TPC-H scale factor (default 0.1 — CPU-box friendly)
    CHAOS_QUERIES  comma-separated subset of q1,q3,q9,q18 (default q1,q3)
    CHAOS_BUDGET   wall-clock budget in seconds (default 600): remaining
                   scenarios are skipped, not overrun
    TRINO_TPU_PAGE_CACHE  honored as usual; defaulted to 1GB here so the
                   cache fault classes have a cache to fault
"""

import functools
import json
import os
import sys
import time

os.environ.setdefault("TRINO_TPU_PAGE_CACHE", str(1 << 30))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    t_start = time.time()
    budget = float(os.environ.get("CHAOS_BUDGET", "600"))
    sf = float(os.environ.get("CHAOS_SF", "0.1"))
    names = [q.strip() for q in
             os.environ.get("CHAOS_QUERIES", "q1,q3").split(",") if q.strip()]
    payload = {"metric": "chaos_pass_fraction", "value": 0.0,
               "unit": "fraction", "sf": sf, "scenarios": []}
    rc = 1
    try:
        dev = jax.devices()[0]
        payload["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                             "count": len(jax.devices())}
        from trino_tpu import Engine
        from trino_tpu.connectors.tpch import TpchConnector
        from trino_tpu.execution import faults
        # the scenario table + signature/leak helpers are SHARED with
        # tests/test_chaos.py: one matrix, pinned by the suite, captured here
        from trino_tpu.execution.chaos_matrix import (QUERIES, SCENARIOS,
                                                      leak_report)
        from trino_tpu.execution.chaos_matrix import result_signature as _sig
        from trino_tpu.execution.faults import InjectedFaultError

        engine = Engine()
        # multi-split geometry at every scale: the generate/h2d classes fire
        # on the 2nd+ split and the prefetch producer only exists for
        # multi-split scans
        split_rows = 1 << 21 if sf >= 1 else 1 << 16
        engine.register_catalog("tpch",
                                TpchConnector(sf=sf, split_rows=split_rows))
        payload["split_rows"] = split_rows
        session = engine.create_session("tpch")
        nocache = engine.create_session("tpch")
        engine.session_properties.set_property(nocache, "page_cache", False)
        baselines = {}
        for q in names:
            engine.execute_sql(QUERIES[q], session)  # cold
            baselines[q] = _sig(engine.execute_sql(QUERIES[q], session))
        done = skipped = 0
        for q in names:
            for (name, spec, kind, clear_pool, cache_on) in SCENARIOS:
                if time.time() - t_start > budget:
                    skipped += 1
                    continue
                rec = {"query": q, "scenario": name, "kind": kind}
                try:
                    if clear_pool:
                        engine.buffer_pool.clear()
                    sess = session if cache_on else nocache
                    with faults.injected(spec) as plan:
                        if kind == "fail":
                            try:
                                engine.execute_sql(QUERIES[q], sess)
                                rec["ok"] = False
                                rec["detail"] = "no error raised"
                            except InjectedFaultError:
                                rec["ok"] = True
                        else:
                            got = _sig(engine.execute_sql(QUERIES[q], sess))
                            rec["ok"] = got == baselines[q]
                            if not rec["ok"]:
                                rec["detail"] = "result diverged"
                    rec["fires"] = plan.total_fires()
                    if rec["fires"] < 1:
                        rec["ok"] = False
                        rec["detail"] = "scenario never fired"
                    leftovers = leak_report(engine)
                    if leftovers:
                        rec["ok"] = False
                        rec["leaks"] = leftovers
                    if rec.get("ok"):
                        # clean-rerun probe: no partial state survived
                        again = _sig(engine.execute_sql(QUERIES[q], session))
                        if again != baselines[q]:
                            rec["ok"] = False
                            rec["detail"] = "post-fault rerun diverged"
                except Exception as e:  # scenario harness failure
                    rec["ok"] = False
                    rec["detail"] = f"{type(e).__name__}: {e}"
                payload["scenarios"].append(rec)
                done += 1
        # round 11: the memory-pressure matrix (tiered spill ladder) — same
        # shared table the test suite pins (chaos_matrix.PRESSURE), run
        # against the REAL q18 at this scale plus the distilled pressure
        # query, inside the same wall-clock budget
        import tempfile

        from trino_tpu.execution.chaos_matrix import (PRESSURE,
                                                      PRESSURE_QUERY,
                                                      run_pressure_scenario)
        from trino_tpu.exec.local_executor import LocalExecutor
        from trino_tpu.sql.frontend import compile_sql

        pressure_queries = {"pressure-agg": PRESSURE_QUERY}
        if "q18" in names:
            pressure_queries["q18"] = QUERIES["q18"]
        for qname, sql in pressure_queries.items():
            plan = compile_sql(sql, engine, session)
            base = _sig(LocalExecutor(engine.catalogs).execute(plan))
            for (name, cfg, spec, kind) in PRESSURE:
                if time.time() - t_start > budget:
                    skipped += 1
                    continue
                scratch = tempfile.mkdtemp(prefix="trino_tpu_chaos_spill_")
                rec = run_pressure_scenario(
                    functools.partial(LocalExecutor, engine.catalogs), plan,
                    base, name, cfg, spec, kind, scratch)
                rec["query"] = qname
                payload["scenarios"].append(rec)
                done += 1
                import shutil

                shutil.rmtree(scratch, ignore_errors=True)
        # round 18: the distributed-exchange matrix — the mesh exchange's
        # fault points (exchange_write/exchange_read at the dist.* sites),
        # run on the worker mesh (virtual CPU workers locally, the real
        # mesh on device)
        from trino_tpu.execution.chaos_matrix import (DIST_QUERIES,
                                                      DIST_SCENARIOS,
                                                      run_dist_scenario)
        from trino_tpu.parallel.mesh import worker_mesh

        n_dev = jax.device_count()
        if n_dev < 2:
            payload["dist_skipped"] = f"single-device backend ({n_dev})"
        else:
            mesh = worker_mesh(min(n_dev, 8))
            dist_baselines = {k: _sig(engine.execute_sql(sql, session))
                              for k, sql in DIST_QUERIES.items()}
            for (name, qkey, spec, kind) in DIST_SCENARIOS:
                if time.time() - t_start > budget:
                    skipped += 1
                    continue
                rec = run_dist_scenario(engine, DIST_QUERIES[qkey], session,
                                        mesh, dist_baselines[qkey], name,
                                        spec, kind)
                rec["query"] = f"dist-{qkey}"
                payload["scenarios"].append(rec)
                done += 1
        # round 12: the result-cache matrix — needs its OWN result-enabled
        # engine (enabling the tier on the main engine would serve the warm
        # statements from cache and the dispatch/generate fault classes
        # above would never fire)
        from trino_tpu.execution.bufferpool import DeviceBufferPool
        from trino_tpu.execution.chaos_matrix import (RESULT_SCENARIOS,
                                                      run_result_scenario)

        if time.time() - t_start > budget:
            skipped += len(RESULT_SCENARIOS)
        else:
            reng = Engine()
            reng.buffer_pool = DeviceBufferPool(budget_bytes=1 << 30,
                                                result_budget_bytes=256 << 20)
            reng.register_catalog("tpch",
                                  TpchConnector(sf=sf, split_rows=split_rows))
            rsess = reng.create_session("tpch")
            rsql = QUERIES[names[0]]
            reng.execute_sql(rsql, rsess)  # cold
            rbase = _sig(reng.execute_sql(rsql, rsess))
            for (name, spec, kind) in RESULT_SCENARIOS:
                if time.time() - t_start > budget:
                    skipped += 1
                    continue
                rec = run_result_scenario(reng, rsql, rsess, rbase, name,
                                          spec, kind)
                rec["query"] = names[0]
                payload["scenarios"].append(rec)
                done += 1
        total = len(payload["scenarios"])
        passed = sum(1 for r in payload["scenarios"] if r.get("ok"))
        payload["value"] = (passed / total) if total else 0.0
        payload["passed"], payload["total"] = passed, total
        payload["skipped_over_budget"] = skipped
        rc = 0 if total and passed == total else 1
    except BaseException as e:
        payload["error"] = f"{type(e).__name__}: {e}"
        raise
    finally:
        payload["wall_s"] = round(time.time() - t_start, 1)
        print(json.dumps(payload), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
