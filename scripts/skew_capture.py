#!/usr/bin/env python
"""Skewed-key mesh capture (round 20): one hot-key statement and a uniform
control through DistributedExecutor, with each warm run's ShardStats records
— the skew/straggler datum that belongs next to the round-18 exchange A/B.

TPC-H data is uniform per key, so the hot-key half sorts on the
low-cardinality o_orderstatus column (3 distinct values, one ~2% of rows):
the sort's range partitioning lands nearly half the table on single boundary
workers, which is exactly the load shape the per-shard attribution exists to
expose.  The control sorts the dense unique key and spreads evenly.

One JSON line always (bench.py contract).  SKEW_SF overrides the scale
factor (default 1).  JAX_PLATFORMS=cpu runs the virtual 8-device mesh
(same env dance as scripts/query_counters.py --distributed).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if os.environ.get("JAX_PLATFORMS") == "cpu" and \
        "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)


def main():
    from trino_tpu import Engine
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.exec.distributed import DistributedExecutor
    from trino_tpu.parallel.mesh import worker_mesh
    from trino_tpu.sql.frontend import compile_sql

    sf = float(os.environ.get("SKEW_SF", "1"))
    out = {"sf": sf, "captured_at": time.strftime("%Y-%m-%dT%H:%M:%S%z")}
    try:
        engine = Engine()
        engine.register_catalog("tpch", TpchConnector(sf=sf))
        session = engine.create_session("tpch")
        mesh = worker_mesh(min(jax.device_count(), 8))
        out["workers"] = int(mesh.devices.size)
        stmts = {
            "hot": "select o_orderstatus, o_totalprice from orders "
                   "order by o_orderstatus",
            "uniform": "select o_orderkey, o_totalprice from orders "
                       "order by o_orderkey",
        }
        for name, sql in stmts.items():
            plan = compile_sql(sql, engine, session)
            ex = DistributedExecutor(engine.catalogs, mesh=mesh)
            ex.execute(plan)  # cold: compile + first routing
            t0 = time.perf_counter()
            ex.execute(plan)
            wall = time.perf_counter() - t0
            stats = [dict(r) for r in ex.counters.shard_stats]
            worst = max((float(r.get("ratio") or 1.0) for r in stats),
                        default=1.0)
            out[name] = {
                "warm_s": round(wall, 3),
                "worst_ratio": round(worst, 2),
                "imbalance_s": round(
                    sum(float(r.get("imbalance_s") or 0.0)
                        for r in stats), 4),
                "shard_stats": stats,
            }
    except Exception as e:  # one JSON line always
        out["error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
