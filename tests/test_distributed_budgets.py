"""Device-boundary budgets for the WORKER-MESH path (round 18).

The round-6 budget discipline extended to the distributed executor: warm
Q3/Q9/Q18 on the 8-device CPU mesh must be byte-identical to local execution
AND stay under committed ceilings on the host bytes pulled at the dist.*
sites.  With the device-resident exchange, routed rows live in carried
[W, cap] device receive buffers inside the routing shard_map — the only
host traffic between scan and the blocking consumer is scalar
overflow/cursor flags, so a full-page pull appearing at an exchange site
(the round-17 host spool's signature) blows the ceiling immediately.

Re-derive after an INTENTIONAL executor change with:

    TRACE_SF=0.02 TRACE_SPLIT_ROWS=4096 TRACE_QUERIES=q3,q9,q18 \
        JAX_PLATFORMS=cpu python scripts/query_counters.py --distributed --sites

Measured trace the ceilings derive from (2026-08-06, jax 0.7 CPU mesh):

    q3  warm: dist bytes 20586 (agg.groups 20480), pulled 20610
    q9  warm: dist bytes 9349, pulled 9403
    q18 warm: dist bytes 563, pulled 598
        (PR 30, re-measured through this file's own _warm_run: q18's semi-join
        moved under orders, inside the first join's build fragment
        (PushSemiJoinThroughJoin): dist bytes 554, pulled 592 where the parent
        read 563 and 619: one build fewer is sized and null-checked at the top
        level, and the split join's 4-byte count is new.  Ceilings unchanged.)

Ceilings sit at ~2x measured for group-count headroom.  A failure means a
bulk pull crept back into the mesh path — fix the path, don't bump the
ceiling.
"""

import jax
import numpy as np
import pytest

from trino_tpu import Engine
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.exec.distributed import DistributedExecutor
from trino_tpu.parallel.mesh import worker_mesh
from trino_tpu.sql.frontend import compile_sql

SF = 0.02
SPLIT_ROWS = 1 << 12

# inlined (budget-suite convention: the ceilings must not drift with a
# benchmark edit) — text matches chip_smoke.py's QUERIES
QUERIES = {
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

# warm, device-exchange mode: total bytes at dist.* sites / total host bytes
CEILINGS = {
    "q3": {"dist_bytes": 45_000, "host_bytes_pulled": 46_000},
    "q9": {"dist_bytes": 20_000, "host_bytes_pulled": 21_000},
    "q18": {"dist_bytes": 2_000, "host_bytes_pulled": 2_600},
}

# full-page exchange/stream spool sites: these existing warm at all means the
# device path silently degraded to the host spool
FORBIDDEN_WARM_SITES = ("dist.exchange.collect", "dist.stream.collect",
                        "dist.shards.pull")


def _frames_equal(a, b):
    assert len(a) == len(b)
    for ca, cb in zip(a.columns, b.columns):
        ga, gb = a[ca].to_numpy(), b[cb].to_numpy()
        if ga.dtype == object or gb.dtype == object:
            assert list(ga) == list(gb), ca
        else:
            np.testing.assert_array_equal(ga, gb, err_msg=ca)


@pytest.fixture(scope="module")
def dist_env():
    assert len(jax.devices()) >= 8, "conftest must force 8 CPU devices"
    engine = Engine()
    engine.register_catalog("tpch",
                            TpchConnector(sf=SF, split_rows=SPLIT_ROWS))
    session = engine.create_session("tpch")
    mesh = worker_mesh(8)
    baselines = {}
    plans = {}
    for name, sql in QUERIES.items():
        baselines[name] = engine.execute_sql(sql, session).to_pandas()
        plans[name] = compile_sql(sql, engine, session)
    return engine, mesh, plans, baselines


def _warm_run(engine, mesh, plan):
    """Cold + warm run on one executor; returns (warm frame, warm counters)."""
    ex = DistributedExecutor(engine.catalogs, mesh=mesh)
    ex.execute(plan)
    warm = ex.execute(plan).to_pandas()
    return warm, ex.counters


@pytest.mark.parametrize("name", list(QUERIES))
def test_mesh_warm_budget(dist_env, name):
    engine, mesh, plans, baselines = dist_env
    warm, c = _warm_run(engine, mesh, plans[name])
    # byte-identity vs the local executor (the acceptance contract)
    _frames_equal(warm, baselines[name])
    sites = c.sites
    for bad in FORBIDDEN_WARM_SITES:
        hits = [k for k in sites if bad in k]
        assert not hits, f"{name}: host-spool site live on the mesh: {hits}"
    dist_bytes = sum(v["bytes"] for k, v in sites.items() if "dist." in k)
    lim = CEILINGS[name]
    site_table = {k: v["bytes"] for k, v in sorted(sites.items())
                  if "dist." in k}
    assert dist_bytes <= lim["dist_bytes"], \
        f"{name}: dist-site bytes {dist_bytes} > {lim['dist_bytes']}: " \
        f"{site_table}"
    assert c.host_bytes_pulled <= lim["host_bytes_pulled"], \
        f"{name}: total pulled {c.host_bytes_pulled} > " \
        f"{lim['host_bytes_pulled']}: {site_table}"
