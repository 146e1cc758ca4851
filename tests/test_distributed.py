"""Distributed (8-virtual-worker SPMD) execution vs local single-device results.

Mirrors the reference's DistributedQueryRunner-vs-H2 pattern (SURVEY.md §4): the same query
runs on the worker mesh and on one device; results must match exactly.
"""

import numpy as np
import pandas as pd
import pytest

import jax

from trino_tpu.parallel.mesh import worker_mesh


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
    "q5": """
        select n_name, sum(l_extendedprice * (1 - l_discount)) as revenue
        from customer, orders, lineitem, supplier, nation, region
        where c_custkey = o_custkey and l_orderkey = o_orderkey
          and l_suppkey = s_suppkey and c_nationkey = s_nationkey
          and s_nationkey = n_nationkey and n_regionkey = r_regionkey
          and r_name = 'ASIA' and o_orderdate >= date '1994-01-01'
          and o_orderdate < date '1994-01-01' + interval '1' year
        group by n_name order by revenue desc""",
    "q6": """
        select sum(l_extendedprice * l_discount) as revenue
        from lineitem
        where l_shipdate >= date '1994-01-01'
          and l_shipdate < date '1994-01-01' + interval '1' year
          and l_discount between 0.05 and 0.07 and l_quantity < 24""",
    "scan_filter": """
        select o_orderkey, o_totalprice from orders
        where o_orderdate >= date '1998-01-01' and o_custkey < 50
        order by o_orderkey limit 50""",
    # north-star suite completion (round-1 VERDICT weak #3: Q9/Q18 shapes fell
    # back to local because of Project-above-Aggregate and null-aware semi)
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
                             having sum(l_quantity) > 100)
          and c_custkey = o_custkey and o_orderkey = l_orderkey
        group by c_name, c_custkey, o_orderkey, o_orderdate, o_totalprice
        order by o_totalprice desc, o_orderdate limit 100""",
    # streaming topN without an aggregate: per-worker device topN + host merge
    "topn_stream": """
        select l_orderkey, l_extendedprice from lineitem
        order by l_extendedprice desc, l_orderkey limit 7""",
    # residual join filter on a non-inner join (match condition, not post-filter)
    "left_filter": """
        select count(*) c, sum(o_totalprice) sp from orders
        left join customer on o_custkey = c_custkey and c_acctbal > 5000""",
    # NOT IN with an empty build set: every probe row survives
    "anti_empty": """
        select count(*) c from orders where o_custkey not in
        (select c_custkey from customer where c_acctbal > 99999999)""",
    # full ORDER BY without LIMIT: range-partitioned exchange + per-worker
    # device sort + host concat in rank order (round-2 VERDICT weak #9)
    "full_sort": """
        select o_orderkey, o_totalprice, o_orderdate from orders
        order by o_totalprice desc, o_orderkey""",
    # dictionary-encoded primary sort key: splitters live in collation-rank space
    "full_sort_dict": """
        select c_custkey, c_mktsegment from customer
        order by c_mktsegment, c_custkey desc""",
    # partitioned window: rows hash-routed so each worker owns whole partitions,
    # then the local window kernel runs per shard (round-2 VERDICT weak #9)
    "window_dist": """
        select o_custkey, o_orderkey, o_totalprice,
               row_number() over (partition by o_custkey order by o_totalprice desc,
                                  o_orderkey) rn,
               sum(o_totalprice) over (partition by o_custkey) tot,
               lag(o_orderkey) over (partition by o_custkey order by o_orderdate,
                                     o_orderkey) prev
        from orders order by o_custkey, o_orderkey""",
    # north-star Q4: EXISTS semi join distributed (bench suite member)
    "q4": """
        select o_orderpriority, count(*) as order_count from orders
        where o_orderdate >= date '1993-07-01'
          and o_orderdate < date '1993-07-01' + interval '3' month
          and exists (select 1 from lineitem where l_orderkey = o_orderkey
                      and l_commitdate < l_receiptdate)
        group by o_orderpriority order by o_orderpriority""",
    # global variance distributed (sum_sq accumulator through psum merge)
    "var_global": """
        select var_pop(l_discount) v, stddev_samp(l_quantity) s,
               sum(l_tax) t from lineitem where l_orderkey < 1000""",
    "window_dist_frame": """
        select o_custkey, o_orderkey,
               sum(o_totalprice) over (partition by o_custkey
                 order by o_orderdate, o_orderkey
                 rows between 1 preceding and current row) s
        from orders order by o_custkey, o_orderkey""",
}


def _frames_equal(a: pd.DataFrame, b: pd.DataFrame):
    assert len(a) == len(b)
    for ca, cb in zip(a.columns, b.columns):
        ga, gb = a[ca].to_numpy(), b[cb].to_numpy()
        if ga.dtype == object or gb.dtype == object:
            assert list(ga) == list(gb), ca
        else:
            np.testing.assert_allclose(ga.astype(np.float64), gb.astype(np.float64),
                                       rtol=1e-12, err_msg=ca)


@pytest.fixture(scope="module")
def mesh8():
    assert len(jax.devices()) >= 8, "conftest must force 8 CPU devices"
    return worker_mesh(8)


@pytest.mark.parametrize("name", list(QUERIES))
def test_distributed_matches_local(engine, mesh8, name):
    sql = QUERIES[name]
    session = engine.create_session("tpch")
    local = engine.execute_sql(sql, session).to_pandas()
    dist = engine.execute_sql(sql, session, distributed=True, mesh=mesh8).to_pandas()
    _frames_equal(dist, local)


def test_distributed_on_subset_mesh(engine):
    """Mesh smaller than the device count (2 workers)."""
    mesh = worker_mesh(2)
    session = engine.create_session("tpch")
    local = engine.execute_sql(QUERIES["q6"], session).to_pandas()
    dist = engine.execute_sql(QUERIES["q6"], session, distributed=True, mesh=mesh).to_pandas()
    _frames_equal(dist, local)


def test_distributed_not_in_empty_build_null_probe(engine, mesh8):
    """NOT IN against an EMPTY set is TRUE even for a NULL probe key (3VL:
    there is nothing to compare against) — NULL-keyed probe rows must survive,
    matching local (regression: distributed dropped them unconditionally)."""
    sql = ("select count(*) c from orders where "
           "(case when o_custkey < 5 then null else o_custkey end) not in "
           "(select c_custkey from customer where c_acctbal > 99999999)")
    session = engine.create_session("tpch")
    local = engine.execute_sql(sql, session).to_pandas()
    dist = engine.execute_sql(sql, session, distributed=True, mesh=mesh8).to_pandas()
    _frames_equal(dist, local)
    # every orders row survives, including the NULL-keyed ones
    n_orders = engine.execute_sql("select count(*) c from orders",
                                  session).to_pandas().iloc[0, 0]
    assert int(local.iloc[0, 0]) == int(n_orders)


def test_distributed_null_aware_anti_with_null_build(engine, mesh8):
    """NOT IN whose subquery yields a NULL: 3VL makes every membership test
    unknown, so zero rows survive — distributed must agree with local."""
    sql = ("select count(*) c from orders where o_custkey not in "
           "(select case when c_custkey < 5 then null else c_custkey end "
           " from customer)")
    session = engine.create_session("tpch")
    local = engine.execute_sql(sql, session).to_pandas()
    dist = engine.execute_sql(sql, session, distributed=True, mesh=mesh8).to_pandas()
    _frames_equal(dist, local)
    assert int(local.iloc[0, 0]) == 0


# lineitem ⋈ partsupp on partkey alone: BOTH sides carry duplicate keys, so
# whichever side builds needs the multi-match (position-links analog) strategy
DUP_KEY_Q = ("select l_partkey, count(*) n, sum(ps_supplycost) sc "
             "from lineitem, partsupp where l_partkey = ps_partkey "
             "group by l_partkey order by l_partkey limit 30")


@pytest.mark.parametrize("threshold", [8, 1 << 30],
                         ids=["partitioned", "broadcast"])
def test_multi_match_join_matches_local(engine, mesh8, threshold):
    """Duplicate-build-key joins run DISTRIBUTED (no silent local fallback) in
    both distribution modes: slot-grouped expansion per shard, overflow
    side-channel retries (VERDICT r2 #3)."""
    from trino_tpu.exec.distributed import DistributedExecutor
    from trino_tpu.sql.frontend import compile_sql

    s = engine.create_session("tpch")
    local = engine.execute_sql(DUP_KEY_Q, s).to_pandas()
    ex = DistributedExecutor(engine.catalogs, mesh=mesh8,
                             partition_threshold=threshold)
    dist = ex.execute(compile_sql(DUP_KEY_Q, engine, s)).to_pandas()
    _frames_equal(dist, local)


def test_multi_match_left_join_matches_local(engine, mesh8):
    """LEFT joins against a duplicate-key build: unmatched probe rows survive
    with NULL build columns through the distributed expansion."""
    sql = ("select count(*) c, sum(ps_availqty) q from part "
           "left join partsupp on p_partkey = ps_partkey "
           "and ps_supplycost > 500")
    s = engine.create_session("tpch")
    local = engine.execute_sql(sql, s).to_pandas()
    from trino_tpu.exec.distributed import DistributedExecutor
    from trino_tpu.sql.frontend import compile_sql

    ex = DistributedExecutor(engine.catalogs, mesh=mesh8,
                             partition_threshold=8)
    dist = ex.execute(compile_sql(sql, engine, s)).to_pandas()
    _frames_equal(dist, local)


def test_probe_bucket_overflow_retries(engine, mesh8):
    """Force the first ladder rung to overflow (skewed partition ids) and
    assert the retry ladder still converges to the right answer: all rows of
    one key hash to ONE worker, so a ~2n/W probe bucket must overflow."""
    from trino_tpu.exec.distributed import DistributedExecutor
    from trino_tpu.sql.frontend import compile_sql

    # constant join key -> every probe row routes to the same partition
    sql = ("select count(*) c from "
           "(select 1 k, l_quantity from lineitem) l "
           "join (select 1 k, n_nationkey from nation) n on l.k = n.k")
    s = engine.create_session("tpch")
    local = engine.execute_sql(sql, s).to_pandas()
    ex = DistributedExecutor(engine.catalogs, mesh=mesh8,
                             partition_threshold=8)
    dist = ex.execute(compile_sql(sql, engine, s)).to_pandas()
    _frames_equal(dist, local)


def test_forget_plan_forgets_a_learned_probe_bucket(engine, mesh8):
    """A selective filter under a partitioned join's probe: one run learns the bucket its
    exchange needs (kept for the Join node, PR 33); ``forget_plan`` drops it with the rest,
    so a new plan node that lands on the old one's id starts from the ladder's own bucket."""
    from trino_tpu.exec.distributed import DistributedExecutor
    from trino_tpu.sql.frontend import compile_sql

    sql = ("select count(*) c, sum(l_quantity) q from lineitem, orders "
           "where l_orderkey = o_orderkey and l_shipdate > date '1995-03-15'")
    s = engine.create_session("tpch")
    local = engine.execute_sql(sql, s).to_pandas()
    ex = DistributedExecutor(engine.catalogs, mesh=mesh8, partition_threshold=8)
    plan = compile_sql(sql, engine, s)
    _frames_equal(ex.execute(plan).to_pandas(), local)
    learned = [k for k in ex._kept if k[1:] == ("probe_need",)]
    assert len(learned) == 1 and ex.counters.probe_exchange_lanes > 0
    _frames_equal(ex.execute(plan).to_pandas(), local)  # the narrowed fragment
    assert ex.counters.mesh_fragment_compiles > 0
    ex.forget_plan(plan)
    assert not [k for k in ex._kept if k[1:] == ("probe_need",)]
    assert not [k for k in ex._kept if k[0] == learned[0][0]]


def test_partitioned_join_matches_local(engine):
    """Hash-partitioned (all-to-all) join distribution vs broadcast/local results."""
    import numpy as np

    from trino_tpu.exec.distributed import DistributedExecutor
    from trino_tpu.sql.frontend import compile_sql

    s = engine.create_session("tpch")
    q = ("select l_orderkey, count(*) n, sum(l_quantity) q from lineitem, orders "
         "where l_orderkey = o_orderkey and o_orderdate < date '1994-01-01' "
         "group by l_orderkey order by l_orderkey limit 50")
    local = engine.execute_sql(q, s).to_pandas()
    ex = DistributedExecutor(engine.catalogs, partition_threshold=8)
    dist = ex.execute(compile_sql(q, engine, s)).to_pandas()
    assert len(dist) == len(local)
    for c in local.columns:
        np.testing.assert_allclose(dist[c].to_numpy().astype(float),
                                   local[c].to_numpy().astype(float), rtol=1e-9)
