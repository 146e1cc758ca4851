"""Join probes that match first and gather after (PR 28).

A unique-key inner or semi join over a probe stream still at scan width runs as
a match step (its own program: the upstream chain, then only what decides
``matched``), a per-batch compaction boundary (``_compacted_stream``: read the
live count, pack into n/4, n/16 or n/64, pass a dense batch through), and a
gather step fused into the consumer.  Answers must equal the pandas oracle's
whichever width a batch took, and ``join_match_lanes`` / ``join_gather_lanes``
say which it took.
"""

import functools
import os
import re
import urllib.request

import jax
import numpy as np
import pandas as pd
import pytest

from benchmark.harness.loader import _load_module
from trino_tpu import Engine
from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.exec import local_executor as le
from trino_tpu.ops import hashjoin

D = np.datetime64

Q3_CHAIN = """
    select l_orderkey, sum(l_extendedprice * (1 - l_discount)) revenue,
           o_orderdate, o_shippriority
    from customer, orders, lineitem
    where c_mktsegment = 'BUILDING' and c_custkey = o_custkey
      and l_orderkey = o_orderkey and o_orderdate < date '1995-03-15'
      and l_shipdate > date '1995-03-15'
    group by l_orderkey, o_orderdate, o_shippriority
    order by l_orderkey"""
Q18_CHAIN = """
    select o_orderkey, o_totalprice, sum(l_quantity) q, count(*) n
    from lineitem, orders where l_orderkey = o_orderkey
    group by o_orderkey, o_totalprice order by o_orderkey"""
# the benchmark's q18 (benchmark/statements/q18.py) at a QUANTITY that keeps a
# few orders at SF0.01: since PR 30 its IN-subquery filters orders inside the
# first join's build, so lineitem's first probe is the selective one
Q18_SEMI, _ = _load_module(os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "statements", "q18.py"),
    "q18").render({"quantity": 250})
SEMI = """
    select l_orderkey, count(*) n from lineitem
    where l_orderkey in (select o_orderkey from orders
                         where o_orderdate < date '1992-02-01')
    group by l_orderkey order by l_orderkey"""
HASHED = """
    select l_partkey, l_suppkey, count(*) n, sum(ps_availqty) a
    from lineitem, partsupp
    where l_partkey = ps_partkey and l_suppkey = ps_suppkey and ps_availqty < 500
    group by l_partkey, l_suppkey order by l_partkey, l_suppkey"""
DENSE_LATER = """
    select o_orderkey, o_totalprice, sum(l_quantity) q, count(*) n
    from lineitem, orders
    where l_orderkey = o_orderkey and (o_orderkey >= 7500 or o_orderkey = 1)
    group by o_orderkey, o_totalprice order by o_orderkey"""
EMPTY_BUILD = """
    select l_orderkey, count(*) n from lineitem, orders
    where l_orderkey = o_orderkey and o_orderdate < date '1900-01-01'
    group by l_orderkey order by l_orderkey"""


def _days(df, col):
    return df[col].to_numpy().astype("datetime64[D]")


def _oracle_q3(T):
    li, o, c = T["lineitem"], T["orders"], T["customer"]
    li = li[_days(li, "l_shipdate") > D("1995-03-15")]
    o = o[_days(o, "o_orderdate") < D("1995-03-15")]
    c = c[c.c_mktsegment == "BUILDING"]
    j = li.merge(o, left_on="l_orderkey", right_on="o_orderkey") \
          .merge(c, left_on="o_custkey", right_on="c_custkey")
    j = j.assign(rev=j.l_extendedprice * (1 - j.l_discount))
    g = j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                  as_index=False).agg(revenue=("rev", "sum"))
    return g.sort_values("l_orderkey")[
        ["l_orderkey", "revenue", "o_orderdate", "o_shippriority"]]


def _oracle_q18(T):
    j = T["lineitem"].merge(T["orders"], left_on="l_orderkey",
                            right_on="o_orderkey")
    return j.groupby(["o_orderkey", "o_totalprice"], as_index=False).agg(
        q=("l_quantity", "sum"), n=("l_quantity", "size")).sort_values("o_orderkey")


def _oracle_q18_semi(T):
    li, o, c = T["lineitem"], T["orders"], T["customer"]
    qty = li.groupby("l_orderkey").l_quantity.sum()
    j = o[o.o_orderkey.isin(qty[qty > 250].index)] \
        .merge(c, left_on="o_custkey", right_on="c_custkey") \
        .merge(li, left_on="o_orderkey", right_on="l_orderkey")
    g = j.groupby(["c_name", "c_custkey", "o_orderkey", "o_orderdate",
                   "o_totalprice"], as_index=False).agg(q=("l_quantity", "sum"))
    return g.sort_values(["o_totalprice", "o_orderdate"],
                         ascending=[False, True]).head(100)


def _oracle_dense_later(T):
    o = T["orders"]
    j = T["lineitem"].merge(o[(o.o_orderkey >= 7500) | (o.o_orderkey == 1)],
                            left_on="l_orderkey", right_on="o_orderkey")
    return j.groupby(["o_orderkey", "o_totalprice"], as_index=False).agg(
        q=("l_quantity", "sum"), n=("l_quantity", "size")).sort_values("o_orderkey")


def _oracle_semi(T):
    o = T["orders"]
    keys = o[_days(o, "o_orderdate") < D("1992-02-01")].o_orderkey
    li = T["lineitem"]
    li = li[li.l_orderkey.isin(keys)]
    return li.groupby("l_orderkey", as_index=False).agg(
        n=("l_quantity", "size")).sort_values("l_orderkey")


def _oracle_hashed(T):
    ps = T["partsupp"]
    ps = ps[ps.ps_availqty < 500]
    j = T["lineitem"].merge(ps, left_on=["l_partkey", "l_suppkey"],
                            right_on=["ps_partkey", "ps_suppkey"])
    return j.groupby(["l_partkey", "l_suppkey"], as_index=False).agg(
        n=("ps_availqty", "size"), a=("ps_availqty", "sum")) \
        .sort_values(["l_partkey", "l_suppkey"])


def _oracle_empty(T):
    return pd.DataFrame({"l_orderkey": np.zeros(0, np.int64),
                         "n": np.zeros(0, np.int64)})


# name -> (sql, oracle, what the boundary does)
CASES = {
    "q3_chain": (Q3_CHAIN, _oracle_q3, "packed"),     # selective first join
    # every live lane of the first page matches: compiled as ONE step, as ever
    "q18_chain": (Q18_CHAIN, _oracle_q18, "fused"),
    # the semi-join sits under orders (PushSemiJoinThroughJoin): few lanes match
    "q18_semi": (Q18_SEMI, _oracle_q18_semi, "packed"),
    # sparse first batch, dense later ones: those pass the boundary unpacked
    "dense_later": (DENSE_LATER, _oracle_dense_later, "mixed"),
    "semi": (SEMI, _oracle_semi, "packed"),
    "hashed": (HASHED, _oracle_hashed, "packed"),     # two-column key: a JoinTable
    "empty_build": (EMPTY_BUILD, _oracle_empty, "pruned"),  # no split survives
}


def _assert_equal(got, want):
    assert len(got) == len(want), (len(got), len(want))
    for gc, wc in zip(got.columns, want.columns):
        g, w = got[gc].to_numpy(), want[wc].to_numpy()
        if g.dtype.kind == "M" or w.dtype.kind == "M":
            assert (g.astype("datetime64[D]") == w.astype("datetime64[D]")).all(), gc
        elif g.dtype.kind == "f" or w.dtype.kind == "f":
            np.testing.assert_allclose(g.astype(np.float64), w.astype(np.float64),
                                       rtol=1e-9, atol=1e-6, err_msg=gc)
        else:
            assert (g == w).all(), gc


def _fresh_engine(tpch_sf001):
    e = Engine()
    e.register_catalog("tpch", tpch_sf001)
    return e


# STAGE_SLOTS_MIN 1: every direct table is "at" the gate (staged in both steps);
# the default leaves every SF0.01 table under it
@pytest.mark.parametrize("stage_min", [1, hashjoin.STAGE_SLOTS_MIN],
                         ids=["staged", "unstaged"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_split_join_matches_the_oracle(name, stage_min, tpch_sf001, tpch_pandas,
                                       monkeypatch):
    monkeypatch.setattr(hashjoin, "STAGE_SLOTS_MIN", stage_min)
    sql, oracle, boundary = CASES[name]
    e = Engine()
    # many small splits, so that "dense_later" has batches on both sides
    e.register_catalog("tpch", tpch_sf001 if boundary != "mixed"
                       else TpchConnector(sf=0.01, split_rows=1 << 11))
    want = oracle(tpch_pandas)
    for run in range(2):  # cold, then the replay of the compiled streams
        got = e.execute_sql(sql, e.create_session("tpch")).to_pandas()
        _assert_equal(got, want)
        c = e.last_query_counters
        if boundary in ("fused", "pruned"):
            assert c.join_match_lanes == 0, c.as_dict()  # on the replay too
            continue
        # (the cold run's first-page sample is a match step too, not counted)
        assert c.join_match_lanes > 0, c.as_dict()
        if boundary == "packed":
            assert c.join_gather_lanes * 4 <= c.join_match_lanes, c.as_dict()
            assert c.compactions >= 1
        else:
            assert c.join_match_lanes > c.join_gather_lanes * 4 // 3 \
                > c.join_match_lanes // 3, c.as_dict()
    if name == "hashed":
        # the build really is a hashed JoinTable, not a direct-address one
        from trino_tpu.ops.hashjoin import JoinTable
        tables = [a for ex in e._executor_pool for (_n, s) in
                  ex._stream_cache.values()
                  for a in jax.tree.leaves(s.aux, is_leaf=lambda x: isinstance(
                      x, (JoinTable, hashjoin.DirectJoinTable)))
                  if isinstance(a, (JoinTable, hashjoin.DirectJoinTable))]
        assert any(isinstance(t, JoinTable) for t in tables), tables


@pytest.fixture(scope="module")
def meng():
    e = Engine()
    e.register_catalog("mem", MemoryConnector())
    s = e.create_session("mem")
    e.execute_sql("create table p (k bigint, v bigint)", s)
    e.execute_sql("create table b (k bigint, w bigint)", s)
    e.execute_sql("create table nobody (k bigint, w bigint)", s)
    rows = ", ".join(f"({'null' if i % 3 == 0 else i % 40}, {i})"
                     for i in range(200))
    e.execute_sql(f"insert into p values {rows}", s)
    e.execute_sql("insert into b values (1, 10), (2, 20), (7, 70), (100, 1000)", s)
    return e, s


def _p_rows():
    return [(None if i % 3 == 0 else i % 40, i) for i in range(200)]


def test_null_probe_keys_never_match(meng):
    e, s = meng
    build = {1: 10, 2: 20, 7: 70, 100: 1000}
    got = e.execute_sql("select v, w from p, b where p.k = b.k order by v",
                        s).to_pandas()
    want = [(v, build[k]) for k, v in _p_rows() if k in build]
    assert list(zip(got.v, got.w)) == want and len(want) > 0
    c = e.last_query_counters
    assert c.join_match_lanes > 0 and c.join_gather_lanes < c.join_match_lanes
    got = e.execute_sql("select v from p where k in (select k from b) order by v",
                        s).to_pandas()
    assert list(got.v) == [v for k, v in _p_rows() if k in build]
    # NOT IN keeps its unmatched lanes: an anti join is never split
    got = e.execute_sql("select count(*) n from p where k not in (select k from b)",
                        s).to_pandas()
    assert list(got.n) == [sum(1 for k, _ in _p_rows()
                               if k is not None and k not in build)]
    assert e.last_query_counters.join_match_lanes == 0


def test_empty_build_side(meng):
    e, s = meng
    got = e.execute_sql("select v, w from p, nobody where p.k = nobody.k", s)
    assert len(got) == 0
    got = e.execute_sql("select v from p where k in (select k from nobody)", s)
    assert len(got) == 0
    # a left join keeps every probe row and is never split
    got = e.execute_sql("select count(*) n, count(w) m from p left join nobody "
                        "on p.k = nobody.k", s).to_pandas()
    assert (got.n[0], got.m[0]) == (200, 0)
    assert e.last_query_counters.join_match_lanes == 0


@functools.lru_cache(maxsize=1)
def lowered_steps(tpch_sf001):
    """{site: lowered text} of the steps one cold q3-shaped statement
    dispatches, with the static lanes of the page that entered the match step
    under ``"lanes"``: every ``_jit`` wrapper made while the statement compiles
    is watched, and each site's first call is lowered again from its shapes."""
    calls = {}
    orig = le._jit

    def spy(fn, site=None, **kw):
        run = orig(fn, site=site, **kw)
        label = site or getattr(fn, "__name__", "jit")

        def watched(*a, **k):
            shapes = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
                if hasattr(x, "shape") else x, (a, k))
            calls.setdefault(label, (run, shapes))
            return run(*a, **k)

        watched.__wrapped__ = run.__wrapped__
        watched.lower = run.lower
        return watched

    le._jit = spy
    try:
        e = _fresh_engine(tpch_sf001)
        e.execute_sql(Q3_CHAIN, e.create_session("tpch"))
    finally:
        le._jit = orig
    out = {site: run.lower(*a, **k).as_text()
           for site, (run, (a, k)) in calls.items()
           if site in ("join.match", "jc_fn", "agg.hash.prepare")}
    out["lanes"] = max(x.shape[0] for x in jax.tree.leaves(calls["join.match"][1])
                       if getattr(x, "shape", ()))
    return out


def _gather_widths(text):
    return [int(m) for m in re.findall(
        r"stablehlo\.gather.*?->\s*tensor<(\d+)x", text)]


def test_gather_step_of_a_packed_batch_holds_no_full_width_gather(tpch_sf001):
    steps = lowered_steps(tpch_sf001)
    n = steps["lanes"]
    # the match step: ONE gather at the page's width (``occ``), nothing scattered
    assert _gather_widths(steps["join.match"]) == [n]
    assert "scatter" not in steps["join.match"]
    # the consumer's step holds the gather step (rows[slot], three build
    # columns of orders) and the second join's probe: all at the bucket
    widths = _gather_widths(steps["agg.hash.prepare"])
    assert len(widths) >= 5, widths
    assert max(widths) <= n // 4, (widths, n)


def test_join_probe_counters_and_surfaces(tpch_sf001):
    """QueryCounters.join_match_lanes / join_gather_lanes: in EXPLAIN ANALYZE,
    the statement's snapshot, the engine's totals and /v1/metrics."""
    e = _fresh_engine(tpch_sf001)
    before = e.counters_total.snapshot()
    r = e.execute_sql("explain analyze " + Q3_CHAIN, e.create_session("tpch"))
    text = "\n".join(str(row[0]) for row in r.rows())
    m = re.search(r"Join probe: (\d+) lanes matched, (\d+) lanes gathered", text)
    assert m, text
    matched, gathered = map(int, m.groups())
    c = e.last_query_counters
    assert (matched, gathered) == (c.join_match_lanes, c.join_gather_lanes)
    # q3's statements read 6.25 % or less of their matched lanes gathered
    assert 0 < gathered * 16 <= matched
    after = e.counters_total
    assert after.join_match_lanes - before.join_match_lanes >= matched
    assert after.join_gather_lanes - before.join_gather_lanes >= gathered
    assert after.as_dict()["join_gather_lanes"] == after.join_gather_lanes
    # a statement without a join prints no line
    r = e.execute_sql("explain analyze select count(*) from orders",
                      e.create_session("tpch"))
    assert "Join probe:" not in "\n".join(str(row[0]) for row in r.rows())

    from test_profiling import _parse_prometheus
    from trino_tpu.server.server import CoordinatorServer

    srv = CoordinatorServer(e, port=0)
    srv.start()
    try:
        parsed = _parse_prometheus(urllib.request.urlopen(
            srv.url + "/v1/metrics", timeout=10).read().decode())
    finally:
        srv.stop()
    for field in ("join_match_lanes", "join_gather_lanes"):
        assert parsed["types"][f"trino_tpu_{field}_total"] == "counter"
        assert parsed["samples"][f"trino_tpu_{field}_total"][0][1] == \
            getattr(after, field)
