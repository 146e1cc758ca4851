"""Aggregations over a multi-split scan on the per-split page loop: a direct
group-by, a hash group-by after a join, a global aggregate, an undersized
``group_by_capacity`` and an EXISTS semi-join feeding a group-by.  Each answer
is the same at ``dispatch_batch`` 1 and 4 and equals pandas over the
connector's host columns (SF0.02, 2^13-row splits: 15 lineitem splits; the
overflow case reads partsupp at SF0.1, the smallest scale at which a clustered
key is too wide for the direct-indexed table that no capacity binds)."""

import functools

import pandas as pd
import pytest

from trino_tpu import Engine
from trino_tpu.connectors.tpch import TpchConnector

from test_dispatch_batch import _assert_results_identical
from test_sql_tpch import D, assert_frames_close, dcol

SPLIT_ROWS = 1 << 13


@functools.lru_cache(maxsize=None)
def _connector(sf):
    return TpchConnector(sf=sf, split_rows=SPLIT_ROWS)


@functools.lru_cache(maxsize=None)
def _host_table(sf, table):
    """One table of the connector as a pandas frame (decoded)."""
    c = _connector(sf)
    return pd.concat(
        [pd.DataFrame(c.generate(s).to_numpy(c.dictionaries(table)))
         for s in c.splits(table)], ignore_index=True)


def _direct_groupby(t):
    li = t("lineitem")
    df = li[dcol(li, "l_shipdate") <= D("1998-09-02")]
    return df.groupby(["l_returnflag", "l_linestatus"], as_index=False).agg(
        q=("l_quantity", "sum"), c=("l_quantity", "size")) \
        .sort_values(["l_returnflag", "l_linestatus"])


def _hash_groupby_after_join(t):
    li, o = t("lineitem"), t("orders")
    j = li[dcol(li, "l_shipdate") > D("1995-03-15")].merge(
        o[dcol(o, "o_orderdate") < D("1995-03-15")],
        left_on="l_orderkey", right_on="o_orderkey")
    j = j.assign(rev=j.l_extendedprice * (1 - j.l_discount))
    g = j.groupby("l_orderkey", as_index=False).agg(rev=("rev", "sum"))
    return g.sort_values(["rev", "l_orderkey"], ascending=[False, True]).head(10)


def _global_aggregate(t):
    li = t("lineitem")
    df = li[li.l_discount > 0.03]
    return pd.DataFrame({"c": [len(df)], "se": [df.l_extendedprice.sum()],
                         "mn": [df.l_discount.min()], "mx": [df.l_tax.max()]})


def _capacity_overflow(t):
    g = t("partsupp").groupby(["ps_partkey", "ps_suppkey"], as_index=False) \
        .agg(q=("ps_availqty", "sum"))
    return g.sort_values(["ps_partkey", "ps_suppkey"]).head(20)


def _semi_join_groupby(t):
    li, o = t("lineitem"), t("orders")
    late = li[dcol(li, "l_commitdate") < dcol(li, "l_receiptdate")].l_orderkey
    od = dcol(o, "o_orderdate")
    df = o[(od >= D("1993-07-01")) & (od < D("1993-10-01"))
           & o.o_orderkey.isin(late)]
    return df.groupby("o_orderpriority", as_index=False).agg(
        c=("o_orderkey", "size")).sort_values("o_orderpriority")


CASES = {
    "direct_groupby": (
        "select l_returnflag, l_linestatus, sum(l_quantity) q, count(*) c "
        "from lineitem where l_shipdate <= date '1998-09-02' "
        "group by l_returnflag, l_linestatus "
        "order by l_returnflag, l_linestatus", _direct_groupby, 0.02),
    "hash_groupby_after_join": (
        "select l_orderkey, sum(l_extendedprice * (1 - l_discount)) rev "
        "from orders, lineitem "
        "where l_orderkey = o_orderkey and o_orderdate < date '1995-03-15' "
        "and l_shipdate > date '1995-03-15' "
        "group by l_orderkey order by rev desc, l_orderkey limit 10",
        _hash_groupby_after_join, 0.02),
    "global_aggregate": (
        "select count(*) c, sum(l_extendedprice) se, min(l_discount) mn, "
        "max(l_tax) mx from lineitem where l_discount > 0.03",
        _global_aggregate, 0.02),
    # 64 slots for 80,000 groups on the sorted path: the merge table overflows,
    # grows fourfold and the scan runs again, six times
    "capacity_overflow": (
        "select ps_partkey, ps_suppkey, sum(ps_availqty) q from partsupp "
        "group by ps_partkey, ps_suppkey "
        "order by ps_partkey, ps_suppkey limit 20", _capacity_overflow, 0.1),
    # dynamic-filter pruned splits under the semi join
    "semi_join_groupby": (
        "select o_orderpriority, count(*) c from orders "
        "where o_orderdate >= date '1993-07-01' "
        "and o_orderdate < date '1993-10-01' "
        "and exists (select 1 from lineitem where l_orderkey = o_orderkey "
        "and l_commitdate < l_receiptdate) "
        "group by o_orderpriority order by o_orderpriority",
        _semi_join_groupby, 0.02),
}


@pytest.mark.parametrize("name", list(CASES))
def test_page_loop_aggregate(name):
    sql, oracle, sf = CASES[name]
    e = Engine()
    e.register_catalog("tpch", _connector(sf))
    results = []
    for batch in (1, 4):
        s = e.create_session("tpch")
        e.session_properties.set_property(s, "dispatch_batch", batch)
        if name == "capacity_overflow":
            e.execute_sql("set session group_by_capacity = 64", s)
        results.append(e.execute_sql(sql, s))
        if name == "capacity_overflow":
            assert e.last_query_counters.groupby_regrows >= 1, \
                e.last_query_counters.as_dict()
    _assert_results_identical(*results, name)
    assert_frames_close(results[0].to_pandas(),
                        oracle(functools.partial(_host_table, sf))
                        .reset_index(drop=True), atol=0.01)
