"""Scan-fused aggregation: the whole scan (generate -> filter/project/join
probes -> group insert) runs inside one ``lax.scan`` over split offsets — O(1)
host dispatches instead of O(splits) (reference analog: the zero-per-page
scheduler cost of operator/Driver.java:372-481, re-designed for a device
where every dispatch is a host-side launch)."""

import numpy as np
import pytest

from trino_tpu import Engine
from trino_tpu.connectors.tpch import TpchConnector


@pytest.fixture()
def feng(monkeypatch):
    """Engine with a counter on the fused path: calls['n'] counts fused-path
    executions that actually took the query (returned a result).  The fused
    paths gate off on the CPU backend by default; force them on here."""
    import trino_tpu.exec.local_executor as LE

    monkeypatch.setenv("TRINO_TPU_SCAN_FUSED", "1")

    calls = {"n": 0, "global": 0}
    orig = LE.LocalExecutor._run_aggregate_scan_fused
    orig_g = LE.LocalExecutor._run_global_scan_fused

    def counting(self, *a, **k):
        out = orig(self, *a, **k)
        if out is not None:
            calls["n"] += 1
        return out

    def counting_g(self, *a, **k):
        out = orig_g(self, *a, **k)
        if out is not None:
            calls["global"] += 1
        return out

    monkeypatch.setattr(LE.LocalExecutor, "_run_aggregate_scan_fused", counting)
    monkeypatch.setattr(LE.LocalExecutor, "_run_global_scan_fused", counting_g)
    e = Engine()
    e.register_catalog("tpch", TpchConnector(sf=0.02, split_rows=1 << 13))
    return e, e.create_session("tpch"), calls


def _oracle(sql):
    """Same query with the fused paths disabled (page-loop execution)."""
    import trino_tpu.exec.local_executor as LE

    e = Engine()
    e.register_catalog("tpch", TpchConnector(sf=0.02, split_rows=1 << 13))
    s = e.create_session("tpch")
    orig = LE.LocalExecutor._run_aggregate_scan_fused
    orig_g = LE.LocalExecutor._run_global_scan_fused
    LE.LocalExecutor._run_aggregate_scan_fused = lambda self, *a, **k: None
    LE.LocalExecutor._run_global_scan_fused = lambda self, *a, **k: None
    try:
        return e.execute_sql(sql, s).to_pandas()
    finally:
        LE.LocalExecutor._run_aggregate_scan_fused = orig
        LE.LocalExecutor._run_global_scan_fused = orig_g


def test_fused_direct_groupby(feng):
    e, s, calls = feng
    sql = ("select l_returnflag, l_linestatus, sum(l_quantity) q, count(*) c "
           "from lineitem where l_shipdate <= date '1998-09-02' "
           "group by l_returnflag, l_linestatus "
           "order by l_returnflag, l_linestatus")
    got = e.execute_sql(sql, s).to_pandas()
    assert calls["n"] == 1, "fused path did not take the grouped aggregation"
    exp = _oracle(sql)
    assert got.values.tolist() == exp.values.tolist()


def test_fused_hash_groupby_after_join(feng):
    e, s, calls = feng
    sql = ("select l_orderkey, sum(l_extendedprice * (1 - l_discount)) rev "
           "from orders, lineitem "
           "where l_orderkey = o_orderkey and o_orderdate < date '1995-03-15' "
           "and l_shipdate > date '1995-03-15' "
           "group by l_orderkey order by rev desc, l_orderkey limit 10")
    got = e.execute_sql(sql, s).to_pandas()
    assert calls["n"] >= 1, "fused path did not take the join+agg pipeline"
    exp = _oracle(sql)
    assert np.allclose(got["rev"].values, exp["rev"].values)
    assert got["l_orderkey"].values.tolist() == exp["l_orderkey"].values.tolist()


def test_fused_global_agg(feng):
    e, s, calls = feng
    sql = ("select count(*) c, sum(l_extendedprice) se, min(l_discount) mn, "
           "max(l_tax) mx from lineitem where l_discount > 0.03")
    got = e.execute_sql(sql, s).to_pandas()
    assert calls["global"] == 1, "fused path did not take the global aggregation"
    exp = _oracle(sql)
    assert got.values.tolist() == exp.values.tolist()


def test_fused_growth_on_undersized_capacity(feng):
    """A tiny session capacity forces in-fused-path overflow: the table grows
    4x and the scan re-runs; results stay exact."""
    e, s, calls = feng
    e.execute_sql("set session group_by_capacity = 64", s)
    sql = ("select l_suppkey, count(*) c from lineitem "
           "group by l_suppkey order by l_suppkey limit 20")
    got = e.execute_sql(sql, s).to_pandas()
    assert calls["n"] >= 1
    exp = _oracle(sql)
    assert got.values.tolist() == exp.values.tolist()


def test_fused_semi_join_agg(feng):
    """EXISTS semi join (dynamic-filter pruned splits) feeding an aggregation:
    the kept-split list must flow into the fused scan."""
    e, s, calls = feng
    sql = ("select o_orderpriority, count(*) c from orders "
           "where o_orderdate >= date '1993-07-01' "
           "and o_orderdate < date '1993-10-01' "
           "and exists (select 1 from lineitem where l_orderkey = o_orderkey "
           "and l_commitdate < l_receiptdate) "
           "group by o_orderpriority order by o_orderpriority")
    got = e.execute_sql(sql, s).to_pandas()
    exp = _oracle(sql)
    assert got.values.tolist() == exp.values.tolist()
