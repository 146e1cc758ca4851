"""Streaming (sorted-input) aggregation (reference: the streaming aggregation
operator over pre-grouped input, operator/aggregation/): when the scan's
declared sort order makes group rows contiguous, segmented reduces replace
the hash probe loop."""

import numpy as np
import pytest

from trino_tpu import Engine
from trino_tpu.connectors.tpch import TpchConnector


@pytest.fixture()
def seng(monkeypatch):
    """Engine at a scale where the composite partsupp key exceeds the
    direct-index range (30 bits > 24), so sorted multi-key GROUP BYs take the
    streaming path; a counter asserts it actually runs."""
    import trino_tpu.exec.local_executor as LE

    calls = {"n": 0}
    orig = LE.LocalExecutor._run_streaming_aggregate

    def counting(self, *a, **k):
        calls["n"] += 1
        return orig(self, *a, **k)

    monkeypatch.setattr(LE.LocalExecutor, "_run_streaming_aggregate", counting)
    e = Engine()
    e.register_catalog("tpch", TpchConnector(sf=0.5, split_rows=1 << 17))
    return e, e.create_session("tpch"), calls


def _oracle(sql):
    e = Engine()
    e.register_catalog("tpch", TpchConnector(sf=0.5, split_rows=1 << 17))
    s = e.create_session("tpch")
    import trino_tpu.exec.local_executor as LE

    orig = LE.LocalExecutor._streaming_agg_order
    LE.LocalExecutor._streaming_agg_order = lambda self, st, nd: None
    try:
        return e.execute_sql(sql, s).to_pandas()
    finally:
        LE.LocalExecutor._streaming_agg_order = orig


def test_sorted_multikey_aggregation_streams(seng):
    e, s, calls = seng
    sql = ("select ps_suppkey, ps_partkey, sum(ps_supplycost) sc, count(*) c, "
           "min(ps_availqty) mn, max(ps_availqty) mx, avg(ps_supplycost) av "
           "from partsupp group by ps_suppkey, ps_partkey "
           "order by ps_partkey, ps_suppkey limit 15")
    got = e.execute_sql(sql, s).to_pandas()
    assert calls["n"] == 1, "streaming path did not activate"
    exp = _oracle(sql)
    assert got.values.tolist() == exp.values.tolist()


def test_streaming_agg_with_filter_masked_lanes(seng):
    e, s, calls = seng
    sql = ("select ps_partkey, ps_suppkey, sum(ps_supplycost) sc "
           "from partsupp where ps_availqty > 5000 "
           "group by ps_partkey, ps_suppkey "
           "order by sc desc, ps_partkey limit 10")
    got = e.execute_sql(sql, s).to_pandas()
    assert calls["n"] == 1
    exp = _oracle(sql)
    assert got.values.tolist() == exp.values.tolist()


def test_unsorted_keys_do_not_stream(seng):
    e, s, calls = seng
    # ps_suppkey alone is NOT a sort-order prefix: must not stream
    e.execute_sql("select ps_suppkey, count(*) c from partsupp "
                  "group by ps_suppkey order by ps_suppkey limit 5", s)
    assert calls["n"] == 0


def test_streaming_agg_overflow_grows_and_restreams(seng):
    """An undersized merge table overflows, grows 4x, and re-streams the
    input; results stay exact (covers the grow path's reservation deltas and
    pages() replayability)."""
    e, s, calls = seng
    e.execute_sql("set session group_by_capacity = 64", s)
    sql = ("select ps_partkey, ps_suppkey, sum(ps_availqty) q from partsupp "
           "where ps_partkey <= 2000 group by ps_partkey, ps_suppkey "
           "order by ps_partkey, ps_suppkey limit 20")
    got = e.execute_sql(sql, s).to_pandas()
    assert calls["n"] == 1
    exp = _oracle(sql)
    assert got.values.tolist() == exp.values.tolist()
