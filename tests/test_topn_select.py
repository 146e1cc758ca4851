"""TopN by selection (`ops/arrays.first_rows`, PR 27): the same rows in the same
order as the lexsort it stands in for, and the served TopN answers unchanged."""

import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu.exec import pages
from trino_tpu import Engine
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.ops.arrays import first_rows


@pytest.mark.parametrize("n,count", [(1000, 10), (37, 37), (5000, 100), (64, 1), (9, 4)])
def test_first_rows_is_the_head_of_a_lexsort(n, count):
    rng = np.random.default_rng(n * 31 + count)
    keys = (jnp.asarray(rng.integers(0, 5, n), jnp.int32),        # least significant
            jnp.asarray(rng.integers(0, 2, n), jnp.int8),
            jnp.asarray(~rng.integers(-3, 3, n).astype(np.int64)),
            jnp.asarray(rng.random(n) < 0.2))                      # most significant
    want = np.asarray(jnp.lexsort(keys))[:count]
    assert np.asarray(first_rows(keys, count)).tolist() == want.tolist()
    # extreme values are ordinary values
    wide = (jnp.asarray([np.iinfo(np.int64).max, 5, np.iinfo(np.int64).min, 5, 7]),)
    assert np.asarray(first_rows(wide, 5)).tolist() == [2, 1, 3, 4, 0]


SQLS = {
    "desc_then_asc": "select o_custkey, o_totalprice, o_orderdate from orders "
                     "order by o_totalprice desc, o_orderdate limit 25",
    "ties_and_nulls": "select o_orderpriority, nullif(o_shippriority, 0) z, count(*) c "
                      "from orders group by o_orderpriority, nullif(o_shippriority, 0) "
                      "order by z nulls first, c desc, o_orderpriority limit 3",
    "grouped": "select l_orderkey, sum(l_quantity) q from lineitem group by l_orderkey "
               "order by q desc, l_orderkey limit 40",
    "more_than_there_are": "select n_name, n_regionkey from nation "
                           "order by n_regionkey desc, n_name limit 1000",
}


@pytest.mark.parametrize("name", sorted(SQLS))
def test_served_topn_is_the_sorted_answers_head(name, monkeypatch):
    def engine():
        e = Engine()
        e.register_catalog("tpch", TpchConnector(sf=0.01))
        return e

    calls = []
    real = pages.first_rows
    monkeypatch.setattr(pages, "first_rows", lambda keys, count: calls.append(count) or real(keys, count))
    got = engine().execute_sql(SQLS[name]).to_pandas()
    assert calls, "the selection did not take this TopN"
    monkeypatch.setattr(pages, "TOPN_SELECT_MAX", 0)  # the sort takes it
    del calls[:]
    want = engine().execute_sql(SQLS[name]).to_pandas()
    assert not calls
    assert got.values.tolist() == want.values.tolist() and len(got) > 0
