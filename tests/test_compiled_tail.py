"""A group-by's finalize and the device Sort/TopN as compiled programs (PR 39): the
same bytes as the eager/host path they stand in for, and a counter that says which ran.

The compiled path is ``LocalExecutor._device_finalize``'s program (pack at the bucket,
finalize, envelope flag, count, validity mask) and ``_sorted_rows`` (ranks, keys, sort or
selection, gathers, narrowing, bit-packing).  The path it is compared with is the one
the executor falls back to by itself: the host-exact finalize (``_finalize_aggs``) and
the host sort (``_sort_page`` / ``_topn_page``), forced here by taking the two device
entries away.
"""

import urllib.request
from decimal import Decimal

import jax.numpy as jnp
import numpy as np
import pytest

import trino_tpu.exec.local_executor as LE
from trino_tpu.exec import pages
from trino_tpu import Engine
from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.page import Field, Page, Schema
from trino_tpu.sql import plan as P
from trino_tpu.types import BIGINT, BOOLEAN


def engine():
    e = Engine()
    e.register_catalog("tpch", TpchConnector(sf=0.01))
    e.register_catalog("mem", MemoryConnector())
    s = e.create_session("mem")
    e.execute_sql("create table t (g bigint, s varchar, b boolean, x double)", s)
    e.execute_sql(
        "insert into t values (1, 'pear', true, 2.0), (1, 'fig', false, 8.0), "
        "(2, 'fig', true, 3.0), (2, null, true, 9.0), (null, 'apple', null, 1.0), "
        "(null, 'apple', false, 4.0), (3, null, null, 5.0), (3, 'pear', true, 6.0), "
        "(4, 'quince', false, 7.0), (null, null, true, 0.5)", s)
    e.execute_sql("create table big (g bigint, v decimal(18,2))", s)
    rows = ", ".join(f"({i % 2}, {8_600_000_000_000_000 + i * 7}.25)" for i in range(40))
    e.execute_sql(f"insert into big values {rows}", s)
    return e, s


# name -> (catalog, statement, (tail_compiled, tail_eager) of the compiled run)
CASES = {
    "nulls_first": ("mem", "select g, count(*) c from t group by g "
                           "order by g nulls first", (2, 0)),
    "nulls_last_desc": ("mem", "select g, s, count(*) c from t group by g, s "
                               "order by g desc nulls last, s nulls first", (2, 0)),
    "desc_keys": ("mem", "select s, b, count(*) c, sum(x) q from t group by s, b "
                         "order by c desc, s desc, b desc", (2, 0)),
    "string_key": ("tpch", "select o_orderpriority, o_orderstatus, count(*) c "
                           "from orders group by o_orderpriority, o_orderstatus "
                           "order by o_orderstatus desc, o_orderpriority", (2, 0)),
    "boolean_key": ("mem", "select b, count(*) c, sum(x) q from t group by b "
                           "order by b nulls first", (2, 0)),
    "limit_under_select_max": (
        "tpch", "select l_orderkey, sum(l_quantity) q from lineitem group by l_orderkey "
                "order by q desc, l_orderkey limit 40", (2, 0)),
    "limit_over_select_max": (
        "tpch", f"select l_orderkey, sum(l_quantity) q from lineitem group by l_orderkey "
                f"order by q desc, l_orderkey limit {pages.TOPN_SELECT_MAX + 500}", (2, 0)),
    "limit_over_the_groups": ("mem", "select s, count(*) c from t group by s "
                                     "order by c, s nulls last limit 100", (2, 0)),
    "float_key": ("mem", "select g, sum(x) q from t group by g order by q desc limit 3",
                  (2, 0)),
    "one_group": ("mem", "select g, count(*) c from t where g = 4 group by g order by g",
                  (2, 0)),
    # an empty group-by hands the Sort a page of no lanes: the host path, as before
    "no_group": ("mem", "select g, count(*) c from t where g > 99 group by g order by g",
                 (1, 1)),
    # a sum past 2^62: the envelope flag sends the finalize to the host-exact path, and
    # the Sort then sees a host-resident page with an object column
    "out_of_envelope_sum": ("mem", "select g, sum(v) s, count(*) c from big group by g "
                                   "order by g desc", (0, 2)),
}


def same_bytes(got, want):
    assert got.names == want.names and got.types == want.types
    assert len(got) == len(want)
    for a, b in list(zip(got.columns, want.columns)) \
            + list(zip(got.raw_columns, want.raw_columns)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype, (a.dtype, b.dtype)
        assert a.tolist() == b.tolist()
        if a.dtype != object:
            assert a.tobytes() == b.tobytes()


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_compiled_tail_answers_as_the_fallback_does(name, monkeypatch):
    catalog, sql, tail = CASES[name]
    e, _ = engine()
    session = e.create_session(catalog)
    first = e.execute_sql(sql, session)
    got = e.execute_sql(sql, session)  # the replay: the bucket is learned, one pull less
    counters = e.last_query_counters
    assert (counters.tail_compiled, counters.tail_eager) == tail
    same_bytes(first, got)
    for module in (LE, pages):  # the executor's TopN, and pages' own full sort
        monkeypatch.setattr(module, "_topn_page_device", lambda *args, **kwargs: None)
    monkeypatch.setattr(LE.LocalExecutor, "_device_finalize", lambda self, node: None)
    e, _ = engine()
    want = e.execute_sql(sql, e.create_session(catalog))
    counters = e.last_query_counters
    assert (counters.tail_compiled, counters.tail_eager) == (0, 2)
    same_bytes(got, want)
    if name == "out_of_envelope_sum":
        assert all(isinstance(v, Decimal) for v in got.columns[1])
        assert got.columns[1][0] == sum(
            Decimal(f"{8_600_000_000_000_000 + i * 7}.25") for i in range(40) if i % 2)


def page_of(n, live):
    """A packed device page of ``n`` lanes: a nullable bigint key, a boolean, a payload."""
    rng = np.random.default_rng(n * 7 + live)
    schema = Schema((Field("k", BIGINT), Field("b", BOOLEAN), Field("v", BIGINT)))
    cols = (jnp.asarray(rng.integers(-5, 5, n)), jnp.asarray(rng.random(n) < 0.5),
            jnp.asarray(rng.integers(0, 1 << 40, n)))
    nulls = (jnp.asarray(rng.random(n) < 0.3), None, None)
    return Page(schema, cols, nulls, jnp.arange(n) < live, live)


@pytest.mark.parametrize("live", [0, 1, 5, 64])
@pytest.mark.parametrize("count", [None, 1, 3, 1000])
def test_a_packed_pages_sort_is_the_host_sorts(live, count):
    """``_topn_page_device`` on a page that knows its live count (0 and 1 among them),
    against the host path on the same page: no ``sort.count`` pull, the same rows."""
    page = page_of(64, live)
    keys = (P.SortKey(0, False, True), P.SortKey(1, True, False), P.SortKey(2, True, False))
    from trino_tpu.execution import tracing

    counters = tracing.QueryCounters()
    with tracing.track_counters(counters):
        got = pages._topn_page_device(page, keys, count)
    assert not any(site.endswith("sort.count") for site in counters.sites), counters.sites
    plain = Page(page.schema, page.columns, page.null_masks, page.valid)
    want = pages._sort_page(plain, keys) if count is None else pages._topn_page(plain, keys, count)
    assert got.capacity == want.capacity == min(live, live if count is None else count)
    for a, b in zip(got.columns, want.columns):
        assert np.asarray(a).dtype == np.asarray(b).dtype
        assert np.asarray(a).tolist() == np.asarray(b).tolist()
    for a, b in zip(got.null_masks, want.null_masks):
        assert (a is None) == (b is None)
        assert a is None or np.asarray(a).tolist() == np.asarray(b).tolist()
    # a page that does NOT know its count pays the one pull, and answers the same
    with tracing.track_counters(counters):
        again = pages._topn_page_device(plain, keys, count)
    assert any(site.endswith("sort.count") for site in counters.sites) == (count is None)
    for a, b in zip(again.columns, want.columns):
        assert np.asarray(a).tolist() == np.asarray(b).tolist()


def test_the_counters_show_in_explain_analyze_and_the_metrics():
    from trino_tpu.server.client import Client
    from trino_tpu.server.server import CoordinatorServer

    e, _ = engine()
    session = e.create_session("mem")
    sql = CASES["nulls_first"][1]
    text = "\n".join(str(r[0]) for r in e.execute_sql("explain analyze " + sql,
                                                      session).rows())
    assert "Tail: 2 compiled, 0 eager" in text, text
    text = "\n".join(str(r[0]) for r in e.execute_sql(
        "explain analyze " + CASES["out_of_envelope_sum"][1], session).rows())
    assert "Tail: 0 compiled, 2 eager" in text, text
    server = CoordinatorServer(e, port=0)
    server.start()
    try:
        before = (e.counters_total.tail_compiled, e.counters_total.tail_eager)
        Client(server.url, catalog="mem").execute(sql)
        # (the protocol has no JSON for an exact wide decimal: the empty group-by's
        # Sort is the eager one here)
        Client(server.url, catalog="mem").execute(CASES["no_group"][1])
        body = urllib.request.urlopen(server.url + "/v1/metrics").read().decode()
        for series, grew, index in (("trino_tpu_tail_compiled_total", 3, 0),
                                    ("trino_tpu_tail_eager_total", 1, 1)):
            lines = [ln for ln in body.splitlines() if ln.startswith(series + " ")]
            assert len(lines) == 1, (series, lines)
            assert int(lines[0].split()[-1]) == before[index] + grew \
                == getattr(e.counters_total, series[len("trino_tpu_"):-len("_total")])
    finally:
        server.stop()
