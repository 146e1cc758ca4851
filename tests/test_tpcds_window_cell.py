"""TPC-DS q51 on the served path (PR 42): the official text of query51.tpl against the
benchmark's reference with NULLs compared as NULLs, the cell's text (the two NULL-able
columns coalesced to -1) through the harness's positional comparison, a FULL OUTER JOIN
against pandas' outer merge, a cumulative ``sum(sum(x)) over (...)`` exact in cents, the
window operator's span and counters, and the guard of the cell's meaning: a replay
compiles nothing and still runs both group-bys and all three window kernels.
"""

import datetime
import decimal
import os
import re
import urllib.request

import numpy as np
import pandas as pd
import pytest

from benchmark.harness import compare
from benchmark.harness.hosttables import HostTables
from benchmark.harness.loader import ROOT, _load_module
from test_tpcds_hash_cell import _frame, _replayed
from trino_tpu import Engine
from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.connectors.tpcds import TpcdsConnector

SF = 0.01
DS_Q51 = _load_module(os.path.join(ROOT, "benchmark", "statements", "ds_q51.py"), "ds_q51")
SQL = DS_Q51.render(DS_Q51.VALIDATION)[0]
DECIMALS = ["web_sales", "store_sales", "web_cumulative", "store_cumulative"]


@pytest.fixture(scope="module")
def ds():
    conn = TpcdsConnector(sf=SF, split_rows=1 << 21)
    e = Engine()
    e.register_catalog("tpcds", conn)
    return e, conn, HostTables(conn, DS_Q51.TABLES)


def _cents(column):
    """A decimal answer column in whole cents, NULL as None."""
    return [None if v is None or v != v else int(round(float(v) * 100)) for v in column]


@pytest.mark.parametrize("dms", [1200, 1188])
def test_the_templates_text_against_the_reference_with_nulls_as_nulls(dms, ds):
    """query51.tpl as it is written: ``web_sales`` / ``store_sales`` are NULL wherever
    only one channel sold that (item, day), and stay NULL in the answer."""
    e, _, tables = ds
    p = {"dms": dms}
    got = _frame(e.execute_sql(DS_Q51.render_template(p)[0], e.create_session("tpcds")))
    want = DS_Q51.reference(tables, p, nulls=None)
    assert list(got.columns) == DS_Q51.COLUMNS == list(want.columns)
    assert len(got) == len(want) == 100
    assert [int(v) for v in got["item_sk"]] == [int(v) for v in want["item_sk"]]
    assert [str(v)[:10] for v in got["d_date"]] == [str(v)[:10] for v in want["d_date"]]
    for name in DECIMALS:
        assert _cents(got[name]) == _cents(want[name]), name
    # most rows have a NULL on one side, and no cumulative maximum of a kept row is NULL
    nulls = sum(v is None for name in ("web_sales", "store_sales") for v in _cents(got[name]))
    assert nulls >= 90
    assert None not in _cents(got["web_cumulative"]) + _cents(got["store_cumulative"])
    assert e.last_query_counters.device_dispatches > 0


def test_the_cells_text_is_the_template_but_for_two_coalesces(ds):
    cell, template = DS_Q51.render(DS_Q51.VALIDATION)[0], \
        DS_Q51.render_template(DS_Q51.VALIDATION)[0]
    assert cell != template and cell.replace(
        "select item_sk, d_date, coalesce(web_sales, -1) web_sales, "
        "coalesce(store_sales, -1) store_sales, web_cumulative, store_cumulative from (",
        "select * from (") == template
    assert "d_month_seq between 1200 and 1200+11" in cell and DS_Q51.VALIDATION == {"dms": 1200}


@pytest.mark.parametrize("dms", [1200, 1212])
def test_the_cells_text_compares_positionally_and_the_float32_control_fails(dms, ds):
    """``ds10_window_outer`` sends the template with the two NULL-able columns coalesced
    to -1: the harness's comparison then holds NULL-ness exactly.  The reference summed
    in float32 in the program's place fails ``max_rel_err``."""
    e, _, tables = ds
    p = {"dms": dms}
    got = _frame(e.execute_sql(DS_Q51.render(p)[0], e.create_session("tpcds")))
    want = DS_Q51.reference(tables, p)
    assert list(got.columns) == list(want.columns) and len(want) == 100
    numbers = compare.compare(got, want)
    assert compare.within_limits(numbers), numbers
    assert numbers["max_rel_err"] == 0  # whole cents under 2^53: every cell exact
    assert (want["web_sales"] == -1).sum() + (want["store_sales"] == -1).sum() >= 90
    control = compare.compare(DS_Q51.reference(tables, p, dtype=np.float32), want)
    assert not compare.within_limits(control), control
    assert control["max_rel_err"] > compare.LIMITS["max_rel_err"], control
    # an answer with a NULL where the reference has a number is counted, not excused
    broken = got.copy()
    broken.loc[3, "web_cumulative"] = None
    assert compare.compare(broken, want)["exact_mismatches"] == 1
    assert e.last_query_counters.device_dispatches > 0


# -- the outer join and the cumulative window, against pandas -------------------------------
def _memory_engine():
    e = Engine()
    e.register_catalog("mem", MemoryConnector())
    s = e.create_session("mem")
    e.execute_sql("create table w (k bigint, d date, v decimal(38,2))", s)
    e.execute_sql("create table s (k bigint, d date, v decimal(38,2))", s)
    e.execute_sql(
        "insert into w values (1, date '2000-01-01', 10.25), (1, date '2000-01-03', 0.75), "
        "(2, date '2000-01-01', 99999999999.99), (2, date '2000-01-02', 0.01), "
        "(4, date '2000-01-05', 5.00), (null, date '2000-01-01', 7.00)", s)
    e.execute_sql(
        "insert into s values (1, date '2000-01-01', 3.50), (1, date '2000-01-02', 4.00), "
        "(3, date '2000-01-01', 8.00), (2, date '2000-01-02', 12345678901.23), "
        "(null, date '2000-01-01', 9.00), (4, null, 1.00)", s)
    day = lambda x: None if x is None else datetime.date(2000, 1, x)  # noqa: E731
    w = pd.DataFrame({"k": [1, 1, 2, 2, 4, None], "d": [day(x) for x in (1, 3, 1, 2, 5, 1)],
                      "v": [1025, 75, 9999999999999, 1, 500, 700]})
    st = pd.DataFrame({"k": [1, 1, 3, 2, None, 4], "d": [day(x) for x in (1, 2, 1, 2, 1, None)],
                       "v": [350, 400, 800, 1234567890123, 900, 100]})
    return e, s, w, st


def _rows_in_cents(result):
    out = []
    for row in result.rows():
        out.append(tuple(int(v * 100) if isinstance(v, decimal.Decimal)
                         else (int(round(v * 100)) if isinstance(v, float) else v)
                         for v in row))
    return out


def _key(row):
    return tuple((v is None, str(v)) for v in row)


@pytest.mark.parametrize("keys", [("k",), ("k", "d")])
def test_a_full_outer_join_against_pandas_outer_merge(keys):
    """Unmatched rows on both sides, and a NULL key on each: a NULL key matches nothing,
    so each such row comes out once, NULL-extended (pandas' merge would match NaN keys
    with each other: they are merged apart)."""
    e, s, w, st = _memory_engine()
    on = " and ".join(f"w.{k} = s.{k}" for k in keys)
    got = _rows_in_cents(e.execute_sql(
        f"select w.k, w.d, w.v, s.k, s.d, s.v from w full outer join s on {on}", s))
    wn, sn = w[w[list(keys)].notna().all(axis=1)], st[st[list(keys)].notna().all(axis=1)]
    m = wn.merge(sn, on=list(keys), how="outer", suffixes=("_w", "_s"), indicator=True)
    want = []
    for _, r in m.iterrows():
        left = r["_merge"] in ("both", "left_only")
        right = r["_merge"] in ("both", "right_only")
        cell = lambda name, side, on_side: (  # noqa: E731
            None if not on_side else r[name] if name in keys else r[f"{name}_{side}"])
        want.append(tuple(cell(c, "w", left) for c in ("k", "d", "v"))
                    + tuple(cell(c, "s", right) for c in ("k", "d", "v")))
    for frame, pad_left in ((w, False), (st, True)):
        for _, r in frame[frame[list(keys)].isna().any(axis=1)].iterrows():
            row = (r["k"], r["d"], r["v"])
            want.append((None,) * 3 + row if pad_left else row + (None,) * 3)
    norm = lambda rows: sorted((tuple(  # noqa: E731
        None if v is None or v != v else (int(v) if not isinstance(v, datetime.date) else v)
        for v in row) for row in rows), key=_key)
    assert norm(got) == norm(want)
    unmatched_left = sum(1 for r in norm(got) if r[3] is None and r[5] is None)
    unmatched_right = sum(1 for r in norm(got) if r[0] is None and r[2] is None)
    assert unmatched_left >= 1 and unmatched_right >= 2  # a NULL key among each


def test_a_cumulative_sum_of_sums_is_exact_in_cents_on_a_decimal_38_2():
    """``sum(sum(v)) over (partition by k order by d rows between unbounded preceding and
    current row)``: a decimal(38,2) running sum, whole cents past 2^53 (a float64 sum
    would round them)."""
    e, s, w, _ = _memory_engine()
    e.execute_sql("insert into w values (2, date '2000-01-01', 0.02), "
                  "(2, date '2000-01-03', 90071992547409.93)", s)
    # (the result surface hands a decimal on as a float64: the cents are read as BIGINT)
    got = [tuple(r) for r in e.execute_sql(
        "select k, d, cast(100 * sum(sum(v)) over (partition by k order by d rows between "
        "unbounded preceding and current row) as bigint) c from w where k is not null "
        "group by k, d order by k, d", s).rows()]
    day = lambda x: datetime.date(2000, 1, x)  # noqa: E731
    assert got == [(1, day(1), 1025), (1, day(3), 1100),
                   (2, day(1), 10000000000001), (2, day(2), 10000000000002),
                   (2, day(3), 9017199254740995), (4, day(5), 500)]
    assert 9017199254740995 > 2 ** 53 and float(9017199254740995) != 9017199254740995


# -- the window operator's span and counters ------------------------------------------------
def test_a_replay_compiles_nothing_and_still_runs_three_window_kernels(ds):
    """The guard of the cell's meaning: the second copy of each CTE (the planner inlines
    a CTE at each use, and FULL OUTER JOIN is a left join UNION an anti join) is a BUILD
    side and stays inside the compiled stream, but each channel's group-by and window,
    and the window over the joined rows, run again in every execution.  A cumulative
    sum kept from one execution to the next would be a result cache."""
    _, conn, tables = ds
    e = Engine()
    e.register_catalog("tpcds", conn)
    e.execute_sql(SQL, e.create_session("tpcds"))
    first = e.last_query_counters
    w = _replayed(e, SQL, "tpcds")
    assert w.compiles == 0 and w.device_dispatches > 0
    assert first.window_kernels == 5 and w.window_kernels == 3
    dd = tables.columns("date_dim")
    days = dd["d_date_sk"][(dd["d_month_seq"] >= 1200) & (dd["d_month_seq"] <= 1211)]
    groups = 0
    for table, prefix in (("web_sales", "ws"), ("store_sales", "ss")):
        cols = tables.columns(table)
        keep = np.isin(cols[prefix + "_sold_date_sk"], days)
        groups += len(set(zip(cols[prefix + "_item_sk"][keep].tolist(),
                              cols[prefix + "_sold_date_sk"][keep].tolist())))
    # each channel's groups pass one window kernel and (outer-joined: a pair sold in both
    # channels once) the third; every kernel sorts its page once a key column at least
    assert groups <= w.window_lanes < first.window_lanes
    assert w.window_sort_lanes >= 2 * w.window_lanes
    # both (item, day) group-bys, over at least the year's sales of both channels
    year = sum(int(np.isin(tables.columns(t)[c], days).sum())
               for t, c in (("web_sales", "ws_sold_date_sk"), ("store_sales", "ss_sold_date_sk")))
    assert year <= w.groupby_insert_lanes < first.groupby_insert_lanes
    assert w.join_build_rows == 0 and w.groupby_regrows == 0
    assert w.join_hash_probe_lanes > 0  # the (item, day) key hashes in both joins


def test_the_window_span_says_what_it_was_handed(ds):
    e, _, _ = ds
    _replayed(e, SQL, "tpcds")
    trace = e.last_query_trace
    spans = [s for s in trace["spans"] if s["name"] == "window"]
    assert len(spans) == 3
    by_id = {s["span_id"]: s for s in trace["spans"]}
    root = [s for s in trace["spans"] if s["name"] == "query" and not s.get("parent_id")]
    assert len(root) == 1
    for s in spans:
        up = s
        while up.get("parent_id"):
            up = by_id[up["parent_id"]]
        assert up is root[0]  # under the statement's root span
        a = s["attributes"]
        assert a["partition_keys"] == [0] and a["order_keys"] == [1]
        assert a["lanes"] > 0 and ("rows" not in a or 0 < a["rows"] <= a["lanes"])
    assert sorted(tuple(s["attributes"]["functions"]) for s in spans) == \
        [("max", "max"), ("sum",), ("sum",)]
    assert sum(s["attributes"]["lanes"] for s in spans) == e.last_query_counters.window_lanes
    # a container of the wall breakdown: what no leaf span covers under it is named
    from trino_tpu.execution import tracing

    assert tracing._is_container("window")
    by = trace["wall_breakdown"]["unattributed_by"]
    assert "window" in by, by
    # (each part is rounded to a microsecond)
    assert abs(sum(by.values()) - trace["wall_breakdown"]["unattributed"]) < 1e-5


def test_explain_analyze_and_metrics_carry_the_window_series(ds):
    from test_profiling import _parse_prometheus
    from trino_tpu.server.server import CoordinatorServer

    _, conn, _ = ds
    e = Engine()
    e.register_catalog("tpcds", conn)
    before = e.counters_total.snapshot()
    r = e.execute_sql("explain analyze " + SQL, e.create_session("tpcds"))
    text = "\n".join(str(row[0]) for row in r.rows())
    c = e.last_query_counters
    m = re.search(r"Window: (\d+) kernels, (\d+) lanes, (\d+) lanes sorted", text)
    assert m, text
    assert tuple(map(int, m.groups())) == (c.window_kernels, c.window_lanes,
                                          c.window_sort_lanes)
    assert c.window_kernels >= 3
    # a statement without a window prints no such line
    plain = e.execute_sql("explain analyze select count(*) from item",
                          e.create_session("tpcds"))
    assert "Window:" not in "\n".join(str(row[0]) for row in plain.rows())
    after = e.counters_total
    srv = CoordinatorServer(e, port=0)
    srv.start()
    try:
        parsed = _parse_prometheus(urllib.request.urlopen(
            srv.url + "/v1/metrics", timeout=10).read().decode())
    finally:
        srv.stop()
    for field in ("window_kernels", "window_lanes", "window_sort_lanes"):
        assert parsed["types"][f"trino_tpu_{field}_total"] == "counter"
        assert parsed["samples"][f"trino_tpu_{field}_total"][0][1] == getattr(after, field)
        assert getattr(after, field) - getattr(before, field) >= getattr(c, field) > 0
        assert after.as_dict()[field] == getattr(after, field)


def test_the_sort_lanes_are_the_argsorts_the_kernel_runs(monkeypatch):
    """``window_sort_lanes`` is lanes x the stable argsorts of ``ops/window.window_order``
    (one a key column it is given): a key column of each distinct (partition, order)
    clause, one more for a nullable key's indicator, one for the pad mask.  The host's
    count (``_window_sort_passes``) is held to what the traced kernel really sorts."""
    from trino_tpu.ops import window as W

    sorted_keys = []
    real = W.window_order
    monkeypatch.setattr(W, "window_order", lambda kcols, desc: (
        sorted_keys.append(len(kcols)), real(kcols, desc))[1])
    e = Engine()
    e.register_catalog("mem", MemoryConnector())
    s = e.create_session("mem")
    e.execute_sql("create table t (k bigint, d bigint, v bigint)", s)
    e.execute_sql("insert into t values (1, 1, 5), (1, 2, 6), (2, 1, 7), (null, 1, 8)", s)
    got = e.execute_sql(
        "select k, d, sum(v) over (partition by k order by d) c, "
        "row_number() over (partition by k order by d) r, "
        "max(v) over (order by d, k) m from t order by k, d", s).rows()
    assert [tuple(None if v is None else int(v) for v in r) for r in got] == [
        (1, 1, 5, 1, 5), (1, 2, 11, 2, 8), (2, 1, 7, 1, 7), (None, 1, 8, 1, 8)]
    c = e.last_query_counters
    # two distinct clauses (the sum and the row_number share one): two sorts
    assert c.window_kernels == 1 and len(sorted_keys) == 2
    assert c.window_sort_lanes == c.window_lanes * sum(sorted_keys)
    assert 4 <= sum(sorted_keys) <= 10  # two keys a clause, indicators and pad or not
    # a group-by's keys are nullable and its page has a validity mask: q51's channel
    # windows sort five times a lane (pad, item's indicator, item, day's indicator, day)
    sorted_keys.clear()
    e.execute_sql("select k, d, sum(sum(v)) over (partition by k order by d rows between "
                  "unbounded preceding and current row) c from t group by k, d", s)
    c = e.last_query_counters
    assert c.window_sort_lanes == c.window_lanes * sum(sorted_keys) > 0
