"""The group-by's sizing counters and its ``aggregate.<mode>`` span (PR 27): each
mode once on the CPU, read from the statement's snapshot, its trace, EXPLAIN
ANALYZE and /v1/metrics."""

import re
import urllib.request

import pytest

import trino_tpu.exec.local_executor as LE
from trino_tpu import Engine
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.ops import hashagg, hashing

INNER_Q18 = ("select l_orderkey, sum(l_quantity) q from lineitem "
             "group by l_orderkey order by q desc, l_orderkey limit 5")
LINEITEM_ROWS = 60_000  # TpchConnector.row_count at SF0.01: four lines an order


def _engine(split_rows=4096):
    e = Engine()
    e.register_catalog("tpch", TpchConnector(sf=0.01, split_rows=split_rows))
    return e


def _agg_spans(e):
    spans = e.last_query_trace["spans"]
    root = next(s for s in spans if s["name"] == "query" and s["parent_id"] is None)
    by_id = {s["span_id"]: s for s in spans}
    out = []
    for s in spans:
        if s["name"].startswith("aggregate."):
            top = s
            while top["parent_id"] is not None:
                top = by_id[top["parent_id"]]
            assert top is root, "an aggregate span outside the root query span"
            out.append(s["name"])
    return out


def _no_direct(monkeypatch):
    monkeypatch.setattr(hashagg, "direct_config", lambda *a, **k: None)


def _no_sorted(monkeypatch):
    monkeypatch.setattr(LE.LocalExecutor, "_streaming_agg_order",
                        lambda self, stream, node: None)


def _mode_direct(monkeypatch):
    return ["aggregate.direct"], dict(regrows=0, passes=0, slots=1 << 14)


def _mode_sorted(monkeypatch):
    _no_direct(monkeypatch)
    return ["aggregate.sorted"], dict(regrows=0, passes=0, slots=None)


def _mode_hash(monkeypatch):
    _no_direct(monkeypatch)
    _no_sorted(monkeypatch)
    return ["aggregate.hash"], dict(regrows=0, passes=0, slots=None)


def _mode_hash_grown_in_loop(monkeypatch):
    # an in-loop rehash replays one chunk, not the input: not a regrow
    _no_direct(monkeypatch)
    _no_sorted(monkeypatch)
    monkeypatch.setattr(LE.LocalExecutor, "_agg_capacity_estimate",
                        lambda self, stream, node, key_ranges: None)
    monkeypatch.setattr(LE, "DEFAULT_GROUP_CAPACITY", 1024)
    return ["aggregate.hash"], dict(regrows=0, passes=0, slots=1 << 14)


def _mode_partitioned(monkeypatch):
    _no_direct(monkeypatch)
    _no_sorted(monkeypatch)
    monkeypatch.setattr(LE.LocalExecutor, "_agg_capacity_estimate",
                        lambda self, stream, node, key_ranges: None)
    monkeypatch.setattr(LE, "DEFAULT_GROUP_CAPACITY", 1024)
    monkeypatch.setattr(LE, "MAX_GROUP_CAPACITY", 8192)
    # the hash table meets the ceiling part of the way through the source, its
    # input goes to four partitions (one more scan), and each partition's
    # table, seeded at a quarter of the ceiling, overflows once and replays
    # its spilled rows
    return ["aggregate.hash", "aggregate.partitioned"], \
        dict(regrows=5, scans=(1, 2), passes=4, slots=None)


def _mode_sorted_regrown(monkeypatch):
    _no_direct(monkeypatch)
    monkeypatch.setattr(LE.LocalExecutor, "_agg_capacity_estimate",
                        lambda self, stream, node, key_ranges: None)
    monkeypatch.setattr(LE, "DEFAULT_GROUP_CAPACITY", 8192)
    return ["aggregate.sorted", "aggregate.sorted"], \
        dict(regrows=1, passes=0, slots=1 << 15)


@pytest.mark.parametrize("mode", [_mode_direct, _mode_sorted, _mode_hash,
                                  _mode_hash_grown_in_loop, _mode_partitioned,
                                  _mode_sorted_regrown],
                         ids=lambda f: f.__name__[len("_mode_"):])
def test_each_mode_counts_and_spans(mode, monkeypatch):
    want_spans, want = mode(monkeypatch)
    e = _engine()
    r = e.execute_sql(INNER_Q18)
    assert len(r) == 5
    c = e.last_query_counters
    assert _agg_spans(e) == want_spans
    assert c.groupby_regrows == want["regrows"]
    assert c.groupby_partitioned_passes == want["passes"]
    assert c.groupby_slots >= 15_000
    if want["slots"] is not None:
        assert c.groupby_slots == want["slots"]
    assert c.groupby_state_bytes > 0
    # every attempt over the source generates it again
    lo, hi = want.get("scans", (want["regrows"], 1 + want["regrows"]))
    assert lo * LINEITEM_ROWS < c.rows_generated <= hi * LINEITEM_ROWS
    assert c.join_build_rows == 0
    assert (c.spilled_bytes > 0) == (want["passes"] > 0)
    d = c.as_dict()
    for f in ("groupby_slots", "groupby_state_bytes", "groupby_regrows",
              "groupby_partitioned_passes", "join_build_rows", "rows_generated"):
        assert d[f] == getattr(c, f)


def test_join_build_rows_only_when_a_stream_is_compiled():
    e = _engine()
    sql = ("select o_orderpriority, count(*) c from orders, customer "
           "where o_custkey = c_custkey and c_mktsegment = 'BUILDING' "
           "group by o_orderpriority order by o_orderpriority")
    first = e.execute_sql(sql).to_pandas()
    built = e.last_query_counters.join_build_rows
    assert 0 < built <= 1_500
    for _ in range(3):  # the advisor may re-plan once after the cold run
        again = e.execute_sql(sql).to_pandas()
        if not e.last_query_counters.compiles:
            break
    assert again.values.tolist() == first.values.tolist()
    assert e.last_query_counters.compiles == 0
    assert e.last_query_counters.join_build_rows == 0


def test_surfaces_explain_analyze_and_metrics():
    from test_profiling import _parse_prometheus
    from trino_tpu.server.server import CoordinatorServer

    e = _engine()
    before = e.counters_total.snapshot()
    r = e.execute_sql("explain analyze " + INNER_Q18)
    text = "\n".join(str(row[0]) for row in r.rows())
    c = e.last_query_counters
    m = re.search(r"Group-by: (\d+) slots, (\d+) state bytes, (\d+) regrows, "
                  r"(\d+) partitioned passes", text)
    assert m, text
    assert tuple(map(int, m.groups())) == (
        c.groupby_slots, c.groupby_state_bytes, c.groupby_regrows,
        c.groupby_partitioned_passes)
    m = re.search(r"Scan: (\d+) rows generated, (\d+) join build rows", text)
    assert m, text
    assert tuple(map(int, m.groups())) == (c.rows_generated, c.join_build_rows)
    assert c.rows_generated == LINEITEM_ROWS
    after = e.counters_total
    assert after.groupby_slots - before.groupby_slots == c.groupby_slots
    assert after.rows_generated - before.rows_generated == c.rows_generated

    srv = CoordinatorServer(e, port=0)
    srv.start()
    try:
        parsed = _parse_prometheus(urllib.request.urlopen(
            srv.url + "/v1/metrics", timeout=10).read().decode())
    finally:
        srv.stop()
    for field in ("groupby_slots", "groupby_state_bytes", "groupby_regrows",
                  "groupby_partitioned_passes", "join_build_rows",
                  "rows_generated"):
        assert parsed["types"][f"trino_tpu_{field}_total"] == "counter"
        assert parsed["samples"][f"trino_tpu_{field}_total"][0][1] == \
            getattr(after, field)


def test_group_by_steps_are_named_by_mode():
    """Every group-by step is a device program named after its mode (``_jit``
    site = XLA module ``jit_<site>``), so a device trace groups by mode."""
    import inspect

    src = inspect.getsource(LE.LocalExecutor)
    for site in ("agg.direct.step", "agg.direct.batch", "agg.hash.prepare",
                 "agg.hash.prepare_batch", "agg.hash.insert_compact",
                 "agg.hash.insert_masked", "agg.sorted.step", "agg.sorted.batch",
                 "agg.sorted.merge", "agg.partitioned.route",
                 "agg.partitioned.insert"):
        assert f'site="{site}"' in src, site
    e = _engine()
    e.execute_sql(INNER_Q18)
    assert any(k.endswith("/agg.direct.batch") or k.endswith("/agg.direct.step")
               for k in e.last_query_counters.sites), e.last_query_counters.sites


# -- the lanes the hash insert's rounds ran over (PR 40), a width a level (PR 41) --------
HASHED = "select l_orderkey * 8 + l_linenumber k, count(*) n from lineitem group by 1"


def _insert_rounds(monkeypatch, sql, floor=None, split_rows=4096, capacity=None):
    """(counters of a replay, [(rounds at each width, lanes)] of its insert steps): the
    vectors are read where the executor pulls them, behind the chunk's overflow flag."""
    import numpy as np

    from trino_tpu.execution import tracing
    from trino_tpu.ops import hashing

    if floor is not None:
        monkeypatch.setattr(hashing, "INSERT_MIN_LANES", floor)
    e = _engine(split_rows)
    for _ in range(4):  # cold, the advisor's re-plan if it makes one, the replay
        session = e.create_session("tpch")
        if capacity:
            session.properties["group_by_capacity"] = capacity
        e.execute_sql(sql, session)
        if not e.last_query_counters.compiles:
            break
    else:
        raise AssertionError("no run without compiles in 4")
    pulled, lanes = [], []
    host, record = LE._host, tracing.record_groupby_insert

    def spy_host(arrays, site=None):
        out = host(arrays, site=site)
        if site == "agg.hash.overflow":
            # (the loop's end pulls scalars at this site too: the count, the envelope flag)
            pulled.extend(np.asarray(r).tolist() for r in out[1:] if np.ndim(r) == 1)
        return out

    def spy_record(n, round_lanes=0):
        if n:
            lanes.append(n)
        return record(n, round_lanes=round_lanes)

    monkeypatch.setattr(LE, "_host", spy_host)
    monkeypatch.setattr(tracing, "record_groupby_insert", spy_record)
    e.execute_sql(sql, session)
    c = e.last_query_counters
    assert c.compiles == 0 and len(pulled) == len(lanes) > 0
    assert sum(lanes) == c.groupby_insert_lanes
    return c, list(zip(pulled, lanes))


@pytest.mark.parametrize("floor", [None, 1024], ids=["floor-as-shipped", "floor-patched-down"])
def test_insert_round_lanes_are_the_sum_over_levels_of_rounds_times_width(
        floor, monkeypatch):
    insert_widths = hashagg.insert_widths

    c, steps = _insert_rounds(monkeypatch, HASHED, floor)
    assert c.groupby_insert_round_lanes == sum(
        r * w for rounds, lanes in steps for r, w in zip(rounds, insert_widths(lanes)))
    if floor is None:
        # under the floor the loop is the one loop it was: rounds x lanes
        assert all(len(rounds) == 1 for rounds, _ in steps)
        assert c.groupby_insert_round_lanes == sum(r[0] * n for r, n in steps)
    else:
        assert all(len(rounds) == 1 + len(hashing.INSERT_SHIFTS) for rounds, _ in steps)
        assert any(sum(rounds[1:]) for rounds, _ in steps)
        assert c.groupby_insert_round_lanes < sum(sum(r) * n for r, n in steps)


def test_a_half_full_table_over_a_lowered_floor_runs_a_third_of_the_round_lanes(
        monkeypatch):
    """15,000 groups from ONE page (under the shipped floor) into 2^15 slots: the one loop
    runs every round at the page's width, the levels the later ones at a quarter and a
    sixty-fourth."""
    sql = "select o_orderkey * 3 k, count(*) n from orders group by 1"
    one = dict(split_rows=1 << 14, capacity=1 << 15)
    c1, steps1 = _insert_rounds(monkeypatch, sql, None, **one)
    (rounds1, lanes), = steps1
    assert len(rounds1) == 1 and c1.groupby_insert_round_lanes == rounds1[0] * lanes
    monkeypatch.undo()
    c3, steps3 = _insert_rounds(monkeypatch, sql, 1024, **one)
    (rounds3, lanes3), = steps3
    assert lanes3 == lanes and sum(rounds3) == rounds1[0] >= 6
    assert lanes <= c3.groupby_insert_round_lanes < c1.groupby_insert_round_lanes // 3
    assert c3.groupby_insert_lanes == c1.groupby_insert_lanes == lanes


def test_a_page_that_one_round_places_reports_exactly_its_lanes(monkeypatch):
    """Seven groups in a roomy table: round 0 places every lane of every page, no level
    runs, and rounds x width is the inserted lanes, over a lowered floor too."""
    c, steps = _insert_rounds(
        monkeypatch, "select l_linenumber * 3 k, count(*) n from lineitem group by 1", 1024)
    assert all(rounds == [1] + [0] * len(hashing.INSERT_SHIFTS) for rounds, _ in steps), steps
    assert c.groupby_insert_round_lanes == c.groupby_insert_lanes
