"""MATCH_RECOGNIZE row-pattern matching (reference: SQL:2016 pattern
recognition — grammar patternRecognition, sql/planner/plan/
PatternRecognitionNode.java, operator/window/matcher/Matcher.java).

Subset under test: linear patterns with ?/*/+ quantifiers (greedy with
backtracking), DEFINE with PREV/NEXT navigation, MEASURES FIRST/LAST/var.col,
ONE ROW PER MATCH, AFTER MATCH SKIP PAST LAST ROW."""

import pytest

from trino_tpu import Engine
from trino_tpu.connectors.memory import MemoryConnector


@pytest.fixture()
def px_engine():
    e = Engine()
    e.register_catalog("mem", MemoryConnector())
    s = e.create_session("mem")
    e.execute_sql("create table px (sym varchar, d bigint, price double)", s)
    e.execute_sql("""insert into px values
      ('a',1,10),('a',2,8),('a',3,7),('a',4,9),('a',5,12),('a',6,11),
      ('b',1,5),('b',2,6),('b',3,4),('b',4,3),('b',5,8)""", s)
    return e, s


def test_v_shape_pattern(px_engine):
    """The canonical V-shape (price falls then recovers) from the reference
    docs (docs/src/main/sphinx/sql/match-recognize.md)."""
    e, s = px_engine
    rows = e.execute_sql("""
        select * from px match_recognize (
          partition by sym order by d
          measures first(a.price) as start_price,
                   last(b.price) as bottom_price,
                   last(c.price) as end_price
          one row per match
          after match skip past last row
          pattern (a b+ c+)
          define b as price < prev(price), c as price > prev(price)
        ) as m order by sym""", s).rows()
    assert rows == [("a", 10.0, 7.0, 12.0), ("b", 6.0, 3.0, 8.0)]


def test_quantifiers_and_multiple_matches(px_engine):
    """* matches zero-or-more (greedy); non-overlapping matches advance past
    the last matched row."""
    e, s = px_engine
    # every maximal strictly-decreasing run of length >= 2 (s = the row the
    # run starts from, d+ = the strictly-lower continuation rows)
    rows = e.execute_sql("""
        select * from px match_recognize (
          partition by sym order by d
          measures first(s.price) as top, last(d.price) as low
          pattern (s d+)
          define d as price < prev(price)
        ) as m order by sym, top""", s).rows()
    assert ("a", 10.0, 7.0) in rows  # 10 > 8 > 7
    assert ("a", 12.0, 11.0) in rows  # 12 > 11
    assert ("b", 6.0, 3.0) in rows  # 6 > 4 > 3
    # optional tail: c? after the run (greedy, may be absent)
    rows = e.execute_sql("""
        select * from px match_recognize (
          order by sym, d
          measures first(r.price) as p0, last(r.price) as p1
          pattern (r r?)
          define r as true
        ) as m""", s).rows()
    # pairs consumed greedily over the whole (single) partition: 11 rows -> 6
    assert len(rows) == 6


def test_unmatched_optional_variable_is_null(px_engine):
    e, s = px_engine
    rows = e.execute_sql("""
        select * from px match_recognize (
          partition by sym order by d
          measures last(z.price) as spike
          pattern (s z?)
          define z as price > 100
        ) as m order by sym""", s).rows()
    # z never matches: one (s) match per row, spike NULL everywhere
    assert len(rows) == 11 and all(r[1] is None for r in rows)


def test_next_navigation(px_engine):
    e, s = px_engine
    rows = e.execute_sql("""
        select * from px match_recognize (
          partition by sym order by d
          measures first(t.d) as at_day
          pattern (t)
          define t as price < next(price)
        ) as m order by sym, at_day""", s).rows()
    # rows whose NEXT price is higher (one-row matches)
    a_days = [r[1] for r in rows if r[0] == "a"]
    assert a_days == [3, 4]  # 7<9, 9<12


# ---------------------------------------------------------------- round 3
def test_alternation_group(px_engine):
    """(U|D)+ — alternation inside a quantified group (reference: pattern
    alternation, leftmost-preferred): classify every move as up or down."""
    e, s = px_engine
    rows = e.execute_sql("""
        select * from px match_recognize (
          partition by sym order by d
          measures first(m.price) as st, last(u.price) as lastup,
                   last(dn.price) as lastdn
          pattern (m (u|dn)+)
          define u as price > prev(price), dn as price < prev(price)
        ) as x order by sym""", s).rows()
    # one maximal match per partition: every subsequent row is up or down
    assert len(rows) == 2
    a = [r for r in rows if r[0] == "a"][0]
    assert a[1] == 10.0 and a[2] == 12.0 and a[3] == 11.0  # d=6: 11 < 12
    b = [r for r in rows if r[0] == "b"][0]
    assert b[1] == 5.0 and b[2] == 8.0 and b[3] == 3.0


def test_all_rows_per_match(px_engine):
    """ALL ROWS PER MATCH: every matched input row survives with its input
    columns plus RUNNING-semantics measures (the reference's ALL ROWS
    default: each row sees the match only up to itself)."""
    e, s = px_engine
    rows = e.execute_sql("""
        select sym, d, price, low from px match_recognize (
          partition by sym order by d
          measures last(dn.price) as low
          all rows per match
          pattern (st dn+)
          define dn as price < prev(price)
        ) as x order by sym, d""", s).rows()
    # partition a: match rows d=1..3 (10 > 8 > 7); partition b: d=2..4 (6>4>3)
    a_rows = [r for r in rows if r[0] == "a"]
    assert a_rows == [("a", 1, 10.0, None), ("a", 2, 8.0, 8.0),
                      ("a", 3, 7.0, 7.0),
                      ("a", 5, 12.0, None), ("a", 6, 11.0, 11.0)]
    b_rows = [r for r in rows if r[0] == "b"]
    assert b_rows == [("b", 2, 6.0, None), ("b", 3, 4.0, 4.0),
                      ("b", 4, 3.0, 3.0)]


def test_alternation_all_rows_combined(px_engine):
    e, s = px_engine
    rows = e.execute_sql("""
        select sym, d, price from px match_recognize (
          partition by sym order by d
          measures first(m.price) as st
          all rows per match
          pattern (m (u|dn)+)
          define u as price > prev(price), dn as price < prev(price)
        ) as x order by sym, d""", s).rows()
    # the whole series matches in each partition (every step is up or down)
    assert len([r for r in rows if r[0] == "a"]) == 6
    assert len([r for r in rows if r[0] == "b"]) == 5


def test_vectorized_matcher_agrees_with_backtracker():
    """The run-length fast path (ops/matcher.py) must produce byte-identical
    results to the host backtracker on the canonical V-pattern over
    randomized data — and must actually ACTIVATE for it."""
    import numpy as np

    import trino_tpu.exec.local_executor as M  # where `_run_match_recognize` looks `vector_match` up
    from trino_tpu import Engine
    from trino_tpu.connectors.memory import MemoryConnector

    rng = np.random.default_rng(7)
    rows = []
    for g in range(4):
        price = 100
        for i in range(200):
            price += int(rng.integers(-8, 9))
            rows.append(f"({g}, {i}, {price})")

    def build():
        e = Engine()
        e.register_catalog("mem", MemoryConnector())
        s = e.create_session("mem")
        e.execute_sql("create table ticks (g bigint, t bigint, price bigint)", s)
        e.execute_sql("insert into ticks values " + ", ".join(rows), s)
        return e, s

    sql = """
        select * from ticks match_recognize (
          partition by g order by t
          measures first(down.price) as top, last(down.price) as bottom,
                   last(up.price) as rebound
          pattern (down+ up+)
          define down as price < prev(price), up as price > prev(price)
        ) order by 1, 2
    """
    calls = {"n": 0}
    orig = M.vector_match

    def counting(*a, **kw):
        out = orig(*a, **kw)
        if out is not None:
            calls["n"] += 1
        return out

    M.vector_match = counting
    try:
        e, s = build()
        fast = e.execute_sql(sql, s).to_pandas()
    finally:
        M.vector_match = orig
    assert calls["n"] == 1, "vector path did not activate for DOWN+ UP+"

    M.vector_match = lambda *a, **kw: None  # force the host backtracker
    try:
        e, s = build()
        slow = e.execute_sql(sql, s).to_pandas()
    finally:
        M.vector_match = orig
    assert fast.values.tolist() == slow.values.tolist()
    assert len(fast) > 10  # the data actually contains matches


def test_vectorized_matcher_rejects_overlapping_conditions():
    """A quantified element whose condition overlaps a later element's must
    fall back (greedy backtracking is not run-length arithmetic there)."""
    import numpy as np

    from trino_tpu.ops.matcher import vector_match

    n = 8
    conds = {"a": np.ones(n, bool), "b": np.ones(n, bool)}
    new_part = np.zeros(n, bool)
    new_part[0] = True
    assert vector_match((("a", "+"), ("b", None)), conds, new_part,
                        set()) is None
    # disjoint conditions pass the gate
    conds2 = {"a": np.arange(n) % 2 == 0, "b": np.arange(n) % 2 == 1}
    assert vector_match((("a", "+"), ("b", None)), conds2, new_part,
                        set()) is not None


def test_vectorized_matcher_partition_boundary_clip():
    """A quantified element clipped at a partition boundary must NOT let a
    later element match in the next partition (review-found: the run-length
    chain gathered the next element's run at the next partition's first row)."""
    import numpy as np

    from trino_tpu.ops.matcher import vector_match

    # partitions {0,1,2} and {3,4,5}; A matches rows 1-2 (to partition end),
    # B matches row 3 (the NEXT partition's first row)
    ok_a = np.array([False, True, True, False, False, False])
    ok_b = np.array([False, False, False, True, False, False])
    new_part = np.array([True, False, False, True, False, False])
    vm = vector_match((("a", "+"), ("b", None)),
                      {"a": ok_a, "b": ok_b}, new_part, set())
    assert vm is not None
    assert not vm.usable[1], "match crossed the partition boundary"
    assert not vm.usable.any()
