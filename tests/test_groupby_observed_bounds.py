"""A group-by over ONE materialised page of a blocking child reads its keys' bounds from
that page (PR 44).

No connector states a range for what a group-by hands on, so the second level of a
two-level aggregate (an avg of sums, a ``count(distinct)``) hashed whatever it was
given: TPC-DS q65's avg by store sent 4,194,304 lanes through the hash insert for 120
groups.  The child is finished before its consumer chooses a mode, so
``LocalExecutor._observed_direct_config`` reads min, max and any-NULL of the integer
keys off the page (one ``agg.key_bounds`` program and one pull an execution) and
``hashagg.observed_direct_config`` names the direct table, where that is no wider than
the page.  Every case runs the statement both ways and asks for the same rows: as the
executor chooses, and forced through hash mode (the method answering None, which is
what it does for a stream without the mark).
"""

import numpy as np
import pytest

from trino_tpu import Engine
from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.exec.local_executor import LocalExecutor
from trino_tpu.ops import hashagg

ROWS = 300
AVG_OF_SUMS = ("select k, avg(s) a, count(*) c from (select g, k, sum(v) s from {t} "
               "group by g, k) x group by k order by k")
# (statement, the table it reads, group-bys direct by observed bounds, the slots of the
# outer group-by's state).  ``t``: g 0..39, k 1..5, w = k * 2^22 (five values spread over
# 2^24), f = k / 2, v 0..999; ``tn``: the same with k NULL in every seventh row
CASES = {
    # k 1..5: three bits
    "avg_of_sums": (AVG_OF_SUMS.format(t="t"), 1, 8),
    # planned as two levels: DISTINCT (g, k), then count by g; g 0..39: six bits
    "count_distinct": ("select g, count(distinct k) c from t group by g order by g", 1, 64),
    # a Filter and a Project between the two levels keep the mark; s >= 0 always
    "filter_between": ("select k + 1 k1, avg(s) a from (select g, k, sum(v) s from t "
                       "group by g, k) x where s >= 0 group by k + 1 order by k1", 1, 8),
    # the child hands on a NULL k group: a flag bit, and the group is kept
    "null_key_group": (AVG_OF_SUMS.format(t="tn"), 1, 16),
    # two keys, both read: g six bits, k three; the child's page (some 290 groups of
    # 300 rows) has 512 lanes: as wide as the table
    "two_keys": ("select g, k, max(s) m from (select g, k, v, sum(v) s from t "
                 "group by g, k, v) x group by g, k order by g, k", 1, 512),
    # five keys spread over 2^24 values in a page of 64 lanes: the table would be wider
    # than the page it groups
    "wider_than_the_page": ("select w, avg(s) a from (select g, w, sum(v) s from t "
                            "group by g, w) x group by w order by w", 0, 65536),
    "floating_key": ("select f, avg(s) a from (select g, f, sum(v) s from t "
                     "group by g, f) x group by f order by f", 0, 65536),
    "empty_child": ("select k, count(*) c from (select g, k, sum(v) s from t where v < 0 "
                    "group by g, k) x group by k order by k", 0, 65536),
}
# the stayed-hashed cases that read the bounds before they say no (a floating key is
# refused by its type, before any program)
READ_AND_REFUSED = ("wider_than_the_page", "empty_child")


def table_rows(null_every=0):
    rng = np.random.default_rng(44)
    g, k, v = rng.integers(0, 40, ROWS), rng.integers(1, 6, ROWS), rng.integers(0, 1000, ROWS)
    return ", ".join(
        f"({a}, {'null' if null_every and i % null_every == 0 else b}, {b << 22}, "
        f"{b / 2}, {c})" for i, (a, b, c) in enumerate(zip(g, k, v)))


@pytest.fixture(scope="module")
def db():
    engine, conn = Engine(), MemoryConnector()
    engine.register_catalog("memory", conn)
    session = engine.create_session("memory")
    for name, null_every in (("t", 0), ("tn", 7)):
        engine.execute_sql(
            f"create table {name} (g bigint, k bigint, w bigint, f double, v bigint)", session)
        engine.execute_sql(f"insert into {name} values " + table_rows(null_every), session)
    yield engine, conn, session
    engine._invalidate()


def run(db, sql, hashed=False):
    """(rows, counters, {span name: [slots]}) of one execution; ``hashed``: forced
    through hash mode, as a stream without the mark runs."""
    engine, _, session = db
    real = LocalExecutor._observed_direct_config
    if hashed:
        LocalExecutor._observed_direct_config = lambda self, *args: None
    try:
        rows = engine.execute_sql(sql, session).rows()
    finally:
        LocalExecutor._observed_direct_config = real
    slots = {}
    for span in engine.last_query_trace["spans"]:
        if span["name"].startswith("aggregate."):
            slots.setdefault(span["name"], []).append(span["attributes"]["slots"])
    return rows, engine.last_query_counters, slots


def key_bounds_site(counters):
    return [v for k, v in counters.sites.items() if k.endswith("/agg.key_bounds")]


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_rows_are_those_of_the_hashed_run_and_the_mode_is_the_expected_one(db, name):
    sql, direct, slots = CASES[name]
    want, hashed, hashed_slots = run(db, sql, hashed=True)
    got, counters, spans = run(db, sql)
    assert got == want and (len(got) > 0 or name == "empty_child")
    assert hashed.groupby_observed_direct == 0 and "aggregate.direct" not in hashed_slots
    assert counters.groupby_observed_direct == direct
    if direct:
        assert spans["aggregate.direct"] == [slots], spans
        # the outer group-by sent no lane through the hash insert
        assert counters.groupby_insert_lanes < hashed.groupby_insert_lanes
    else:
        assert "aggregate.direct" not in spans and slots in spans["aggregate.hash"]
        assert counters.groupby_insert_lanes == hashed.groupby_insert_lanes
    assert counters.groupby_regrows == 0


def test_a_null_key_group_of_the_child_keeps_its_group(db):
    rows, counters, _ = run(db, CASES["null_key_group"][0])
    assert counters.groupby_observed_direct == 1
    assert [r[0] for r in rows].count(None) == 1 and len(rows) == 6
    null_group = next(r for r in rows if r[0] is None)
    assert null_group[2] > 0  # (the (g, NULL) pairs it averaged over)


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_bounds_are_one_program_and_one_pull_an_execution(db, name):
    """Warm against warm: where the bounds are read and the group-by stays hashed, the
    run is the hashed run and exactly one dispatch and one pull more; where it goes
    direct, the direct step stands in for the hashed run's prepare and insert, and the
    page's live count is no longer pulled: never more than one of each over the hashed
    run.  A floating key launches nothing."""
    sql, direct, _ = CASES[name]
    for hashed in (True, False):
        run(db, sql, hashed)
    _, hashed, _ = run(db, sql, hashed=True)
    _, counters, _ = run(db, sql)
    assert hashed.compiles == 0 and counters.compiles == 0
    assert key_bounds_site(hashed) == []
    extra = (counters.device_dispatches - hashed.device_dispatches,
             counters.host_transfers - hashed.host_transfers)
    if direct or name in READ_AND_REFUSED:
        (site,) = key_bounds_site(counters)
        assert (site["dispatches"], site["transfers"]) == (1, 1)
        assert site["bytes"] <= 3 * 8 * 2  # (min, max, any NULL) a key
        if direct:
            assert extra[0] <= 1 and extra[1] <= 1, extra
        else:
            assert extra == (1, 1), extra
    else:
        assert key_bounds_site(counters) == [] and extra == (0, 0)


def set_k(db, old, new):
    """Every k = ``old`` becomes ``new``, by ``update_rows`` on the connector itself: the
    engine's UPDATE drops every cached plan, and this is about the SAME compiled plan
    over changed data."""
    _, conn, _ = db
    table = conn._tables["u"]
    conn.update_rows("u", table.columns[table.schema.index("k")] == old,
                     {"k": np.full(ROWS, new, object)})


def test_the_same_plan_over_changed_data_recompiles_only_for_another_bit(db):
    engine, conn, session = db
    engine.execute_sql("create table u (g bigint, k bigint, w bigint, f double, v bigint)",
                       session)
    engine.execute_sql("insert into u values " + table_rows(), session)
    sql = AVG_OF_SUMS.format(t="u")
    run(db, sql)
    _, warm, spans = run(db, sql)
    assert warm.compiles == 0 and warm.groupby_observed_direct == 1
    assert spans["aggregate.direct"] == [8]

    # k = 5 becomes 8: the maximum moves inside its three bits (1..8): the same config
    set_k(db, 5, 8)
    got, moved, spans = run(db, sql)
    assert moved.compiles == 0 and moved.groupby_observed_direct == 1
    assert spans["aggregate.direct"] == [8] and [r[0] for r in got] == [1, 2, 3, 4, 8]
    assert got == run(db, sql, hashed=True)[0]
    # 8 becomes 9: a fourth bit, another config, the right answer
    set_k(db, 8, 9)
    got, wider, spans = run(db, sql)
    assert wider.groupby_observed_direct == 1 and spans["aggregate.direct"] == [16]
    assert wider.compiles > 0 and [r[0] for r in got] == [1, 2, 3, 4, 9]
    assert got == run(db, sql, hashed=True)[0]
    # an INSERT through the engine (every plan dropped): a NULL key arrives
    engine.execute_sql("insert into u values (1, null, 0, 0.5, 10)", session)
    got, after, spans = run(db, sql)
    assert after.groupby_observed_direct == 1 and spans["aggregate.direct"] == [32]
    assert got[-1][0] is None and got == run(db, sql, hashed=True)[0]
    # and nothing of the data was kept: the bounds are read again by every execution
    (site,) = key_bounds_site(run(db, sql)[1])
    assert (site["dispatches"], site["transfers"]) == (1, 1)


@pytest.mark.parametrize("bounds,lanes,want", [
    # q65's avg by store at scale 10: 1..120, no NULL, a page of 4,194,304 lanes
    ([(1, 120, False)], 4_194_304, ((False, 1, 128, 7),)),
    ([(1, 97, False)], 4_194_304, ((False, 1, 128, 7),)),  # the same seven bits
    ([(1, 120, True)], 4_194_304, ((True, 1, 128, 7),)),  # a flag bit: 256 slots
    ([(1, 129, False)], 4_194_304, ((False, 1, 256, 8),)),
    ([(5, 5, False)], 64, ((False, 5, 6, 1),)),  # one value: one bit
    ([(0, 63, False)], 64, ((False, 0, 63, 6),)),  # as wide as the page: taken
    ([(0, 64, False)], 64, None),  # wider than the page
    ([(0, 1 << 22, False)], 256, None),
    ([(0, 1 << 40, False)], 1 << 30, None),  # over DIRECT_BITS_MAX
    ([(np.iinfo(np.int64).max, np.iinfo(np.int64).min, False)], 64, None),  # no live value
    ([(0, 7, False), (np.iinfo(np.int64).max, np.iinfo(np.int64).min, True)], 64, None),
    ([(0, 7, False), (10, 12, True)], 64, ((False, 0, 7, 3), (True, 10, 13, 2))),
])
def test_the_rule_names_the_envelope_of_the_bits_and_never_a_table_wider_than_the_page(
        bounds, lanes, want):
    cfg = hashagg.observed_direct_config(bounds, lanes)
    assert (None if cfg is None else cfg.entries) == want
    if cfg is not None:
        assert cfg.capacity <= lanes and cfg.total_bits <= hashagg.DIRECT_BITS_MAX


def test_a_null_that_meets_a_config_without_the_flag_bit_falls_to_hash_mode():
    """What makes "no NULL seen" safe to act on: ``_direct_slot`` routes it to the
    overflow flag, and the executor then runs hash mode over the whole input."""
    import jax.numpy as jnp

    cfg = hashagg.observed_direct_config([(1, 5, False)], 64)
    state = hashagg.direct_groupby_init(cfg, (jnp.int64,), ((jnp.int64, 0),))
    keys = jnp.asarray([1, 5, 3, 0], jnp.int64)
    nulls = jnp.asarray([False, False, False, True])
    valid = jnp.ones((4,), bool)
    out = hashagg.direct_groupby_insert(state, cfg, (keys,), valid, [(None, None)],
                                        ("count_star",), (nulls,))
    assert bool(out.overflow)
    clean = hashagg.direct_groupby_insert(state, cfg, (keys,), valid & ~nulls,
                                          [(None, None)], ("count_star",), (nulls,))
    assert not bool(clean.overflow) and int(clean.accs[0][:cfg.capacity].sum()) == 3


def test_explain_analyze_and_the_metrics_page_show_the_counter(db):
    engine, _, session = db
    text = "\n".join(str(r[0]) for r in engine.execute_sql(
        "explain analyze " + CASES["avg_of_sums"][0], session).rows())
    assert "1 direct by observed bounds" in text, text
    assert "agg.key_bounds: 1 dispatches, 1 transfers" in text, text
    plain = "\n".join(str(r[0]) for r in engine.execute_sql(
        "explain analyze " + CASES["floating_key"][0], session).rows())
    assert "direct by observed bounds" not in plain
    assert engine.last_query_counters.as_dict()["groupby_observed_direct"] == 0
