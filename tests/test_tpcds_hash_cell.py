"""TPC-DS on the served path (PR 36): the official texts of query 65 and query 93
against their references (q93's is the benchmark's own; ties of q65's ORDER BY are the
reference's and the comparison's to settle, not the engine's), the generator that
compiles once a table and returns what was sold, the counters that tell a hashed probe
from a direct one and count the lanes a hash group-by inserts, the ``join.build`` span,
and a hash group-by that starts its replay at the capacity it grew to.
"""

import os
import re
import time
import urllib.request

import numpy as np
import pandas as pd
import pytest

from benchmark.harness import compare
from benchmark.harness.hosttables import HostTables
from benchmark.harness.loader import ROOT, _load_module
from trino_tpu import Engine
from trino_tpu.connectors import tpcds
from trino_tpu.connectors.tpcds import TpcdsConnector

SF = 0.01


def _statement(name):
    return _load_module(os.path.join(ROOT, "benchmark", "statements", name + ".py"), name)


DS_Q65, DS_Q93 = _statement("ds_q65"), _statement("ds_q93")
# (statement, parameters, the answer has rows at SF0.01): at a hundredth of the scale a
# (store, item) pair has thirty sales, so none is under a tenth of the average; the
# second case asks for those under the average itself
TEXTS = {"ds_q65": (DS_Q65, DS_Q65.VALIDATION, False),
         "ds_q65_under_average": (DS_Q65, dict(DS_Q65.VALIDATION, factor="1.0"), True),
         "ds_q93": (DS_Q93, DS_Q93.VALIDATION, True)}


@pytest.fixture(scope="module")
def ds():
    conn = TpcdsConnector(sf=SF, split_rows=1 << 21)
    e = Engine()
    e.register_catalog("tpcds", conn)
    wanted = {}
    for statement in (DS_Q65, DS_Q93):
        for table, cols in statement.TABLES.items():
            wanted.setdefault(table, []).extend(cols)
    return e, conn, HostTables(conn, wanted)


def _frame(result):
    return pd.DataFrame(result.rows(), columns=list(result.names))


def tie_aligned(got, full, keys):
    """An answer under ORDER BY ``keys`` LIMIT len(got), and the reference's answer
    WITHOUT its limit (ties broken by the remaining columns), as two frames to compare
    row by row: ``got`` with the rows of each tie group put in the reference's order
    (SQL leaves it open), and the reference's rows they stand against.  A group that the
    LIMIT does not cut is the reference's whole group; of the group it cuts, the
    reference's rows that are nearest to the engine's, each taken once, so a row that is
    in no such group compares as wrong.  The ORDER BY order itself is checked here."""
    cols = list(got.columns)
    rest = [c for c in cols if c not in keys]
    assert list(full.columns) == cols and len(got) <= len(full)
    ordered = got.sort_values(keys, kind="stable")
    assert ordered.index.equals(got.index), "not in ORDER BY order"
    got = got.sort_values(keys + rest, kind="stable").reset_index(drop=True)
    want = full.head(len(got)).reset_index(drop=True)
    if len(got) and len(full) > len(got):
        last = tuple(got[keys].iloc[-1])
        cut = (got[keys].apply(tuple, axis=1) == last).to_numpy()
        group = full[(full[keys].apply(tuple, axis=1) == last).to_numpy()]
        if cut.sum() < len(group):  # the LIMIT cuts this group: membership
            left, taken = list(group.index), []
            for _, row in got[cut].iterrows():
                dist = [sum(abs(float(row[c]) - float(full.loc[i, c]))
                            if pd.api.types.is_number(row[c])
                            else float(str(row[c]) != str(full.loc[i, c])) * 1e9
                            for c in rest) for i in left]
                taken.append(left.pop(int(np.argmin(dist))))
            want = pd.concat([want[~cut], full.loc[taken]]).reset_index(drop=True)
    return got, want


@pytest.mark.parametrize("case", sorted(TEXTS))
def test_official_text_against_the_benchmarks_reference(case, ds):
    """float64 passes every limit of the comparison; the reference in float32 in the
    program's place fails ``max_rel_err`` (wherever the answer has a row)."""
    e, _, tables = ds
    statement, p, has_rows = TEXTS[case]
    assert "left outer join" in DS_Q93.SQL and "0.1" == DS_Q65.VALIDATION["factor"]
    # (q65: the template's own ORDER BY, which leaves ties; the cell's text, whose
    # order is total, is the next test's)
    text = (statement.render_template if statement is DS_Q65 else statement.render)(p)[0]
    got = _frame(e.execute_sql(text, e.create_session("tpcds")))
    want = statement.reference(tables, p)
    assert bool(len(want)) == has_rows
    if statement is DS_Q65:
        got, aligned = tie_aligned(got, statement.reference(tables, p, limit=None),
                                   statement.ORDER_BY)
        assert len(aligned) == len(want)
        numbers = compare.compare(got, aligned)
        assert compare.within_limits(numbers), numbers
    numbers = compare.compare(got, want)
    assert compare.within_limits(numbers), numbers
    control = compare.compare(statement.reference(tables, p, dtype=np.float32), want)
    if has_rows:
        assert control["max_rel_err"] > compare.LIMITS["max_rel_err"], control
        assert control["exact_mismatches"] == 0
    assert e.last_query_counters.device_dispatches > 0


@pytest.mark.parametrize("case", ["ds_q65", "ds_q65_under_average"])
def test_the_cells_total_order_text_compares_positionally(case, ds):
    """``ds10_hash_groupby`` sends query65.tpl with its ORDER BY completed by the
    remaining SELECT columns (the one departure, ``assumed.order_by`` of its
    configuration): the order is total, so the harness's positional comparison holds
    the answer as it is, with no alignment of tie groups."""
    e, _, tables = ds
    statement, p, has_rows = TEXTS[case]
    cell, template = statement.render(p)[0], statement.render_template(p)[0]
    assert cell != template and cell.replace(
        ", sc.revenue, i_current_price, i_wholesale_cost, i_brand\nlimit", "\nlimit") \
        == template  # the ORDER BY's tail is the only difference
    assert statement.SQL.format(**p) == cell
    got = _frame(e.execute_sql(cell, e.create_session("tpcds")))
    want = statement.reference(tables, p)
    assert bool(len(want)) == has_rows and list(got.columns) == list(want.columns)
    numbers = compare.compare(got, want)
    assert compare.within_limits(numbers), numbers
    assert e.last_query_counters.device_dispatches > 0
    if has_rows:
        control = compare.compare(statement.reference(tables, p, dtype=np.float32), want)
        assert control["max_rel_err"] > compare.LIMITS["max_rel_err"], control
        assert control["exact_mismatches"] == 0


def test_a_replay_of_q65_compiles_nothing_and_still_groups_the_years_sales(ds):
    """The guard of the cell's meaning: ``sc`` is the BUILD side of ``sb x sc`` and stays
    inside the compiled stream, but ``sa`` (under ``sb``) is computed again by every
    execution: a replay that compiles nothing still sends the year's (store, item)
    lanes through the hash insert.  An aggregate kept from one execution to the next
    would be a result cache."""
    _, conn, tables = ds
    e = Engine()
    e.register_catalog("tpcds", conn)
    sql = DS_Q65.render(DS_Q65.VALIDATION)[0]
    e.execute_sql(sql, e.create_session("tpcds"))
    first = e.last_query_counters
    w = _replayed(e, sql, "tpcds")
    assert w.compiles == 0 and w.device_dispatches > 0
    ss, dd = tables.columns("store_sales"), tables.columns("date_dim")
    days = dd["d_date_sk"][(dd["d_month_seq"] >= 1176) & (dd["d_month_seq"] <= 1187)]
    year = int(np.isin(ss["ss_sold_date_sk"], days).sum())
    # one (store, item) group-by a replay (the first run made two), over at least the
    # year's sales, and its rounds at least once over every inserted lane
    assert year <= w.groupby_insert_lanes < first.groupby_insert_lanes
    assert w.groupby_insert_round_lanes >= w.groupby_insert_lanes
    assert w.join_build_rows == 0 and w.groupby_regrows == 0
    # (PR 44) the avg by store over ``sa``'s finished page reads its key's bounds from
    # that page and is direct-indexed: it no longer adds to the inserted lanes, and the
    # (store, item) group-by above still does
    assert w.groupby_observed_direct == 1


def test_q93s_left_join_runs_as_an_inner_join(ds):
    """``where sr_reason_sk = r_reason_sk`` rejects the NULL-extended rows, so the
    planner may, and does, say INNER (rules.OuterJoinToInner); a predicate that keeps
    them (IS NULL) keeps the LEFT join."""
    e, _, _ = ds
    plan = "\n".join(str(r[0]) for r in e.execute_sql(
        "explain " + DS_Q93.render(DS_Q93.VALIDATION)[0], e.create_session("tpcds")).rows())
    assert "LeftJoin" not in plan and plan.count("InnerJoin") == 2, plan
    kept = "\n".join(str(r[0]) for r in e.execute_sql(
        "explain select count(*) from store_sales left join store_returns "
        "on sr_item_sk = ss_item_sk and sr_ticket_number = ss_ticket_number "
        "where sr_reason_sk is null or sr_reason_sk = 3",
        e.create_session("tpcds")).rows())
    assert "LeftJoin" in kept, kept
    by_filter = "\n".join(str(r[0]) for r in e.execute_sql(
        "explain select count(*) from store_sales left join store_returns "
        "on sr_item_sk = ss_item_sk and sr_ticket_number = ss_ticket_number "
        "where sr_return_quantity > 5", e.create_session("tpcds")).rows())
    assert "LeftJoin" not in by_filter, by_filter


# -- the generator ---------------------------------------------------------------------
GEN = TpcdsConnector(sf=0.02, split_rows=5000)  # several splits, and a masked tail


@pytest.mark.parametrize("table", sorted(tpcds.GENERATORS))
def test_every_split_of_a_table_runs_one_program_and_gives_the_static_forms_page(table):
    """``generate`` traces ``lo``: all splits of a (table, column set) are ONE compiled
    program, and each page equals the generator run eagerly at that split's own ``lo``
    (the form that was static until PR 36) under the row bound's mask."""
    splits = GEN.splits(table)
    names = GEN.schema(table).names
    program = tpcds._GENERATE_PROGRAMS.get(table)
    before = program._cache_size() if program is not None else 0
    pages = [GEN.generate(s) for s in splits]
    assert tpcds._GENERATE_PROGRAMS[table]._cache_size() - before <= 1
    n = GEN.row_count(table)
    for split, page in zip(splits, pages):
        length = split.hi - split.lo
        want = tpcds.GENERATORS[table](GEN.sf, split.lo, length)
        for name, col in zip(names, page.columns):
            w = np.asarray(want[name]).astype(np.asarray(col).dtype)
            assert (np.asarray(col) == w).all(), (table, split, name)
        assert (np.asarray(page.valid_mask())
                == (np.arange(split.lo, split.hi) < n)).all(), (table, split)
    assert sum(int(np.asarray(p.valid_mask()).sum()) for p in pages) == n


def test_store_sales_splits_cost_one_backend_compile():
    """What the 14 splits of SF10's store_sales cost on the chip, at a small size: one
    backend compile for the scan's column set, however many splits it has."""
    conn = TpcdsConnector(sf=0.03, split_rows=7000)
    cols = ["ss_item_sk", "ss_customer_sk", "ss_ticket_number", "ss_quantity",
            "ss_sales_price", "ss_hdemo_sk"]  # (a column set no other test generates)
    splits = conn.splits("store_sales")
    assert len(splits) > 8
    from trino_tpu.execution import tracing

    program = tpcds._GENERATE_PROGRAMS.get("store_sales")
    before = program._cache_size() if program is not None else 0
    token = tracing.begin_compile_capture()
    try:
        for s in splits:
            conn.generate(s, cols)
    finally:
        tracing.end_compile_capture(token)
    # one program in the process, and at most one backend compile for it (none where the
    # persistent compile cache of an earlier run of this test serves it)
    assert tpcds._GENERATE_PROGRAMS["store_sales"]._cache_size() - before == 1
    assert token[1].backend_compiles <= 1, token[1].backend_compiles


# -- the counters and the span ----------------------------------------------------------
HASHED = """
    select l_partkey, l_suppkey, count(*) n, sum(ps_availqty) a
    from lineitem, partsupp
    where l_partkey = ps_partkey and l_suppkey = ps_suppkey and ps_availqty < 500
    group by l_partkey, l_suppkey order by l_partkey, l_suppkey"""
Q3 = _statement("q3")


def _build_spans(engine):
    spans = (engine.last_query_trace or {}).get("spans", ())
    return [s["attributes"] for s in spans if s["name"] == "join.build"]


def _pow2(n):
    return 1 << max(int(n) - 1, 1).bit_length()


def test_a_direct_probe_counts_as_direct_and_its_build_span_says_so(tpch_sf001, tpch_pandas):
    e = Engine()
    e.register_catalog("tpch", tpch_sf001)
    sql = Q3.render(Q3.VALIDATION)[0]
    e.execute_sql(sql, e.create_session("tpch"))
    builds = _build_spans(e)
    assert builds and {b["kind"] for b in builds} == {"direct"}, builds
    c = tpch_pandas["customer"]
    building = c[c.c_mktsegment == "BUILDING"]
    assert sorted(int(b["rows"]) for b in builds)[0] == len(building)
    assert all(int(b["slots"]) >= int(b["rows"]) for b in builds)
    e.execute_sql(sql, e.create_session("tpch"))  # the replay: nothing is built
    w = e.last_query_counters
    assert _build_spans(e) == [] and w.join_build_rows == 0
    assert w.join_hash_probe_lanes == 0 and w.join_hash_table_slots == 0
    # the first join of q3's probe side is split, the second runs fused at the width
    # the boundary left: over the split join alone the probed lanes are the matched ones
    assert w.join_match_lanes > w.join_gather_lanes > 0
    assert w.join_direct_probe_lanes == w.join_match_lanes + w.join_gather_lanes


def test_a_hashed_probe_counts_as_hashed_with_its_tables_slots(tpch_sf001, tpch_pandas):
    e = Engine()
    e.register_catalog("tpch", tpch_sf001)
    e.execute_sql(HASHED, e.create_session("tpch"))
    cold = e.last_query_counters
    ps = tpch_pandas["partsupp"]
    rows = int((ps.ps_availqty < 500).sum())
    builds = _build_spans(e)
    assert [(b["kind"], int(b["rows"])) for b in builds] == [("hash", rows)]
    slots = int(builds[0]["slots"])  # 4 x the pow2 cover of the build page's lanes
    assert slots >= 4 * _pow2(rows) and slots == _pow2(slots)
    assert cold.join_hash_table_slots == slots
    assert cold.join_build_rows == rows
    e.execute_sql(HASHED, e.create_session("tpch"))
    w = e.last_query_counters
    assert w.join_hash_probe_lanes == w.join_match_lanes > 0
    assert w.join_direct_probe_lanes == 0 and w.join_hash_table_slots == 0
    assert w.join_gather_lanes * 4 <= w.join_match_lanes


def test_q93_probes_through_the_hash_loop_and_inserts_what_survives(ds):
    _, conn, tables = ds
    e = Engine()  # (the module's engine has q93's plan, with its tables, already)
    e.register_catalog("tpcds", conn)
    sql = DS_Q93.render(DS_Q93.VALIDATION)[0]
    builds = None
    for _ in range(3):  # cold, the advisor's re-plan if it makes one, the replay
        e.execute_sql(sql, e.create_session("tpcds"))
        builds = builds or _build_spans(e)
        if not e.last_query_counters.compiles:
            break
    w = e.last_query_counters
    assert w.compiles == 0
    # store_returns' (item, ticket) pairs are its primary key: ONE hashed table of
    # unique keys over all of its rows, and the direct table over the one reason
    returns = len(tables.columns("store_returns")["sr_item_sk"])
    assert sorted((b["kind"], int(b["rows"])) for b in builds) == \
        [("direct", 1), ("hash", returns)], builds
    assert not any(b.get("dups") for b in builds)
    lanes = sum(s.hi - s.lo for s in conn.splits("store_sales"))
    # the join is SPLIT: every lane of the scan goes through the probe loop in the
    # match step, the boundary packs what matched (a tenth), and the join with
    # `reason` runs fused, direct, at the width the boundary left
    assert w.join_hash_probe_lanes == w.join_match_lanes == lanes
    assert w.join_direct_probe_lanes == w.join_gather_lanes
    assert 0 < w.join_gather_lanes <= lanes // 4
    joined = len(DS_Q93.reference(tables, DS_Q93.VALIDATION))
    assert joined <= w.groupby_insert_lanes <= max(_pow2(w.join_gather_lanes), 1024)


# -- the generator's returns ------------------------------------------------------------
def test_every_return_copies_a_sale_and_no_sale_is_returned_twice(ds):
    """TPC-DS 2.x: (ss_item_sk, ss_ticket_number) and (sr_item_sk, sr_ticket_number) are
    primary keys, and a return row is the return OF a sale: its item, ticket, customer
    and store are that sale's.  About a tenth of the sales have a return."""
    _, conn, _ = ds
    t = HostTables(conn, {
        "store_sales": ["ss_item_sk", "ss_ticket_number", "ss_customer_sk", "ss_store_sk"],
        "store_returns": ["sr_item_sk", "sr_ticket_number", "sr_customer_sk",
                          "sr_store_sk"]})
    ss, sr = t["store_sales"], t["store_returns"]
    assert not ss.duplicated(["ss_item_sk", "ss_ticket_number"]).any()
    assert not sr.duplicated(["sr_item_sk", "sr_ticket_number"]).any()
    m = sr.merge(ss, left_on=["sr_item_sk", "sr_ticket_number"],
                 right_on=["ss_item_sk", "ss_ticket_number"])
    assert len(m) == len(sr) == conn.row_count("store_returns")
    assert (m.sr_customer_sk == m.ss_customer_sk).all()
    assert (m.sr_store_sk == m.ss_store_sk).all()
    assert 0.09 < len(sr) / len(ss) < 0.11
    assert ss.ss_item_sk.nunique() == conn.row_count("item")  # every item sells


def test_a_ticket_is_the_same_quotient_in_32_bits_and_in_64():
    """``_ticket`` divides in int32 where a table's row indexes fit (the TPU compiler
    spends seconds on an emulated 64-bit division) and in int64 where they do not."""
    import jax.numpy as jnp

    i = jnp.asarray([0, 11, 12, 28_799_999, (1 << 30) - 1], jnp.int64)
    narrow, wide = tpcds._ticket(i, 28_800_000), tpcds._ticket(i, 1 << 40)
    assert narrow.dtype == wide.dtype == jnp.int64
    assert narrow.tolist() == wide.tolist() == [0, 0, 1, 2_399_999, ((1 << 30) - 1) // 12]
    big = jnp.asarray([(1 << 40) + 5], jnp.int64)
    assert tpcds._ticket(big, 1 << 41).tolist() == [((1 << 40) + 5) // 12]


# -- a group-by's learned capacity ------------------------------------------------------
def test_a_hash_group_by_replays_at_the_capacity_it_grew_to(tpch_sf001, tpch_pandas):
    """A cached plan's hash group-by that outgrew its table starts its next run at the
    capacity it ended with: the regrow (a rehash and the chunk again, and on the chip the
    probe loop at MAX_PROBES for every lane that met the full table) is paid once."""
    e = Engine()
    e.register_catalog("tpch", tpch_sf001)
    # (a computed key has no range to index directly: hash mode)
    sql = "select l_partkey * 7919 + l_suppkey k, count(*) n from lineitem group by 1"
    groups = len(tpch_pandas["lineitem"].groupby(["l_partkey", "l_suppkey"]))

    def run():
        session = e.create_session("tpch")
        session.properties["group_by_capacity"] = 1024
        r = e.execute_sql(sql, session)
        spans = (e.last_query_trace or {}).get("spans", ())
        slots = [int(s["attributes"]["slots"]) for s in spans
                 if s["name"] == "aggregate.hash"]
        return len(r), slots, e.last_query_counters

    n, first_slots, first = run()
    assert n == groups and first_slots == [1024]
    assert first.groupby_slots >= groups > 1024  # it grew, inside the run
    n, again_slots, again = run()
    # the one program a table size that is new to the replay: its initial state (PR 39:
    # `agg.hash.init`, shared by the process: another test may have made one of this size;
    # the regrow made its tables inside the rehash)
    assert n == groups and [site.split("/")[-1] for site, rec in again.sites.items()
                            if rec.get("compiles")] in ([], ["agg.hash.init"])
    assert again_slots == [first.groupby_slots] and again.groupby_slots == first.groupby_slots
    assert run()[2].compiles == 0
    # no rehash and no chunk inserted twice: fewer lanes than the run that grew
    assert 0 < again.groupby_insert_lanes < first.groupby_insert_lanes


def test_explain_analyze_and_metrics_carry_the_new_series(tpch_sf001):
    from test_profiling import _parse_prometheus
    from trino_tpu.server.server import CoordinatorServer

    e = Engine()
    e.register_catalog("tpch", tpch_sf001)
    before = e.counters_total.snapshot()
    r = e.execute_sql("explain analyze " + HASHED, e.create_session("tpch"))
    text = "\n".join(str(row[0]) for row in r.rows())
    c = e.last_query_counters
    m = re.search(r"Join probe: (\d+) lanes matched, (\d+) lanes gathered; "
                  r"(\d+) lanes hashed, (\d+) lanes direct", text)
    assert m, text
    assert tuple(map(int, m.groups())) == (
        c.join_match_lanes, c.join_gather_lanes, c.join_hash_probe_lanes,
        c.join_direct_probe_lanes)
    assert c.join_hash_probe_lanes > 0
    m = re.search(r"partitioned passes, (\d+) lanes inserted", text)
    assert m and int(m.group(1)) == c.groupby_insert_lanes, text
    after = e.counters_total
    srv = CoordinatorServer(e, port=0)
    srv.start()
    try:
        parsed = _parse_prometheus(urllib.request.urlopen(
            srv.url + "/v1/metrics", timeout=10).read().decode())
    finally:
        srv.stop()
    for field in ("join_hash_probe_lanes", "join_direct_probe_lanes",
                  "join_hash_table_slots", "groupby_insert_lanes"):
        assert parsed["types"][f"trino_tpu_{field}_total"] == "counter"
        assert parsed["samples"][f"trino_tpu_{field}_total"][0][1] == \
            getattr(after, field)
        assert getattr(after, field) - getattr(before, field) >= getattr(c, field)
        assert after.as_dict()[field] == getattr(after, field)


# -- the rounds of the hashed lookup (PR 37) ------------------------------------------------
def _replayed(engine, sql, catalog):
    """The counters of the first execution that compiles nothing."""
    for _ in range(4):  # cold, the advisor's re-plan if it makes one, the replay
        engine.execute_sql(sql, engine.create_session(catalog))
        if not engine.last_query_counters.compiles:
            return engine.last_query_counters
    raise AssertionError("no run without compiles in 4")


def _q93_replayed(conn):
    e = Engine()  # (its own: a kept plan keeps its compiled match step)
    e.register_catalog("tpcds", conn)
    return _replayed(e, DS_Q93.render(DS_Q93.VALIDATION)[0], "tpcds")


@pytest.mark.parametrize("case", ["q93-floor-as-shipped", "q93-floor-patched-down",
                                  "direct-tables"])
def test_a_split_join_records_the_lanes_its_probe_rounds_gathered_for(
        case, ds, tpch_sf001, monkeypatch):
    from trino_tpu.ops import hashing, hashjoin

    if case == "direct-tables":
        e = Engine()
        e.register_catalog("tpch", tpch_sf001)
        w = _replayed(e, Q3.render(Q3.VALIDATION)[0], "tpch")
        assert w.join_direct_probe_lanes > 0 and w.join_hash_probe_lanes == 0
        assert w.join_hash_probe_round_lanes == 0
        return
    _, conn, _ = ds
    lanes = sum(s.hi - s.lo for s in conn.splits("store_sales"))
    assert hashjoin.probe_widths(lanes) == (lanes,)  # a tier-1 page is under the floor
    w = _q93_replayed(conn)
    assert w.join_hash_probe_lanes == lanes
    # one level: whole rounds of every lane, at least one, at most MAX_PROBES
    assert w.join_hash_probe_round_lanes % lanes == 0
    assert lanes <= w.join_hash_probe_round_lanes <= hashjoin.MAX_PROBES * lanes
    if case == "q93-floor-patched-down":
        monkeypatch.setattr(hashing, "NARROW_MIN_LANES", 1024)
        assert len(hashjoin.probe_widths(lanes)) == 1 + len(hashing.NARROW_SHIFTS)
        narrowed = _q93_replayed(conn)
        # the same lanes and the same rounds, the later ones at a quarter and less
        assert narrowed.join_hash_probe_lanes == lanes
        assert lanes <= narrowed.join_hash_probe_round_lanes \
            < w.join_hash_probe_round_lanes


def test_the_probe_round_lanes_reach_explain_analyze_and_the_metrics(tpch_sf001):
    from test_profiling import _parse_prometheus
    from trino_tpu.server.server import CoordinatorServer

    e = Engine()
    e.register_catalog("tpch", tpch_sf001)
    r = e.execute_sql("explain analyze " + HASHED, e.create_session("tpch"))
    text = "\n".join(str(row[0]) for row in r.rows())
    c = e.last_query_counters
    m = re.search(r"(\d+) lanes hashed, \d+ lanes direct; (\d+) lanes in probe rounds", text)
    assert m, text
    assert tuple(map(int, m.groups())) == (
        c.join_hash_probe_lanes, c.join_hash_probe_round_lanes)
    assert c.join_hash_probe_round_lanes >= c.join_hash_probe_lanes > 0
    total = e.counters_total
    srv = CoordinatorServer(e, port=0)
    srv.start()
    try:
        parsed = _parse_prometheus(urllib.request.urlopen(
            srv.url + "/v1/metrics", timeout=10).read().decode())
    finally:
        srv.stop()
    name = "trino_tpu_join_hash_probe_round_lanes_total"
    assert parsed["types"][name] == "counter"
    assert parsed["samples"][name][0][1] == total.join_hash_probe_round_lanes \
        >= c.join_hash_probe_round_lanes
    assert total.as_dict()["join_hash_probe_round_lanes"] \
        == total.join_hash_probe_round_lanes


# -- ties under an ORDER BY that is not total ----------------------------------------------
def _tie_case():
    full = pd.DataFrame({"name": ["able"] * 3 + ["anti"] * 4 + ["bar"] * 2,
                         "v": [1.0, 2.0, 3.0, 1.0, 2.0, 2.0, 5.0, 7.0, 8.0],
                         "w": list("abcdefghi")})
    return full, ["name"]


@pytest.mark.parametrize("case", ["shuffled_groups", "any_rows_of_the_cut_group",
                                  "a_row_of_no_group", "out_of_order"])
def test_tie_groups_compare_as_sets_and_the_cut_group_by_membership(case):
    full, keys = _tie_case()
    if case == "shuffled_groups":  # LIMIT 7 cuts nothing: `anti` in another order
        got = full.iloc[[2, 0, 1, 6, 4, 3, 5]].reset_index(drop=True)
    elif case == "any_rows_of_the_cut_group":  # LIMIT 5: any two of `anti`'s four rows
        got = full.iloc[[0, 1, 2, 6, 4]].reset_index(drop=True)
    elif case == "a_row_of_no_group":
        got = full.iloc[[0, 1, 2, 6, 4]].reset_index(drop=True)
        got.loc[3, "v"] = 6.0
    else:
        got = full.iloc[[3, 0, 1, 2, 4]].reset_index(drop=True)
    if case == "out_of_order":
        with pytest.raises(AssertionError, match="ORDER BY"):
            tie_aligned(got, full, keys)
        return
    g, w = tie_aligned(got, full, keys)
    numbers = compare.compare(g, w)
    assert compare.within_limits(numbers) == (case != "a_row_of_no_group"), numbers


# -- the rounds of the group-by's hash insert (PR 40) ---------------------------------------
def _insert_rounds_by_hand(packed, capacity):
    """The claim protocol of ``hashagg._probe_insert`` in plain Python over one page of
    distinct, valid keys and an empty table: the rounds it takes."""
    import jax.numpy as jnp
    from trino_tpu.ops import hashing

    h0 = hashing.splitmix64(jnp.asarray(packed, jnp.int64))
    stp = [int(v) for v in np.asarray(hashing.probe_step(h0))]
    h0 = [int(v) for v in np.asarray(h0)]
    table, left, rounds = {}, list(range(len(packed))), 0
    while left:
        at = {i: (h0[i] + rounds * stp[i]) & (capacity - 1) for i in left}
        claims = {}  # the scatter-min over the words of those that found a slot empty
        for i in left:
            if at[i] not in table:
                claims[at[i]] = min(claims.get(at[i], packed[i]), packed[i])
        table.update(claims)
        left = [i for i in left if table[at[i]] != packed[i]]
        rounds += 1
    return rounds


def test_the_insert_hands_back_rounds_times_width_on_a_known_collision_chain():
    """Three keys whose first probe is one slot: the smallest word claims it, the other
    two go on, so the loop runs as many rounds as the longest chain, every one (under
    `hashing.INSERT_MIN_LANES`) at the width of the page; the count is what
    ``groupby_insert(with_rounds=True)`` returns, one entry a width."""
    import jax.numpy as jnp
    from trino_tpu.ops import hashagg, hashing
    from trino_tpu.types import BIGINT

    C, width = 1 << 10, 64
    keys = np.arange(1, 200_000, dtype=np.int64)
    first = np.asarray(hashing.splitmix64(jnp.asarray(keys))) & (C - 1)
    slot, n = np.unique(first, return_counts=True)
    chain = keys[first == slot[np.argmax(n >= 3)]][:3]  # they collide at round 0
    page = np.concatenate([chain, np.zeros(width - 3, np.int64)])
    valid = np.arange(width) < 3
    state = hashagg.groupby_init(C, (jnp.int64,), ((jnp.int64, 0),))
    state, rounds = hashagg.groupby_insert(
        state, (jnp.asarray(page),), (BIGINT,), jnp.asarray(valid), ((None, None),),
        ("count_star",), with_rounds=True)
    packed = np.asarray(hashing.pack_keys((jnp.asarray(chain),), (BIGINT,))[0])
    want = _insert_rounds_by_hand([int(v) for v in packed], C)
    # (one entry a width of the page: 64 lanes are under the floor, one width)
    assert rounds.tolist() == [want] and want >= 2 and not bool(state.overflow)
    assert int(np.asarray(state.accs[0])[:C].sum()) == 3
    # and the plain call is the state alone, as every other caller takes it
    again = hashagg.groupby_insert(
        hashagg.groupby_init(C, (jnp.int64,), ((jnp.int64, 0),)),
        (jnp.asarray(page),), (BIGINT,), jnp.asarray(valid), ((None, None),),
        ("count_star",))
    assert isinstance(again, hashagg.GroupByState)
    assert (np.asarray(again.table) == np.asarray(state.table)).all()


def test_the_insert_round_lanes_reach_explain_analyze_and_the_metrics(tpch_sf001):
    from test_profiling import _parse_prometheus
    from trino_tpu.server.server import CoordinatorServer

    e = Engine()
    e.register_catalog("tpch", tpch_sf001)
    # (a computed key has no range to index directly: hash mode, one masked insert a page)
    sql = "select l_partkey * 7919 + l_suppkey k, count(*) n from lineitem group by 1"
    w = _replayed(e, sql, "tpch")
    lanes = w.groupby_insert_lanes
    assert lanes > 0 and w.groupby_regrows == 0
    # rounds of every inserted lane: at least one, at most MAX_PROBES (whole rounds of
    # the page under `hashing.INSERT_MIN_LANES`; over it the later rounds run narrower)
    from trino_tpu.ops import hashagg

    assert lanes <= w.groupby_insert_round_lanes <= hashagg.MAX_PROBES * lanes
    # the sum rides the overflow flag's pull, no pull and no dispatch of its own: the one
    # chunk's and the loop's end are the statement's two pulls at that site
    (pulls,) = [rec for site, rec in w.sites.items() if site.endswith("agg.hash.overflow")]
    assert pulls["transfers"] == 2, w.sites
    r = e.execute_sql("explain analyze " + sql, e.create_session("tpch"))
    text = "\n".join(str(row[0]) for row in r.rows())
    c = e.last_query_counters
    m = re.search(r"(\d+) lanes inserted; (\d+) lanes in insert rounds", text)
    assert m, text
    assert tuple(map(int, m.groups())) == (c.groupby_insert_lanes,
                                          c.groupby_insert_round_lanes)
    assert c.groupby_insert_round_lanes == w.groupby_insert_round_lanes
    total = e.counters_total
    srv = CoordinatorServer(e, port=0)
    srv.start()
    try:
        parsed = _parse_prometheus(urllib.request.urlopen(
            srv.url + "/v1/metrics", timeout=10).read().decode())
    finally:
        srv.stop()
    name = "trino_tpu_groupby_insert_round_lanes_total"
    assert parsed["types"][name] == "counter"
    assert parsed["samples"][name][0][1] == total.groupby_insert_round_lanes \
        >= c.groupby_insert_round_lanes
    assert total.as_dict()["groupby_insert_round_lanes"] == total.groupby_insert_round_lanes


@pytest.mark.parametrize("case", ["cut-by-the-estimates-cap", "sized-by-hand"])
def test_a_first_run_makes_room_for_a_page_where_the_cap_cut_its_table(
        case, tpch_sf001, monkeypatch):
    """Until a plan has proven a capacity, a table that the estimate's cap cut short
    takes ONE step of four times the slots before the first page that alone has more
    live lanes than it has slots: the page goes in once, behind the rehash of an empty table, which runs
    no round.  A table sized by hand (or by default) takes none: live lanes are not
    groups, and only an overflow grows it."""
    from trino_tpu.exec import local_executor

    # (lineitem's row bound asks for 2^17 slots)
    monkeypatch.setattr(local_executor, "DEFAULT_GROUP_CAPACITY", 1024)
    monkeypatch.setattr(local_executor, "FIRST_CAPACITY_CAP", 1 << 14)
    e = Engine()
    e.register_catalog("tpch", tpch_sf001)
    sql = "select l_partkey * 7907 + l_suppkey k, count(*) n from lineitem group by 1"

    def run():
        session = e.create_session("tpch")
        if case == "sized-by-hand":
            session.properties["group_by_capacity"] = 1 << 14
        r = e.execute_sql(sql, session)
        spans = (e.last_query_trace or {}).get("spans", ())
        started = [int(s["attributes"]["slots"]) for s in spans
                   if s["name"] == "aggregate.hash"]
        return len(r), started, e.last_query_counters

    (n, started, first), (_, again_started, again) = run(), run()
    page = again.groupby_insert_lanes  # the replay: the one page, once
    assert started == [1 << 14] and n <= (1 << 14) // 2  # the groups would have fitted
    assert again.groupby_insert_round_lanes >= page > 0 and first.groupby_regrows == 0
    if case == "sized-by-hand":
        assert first.groupby_slots == 1 << 14 and first.groupby_insert_lanes == page
    else:
        assert first.groupby_slots == 1 << 16 and again_started == [1 << 16]
        assert first.groupby_insert_lanes == page + (1 << 14)  # (a rehash's slots count)
    assert first.groupby_insert_round_lanes >= again.groupby_insert_round_lanes


# -- the generators' warm launches (PR 40: both connectors) ---------------------------------
@pytest.mark.parametrize("connector", ["tpcds", "tpch"])
def test_a_scan_waits_for_its_generators_warm_launch(connector):
    """``warm_scan`` starts a (table, columns)'s generator compiling once a connector,
    and a scan that reaches the generator meanwhile waits for that launch, where it used
    to compile the same program a second time beside it."""
    from trino_tpu.connectors.tpch import TpchConnector

    conn, table, cols = (TpcdsConnector(sf=SF, split_rows=4096), "store", ["s_store_sk"]) \
        if connector == "tpcds" else (TpchConnector(sf=SF, split_rows=4096), "nation",
                                      ["n_nationkey"])
    order = []

    def slow(split, columns):
        time.sleep(0.3)
        order.append(("warm", split.table, tuple(columns)))

    conn.warm_scan(table, tuple(cols), generate=slow)
    conn.warm_scan(table, tuple(cols), generate=slow)  # once a connector
    page = conn.generate(conn.splits(table)[0], cols)
    order.append("scan")
    assert order == [("warm", table, tuple(cols)), "scan"] and page.capacity > 0
    # only its OWN column set's launch: another set's is still asleep when this returns
    conn.warm_scan(table, tuple(conn.schema(table).names),
                   generate=lambda split, columns: (time.sleep(2.0), slow(split, columns)))
    conn.generate(conn.splits(table)[0], cols)
    assert len(order) == 2
