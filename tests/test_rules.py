"""Iterative rule-based optimizer: Memo mechanics + one plan assertion per
rule + fixpoint behavior (reference test model: the per-rule BaseRuleTest
subclasses under sql/planner/iterative/rule/, e.g. TestMergeFilters, each
asserting on the rewritten plan shape)."""

import dataclasses
import os

import numpy as np
import pytest

from benchmark.harness.loader import _load_module
from trino_tpu import Engine
from trino_tpu.connectors.memory import MemoryConnector
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.page import Field, Schema
from trino_tpu.sql import ir
from trino_tpu.sql import plan as P
from trino_tpu.sql.frontend import compile_sql
from trino_tpu.sql.rules import (DEFAULT_RULES, IterativeOptimizer, Memo,
                                 optimize_plan)
from trino_tpu.types import BIGINT, BOOLEAN


def _scan():
    schema = Schema((Field("a", BIGINT), Field("b", BIGINT)))
    return P.TableScan("cat", "t", ("a", "b"), schema)


def _pred(ch, op, v):
    return ir.Call(op, (ir.FieldRef(ch, BIGINT), ir.Constant(v, BIGINT)),
                   BOOLEAN)


def _find(node, kind):
    out = []

    def walk(n):
        if isinstance(n, kind):
            out.append(n)
        for c in n.children:
            walk(c)

    walk(node)
    return out


def _opt(plan):
    return IterativeOptimizer(DEFAULT_RULES).run(plan)


def test_memo_roundtrip():
    plan = P.Limit(P.Filter(_scan(), _pred(0, "lt", 5)), 3)
    m = Memo(plan)
    assert m.extract() == plan  # insert + extract is identity


def test_merge_filters():
    plan = P.Filter(P.Filter(P.Filter(_scan(), _pred(0, "lt", 5)),
                             _pred(1, "gt", 1)), _pred(0, "gt", 0))
    out = _opt(plan)
    filters = _find(out, P.Filter)
    assert len(filters) == 1  # fixpoint: the whole chain merged
    # all three conjuncts survive in one AND tree
    assert "lt" in repr(filters[0].predicate)
    assert "gt" in repr(filters[0].predicate)


def test_merge_limits():
    plan = P.Limit(P.Limit(_scan(), 10), 3)
    out = _opt(plan)
    limits = _find(out, P.Limit)
    assert len(limits) == 1 and limits[0].count == 3
    plan = P.Limit(P.Limit(_scan(), 2), 7)
    assert _find(_opt(plan), P.Limit)[0].count == 2


def test_eliminate_limit_zero():
    plan = P.Limit(P.Filter(_scan(), _pred(0, "lt", 5)), 0)
    out = _opt(plan)
    assert isinstance(out, P.Values) and out.rows == ()
    assert not _find(out, P.TableScan)  # the pipeline under it is gone


def test_remove_identity_project():
    scan = _scan()
    plan = P.Project(scan, (ir.FieldRef(0, BIGINT), ir.FieldRef(1, BIGINT)),
                     scan.schema, None)
    out = _opt(P.Limit(plan, 5))
    assert not _find(out, P.Project)
    # a renaming projection is NOT removed
    renamed = Schema((Field("x", BIGINT), Field("y", BIGINT)))
    plan = P.Project(scan, (ir.FieldRef(0, BIGINT), ir.FieldRef(1, BIGINT)),
                     renamed, None)
    assert _find(_opt(P.Limit(plan, 5)), P.Project)


def test_eliminate_sort_under_aggregate():
    agg = P.Aggregate(
        P.Sort(_scan(), (P.SortKey(0),)), (0,),
        (P.AggSpec("count_star", None, "c", BIGINT),),
        Schema((Field("a", BIGINT), Field("c", BIGINT))))
    out = _opt(agg)
    assert not _find(out, P.Sort)
    # Sort directly under Limit (the TopN shape) is preserved
    topn = P.Limit(P.Sort(_scan(), (P.SortKey(0),)), 5)
    assert _find(_opt(topn), P.Sort)


def test_infer_join_side_filters():
    left, right = _scan(), _scan()
    join = P.Join(
        "inner", P.Filter(left, _pred(0, "lt", 100)), right, (0,), (1,),
        Schema(tuple(left.schema.fields) + tuple(right.schema.fields)))
    out = _opt(join)
    j = _find(out, P.Join)[0]
    # the right side gained the mirrored comparison on ITS key channel
    rfilters = _find(j.right, P.Filter)
    assert rfilters, "expected inferred filter on the build side"
    pred = rfilters[0].predicate
    assert isinstance(pred, ir.Call) and pred.op == "lt"
    ref, const = pred.args
    assert isinstance(ref, ir.FieldRef) and ref.index == 1  # right key channel
    assert ref.type == right.schema.fields[1].type  # destination field's type
    assert const.value == 100
    # outer joins must NOT infer (unmatched rows survive)
    outer = P.Join(
        "left", P.Filter(left, _pred(0, "lt", 100)), right, (0,), (1,),
        Schema(tuple(left.schema.fields) + tuple(right.schema.fields)))
    j2 = _find(_opt(outer), P.Join)[0]
    assert not _find(j2.right, P.Filter)


def test_rules_fixpoint_terminates():
    """Stacked rewrites converge: filters + limits + identity projects in one
    tree all fire without looping."""
    scan = _scan()
    plan = P.Limit(
        P.Limit(
            P.Project(
                P.Filter(P.Filter(scan, _pred(0, "lt", 5)), _pred(1, "gt", 1)),
                (ir.FieldRef(0, BIGINT), ir.FieldRef(1, BIGINT)),
                scan.schema, None),
            10),
        3)
    out = _opt(plan)
    assert len(_find(out, P.Filter)) == 1
    assert len(_find(out, P.Limit)) == 1
    assert not _find(out, P.Project)


# ------------------------------------------------------------- end-to-end SQL
@pytest.fixture(scope="module")
def tpch_engine():
    e = Engine()
    e.register_catalog("tpch", TpchConnector(sf=0.01, split_rows=1 << 11))
    return e, e.create_session("tpch")


def test_sql_limit_zero_short_circuits(tpch_engine):
    e, s = tpch_engine
    assert e.execute_sql(
        "select l_orderkey from lineitem limit 0", s).rows() == []


def test_sql_infer_join_filter_correct(tpch_engine):
    """Inference keeps results identical while the plan gains the mirrored
    filter (checked via the compiled plan)."""
    e, s = tpch_engine
    q = ("select count(*) c from lineitem, orders "
         "where l_orderkey = o_orderkey and o_orderkey < 1000")
    plan = compile_sql(q, e, s)
    joins = _find(plan, P.Join)
    assert joins
    assert _find(joins[0].left, P.Filter), "expected inferred probe-side filter"
    got = e.execute_sql(q, s).rows()
    # oracle: the filter on the join key holds on both sides by transitivity
    expected = e.execute_sql(
        "select count(*) c from lineitem, orders "
        "where l_orderkey = o_orderkey and o_orderkey < 1000 "
        "and l_orderkey < 1000", s).rows()
    assert got == expected


def test_sql_subquery_sort_removed_under_group_by(tpch_engine):
    e, s = tpch_engine
    q = ("select l_returnflag, count(*) c from "
         "(select * from lineitem order by l_orderkey) "
         "group by l_returnflag order by l_returnflag")
    plan = compile_sql(q, e, s)
    aggs = _find(plan, P.Aggregate)
    assert aggs and not _find(aggs[0].child, P.Sort)
    rows = e.execute_sql(q, s).rows()
    expected = e.execute_sql(
        "select l_returnflag, count(*) c from lineitem "
        "group by l_returnflag order by l_returnflag", s).rows()
    assert rows == expected


def test_push_filter_through_project():
    proj = P.Project(_scan(), (ir.FieldRef(1, BIGINT, "b"),
                               ir.FieldRef(0, BIGINT, "a")),
                     Schema((Field("b", BIGINT), Field("a", BIGINT))))
    plan = P.Filter(proj, _pred(0, "lt", 5))  # filters on OUTPUT channel 0 = b
    out = _opt(plan)
    assert isinstance(out, P.Project)
    filt = _find(out, P.Filter)
    assert len(filt) == 1
    # the rewritten predicate references INPUT channel 1 (column b)
    assert filt[0].predicate.args[0].index == 1
    assert isinstance(filt[0].child, P.TableScan)


def test_push_limit_through_project_keeps_topn():
    proj = P.Project(_scan(), (ir.FieldRef(0, BIGINT, "a"),
                               ir.FieldRef(1, BIGINT, "b")),
                     Schema((Field("a", BIGINT), Field("b", BIGINT))))
    out = _opt(P.Limit(proj, 7))
    # identity project is ALSO removed; the limit must sit under any project
    lims = _find(out, P.Limit)
    assert len(lims) == 1 and isinstance(lims[0].child, P.TableScan)
    # Limit(Project(Sort)) stays a TopN shape: the limit must NOT split from
    # its sort
    srt = P.Sort(_scan(), (P.SortKey(0, True, False),))
    proj2 = P.Project(srt, (ir.FieldRef(0, BIGINT, "a"),
                            ir.FieldRef(1, BIGINT, "bb")),
                      Schema((Field("a", BIGINT), Field("bb", BIGINT))))
    out2 = _opt(P.Limit(proj2, 7))
    lims2 = _find(out2, P.Limit)
    assert len(lims2) == 1


def test_remove_trivial_filter():
    t = _opt(P.Filter(_scan(), ir.Constant(True, BOOLEAN)))
    assert isinstance(t, P.TableScan)
    f = _opt(P.Filter(_scan(), ir.Constant(False, BOOLEAN)))
    assert isinstance(f, P.Values) and len(f.rows) == 0


def test_merge_unions_flattens():
    s = _scan()
    inner = P.Union((s, _scan()), s.schema)
    outer = P.Union((inner, _scan()), s.schema)
    out = _opt(outer)
    assert isinstance(out, P.Union)
    assert len(out.inputs) == 3
    assert all(isinstance(c, P.TableScan) for c in out.inputs)


def test_push_limit_through_union():
    s = _scan()
    u = P.Union((s, _scan()), s.schema)
    out = _opt(P.Limit(u, 5))
    assert isinstance(out, P.Limit)
    inner = out.child
    assert isinstance(inner, P.Union)
    assert all(isinstance(c, P.Limit) and c.count == 5 for c in inner.inputs)


def test_remove_redundant_limit_over_global_agg():
    agg = P.Aggregate(_scan(), (), (P.AggSpec("count_star", None, "c",
                                              BIGINT),),
                      Schema((Field("c", BIGINT),)))
    out = _opt(P.Limit(agg, 10))
    assert isinstance(out, P.Aggregate)


# ---------------------------------------------------------------- round-5 rules
def _join(kind="inner"):
    l = _scan()
    r_schema = Schema((Field("c", BIGINT), Field("d", BIGINT)))
    r = P.TableScan("cat", "u", ("c", "d"), r_schema)
    schema = Schema((Field("l0", BIGINT), Field("l1", BIGINT),
                     Field("r0", BIGINT), Field("r1", BIGINT)))
    if kind in ("semi", "anti"):
        schema = Schema((Field("l0", BIGINT), Field("l1", BIGINT)))
    return P.Join(kind, l, r, (0,), (0,), schema)


def test_push_filter_through_join_splits_sides():
    pred = ir.Call("and", (_pred(1, "gt", 5), _pred(3, "lt", 9)), BOOLEAN)
    out = _opt(P.Filter(_join("inner"), pred))
    join = _find(out, P.Join)[0]
    assert isinstance(out, P.Join) or not isinstance(out, P.Filter)
    lf = _find(join.left, P.Filter)
    rf = _find(join.right, P.Filter)
    assert lf and rf, "both side-local conjuncts must push below the join"
    # the right conjunct's channel remapped into build-side coordinates
    assert "$1" in repr(rf[0].predicate)


def test_push_filter_through_outer_join_keeps_build_conjunct():
    # (a conjunct that a NULL-extended row can pass: IS NULL rejects no NULL)
    keeps_nulls = ir.Call("is_null", (ir.FieldRef(3, BIGINT),), BOOLEAN)
    pred = ir.Call("and", (_pred(1, "gt", 5), keeps_nulls), BOOLEAN)
    out = _opt(P.Filter(_join("left"), pred))
    join = _find(out, P.Join)[0]
    assert join.kind == "left"
    assert _find(join.left, P.Filter), "probe conjunct pushes"
    assert not _find(join.right, P.Filter), \
        "NULL-extended build conjunct must NOT push below a left join"
    assert isinstance(out, P.Filter), "build conjunct stays above"


def test_outer_join_under_a_null_rejecting_build_conjunct_becomes_inner():
    """PR 36 (TPC-DS q93): a comparison on a build-side column is not true of a
    NULL-extended row, so the LEFT join is an INNER join, and then the build conjunct
    pushes below it like any other."""
    pred = ir.Call("and", (_pred(1, "gt", 5), _pred(3, "lt", 9)), BOOLEAN)
    out = _opt(P.Filter(_join("left"), pred))
    join = _find(out, P.Join)[0]
    assert join.kind == "inner" and not isinstance(out, P.Filter)
    assert _find(join.left, P.Filter) and _find(join.right, P.Filter)


def test_outer_join_under_an_inner_join_keyed_on_its_build_side_becomes_inner():
    lj = _join("left")
    r2 = P.TableScan("cat", "v", ("e",), Schema((Field("e", BIGINT),)))
    schema = Schema(lj.schema.fields + (Field("e", BIGINT),))
    on_build = _opt(P.Join("inner", lj, r2, (2,), (0,), schema))  # keyed on r0
    assert [j.kind for j in _find(on_build, P.Join)] == ["inner", "inner"]
    on_probe = _opt(P.Join("inner", lj, r2, (1,), (0,), schema))  # keyed on l1
    assert sorted(j.kind for j in _find(on_probe, P.Join)) == ["inner", "left"]


def test_push_filter_through_aggregate_keys_only():
    agg_schema = Schema((Field("a", BIGINT), Field("n", BIGINT)))
    agg = P.Aggregate(_scan(), (0,),
                      (P.AggSpec("count_star", None, "n", BIGINT),),
                      agg_schema)
    # key-channel conjunct pushes; agg-output conjunct stays
    pred = ir.Call("and", (_pred(0, "gt", 3), _pred(1, "lt", 100)), BOOLEAN)
    out = _opt(P.Filter(agg, pred))
    assert isinstance(out, P.Filter), "agg-output conjunct stays above"
    agg2 = _find(out, P.Aggregate)[0]
    inner_f = _find(agg2.child, P.Filter) + (
        [agg2.child] if isinstance(agg2.child, P.Filter) else [])
    assert inner_f, "key conjunct must push below the aggregation"


def test_push_filter_through_window_partition_keys():
    w_schema = Schema((Field("a", BIGINT), Field("b", BIGINT),
                       Field("rn", BIGINT)))
    spec = P.WindowSpec("row_number", None, (0,), (P.SortKey(1),),
                        "rn", BIGINT)
    win = P.Window(_scan(), (spec,), w_schema)
    pred = ir.Call("and", (_pred(0, "eq", 7), _pred(1, "gt", 2)), BOOLEAN)
    out = _opt(P.Filter(win, pred))
    assert isinstance(out, P.Filter), "non-partition conjunct stays above"
    win2 = _find(out, P.Window)[0]
    assert isinstance(win2.child, P.Filter), \
        "partition-key conjunct pushes below the window"


def test_push_filter_through_union_and_sort():
    u_schema = Schema((Field("a", BIGINT), Field("b", BIGINT)))
    u = P.Union((_scan(), _scan()), u_schema)
    out = _opt(P.Filter(u, _pred(0, "gt", 1)))
    assert not isinstance(out, P.Filter)
    union = _find(out, P.Union)[0]
    for c in union.children:
        assert _find(c, P.Filter) or isinstance(c, P.Filter)
    out2 = _opt(P.Filter(P.Sort(_scan(), (P.SortKey(0),)), _pred(0, "gt", 1)))
    assert isinstance(out2, P.Sort), "filter moves below the sort"


def test_empty_propagation_collapses_pipeline():
    # LIMIT 0 seeds an empty Values; everything above collapses with it
    plan = P.Sort(P.Filter(P.Limit(_scan(), 0), _pred(0, "gt", 1)),
                  (P.SortKey(0),))
    out = _opt(plan)
    assert isinstance(out, P.Values) and not out.rows
    # inner join with an empty side collapses too
    j = _join("inner")
    j = dataclasses.replace(j, right=P.Values((), j.right.schema))
    out2 = _opt(j)
    assert isinstance(out2, P.Values) and not out2.rows


def test_merge_adjacent_projects():
    s = _scan()
    inner = P.Project(s, (ir.FieldRef(1, BIGINT), ir.FieldRef(0, BIGINT)),
                      Schema((Field("x", BIGINT), Field("y", BIGINT))))
    outer = P.Project(inner, (ir.Call("add", (ir.FieldRef(0, BIGINT),
                                              ir.FieldRef(1, BIGINT)),
                                      BIGINT),),
                      Schema((Field("z", BIGINT),)))
    out = _opt(outer)
    projs = _find(out, P.Project)
    assert len(projs) == 1, "adjacent projects must merge"
    assert "add" in repr(projs[0].exprs[0])


def test_simplify_constant_predicate():
    t = ir.Call("lt", (ir.Constant(1, BIGINT), ir.Constant(2, BIGINT)),
                BOOLEAN)
    out = _opt(P.Filter(_scan(), t))
    assert isinstance(out, P.TableScan), "1<2 folds to TRUE -> filter gone"
    f = ir.Call("gt", (ir.Constant(1, BIGINT), ir.Constant(2, BIGINT)),
                BOOLEAN)
    out2 = _opt(P.Filter(_scan(), f))
    assert isinstance(out2, P.Values) and not out2.rows


def test_values_folding_filter_and_limit():
    schema = Schema((Field("a", BIGINT),))
    vals = P.Values(((1,), (5,), (9,)), schema)
    out = _opt(P.Filter(vals, _pred(0, "gt", 4)))
    assert isinstance(out, P.Values) and out.rows == ((5,), (9,))
    out2 = _opt(P.Limit(P.Values(((1,), (2,), (3,)), schema), 2))
    assert isinstance(out2, P.Values) and out2.rows == ((1,), (2,))


def test_dedup_sort_and_join_keys():
    s = P.Sort(_scan(), (P.SortKey(0), P.SortKey(1), P.SortKey(0, False)))
    out = _opt(s)
    assert tuple(k.channel for k in out.keys) == (0, 1)
    j = P.Join("inner", _scan(), _scan(), (0, 1, 0), (0, 1, 0),
               Schema((Field("l0", BIGINT), Field("l1", BIGINT),
                       Field("r0", BIGINT), Field("r1", BIGINT))))
    out2 = _opt(j)
    assert out2.left_keys == (0, 1) and out2.right_keys == (0, 1)


def test_distinct_over_distinct_collapses():
    inner_schema = Schema((Field("a", BIGINT),))
    inner = P.Aggregate(_scan(), (0,), (), inner_schema)
    outer = P.Aggregate(inner, (0,), (), inner_schema)
    out = _opt(outer)
    aggs = _find(out, P.Aggregate)
    assert len(aggs) == 1, "stacked DISTINCT must collapse to one"


def test_push_filter_through_union_with_existing_branch_filter():
    """A branch's own unrelated filter must not block pushing a NEW predicate
    into every branch (round-5 review finding)."""
    u_schema = Schema((Field("a", BIGINT), Field("b", BIGINT)))
    filtered_branch = P.Filter(_scan(), _pred(1, "lt", 100))
    u = P.Union((filtered_branch, _scan()), u_schema)
    out = _opt(P.Filter(u, _pred(0, "gt", 1)))
    assert not isinstance(out, P.Filter), "predicate must push below the union"
    union = _find(out, P.Union)[0]
    for c in union.children:
        preds = repr([f.predicate for f in _find(c, P.Filter)]
                     + ([c.predicate] if isinstance(c, P.Filter) else []))
        assert "gt" in preds, f"branch missing pushed predicate: {preds}"


def test_merge_projects_guards_duplicated_expensive_expr():
    """A non-trivial inner expression referenced twice above must NOT inline
    (exponential-growth guard, InlineProjections analog)."""
    s = _scan()
    inner = P.Project(s, (ir.Call("mul", (ir.FieldRef(0, BIGINT),
                                          ir.FieldRef(1, BIGINT)), BIGINT),),
                      Schema((Field("x", BIGINT),)))
    outer = P.Project(inner, (ir.Call("add", (ir.FieldRef(0, BIGINT),
                                              ir.FieldRef(0, BIGINT)),
                                      BIGINT),),
                      Schema((Field("z", BIGINT),)))
    out = _opt(outer)
    assert len(_find(out, P.Project)) == 2, "double-use inner expr must stay"


# ------------------------------------------------- PushSemiJoinThroughJoin (PR 30)
@pytest.fixture(scope="module")
def semi_tables():
    """p, b, d, c in a memory catalog, NULL keys in all of them; -> the engine
    and each table's TableScan as the planner makes it."""
    e = Engine()
    e.register_catalog("mem", MemoryConnector())
    s = e.create_session("mem")
    e.execute_sql("create table p (k bigint, v bigint)", s)
    e.execute_sql("create table b (k bigint, w bigint)", s)
    e.execute_sql("create table d (w bigint, x bigint)", s)
    e.execute_sql("create table c (k bigint, j bigint)", s)
    rows = ", ".join(f"({'null' if i % 3 == 0 else i % 40}, {i})"
                     for i in range(200))
    e.execute_sql(f"insert into p values {rows}", s)
    e.execute_sql("insert into b values (1, 10), (2, 20), (7, 70), "
                  "(100, 1000), (null, 5), (8, 7)", s)
    e.execute_sql("insert into d values (10, 1), (70, 2), (7, 3), (null, 4)", s)
    e.execute_sql("insert into c values (1, 10), (7, 70), (null, 5), (8, 9), "
                  "(55, 1), (41, 7)", s)
    scans = {t: _find(compile_sql(f"select * from {t}", e, s), P.TableScan)[0]
             for t in "pbdc"}
    return e, scans


def _cat(*nodes):
    return Schema(tuple(f for n in nodes for f in n.schema.fields))


def _semi_case(name, t):
    """-> (plan, where the semi-join must end up).  ``pb`` is p joined to b on
    k: channels 0, 1 are p's (the probe side), 2, 3 are b's (the build side)."""
    p, b, d, c = (t[x] for x in "pbdc")
    kind = {"left_preserved": "left", "left_null_extended": "left"}.get(
        name, "inner")
    pb = P.Join(kind, p, b, (0,), (0,), _cat(p, b))

    def semi(keys, kind="semi", over=pb, right_keys=(0,), schema=None, **kw):
        return P.Join(kind, over, c, keys, right_keys, schema or over.schema,
                      **kw)

    if name == "inner_build_key":          # q18's: o_orderkey IN (...)
        return semi((2,)), "right"
    if name == "inner_probe_key":
        return semi((0,)), "left"
    if name == "left_preserved":
        return semi((0,)), "left"
    if name == "left_null_extended":       # b's rows below are not b's above
        return semi((2,)), "stays"
    if name == "both_sides":
        return semi((1, 3), right_keys=(0, 1)), "stays"
    if name == "mark":                     # it adds a channel
        return semi((2,), kind="mark", schema=Schema(
            pb.schema.fields + (Field("m", BOOLEAN),))), "stays"
    if name == "anti":
        return semi((2,), kind="anti"), "stays"
    if name == "anti_null_aware":
        return semi((2,), kind="anti", null_aware=True), "stays"
    if name == "residual_filter":          # p.v < c.j decides a match too
        return semi((2,), filter=ir.Call(
            "lt", (ir.FieldRef(1, BIGINT), ir.FieldRef(5, BIGINT)),
            BOOLEAN)), "stays"
    if name == "stacked_inner":            # passes pbd, then lands on b
        pbd = P.Join("inner", pb, d, (3,), (0,), _cat(pb, d))
        return semi((2,), over=pbd), "right_of_lower"
    if name == "null_aware_build_key":     # NULLs in p.k, b.k and c.k
        return semi((2,), null_aware=True), "right"
    if name == "null_aware_probe_key":
        return semi((0,), null_aware=True), "left"
    if name == "over_semi":                # two IN-subqueries over one input:
        ps = P.Join("semi", p, b, (0,), (0,), p.schema)  # they would swap for ever
        return semi((0,), over=ps), "stays"
    if name == "over_anti":                # NOT IN below, IN above: passes it
        pa = P.Join("anti", p, d, (1,), (0,), p.schema)
        return semi((0,), over=pa), "left"
    raise KeyError(name)


SEMI_CASES = ("inner_build_key", "inner_probe_key", "left_preserved",
              "left_null_extended", "both_sides", "mark", "anti",
              "anti_null_aware", "residual_filter", "stacked_inner",
              "null_aware_build_key", "null_aware_probe_key", "over_semi",
              "over_anti")


def _optimized(root, rule_list):
    """-> (the plan as ``optimize_plan`` would leave it, rule applications tried)."""
    from trino_tpu.sql.optimizer import prune_columns

    opt = IterativeOptimizer(rule_list)
    out = prune_columns(opt.run(root))
    return out, opt.max_iterations - opt._budget


def _below_exchanges(node):
    while isinstance(node, P.Exchange):
        node = node.child
    return node


def _without_semi_push():
    from trino_tpu.sql.rules import PushSemiJoinThroughJoin

    rest = tuple(r for r in DEFAULT_RULES
                 if not isinstance(r, PushSemiJoinThroughJoin))
    assert len(rest) == len(DEFAULT_RULES) - 1  # the rule is registered, once
    return rest


def _filtering(node):
    return node.kind in ("semi", "anti", "mark") and \
        _find(node.right, P.TableScan)[0].table == "c"


@pytest.mark.parametrize("name", SEMI_CASES)
def test_push_semi_join_through_join(name, semi_tables):
    """Plan shape, and the same rows as the plan made without the rule."""
    e, scans = semi_tables
    plan, where = _semi_case(name, scans)
    root = P.Output(plan, tuple(f"c{i}" for i in range(len(plan.schema.fields))))
    got, tried = _optimized(root, DEFAULT_RULES)
    assert tried < 200, "the rules chase each other"
    base, _ = _optimized(root, _without_semi_push())
    # without the rule the subquery's join stays where the planner put it
    assert _filtering(base.child), base
    top = got.child
    if where == "stays":
        assert got == base
    elif where == "right_of_lower":
        assert top.kind == "inner" and top.left.kind == "inner"
        assert _filtering(top.left.right) and top.left.right.left_keys == (0,)
        assert isinstance(top.left.right.left, P.TableScan)
        assert top.schema == plan.schema
    else:
        assert not _filtering(top) and top.schema == plan.schema
        moved = getattr(top, where)
        assert _filtering(moved) and moved.left_keys == (0,)
        assert moved.null_aware == plan.null_aware
        assert isinstance(moved.left, P.TableScan)
        assert moved.schema == moved.left.schema
        other = top.right if where == "left" else top.left
        assert not _find(other, P.Join)
    key = lambda r: tuple((x is None, x) for x in r)  # noqa: E731
    rows = sorted(e.execute_plan(got).rows(), key=key)
    assert rows == sorted(e.execute_plan(base).rows(), key=key)
    if name not in ("anti_null_aware", "residual_filter"):
        assert rows, "a case that keeps no row proves nothing"


Q18 = _load_module(os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "statements", "q18.py"), "q18")


def test_q18_semi_join_is_the_build_child_of_the_lineitem_orders_join(
        tpch_engine, monkeypatch):
    """The benchmark's q18 (benchmark/statements/q18.py): its IN-subquery
    filters orders inside the first join's build side, in the plan and in
    EXPLAIN, and the answer is the one the old plan gave."""
    e, s = tpch_engine
    sql, _ = Q18.render({"quantity": 250})  # 300 keeps no order at SF0.01
    plan = compile_sql(sql, e, s)
    first = next(j for j in _find(plan, P.Join)
                 if isinstance(j.left, P.TableScan)
                 and j.left.table == "lineitem" and j.kind == "inner")
    build = _below_exchanges(first.right)
    assert isinstance(build, P.Join) and build.kind == "semi", build
    assert build.left.table == "orders" and build.left_keys == (0,)
    assert [j.kind for j in _find(plan, P.Join)].count("semi") == 1
    text = [str(r[0]) for r in e.execute_sql("explain " + sql, s).rows()]
    at = {k: next(i for i, ln in enumerate(text) if k in ln) for k in
          ("TableScan[tpch.lineitem]", "SemiJoin[", "TableScan[tpch.orders]",
           "TableScan[tpch.customer]")}
    assert at["TableScan[tpch.lineitem]"] < at["SemiJoin["] \
        < at["TableScan[tpch.orders]"] < at["TableScan[tpch.customer]"], text
    got = e.execute_sql(sql, s).rows()
    assert len(got) > 0
    from trino_tpu.sql import rules

    monkeypatch.setattr(rules, "DEFAULT_RULES", _without_semi_push())
    old = compile_sql(sql, e, s)
    assert _find(old, P.Join)[0].kind == "semi"  # planned last, over the chain
    assert e.execute_plan(old).rows() == got


SEMI_SQL = {
    # TPC-H q20's outer shape: the semi-join lands on supplier, under nation's join
    "q20_shape": """
        select s_name, n_name from supplier, nation
        where s_suppkey in (select ps_suppkey from partsupp
                            where ps_availqty > 9900)
          and s_nationkey = n_nationkey and n_name = 'CANADA' order by s_name""",
    # two IN-subqueries over one join: each goes to the side that owns its key
    "two_in": """
        select o_orderkey, count(*) n from lineitem, orders
        where l_orderkey = o_orderkey
          and o_custkey in (select c_custkey from customer
                            where c_mktsegment = 'BUILDING')
          and l_partkey in (select p_partkey from part where p_size < 3)
        group by o_orderkey order by o_orderkey""",
    # a key the FROM relation computes: a Project sits between, nothing moves
    "computed_key": """
        select count(*) n from lineitem, orders
        where l_orderkey = o_orderkey
          and o_orderkey + 1 in (select o_orderkey from orders
                                 where o_totalprice > 400000)""",
}


@pytest.mark.parametrize("name", sorted(SEMI_SQL))
def test_push_semi_join_through_join_sql(name, tpch_engine, monkeypatch):
    e, s = tpch_engine
    plan = compile_sql(SEMI_SQL[name], e, s)
    semis = [j for j in _find(plan, P.Join) if j.kind == "semi"]

    def probe(j):
        return _below_exchanges(j.left)

    if name == "computed_key":
        assert not isinstance(probe(semis[0]), P.TableScan)
    else:
        assert semis and all(isinstance(probe(j), (P.TableScan, P.Filter)) or
                             probe(j).kind == "semi" for j in semis), plan
    got = e.execute_plan(plan).rows()
    assert got and (len(got) > 1 or got[0][0] > 0)
    from trino_tpu.sql import rules

    monkeypatch.setattr(rules, "DEFAULT_RULES", _without_semi_push())
    old = compile_sql(SEMI_SQL[name], e, s)
    assert len([j for j in _find(old, P.Join) if j.kind == "semi"]) == len(semis)
    assert isinstance(probe(_find(old, P.Join)[0]), (P.Join, P.Project))
    assert e.execute_plan(old).rows() == got
