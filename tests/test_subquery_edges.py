"""Regression tests for subquery/join planner edge cases found in review:
key-type coercion in semi joins, computed correlation keys, CTE scoping, scalar
subquery cardinality errors, distributed fallback for duplicate build keys."""

import pytest

from trino_tpu.sql.frontend import SemanticError


def test_in_subquery_key_type_coercion(engine):
    """decimal IN (select bigint ...): both sides must coerce to the common key type."""
    a = engine.execute_sql(
        "select count(*) c from lineitem where l_quantity in (select p_size from part)")
    lits = ",".join(str(i) for i in range(1, 51))
    b = engine.execute_sql(
        f"select count(*) c from lineitem where l_quantity in ({lits})")
    assert a.columns[0][0] == b.columns[0][0] > 0


def test_correlated_agg_computed_key(engine):
    """A computed/coerced correlation key appends a probe helper channel; the aggregate
    column must still resolve to the right channel."""
    plain = engine.execute_sql(
        "select count(*) c from orders where o_totalprice < "
        "(select sum(l_extendedprice) from lineitem where l_orderkey = o_orderkey)")
    computed = engine.execute_sql(
        "select count(*) c from orders where o_totalprice < "
        "(select sum(l_extendedprice) from lineitem where l_orderkey = o_orderkey + 0)")
    assert plain.columns[0][0] == computed.columns[0][0] > 0


def test_cte_shadowing(engine):
    r = engine.execute_sql("""
        with t as (select n_name from nation)
        select * from (with t as (select r_name from region)
                       select r_name from t) y limit 3""")
    assert r.names == ("r_name",) and len(r) == 3
    r = engine.execute_sql("with t as (select n_name from nation) select n_name from t")
    assert r.names == ("n_name",) and len(r) == 25


def test_scalar_subquery_cardinality_error(engine):
    with pytest.raises(SemanticError, match="exactly one value"):
        engine.execute_sql("select count(*) c from orders where o_totalprice > "
                           "(select o_totalprice from orders)")


def test_distributed_dup_key_join_falls_back(engine):
    r = engine.execute_sql(
        "select l_orderkey from lineitem, partsupp where ps_suppkey = l_suppkey limit 5",
        distributed=True)
    assert len(r) == 5


def test_empty_build_side_joins(engine):
    """Filters selecting zero build rows must not crash any join kind."""
    r = engine.execute_sql("""select count(*) c from nation left outer join customer
                              on n_nationkey = c_nationkey and c_acctbal < -99999999""")
    assert r.columns[0][0] == 25
    r = engine.execute_sql("""select count(*) c from nation, customer
                              where n_nationkey = c_nationkey and c_acctbal < -99999999""")
    assert r.columns[0][0] == 0


def test_correlated_count_empty_group(engine):
    """count() over an empty correlated group is 0, not a dropped row."""
    a = engine.execute_sql(
        "select count(*) c from customer where "
        "(select count(*) from orders where o_custkey = c_custkey) = 0")
    b = engine.execute_sql(
        "select count(*) c from customer where "
        "not exists (select * from orders where o_custkey = c_custkey)")
    assert a.columns[0][0] == b.columns[0][0] > 0


def test_exists_group_having_semantics(engine):
    with pytest.raises(SemanticError, match="HAVING"):
        engine.execute_sql(
            "select count(*) from customer where exists "
            "(select 1 from orders where o_custkey = c_custkey "
            " group by o_orderstatus having count(*) > 1000)")
    # ungrouped aggregate subquery always yields one row: EXISTS is constant-true
    r = engine.execute_sql("select count(*) c from nation where exists "
                           "(select max(o_orderkey) from orders where o_custkey = -1)")
    assert r.columns[0][0] == 25


def test_in_subquery_respects_limit(engine):
    a = engine.execute_sql(
        "select count(*) c from lineitem where l_partkey in "
        "(select p_partkey from part order by p_partkey limit 5)")
    b = engine.execute_sql(
        "select count(*) c from lineitem where l_partkey in (1, 2, 3, 4, 5)")
    assert a.columns[0][0] == b.columns[0][0] > 0


def test_exists_nested_explicit_joins(engine):
    r = engine.execute_sql("""
        select count(*) c from supplier s1 where exists (
            select 1 from lineitem l2
            join orders o2 on l2.l_orderkey = o2.o_orderkey
            join customer c2 on o2.o_custkey = c2.c_custkey
            where l2.l_suppkey = s1.s_suppkey and o2.o_orderstatus = 'F')""")
    assert r.columns[0][0] > 0


def test_not_in_null_semantics(engine):
    """x NOT IN (set containing NULL) is UNKNOWN -> no rows (SQL 3VL)."""
    r = engine.execute_sql(
        "select count(*) c from nation where n_nationkey not in "
        "(select case when r_regionkey > 0 then r_regionkey else null end from region)")
    assert r.columns[0][0] == 0
    r = engine.execute_sql(
        "select count(*) c from nation where n_nationkey in "
        "(select case when r_regionkey > 0 then r_regionkey else null end from region)")
    assert r.columns[0][0] == 4  # nationkeys 1..4


def test_constant_join_key(engine):
    r = engine.execute_sql(
        "select count(*) c from nation join region on r_regionkey = 0")
    assert r.columns[0][0] == 25


def test_dynamic_filter_split_pruning(tpch_sf001):
    """Inner/semi joins prune probe splits outside the build-key domain,
    observed through the page loop's generate calls."""
    from trino_tpu import Engine
    from trino_tpu.connectors.tpch import TpchConnector

    conn = TpchConnector(sf=0.01, split_rows=1 << 12)
    e = Engine()
    e.register_catalog("tpch", conn)
    calls = {"n": 0}
    orig = conn.generate

    def counting(split, columns=None):
        if split.table == "lineitem":
            calls["n"] += 1
        return orig(split, columns)

    conn.generate = counting
    n_splits = len(conn.splits("lineitem"))
    assert n_splits > 10
    r = e.execute_sql("select count(*) c from lineitem where l_orderkey in "
                      "(select o_orderkey from orders where o_orderkey < 100)")
    # the kept splits, plus ONE page for the split join's first-page sample
    # (PR 28: generated once as the join's stream is compiled)
    assert calls["n"] <= 3
    r2 = e.execute_sql("select count(*) c from lineitem, orders "
                       "where l_orderkey = o_orderkey and o_orderkey < 100")
    assert r.columns[0][0] == r2.columns[0][0] > 0
    # outer/anti joins must NOT prune
    calls["n"] = 0
    r3 = e.execute_sql("select count(*) c from lineitem where l_orderkey not in "
                       "(select o_orderkey from orders where o_orderkey >= 100)")
    assert calls["n"] == n_splits
    assert r3.columns[0][0] == r.columns[0][0]
