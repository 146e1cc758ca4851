"""Adaptive execution (round 19, execution/adaptive.py): the feedback loop
from recorded plan-actuals + measured compile costs to plan decisions.

What these tests pin:
- the advisor's decision model at the unit layer: material-misestimate
  gating (EWMA ratio >= threshold, "under" anywhere or "over" on a join
  build, CBO-blind nodes NEVER corrected), win-vs-price arithmetic (unknown
  price = hold), frozen replan tokens, probation -> confirm / regress ->
  demote -> cooldown -> reconsider, failed() demotion;
- the engine loop end-to-end: a join whose build side the CBO under-
  estimates 16x records history on execution 1, re-plans on execution 2
  (broadcast/auto -> partitioned via CONFIDENT observed-rows facts), with
  byte-identical results, the warm corrected dispatch count no worse than
  the uncorrected warm run, and the decision visible in counters, EXPLAIN
  (plain + ANALYZE "Adaptive:" line) and the flight record;
- hold when the compile price outweighs the predicted win (price_scale test
  hook), with warm counters UNCHANGED run-over-run (consult is free at the
  device boundary — the budget suite's ceilings stay pinned with the
  advisor enabled);
- satellite 1: ``adaptive_execution`` is plan-shaping — SET SESSION flips
  the ``_plan_shape_props`` component, so corrected and uncorrected plans
  can never share a plan/result/template cache key.
"""

import pytest

from trino_tpu import Engine
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.execution import history as H
from trino_tpu.execution.adaptive import (AdaptiveAdvisor, correction_token,
                                          describe_decision)

KEY = ("stmt", "tpch", False, "user", ())


def _store(nodes, fp="fp-base"):
    st = H.PlanHistoryStore(max_plans=8)
    st.record(fp, nodes)
    return st


def _rec(op="Join", est=100.0, actual=1600, wall=0.2, build=False,
         spill=None, splits=0):
    rec = {"op": op, "est_rows": est, "actual_rows": actual, "wall_s": wall,
           "spilled_bytes": 0, "spill_tiers": dict(spill or {}),
           "cache_hits": 0}
    if build:
        rec["build"] = True
    if splits:
        rec["splits"] = splits
    return rec


def _advisor(store, **kw):
    kw.setdefault("threshold", 4.0)
    kw.setdefault("horizon", 8.0)
    kw.setdefault("cooldown", 2)
    return AdaptiveAdvisor(history=store, compile_log=None, **kw)


def _base(adv, key=KEY, fp="fp-base", wall=0.2, compile_s=0.1):
    """One uncorrected completion: anchors base_fp, wall EWMA and the
    observed cold compile price."""
    adv.observe(key, fp, corrected=False, wall_s=wall,
                compiles=1, compile_s=compile_s, sql="select 1")


# ------------------------------------------------------------------ unit layer
def test_token_stable_and_order_independent():
    a = correction_token({"rows": {"Join#0.0": 10.0, "Filter#0.1": 5.0}})
    b = correction_token({"rows": {"Filter#0.1": 5.0, "Join#0.0": 10.0}})
    assert a == b and len(a) == 12
    assert a != correction_token({"rows": {"Join#0.0": 11.0}})


def test_no_history_no_opinion():
    adv = _advisor(_store({"Join#0.0": _rec()}))
    assert adv.consult(KEY) is None  # never observed: no state, no opinion
    disabled = AdaptiveAdvisor(history=H.PlanHistoryStore(max_plans=0))
    assert disabled.consult(KEY) is None


def test_under_misestimate_replans_with_frozen_token():
    adv = _advisor(_store({"Join#0.0": _rec(est=100.0, actual=1600)}))
    _base(adv)
    dec = adv.consult(KEY)
    assert dec is not None and dec["verdict"] == "replan"
    assert dec["corrections"]["rows"]["Join#0.0"] == pytest.approx(1600.0)
    # win = avg wall x (1 - 1/min(ratio, 10)) = 0.2 * 0.9; price = observed
    # cold compile seconds; win x horizon > price -> replan
    assert dec["predicted_win_s"] == pytest.approx(0.18)
    assert dec["compile_price_s"] == pytest.approx(0.1)
    assert dec["token"] and adv.info()["replans_total"] == 1
    # FROZEN: the same token + corrections on every subsequent consult
    again = adv.consult(KEY)
    assert again["token"] == dec["token"]
    assert again["corrections"] == dec["corrections"]
    assert adv.info()["replans_total"] == 1  # no double count
    assert "replan" in describe_decision(dec)
    assert "rows Join#0.0 -> 1600" in describe_decision(dec)


def test_blind_node_never_corrects():
    # CBO-blind (est None) nodes must never fabricate a correction, however
    # large their actuals (satellite 2: "wrong" vs "blind")
    adv = _advisor(_store({"Join#0.0": _rec(est=None, actual=10 ** 6)}))
    _base(adv)
    assert adv.consult(KEY) is None


def test_over_estimate_corrects_only_join_builds():
    # "over" on a non-build node: not actionable (the r15 canonical
    # correlated-filter over-estimate must not trigger wasteful re-plans)
    adv = _advisor(_store({"Filter#0.0": _rec(op="Filter", est=5000.0,
                                              actual=10)}))
    _base(adv)
    assert adv.consult(KEY) is None
    # the same over-estimate on a join BUILD side: a partitioned build that
    # measured tiny should flip back to broadcast
    adv2 = _advisor(_store({"Project#0.1": _rec(op="Project", est=5000.0,
                                                actual=10, build=True)}))
    _base(adv2)
    dec = adv2.consult(KEY)
    assert dec is not None and dec["verdict"] == "replan"
    assert dec["corrections"]["rows"]["Project#0.1"] == pytest.approx(10.0)


def test_hold_when_price_exceeds_win():
    adv = _advisor(_store({"Join#0.0": _rec()}), price_scale=1e9)
    _base(adv)
    dec = adv.consult(KEY)
    assert dec is not None and dec["verdict"] == "hold"
    assert dec["token"] is None
    assert any("compile price" in r for r in dec["reasons"])
    assert adv.info()["holds_total"] == 1 and adv.info()["replans_total"] == 0
    assert describe_decision(dec).startswith("hold")


def test_hold_when_price_unknown():
    adv = _advisor(_store({"Join#0.0": _rec()}))
    # base observation WITHOUT a compile observation, and no compile log:
    # unknown price = assume expensive
    adv.observe(KEY, "fp-base", corrected=False, wall_s=0.2)
    dec = adv.consult(KEY)
    assert dec is not None and dec["verdict"] == "hold"
    assert dec["compile_price_s"] is None
    assert any("unknown" in r for r in dec["reasons"])


def test_peek_consult_transitions_nothing():
    adv = _advisor(_store({"Join#0.0": _rec()}))
    _base(adv)
    dec = adv.consult(KEY, peek=True)
    assert dec is not None and dec["verdict"] == "hold"
    assert any("peek" in r for r in dec["reasons"])
    assert adv.info()["holds_total"] == 0 and adv.info()["replans_total"] == 0
    # the statement is still free to replan on the real consult
    assert adv.consult(KEY)["verdict"] == "replan"


def test_aggregate_capacity_and_grace_corrections():
    adv = _advisor(_store({"Aggregate#0.0": _rec(
        op="Aggregate", est=100.0, actual=50000,
        spill={"host": 1 << 20})}))
    _base(adv)
    corr = adv.consult(KEY)["corrections"]
    # capacity = pow2(2 x observed groups); grace_parts only because the
    # node spilled
    assert corr["capacity"]["Aggregate#0.0"] == 131072
    assert corr["grace_parts"]["Aggregate#0.0"] == 4
    adv2 = _advisor(_store({"Aggregate#0.0": _rec(op="Aggregate", est=100.0,
                                                  actual=50000)}))
    _base(adv2)
    corr2 = adv2.consult(KEY)["corrections"]
    assert corr2["capacity"]["Aggregate#0.0"] == 131072
    assert "grace_parts" not in corr2  # no spill observed: no Grace seed


def test_dispatch_batch_rides_along():
    from trino_tpu.exec.boundary import DISPATCH_BATCH as cur

    adv = _advisor(_store({
        "Join#0.0": _rec(),
        "TableScan#0.0.0": _rec(op="TableScan", est=None, actual=0, wall=0.0,
                                splits=64)}))
    _base(adv)
    corr = adv.consult(KEY)["corrections"]
    assert corr["dispatch_batch"] == min(16, max(cur, 16))
    assert corr["dispatch_batch"] > cur


def test_probation_confirms_on_warm_no_worse():
    adv = _advisor(_store({"Join#0.0": _rec()}))
    _base(adv)
    assert adv.consult(KEY)["verdict"] == "replan"
    # cold corrected run (compiles > 0): compile-dominated wall, no verdict
    adv.observe(KEY, "fp-corr", corrected=True, wall_s=5.0, compiles=3,
                compile_s=1.0)
    assert adv.decision_trace()[-1]["state"] == "probation"
    # first WARM corrected run, no worse than the base EWMA: confirmed
    adv.observe(KEY, "fp-corr", corrected=True, wall_s=0.15)
    assert adv.decision_trace()[-1]["state"] == "confirmed"
    assert adv.info()["confirms_total"] == 1
    assert adv.consult(KEY)["verdict"] == "replan"  # still frozen


def test_regression_demotes_then_cooldown_reconsiders():
    adv = _advisor(_store({"Join#0.0": _rec()}))
    _base(adv)
    tok = adv.consult(KEY)["token"]
    # warm corrected run REGRESSES past base x 1.5 + floor: demote
    adv.observe(KEY, "fp-corr", corrected=True, wall_s=2.0)
    assert adv.info()["demotions_total"] == 1
    dec = adv.consult(KEY)
    assert dec["verdict"] == "hold" and dec["token"] is None
    assert any("cooling down" in r for r in dec["reasons"])
    # cooldown counts UNCORRECTED executions (cooldown=2 here)
    _base(adv)
    assert adv.consult(KEY)["verdict"] == "hold"
    _base(adv)
    dec2 = adv.consult(KEY)  # cooled down: watching again, re-decides fresh
    assert dec2 is not None and dec2["verdict"] == "replan"
    assert dec2["token"] == tok  # same frozen facts -> same stable token


def test_failed_demotes_immediately():
    adv = _advisor(_store({"Join#0.0": _rec()}))
    _base(adv)
    assert adv.consult(KEY)["verdict"] == "replan"
    adv.failed(KEY)
    assert adv.info()["demotions_total"] == 1
    assert adv.consult(KEY)["verdict"] == "hold"
    adv.failed(KEY)  # idempotent on a non-corrected state
    assert adv.info()["demotions_total"] == 1


def test_decision_trace_shape():
    adv = _advisor(_store({"Join#0.0": _rec()}))
    _base(adv)
    adv.consult(KEY)
    t = adv.decision_trace()
    assert len(t) == 1
    row = t[0]
    assert row["state"] == "probation" and row["last_verdict"] == "replan"
    assert row["sql"] == "select 1" and row["base_executions"] == 1
    assert row["corrections"]["rows"] and row["reasons"]


# ---------------------------------------------------------------- engine layer
# the build side's two expression predicates are always TRUE but
# un-estimatable (COMPARISON_COEFFICIENT each): the CBO estimates
# 1500 x 0.0625 ~ 94 build rows, the executor measures 1500 — a 16x
# UNDER-estimate on a join build, the advisor's canonical trigger
JOIN_Q = ("select count(*) from orders join customer "
          "on o_custkey = c_custkey "
          "where c_custkey * 2 >= c_custkey and c_nationkey + c_custkey >= 0")


def _engine():
    e = Engine()
    e.register_catalog("tpch", TpchConnector(sf=0.01, split_rows=1 << 11))
    return e


@pytest.fixture()
def small_thresholds(monkeypatch):
    """Pull the AddExchanges thresholds under the sf0.01 build size (1500
    rows) so the corrected CONFIDENT estimate crosses them: broadcast is
    blocked by the absolute cap, partitioned engages."""
    from trino_tpu.sql import exchanges as X

    monkeypatch.setattr(X, "BROADCAST_ABS_CAP", 256)
    monkeypatch.setattr(X, "PARTITIONED_JOIN_THRESHOLD", 1024)


def test_misestimated_join_replans_and_improves(small_thresholds):
    e = _engine()
    e.adaptive_advisor.price_scale = 0.0  # test hook: any material win takes
    s = e.create_session("tpch")

    # control: the same statement with adaptive OFF (session property), warm
    ctl = e.create_session("tpch")
    e.execute_sql("set session adaptive_execution = false", ctl)
    expected = e.execute_sql(JOIN_Q, ctl).rows()
    e.execute_sql(JOIN_Q, ctl)
    warm_off = e.last_query_counters.snapshot()
    assert warm_off.adaptive_replans == 0 and warm_off.adaptive_holds == 0

    # before any history: plain EXPLAIN shows the uncorrected placement
    before = "\n".join(r[0] for r in e.execute_sql(
        f"explain {JOIN_Q}", s).rows())
    assert "partitioned" not in before, before
    assert "Adaptive:" not in before

    # execution 1 records the build-side under-estimate; execution 2 diverts
    # to the corrected plan — byte-identical, counted, partitioned
    r1 = e.execute_sql(JOIN_Q, s)
    assert r1.rows() == expected
    c1 = e.last_query_counters.snapshot()
    assert c1.adaptive_replans == 0
    r2 = e.execute_sql(JOIN_Q, s)
    assert r2.rows() == expected
    c2 = e.last_query_counters.snapshot()
    assert c2.adaptive_replans == 1, e.adaptive_advisor.decision_trace()
    assert e.adaptive_advisor.info()["replans_total"] == 1

    # the frozen decision's facts flipped the build distribution: observed
    # 1500 rows is CONFIDENT and past the (shrunk) partitioned threshold
    dec = e.adaptive_advisor.decision_trace()[-1]
    assert dec["state"] in ("probation", "confirmed")
    assert any(v >= 1000 for v in dec["corrections"]["rows"].values()), dec
    after = "\n".join(r[0] for r in e.execute_sql(
        f"explain {JOIN_Q}", s).rows())
    assert "partitioned" in after, after
    assert "Adaptive: replan" in after

    # warm corrected execution: no worse than the uncorrected warm run at
    # the device boundary (the advisor may only SPEND a recompile, never a
    # standing dispatch tax), and the correction confirms
    r3 = e.execute_sql(JOIN_Q, s)
    assert r3.rows() == expected
    c3 = e.last_query_counters.snapshot()
    assert c3.device_dispatches <= warm_off.device_dispatches, \
        (c3.device_dispatches, warm_off.device_dispatches)
    assert c3.host_bytes_pulled <= warm_off.host_bytes_pulled
    assert e.adaptive_advisor.decision_trace()[-1]["state"] == "confirmed"

    # EXPLAIN ANALYZE renders the win-vs-price arithmetic
    text = "\n".join(r[0] for r in e.execute_sql(
        f"explain analyze {JOIN_Q}", s).rows())
    assert "Adaptive: replan" in text, text
    assert "predicted win" in text

    # the decision rides the flight record
    recs = [r for r in e.flight_recorder.snapshot(kind="query")
            if r.get("adaptive")]
    assert recs, "no flight record carried the adaptive decision"
    assert recs[-1]["adaptive"]["verdict"] == "replan"


def test_hold_keeps_plan_and_counters_stable(small_thresholds):
    e = _engine()
    e.adaptive_advisor.price_scale = 1e9  # test hook: price always wins
    s = e.create_session("tpch")
    r1 = e.execute_sql(JOIN_Q, s)
    r2 = e.execute_sql(JOIN_Q, s)
    assert r2.rows() == r1.rows()
    c2 = e.last_query_counters.snapshot()
    assert c2.adaptive_holds == 1 and c2.adaptive_replans == 0
    assert e.adaptive_advisor.info()["replans_total"] == 0
    # consult is free at the device boundary: the held statement's warm
    # counters do not move run-over-run (the budget-suite invariant)
    e.execute_sql(JOIN_Q, s)
    c3 = e.last_query_counters.snapshot()
    assert c3.device_dispatches == c2.device_dispatches
    assert c3.host_transfers == c2.host_transfers
    assert c3.host_bytes_pulled == c2.host_bytes_pulled
    assert c3.adaptive_holds == 1
    # the hold (win-vs-price) is visible without changing the plan
    text = "\n".join(r[0] for r in e.execute_sql(
        f"explain analyze {JOIN_Q}", s).rows())
    assert "Adaptive: hold" in text, text
    assert "partitioned" not in text


def test_adaptive_off_never_consults(small_thresholds):
    e = _engine()
    e.adaptive_advisor.price_scale = 0.0
    s = e.create_session("tpch")
    e.execute_sql("set session adaptive_execution = false", s)
    for _ in range(3):
        e.execute_sql(JOIN_Q, s)
    c = e.last_query_counters.snapshot()
    assert c.adaptive_replans == 0 and c.adaptive_holds == 0
    assert e.adaptive_advisor.info()["replans_total"] == 0
    assert e.adaptive_advisor.decision_trace() == []


# ------------------------------------------------------------------ satellite 1
def test_session_property_is_plan_shaping():
    from trino_tpu.engine import _effective_adaptive, _plan_shape_props

    e = _engine()
    s = e.create_session("tpch")
    on = _plan_shape_props(s)
    assert on[-1] is True and _effective_adaptive(s)
    e.execute_sql("set session adaptive_execution = false", s)
    off = _plan_shape_props(s)
    assert off[-1] is False and off != on
    e.execute_sql("reset session adaptive_execution", s)
    assert _plan_shape_props(s) == on


def test_the_session_sets_adaptive_in_both_directions():
    from trino_tpu.engine import _effective_adaptive, _plan_shape_props

    e = _engine()
    s = e.create_session("tpch")
    default = _plan_shape_props(s)
    e.execute_sql("set session adaptive_execution = false", s)
    assert not _effective_adaptive(s)
    assert _plan_shape_props(s)[-1] is False
    # a session that NAMES the default is keyed as the default is
    e.execute_sql("set session adaptive_execution = true", s)
    assert _effective_adaptive(s)
    assert _plan_shape_props(s) == default and default[-1] is True
