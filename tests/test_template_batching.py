"""Continuous template batching (round 21): N concurrent same-template
requests fused into ONE device dispatch, per-request demux.

Covers the acceptance surface:

- batched-vs-serial BYTE IDENTITY: a deterministically fused window of
  concurrent protocol-parameterized EXECUTEs (distinct bindings, one NULL
  binding, one BindError fallback sharing the window) returns exactly what
  serial execution returns;
- per-request isolation: a batch member that errors (per-lane decode fault
  via the BATCH_LANE_TEST_HOOK seam) fails ONLY its own request — the rest
  of the window gets correct results;
- unbatchable plans (Sort/Limit are outside the fused subset) demote the
  template to serial lanes (``batchable=False``) and every member still
  answers correctly;
- the dispatch amortization claim: a fused window of N bills within 2x of
  ONE request's warm serial dispatch count, not N times it;
- split-union pruning: a fused window whose bindings prune to DIFFERENT
  splits scans the union and stays byte-identical per lane;
- accounting: ``batched_requests`` counts every member (driver + riders,
  totals == sum of per-request snapshots), flight records carry
  ``batched_with``, EXPLAIN ANALYZE prints the "Batched:" line only when
  nonzero, /v1/metrics exports the batch counters + size histogram;
- the TemplateBatcher protocol itself (no engine): leader-runs-serial,
  window fusion via LEADER_EXIT_HOOK, whole-batch failure -> all-serial
  fallback, singleton window -> serial, arity-mismatch -> serial,
  TRINO_TPU_TEMPLATE_BATCH=0 -> pass-through.

Fusion in engine tests is MANUFACTURED, never raced: the template's lane is
marked busy, the window's members enqueue, and a manual handoff promotes
the first to driver — the exact state the wall-clock gather window
produces, minus the timing dependence (same technique as
scripts/query_counters.py --serve-batch).
"""

import threading
import time

import pytest

from trino_tpu import Engine
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.execution import batcher as BA
from trino_tpu.execution.chaos_matrix import result_signature as _sig

SF, SPLIT_ROWS = 0.01, 1 << 14

POINT = ("select c_name, c_acctbal, c_mktsegment from customer "
         "where c_custkey = ?")


@pytest.fixture(scope="module")
def tpch_conn():
    return TpchConnector(sf=SF, split_rows=SPLIT_ROWS)


@pytest.fixture()
def eng(tpch_conn, monkeypatch):
    """Template+batcher engine; result/page tiers off (the fused win must be
    measured on the execute path, and a result-cache hit would answer a
    member before it ever reaches the lane)."""
    monkeypatch.setenv("TRINO_TPU_RESULT_CACHE", "0")
    monkeypatch.setenv("TRINO_TPU_PAGE_CACHE", "0")
    e = Engine()
    e.register_catalog("tpch", tpch_conn)
    assert e.template_batcher.enabled
    return e


@pytest.fixture()
def baseline(tpch_conn, monkeypatch):
    """Serial oracle: templates on, batcher off — same plans, same binds,
    never fused."""
    monkeypatch.setenv("TRINO_TPU_RESULT_CACHE", "0")
    monkeypatch.setenv("TRINO_TPU_PAGE_CACHE", "0")
    e = Engine()
    e.template_batcher.enabled = False
    e.register_catalog("tpch", tpch_conn)
    return e


def _warm(eng, text, bindings=((42,), (97,))):
    """Create + CONFIRM the template (the batcher only fuses confirmed
    templates) and compile the serial path."""
    s = eng.create_session("tpch")
    for ps in bindings:
        eng.execute_sql(text, s, parameters=list(ps))


def _fused(eng, text, params_list, expect_members=None, timeout=60):
    """Run the requests concurrently as ONE deterministically fused window.
    Returns results (or the exception each request raised) in input order.
    ``expect_members`` caps the enqueue wait when some requests are known
    to bypass the batcher (BindError fallbacks)."""
    bt = eng.template_batcher
    key = eng._template_key(text, eng.create_session("tpch"))
    with bt._lock:
        lane = bt._lanes.setdefault(key, BA._Lane())
        lane.busy = True
    n = len(params_list) if expect_members is None else expect_members
    out = [None] * len(params_list)

    def fire(i, ps):
        s = eng.create_session("tpch")
        try:
            out[i] = eng.execute_sql(text, s, parameters=list(ps))
        except Exception as e:
            out[i] = e

    threads = [threading.Thread(target=fire, args=(i, ps))
               for i, ps in enumerate(params_list)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        with bt._lock:
            if len(lane.queue) >= n:
                break
        time.sleep(0.001)
    bt._handoff(lane)
    for t in threads:
        t.join(timeout)
    assert not any(t.is_alive() for t in threads), "fused window hung"
    return out


def _serial_results(eng, text, params_list):
    s = eng.create_session("tpch")
    return [eng.execute_sql(text, s, parameters=list(ps))
            for ps in params_list]


# ------------------------------------------------------- byte identity
def test_fused_window_byte_identity(eng, baseline):
    """The headline contract: distinct bindings + one NULL binding fused
    into one window == serial, and every member is counted + flight-marked."""
    _warm(eng, POINT)
    _warm(baseline, POINT)
    params = [(42,), (97,), (None,), (7,)]
    before = eng.counters_total.as_dict()
    out = _fused(eng, POINT, params)
    ref = _serial_results(baseline, POINT, params)
    for i, (a, b) in enumerate(zip(out, ref)):
        assert not isinstance(a, Exception), f"member {i} raised: {a!r}"
        assert _sig(a) == _sig(b), f"member {i} diverged from serial"
    after = eng.counters_total.as_dict()
    # every member of the fused window counts once — driver and riders
    assert after["batched_requests"] - before.get("batched_requests", 0) \
        == len(params)
    bi = eng.template_batcher.info()
    assert bi["batches_total"] >= 1
    assert bi["sizes"].get(len(params), 0) >= 1
    # flight records: each member's record carries the window size
    recs = [r for r in eng.flight_recorder.snapshot(kind="query")
            if r.get("batched_with") == len(params)]
    assert len(recs) >= len(params)


def test_binderror_fallback_shares_the_window(eng, baseline):
    """A BindError binding (fractional literal in the integer slot) never
    enters the batcher — it substitutes per execution — while the rest of
    the window fuses.  Everyone answers correctly."""
    _warm(eng, POINT)
    _warm(baseline, POINT)
    params = [(42,), (1.5,), (97,), (None,)]  # 1.5 -> BindError -> fallback
    before = eng.counters_total.as_dict()
    out = _fused(eng, POINT, params, expect_members=len(params) - 1)
    ref = _serial_results(baseline, POINT, params)
    for i, (a, b) in enumerate(zip(out, ref)):
        assert not isinstance(a, Exception), f"member {i} raised: {a!r}"
        assert _sig(a) == _sig(b), f"member {i} diverged from serial"
    after = eng.counters_total.as_dict()
    # only the three bindable members batched; the fallback ran substitution
    assert after["batched_requests"] - before.get("batched_requests", 0) \
        == len(params) - 1


def test_fused_window_unions_pruned_splits(monkeypatch):
    """Bindings that prune to DIFFERENT splits: the fused scan takes the
    union of the per-member pruned split lists and each lane still matches
    serial (the predicate masks the other members' rows per lane)."""
    monkeypatch.setenv("TRINO_TPU_RESULT_CACHE", "0")
    monkeypatch.setenv("TRINO_TPU_PAGE_CACHE", "0")
    conn = TpchConnector(sf=SF, split_rows=256)  # 1500 rows -> 6 splits
    e = Engine()
    e.register_catalog("tpch", conn)
    b = Engine()
    b.template_batcher.enabled = False
    b.register_catalog("tpch", conn)
    _warm(e, POINT)
    _warm(b, POINT)
    params = [(5,), (700,), (1400,), (901,)]  # distinct splits
    out = _fused(e, POINT, params)
    ref = _serial_results(b, POINT, params)
    for i, (a, r) in enumerate(zip(out, ref)):
        assert not isinstance(a, Exception), f"member {i} raised: {a!r}"
        assert _sig(a) == _sig(r), f"member {i} diverged across splits"


# ------------------------------------------------------- error isolation
def test_member_error_fails_only_its_own_request(eng, monkeypatch):
    """A per-lane demux fault (injected at the BATCH_LANE_TEST_HOOK seam)
    surfaces on exactly that member; the other members of the same fused
    window still get correct results."""
    from trino_tpu.exec import local_executor as LE

    _warm(eng, POINT)
    ref = _serial_results(eng, POINT, [(42,), (97,), (7,)])

    def hook(lane, nlanes):
        if lane == 1:
            raise RuntimeError("injected lane fault")

    monkeypatch.setattr(LE, "BATCH_LANE_TEST_HOOK", hook)
    out = _fused(eng, POINT, [(42,), (97,), (7,)])
    monkeypatch.setattr(LE, "BATCH_LANE_TEST_HOOK", None)
    assert isinstance(out[1], Exception) \
        and "injected lane fault" in str(out[1])
    assert _sig(out[0]) == _sig(ref[0])
    assert _sig(out[2]) == _sig(ref[2])


def test_unbatchable_template_demotes_to_serial(eng, baseline):
    """Sort/Limit plans are templatable but outside the FUSED subset: the
    first fused attempt raises BatchUnsupported, the template demotes
    (batchable=False), every member of that window re-runs serially with
    correct results, and later windows skip the fused path entirely."""
    text = ("select c_name from customer where c_custkey < ? "
            "order by c_name limit 5")
    bindings = ((100,), (500,))
    s1, s2 = eng.create_session("tpch"), baseline.create_session("tpch")
    for ps in bindings:
        eng.execute_sql(text, s1, parameters=[ps[0]])
        baseline.execute_sql(text, s2, parameters=[ps[0]])
    tpl = next(v[0] for v in eng._template_cache.values()
               if getattr(v[0], "text", None) is not None
               and "order by" in v[0].text)
    assert tpl.batchable
    params = [(100,), (500,), (900,)]
    before = eng.counters_total.as_dict()
    out = _fused(eng, text, params)
    ref = _serial_results(baseline, text, params)
    for i, (a, b) in enumerate(zip(out, ref)):
        assert not isinstance(a, Exception), f"member {i} raised: {a!r}"
        assert _sig(a) == _sig(b), f"member {i} diverged after fallback"
    assert not tpl.batchable
    after = eng.counters_total.as_dict()
    # nothing fused: the serial fallback never stamps batched_requests
    assert after.get("batched_requests", 0) \
        == before.get("batched_requests", 0)
    # a later window goes straight to serial lanes (no BatchUnsupported
    # round-trip) and stays correct
    out2 = _fused(eng, text, [(250,)], expect_members=1)
    assert _sig(out2[0]) == _sig(
        _serial_results(baseline, text, [(250,)])[0])


# ------------------------------------------------------- amortization
def test_fused_dispatches_within_2x_of_one_request(eng):
    """The acceptance ratio: a warm fused window of 4 bills within 2x of
    ONE warm serial request's dispatches — not 4x."""
    _warm(eng, POINT)
    s = eng.create_session("tpch")
    before = eng.counters_total.as_dict()
    eng.execute_sql(POINT, s, parameters=[11])
    mid = eng.counters_total.as_dict()
    serial_d = mid["device_dispatches"] - before["device_dispatches"]
    assert serial_d > 0
    params = [(21,), (31,), (41,), (51,)]
    _fused(eng, POINT, params)          # compiles the rung's bindings jit
    mid2 = eng.counters_total.as_dict()
    out = _fused(eng, POINT, [(22,), (32,), (42,), (52,)])  # warm window
    assert not any(isinstance(r, Exception) for r in out)
    after = eng.counters_total.as_dict()
    fused_d = after["device_dispatches"] - mid2["device_dispatches"]
    assert 0 < fused_d <= 2 * serial_d, \
        f"fused window of 4 cost {fused_d} dispatches vs serial {serial_d}"


# ------------------------------------------------------- observability
def test_explain_analyze_batched_line(eng):
    """format_plan prints "Batched:" only when the counter is nonzero —
    zero-batch statements (the whole budget suite) print byte-unchanged."""
    from trino_tpu.execution.tracing import QueryCounters
    from trino_tpu.sql.planprinter import format_plan

    s = eng.create_session("tpch")
    eng.execute_sql("select c_custkey from customer "
                    "where c_custkey = 42", s)
    res = eng.execute_sql("explain analyze select c_custkey from customer "
                          "where c_custkey = 42", s)
    text = "\n".join(str(row[0]) for row in res.rows())
    assert "Batched:" not in text
    c = QueryCounters()
    c.batched_requests = 5
    # the point lookup auto-parameterized into the template cache
    plan = next(v[0].plan for v in eng._template_cache.values()
                if getattr(v[0], "plan", None) is not None)
    out = format_plan(plan, counters=c)
    assert "Batched: 5 requests" in out
    c.batched_requests = 0
    assert "Batched:" not in format_plan(plan, counters=c)


def test_metrics_export_batch_series(eng):
    from trino_tpu.server.server import CoordinatorServer

    _warm(eng, POINT)
    out = _fused(eng, POINT, [(42,), (97,), (7,)])
    assert not any(isinstance(r, Exception) for r in out)
    body = CoordinatorServer(eng)._metrics_text()
    assert "trino_tpu_template_batches_total 1" in body
    assert "trino_tpu_batched_requests_total 3" in body
    assert 'trino_tpu_template_batch_size_bucket{le="4"} 1' in body
    assert "trino_tpu_template_batch_size_sum 3" in body


# ------------------------------------------------------- batcher protocol
def _mk(window_ms=0.0, max_batch=16, enabled=True):
    return BA.TemplateBatcher(window_ms=window_ms, max_batch=max_batch,
                              enabled=enabled)


def test_batcher_disabled_is_passthrough():
    bt = _mk(enabled=False)
    res, n = bt.execute("k", (1,), lambda rt: ("serial", rt), None)
    assert res == ("serial", (1,)) and n == 0
    assert bt.info()["batches_total"] == 0


def test_batcher_leader_runs_serial_immediately():
    bt = _mk()
    calls = []
    res, n = bt.execute("k", (1,), lambda rt: calls.append(rt) or "ok",
                        lambda rts: pytest.fail("fused on an idle lane"))
    assert res == "ok" and n == 0 and calls == [(1,)]
    assert not bt._lanes["k"].busy  # lane released


def _fuse_via_hook(bt, runtimes, serial_fn, batch_fn, monkeypatch):
    """Real leader->handoff->driver choreography: the leader parks in
    LEADER_EXIT_HOOK until every member is enqueued."""
    ready = threading.Event()
    monkeypatch.setattr(BA, "LEADER_EXIT_HOOK",
                        lambda key: ready.wait(timeout=30))
    out = {}

    def run(name, rt):
        try:
            out[name] = bt.execute("k", rt, serial_fn, batch_fn)
        except Exception as e:
            out[name] = e

    lead = threading.Thread(target=run, args=("leader", ("L",)))
    lead.start()
    t0 = time.monotonic()
    while "k" not in bt._lanes and time.monotonic() - t0 < 10:
        time.sleep(0.001)
    members = [threading.Thread(target=run, args=(f"m{i}", rt))
               for i, rt in enumerate(runtimes)]
    for t in members:
        t.start()
    while time.monotonic() - t0 < 10:
        with bt._lock:
            if len(bt._lanes["k"].queue) >= len(runtimes):
                break
        time.sleep(0.001)
    ready.set()
    for t in [lead] + members:
        t.join(30)
    monkeypatch.setattr(BA, "LEADER_EXIT_HOOK", None)
    return out


def test_batcher_window_fuses_members(monkeypatch):
    bt = _mk(window_ms=5.0)
    fused = []

    def batch_fn(rts):
        fused.append(list(rts))
        return [("batched", rt) for rt in rts]

    out = _fuse_via_hook(bt, [("a",), ("b",), ("c",)],
                         lambda rt: ("serial", rt), batch_fn, monkeypatch)
    assert out["leader"] == (("serial", ("L",)), 0)
    assert len(fused) == 1 and sorted(fused[0]) == [("a",), ("b",), ("c",)]
    for name, rt in (("m0", ("a",)), ("m1", ("b",)), ("m2", ("c",))):
        assert out[name] == (("batched", rt), 3)
    info = bt.info()
    assert info["batches_total"] == 1
    assert info["batched_requests_total"] == 3
    assert info["sizes"] == {3: 1}
    assert not bt._lanes["k"].busy


def test_batcher_whole_batch_failure_falls_back_serial(monkeypatch):
    bt = _mk(window_ms=5.0)

    def batch_fn(rts):
        raise RuntimeError("device fault")

    out = _fuse_via_hook(bt, [("a",), ("b",)],
                         lambda rt: ("serial", rt), batch_fn, monkeypatch)
    for name, rt in (("m0", ("a",)), ("m1", ("b",))):
        assert out[name] == (("serial", rt), 0)
    assert bt.info()["batches_total"] == 0
    assert not bt._lanes["k"].busy


def test_batcher_arity_mismatch_falls_back_serial(monkeypatch):
    bt = _mk(window_ms=5.0)
    out = _fuse_via_hook(bt, [("a",), ("b",)], lambda rt: ("serial", rt),
                         lambda rts: [("only-one", rts[0])], monkeypatch)
    for name, rt in (("m0", ("a",)), ("m1", ("b",))):
        assert out[name] == (("serial", rt), 0)


def test_batcher_member_error_is_its_own(monkeypatch):
    bt = _mk(window_ms=5.0)

    def batch_fn(rts):
        return [ValueError("lane poisoned") if rt == ("b",)
                else ("batched", rt) for rt in rts]

    out = _fuse_via_hook(bt, [("a",), ("b",), ("c",)],
                         lambda rt: ("serial", rt), batch_fn, monkeypatch)
    bad = [v for v in out.values() if isinstance(v, ValueError)]
    assert len(bad) == 1 and "lane poisoned" in str(bad[0])
    good = [v for v in out.values()
            if isinstance(v, tuple) and v[1] == 3]
    assert len(good) == 2


def test_batcher_singleton_window_runs_serial():
    """A driver that gathers nobody runs the serial path — no rung-1 fused
    overhead, batch_fn never called."""
    bt = _mk(window_ms=1.0)
    lane = BA._Lane()
    bt._lanes["k"] = lane
    lane.busy = True
    out = {}

    def member():
        out["m"] = bt.execute("k", ("solo",), lambda rt: ("serial", rt),
                              lambda rts: pytest.fail("fused a singleton"))

    t = threading.Thread(target=member)
    t.start()
    t0 = time.monotonic()
    while not lane.queue and time.monotonic() - t0 < 10:
        time.sleep(0.001)
    bt._handoff(lane)
    t.join(30)
    assert out["m"] == (("serial", ("solo",)), 0)
    assert not lane.busy


def test_batcher_disabled_runs_every_request_serially():
    off = BA.TemplateBatcher(enabled=False)
    assert not off.enabled and BA.TemplateBatcher(enabled=True).enabled
    assert BA.TemplateBatcher().enabled  # on is what runs
    got = off.execute("k", (1,), lambda rt: ("serial", rt), None)
    assert got == (("serial", (1,)), 0) and off.info()["batches_total"] == 0
