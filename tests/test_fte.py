"""Fault-tolerant execution: page serde, spooled exchange, task retries,
failure injection, dedup.

Reference test models: BaseFailureRecoveryTest (testing/trino-testing/.../
BaseFailureRecoveryTest.java:84) — inject TASK_FAILURE /
TASK_GET_RESULTS_FAILURE via the production FailureInjector hook and assert
queries still succeed; serde tests mirror TestPagesSerde.
"""

import numpy as np
import pytest

from trino_tpu import Engine
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.exec.fte import (FailureInjector, FaultTolerantExecutor,
                                InjectedFailure, SpoolingExchange,
                                deserialize_page, serialize_page)
from trino_tpu.sql.frontend import compile_sql

Q1 = """select l_returnflag, l_linestatus, sum(l_quantity) qty, count(*) c,
               avg(l_discount) d
        from lineitem where l_shipdate <= date '1998-09-02'
        group by l_returnflag, l_linestatus order by l_returnflag, l_linestatus"""


def test_page_serde_roundtrip():
    cols = [np.arange(10, dtype=np.int64), np.linspace(0, 1, 10)]
    nulls = [None, np.arange(10) % 3 == 0]
    data = serialize_page(cols, nulls)
    rc, rn = deserialize_page(data)
    np.testing.assert_array_equal(rc[0], cols[0])
    np.testing.assert_array_equal(rc[1], cols[1])
    assert rn[0] is None
    np.testing.assert_array_equal(rn[1], nulls[1])
    # corruption is detected
    bad = data[:20] + bytes([data[20] ^ 0xFF]) + data[21:]
    with pytest.raises(ValueError):
        deserialize_page(bad)


def test_page_serde_codecs(monkeypatch):
    """NONE/ZLIB/ZSTD codecs round-trip (reference: CompressionCodec.java:23)."""
    import trino_tpu.exec.fte as F

    cols = [np.arange(1000, dtype=np.int64), np.linspace(0, 1, 1000)]
    nulls = [None, np.arange(1000) % 3 == 0]
    codecs = ["none", "zlib"]
    try:  # stdlib-only container: zstd binding is optional
        import zstandard  # noqa: F401

        codecs.append("zstd")
    except ImportError:
        pass
    for codec in codecs:
        monkeypatch.setattr(F, "PAGE_CODEC", codec)
        rc, rn = deserialize_page(serialize_page(cols, nulls))
        np.testing.assert_array_equal(rc[0], cols[0])
        np.testing.assert_array_equal(rn[1], nulls[1])


def test_page_serde_encryption(monkeypatch):
    """AES-GCM exchange encryption: round-trips with the key, refuses without
    it, and authenticated tampering fails (reference:
    CompressingEncryptingPageSerializer.java:58)."""
    pytest.importorskip("cryptography")  # optional dep (stdlib-only container)
    cols = [np.arange(100, dtype=np.int64)]
    nulls = [None]
    monkeypatch.setenv("TRINO_TPU_EXCHANGE_KEY", "00" * 16)
    data = serialize_page(cols, nulls)
    assert data[4] & 0x80  # encrypted flag
    rc, _ = deserialize_page(data)
    np.testing.assert_array_equal(rc[0], cols[0])
    # tamper INSIDE the ciphertext and fix up the CRC: GCM must still refuse
    import zlib as _z

    body = bytearray(data)
    body[30] ^= 0xFF
    crc = _z.crc32(bytes(body[17:]))
    body[5:9] = crc.to_bytes(4, "little")
    with pytest.raises(Exception):
        deserialize_page(bytes(body))
    monkeypatch.delenv("TRINO_TPU_EXCHANGE_KEY")
    with pytest.raises(ValueError, match="encrypted"):
        deserialize_page(data)


def test_spool_first_commit_wins(tmp_path):
    ex = SpoolingExchange(str(tmp_path / "x"))
    assert ex.commit(0, 0, b"attempt0")
    assert not ex.commit(0, 1, b"attempt1")  # dedup: first commit wins
    assert ex.read(0) == b"attempt0"


def _setup(tmp_path, **kw):
    e = Engine()
    e.register_catalog("tpch", TpchConnector(sf=0.01, split_rows=1 << 11))
    s = e.create_session("tpch")
    plan = compile_sql(Q1, e, s)
    inj = FailureInjector()
    ex = FaultTolerantExecutor(e.catalogs, str(tmp_path / "spool"), injector=inj, **kw)
    expected = e.execute_sql(Q1, s).rows()
    return plan, inj, ex, expected


def test_fte_no_failures_matches_local(tmp_path):
    plan, inj, ex, expected = _setup(tmp_path)
    assert ex.execute(plan).rows() == expected


def test_fte_recovers_from_task_failures(tmp_path):
    plan, inj, ex, expected = _setup(tmp_path)
    inj.inject(0, "TASK_FAILURE", times=2)
    inj.inject(1, "TASK_GET_RESULTS_FAILURE", times=1)
    assert ex.execute(plan).rows() == expected
    assert ex.task_attempts[0] == 3  # two failed attempts + success
    assert ex.task_attempts[1] == 2


def test_fte_post_commit_failure_does_not_duplicate(tmp_path):
    plan, inj, ex, expected = _setup(tmp_path)
    inj.inject(2, "POST_COMMIT_FAILURE", times=1)
    assert ex.execute(plan).rows() == expected  # dedup: sums not doubled


def test_fte_exhausted_retries_fail_query(tmp_path):
    plan, inj, ex, _ = _setup(tmp_path, max_attempts=2)
    inj.inject(0, "TASK_FAILURE", times=5)
    with pytest.raises(RuntimeError, match="failed after 2 attempts"):
        ex.execute(plan)


def test_fte_join_query_via_engine(tmp_path):
    """Join above the scan-fed aggregate: FTE handles the aggregation stage and
    the remaining plan runs locally; engine entry point routes it."""
    e = Engine()
    e.register_catalog("tpch", TpchConnector(sf=0.01, split_rows=1 << 11))
    s = e.create_session("tpch")
    q = """select o_orderpriority, count(*) from orders
           group by o_orderpriority order by 1"""
    expected = e.execute_sql(q, s).rows()
    got = e.execute_sql(q, s, fault_tolerant=True).rows()
    assert got == expected


# ------------------------------------------------------------------- fragments
# round-2 generalization: the retryable unit is any blocking plan fragment
# (joins, windows, sorts included), not just scan-fed aggregations
# (reference: EventDrivenFaultTolerantQueryScheduler schedules arbitrary
# fragments whose inputs are replayable TaskDescriptors / spooled exchanges)

QJOIN = """select o_orderpriority, count(*) c
           from lineitem, orders
           where l_orderkey = o_orderkey and o_totalprice > 100000
           group by o_orderpriority order by o_orderpriority"""

QWINDOW = """select o_custkey, o_orderkey,
                    row_number() over (partition by o_custkey
                                       order by o_orderkey) rn
             from orders where o_custkey < 100
             order by o_custkey, o_orderkey limit 50"""


def _setup_q(tmp_path, sql, **kw):
    e = Engine()
    e.register_catalog("tpch", TpchConnector(sf=0.01, split_rows=1 << 11))
    s = e.create_session("tpch")
    plan = compile_sql(sql, e, s)
    inj = FailureInjector()
    ex = FaultTolerantExecutor(e.catalogs, str(tmp_path / "spool"),
                               injector=inj, **kw)
    expected = e.execute_sql(sql, s).rows()
    return plan, inj, ex, expected


def test_fte_mid_join_task_kill(tmp_path):
    """A join fragment task dies twice mid-execution and recovers — its inputs
    (scan splits) replay, its committed output dedups."""
    plan, inj, ex, expected = _setup_q(tmp_path, QJOIN)
    inj.inject("frag0", "TASK_FAILURE", times=2)  # frag0 = the join fragment
    assert ex.execute(plan).rows() == expected
    assert ex.task_attempts["frag0"] == 3


def test_fte_join_post_commit_failure_no_duplicates(tmp_path):
    plan, inj, ex, expected = _setup_q(tmp_path, QJOIN)
    inj.inject("frag0", "POST_COMMIT_FAILURE", times=1)
    inj.inject("frag1", "TASK_GET_RESULTS_FAILURE", times=1)
    assert ex.execute(plan).rows() == expected


def test_fte_window_fragment_retries(tmp_path):
    plan, inj, ex, expected = _setup_q(tmp_path, QWINDOW)
    inj.inject("frag0", "TASK_FAILURE", times=1)  # the window fragment
    assert ex.execute(plan).rows() == expected
    assert ex.task_attempts["frag0"] == 2


def test_fte_join_exhausted_retries(tmp_path):
    plan, inj, ex, _ = _setup_q(tmp_path, QJOIN, max_attempts=2)
    inj.inject("frag0", "TASK_FAILURE", times=5)
    with pytest.raises(RuntimeError, match="failed after 2 attempts"):
        ex.execute(plan)


class _FlakyGenerate:
    """Connector shim whose generate raises a REAL exception for the first
    ``fail_times`` calls — the reference's flaky-connector recovery shape
    (BaseFailureRecoveryTest exercises real task failures, not only injected
    ones)."""

    def __init__(self, conn, exc_factory, fail_times):
        self._orig = conn.generate
        self._exc = exc_factory
        self.left = fail_times

    def __call__(self, *a, **k):
        if self.left > 0:
            self.left -= 1
            raise self._exc()
        return self._orig(*a, **k)


def test_fte_retries_real_connector_failures(tmp_path):
    """A connector raising a genuine OSError mid-scan recovers under FTE (the
    retry loop classifies it retryable) but fails the plain executor."""
    from trino_tpu.exec.local_executor import LocalExecutor

    plan, inj, ex, expected = _setup(tmp_path)
    conn = ex.catalogs["tpch"]
    conn.generate = _FlakyGenerate(conn, lambda: OSError("simulated io loss"), 2)
    try:
        assert ex.execute(plan).rows() == expected
    finally:
        del conn.generate
    # without fault tolerance the same flake kills the query
    conn.generate = _FlakyGenerate(conn, lambda: OSError("simulated io loss"), 2)
    plain = LocalExecutor(ex.catalogs)
    try:
        with pytest.raises(OSError):
            plain.execute(plan)
    finally:
        del conn.generate


def test_fte_deterministic_errors_do_not_retry(tmp_path):
    """SemanticError-class failures would fail identically every attempt:
    they surface immediately instead of burning the retry budget."""
    plan, inj, ex, _ = _setup(tmp_path)
    conn = ex.catalogs["tpch"]
    conn.generate = _FlakyGenerate(
        conn, lambda: NotImplementedError("unsupported encoding"), 99)
    try:
        with pytest.raises(NotImplementedError):
            ex.execute(plan)
    finally:
        del conn.generate
    assert max(ex.task_attempts.values()) == 1  # no retries burned


def test_fte_consumes_spooled_join_output(tmp_path):
    """The aggregate above a join fragment must read the join's SPOOLED page,
    not re-execute the join from its cached stream (the join would silently run
    twice): under FTE every scan split generates exactly as many pages as one
    local execution pulls."""
    from trino_tpu.exec.local_executor import LocalExecutor

    plan, inj, ex, expected = _setup_q(tmp_path, QJOIN)
    conn = ex.catalogs["tpch"]
    calls = []
    orig = conn.generate
    conn.generate = lambda *a, **k: (calls.append(1), orig(*a, **k))[1]
    try:
        assert ex.execute(plan).rows() == expected
        fte_calls = len(calls)
        calls.clear()
        LocalExecutor(ex.catalogs).execute(plan)
        local_calls = len(calls)
    finally:
        del conn.generate
    assert fte_calls == local_calls


def test_fte_engine_join_fault_tolerant(tmp_path):
    """Engine-level fault_tolerant execution of a join+window plan matches the
    plain path."""
    e = Engine()
    e.register_catalog("tpch", TpchConnector(sf=0.01, split_rows=1 << 11))
    s = e.create_session("tpch")
    q = QJOIN
    expected = e.execute_sql(q, s).rows()
    got = e.execute_sql(q, s, fault_tolerant=True).rows()
    assert got == expected


def test_adaptive_join_side_swap(tmp_path):
    """Adaptive replanning (reference: AdaptivePlanner.java:121): once both
    join children materialize, actual row counts replace estimates — a build
    side that materialized clearly larger than the probe swaps sides, with a
    projection restoring column order; results are identical."""
    from trino_tpu import Engine
    from trino_tpu.connectors.tpch import TpchConnector

    e = Engine()
    e.register_catalog("tpch", TpchConnector(sf=0.01))
    s = e.create_session("tpch")
    sql = """
        select a.k, a.ca, b.cb from
         (select s_suppkey k, count(*) ca from supplier
          where s_suppkey <= 3 group by s_suppkey) a
         join (select o_custkey k, count(*) cb from orders
               group by o_custkey) b
         on a.k = b.k
        order by a.k"""
    plain = e.execute_sql(sql, s).to_pandas()
    fte = e.execute_sql(sql, s, fault_tolerant=True).to_pandas()
    assert plain.values.tolist() == fte.values.tolist()
    # the 3-row build vs 1500-group probe inversion must have triggered a swap
    assert getattr(e._fte_executor, "adaptive_swaps", 0) >= 1
