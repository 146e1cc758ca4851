"""Dispatch coalescing (TRINO_TPU_DISPATCH_BATCH / SET SESSION dispatch_batch):
batched multi-split execution must be a pure dispatch-count optimization —
byte-identical results, identical page generation (once per split, never
regenerated on the device), and a visible `coalesced_splits` counter.  batch=1
is the exact-old-behavior escape hatch.

Scale here is tiny but split-RICH (sf=0.02, split_rows=1<<11 -> ~100 lineitem
splits): coalescing coverage comes from split count, not data volume.
"""

import numpy as np
import pytest

from trino_tpu import Engine
from trino_tpu.connectors.tpch import TpchConnector

from test_query_budgets import QUERIES  # the tier-1 north-star queries

SF = 0.02
SPLIT_ROWS = 1 << 11


@pytest.fixture(scope="module")
def ab_engine():
    """One engine, two sessions: dispatch_batch is plan-shaping, so each
    session keys (and compiles) its own plan — the A/B runs share nothing but
    the connector."""
    e = Engine()
    e.register_catalog("tpch", TpchConnector(sf=SF, split_rows=SPLIT_ROWS))
    s1 = e.create_session("tpch")
    e.session_properties.set_property(s1, "dispatch_batch", 1)
    s4 = e.create_session("tpch")
    e.session_properties.set_property(s4, "dispatch_batch", 4)
    yield e, s1, s4
    e._invalidate()


def _assert_results_identical(r1, r4, name):
    assert r1.names == r4.names
    assert r1.types == r4.types
    for decoded in (False, True):
        cols1 = r1.columns if decoded else r1.raw_columns
        cols4 = r4.columns if decoded else r4.raw_columns
        for cn, c1, c4 in zip(r1.names, cols1, cols4):
            a1, a4 = np.asarray(c1), np.asarray(c4)
            # byte-identical: same dtype (DATE/TIMESTAMP surfaces decode to
            # datetime64, dictionary columns decode to their values) and same
            # values in the same row order
            assert a1.dtype == a4.dtype, (name, cn, a1.dtype, a4.dtype)
            assert np.array_equal(a1, a4), (name, cn, decoded)


@pytest.mark.parametrize("name", sorted(QUERIES))
def test_batch1_vs_batch4_results_byte_identical(ab_engine, name):
    e, s1, s4 = ab_engine
    r1 = e.execute_sql(QUERIES[name], s1)
    r4 = e.execute_sql(QUERIES[name], s4)
    assert len(r1) == len(r4) and len(r1) > 0
    _assert_results_identical(r1, r4, name)


@pytest.mark.parametrize("name", sorted(QUERIES))
def test_warm_dispatch_reduction(ab_engine, name):
    """Batch=4 must dispatch strictly less than batch=1, with the
    coalesced-splits counter attributing the difference; batch=1 must not
    coalesce at all (the escape hatch is exact old behavior).  The counted
    execution of each mode is a replay: under xdist this test may be the
    first to run a statement on its worker's engine, and a COLD run's
    build-side pulls (the build keys that dynamic split pruning reads, sized
    by page buckets that follow the batch width) are not what coalescing is
    held to.  PR 28: pruning now reaches through a split join's boundary, so
    q9's upper joins make those pulls too."""
    e, s1, s4 = ab_engine
    counted = []
    for s in (s1, s4):
        e.execute_sql(QUERIES[name], s)
        e.execute_sql(QUERIES[name], s)
        counted.append(e.last_query_counters)
    c1, c4 = counted
    assert c1.coalesced_splits == 0, c1.as_dict()
    assert c4.coalesced_splits > 0, c4.as_dict()
    assert c4.device_dispatches < c1.device_dispatches, \
        (name, c1.as_dict(), c4.as_dict())
    # bytes must not regress: coalescing only batches dispatches (per-batch
    # live-count scalars can only get fewer)
    assert c4.host_bytes_pulled <= c1.host_bytes_pulled, \
        (name, c1.as_dict(), c4.as_dict())


def test_pages_generated_once_per_split():
    """Coalescing stacks pages the connector already produced — the page
    generation count per split must not change with the batch width (guards
    against on-device regeneration, and against a batcher that drops or
    duplicates splits)."""
    def run(batch):
        e = Engine()
        conn = TpchConnector(sf=0.01, split_rows=SPLIT_ROWS)
        calls = []
        orig = conn.generate
        conn.generate = lambda sp, cols=None: (calls.append(sp),
                                               orig(sp, cols))[1]
        e.register_catalog("tpch", conn)
        s = e.create_session("tpch")
        e.session_properties.set_property(s, "dispatch_batch", batch)
        r = e.execute_sql(QUERIES["q3"], s)
        e._invalidate()
        return calls, r

    calls1, r1 = run(1)
    calls4, r4 = run(4)
    assert sorted(repr(sp) for sp in calls1) == \
        sorted(repr(sp) for sp in calls4)
    _assert_results_identical(r1, r4, "q3")


def test_set_session_rides_plan_cache(ab_engine):
    """SET SESSION dispatch_batch must take effect on an already-cached
    statement: the property is plan-shaping (engine._plan_shape_props), so
    changing it re-keys the plan instead of silently reusing the old one."""
    e, _, _ = ab_engine
    s = e.create_session("tpch")
    sql = QUERIES["q1"]
    e.execute_sql(sql, s)
    e.execute_sql(sql, s)  # warm at the default batch (4)
    assert e.last_query_counters.coalesced_splits > 0
    warm_default = e.last_query_counters.device_dispatches
    e.execute_sql("set session dispatch_batch = 1", s)
    e.execute_sql(sql, s)
    e.execute_sql(sql, s)
    assert e.last_query_counters.coalesced_splits == 0
    assert e.last_query_counters.device_dispatches > warm_default
    e.execute_sql("reset session dispatch_batch", s)
    e.execute_sql(sql, s)
    assert e.last_query_counters.coalesced_splits > 0


def test_explain_analyze_shows_coalescing(ab_engine):
    e, _, s4 = ab_engine
    r = e.execute_sql(
        "explain analyze select count(*), sum(l_quantity) from lineitem", s4)
    text = "\n".join(str(row[0]) for row in r.rows())
    assert "splits coalesced" in text
