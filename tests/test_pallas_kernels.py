"""Pallas kernel parity vs the XLA paths (interpret mode on CPU; compiled
Mosaic on TPU).

Round-13 contract (ops/pallas_kernels.py docstring):
- hash_probe is BIT-identical to the XLA while_loop probe given the same
  table (same hash family, same probe order, same MAX_PROBES/EMPTY
  semantics).
- hash_insert resolves slot contention by min row index instead of
  scatter-min over packed words, so the slot LAYOUT may differ from the XLA
  table; both protocols keep the open-addressing chain invariant, so parity
  is pinned on OBSERVABLES: placed sets, table word sets, table[slot] ==
  packed, and probe results against either table.  Never assert raw slot
  order across backends.
- compact_rows / bucketize are byte-identical.
- engine results are byte-identical between TRINO_TPU_PALLAS=0 and =1
  (pallas_kernels.force + jax.clear_caches between modes: the choice is
  baked into cached executables at trace time).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu.ops import hashagg, hashjoin, pallas_kernels as pk
from trino_tpu.ops.arrays import compact_rows
from trino_tpu.ops.exchange import bucketize
from trino_tpu.ops.hashing import (EMPTY_KEY, ceil_pow2, pack_keys, probe_step,
                                   splitmix64)
from trino_tpu.ops.pallas_kernels import fused_segment_agg
from trino_tpu.types import BIGINT, INTEGER

INTERPRET = jax.default_backend() != "tpu"


@pytest.fixture
def forced(request):
    """Run a test body under both backends cleanly: force(mode) +
    jax.clear_caches() per switch, always restored."""
    def run(fn):
        out = {}
        for mode in (False, True):
            pk.force(mode)
            jax.clear_caches()
            try:
                out[mode] = fn()
            finally:
                pk.force(None)
        jax.clear_caches()
        return out[False], out[True]
    return run


def _xla_probe(table, rows, packed, valid):
    """The hashjoin.probe while_loop body, pinned here so the parity baseline
    cannot silently change backends."""
    C = table.shape[0] - 1
    h0 = splitmix64(packed)
    stp = probe_step(h0)
    row_ids = jnp.zeros(packed.shape, jnp.int32)
    matched = jnp.zeros(packed.shape, bool)
    done = ~valid

    def cond(c):
        return (c[0] < hashjoin.MAX_PROBES) & ~jnp.all(c[3])

    def body(c):
        p, r, m, d = c
        idx = ((h0 + p * stp) & (C - 1)).astype(jnp.int32)
        cur = table[idx]
        hit = (cur == packed) & ~d
        r = jnp.where(hit, rows[idx], r)
        m = m | hit
        d = d | hit | (cur == EMPTY_KEY)
        return p + 1, r, m, d

    _, r, m, _ = jax.lax.while_loop(
        cond, body, (jnp.zeros((), jnp.int32), row_ids, matched, done))
    return r, m


def _build_xla(keys, C, valid=None):
    n = keys.shape[0]
    if valid is None:
        valid = jnp.ones((n,), bool)
    packed, _ = pack_keys((keys,), (BIGINT,))
    packed = jnp.where(valid, packed, EMPTY_KEY - 1)
    table0 = jnp.full((C + 1,), EMPTY_KEY, jnp.int64)
    pk.force(False)
    try:
        table, slot, placed, _ = hashagg._probe_insert(table0, packed, valid)
    finally:
        pk.force(None)
    rows = jnp.full((C + 1,), 2**31 - 1, jnp.int32).at[
        jnp.where(placed & valid, slot, C)].min(
        jnp.arange(n, dtype=jnp.int32)).at[C].set(0)
    return table, rows, packed


@pytest.mark.parametrize("nb,C_req,npr,seed", [
    (100, 256, 1000, 0),
    (1000, 1024, 4096, 1),
    (256, 256, 512, 2),   # table at 100% load: wraparound + MAX_PROBES paths
    (5, 8, 64, 3),        # capacity < MAX_PROBES: chain revisits slots
])
def test_hash_probe_bit_parity(nb, C_req, npr, seed):
    """Same table -> pallas probe must be BIT-identical to the XLA loop,
    across present keys, absent keys (EMPTY termination and probe
    exhaustion) and invalid lanes."""
    rng = np.random.default_rng(seed)
    C = ceil_pow2(C_req)
    bkeys = jnp.asarray(rng.choice(np.arange(1, 20 * nb), nb,
                                   replace=False).astype(np.int64))
    table, rows, _ = _build_xla(bkeys, C)
    pool = np.concatenate([np.asarray(bkeys), np.asarray(bkeys).max() + 1
                           + np.arange(nb)])
    probe_keys = jnp.asarray(rng.choice(pool, npr))
    valid = jnp.asarray(rng.random(npr) < 0.9)
    packed, _ = pack_keys((probe_keys,), (BIGINT,))
    r_x, m_x = _xla_probe(table, rows, packed, valid)
    h0 = splitmix64(packed)
    r_p, m_p = pk.hash_probe(table[:C], rows[:C], packed, h0, probe_step(h0),
                             valid, interpret=INTERPRET)
    assert np.array_equal(np.asarray(m_x), np.asarray(m_p))
    assert np.array_equal(np.asarray(r_x), np.asarray(r_p))


def test_hash_probe_all_invalid_and_empty_table():
    C = 64
    table = jnp.full((C + 1,), EMPTY_KEY, jnp.int64)
    rows = jnp.zeros((C + 1,), jnp.int32)
    keys = jnp.arange(32, dtype=jnp.int64)
    packed, _ = pack_keys((keys,), (BIGINT,))
    h0 = splitmix64(packed)
    # empty table: every probe terminates at round 0 EMPTY
    r, m = pk.hash_probe(table[:C], rows[:C], packed, h0, probe_step(h0),
                         jnp.ones((32,), bool), interpret=INTERPRET)
    assert not bool(m.any()) and not bool((r != 0).any())
    # all-invalid lanes: nothing matches regardless of table contents
    full_table, frows, _ = _build_xla(keys, C)
    r, m = pk.hash_probe(full_table[:C], frows[:C], packed, h0, probe_step(h0),
                         jnp.zeros((32,), bool), interpret=INTERPRET)
    assert not bool(m.any()) and not bool((r != 0).any())


def test_hash_probe_dictionary_id_key_mix():
    """Multi-column key: int64 + int32 dictionary ids through pack_keys —
    the packed-word compare in-kernel must agree with the XLA loop."""
    rng = np.random.default_rng(4)
    n, C = 512, 1024
    k64 = rng.integers(0, 1 << 20, n).astype(np.int64)
    k32 = rng.integers(0, 500, n).astype(np.int32)  # dictionary-id shaped
    # stats-derived ranges keep the two-column pack injective (the planner's
    # TupleDomain path): 21 + 9 bits << 62
    packed, exact = pack_keys((jnp.asarray(k64), jnp.asarray(k32)),
                              (BIGINT, INTEGER),
                              ranges=((0, 1 << 20), (0, 499)))
    assert exact
    table0 = jnp.full((C + 1,), EMPTY_KEY, jnp.int64)
    table, slot, placed, _ = hashagg._probe_insert(table0, packed,
                                                jnp.ones((n,), bool))
    rows = jnp.arange(C + 1, dtype=jnp.int32)
    valid = jnp.asarray(rng.random(n) < 0.8)
    r_x, m_x = _xla_probe(table, rows, packed, valid)
    h0 = splitmix64(packed)
    r_p, m_p = pk.hash_probe(table[:C], rows[:C], packed, h0, probe_step(h0),
                             valid, interpret=INTERPRET)
    assert np.array_equal(np.asarray(m_x), np.asarray(m_p))
    assert np.array_equal(np.asarray(r_x), np.asarray(r_p))


@pytest.mark.parametrize("n,C_req,dup,seed", [
    (1000, 4096, False, 0),
    (1000, 1024, True, 1),
    (512, 512, False, 2),   # table ends at 100% load
    (30, 32, True, 3),
])
def test_hash_insert_observable_parity(n, C_req, dup, seed):
    """hash_insert vs the XLA claim protocol on the layout-independent
    observables: identical placed lanes, identical table word sets, slot ->
    packed consistency, and identical probe results over either table."""
    rng = np.random.default_rng(seed)
    C = ceil_pow2(C_req)
    keys = (rng.integers(1, n, n) if dup
            else rng.choice(np.arange(1, 20 * n), n, replace=False)).astype(np.int64)
    valid = jnp.asarray(rng.random(n) < 0.85)
    packed, _ = pack_keys((jnp.asarray(keys),), (BIGINT,))
    packed = jnp.where(valid, packed, EMPTY_KEY - 1)
    t0 = jnp.full((C + 1,), EMPTY_KEY, jnp.int64)
    pk.force(False)
    try:
        tx, sx, px, _ = hashagg._probe_insert(t0, packed, valid)
    finally:
        pk.force(None)
    tp, sp, pp = pk.hash_insert(t0, packed, valid, interpret=INTERPRET)
    assert np.array_equal(np.asarray(px), np.asarray(pp))
    assert np.array_equal(np.sort(np.asarray(tx[:C])), np.sort(np.asarray(tp[:C])))
    assert int(tp[C]) == EMPTY_KEY
    live = np.asarray(valid & pp)
    assert np.array_equal(np.asarray(tp)[np.asarray(sp)[live]],
                          np.asarray(packed)[live])
    rows = jnp.arange(C + 1, dtype=jnp.int32)
    pv = jnp.ones((n,), bool)
    _, m1 = _xla_probe(tx, rows, packed, pv)
    s2, m2 = _xla_probe(tp, rows, packed, pv)
    assert np.array_equal(np.asarray(m1), np.asarray(m2))
    # the slot a probe finds in the pallas table holds the probed key
    mm = np.asarray(m2)
    assert np.array_equal(np.asarray(tp)[np.asarray(s2)[mm]],
                          np.asarray(packed)[mm])


def test_hash_insert_multi_page_state_threading():
    """A table built page-by-page (the groupby state threading shape) stays
    chain-consistent: page 2's duplicate keys must find page 1's slots."""
    rng = np.random.default_rng(5)
    C = 1024
    k1 = rng.choice(np.arange(1, 5000), 400, replace=False).astype(np.int64)
    k2 = np.concatenate([k1[:200], 5000 + np.arange(200)]).astype(np.int64)
    p1, _ = pack_keys((jnp.asarray(k1),), (BIGINT,))
    p2, _ = pack_keys((jnp.asarray(k2),), (BIGINT,))
    t = jnp.full((C + 1,), EMPTY_KEY, jnp.int64)
    t, s1, pl1 = pk.hash_insert(t, p1, jnp.ones((400,), bool), interpret=INTERPRET)
    t, s2, pl2 = pk.hash_insert(t, p2, jnp.ones((400,), bool), interpret=INTERPRET)
    assert bool(pl1.all()) and bool(pl2.all())
    # repeated keys landed on their page-1 slots
    assert np.array_equal(np.asarray(s2[:200]), np.asarray(s1[:200]))
    assert int(jnp.sum(t[:C] != EMPTY_KEY)) == 600


def test_groupby_insert_backend_equivalence(forced):
    """End-to-end hashagg: same groups/accumulators from either backend
    (compared as key -> value maps; slot order is backend-private)."""
    rng = np.random.default_rng(6)
    n = 2000
    keys = jnp.asarray(rng.integers(0, 300, n))
    vals = jnp.asarray(rng.random(n))
    valid = jnp.asarray(rng.random(n) < 0.9)

    def run():
        state = hashagg.groupby_init(1024, (np.int64,), ((np.float64, 0.0),))
        state = hashagg.groupby_insert(state, (keys,), (BIGINT,), valid,
                                       [(vals, None)], ["sum"])
        occ, (k,), (acc,) = hashagg.agg_finalize(state)
        occ = np.asarray(occ)
        return dict(zip(np.asarray(k)[occ].tolist(),
                        np.round(np.asarray(acc)[occ], 9).tolist()))

    ref, got = forced(run)
    assert ref == got


@pytest.mark.parametrize("n,sel,bucket", [
    (1000, 0.1, 256), (4096, 0.5, 4096), (512, 0.0, 64),
    (300, 1.0, 100),  # live rows overflow the bucket: clamp/drop path
    (100, 0.5, 200),  # out_len > n: invalid rows must still DROP, not leak
])
def test_compact_rows_byte_parity(n, sel, bucket, forced):
    rng = np.random.default_rng(int(n + bucket))
    valid = jnp.asarray(rng.random(n) < sel)
    cols = (jnp.asarray(rng.integers(-2**62, 2**62, n)),
            jnp.asarray(rng.integers(0, 2**31, n).astype(np.int32)),
            jnp.asarray(rng.random(n).astype(np.float32)),
            jnp.asarray(rng.random(n) < 0.5),
            None)

    def run():
        packed, total = compact_rows(cols, valid, bucket)
        return ([None if p is None else np.asarray(p) for p in packed],
                int(total))

    (ref, rt), (got, gt) = forced(run)
    assert rt == gt == int(valid.sum())
    for r, g in zip(ref, got):
        if r is None:
            assert g is None
        else:
            assert r.dtype == g.dtype and np.array_equal(r, g)
    # the documented contract, independent of backend agreement: zeros
    # beyond the live count (an out_len > n leak once survived review)
    live = min(int(valid.sum()), bucket)
    assert not np.any(ref[0][live:])


def test_compact_gate_admits_only_compiled_shapes():
    """DOUBLE columns, outputs past the compiled VMEM bound and rows wider
    than one lane tile stay on the XLA path (the v5e compiler refused each)."""
    i64 = jnp.zeros((8,), jnp.int64)
    pk.force(True)
    try:
        assert pk.compact_enabled(8, pk.COMPACT_OUT_MAX, (i64,))
        assert not pk.compact_enabled(8, pk.COMPACT_OUT_MAX + 1, (i64,))
        assert not pk.compact_enabled(8, 64, (i64, jnp.zeros((8,), jnp.float64)))
        assert not pk.compact_enabled(8, 64, (i64,) * 65)
        assert not pk.compact_enabled(0, 64, (i64,))
        # float64 through the shared entry: XLA packs it, same answer
        f64 = jnp.arange(8, dtype=jnp.float64)
        (got,), total = compact_rows((f64,), f64 > 3, 8)
        assert int(total) == 4 and np.array_equal(np.asarray(got)[:4], [4, 5, 6, 7])
    finally:
        pk.force(None)


def test_bucketize_byte_parity(forced):
    rng = np.random.default_rng(8)
    n, P, bucket = 2048, 8, 320
    cols = (jnp.asarray(rng.integers(0, 1 << 40, n)),
            jnp.asarray(rng.random(n).astype(np.float32)),
            jnp.asarray(rng.random(n) < 0.5))
    valid = jnp.asarray(rng.random(n) < 0.9)
    pid = jnp.asarray(rng.integers(0, P, n).astype(np.int32))

    def run():
        packed, pvalid, counts = bucketize(cols, valid, pid, P, bucket)
        return ([np.asarray(c) for c in packed], np.asarray(pvalid),
                np.asarray(counts))

    (rc, rv, ro), (gc, gv, go) = forced(run)
    # the rows bound for each partition, before the bucket cuts them
    assert np.array_equal(ro, go)
    assert np.array_equal(ro, np.bincount(np.asarray(pid)[np.asarray(valid)], minlength=P))
    assert np.array_equal(rv, gv)
    for r, g in zip(rc, gc):
        assert np.array_equal(r, g)


def test_shard_map_pallas_parity(forced):
    """The kernels as the DISTRIBUTED path runs them — inside shard_map over
    the 8-device CPU mesh: bucketize + all_to_all routing, and per-worker
    insert + probe_slots with a REPLICATED build side against varying probe
    keys (the round-5 varying-axis shape).  use_pallas() is OFF by default on
    this mesh, so without this test the shard_map Pallas traces would first
    execute on a real chip."""
    from functools import partial

    from jax.sharding import NamedSharding, PartitionSpec as PS

    from jax import shard_map

    from trino_tpu.parallel.mesh import WORKER_AXIS, worker_mesh

    W = min(8, len(jax.devices()))
    if W < 2:
        pytest.skip("needs a multi-device mesh")
    per, C = 256, 1024
    rng = np.random.default_rng(9)
    mesh = worker_mesh(W)
    pkeys = jax.device_put(jnp.asarray(rng.integers(1, 4000, (W, per))),
                           NamedSharding(mesh, PS(WORKER_AXIS)))
    bkeys = jnp.asarray(rng.choice(np.arange(1, 4000), 500,
                                   replace=False).astype(np.int64))

    def frag(pk_keys, bkeys):
        from trino_tpu.ops.exchange import bucketize, exchange_all_to_all

        k = pk_keys[0]
        pid = (k % W).astype(jnp.int32)
        packed, pvalid, _ = bucketize((k,), jnp.ones_like(k, bool), pid, W,
                                      per)
        recv, rvalid = exchange_all_to_all(packed, pvalid, WORKER_AXIS, W)
        bpacked, _ = pack_keys((bkeys,), (BIGINT,))
        t0 = jnp.full((C + 1,), EMPTY_KEY, jnp.int64)
        table, _, _, _ = hashagg._probe_insert(t0, bpacked,
                                            jnp.ones(bkeys.shape, bool))
        slot, matched = hashjoin.probe_slots(table, (recv[0],), (BIGINT,),
                                             rvalid)
        # slot layout is backend-private: reduce to the layout-independent
        # observable (the probed key word where matched)
        found = jnp.where(matched, table[slot], 0)
        return found[None], matched[None]

    def run():
        # check_vma=False: in INTERPRET mode Pallas re-evaluates the kernel's
        # jaxpr against the per-worker operands, and primitives refuse to mix
        # them with the kernel's own (unvarying) constants.  A compiled Mosaic
        # kernel is opaque to that check; what it needs — `vma` on the
        # pallas_call out-shapes — is pinned with check_vma ON over a
        # described four-chip mesh in tests/test_chip_compile.py.
        f = partial(shard_map, mesh=mesh, in_specs=(PS(WORKER_AXIS), PS()),
                    out_specs=(PS(WORKER_AXIS), PS(WORKER_AXIS)),
                    check_vma=False)(frag)
        found, matched = jax.jit(f)(pkeys, bkeys)
        return np.asarray(found), np.asarray(matched)

    (f_x, m_x), (f_p, m_p) = forced(run)
    assert np.array_equal(m_x, m_p)
    assert np.array_equal(f_x, f_p)
    assert m_x.any()  # the probe actually matched something


# ------------------------------------------------------------ engine tier-1
# Byte-identity of full statements between TRINO_TPU_PALLAS=0 and =1.  q1/q3
# are the ISSUE's pinned pair; the planner's direct-index paths bypass the
# hash kernels for TPC-H's dense keys, so two hash-shaped statements ride
# along (multi-column join key -> JoinTable probe; expression group-by key ->
# unknown ranges -> _probe_insert) and the test asserts the pallas branch
# actually fired for them.
_ENGINE_STMTS = {
    "q1": None,  # filled from chaos_matrix below
    "q3": None,
    "join2": ("select count(*) c, sum(ps_availqty) s from lineitem l "
              "join partsupp ps on l.l_partkey = ps.ps_partkey "
              "and l.l_suppkey = ps.ps_suppkey"),
    "aggexpr": ("select l_orderkey % 97 as k, count(*) c, sum(l_quantity) q "
                "from lineitem group by l_orderkey % 97 order by k"),
}


def test_engine_results_byte_identical_across_backends(monkeypatch):
    from trino_tpu import Engine
    from trino_tpu.connectors.tpch import TpchConnector
    from trino_tpu.execution.chaos_matrix import QUERIES, result_signature

    stmts = dict(_ENGINE_STMTS)
    stmts["q1"] = QUERIES["q1"]
    stmts["q3"] = QUERIES["q3"]

    picks = {"probe": 0, "insert": 0}
    real_probe, real_insert = pk.hash_probe, pk.hash_insert

    def count_probe(*a, **k):
        picks["probe"] += 1
        return real_probe(*a, **k)

    def count_insert(*a, **k):
        picks["insert"] += 1
        return real_insert(*a, **k)

    monkeypatch.setattr(pk, "hash_probe", count_probe)
    monkeypatch.setattr(pk, "hash_insert", count_insert)

    sigs = {}
    for mode in (False, True):
        pk.force(mode)
        jax.clear_caches()
        try:
            e = Engine()
            e.register_catalog("tpch", TpchConnector(sf=0.01, split_rows=4096))
            s = e.create_session("tpch")
            sigs[mode] = {n: result_signature(e.execute_sql(q, s))
                          for n, q in stmts.items()}
        finally:
            pk.force(None)
    jax.clear_caches()
    for name in stmts:
        assert sigs[False][name] == sigs[True][name], name
    # the hash-shaped statements must have taken the pallas branch
    assert picks["probe"] >= 1 and picks["insert"] >= 1, picks


# ----------------------------------------------------- fused segment agg (r3)
def test_fused_segment_agg_matches_numpy():
    rng = np.random.default_rng(7)
    n, C = 10_000, 8
    slot = rng.integers(0, C, n).astype(np.int32)
    valid = rng.random(n) < 0.8
    v1 = rng.random(n)
    v2 = rng.random(n) * 10
    counts, (s1, s2) = fused_segment_agg(
        jax.numpy.asarray(slot), jax.numpy.asarray(valid),
        (jax.numpy.asarray(v1), jax.numpy.asarray(v2)), n_slots=C,
        interpret=INTERPRET)
    for c in range(C):
        m = valid & (slot == c)
        assert int(counts[c]) == int(m.sum())
        assert np.isclose(float(s1[c]), v1[m].sum(), rtol=1e-5)
        assert np.isclose(float(s2[c]), v2[m].sum(), rtol=1e-5)


def test_fused_segment_agg_no_values():
    slot = jax.numpy.asarray(np.array([0, 1, 1, 2, 2, 2], np.int32))
    valid = jax.numpy.asarray(np.array([True] * 5 + [False]))
    counts, sums = fused_segment_agg(slot, valid, (), n_slots=4,
                                     interpret=INTERPRET)
    assert list(np.asarray(counts)) == [1, 2, 2, 0]
    assert sums == ()
