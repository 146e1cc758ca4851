"""Device-side row compaction: `ops/arrays.live_indices` and its four users
(compact_rows' XLA branch, exec/pages._compact_part / _compact_part_sized,
hashagg.compact_groups) against a numpy reference, and the structural guard
that none of their programs holds a scatter (on a v5e an XLA scatter pays per
INPUT lane, 175-290 ns each: PERF.md section 6, PR 26)."""

import re
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu.exec import pages
from trino_tpu.ops import hashagg
from trino_tpu.ops import pallas_kernels as pk
from trino_tpu.ops.arrays import compact_rows, live_indices
from trino_tpu.ops.hashing import EMPTY_KEY

NS = (1, 1023, 1024, 2**16 + 3)
SELECTIVITIES = ("none", "one", "first", "last", "sparse", "all")
OUT_LENS = ("under", "exact", "over_n")


@pytest.fixture(autouse=True)
def xla_branch():
    """The XLA branch whatever TRINO_TPU_PALLAS says (read at trace time)."""
    pk.force(False)
    yield
    pk.force(None)


def _mask(n, sel, rng):
    valid = np.zeros(n, bool)
    if sel == "one":
        valid[n // 2] = True
    elif sel == "first":
        valid[0] = True
    elif sel == "last":
        valid[-1] = True
    elif sel == "sparse":
        valid = rng.random(n) < 0.05
    elif sel == "all":
        valid[:] = True
    return valid


def _columns(n, rng):
    """One column per dtype the engine moves, and a ``None`` entry."""
    return (rng.integers(-2**62, 2**62, n, dtype=np.int64),
            rng.integers(-2**31, 2**31, n).astype(np.int32),
            None,
            rng.integers(-128, 128, n).astype(np.int8),
            rng.random(n) < 0.5,
            rng.random(n).astype(np.float32),
            rng.random(n) - 0.5)


def _size(kind, count, n):
    return {"under": max(count // 2, 1), "exact": max(count, 1),
            "over_n": n + 5}[kind]


def _same(got, want):
    got = np.asarray(got)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.array_equal(got, want)


@pytest.mark.parametrize("out_len", OUT_LENS)
@pytest.mark.parametrize("sel", SELECTIVITIES)
@pytest.mark.parametrize("n", NS)
def test_compaction_parity(n, sel, out_len):
    rng = np.random.default_rng(n * 31 + len(sel))
    valid = _mask(n, sel, rng)
    live = np.nonzero(valid)[0]
    count = len(live)
    size = _size(out_len, count, n)
    kept = live[:size]
    cols = _columns(n, rng)
    dcols = tuple(None if c is None else jnp.asarray(c) for c in cols)
    dvalid = jnp.asarray(valid)

    # live_indices: ascending lane numbers, in-bounds sorted filler, the count
    idx, total = jax.jit(live_indices, static_argnums=1)(dvalid, size)
    idx = np.asarray(idx)
    assert idx.dtype == np.int32 and idx.shape == (size,)
    assert np.asarray(total).dtype == np.int32 and int(total) == count
    assert np.array_equal(idx[:len(kept)], kept)
    assert idx.min() >= 0 and idx.max() < n and np.all(np.diff(idx) >= 0)

    # compact_rows: live rows first in arrival order, zeros beyond them,
    # overflow lanes dropped, None passed through, the full live count
    packed, total = jax.jit(compact_rows, static_argnums=2)(dcols, dvalid, size)
    assert int(total) == count
    for c, p in zip(cols, packed):
        if c is None:
            assert p is None
            continue
        want = np.zeros(size, c.dtype)
        want[:len(kept)] = c[kept]
        _same(p, want)

    # _compact_part / _compact_part_sized (sizes never pass n in the executor)
    psize = min(size, n)
    pkept = live[:psize]
    vals = tuple(c for c in dcols if c is not None)
    nulls = tuple(jnp.asarray(rng.random(n) < 0.3) if i % 2 else None
                  for i in range(len(vals)))
    ccols, cnulls = pages._compact_part(vals, nulls, dvalid, psize)
    scols, snulls, pvalid = pages._compact_part_sized(vals, nulls, dvalid, psize)
    _same(pvalid, np.arange(psize) < count)
    for got_c, got_n in ((ccols, cnulls), (scols, snulls)):
        for src, got in zip(vals + nulls, got_c + got_n):
            if src is None:
                assert got is None
                continue
            assert got.shape == (psize,) and got.dtype == src.dtype
            _same(got[:len(pkept)], np.asarray(src)[pkept])

    # compact_groups: the occupied slots of a table of capacity n, slot order
    table = np.full(n + 1, EMPTY_KEY, np.int64)
    table[live] = live

    def slots(c):  # capacity + 1: the table's overflow sink
        return jnp.asarray(np.concatenate([c, np.zeros(1, c.dtype)]))

    state = hashagg.GroupByState(
        jnp.asarray(table), (slots(cols[0]),), (slots(cols[4]),),
        (slots(cols[6]), slots(cols[1])), jnp.zeros((), bool))
    keys, key_nulls, accs = hashagg.compact_groups(state, size)
    for src, got in zip((cols[0], cols[4], cols[6], cols[1]),
                        keys + key_nulls + accs):
        assert got.shape == (size,) and got.dtype == src.dtype
        _same(got[:len(kept)], src[kept])


def _lowered_ops(text):
    return set(re.findall(r"\b(?:stablehlo|mhlo|chlo)\.([\w.]+)", text))


@pytest.mark.parametrize("site", ["jc_fn", "_compact_part",
                                  "_compact_part_sized", "compact_groups",
                                  "join.match"])
def test_compaction_programs_hold_no_scatter(site, tpch_sf001):
    """A later edit or a JAX upgrade that brings a scatter (or a
    ``jnp.nonzero``, whose ``bincount`` is a scatter-add) back into a
    compaction program fails here, not in a benchmark.  The match step of a
    split join (PR 28) runs before every boundary's pack: a filter, one
    gather, no sort and no scatter."""
    if site == "join.match":
        from test_split_join import lowered_steps

        ops = _lowered_ops(lowered_steps(tpch_sf001)[site])
        assert "gather" in ops and "sort" not in ops, ops
        assert not [op for op in ops if "scatter" in op], ops
        return
    n, size = 4096, 256
    i64 = jax.ShapeDtypeStruct((n,), jnp.int64)
    f64 = jax.ShapeDtypeStruct((n,), jnp.float64)
    mask = jax.ShapeDtypeStruct((n,), jnp.bool_)
    cols, nulls = (i64, f64, mask), (mask, None, None)
    if site == "jc_fn":
        text = jax.jit(pages._compact_page, static_argnums=3).lower(
            cols, nulls, mask, size).as_text()
    elif site == "compact_groups":
        slots = jax.ShapeDtypeStruct((n + 1,), jnp.int64)
        state = hashagg.GroupByState(
            slots, (slots,), (jax.ShapeDtypeStruct((n + 1,), jnp.bool_),),
            (slots,), jax.ShapeDtypeStruct((), jnp.bool_))
        text = hashagg.compact_groups.lower(state, size).as_text()
    else:
        text = getattr(pages, site).lower(cols, nulls, mask, size).as_text()
    ops = _lowered_ops(text)
    assert "gather" in ops and "sort" in ops, ops
    assert not [op for op in ops if "scatter" in op], ops
    assert "scatter" not in text


def test_compaction_counters_and_surfaces():
    """QueryCounters.compactions / compact_lanes_in / compact_lanes_out: the
    static lanes of each compaction dispatch, in EXPLAIN ANALYZE and summed
    into the engine's totals (what /v1/metrics prints)."""
    from trino_tpu import Engine
    from trino_tpu.connectors.tpch import TpchConnector

    e = Engine()
    e.register_catalog("tpch", TpchConnector(sf=0.01))
    before = e.counters_total.snapshot()
    r = e.execute_sql(
        "explain analyze select o_orderpriority, count(*) c from orders "
        "where o_totalprice > 400000 group by o_orderpriority")
    text = "\n".join(str(row[0]) for row in r.rows())
    m = re.search(r"Compaction: (\d+) compactions, (\d+) lanes in, "
                  r"(\d+) lanes out", text)
    assert m, text
    n, lanes_in, lanes_out = map(int, m.groups())
    c = e.last_query_counters
    assert (n, lanes_in, lanes_out) == \
        (c.compactions, c.compact_lanes_in, c.compact_lanes_out)
    assert n >= 1 and lanes_in >= n and lanes_out >= n
    after = e.counters_total
    assert after.compactions - before.compactions >= n
    assert after.compact_lanes_in - before.compact_lanes_in >= lanes_in
    assert after.as_dict()["compact_lanes_out"] == after.compact_lanes_out

    from test_profiling import _parse_prometheus
    from trino_tpu.server.server import CoordinatorServer

    srv = CoordinatorServer(e, port=0)
    srv.start()
    try:
        parsed = _parse_prometheus(urllib.request.urlopen(
            srv.url + "/v1/metrics", timeout=10).read().decode())
    finally:
        srv.stop()
    for field in ("compactions", "compact_lanes_in", "compact_lanes_out"):
        assert parsed["types"][f"trino_tpu_{field}_total"] == "counter"
        assert parsed["samples"][f"trino_tpu_{field}_total"][0][1] == \
            getattr(after, field)
