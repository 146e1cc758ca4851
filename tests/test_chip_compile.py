"""What the chip's own compiler says, asked without a chip.

libtpu is installed here, and it compiles for a v5e that is DESCRIBED, not
attached (`jax.experimental.topologies`).  Interpret mode cannot show what
these cases show: every Pallas kernel the TPU backend takes by default went
through every interpret-mode parity test and was still refused by Mosaic
(PR 22).  Each case lowers with `interpret=False`, `jax_enable_x64` on as the
engine runs, at both ends of the shapes the kernel's gate admits, so a later
PR that widens a gate or edits a kernel meets the compiler here, at no chip
time.  Nothing runs: a compile that passes is not a chip run.

The topology is described inside a module-scoped fixture (never at import,
in a skipif, in parametrize or in conftest.py) and the compiles happen in
this process: one process at a time may load libtpu, and under xdist every
worker imports every test file.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS, SingleDeviceSharding

from trino_tpu.ops import pallas_kernels as pk

PAGE_ROWS = 1 << 21  # chip_smoke.py's and the benchmark's split_rows


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """conftest.py turns the persistent compile cache on; an executable for a
    described chip can be written to it but never read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


@pytest.fixture
def as_on_tpu(monkeypatch, no_persistent_cache):
    """Steer the trace the way the TPU backend would: kernels on, compiled.
    (`jax.default_backend()` still says cpu here, so the gates are steered
    from the test, not through an option of the program.)"""
    monkeypatch.setattr(pk, "pallas_interpret", lambda: False)
    pk.force(True)
    yield
    pk.force(None)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    return compiled.as_text()


def _s(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


# Q1's group table starts at 64 slots; 2 and PALLAS_TABLE_MAX are the gate's ends
@pytest.mark.parametrize("capacity", [2, 64, 4096, pk.PALLAS_TABLE_MAX])
def test_hash_probe_compiles_for_v5e(capacity, one_chip, as_on_tpu):
    assert pk.table_kernels_enabled(capacity)
    assert not pk.table_kernels_enabled(2 * pk.PALLAS_TABLE_MAX)
    n = PAGE_ROWS
    text = _compile(
        lambda t, v, p, h, s, ok: pk.hash_probe(t, v, p, h, s, ok),
        _s(one_chip, (capacity,), jnp.int64), _s(one_chip, (capacity,), jnp.int32),
        _s(one_chip, (n,), jnp.int64), _s(one_chip, (n,), jnp.int64),
        _s(one_chip, (n,), jnp.int64), _s(one_chip, (n,), jnp.bool_))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("capacity", [2, 64, 4096, pk.PALLAS_TABLE_MAX])
def test_hash_insert_compiles_for_v5e(capacity, one_chip, as_on_tpu):
    n = PAGE_ROWS
    text = _compile(
        lambda t, p, ok: pk.hash_insert(t, p, ok),
        _s(one_chip, (capacity + 1,), jnp.int64),
        _s(one_chip, (n,), jnp.int64), _s(one_chip, (n,), jnp.bool_))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("out_len,limbs", [
    (1, 1), (1 << 14, 4), (pk.COMPACT_OUT_MAX, 1),
    (pk.COMPACT_OUT_MAX, pk.COMPACT_LIMBS_MAX)])
def test_compact_compiles_for_v5e(out_len, limbs, one_chip, as_on_tpu):
    n = PAGE_ROWS
    text = _compile(
        lambda m, ok: pk.compact_rows_matrix(m, ok, out_len),
        _s(one_chip, (n, limbs), jnp.int32), _s(one_chip, (n,), jnp.bool_))
    assert "tpu_custom_call" in text


# three stacked 2^21-lane pages into the n/16 bucket (the shape ISSUE 26
# named), what q9's gated compaction really runs at SF1 (lineitem as one
# resident page into n >> 6; PERF.md section 5), one page into
# _compact_part*'s floor
@pytest.mark.parametrize("n,size", [(3 * PAGE_ROWS, 3 * PAGE_ROWS // 16),
                                    (12_582_906, 196_607), (PAGE_ROWS, 1024)])
def test_live_index_pack_compiles_for_v5e(n, size, one_chip, no_persistent_cache):
    """`ops/arrays.live_indices` plus seven 64-bit gathers: the XLA compaction
    every bucket past the Pallas gate takes, and no scatter in what the v5e
    compiler makes of it."""
    from trino_tpu.ops.arrays import gather_rows, live_indices

    def pack(cols, valid):
        idx, total = live_indices(valid, size)
        return tuple(gather_rows(c, idx) for c in cols), total

    text = _compile(pack, tuple(_s(one_chip, (n,), jnp.int64) for _ in range(7)),
                    _s(one_chip, (n,), jnp.bool_))
    assert "sort" in text and "gather" in text
    assert "scatter" not in text


# q3's TopN at SF10 (10 of 113,513 groups by revenue desc, o_orderdate) and the
# widest the gate admits (TOPN_SELECT_MAX rows over TOPN_SELECT_WORK lane-rounds)
@pytest.mark.parametrize("n,count", [(113_513, 10), (1 << 21, 1024)])
def test_topn_selection_compiles_for_v5e(n, count, one_chip, no_persistent_cache):
    """`ops/arrays.first_rows`: reductions in a loop, and no sort in what the
    v5e compiler makes of it (a sort that carries a payload costs the TPU
    compiler one to two minutes, PERF.md PR 27)."""
    import time

    from trino_tpu.ops.arrays import first_rows

    t0 = time.perf_counter()
    text = first_rows.lower(
        (_s(one_chip, (n,), jnp.int32), _s(one_chip, (n,), jnp.int64),
         _s(one_chip, (n,), jnp.bool_)), count).compile().as_text()
    assert " sort(" not in text  # the HLO instruction, not a name in the metadata
    assert time.perf_counter() - t0 < 60


# PR 39: a group-by statement's epilogue as one program.  The dashboard's q1 (a full
# Sort of a 64-lane packed page by two dictionary keys, ten columns, six with a mask),
# SF10 q3's TopN (10 of 113,513 groups in a 2^17-lane page: the selection, no sort).
# (A full Sort of 2^14 lanes by two keys with masks costs the v5e compiler 67 s here,
# 65 of them the five-key `jnp.lexsort` alone, as it did eager: PERF.md PR 27, PR 39.)
@pytest.mark.parametrize("name,n,count,select", [
    ("dashboard_q1", 64, 4, False), ("sf10_q3_topn", 1 << 17, 10, True)])
def test_the_sort_program_compiles_for_v5e(name, n, count, select, one_chip,
                                           no_persistent_cache):
    import time

    from trino_tpu.exec import pages

    strings = 2 if name == "dashboard_q1" else 0
    cols = tuple(_s(one_chip, (n,), jnp.int32) for _ in range(strings)) \
        + tuple(_s(one_chip, (n,), jnp.int64) for _ in range(4 if strings else 3)) \
        + ((_s(one_chip, (n,), jnp.float64),) * 4 if strings else ())
    nulls = tuple(_s(one_chip, (n,), jnp.bool_) if i < 6 else None
                  for i in range(len(cols)))
    luts = tuple(_s(one_chip, (3,), jnp.int64) for _ in range(strings))
    keys = ((0, True, False, 0), (1, True, False, 1)) if strings \
        else ((1, False, False, -1), (2, True, False, -1))
    narrow = tuple(np.dtype(np.int8) if i < strings else None for i in range(len(cols)))
    t0 = time.perf_counter()
    text = pages._sorted_rows.lower(cols, nulls, _s(one_chip, (n,), jnp.bool_), luts,
                                    keys, count, select, narrow, False).compile().as_text()
    assert (" sort(" in text) != select  # the selection sorts nothing
    assert time.perf_counter() - t0 < 90


def test_a_wide_group_bys_initial_state_compiles_for_v5e(one_chip, no_persistent_cache):
    """`agg.hash.init` at SF10 q18's 2^24 slots: fills, nothing folded into the program
    as a constant of the state's size."""
    from trino_tpu.exec import groupby

    compiled = groupby._hash_init.lower(
        1 << 24, (jnp.int64,), ((jnp.int64, 0), (jnp.float64, 0))).compile()
    assert compiled.memory_analysis().generated_code_size_in_bytes < 1 << 20


def test_the_observed_direct_insert_keeps_its_one_hot_inside_the_reduces(
        one_chip, no_persistent_cache):
    """PR 44: q65's avg by store at scale 10, direct-indexed at the 128 slots its
    observed bounds name (`hashagg.observed_direct_config`): 4,194,304 lanes, one int64
    key and one int64 input, both with null masks, `sum_hi32` / `sum_lo32` / `count`.
    Materialised, the `[lanes, 128]` one-hot would be 4.3 GB an accumulator; inside the
    reduce fusions the program's temporaries are under a megabyte (708,608 B when this
    was written).  Were it to leave them, `direct_groupby_insert` has to block its rows."""
    from trino_tpu.ops import hashagg

    lanes, kinds = 1 << 22, ("sum_hi32", "sum_lo32", "count")
    cfg = hashagg.observed_direct_config([(1, 120, False)], lanes)
    assert cfg.capacity == hashagg.ONEHOT_CAP_MAX

    def insert(state, key, key_null, value, value_null, valid):
        return hashagg.direct_groupby_insert(
            state, cfg, (key,), valid, tuple((value, value_null) for _ in kinds), kinds,
            (key_null,))

    state = jax.tree.map(
        lambda a: _s(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: hashagg.direct_groupby_init(
            cfg, (jnp.int64,), tuple((jnp.int64, 0) for _ in kinds))))
    wide, mask = _s(one_chip, (lanes,), jnp.int64), _s(one_chip, (lanes,), jnp.bool_)
    compiled = jax.jit(insert).lower(state, wide, mask, wide, mask, mask).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_q1_page_step_compiles_for_v5e(one_chip, as_on_tpu):
    """The jitted per-page step of Q1 (scan transform -> group-by insert into
    the 64-slot table) — the first aggregation of the first query."""
    import __graft_entry__

    step, args = __graft_entry__.entry()
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), args)
    text = _compile(step, *shapes)
    assert "tpu_custom_call" in text  # hash_insert at capacity 64


def test_exchange_fragment_compiles_over_four_chips(topo, as_on_tpu):
    """The mesh executor's probe exchange (bucketize -> all_to_all, and the side
    channel that carries its counts to the consumer's flags pull) inside shard_map
    with check_vma ON over a Mesh of the four described chips: the Pallas compaction
    sits inside the partitioned pack, and the exchange is a real all-to-all."""
    from trino_tpu.exec.distributed import (_route_rows, _side, _side_merge,
                                            _side_probe)
    from trino_tpu.parallel.mesh import WORKER_AXIS

    W = 4
    mesh = Mesh(np.array(topo.devices).reshape(W), (WORKER_AXIS,))
    per, bucket = 1 << 16, 1 << 15

    def frag(keys, vals, carried):
        k, v = keys[0], vals[0]
        valid = jnp.ones_like(k, bool)
        pid = (k % W).astype(jnp.int32)
        recv, _, rvalid, counts = _route_rows((k, v), (None, None), valid, pid, W,
                                              bucket, WORKER_AXIS)
        of = _side_merge(carried[0], _side_probe(_side(valid), counts, bucket))
        return recv[0][None], recv[1][None], rvalid[None], of[None]

    f = jax.shard_map(frag, mesh=mesh, in_specs=(PS(WORKER_AXIS),) * 3,
                      out_specs=(PS(WORKER_AXIS),) * 4)
    sharded = NamedSharding(mesh, PS(WORKER_AXIS))
    text = _compile(f, jax.ShapeDtypeStruct((W, per), jnp.int64, sharding=sharded),
                    jax.ShapeDtypeStruct((W, per), jnp.int32, sharding=sharded),
                    jax.ShapeDtypeStruct((W, 5), jnp.int64, sharding=sharded))
    assert "all-to-all" in text
    assert "tpu_custom_call" in text


def test_the_narrowed_hash_lookup_compiles_over_four_chips(topo, no_persistent_cache):
    """`ops/hashjoin._find_slots` as the mesh's q3 runs it (PR 37): a per-worker table of
    2^21 slots (above `PALLAS_TABLE_MAX`: the XLA lookup), 2^20 received lanes a chip,
    inside shard_map with check_vma ON, once with a CONSTANT key (unvarying, against the
    per-worker table).  The packed levels are there: a sort a level, a loop a width."""
    from trino_tpu.ops import hashing, hashjoin as hj
    from trino_tpu.parallel.mesh import WORKER_AXIS
    from trino_tpu.types import BIGINT

    W, slots, lanes = 4, 1 << 21, 1 << 20
    assert len(hj.probe_widths(lanes)) == 1 + len(hashing.NARROW_SHIFTS)
    mesh = Mesh(np.array(topo.devices).reshape(W), (WORKER_AXIS,))

    def frag(tables, keys, valid):
        slot, matched = hj.probe_slots(tables[0], (keys[0],), (BIGINT,), valid[0])
        cslot, cmatched = hj.probe_slots(
            tables[0], (jnp.ones((lanes,), jnp.int64),), (BIGINT,), valid[0])
        return slot[None], matched[None], cslot[None], cmatched[None]

    f = jax.shard_map(frag, mesh=mesh, in_specs=(PS(WORKER_AXIS),) * 3,
                      out_specs=(PS(WORKER_AXIS),) * 4)
    sharded = NamedSharding(mesh, PS(WORKER_AXIS))
    text = _compile(f, jax.ShapeDtypeStruct((W, slots + 1), jnp.int64, sharding=sharded),
                    jax.ShapeDtypeStruct((W, lanes), jnp.int64, sharding=sharded),
                    jax.ShapeDtypeStruct((W, lanes), jnp.bool_, sharding=sharded))
    levels = 1 + len(hashing.NARROW_SHIFTS)
    assert len(re.findall(r" while\(", text)) >= 2 * levels
    assert len(re.findall(r" sort\(", text)) >= 2 * (levels - 1)


def test_the_narrowed_hash_insert_compiles_over_four_chips(topo, no_persistent_cache,
                                                           monkeypatch):
    """`ops/hashagg._probe_insert` (PR 41) inside shard_map with check_vma ON: a
    per-worker table of 2^18 slots (above `PALLAS_TABLE_MAX`: the XLA claim loop) under
    2^17 lanes a chip, once with a CONSTANT key (unvarying) against the per-worker table
    and once with per-worker keys against a table made in the program (`groupby_init`:
    unvarying).  The packed levels are there: a sort and a loop a level, each level
    behind a conditional on what its wider loop left unplaced."""
    from trino_tpu.ops import hashagg, hashing
    from trino_tpu.ops.hashing import EMPTY_KEY
    from trino_tpu.parallel.mesh import WORKER_AXIS

    W, slots, lanes = 4, 1 << 18, 1 << 17
    # (the insert's floor is 2^20 lanes: a sort a level at that width is a minute of
    # this file's time; the levels' program is the same at 2^17)
    monkeypatch.setattr(hashing, "INSERT_MIN_LANES", 1 << 16)
    levels = len(hashagg.insert_widths(lanes))
    assert levels == 1 + len(hashing.INSERT_SHIFTS)
    mesh = Mesh(np.array(topo.devices).reshape(W), (WORKER_AXIS,))

    def frag(tables, keys, valid):
        out = hashagg._probe_insert(tables[0], jnp.ones((lanes,), jnp.int64), valid[0])
        out += hashagg._probe_insert(jnp.full((slots + 1,), EMPTY_KEY, jnp.int64),
                                     keys[0], valid[0])
        return tuple(o[None] for o in out)

    f = jax.shard_map(frag, mesh=mesh, in_specs=(PS(WORKER_AXIS),) * 3,
                      out_specs=(PS(WORKER_AXIS),) * 8)
    sharded = NamedSharding(mesh, PS(WORKER_AXIS))
    text = _compile(f, jax.ShapeDtypeStruct((W, slots + 1), jnp.int64, sharding=sharded),
                    jax.ShapeDtypeStruct((W, lanes), jnp.int64, sharding=sharded),
                    jax.ShapeDtypeStruct((W, lanes), jnp.bool_, sharding=sharded))
    assert len(re.findall(r" while\(", text)) >= 2 * levels
    assert len(re.findall(r" sort\(", text)) >= 2 * (levels - 1)
    assert len(re.findall(r" conditional\(", text)) >= 2 * (levels - 1)


def _gathers_from_arguments(text):
    """Names of the entry parameters that a gather of the compiled program reads
    directly (not through a copy or a fusion of the program's own)."""

    entry = text[text.index("\nENTRY"):]
    params = set(re.findall(r"%([\w.\-]+) = \S+ parameter\(", entry))
    hit = set()
    for line in entry.splitlines():
        if "kind=kCustom" in line and "gather" in line:
            ops = re.search(r" fusion\(([^)]*)\)", line).group(1)
            hit |= {o.strip().lstrip("%") for o in ops.split(",")} & params
    return hit


# q3's probe of the table over orders at SF10 (14,999,994 slots, a build page of
# 2^23 lanes, four 2^21-lane pages a dispatch) and the gate's own size
@pytest.mark.parametrize("slots,build,lanes", [(14_999_994, 1 << 23, 1 << 23),
                                               (1 << 22, 1 << 21, 1 << 21)])
def test_staged_direct_probe_compiles_for_v5e(slots, build, lanes, one_chip,
                                              no_persistent_cache):
    """`hashjoin.stage_direct_table`: every gather of the probe reads a copy that
    the program made (the barriers hold: the copies are neither folded away nor
    fused into the gathers), none reads an argument where the allocator put it."""
    from trino_tpu.ops import hashjoin as hj

    def probe(dt, key, valid, staged):
        if staged:
            dt = hj.stage_direct_table(dt)
        rows, matched = hj.direct_probe(dt, key, valid)
        safe = jnp.where(matched, rows, 0)
        return tuple(c[safe] for c in dt.build_columns), matched

    dt = hj.DirectJoinTable(
        rows=_s(one_chip, (slots,), jnp.int32), occ=_s(one_chip, (slots,), jnp.bool_),
        build_columns=tuple(_s(one_chip, (build,), d)
                            for d in (jnp.int64, jnp.int32, jnp.int32)),
        build_null_masks=(None, None, None), dup_count=_s(one_chip, (), jnp.int32), lo=0)
    args = (dt, _s(one_chip, (lanes,), jnp.int64), _s(one_chip, (lanes,), jnp.bool_))
    staged = jax.jit(probe, static_argnums=3).lower(*args, True).compile().as_text()
    assert "gather" in staged
    assert _gathers_from_arguments(staged) == set()
    small = hj.DirectJoinTable(
        rows=jnp.zeros(9, jnp.int32), occ=jnp.zeros(9, bool), build_columns=(),
        build_null_masks=(), dup_count=jnp.int32(0), lo=0)
    assert hj.stage_direct_table(small) is small  # under the gate: left as it is


def test_tpcds_store_sales_generator_compiles_small_for_v5e(one_chip, no_persistent_cache):
    """The generator of a TPC-DS scan, at SF10's split and with the split's first row
    TRACED, as `TpcdsConnector.generate` runs it: one program a (table, length, column
    set).  `ss_sold_date_sk` needs a civil month of every candidate day, and in emulated
    64-bit arithmetic that alone was 18,372 lines of HLO and 204 s of the TPU compiler
    (PR 36: q65's first run on the chip waited 228 s for it); in int32 it is a quarter of
    the lines and seconds.  The guard is on the program's size, which is what the compiler
    was slow over."""
    from trino_tpu.connectors import tpcds

    def generate(lo):
        return tpcds._generate_cols("store_sales", 10.0, lo, PAGE_ROWS,
                                    ("ss_sold_date_sk",), 28_800_000)

    text = _compile(generate, _s(one_chip, (), jnp.int64))
    assert text.count("\n") < 9_000, text.count("\n")
