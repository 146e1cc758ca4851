"""Pallas TPU kernels for the scatter/gather-bound hot loops + the engineering
record of what does and does not belong in Pallas for a SQL engine on TPU.

The reference's native-performance surface is runtime bytecode generation and
Java Vector-API SIMD (SURVEY.md §2: sql/gen/*, simd/BlockEncodingSimdSupport);
the TPU build's equivalents are jit-traced XLA programs plus, where profitable,
hand-written Mosaic kernels.  Round 3 findings (kept below: fused_segment_agg);
round 13 adds the three scatter/gather-bound inner loops as selectable backends
behind the XLA paths (ROADMAP item 2):

1. `fused_segment_agg` computes EVERY accumulator of a <=128-slot
   direct-indexed GROUP BY in one pass (one-hot x values matmul per block,
   grid-accumulated in VMEM).  It compiles and runs at memory bandwidth —
   88us vs XLA's 57us for 8 accumulators: XLA's fusion of the masked-reduce
   form is already optimal, so the engine keeps the XLA path by default and
   this kernel is the documented alternative (`use_pallas=True` kwarg).
2. A VMEM-resident hash table is NOT expressible as direct vector indexing:
   per-element indexing of a ref raises "Cannot do int indexing on TPU", and
   `jnp.take` lowers only for 2D same-lane gathers.  Round 13's answer is to
   RESTATE the probe as a tensor program with no gather at all (the TQP move,
   arxiv 2203.01877): because the double-hash step is ODD and capacities are
   powers of two, the probe round at which row r visits slot s INVERTS in
   closed form — p_r(s) = ((s - h0_r) * stp_r^{-1}) mod C, a few int32 ops —
   so `hash_probe` streams the whole table through VMEM tiles ONCE, compares
   every (row, slot) pair, and min-reduces the candidate rounds.  Hit iff the
   matching slot's round precedes both MAX_PROBES and the nearest EMPTY
   along the chain.  O(rows x capacity) VPU compares replace O(rows x rounds)
   HBM gathers; `PALLAS_TABLE_MAX` caps the capacities admitted and the XLA
   path remains above it (the on-chip A/B against XLA is pending).
3. `hash_insert` keeps the XLA claim protocol's shape (rounds of
   probe/claim/re-check) but runs it block-sequentially over the TPU's
   sequential grid with the table carried in VMEM; slot contention resolves
   by MIN ROW INDEX (deterministic) instead of scatter-min over packed
   words.  The resulting LAYOUT can differ from the XLA table, but both
   protocols preserve the open-addressing chain invariant (a key sits on its
   own probe chain behind no EMPTY slot), so probes against either table
   return identical (row_ids, matched) and aggregation states are
   key-equivalent — parity is defined on those observables, never on raw
   slot order (tests/test_pallas_kernels.py pins both).
4. `compact_rows_matrix` packs masked lanes to the front (the
   filter->compaction step) as a block-local prefix count + one-hot matmul:
   8-bit limbs keep the MXU products exact even at bf16, and the running offset
   rides an SMEM output across the sequential grid.  Integer, bool and f32
   columns ride one [n, limbs] int32 matrix (bitcast outside the kernel);
   DOUBLE columns keep the XLA path (no f64 bitcast on a TPU).
5. Mosaic is 32-bit: under the engine's global x64 session, kernels are
   built inside `with jax.enable_x64(False)` and i64 words are split into
   (hi32, lo32) pairs before entering a kernel
   (`jax.lax.bitcast_convert_type`, element 0 = low word).  The kernel BODY
   is lowered later, when the outer x64 jit lowers, so anything Mosaic
   re-traces then leaks 64-bit types: a reduction straight to a scalar
   (`jnp.sum(v)`, `jnp.all(v)`) is the known case — reduce along an axis or
   to a kept dim and index, and use max/min (no int32->int64 promotion).
6. What the v5e compiler asked for (PR 22, compiled for a described chip,
   tests/test_chip_compile.py): 1-D operands in blocks of the 1-D layout
   tile (1024), dynamic 1-D ref slices with a provable 1024-multiple start
   (`_tile_start`), no bool vectors or whole tables as while_loop carries
   (hash_insert keeps its table in the output refs), and `vma` on
   pallas_call out-shapes under shard_map.

Selection is the single chokepoint `use_pallas()` plus the per-kernel shape
gates (`table_kernels_enabled`, `compact_enabled`): default ON when the
backend is TPU, OFF on CPU (the XLA path is unchanged);
`TRINO_TPU_PALLAS=1/0` forces either way, with `interpret=True` only when the
backend is not TPU, so tier-1 exercises the real kernel bodies on the CPU
mesh.  The env var is read at TRACE time: flipping it in-process requires
fresh executors plus `jax.clear_caches()` (module-level jits like
hashjoin._multi_build_jit bake the choice into their cached executables) —
which is also why there is deliberately NO session property: kernel choice
shapes compiled streams, so any future property variant must ride
`engine._plan_shape_props` (CLAUDE.md round-13 notes).

Precision contract: fused_segment_agg counts accumulate in int32 (exact to
2^31 rows); sums run on the MXU in float32 and are offered for DOUBLE inputs
only.  The round-13 kernels are bit-exact by construction: table words
compare as (hi32, lo32) pairs, compaction moves 8-bit limbs through f32
one-hot matmuls whose products are exact, and every value re-enters the x64
world by bitcast, not conversion.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from .hashing import EMPTY_KEY, probe_step, splitmix64

__all__ = ["fused_segment_agg", "ONEHOT_BLOCK", "use_pallas", "pallas_interpret",
           "force", "table_kernels_enabled", "compact_enabled", "compact_limbs",
           "hash_probe",
           "hash_insert", "compact_rows_matrix", "compact_columns",
           "PALLAS_TABLE_MAX", "PROBE_BLOCK", "INSERT_BLOCK", "COMPACT_BLOCK",
           "TABLE_TILE", "COMPACT_OUT_MAX", "COMPACT_LIMBS_MAX", "MAX_PROBES"]

ONEHOT_BLOCK = 2048

MAX_PROBES = 64  # must match ops/hashjoin.py / ops/hashagg.py

# Admitted shapes = what Mosaic compiled for a described v5e (PR 22;
# tests/test_chip_compile.py compiles both ends of every range).
# hash_probe/hash_insert pay O(rows x capacity) VPU compares for the
# gather-free formulation: past 64K slots the table scan is not expected to
# beat XLA's HBM gathers, and the VMEM-resident table (3-4 int32 arrays) stops
# fitting comfortably anyway.  compact's packed output block stays
# VMEM-resident across the grid with its limb dim padded to one 128-lane tile:
# 32K rows (~17 MB) compiled, 128K rows ran out of VMEM, and more than 128
# limbs turns the running-offset store into an unprovable sublane slice.
PALLAS_TABLE_MAX = 1 << 16
COMPACT_OUT_MAX = 1 << 15
COMPACT_LIMBS_MAX = 128

# Row blocks and the table tile are the 1-D int32 layout tile (8 x 128): XLA
# hands Mosaic 1-D operands tiled T(1024), and a smaller block is refused
# ("XLA layout does not match Mosaic layout").
PROBE_BLOCK = 1024
INSERT_BLOCK = 1024
COMPACT_BLOCK = 1024
TABLE_TILE = 1024

_FORCE: bool | None = None  # tests' override; trace-time, like the env


def force(mode: bool | None) -> None:
    """Test override for `use_pallas()` (None = back to env/backend).
    TRACE-time only: never flip it across calls of one jitted callable —
    build a fresh jit per mode or `jax.clear_caches()` first
    (tests/test_pallas_kernels.py)."""
    global _FORCE
    _FORCE = mode


def use_pallas() -> bool:
    """THE backend-selection chokepoint (read at trace time)."""
    if _FORCE is not None:
        return _FORCE
    env = os.environ.get("TRINO_TPU_PALLAS")
    if env not in (None, ""):
        return env not in ("0", "false", "off")
    return jax.default_backend() == "tpu"


def pallas_interpret() -> bool:
    """Interpret mode whenever the backend cannot compile Mosaic: the CPU
    mesh runs the REAL kernel bodies through the Pallas interpreter, which is
    what makes the parity tests tier-1 instead of device-only."""
    return jax.default_backend() != "tpu"


def table_kernels_enabled(capacity: int) -> bool:
    """Gate for hash_probe/hash_insert at a static table capacity."""
    return use_pallas() and 2 <= capacity <= PALLAS_TABLE_MAX


def compact_limbs(cols) -> int:
    """int32 limbs one row occupies in compact_columns' [n, limbs] matrix."""
    return sum(2 if c.dtype.itemsize == 8 else 1 for c in cols) if cols else 1


def compact_enabled(n_rows: int, out_len: int, cols) -> bool:
    """Gate for compact_columns over ``cols`` — THE shared definition for
    every caller (arrays.compact_rows, exchange.bucketize).  DOUBLE columns
    stay on the XLA path: a TPU holds f64 as an f32 pair, and XLA's X64
    rewrite has no bitcast to int32 limbs for it."""
    return (use_pallas() and n_rows >= 1 and out_len <= COMPACT_OUT_MAX
            and compact_limbs(cols) <= COMPACT_LIMBS_MAX
            and not any(c.dtype == jnp.float64 for c in cols))


# ------------------------------------------------------------------ 32-bit prep
# Mosaic is 32-bit; every 64-bit word crosses the kernel boundary as a
# (hi32, lo32) pair via bitcast (element 0 = low word), never by conversion.

# int64-max sentinel split into int32 words (plain python ints: importing
# this module builds no device array)
_EMPTY_HI32 = (1 << 31) - 1
_EMPTY_LO32 = -1


def _split32(x):
    """int64 [n] -> (hi, lo) int32 pair."""
    w = jax.lax.bitcast_convert_type(x, jnp.int32)
    return w[..., 1], w[..., 0]


def _lo32(x):
    return jax.lax.bitcast_convert_type(x, jnp.int32)[..., 0]


def _combine64(hi, lo):
    return jax.lax.bitcast_convert_type(jnp.stack([lo, hi], axis=-1), jnp.int64)


def _modinv_odd32(a):
    """Inverse of an odd int32 word mod 2^32 (Newton; 3->6->12->24->48 bits).
    probe_step() forces the double-hash step odd exactly so this exists."""
    x = a
    for _ in range(5):
        x = x * (2 - a * x)
    return x


def _pad_to(block, *arrays):
    n = arrays[0].shape[0]
    pad = (-n) % block
    if not pad:
        return arrays
    return tuple(jnp.concatenate([a, jnp.zeros((pad,) + a.shape[1:], a.dtype)])
                 for a in arrays)


def _tile_loop(n_tiles: int, body, init):
    """int32-explicit counted loop for KERNEL bodies.  lax.fori_loop is a trap
    here: interpret-mode kernels re-trace at LOWERING time, outside the
    `jax.enable_x64(False)` scope, so fori's weak python-int bound/increment
    constants materialize as i64 against an i32 induction variable and MLIR
    verification fails ("op requires the same element type").  Every loop
    constant below carries an explicit dtype, which is phase-robust."""

    def cond(c):
        return c[0] < jnp.int32(n_tiles)

    def step(c):
        t, carry = c
        return (t + jnp.int32(1), body(t, carry))

    return jax.lax.while_loop(cond, step, (jnp.int32(0), init))[1]


def _out_struct(shape, like):
    """pallas_call out_shape entry that carries the operands' varying mesh
    axes: under shard_map (check_vma) an output declared without ``vma``
    while the inputs vary per worker is rejected at trace time."""
    vma = frozenset().union(*(jax.typeof(a).vma for a in like))
    return jax.ShapeDtypeStruct(shape, jnp.int32, vma=vma)


def _tile_start(t, tile: int, n_tiles: int):
    """Start index of table tile ``t`` for a ref slice.  Mosaic loads a
    dynamic 1-D slice only when it can PROVE the start is a multiple of the
    1-D int32 tile (1024 lanes), hence TABLE_TILE=1024 plus the hint; a table
    that fits one tile is read at a STATIC zero."""
    from jax.experimental import pallas as pl

    if n_tiles == 1:
        return 0
    return pl.multiple_of(t * jnp.int32(tile), tile)


# ------------------------------------------------------------------- hash probe
@functools.partial(jax.jit, static_argnames=("max_probes", "interpret"))  # compile-ok: module-level Pallas kernel entry; dispatched inside exec's _jit step fns
def hash_probe(table, vals, packed, h0, stp, valid, max_probes: int = MAX_PROBES,
               interpret: bool | None = None):
    """Open-addressed probe as a gather-free tensor program.

    table:  [C] int64 packed keys (the [:capacity] slice, pow2 C)
    vals:   [C] int32 per-slot payload (rows for probe(), iota for
            probe_slots()) — the matching slot's value returns in-pass
    packed/h0/stp: [n] int64 per-row key word, splitmix64 hash, odd step
    valid:  [n] bool
    returns (vals[match_slot] | 0, matched) — bit-identical to the XLA
    while_loop probe over the same table.

    Inner loop: stream table tiles through VMEM; for every (row, slot) pair
    recover the probe round p = ((s - h0) * stp^-1) & (C-1) and min-reduce
    the rounds of key-matching and EMPTY slots; a row matches iff its hit
    round precedes both the nearest EMPTY and max_probes.  Work is
    O(n x C) int32 VPU ops with zero gathers — see module docstring for the
    crossover cap."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = pallas_interpret()
    C = table.shape[0]
    n = packed.shape[0]
    T = min(TABLE_TILE, C)
    B = PROBE_BLOCK
    th, tl = _split32(table)
    ph, plo = _split32(packed)
    h0lo = _lo32(h0)
    inv = _modinv_odd32(_lo32(stp))
    ph, plo, h0lo, inv, valid = _pad_to(B, ph, plo, h0lo, inv, valid)

    def kernel(th_ref, tl_ref, tv_ref, h0_ref, inv_ref, ph_ref, plo_ref, v_ref,
               val_ref, m_ref):
        rh0 = h0_ref[...]
        rinv = inv_ref[...]
        rph = ph_ref[...]
        rplo = plo_ref[...]
        cmask = jnp.int32(C - 1)
        big = jnp.int32(2**31 - 1)

        def tile(t, carry):
            hitp, emptyp, val = carry
            s0 = _tile_start(t, T, C // T)
            tth = th_ref[pl.ds(s0, T)]
            ttl = tl_ref[pl.ds(s0, T)]
            ttv = tv_ref[pl.ds(s0, T)]
            svec = s0 + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
            p_rs = ((svec - rh0[:, None]) * rinv[:, None]) & cmask
            match = (tth[None, :] == rph[:, None]) & (ttl[None, :] == rplo[:, None])
            empty = (tth == jnp.int32(_EMPTY_HI32)) & (ttl == jnp.int32(_EMPTY_LO32))
            hitp = jnp.minimum(hitp, jnp.min(jnp.where(match, p_rs, big), axis=1))
            emptyp = jnp.minimum(
                emptyp, jnp.min(jnp.where(empty[None, :], p_rs, big), axis=1))
            val = val + jnp.sum(jnp.where(match, ttv[None, :], jnp.int32(0)), axis=1)
            return hitp, emptyp, val

        init = (jnp.full((B,), big, jnp.int32), jnp.full((B,), big, jnp.int32),
                jnp.zeros((B,), jnp.int32))
        hitp, emptyp, val = _tile_loop(C // T, tile, init)
        matched = v_ref[...] & (hitp < jnp.int32(max_probes)) & (hitp < emptyp)
        m_ref[...] = matched.astype(jnp.int32)
        val_ref[...] = jnp.where(matched, val, jnp.int32(0))

    operands = (th, tl, vals.astype(jnp.int32), h0lo, inv, ph, plo, valid)
    with jax.enable_x64(False):
        val, matched = pl.pallas_call(
            kernel,
            grid=(ph.shape[0] // B,),
            in_specs=[
                pl.BlockSpec((C,), lambda i: (0,), memory_space=pltpu.VMEM),
                pl.BlockSpec((C,), lambda i: (0,), memory_space=pltpu.VMEM),
                pl.BlockSpec((C,), lambda i: (0,), memory_space=pltpu.VMEM),
                pl.BlockSpec((B,), lambda i: (i,), memory_space=pltpu.VMEM),
                pl.BlockSpec((B,), lambda i: (i,), memory_space=pltpu.VMEM),
                pl.BlockSpec((B,), lambda i: (i,), memory_space=pltpu.VMEM),
                pl.BlockSpec((B,), lambda i: (i,), memory_space=pltpu.VMEM),
                pl.BlockSpec((B,), lambda i: (i,), memory_space=pltpu.VMEM),
            ],
            out_specs=(
                pl.BlockSpec((B,), lambda i: (i,), memory_space=pltpu.VMEM),
                pl.BlockSpec((B,), lambda i: (i,), memory_space=pltpu.VMEM),
            ),
            out_shape=(
                _out_struct((ph.shape[0],), operands),
                _out_struct((ph.shape[0],), operands),
            ),
            interpret=interpret,
        )(*operands)
    return val[:n], matched[:n] != 0


# ------------------------------------------------------------------ hash insert
@functools.partial(jax.jit, static_argnames=("max_probes", "interpret"))  # compile-ok: module-level Pallas kernel entry; dispatched inside exec's _jit step fns
def hash_insert(table, packed, valid, max_probes: int = MAX_PROBES,
                interpret: bool | None = None):
    """CAS-style claim loop for open-addressing insertion, in-kernel.

    table: [C+1] int64 (sink last), packed/valid per row.  Returns
    (table', slot[int32], placed[bool]) — the same contract as
    hashagg._probe_insert.  Row blocks advance through the TPU's SEQUENTIAL
    grid with the table carried in VMEM; per block the XLA protocol's rounds
    run to completion (probe -> claim EMPTY by min row index -> re-check the
    claimed word) before the next block starts.  Claim order therefore
    differs from the XLA scatter-min protocol and the slot LAYOUT may too —
    both keep the chain invariant, so the tables are probe-equivalent (see
    module docstring; parity is pinned on observables)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = pallas_interpret()
    C = table.shape[0] - 1
    n = packed.shape[0]
    T = min(TABLE_TILE, C)
    B = INSERT_BLOCK
    h0 = splitmix64(packed)
    stp = probe_step(h0)
    th0, tl0 = _split32(table[:C])
    ph, plo = _split32(packed)
    h0lo = _lo32(h0)
    stplo = _lo32(stp)
    ph, plo, h0lo, stplo, valid_p = _pad_to(B, ph, plo, h0lo, stplo, valid)

    def kernel(th_in, tl_in, ph_ref, plo_ref, h0_ref, stp_ref, v_ref,
               th_out, tl_out, slot_ref, placed_ref):
        i = pl.program_id(0)

        @pl.when(i == jnp.int32(0))
        def _():
            th_out[...] = th_in[...]
            tl_out[...] = tl_in[...]

        rph = ph_ref[...]
        rplo = plo_ref[...]
        rh0 = h0_ref[...]
        rstp = stp_ref[...]
        v = v_ref[...]
        cmask = jnp.int32(C - 1)
        bigr = jnp.int32(2**31 - 1)
        rloc = jax.lax.broadcasted_iota(jnp.int32, (B, 1), 0)[:, 0]
        n_tiles = C // T

        def gather(idx):
            def tile(t, cur):
                ch, cl = cur
                s0 = _tile_start(t, T, n_tiles)
                tth = th_out[pl.ds(s0, T)]
                ttl = tl_out[pl.ds(s0, T)]
                svec = s0 + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
                m = idx[:, None] == svec
                ch = ch + jnp.sum(jnp.where(m, tth[None, :], jnp.int32(0)), axis=1)
                cl = cl + jnp.sum(jnp.where(m, ttl[None, :], jnp.int32(0)), axis=1)
                return ch, cl

            z = jnp.zeros((B,), jnp.int32)
            return _tile_loop(n_tiles, tile, (z, z))

        def cond(carry):
            p, placed_i, _ = carry
            # (bool vectors do not legalize as scf.while carries: int32 0/1)
            placed = placed_i != jnp.int32(0)
            # a reduction straight to a scalar goes through a Mosaic proxy
            # that is re-traced at LOWERING time, under the engine's x64, and
            # leaks 64-bit types into the kernel: keep one dim, then index
            unplaced = jnp.max(jnp.where(placed, jnp.int32(0), jnp.int32(1)),
                               keepdims=True)[0]
            return (p < jnp.int32(max_probes)) & (unplaced > jnp.int32(0))

        def body(carry):
            p, placed_i, slot = carry
            placed = placed_i != jnp.int32(0)
            idx = (rh0 + p * rstp) & cmask
            ch, cl = gather(idx)
            hit = (ch == rph) & (cl == rplo) & ~placed
            slot = jnp.where(hit, idx, slot)
            placed = placed | hit
            contend = ((ch == jnp.int32(_EMPTY_HI32))
                       & (cl == jnp.int32(_EMPTY_LO32)) & ~placed)

            def claim(t, carry2):
                c2h, c2l = carry2
                s0 = _tile_start(t, T, n_tiles)
                tth = th_out[pl.ds(s0, T)]
                ttl = tl_out[pl.ds(s0, T)]
                svec = s0 + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
                hits_t = idx[:, None] == svec
                m = hits_t & contend[:, None]
                win = jnp.min(jnp.where(m, rloc[:, None], bigr), axis=0)
                claimed = win < bigr
                wonrow = m & (rloc[:, None] == win[None, :])
                wph = jnp.sum(jnp.where(wonrow, rph[:, None], jnp.int32(0)), axis=0)
                wpl = jnp.sum(jnp.where(wonrow, rplo[:, None], jnp.int32(0)), axis=0)
                nth = jnp.where(claimed, wph, tth)
                ntl = jnp.where(claimed, wpl, ttl)
                th_out[pl.ds(s0, T)] = nth
                tl_out[pl.ds(s0, T)] = ntl
                c2h = c2h + jnp.sum(jnp.where(hits_t, nth[None, :], jnp.int32(0)), axis=1)
                c2l = c2l + jnp.sum(jnp.where(hits_t, ntl[None, :], jnp.int32(0)), axis=1)
                return c2h, c2l

            z = jnp.zeros((B,), jnp.int32)
            c2h, c2l = _tile_loop(n_tiles, claim, (z, z))
            won = contend & (c2h == rph) & (c2l == rplo)
            slot = jnp.where(won, idx, slot)
            placed = placed | won
            return p + jnp.int32(1), placed.astype(jnp.int32), slot

        init = (jnp.int32(0), (~v).astype(jnp.int32),
                jnp.full((B,), C, jnp.int32))
        _, placed, slot = jax.lax.while_loop(cond, body, init)
        slot_ref[...] = slot
        placed_ref[...] = placed

    operands = (th0, tl0, ph, plo, h0lo, stplo, valid_p)
    with jax.enable_x64(False):
        th2, tl2, slot, placed = pl.pallas_call(
            kernel,
            grid=(ph.shape[0] // B,),
            in_specs=[
                pl.BlockSpec((C,), lambda i: (0,), memory_space=pltpu.VMEM),
                pl.BlockSpec((C,), lambda i: (0,), memory_space=pltpu.VMEM),
                pl.BlockSpec((B,), lambda i: (i,), memory_space=pltpu.VMEM),
                pl.BlockSpec((B,), lambda i: (i,), memory_space=pltpu.VMEM),
                pl.BlockSpec((B,), lambda i: (i,), memory_space=pltpu.VMEM),
                pl.BlockSpec((B,), lambda i: (i,), memory_space=pltpu.VMEM),
                pl.BlockSpec((B,), lambda i: (i,), memory_space=pltpu.VMEM),
            ],
            out_specs=(
                pl.BlockSpec((C,), lambda i: (0,), memory_space=pltpu.VMEM),
                pl.BlockSpec((C,), lambda i: (0,), memory_space=pltpu.VMEM),
                pl.BlockSpec((B,), lambda i: (i,), memory_space=pltpu.VMEM),
                pl.BlockSpec((B,), lambda i: (i,), memory_space=pltpu.VMEM),
            ),
            out_shape=(
                _out_struct((C,), operands),
                _out_struct((C,), operands),
                _out_struct((ph.shape[0],), operands),
                _out_struct((ph.shape[0],), operands),
            ),
            interpret=interpret,
        )(*operands)
    # sink word derives from the INPUT table (x*0 + sentinel), not a fresh
    # constant: under shard_map a fresh constant is "unvarying" while the
    # table is per-worker — the round-5 varying-axis seeding rule
    sink = table[C:] * 0 + EMPTY_KEY
    new_table = jnp.concatenate([_combine64(th2, tl2), sink])
    return new_table, slot[:n], placed[:n] != 0


# -------------------------------------------------------------- compaction pack
@functools.partial(jax.jit, static_argnames=("out_len", "interpret"))  # compile-ok: module-level Pallas kernel entry; dispatched inside exec's _jit step fns
def compact_rows_matrix(mat, valid, out_len: int, interpret: bool | None = None):
    """Order-preserving masked-lane pack: [n, L] int32 -> [out_len, L].

    Block-local prefix count (masked row-sum over a lower-triangular
    pattern) places each live row; values move through a
    [block, block] one-hot matmul over 8-bit limbs (exact products); the
    running output offset rides an SMEM output across the sequential grid.
    Rows past ``out_len`` drop into a write-and-discard pad zone — the same
    semantics as the XLA path's dropped overflow lanes.  Returns
    (packed, total_live_count)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = pallas_interpret()
    n, L = mat.shape
    B = COMPACT_BLOCK
    mat, valid = _pad_to(B, mat, valid)

    def kernel(v_ref, m_ref, out_ref, off_ref):
        i = pl.program_id(0)

        @pl.when(i == jnp.int32(0))
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)
            off_ref[0] = jnp.int32(0)

        v = v_ref[...]
        # inclusive prefix count as a masked row-sum (VPU): a tri @ v matmul
        # would pair an unvarying constant with per-worker data, which
        # dot_general refuses under shard_map's check_vma
        tri = (jax.lax.broadcasted_iota(jnp.int32, (B, B), 0)
               >= jax.lax.broadcasted_iota(jnp.int32, (B, B), 1))
        pos = jnp.sum(jnp.where(tri, v.astype(jnp.int32)[None, :], jnp.int32(0)),
                      axis=1) - jnp.int32(1)
        dst = jnp.where(v, pos, jnp.int32(B))
        j = jax.lax.broadcasted_iota(jnp.int32, (B, 1), 0)[:, 0]
        onehot = (dst[None, :] == j[:, None]).astype(jnp.float32)  # [out, in]
        m = m_ref[...]
        # 8-bit limbs: Mosaic's default contraction feeds the MXU bf16 (8
        # mantissa bits), which holds 0..255 and the 0/1 one-hot exactly; each
        # output sums ONE nonzero product in f32, so every byte moves exactly.
        # (fp32 contraction of 16-bit limbs ran out of VMEM at this block.)
        pk = jnp.zeros(m.shape, jnp.int32)
        for shift in (0, 8, 16, 24):
            limb = ((m >> jnp.int32(shift)) & jnp.int32(0xFF)).astype(jnp.float32)
            moved = jax.lax.dot_general(
                onehot, limb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).astype(jnp.int32)
            pk = pk | (moved << jnp.int32(shift))
        start = jnp.minimum(off_ref[0], jnp.int32(out_len))
        out_ref[pl.ds(start, B), :] = pk
        # live count = the last live row's prefix count; as a max to a kept
        # dim (a jnp.sum straight to a scalar leaks int64, module docstring)
        live = jnp.max(jnp.where(v, pos + jnp.int32(1), jnp.int32(0)),
                       keepdims=True)[0]
        off_ref[0] = off_ref[0] + live

    with jax.enable_x64(False):
        out, off = pl.pallas_call(
            kernel,
            grid=(mat.shape[0] // B,),
            in_specs=[
                pl.BlockSpec((B,), lambda i: (i,), memory_space=pltpu.VMEM),
                pl.BlockSpec((B, L), lambda i: (i, 0), memory_space=pltpu.VMEM),
            ],
            out_specs=(
                pl.BlockSpec((out_len + B, L), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1,), lambda i: (0,), memory_space=pltpu.SMEM),
            ),
            out_shape=(
                _out_struct((out_len + B, L), (valid, mat)),
                _out_struct((1,), (valid, mat)),
            ),
            interpret=interpret,
        )(valid, mat)
    return out[:out_len], off[0]


def compact_columns(cols, valid, out_len: int, interpret: bool | None = None):
    """Dtype-generic wrapper over compact_rows_matrix: every column rides the
    one [n, limbs] int32 matrix (64-bit and f32 words by bitcast — exact —
    bool/narrow ints by widening), one kernel launch for the whole page.
    Returns (packed column tuple, total live count)."""
    parts, specs = [], []
    for a in cols:
        d = a.dtype
        if d == jnp.bool_:
            parts.append(a.astype(jnp.int32)[:, None])
            specs.append((d, 1))
        elif d.itemsize == 8:
            parts.append(jax.lax.bitcast_convert_type(a, jnp.int32))
            specs.append((d, 2))
        elif d.itemsize == 4:
            parts.append(jax.lax.bitcast_convert_type(a, jnp.int32)[:, None])
            specs.append((d, 1))
        else:  # int8/int16: widen exactly, narrow back after
            parts.append(a.astype(jnp.int32)[:, None])
            specs.append((d, 1))
    mat = jnp.concatenate(parts, axis=1)
    packed, total = compact_rows_matrix(mat, valid, out_len, interpret=interpret)
    outs, o = [], 0
    for d, w in specs:
        seg = packed[:, o:o + w]
        o += w
        if d == jnp.bool_:
            outs.append(seg[:, 0] != 0)
        elif d.itemsize == 8:
            outs.append(jax.lax.bitcast_convert_type(seg, d))
        elif d.itemsize == 4:
            outs.append(jax.lax.bitcast_convert_type(seg[:, 0], d))
        else:
            outs.append(seg[:, 0].astype(d))
    return tuple(outs), total


# --------------------------------------------------------- fused segment agg
@functools.partial(jax.jit, static_argnames=("n_slots", "interpret"))  # compile-ok: module-level Pallas kernel entry; dispatched inside exec's _jit step fns
def fused_segment_agg(slot, valid, value_cols, n_slots: int, interpret: bool = False):
    """All-in-one-pass segment aggregation for a direct-indexed group-by.

    slot:   [n] int32 group slot per row (< n_slots <= 128)
    valid:  [n] bool live-row mask
    value_cols: tuple of [n] float arrays (cast to f32 on entry)
    returns ([n_slots] int32 counts, tuple of [n_slots] f32 sums)

    One onehot^T @ values matmul per block on the MXU, accumulated across the
    sequential TPU grid in VMEM (reference analog: a GroupedAggregator applying
    every accumulator during one page pass,
    operator/aggregation/GroupedAggregator.java).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = slot.shape[0]
    k = len(value_cols)
    blk = min(ONEHOT_BLOCK, max(n, 8))
    pad = (-n) % blk
    if pad:
        slot = jnp.concatenate([slot, jnp.zeros((pad,), jnp.int32)])
        valid = jnp.concatenate([valid, jnp.zeros((pad,), bool)])
        value_cols = tuple(jnp.concatenate([v, jnp.zeros((pad,), v.dtype)])
                           for v in value_cols)
    vmat = (jnp.stack([v.astype(jnp.float32) for v in value_cols], axis=1)
            if k else jnp.zeros((slot.shape[0], 1), jnp.float32))

    def kernel(slot_ref, valid_ref, val_ref, cnt_ref, sum_ref):
        i = pl.program_id(0)
        s = slot_ref[...]
        # Mosaic constraint: minor-dim insertion ([:, None]) needs 32-bit types,
        # so the bool mask becomes f32 before broadcasting
        livef = valid_ref[...].astype(jnp.float32)
        lanes = jax.lax.broadcasted_iota(jnp.int32, (blk, n_slots), 1)
        onehot = (s[:, None] == lanes).astype(jnp.float32) * livef[:, None]

        @pl.when(i == 0)
        def _():
            cnt_ref[...] = jnp.zeros_like(cnt_ref)
            sum_ref[...] = jnp.zeros_like(sum_ref)

        # per-block count <= blk: exact in f32, accumulated exactly in i32
        cnt_ref[...] += jnp.sum(onehot, axis=0).astype(jnp.int32)[None, :]
        part = jax.lax.dot_general(
            onehot, val_ref[...],
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        sum_ref[...] += part

    with jax.enable_x64(False):
        counts, sums = pl.pallas_call(
            kernel,
            grid=(slot.shape[0] // blk,),
            in_specs=[
                pl.BlockSpec((blk,), lambda i: (i,), memory_space=pltpu.VMEM),
                pl.BlockSpec((blk,), lambda i: (i,), memory_space=pltpu.VMEM),
                pl.BlockSpec((blk, max(k, 1)), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=(
                pl.BlockSpec((1, n_slots), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((n_slots, max(k, 1)), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
            ),
            out_shape=(
                jax.ShapeDtypeStruct((1, n_slots), jnp.int32),
                jax.ShapeDtypeStruct((n_slots, max(k, 1)), jnp.float32),
            ),
            interpret=interpret,
        )(slot.astype(jnp.int32), valid, vmat)
    return counts[0], tuple(sums[:, j] for j in range(k))
