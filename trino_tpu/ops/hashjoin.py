"""Hash join build + probe kernels.

Reference: HashBuilderOperator builds a PagesIndex + open-addressing JoinHash
(operator/join/spilling/HashBuilderOperator.java:68, join/JoinHash.java:28,
join/DefaultPagesHash.java:159-197 — note its batch probe getAddressIndex(int[],Page,long[])
is already vectorized in spirit); LookupJoinOperator probes per page
(join/spilling/LookupJoinOperator.java:43, JoinProbe.advanceNextPosition:76).

TPU re-design:
- build side is a fixed-capacity int64 table of packed keys (ops/hashing.pack_keys) claimed
  with the same deterministic scatter-min protocol as hashagg; a parallel ``rows`` array maps
  slot -> build row index;
- probe is gather-only (no scatter): MAX_PROBES rounds of table lookup inside one jitted
  kernel, whole page at a time — the batch analog of DefaultPagesHash.getAddressIndex;
- build columns stay as device arrays; matches gather them by row id (the PagesIndex analog);
- duplicate build keys are detected at build time (``dup_count > 0``); the executor falls
  back to an expanding multi-match strategy for those (reference handles them via position
  links, join/PositionLinks.java — our equivalent is planned: sorted multi-probe).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .arrays import gather_rows, live_indices
from .hashing import (EMPTY_KEY, ceil_pow2, pack_keys, probe_step, probe_widths,
                      splitmix64)

__all__ = ["JoinTable", "build_table_init", "build_insert", "probe", "probe_counted",
           "probe_widths", "MAX_PROBES",
           "MultiJoinTable", "multi_build", "probe_slots", "expand_counts",
           "DirectJoinTable", "direct_build", "direct_match", "direct_probe",
           "DirectMultiJoinTable",
           "direct_multi_build", "direct_probe_slots", "DIRECT_JOIN_RANGE_MAX"]

MAX_PROBES = 64


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class JoinTable:
    table: jnp.ndarray  # [capacity+1] packed keys
    rows: jnp.ndarray  # [capacity+1] int32 build row index per slot
    build_columns: tuple  # full build-side columns (device)
    build_null_masks: tuple
    n_build_rows: jnp.ndarray  # int32 scalar
    dup_count: jnp.ndarray  # int32 scalar: valid build rows minus occupied slots
    overflow: jnp.ndarray  # bool scalar

    def tree_flatten(self):
        return (
            (self.table, self.rows, self.build_columns, self.build_null_masks,
             self.n_build_rows, self.dup_count, self.overflow),
            None,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def capacity(self):
        return self.table.shape[0] - 1


def build_table_init(capacity: int, build_page) -> JoinTable:
    capacity = ceil_pow2(capacity)  # double-hash coverage needs a pow2 table
    return JoinTable(
        table=jnp.full((capacity + 1,), EMPTY_KEY, jnp.int64),
        rows=jnp.full((capacity + 1,), 2**31 - 1, jnp.int32),  # min-claim: first row wins
        build_columns=build_page.columns,
        build_null_masks=build_page.null_masks,
        n_build_rows=jnp.zeros((), jnp.int32),
        dup_count=jnp.zeros((), jnp.int32),
        overflow=jnp.zeros((), bool),
    )


def build_insert(jt: JoinTable, key_cols, key_types, valid) -> JoinTable:
    """Insert build rows (SQL join keys are never NULL-matching: rows with NULL keys are
    dropped by the caller via ``valid``)."""
    from .hashagg import _probe_insert

    packed, _ = pack_keys(key_cols, key_types)
    packed = jnp.where(valid, packed, EMPTY_KEY - 1)
    table, slot, placed, _ = _probe_insert(jt.table, packed, valid)
    live = valid & placed
    C = jt.capacity
    row_idx = jnp.arange(packed.shape[0], dtype=jnp.int32)
    sidx = jnp.where(live, slot, C).astype(jnp.int32)
    # min: first build row wins deterministically for duplicate keys
    rows = jt.rows.at[sidx].min(jnp.where(live, row_idx, jnp.int32(2**31 - 1)))
    rows = rows.at[C].set(0)
    n_valid = jnp.sum(valid, dtype=jnp.int32)
    occupied = jnp.sum(table[:C] != EMPTY_KEY, dtype=jnp.int32)
    return JoinTable(
        table=table,
        rows=rows,
        build_columns=jt.build_columns,
        build_null_masks=jt.build_null_masks,
        n_build_rows=jt.n_build_rows + n_valid,
        dup_count=jt.n_build_rows + n_valid - occupied,
        overflow=jt.overflow | jnp.any(valid & ~placed),
    )


def _find_slots(table, packed, valid, p=0, widths=None):
    """(slot, matched, rounds): the gather-only open-addressing lookup of
    ``packed`` in ``table`` ([C+1] packed keys, C a power of two), the ONE loop
    of `probe` and `probe_slots`.  ``slot`` is 0 where nothing matched;
    ``rounds`` int32[len(widths)] the rounds run at each of ``widths``
    (`probe_widths` of the lanes), from round ``p`` on."""
    C = table.shape[0] - 1
    n = packed.shape[0]
    if widths is None:
        widths = probe_widths(n)
    h0 = splitmix64(packed)
    stp = probe_step(h0)
    # derive the loop carries from BOTH operands' varying axes: under
    # shard_map, fresh constants are "unvarying" and the while_loop rejects a
    # carry the body mixes with per-worker data.  Keys alone are not enough —
    # a CONSTANT join key (select 1 k ... join ... on l.k = n.k) folds to an
    # unvarying array while the TABLE is still per-worker, so the zero must
    # also touch the table (caught by the r05 AddExchanges distribution flip).
    vzero = (h0 * 0).astype(jnp.int32) \
        + (table[jnp.zeros((), jnp.int32)] * 0).astype(jnp.int32) \
        + (valid.astype(jnp.int32) * 0)
    slot = vzero
    matched = (valid & False) | (vzero != 0)
    done = ~valid | (vzero != 0)
    leave = widths[1] if len(widths) > 1 else 0

    # the loop counts its OWN rounds from a constant 0 and adds the rounds run
    # before it (``p``, a device scalar it does not carry): a carried round
    # that starts at a traced value cost the v5e compiler 84 s more at 8.4 M
    # lanes than one that starts at 0 (PERF.md section 6, PR 37)
    def cond(carry):
        q, slot, matched, done = carry
        return (p + q < MAX_PROBES) & (jnp.sum(~done, dtype=jnp.int32) > leave)

    def body(carry):
        q, slot, matched, done = carry
        idx = ((h0 + (p + q) * stp) & (C - 1)).astype(jnp.int32)
        cur = table[idx]
        hit = (cur == packed) & ~done
        slot = jnp.where(hit, idx, slot)
        matched = matched | hit
        done = done | hit | (cur == EMPTY_KEY)
        return q + 1, slot, matched, done

    q, slot, matched, done = jax.lax.while_loop(
        cond, body, (jnp.zeros((), jnp.int32), slot, matched, done))
    rounds = q[None]
    if len(widths) == 1:
        return slot, matched, rounds
    # pack what is unfinished (at most ``leave`` lanes, unless the loop left at
    # MAX_PROBES: then the narrower levels run no round and every lane stays
    # unmatched, as it always did) and finish it there.  Only the keys move:
    # an unfinished lane has hit nothing yet, and its hash is two multiplies
    idx, count = live_indices(~done, leave)
    packed_lane = jax.lax.iota(jnp.int32, leave)
    nslot, nmatched, nrounds = _find_slots(
        table, gather_rows(packed, idx), packed_lane < count, p + q, widths[1:])
    # hand back: ONE scatter of ``leave`` answers to the lanes they came from
    # (ascending and distinct; the filler lanes of ``idx`` are sent out of
    # bounds, each to a place of its own, and dropped).  On a v5e it beat a
    # gather of all n lanes through the prefix count of the unfinished at
    # both widths tried (PERF.md section 6, PR 37)
    found = jnp.full((n,), -1, jnp.int32).at[
        jnp.where(packed_lane < count, idx, n + packed_lane)].set(
        jnp.where(nmatched, nslot, -1), mode="drop",
        indices_are_sorted=True, unique_indices=True)
    hit = found >= 0
    return (jnp.where(hit, found, slot), matched | hit,
            jnp.concatenate([rounds, nrounds]))


def probe_counted(jt: JoinTable, key_cols, key_types, valid):
    """`probe`, and the rounds its lookup ran: (build_row_ids[int32],
    matched[bool], rounds int32[len(probe_widths(lanes))]).  The rounds of the
    Pallas kernel (it streams the table once, it has none) read 0."""
    from . import pallas_kernels as pk

    packed, _ = pack_keys(key_cols, key_types)
    C = jt.capacity
    if pk.table_kernels_enabled(C) and packed.shape[0]:
        h0 = splitmix64(packed)
        return pk.hash_probe(jt.table[:C], jt.rows[:C], packed, h0,
                             probe_step(h0), valid, max_probes=MAX_PROBES) \
            + (jnp.zeros((len(probe_widths(packed.shape[0])),), jnp.int32),)
    # the lookup carries the SLOT of a hit and ``rows`` is gathered once after
    # it: one gather over every lane a probe round, not two
    slot, matched, rounds = _find_slots(jt.table, packed, valid)
    return jnp.where(matched, jt.rows[slot], 0), matched, rounds


def probe(jt: JoinTable, key_cols, key_types, valid):
    """Gather-only probe: returns (build_row_ids[int32], matched[bool]) per probe row.

    Backend selection (round 13): small/medium tables route to the Pallas
    tensor-program probe (`pallas_kernels.hash_probe` — same hash family,
    same probe order, bit-identical outputs); the XLA lookup (`_find_slots`)
    is the fallback and the only path above `PALLAS_TABLE_MAX`.  The choice is
    trace-time static (capacity is a shape), so compiled streams bake it in."""
    return probe_counted(jt, key_cols, key_types, valid)[:2]


# ---------------------------------------------------------------------------- direct index
# Dense single-key joins (TPC-H joins are mostly PK-FK on dense integer keys):
# slot = key - lo, no hashing, no probe rounds — build is one scatter, probe is one
# gather.  The analog of the reference's array-based lookup when join keys are
# small integers (BigintGroupByHash / direct PagesHash addressing ideas applied to
# joins; reference hashes always, we exploit the static key range instead).

DIRECT_JOIN_RANGE_MAX = 1 << 26  # <= 64M slots (256MB of int32 rows)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DirectJoinTable:
    """Unique-key direct-address join table: rows[key - lo] = build row id."""

    rows: jnp.ndarray  # [R+1] int32 build row per slot (min-claim)
    occ: jnp.ndarray  # [R+1] bool
    build_columns: tuple
    build_null_masks: tuple
    dup_count: jnp.ndarray  # int32 scalar
    lo: int  # static

    def tree_flatten(self):
        return ((self.rows, self.occ, self.build_columns, self.build_null_masks,
                 self.dup_count), self.lo)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, lo=aux)


def direct_build(lo: int, span: int, build_page, key_channel: int) -> DirectJoinTable:
    """span = hi - lo + 1 slots; rows outside [lo, hi] cannot exist (lo/hi measured
    from the build page itself)."""
    key = build_page.columns[key_channel]
    valid = build_page.valid_mask()
    nm = build_page.null_masks[key_channel]
    if nm is not None:
        valid = valid & ~nm
    slot = (key.astype(jnp.int64) - lo).astype(jnp.int32)
    live = valid & (slot >= 0) & (slot < span)
    idx = jnp.where(live, slot, span)
    n = key.shape[0]
    row_idx = jnp.arange(n, dtype=jnp.int32)
    rows = jnp.full((span + 1,), 2**31 - 1, jnp.int32).at[idx].min(
        jnp.where(live, row_idx, jnp.int32(2**31 - 1)))
    occ = jnp.zeros((span + 1,), bool).at[idx].max(live)
    occ = occ.at[span].set(False)
    dup = jnp.sum(live, dtype=jnp.int32) - jnp.sum(occ[:span], dtype=jnp.int32)
    return DirectJoinTable(rows, occ, build_page.columns, build_page.null_masks,
                           dup, lo)


STAGE_SLOTS_MIN = 1 << 22  # direct tables of this many slots or more are staged


def stage_direct_table(dt: DirectJoinTable, fields=None) -> DirectJoinTable:
    """A large direct table's arrays copied inside the program that gathers
    from them.

    A gather straight from a long-lived HBM argument costs about 14.5 ns an
    element on a v5e and runs at speed levels that follow where the allocator
    happened to put the argument: q3 at SF10 sat at 8.0, 9.0, 9.2 or 11.5 s for
    the life of a process, and re-placing the build arrays moved it (PERF.md,
    PR 27).  The compiler prefetches most gather operands into its fast memory
    space, but not every one (q3's probe step: five of six; q18's: seven of
    fourteen).  A copy made in the program is a temporary whose place the
    compiler assigns, the fast space where it fits, so the probes no longer
    read what the heap's history placed: q3 6.90-6.94 s over nine placements.
    The barriers keep the copy from being folded away or fused into the
    gather.  A copy is one sequential pass (under a millisecond for 60 MB).
    Tables below STAGE_SLOTS_MIN keep their programs as they were.

    ``fields`` names the arrays this program gathers from (default: all of
    them).  A split join's match step reads ``MATCH_FIELDS`` and its gather
    step ``GATHER_FIELDS``: neither copies what the other reads."""
    if dt.occ.shape[0] < STAGE_SLOTS_MIN:
        return dt

    def stage(a):
        zero = jax.lax.optimization_barrier(jnp.zeros((), a.dtype))
        return jax.lax.optimization_barrier(
            a | zero if a.dtype == jnp.bool_ else a + zero)

    if fields is None:
        return jax.tree_util.tree_map(stage, dt)
    return dataclasses.replace(dt, **{
        f: jax.tree_util.tree_map(stage, getattr(dt, f)) for f in fields})


MATCH_FIELDS = ("occ",)  # what direct_match gathers from
GATHER_FIELDS = ("rows", "build_columns", "build_null_masks")


def direct_match(dt: DirectJoinTable, key_col, valid):
    """(slot, matched): the half of direct_probe that decides the match, one
    gather (of ``occ``).  ``slot`` is clipped into the table, so
    ``dt.rows[slot]`` is the build row of every matched lane."""
    span = dt.occ.shape[0] - 1
    slot = (key_col.astype(jnp.int64) - dt.lo).astype(jnp.int32)
    inr = (slot >= 0) & (slot < span)
    cslot = jnp.clip(slot, 0, span - 1)
    return cslot, valid & inr & dt.occ[cslot]


def direct_probe(dt: DirectJoinTable, key_col, valid):
    """(build_row_ids, matched) — two gathers, no rounds."""
    cslot, matched = direct_match(dt, key_col, valid)
    return jnp.where(matched, dt.rows[cslot], 0), matched


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DirectMultiJoinTable:
    """Duplicate-capable direct-address join layout: slot = key - lo,
    counts/starts/order exactly as MultiJoinTable (searchsorted expansion reuses
    the same machinery)."""

    counts: jnp.ndarray  # [span+1] int32 (sink = 0)
    starts: jnp.ndarray  # [span+1] int32 exclusive prefix sum
    order: jnp.ndarray  # [n_rows] int32 build rows grouped by slot
    build_columns: tuple
    build_null_masks: tuple
    lo: int  # static

    def tree_flatten(self):
        return ((self.counts, self.starts, self.order, self.build_columns,
                 self.build_null_masks), self.lo)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, lo=aux)


def direct_multi_build(lo: int, span: int, build_page,
                       key_channel: int) -> DirectMultiJoinTable:
    key = build_page.columns[key_channel]
    valid = build_page.valid_mask()
    nm = build_page.null_masks[key_channel]
    if nm is not None:
        valid = valid & ~nm
    slot = (key.astype(jnp.int64) - lo).astype(jnp.int32)
    live = valid & (slot >= 0) & (slot < span)
    slot_v = jnp.where(live, slot, span)
    counts = jnp.zeros((span + 1,), jnp.int32).at[slot_v].add(
        jnp.where(live, jnp.int32(1), jnp.int32(0)))
    counts = counts.at[span].set(0)
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts, dtype=jnp.int32)[:-1]])
    order = jnp.argsort(slot_v, stable=True).astype(jnp.int32)
    return DirectMultiJoinTable(counts, starts, order, build_page.columns,
                                build_page.null_masks, lo)


def direct_probe_slots(dt: DirectMultiJoinTable, key_col, valid):
    """(slot, matched) compatible with the MultiJoinTable expansion path."""
    span = dt.counts.shape[0] - 1
    slot = (key_col.astype(jnp.int64) - dt.lo).astype(jnp.int32)
    inr = (slot >= 0) & (slot < span)
    cslot = jnp.clip(slot, 0, span - 1)
    matched = valid & inr & (dt.counts[cslot] > 0)
    return jnp.where(matched, cslot, 0), matched


# ---------------------------------------------------------------------------- multi-match
# Duplicate build keys: the reference chains same-key rows through position links
# (operator/join/PositionLinks.java, JoinHash.java:145).  The TPU equivalent groups build
# rows contiguously by hash slot (argsort by slot = the "links", but as one dense gatherable
# layout): slot -> (start, count) into a row-order array.  Probe finds the slot; match
# expansion is a searchsorted over the per-probe-row cumulative match counts — every step is
# a dense gather/scan that XLA maps onto the TPU without scalar loops.


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class MultiJoinTable:
    table: jnp.ndarray  # [capacity+1] packed keys
    counts: jnp.ndarray  # [capacity+1] int32 build rows per slot (sink = 0)
    starts: jnp.ndarray  # [capacity+1] int32 exclusive prefix sum over slots
    order: jnp.ndarray  # [n_rows] int32 build row ids grouped by slot
    build_columns: tuple
    build_null_masks: tuple
    overflow: jnp.ndarray  # bool scalar

    def tree_flatten(self):
        return (
            (self.table, self.counts, self.starts, self.order, self.build_columns,
             self.build_null_masks, self.overflow),
            None,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def capacity(self):
        return self.table.shape[0] - 1


def _multi_build_step(table0, key_cols, key_types, valid):
    from .hashagg import _probe_insert

    packed, _ = pack_keys(key_cols, key_types)
    packed = jnp.where(valid, packed, EMPTY_KEY - 1)
    table, slot, placed, _ = _probe_insert(table0, packed, valid)
    C = table.shape[0] - 1
    live = valid & placed
    slot_v = jnp.where(live, slot, C).astype(jnp.int32)
    counts = jnp.zeros((C + 1,), jnp.int32).at[slot_v].add(
        jnp.where(live, jnp.int32(1), jnp.int32(0)))
    counts = counts.at[C].set(0)
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts, dtype=jnp.int32)[:-1]])
    # rows grouped by slot; invalid rows (slot == C, the max) sort to the tail and are
    # never addressed because counts exclude them
    order = jnp.argsort(slot_v, stable=True).astype(jnp.int32)
    overflow = jnp.any(valid & ~placed)
    return table, counts, starts, order, overflow


_multi_build_jit = jax.jit(_multi_build_step, static_argnums=(2,))  # compile-ok: module-level build kernel shared across executors; exec-side dispatch accounting wraps its callers


def multi_build(capacity: int, build_page, key_channels, key_types) -> MultiJoinTable:
    """Host-driven build with capacity-bucket growth (reference: FlatHash#rehash)."""
    key_cols = tuple(build_page.columns[i] for i in key_channels)
    valid = build_page.valid_mask()
    for ch in key_channels:
        nm = build_page.null_masks[ch]
        if nm is not None:
            valid = valid & ~nm
    step = _multi_build_jit
    capacity = ceil_pow2(capacity)  # double-hash coverage needs a pow2 table
    while True:
        table0 = jnp.full((capacity + 1,), EMPTY_KEY, jnp.int64)
        table, counts, starts, order, overflow = step(table0, key_cols, key_types, valid)
        if not bool(overflow):
            break
        capacity *= 4
    return MultiJoinTable(table, counts, starts, order, build_page.columns,
                          build_page.null_masks, overflow)


def probe_slots(table, key_cols, key_types, valid):
    """Gather-only probe returning (slot[int32], matched[bool]) per probe row.

    Same round-13 backend split as probe(): the Pallas kernel returns the
    matching slot itself (per-slot payload = iota), bit-identical to the
    while_loop; XLA remains the fallback above the capacity cap."""
    from . import pallas_kernels as pk

    packed, _ = pack_keys(key_cols, key_types)
    C = table.shape[0] - 1
    if pk.table_kernels_enabled(C) and packed.shape[0]:
        h0 = splitmix64(packed)
        return pk.hash_probe(table[:C], jnp.arange(C, dtype=jnp.int32), packed,
                             h0, probe_step(h0), valid, max_probes=MAX_PROBES)
    return _find_slots(table, packed, valid)[:2]


def expand_counts(incl, out_counts, size: int):
    """Map expanded row index -> (probe row index, within-group ordinal k, in-range).

    ``incl`` = inclusive cumsum of per-probe-row output counts; ``size`` is the static
    output capacity (>= incl[-1], padded to a shape bucket by the caller)."""
    n = incl.shape[0]
    i = jnp.arange(size, dtype=jnp.int32)
    pidx = jnp.clip(jnp.searchsorted(incl, i, side="right"), 0, n - 1).astype(jnp.int32)
    excl = incl[pidx] - out_counts[pidx]
    k = i - excl
    in_range = i < incl[n - 1]
    return pidx, k, in_range
