"""Hash aggregation: vectorized open-addressing group-by over fixed-capacity tables.

Reference design: HashAggregationOperator (operator/HashAggregationOperator.java:46) →
FlatGroupByHash/FlatHash (operator/FlatHash.java:57-59, probe/insert :271-396) assigns dense
group ids per input row (Work<int[]> getGroupIds(Page), operator/GroupByHash.java:125), then
GroupedAggregators scatter per-group state updates.

TPU re-design (no per-row control flow, everything jit-compiled):
- keys are packed to one int64 word per row (ops/hashing.pack_keys);
- the table is a fixed-capacity int64 array; insertion is a *deterministic parallel claim*:
  per probe round, rows gather their slot, matching rows finish, rows seeing EMPTY contend
  with scatter-min (min over distinct packed keys is a deterministic winner), losers advance
  along their double-hashed probe sequence.  Up to MAX_PROBES rounds of gather+scatter
  replace the reference's per-row CAS loop, each round at the width of what is still
  unplaced: the whole page first, then the unplaced lanes packed to ever narrower
  vectors (`insert_widths`, `_claim_slots`);
- aggregation state is a struct-of-arrays indexed by slot; updates are masked segment
  scatter-adds (XLA lowers these to efficient sorted-scatter on TPU);
- the table never rehashes inside a trace: capacity is a static bucket chosen by the planner
  (reference rehashes dynamically, FlatHash#rehash — here a capacity overflow sets a flag the
  driver can observe to re-run the batch against the next capacity bucket, keeping shapes
  static for XLA).

State is a pytree, so multi-page accumulation runs as `state = step(state, page)` inside jit.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..types import BOOLEAN as _BOOL_KEY
from . import hashing
from .arrays import gather_rows, live_indices
from .hashing import ceil_pow2, probe_step, EMPTY_KEY, pack_keys, splitmix64

__all__ = ["GroupByState", "groupby_init", "groupby_insert", "AGG_INITS", "agg_update",
           "agg_finalize", "DirectConfig", "direct_config", "direct_groupby_init",
           "direct_groupby_insert"]

MAX_PROBES = 64

# Direct-index mode bounds (reference: BigintGroupByHash fast path when the single
# key is a small bigint, operator/GroupByHash.java:90-99 — generalized here to any
# key set whose packed width is statically small).
DIRECT_BITS_MAX = 24  # <= 16M slots: slot = packed key, no probing at all
ONEHOT_CAP_MAX = 128  # <= 128 slots: masked-reduce aggregation, no scatter at all


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class GroupByState:
    """Open-addressing table + per-slot aggregate accumulators."""

    table: jnp.ndarray  # [capacity+1] int64 packed keys; EMPTY_KEY = free; last slot = overflow sink
    key_cols: tuple  # per-key original column values captured at insert ([capacity+1] each)
    key_nulls: tuple  # per-key null flag per slot (SQL GROUP BY: NULLs form ONE group)
    accs: tuple  # per-aggregate accumulator arrays ([capacity+1, ...])
    overflow: jnp.ndarray  # bool scalar: some row failed to place within MAX_PROBES

    def tree_flatten(self):
        return (self.table, self.key_cols, self.key_nulls, self.accs, self.overflow), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def capacity(self) -> int:
        return self.table.shape[0] - 1


def groupby_init(capacity: int, key_dtypes, acc_specs) -> GroupByState:
    """acc_specs: sequence of (dtype, init_scalar) per accumulator array."""
    capacity = ceil_pow2(capacity)  # double-hash coverage needs a pow2 table
    table = jnp.full((capacity + 1,), EMPTY_KEY, dtype=jnp.int64)
    key_cols = tuple(jnp.zeros((capacity + 1,), dt) for dt in key_dtypes)
    key_nulls = tuple(jnp.zeros((capacity + 1,), bool) for _ in key_dtypes)
    accs = tuple(jnp.full((capacity + 1,), init, dtype=dt) for dt, init in acc_specs)
    return GroupByState(table, key_cols, key_nulls, accs, jnp.zeros((), bool))


@dataclasses.dataclass(frozen=True)
class DirectConfig:
    """Static layout of a direct-indexed group-by: per key (nullable, lo, hi,
    value_bits), most-significant first.  slot = bit-concatenation of
    [null_flag?, (value - lo)] fields; total_bits <= DIRECT_BITS_MAX."""

    entries: tuple  # ((nullable, lo, hi, value_bits), ...) aligned with keys
    total_bits: int

    @property
    def capacity(self) -> int:
        return 1 << self.total_bits


def direct_config(key_ranges, key_nullable, max_bits: int = DIRECT_BITS_MAX):
    """Build a DirectConfig, or None when ranges are unknown/too wide.

    key_ranges: per key (lo, hi) inclusive value bounds or None;
    key_nullable: per key, whether a null mask is present at trace time.
    """
    entries, total = [], 0
    for rng, nullable in zip(key_ranges, key_nullable):
        if rng is None or rng[0] is None or rng[1] is None:
            return None
        lo, hi = int(rng[0]), int(rng[1])
        if hi < lo:
            hi = lo
        vb = max(int(hi - lo).bit_length(), 1)
        total += vb + (1 if nullable else 0)
        entries.append((bool(nullable), lo, hi, vb))
    if total > max_bits:
        return None
    return DirectConfig(tuple(entries), total)


def observed_direct_config(key_bounds, lanes: int):
    """The DirectConfig of a group-by whose keys' bounds were READ from the one
    page it groups (`LocalExecutor._observed_direct_config`), or None: hash mode.

    key_bounds: per key ``(lo, hi, any_null)``, the inclusive bounds of its live
    non-NULL values and whether a live lane is NULL; ``lanes``: the page's width.
    The config is a compile shape, so the bounds are widened to the envelope of
    the bits they need (``hi = lo + 2^bits - 1``): a maximum that moves inside
    its bit width names the same config.  Direct only where it is the smaller
    thing: a table is never wider than the page it groups (keys spread over 2^22
    values in a 256-lane page stay hashed).  ``hi < lo`` is a key with no live
    non-NULL value: nothing to index by."""
    ranges = []
    for lo, hi, _ in key_bounds:
        if hi < lo:
            return None
        ranges.append((lo, lo + (1 << max((hi - lo).bit_length(), 1)) - 1))
    cfg = direct_config(ranges, [any_null for _, _, any_null in key_bounds])
    return cfg if cfg is not None and cfg.capacity <= lanes else None


def direct_groupby_init(cfg: DirectConfig, key_dtypes, acc_specs) -> GroupByState:
    """Direct-mode state: key columns are PRE-FILLED by unpacking each slot index
    (packing is injective), so inserts never scatter key captures."""
    C = cfg.capacity
    table = jnp.full((C + 1,), EMPTY_KEY, dtype=jnp.int64)
    slots = jnp.arange(C + 1, dtype=jnp.int64)
    key_cols, key_nulls = [], []
    shift = cfg.total_bits
    for (nullable, lo, hi, vb), dt in zip(cfg.entries, key_dtypes):
        if nullable:
            shift -= 1
            flag = ((slots >> shift) & 1).astype(bool)
        else:
            flag = jnp.zeros((C + 1,), bool)
        shift -= vb
        field = (slots >> shift) & ((1 << vb) - 1)
        val = (field + lo).astype(dt)
        # null rows pack a masked value of 0 -> field (0 - lo) & mask; the value
        # lane is garbage for them but the null flag marks the group as NULL
        key_cols.append(jnp.where(flag, jnp.zeros((), dt), val))
        key_nulls.append(flag)
    accs = tuple(jnp.full((C + 1,), init, dtype=dt) for dt, init in acc_specs)
    return GroupByState(table, tuple(key_cols), tuple(key_nulls), accs,
                        jnp.zeros((), bool))


def _direct_slot(cfg: DirectConfig, key_vals, key_nulls, valid):
    """(slot[int32], in_range[bool]) — slot is the packed key; rows outside the
    static ranges raise the overflow flag (stale stats) so the caller can fall
    back to hash mode."""
    n = key_vals[0].shape[0]
    acc = jnp.zeros((n,), jnp.int64)
    ok = jnp.ones((n,), bool)
    for (nullable, lo, hi, vb), kv, kn in zip(cfg.entries, key_vals, key_nulls):
        isnull = kn if kn is not None else jnp.zeros((n,), bool)
        mv = jnp.where(isnull, jnp.zeros((), kv.dtype), kv) if kn is not None else kv
        v64 = mv.astype(jnp.int64)
        ok = ok & (isnull | ((v64 >= lo) & (v64 <= hi)))
        if nullable:
            acc = (acc << 1) | isnull.astype(jnp.int64)
        elif kn is not None:
            # the config was frozen from a page WITHOUT a null mask on this key;
            # a later page introduced one (no flag bit reserved) — route NULL rows
            # to overflow so the caller falls back to hash mode instead of merging
            # them into the value-`lo` group
            ok = ok & ~isnull
        acc = (acc << vb) | ((v64 - lo) & ((1 << vb) - 1))
    return acc.astype(jnp.int32), ok


def direct_groupby_insert(state: GroupByState, cfg: DirectConfig, key_vals,
                          valid, agg_inputs, agg_updates,
                          key_nulls=None) -> GroupByState:
    """One page -> updated direct-mode state.  No probing: slot = packed key.
    Capacities <= ONEHOT_CAP_MAX aggregate via masked reductions over a
    [rows, capacity] one-hot — zero scatters, MXU/VPU-friendly, fast to compile."""
    if key_nulls is None:
        key_nulls = tuple(None for _ in key_vals)
    C = cfg.capacity
    slot, ok = _direct_slot(cfg, key_vals, key_nulls, valid)
    live = valid & ok
    overflow = state.overflow | jnp.any(valid & ~ok)

    if C <= ONEHOT_CAP_MAX:
        lanes = jnp.arange(C, dtype=jnp.int32)
        onehot = (slot[:, None] == lanes[None, :]) & live[:, None]  # [n, C]
        occ = jnp.any(onehot, axis=0)
        table = jnp.where(jnp.concatenate([occ, jnp.zeros((1,), bool)]),
                          jnp.arange(C + 1, dtype=jnp.int64), state.table)
        accs = tuple(
            _onehot_agg_update(acc, kind, onehot, vals_nulls)
            for acc, kind, vals_nulls in zip(state.accs, agg_updates, agg_inputs)
        )
        return GroupByState(table, state.key_cols, state.key_nulls, accs, overflow)

    idx = jnp.where(live, slot, C)
    table = state.table.at[idx].set(jnp.where(live, idx.astype(jnp.int64), EMPTY_KEY))
    table = table.at[C].set(EMPTY_KEY)
    accs = tuple(
        agg_update(acc, kind, slot, live, vals_nulls)
        for acc, kind, vals_nulls in zip(state.accs, agg_updates, agg_inputs)
    )
    return GroupByState(table, state.key_cols, state.key_nulls, accs, overflow)


def _onehot_agg_update(acc, kind, onehot, vals_nulls):
    """Aggregate one page into [capacity]-wide accumulators via masked reductions
    over the one-hot (plus the overflow sink kept untouched at the end)."""
    vals, nulls = vals_nulls if vals_nulls is not None else (None, None)
    C = onehot.shape[1]
    mask = onehot if (nulls is None or vals is None) else (onehot & ~nulls[:, None])
    if kind in ("count_star", "count"):
        m = onehot if kind == "count_star" else mask
        delta = jnp.sum(m, axis=0).astype(acc.dtype)
        return acc.at[:C].add(delta)
    if kind == "sum":
        delta = jnp.sum(jnp.where(mask, vals[:, None], 0), axis=0).astype(acc.dtype)
        return acc.at[:C].add(delta)
    if kind in ("sum_hi32", "sum_lo32"):
        v = (vals >> 32) if kind == "sum_hi32" else (vals & 0xFFFFFFFF)
        delta = jnp.sum(jnp.where(mask, v[:, None], 0), axis=0).astype(acc.dtype)
        return acc.at[:C].add(delta)
    if kind == "sum_sq":
        v = vals.astype(acc.dtype)
        delta = jnp.sum(jnp.where(mask, (v * v)[:, None], 0), axis=0)
        return acc.at[:C].add(delta)
    if kind == "min":
        big = _extreme(acc.dtype, +1)
        page_min = jnp.min(jnp.where(mask, vals[:, None].astype(acc.dtype), big),
                           axis=0)
        return acc.at[:C].min(page_min)
    if kind == "max":
        small = _extreme(acc.dtype, -1)
        page_max = jnp.max(jnp.where(mask, vals[:, None].astype(acc.dtype), small),
                           axis=0)
        return acc.at[:C].max(page_max)
    raise NotImplementedError(kind)


def insert_widths(n: int) -> tuple:
    """The lane widths at which a page of ``n`` lanes runs its claim rounds,
    widest first: what the ``rounds`` of `groupby_insert` and `rehash` multiply."""
    return hashing.probe_widths(n, hashing.INSERT_SHIFTS, hashing.INSERT_MIN_LANES)


def insert_bucket(n: int, width: int) -> int:
    """The lanes at which a page of ``width`` lanes, ``n`` of them live, is inserted:
    ``width`` itself says masked, anything less the bucket its live lanes are packed to
    first (`LocalExecutor._run_hash_inserts`).  An insert's scatters cost by the WIDTH of
    their vector: a dead lane is routed to the sink slot and pays what a live one pays.

    Where the power of two that holds ``n`` (at least 1,024) is under half the width, it
    is the bucket.  Else the bucket is the least multiple of an EIGHTH of the width that
    holds ``n``, if that is at most half the width: eighths of the page's own static width
    keep the compiled shapes to two or three a width.  Never under
    `hashing.INSERT_MIN_LANES`: a narrower bucket would run every claim round at its full
    width in the ONE loop (`insert_widths`), which costs more than the dead lanes'
    scatters do; so a page of under twice the floor is packed to a power of two or not
    at all.  Read on a v5e (PERF.md section 6, PR 43; one `groupby_insert`): q65's and
    q51's page of 7,340,032 lanes, 2,213,324 of them live, masked 5.53 s; packed to 4/8
    3.74 s + the pack's 0.19; to 3/8 = 2,752,512 lanes, its bucket, 2.82 + 0.14."""
    bucket = max(ceil_pow2(n), 1024)
    if bucket * 2 < width:
        return bucket
    eighth = -(-width // 8192) * 1024  # a page's lanes are a multiple of 8,192 as a rule
    bucket = -(-max(n, hashing.INSERT_MIN_LANES) // eighth) * eighth
    return bucket if bucket * 2 <= width else width


def _probe_insert(table, packed, valid):
    """Assign each valid row a slot whose table word == its packed key; claim empty slots
    deterministically. Returns (table, slot[int32], placed[bool], rounds): ``rounds``
    int32[len(insert_widths(lanes))], the rounds the open-addressing loop ran at each
    width (`_claim_slots`; zeros from the Pallas kernel, which has no rounds to report).

    Round-13 backend split: capacities within `PALLAS_TABLE_MAX` route to the
    in-kernel claim loop (`pallas_kernels.hash_insert`).  Its contention
    winner differs (min row index vs scatter-min over packed words) so the
    slot LAYOUT may differ from this XLA protocol, but both preserve the
    open-addressing chain invariant — probes and multi-page re-inserts against
    either table are key-equivalent, which is the contract every consumer
    (state threading, rehash, build tables) actually relies on.  Parity tests
    pin the observables; never assert raw slot order across backends."""
    from . import pallas_kernels as pk

    widths = insert_widths(packed.shape[0])
    if pk.table_kernels_enabled(table.shape[0] - 1) and packed.shape[0]:
        return pk.hash_insert(table, packed, valid, max_probes=MAX_PROBES) \
            + (jnp.zeros((len(widths),), jnp.int32),)
    return _claim_slots(table, packed, valid, 0, widths)


def _claim_slots(table, packed, valid, p, widths):
    """`_probe_insert`'s claim loop from round ``p`` on, at ``widths[0]`` lanes
    (those of ``packed``) and then at each narrower width: (table, slot,
    placed, rounds int32[len(widths)]).

    A round gathers the table twice, scatter-mins the contenders' keys and
    sets the sink for EVERY lane of its vector, placed or not (a placed lane
    is routed to the sink slot C and still pays), and a page ends with its
    longest chain: q65's 7,340,032 lanes ran 20 rounds at 2^22 slots where
    a fifth of them went past the first (PERF.md section 6, PR 41).  So a
    level's loop runs only while more lanes are unplaced than the next level
    holds; then the unplaced lanes' keys are packed to that width, go on from
    the same round there against the SAME carried table, and their slots
    return by one scatter (as `hashjoin._find_slots` hands back its answers).
    A round's contenders are the same lanes with the same keys at the same
    probe position in a vector of n or of n/8, scatter-min picks the same
    winner, and a duplicate of the winner's key still finishes in the winner's
    round: the table, the slots and ``placed`` are what the one loop gave, bit
    for bit."""
    C = table.shape[0] - 1
    n = packed.shape[0]
    h0 = splitmix64(packed)
    stp = probe_step(h0)
    # derive every loop carry from BOTH operands' varying axes: under
    # shard_map a fresh constant (a groupby_init table built inside the traced
    # program, a zeros slot vector) is "unvarying" and the while_loop rejects
    # the carry once the body mixes it with per-worker data.  Adding a zeroed
    # varying term is a no-op numerically but inherits the varying axis; keys
    # alone are not enough (a constant key against a per-worker table), so the
    # lanes' zero touches the table and the table's the keys.
    # (a reduction keeps the varying axis and, unlike packed[:1], broadcasts
    # against the table even when the page has zero rows)
    table = table + (jnp.sum(packed) & 0) + (jnp.sum(valid) & 0)
    vzero = (h0 * 0).astype(jnp.int32) \
        + (table[jnp.zeros((), jnp.int32)] * 0).astype(jnp.int32) \
        + (valid.astype(jnp.int32) * 0)
    slot = vzero + C  # default: overflow sink
    placed = ~valid | (vzero != 0)  # invalid rows are trivially "done" (routed to sink)
    leave = widths[1] if len(widths) > 1 else 0

    # the loop counts its OWN rounds from a constant 0 and adds the rounds run
    # before it (``p``, a device scalar it does not carry): a carried round
    # that starts at a traced value cost the v5e compiler 84 s more at 8.4 M
    # lanes than one that starts at 0 (PERF.md section 6, PR 37)
    def cond(carry):
        q, table, slot, placed = carry
        # early exit once what is unplaced fits the next level (none, at the
        # last): typical inserts finish in 1-3 rounds, far below MAX_PROBES
        return (p + q < MAX_PROBES) & (jnp.sum(~placed, dtype=jnp.int32) > leave)

    def body(carry):
        q, table, slot, placed = carry
        idx = ((h0 + (p + q) * stp) & (C - 1)).astype(jnp.int32)
        idx = jnp.where(placed, C, idx)
        cur = table[idx]
        hit = (cur == packed) & ~placed
        slot = jnp.where(hit, idx, slot)
        placed = placed | hit
        contend = (cur == EMPTY_KEY) & ~placed
        sidx = jnp.where(contend, idx, C).astype(jnp.int32)
        table = table.at[sidx].min(jnp.where(contend, packed, EMPTY_KEY))
        # sink slot may have been clobbered by routed writes; restore
        table = table.at[C].set(EMPTY_KEY)
        cur2 = table[idx]
        won = (cur2 == packed) & ~placed
        slot = jnp.where(won, idx, slot)
        placed = placed | won
        return q + 1, table, slot, placed

    q, table, slot, placed = jax.lax.while_loop(
        cond, body, (jnp.zeros((), jnp.int32), table, slot, placed))
    rounds = q[None]
    if len(widths) == 1:
        return table, slot, placed, rounds

    def narrow(table):
        # pack what is unplaced (at most ``leave`` lanes) and claim for it
        # there.  Only the keys move: an unplaced lane has no slot yet, and
        # its hash is two multiplies.  Filler lanes are not valid: routed to
        # the sink, never contending
        idx, count = live_indices(~placed, leave)
        lane = jax.lax.iota(jnp.int32, leave)
        table, nslot, nplaced, nrounds = _claim_slots(
            table, gather_rows(packed, idx), lane < count, p + q, widths[1:])
        # hand back: ONE scatter of ``leave`` slots to the lanes they came
        # from (ascending and distinct; the filler lanes of ``idx`` are sent
        # out of bounds, each to a place of its own, and dropped)
        found = (vzero - 1).at[jnp.where(lane < count, idx, n + lane)].set(
            jnp.where(nplaced & (lane < count), nslot, -1), mode="drop",
            indices_are_sorted=True, unique_indices=True)
        return table, found, nrounds

    def done(table):
        return table, vzero - 1, jnp.zeros((len(widths) - 1,), jnp.int32) + (q & 0)

    # a page that the wide loop places whole (most inserts at low load: 1-3
    # rounds) pays nothing for the levels: no sort, no narrower loop, no
    # hand-back.  Nor does one whose wide loop ran out of rounds with more than
    # ``leave`` unplaced: no level would run a round, and every such lane
    # overflows as it always did.  Only the table goes through the conditional
    # and comes back; the lanes' slots come back as ``found``
    unplaced = jnp.sum(~placed, dtype=jnp.int32)
    table, found, nrounds = jax.lax.cond(
        (unplaced > 0) & (p + q < MAX_PROBES), narrow, done, table)
    hit = found >= 0
    slot, placed = jnp.where(hit, found, slot), placed | hit
    return table, slot, placed, jnp.concatenate([rounds, nrounds])


def groupby_insert(state: GroupByState, key_vals: Sequence, key_types, valid,
                   agg_inputs: Sequence, agg_updates: Sequence[str],
                   key_nulls: Sequence = None, with_rounds: bool = False):
    """One page of input → updated state; ``with_rounds``: (state, the rounds
    ``_probe_insert`` ran at each of `insert_widths` of the page's lanes,
    an int32 vector).

    agg_inputs[i]: (value_array|None, input_null_mask|None); agg_updates[i]: update kind
    ('sum','count','min','max','count_star'); key_nulls[i]: null mask of key i or None
    (SQL GROUP BY treats all NULLs as one group — the null flag joins the packed key
    and masked values keep NULL rows from colliding with a real value).
    """
    if key_nulls is None:
        key_nulls = tuple(None for _ in key_vals)
    # The packed layout must be IDENTICAL for every page of one aggregation, or the
    # same key value lands in different slots across pages whose null-mask structure
    # differs (e.g. parquet row groups with and without NULLs).  Single-key: no flag
    # bit ever — the NULL group routes to a reserved sentinel word (keeps the exact
    # single-64-bit-key packing).  Multi-key: a flag bit per key, always present.
    if len(key_vals) == 1:
        kv, kt, kn = key_vals[0], key_types[0], key_nulls[0]
        mv = jnp.where(kn, jnp.zeros((), kv.dtype), kv) if kn is not None else kv
        masked_vals = [mv]
        packed, exact = pack_keys((mv,), (kt,))
        if kn is not None:
            # EMPTY_KEY is the free-slot marker (its remap target is EMPTY_KEY-1);
            # EMPTY_KEY-2 is the NULL group's reserved word.  A real key equal to
            # the sentinel joins the existing EMPTY_KEY-1 remap pool instead of
            # being merged with the NULL group (same accepted int64-max-adjacent
            # collision class as pack_keys' EMPTY_KEY remap).
            packed = jnp.where(packed == EMPTY_KEY - 2, EMPTY_KEY - 1, packed)
            packed = jnp.where(kn, EMPTY_KEY - 2, packed)
    else:
        pack_cols, pack_types = [], []
        masked_vals = []
        for kv, kt, kn in zip(key_vals, key_types, key_nulls):
            mv = kv if kn is None else jnp.where(kn, jnp.zeros((), kv.dtype), kv)
            masked_vals.append(mv)
            pack_cols.append(jnp.zeros(kv.shape, jnp.int8) if kn is None
                             else kn.astype(jnp.int8))
            pack_types.append(_BOOL_KEY)
            pack_cols.append(mv)
            pack_types.append(kt)
        packed, exact = pack_keys(tuple(pack_cols), tuple(pack_types))
    table, slot, placed, rounds = _probe_insert(state.table, packed, valid)
    overflow = state.overflow | jnp.any(valid & ~placed)
    live = valid & placed

    # capture original key values per slot (idempotent writes: same key -> same value)
    key_cols = tuple(
        kc.at[jnp.where(live, slot, kc.shape[0] - 1)].set(jnp.where(live, kv, kc[-1]))
        for kc, kv in zip(state.key_cols, masked_vals)
    )
    state_knulls = tuple(
        sk if kn is None else
        sk.at[jnp.where(live, slot, sk.shape[0] - 1)].set(jnp.where(live, kn, sk[-1]))
        for sk, kn in zip(state.key_nulls, key_nulls)
    )
    accs = tuple(
        agg_update(acc, kind, slot, live, vals_nulls)
        for acc, kind, vals_nulls in zip(state.accs, agg_updates, agg_inputs)
    )
    out = GroupByState(table, key_cols, state_knulls, accs, overflow)
    return (out, rounds) if with_rounds else out


def agg_update(acc, kind, slot, live, vals_nulls):
    vals, nulls = vals_nulls if vals_nulls is not None else (None, None)
    mask = live if (nulls is None or vals is None) else (live & ~nulls)
    sink = acc.shape[0] - 1
    idx = jnp.where(mask, slot, sink)
    if kind == "count_star":
        return acc.at[idx].add(jnp.where(live, 1, 0).astype(acc.dtype))
    if kind == "count":
        return acc.at[idx].add(jnp.where(mask, 1, 0).astype(acc.dtype))
    if kind == "sum":
        return acc.at[idx].add(jnp.where(mask, vals, 0).astype(acc.dtype))
    if kind in ("sum_hi32", "sum_lo32"):
        # two-limb exact decimal sum (reference: Int128 state in
        # DecimalSumAggregation): each int64 input splits as
        # v == (v >> 32) * 2^32 + (v & 0xFFFFFFFF); the halves accumulate
        # separately without overflow and recombine exactly on the host
        v = (vals >> 32) if kind == "sum_hi32" else (vals & 0xFFFFFFFF)
        return acc.at[idx].add(jnp.where(mask, v, 0).astype(acc.dtype))
    if kind == "sum_sq":
        v = vals.astype(acc.dtype)
        return acc.at[idx].add(jnp.where(mask, v * v, 0))
    if kind == "min":
        big = _extreme(acc.dtype, +1)
        return acc.at[idx].min(jnp.where(mask, vals, big).astype(acc.dtype))
    if kind == "max":
        small = _extreme(acc.dtype, -1)
        return acc.at[idx].max(jnp.where(mask, vals, small).astype(acc.dtype))
    raise NotImplementedError(kind)


def _extreme(dtype, sign):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.inf * sign
    info = jnp.iinfo(dtype)
    return info.max if sign > 0 else info.min


AGG_INITS = {
    "sum": 0,
    "count": 0,
    "count_star": 0,
    "min": None,  # filled with dtype max
    "max": None,  # filled with dtype min
}


_REHASH_KIND = {"sum": "sum", "count": "sum", "count_star": "sum",
                "min": "min", "max": "max", "sum_sq": "sum",
                # limb accumulators re-insert by plain addition (already split)
                "sum_hi32": "sum", "sum_lo32": "sum"}


@partial(jax.jit, static_argnums=(1, 2, 3))  # compile-ok: module-level kernel invoked from exec's _jit-wrapped steps and driver loops; per-capacity compiles are bounded by pow2 growth
def rehash(state: GroupByState, new_capacity: int, acc_kinds: tuple = (),
           with_rounds: bool = False):
    """Re-insert every occupied entry into a larger table (reference:
    FlatHash#rehash).  Accumulators re-insert as partial values (count -> sum).
    Keeps growth at one table-sized pass instead of re-streaming the input.
    ``with_rounds``: (state, the rounds the re-insert's loop ran at each of
    `insert_widths` of the table it leaves)."""
    C = state.capacity
    occupied = state.table[:C] != EMPTY_KEY
    keys = tuple(k[:C] for k in state.key_cols)
    knulls = tuple(kn[:C] for kn in state.key_nulls)
    accs = tuple(a[:C] for a in state.accs)
    fresh = GroupByState(
        table=jnp.full((new_capacity + 1,), EMPTY_KEY, dtype=jnp.int64),
        key_cols=tuple(jnp.zeros((new_capacity + 1,), k.dtype) for k in state.key_cols),
        key_nulls=tuple(jnp.zeros((new_capacity + 1,), bool) for _ in state.key_nulls),
        accs=tuple(jnp.full((new_capacity + 1,), _init_for(kind, a.dtype), a.dtype)
                   for kind, a in zip(acc_kinds, state.accs)),
        overflow=jnp.zeros((), bool),
    )
    key_types = tuple(_DTYPE_KEY_TYPE(k.dtype) for k in keys)
    merge = [_REHASH_KIND[k] for k in acc_kinds]
    return groupby_insert(fresh, keys, key_types, occupied,
                          [(a, None) for a in accs], merge, knulls,
                          with_rounds=with_rounds)


def _init_for(kind: str, dtype):
    if kind == "min":
        return _extreme(dtype, +1)
    if kind == "max":
        return _extreme(dtype, -1)
    return 0


class _KT:
    """Minimal Type stand-in for rehash key packing.  pack_keys reads only
    `.name` (bit width class) and dtype-driven conversion, so mapping the
    stored dtype back to its widest type class reproduces the original packed
    layout exactly (int64 -> 64-bit path, int32/date/dict ids -> 32, ...)."""

    _NAMES = {"int64": "bigint", "int32": "integer", "int16": "smallint",
              "int8": "tinyint", "bool": "boolean", "float64": "double",
              "float32": "real"}

    def __init__(self, dtype):
        self.dtype = dtype
        self.name = self._NAMES.get(np.dtype(dtype).name, "bigint")
        self.is_string = False
        self.is_floating = np.issubdtype(np.dtype(dtype), np.floating)


def _DTYPE_KEY_TYPE(dtype):
    return _KT(dtype)


def agg_finalize(state: GroupByState):
    """Returns (group_valid[capacity] bool, key_cols, accs) with the overflow sink dropped."""
    C = state.capacity
    occupied = state.table[:C] != EMPTY_KEY
    keys = tuple(k[:C] for k in state.key_cols)
    accs = tuple(a[:C] for a in state.accs)
    return occupied, keys, accs



def group_count(state: GroupByState):
    """Occupied-slot count (device scalar; ONE host sync to size the compaction)."""
    C = state.capacity
    return jnp.sum(state.table[:C] != EMPTY_KEY, dtype=jnp.int32)


@partial(jax.jit, static_argnums=(1,))  # compile-ok: module-level kernel; pow2 size buckets bound its compile count
def compact_groups(state: GroupByState, size: int):
    """Gather the occupied groups into dense ``size``-bounded arrays ON DEVICE.

    The hash table is capacity-sized but real group counts are usually tiny
    (Q1: 6 groups in a 65k table) — transferring the full table to the host
    dominates query time on low-bandwidth device links, so compaction must
    happen before any device->host copy.  ``size`` is a power-of-two bucket
    (cached executable per bucket)."""
    C = state.capacity
    occupied = state.table[:C] != EMPTY_KEY
    idx, _ = live_indices(occupied, size)
    keys = tuple(gather_rows(k[:C], idx) for k in state.key_cols)
    key_nulls = tuple(gather_rows(kn[:C], idx) for kn in state.key_nulls)
    accs = tuple(gather_rows(a[:C], idx) for a in state.accs)
    return keys, key_nulls, accs
