"""Hashing + key packing for group-by / join / partitioned exchange.

The reference hashes per-row with generated bytecode over TypeOperators XX_HASH_64 method
handles (spi/type/TypeOperators.java:71; join-key hashing in sql/gen/JoinCompiler.java:101).
The TPU design hashes whole columns at once with a vectorized splitmix64 finisher, and packs
multi-column fixed-width keys into a single int64 when the combined bit-width fits (the common
case with dictionary-encoded strings, dates and narrow ints) so the hash table stores ONE word
per key — the same flat fixed-size-record trick as the reference's FlatHash
(operator/FlatHash.java:57) taken further.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..types import Type

__all__ = ["splitmix64", "hash_columns", "pack_keys", "key_bits", "EMPTY_KEY",
           "probe_widths", "NARROW_SHIFTS", "NARROW_MIN_LANES", "INSERT_SHIFTS",
           "INSERT_MIN_LANES"]

# Sentinel for an unoccupied hash-table slot: int64 max sorts AFTER every packed key (packed
# keys are offset to be non-negative and < 2^62), so scatter-min claims work.
EMPTY_KEY = np.iinfo(np.int64).max


def splitmix64(x):
    """Vectorized splitmix64 finalizer over int64 lanes (same mixer family the reference
    uses via XxHash64; exact function differs — we need speed + avalanche, not compat)."""
    x = x.astype(jnp.uint64)
    x = (x + jnp.uint64(0x9E3779B97F4A7C15)) & jnp.uint64(0xFFFFFFFFFFFFFFFF)
    x = x ^ (x >> 30)
    x = (x * jnp.uint64(0xBF58476D1CE4E5B9)) & jnp.uint64(0xFFFFFFFFFFFFFFFF)
    x = x ^ (x >> 27)
    x = (x * jnp.uint64(0x94D049BB133111EB)) & jnp.uint64(0xFFFFFFFFFFFFFFFF)
    x = x ^ (x >> 31)
    return x.astype(jnp.int64)


_COMBINE_MULT = np.int64(np.uint64(0x9E3779B97F4A7C15).astype(np.int64))


def hash_columns(cols) -> jnp.ndarray:
    """Combined 64-bit hash of one or more columns (row-wise).

    The combiner is asymmetric (multiply-then-xor, not plain xor) so equal values in
    different columns don't cancel.
    """
    acc = None
    for c in cols:
        h = splitmix64(_to_i64(c))
        acc = h if acc is None else splitmix64(acc * _COMBINE_MULT ^ h)
    return acc


def probe_step(h0):
    """Double-hash probe step: a second splitmix of the hash, forced ODD so the
    sequence idx_p = (h0 + p*step) & (C-1) cycles through EVERY slot of a
    power-of-two table (bit-masking, not abs-then-mod: folding negatives
    through abs would alias residues and void the full-cycle guarantee).  Linear probing (step 1) suffers primary clustering:
    the lockstep batch probe loops until the WORST row's chain ends, and at
    realistic load factors clustered chains reach dozens of rounds, each a full
    table gather.  Distinct steps make colliding rows diverge immediately.
    (Table constructors round capacities up to powers of two for coprimality.)
    Reference analog: the chained addressing of join/JoinHash.java."""
    return splitmix64(h0) | jnp.int64(1)


# The widths the open-addressing loops run at: the join's lookup
# (`hashjoin._find_slots`, PR 37) and the claim loop of the insert
# (`hashagg._probe_insert`, PR 41).  A round works for every lane of its
# vector, finished or not, and a vector used to end with its longest chain:
# over q93's 2^24-slot table at load 0.17 a batch of 8.4 M lanes ran ten rounds
# of lookup (two 14.4-ns gathers a lane a round: 289 ns a lane) where 1.2
# gathers a lane were useful.  Now a level's loop runs only while more lanes
# are unfinished than the next, narrower level holds; then the unfinished
# lanes' keys are packed to that width (`arrays.live_indices`: one int32
# sort), go on from the same round there, and their answers return by one
# scatter.  The probe sequence and the answer of every lane are what they
# were.  Constants, chosen on a v5e (PERF.md section 6, PR 37: q93's shape
# 208 -> 53 ns a lane; one level at n >> 4 read 67, (3, 6) 69, (2, 4, 6) 59 with
# the slower hand-back), not knobs.
NARROW_SHIFTS = (2, 6)  # the narrower levels, as shifts of the vector's lanes
# Below this many lanes a loop is the one loop it always was.  On the chip the
# levels win down to 1,024 lanes (0.9 for 1.2 ms; 5.0 for 15.1 ms at 65,536),
# but each level is a sort for the TPU compiler (1.7 for 0.6 s at 1,024 lanes,
# 9 for 0.7 s at 65,536) and under 2^16 lanes a call saves less than 10 ms; on
# the CPU backend, where every tier-1 table lives, a sort costs more than the
# gathers it saves
NARROW_MIN_LANES = 1 << 16


# The insert's levels (PR 41) are not the lookup's, for two reasons read on a
# v5e at q65's shape (7,340,032 lanes, 2,213,324 of them live, 2,103,739 keys,
# 2^22 slots; PERF.md section 6, PR 41).  A claim round costs 144 ns a lane
# (two gathers, a scatter-min, a set) where a lookup round costs 29, and a
# level costs what it costs the lookup (its sort 1.7 ns a lane of the wider
# level, its hand-back 5.3 ns a lane of its own): a level pays sooner.  And a
# masked page arrives a quarter to all live (`_run_hash_inserts` packs a
# sparser one first), so a first level at n/2 takes a page that is under half
# live before it runs any round at n: q65's page runs none.  Read there:
# (2, 6) 2.31 s, (2, 4, 6) 2.21, (3, 6) 1.95, (2, 4) 3.31, (1, 3, 6) 1.39,
# the one loop 21.2
INSERT_SHIFTS = (1, 3, 6)
# The insert's floor is not the lookup's either.  On the microbenchmark the
# levels win at 114,688 lanes (0.076 -> 0.026 s), but in the cells the whole
# insert STEP read slower where its page is small: q9 at SF1 (one page of about
# 180,000 lanes that ONE round places, 2^20 slots) 0.3207 -> 0.3743 s a
# statement, q93 (114,688 lanes) +0.6 %, with the levels' sorts and conditional
# in the step's program beside the key columns' and accumulators' scatters
# (cause not resolved; PERF.md section 6, PR 41).  A page of a million lanes or
# more is where a round costs 0.15 s and up; q65's has seven million
INSERT_MIN_LANES = 1 << 20


def probe_widths(n: int, shifts: tuple = None, floor: int = None) -> tuple:
    """The lane widths at which a vector of ``n`` lanes runs its probe rounds,
    widest first: what the ``rounds`` of `hashjoin.probe_counted` (the
    lookup's `NARROW_SHIFTS` over `NARROW_MIN_LANES`, the defaults) and of
    `hashagg.groupby_insert` (`INSERT_SHIFTS` over `INSERT_MIN_LANES`, through
    `hashagg.insert_widths`) multiply."""
    if n < (NARROW_MIN_LANES if floor is None else floor):
        return (n,)
    shifts = NARROW_SHIFTS if shifts is None else shifts
    return (n,) + tuple(n >> s for s in shifts if n >> s)


def ceil_pow2(n: int) -> int:
    """Smallest power of two >= n (>= 2): probe-table capacities must be
    powers of two for the double-hash step to cover the whole table."""
    return 1 << max(int(n) - 1, 1).bit_length()


def _to_i64(c):
    if c.dtype == jnp.bool_:
        return c.astype(jnp.int64)
    if jnp.issubdtype(c.dtype, jnp.floating):
        return c.astype(jnp.float64).view(jnp.int64) if c.dtype == jnp.float64 else c.astype(
            jnp.float32
        ).view(jnp.int32).astype(jnp.int64)
    return c.astype(jnp.int64)


def key_bits(t: Type, stats_max: int | None = None) -> int:
    """Bits needed to pack a column of type ``t`` (None = cannot pack)."""
    name = t.name
    if name == "boolean":
        return 1
    if name == "tinyint":
        return 8
    if name == "smallint":
        return 16
    if name in ("integer", "date") or name.startswith(("varchar", "char")):
        return 32  # dictionary ids / days
    if name == "bigint" or name.startswith("decimal"):
        return 64
    return 64


def pack_keys(cols, types, ranges=None) -> tuple[jnp.ndarray, bool]:
    """Pack key columns into one int64 per row.

    ``ranges``: optional per-column (min, max) value bounds from connector/runtime stats
    (reference analog: TupleDomain-based stats, spi/statistics) — tight bounds shrink the
    per-column bit width so multi-column keys stay injective.

    Returns (packed, exact): ``exact`` is True when the packing is injective (total bits
    ≤ 62 after offsetting value ranges) — the hash table can then compare packed words
    directly.  Otherwise ``packed`` is a 64-bit fingerprint (hash); callers that need
    exactness must verify separately (not yet needed: the planner keeps key sets narrow).
    """
    if ranges is not None:
        bits, offsets = [], []
        for (lo, hi), t in zip(ranges, types):
            if lo is None or hi is None:
                bits.append(key_bits(t)); offsets.append(None)
            else:
                bits.append(max(int(hi - lo).bit_length(), 1)); offsets.append(lo)
        if sum(bits) <= 62:
            acc = jnp.zeros(cols[0].shape, jnp.int64)
            for c, b, off in zip(cols, bits, offsets):
                v = _to_i64(c)
                v = v - off if off is not None else v + (1 << (b - 1))
                acc = (acc << b) | (v & ((1 << b) - 1))
            return acc, True
    bits = [key_bits(t) for t in types]
    total = sum(bits)
    if total <= 62:
        acc = jnp.zeros(cols[0].shape, jnp.int64)
        for c, b in zip(cols, bits):
            v = _to_i64(c) + (1 << (b - 1))  # shift signed range to non-negative
            acc = (acc << b) | (v & ((1 << b) - 1))
        return acc, True
    if len(cols) == 1 and bits[0] == 64:
        # single 64-bit key: use value itself remapped away from EMPTY sentinel
        v = _to_i64(cols[0])
        return jnp.where(v == EMPTY_KEY, EMPTY_KEY - 1, v), True
    return jnp.abs(hash_columns(cols)) & ((1 << 62) - 1), False
