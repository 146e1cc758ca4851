"""The hash group-by's insert rounds run at the width of what is still unplaced (PR 41):
`ops/hashagg._probe_insert`'s claim loop leaves its wide loop when the unplaced lanes fit
the next narrower level, packs them, claims for them there against the same carried
table and hands their slots back.  The loop it replaced is kept HERE as the plain
reference: the table, every lane's slot and ``placed`` and the overflow flag are the old
loop's, and the rounds it reports are the old loop's count run at fewer lanes.  The twin
of `tests/test_hash_probe_narrow.py`."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu.ops import hashagg, hashing
from trino_tpu.ops.hashagg import MAX_PROBES, insert_widths
from trino_tpu.ops.hashing import EMPTY_KEY, pack_keys, probe_step, splitmix64
from trino_tpu.types import BIGINT

FLOOR = 1 << 8  # the static floor, patched down: every tier-1 page is small
SLOTS = 1 << 12
LEVELS = 1 + len(hashing.INSERT_SHIFTS)  # the widths of a page over the floor


def old_loop(table, packed, valid):
    """`_probe_insert`'s loop as it was before PR 41: every round gathers the table
    twice, scatter-mins and sets the sink for every lane, and the page ends with its
    longest chain.  (table, slot, placed, rounds, the lanes still unplaced after each
    round)."""
    C = table.shape[0] - 1
    h0 = splitmix64(packed)
    stp = probe_step(h0)

    def cond(carry):
        return (carry[0] < MAX_PROBES) & ~jnp.all(carry[3])

    def body(carry):
        p, table, slot, placed, left = carry
        idx = ((h0 + p * stp) & (C - 1)).astype(jnp.int32)
        idx = jnp.where(placed, C, idx)
        cur = table[idx]
        hit = (cur == packed) & ~placed
        slot = jnp.where(hit, idx, slot)
        placed = placed | hit
        contend = (cur == EMPTY_KEY) & ~placed
        sidx = jnp.where(contend, idx, C).astype(jnp.int32)
        table = table.at[sidx].min(jnp.where(contend, packed, EMPTY_KEY))
        table = table.at[C].set(EMPTY_KEY)
        won = (table[idx] == packed) & ~placed
        placed = placed | won
        return (p + 1, table, jnp.where(won, idx, slot), placed,
                left.at[p].set(jnp.sum(~placed, dtype=jnp.int32)))

    p, table, slot, placed, left = jax.lax.while_loop(
        cond, body, (jnp.zeros((), jnp.int32), table,
                     jnp.full(packed.shape, C, jnp.int32), ~valid,
                     jnp.zeros((MAX_PROBES,), jnp.int32)))
    return table, slot, placed, p, left


def rounds_by_rule(live, left, widths):
    """The rounds each level runs: a level's loop goes on while more lanes are unplaced
    than the next level holds (none, at the last), from the round the level before it
    left at; ``left`` the old loop's unplaced lanes after each round."""
    out, r, unplaced = [], 0, live
    for k in range(len(widths)):
        leave, q = (widths[k + 1] if k + 1 < len(widths) else 0), 0
        while r < MAX_PROBES and unplaced > leave:
            unplaced, r, q = int(left[r]), r + 1, q + 1
        out.append(q)
    return out


@pytest.fixture
def low_floor(monkeypatch):
    monkeypatch.setattr(hashing, "INSERT_MIN_LANES", FLOOR)
    hashagg.rehash.clear_cache()  # a module-level jit: its traces read the floor
    yield
    hashagg.rehash.clear_cache()


def _empty(slots=SLOTS):
    return jnp.full((slots + 1,), EMPTY_KEY, jnp.int64)


def _keys(rng, count):
    return rng.choice(1 << 40, count, replace=False).astype(np.int64)


def _page(rng, keys, lanes_a_key, live):
    """A page in which every one of ``keys`` has a lane and ``lanes_a_key`` on average,
    shuffled; validity by ``live`` ("all", "none", or a share)."""
    lanes = max(int(len(keys) * lanes_a_key), len(keys))
    page = rng.permutation(np.concatenate(
        [keys, keys[rng.integers(0, len(keys), lanes - len(keys))]]))
    valid = {"all": np.ones(lanes, bool), "none": np.zeros(lanes, bool)}.get(
        live, rng.random(lanes) < 0.7)
    return jnp.asarray(page), jnp.asarray(valid)


def _check(table0, keys, valid):
    """`_probe_insert` against the old loop: the table, every lane, the flag, the rounds."""
    packed, _ = pack_keys((keys,), (BIGINT,))
    table1, slot1, placed1, rounds1, left = jax.jit(old_loop)(table0, packed, valid)
    # (a fresh function a call: jit keeps its traces by function, and a trace reads the floor)
    table, slot, placed, rounds = jax.jit(
        lambda t, k, v: hashagg._probe_insert(t, k, v))(table0, packed, valid)
    assert np.array_equal(table, table1)
    assert np.array_equal(slot, slot1) and np.array_equal(placed, placed1)
    rounds = np.asarray(rounds)
    assert rounds.shape == (len(insert_widths(packed.shape[0])),)
    # no round is dropped and none is run twice: the levels' rounds are the old loop's,
    # and each level left when what was unplaced fitted the next
    assert int(rounds.sum()) == int(rounds1)
    assert rounds.tolist() == rounds_by_rule(
        int(np.asarray(valid).sum()), np.asarray(left), insert_widths(packed.shape[0]))
    overflow = bool(np.any(np.asarray(valid) & ~np.asarray(placed)))
    return rounds, np.asarray(table), overflow


CASES = {
    f"load{load}-{lanes_a_key}-a-key-{live}": dict(load=load, lanes_a_key=lanes_a_key,
                                                   live=live)
    for load, lanes_a_key, live in itertools.product(
        (0.1, 0.5, 0.9), (1, 3.5, 64), ("all", "some", "none"))}


@pytest.mark.parametrize("case", list(CASES))
def test_the_insert_places_every_lane_as_the_old_loop_does(case, low_floor):
    c = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case))
    keys, valid = _page(rng, _keys(rng, int(SLOTS * c["load"])), c["lanes_a_key"],
                        c["live"])
    assert keys.shape[0] >= FLOOR
    rounds, table, overflow = _check(_empty(), keys, valid)
    live = np.unique(np.asarray(keys)[np.asarray(valid)])
    # (at load 0.9 a few chains pass MAX_PROBES, in the old loop as in this one)
    assert (table[:SLOTS] != EMPTY_KEY).sum() <= len(live)
    if c["load"] < 0.9:
        assert not overflow and (table[:SLOTS] != EMPTY_KEY).sum() == len(live)
    if c["live"] == "none":
        assert rounds.sum() == 0
    elif c["load"] >= 0.5:
        # a chain longer than the levels are deep: the later rounds ran narrow
        assert rounds[1:].sum() > 0 and rounds[0] < rounds.sum()


LANES = {"under-the-floor": FLOOR - 1, "at-the-floor": FLOOR,
         "over-the-floor-odd": 7 * (FLOOR << 1)}  # 7 x a power of two, as q65's page


@pytest.mark.parametrize("case", list(LANES))
def test_lane_counts_on_both_sides_of_the_floor(case, low_floor):
    lanes = LANES[case]
    rng = np.random.default_rng(lanes)
    slots = 1 << 9  # about half full under the smallest of these pages
    keys = _keys(rng, slots // 2)
    page = jnp.asarray(keys[rng.integers(0, len(keys), lanes)])
    rounds, _, overflow = _check(_empty(slots), page,
                                 jnp.asarray(rng.random(lanes) < 0.9))
    assert not overflow
    if case == "under-the-floor":
        assert rounds.shape == (1,) and rounds[0] > 1
    else:
        assert rounds.shape == (LEVELS,) and rounds[1:].sum() > 0
        assert insert_widths(lanes) == (lanes,) + tuple(
            lanes >> s for s in hashing.INSERT_SHIFTS)


def test_a_second_page_into_a_half_full_table(low_floor):
    """The table a page left is the next page's: its keys hit along their chains, new
    keys claim behind them."""
    rng = np.random.default_rng(2)
    first = _keys(rng, SLOTS // 2)
    page, valid = _page(rng, first, 3.5, "all")
    _, table, _ = _check(_empty(), page, valid)
    fresh = _keys(rng, SLOTS // 4)
    again, valid = _page(rng, np.concatenate([first[: SLOTS // 4], fresh]), 3.5, "some")
    rounds, table2, overflow = _check(jnp.asarray(table), again, valid)
    assert not overflow and rounds[1:].sum() > 0
    assert (table2[:SLOTS] != EMPTY_KEY).sum() > (table[:SLOTS] != EMPTY_KEY).sum()


def _full_table(rng, slots):
    """(table, short): every slot taken, each key placed on the host at the first empty
    slot of its own probe sequence; ``short`` the keys that sit within eight probes."""
    keys = _keys(rng, slots)
    packed, _ = pack_keys((jnp.asarray(keys),), (BIGINT,))
    h0 = splitmix64(packed)
    table = np.full(slots + 1, EMPTY_KEY, np.int64)
    short = []
    for key, h, step in zip(np.asarray(packed).tolist(), np.asarray(h0).tolist(),
                            np.asarray(probe_step(h0)).tolist()):
        p = 0
        while table[(h + p * step) & (slots - 1)] != EMPTY_KEY:
            p += 1
        table[(h + p * step) & (slots - 1)] = key
        if p < 8:
            short.append(key)
    return jnp.asarray(table), np.asarray(short, np.int64)


@pytest.mark.parametrize("case", ["exhausted-wide", "exhausted-narrow"])
def test_a_table_that_fills_raises_the_flag_as_before(case, low_floor):
    """A lane no level places within MAX_PROBES overflows.  Four times the keys the table
    has slots: the WIDE loop itself ends at MAX_PROBES (more unplaced than the next level
    holds) and the narrower levels run no round.  A full table under a page of keys it
    holds and a few strangers: the levels take the hits, and only the LAST one, holding
    the strangers, runs out of rounds."""
    rng = np.random.default_rng(7)
    if case == "exhausted-wide":
        slots = 1 << 9
        page, valid = _page(rng, _keys(rng, 4 * slots), 2, "all")
        rounds, table, overflow = _check(_empty(slots), page, valid)
        assert overflow and rounds.tolist() == [MAX_PROBES] + [0] * (LEVELS - 1)
        assert (table[:slots] != EMPTY_KEY).all()
        return
    table0, short = _full_table(rng, SLOTS)
    page = short[rng.integers(0, len(short), 1 << 13)]
    page[rng.integers(0, 1 << 13, 5)] = (1 << 41) + np.arange(5)  # in no slot
    rounds, table, overflow = _check(table0, jnp.asarray(page), jnp.ones((1 << 13,), bool))
    assert overflow and np.array_equal(table, table0)
    assert rounds.sum() == MAX_PROBES and rounds[0] < 8 and rounds[-1] > MAX_PROBES - 16


def test_rehash_into_four_times_the_slots(low_floor):
    """`rehash` re-inserts every slot of the table it leaves, occupied or not, through the
    same loop: the grown table is the old loop's over the same lanes, the rounds are its
    count, and no group or count is lost."""
    rng = np.random.default_rng(4)
    slots = 1 << 10
    keys = _keys(rng, slots // 2)
    page, valid = _page(rng, keys, 3.5, "all")
    state = hashagg.groupby_init(slots, (jnp.int64,), ((jnp.int64, 0),))
    state, rounds = jax.jit(lambda s, k, v: hashagg.groupby_insert(
        s, (k,), (BIGINT,), v, ((None, None),), ("count_star",), with_rounds=True))(
        state, page, valid)
    assert rounds.shape == (LEVELS,) and not bool(state.overflow)
    grown, grounds = hashagg.rehash(state, 4 * slots, ("count_star",), with_rounds=True)
    occupied = state.table[:slots] != EMPTY_KEY
    want = jax.jit(old_loop)(_empty(4 * slots), state.table[:slots], occupied)[:4]
    assert np.array_equal(grown.table, want[0]) and not bool(grown.overflow)
    grounds = np.asarray(grounds)
    assert grounds.shape == (len(insert_widths(slots)),) == (LEVELS,)
    assert int(grounds.sum()) == int(want[3])
    taken = np.asarray(grown.table[:-1]) != EMPTY_KEY
    got = dict(zip(np.asarray(grown.key_cols[0])[:-1][taken].tolist(),
                   np.asarray(grown.accs[0])[:-1][taken].tolist()))
    lanes, counts = np.unique(np.asarray(page), return_counts=True)
    assert got == dict(zip(lanes.tolist(), counts.tolist()))
    # and the plain call is the state alone, as every other caller takes it
    assert isinstance(hashagg.rehash(state, 4 * slots, ("count_star",)),
                      hashagg.GroupByState)


def test_the_rounds_a_level_ran_multiply_its_width(low_floor):
    """At load 0.5 a round leaves under half of what it had: one wide round, the next few
    at the widths that hold what is left, the tail at the narrowest; rounds x width is
    under a third of the one loop's rounds x lanes."""
    rng = np.random.default_rng(5)
    page, valid = _page(rng, _keys(rng, SLOTS // 2), 3.5, "all")
    rounds, _, _ = _check(_empty(), page, valid)
    lanes = page.shape[0]
    widths = insert_widths(lanes)
    assert len(widths) == LEVELS and widths[0] == lanes
    assert rounds[0] == 1 and rounds[1:-1].sum() <= 6 and rounds[-1] > 0
    ran = int((rounds * np.asarray(widths)).sum())
    assert lanes <= ran < int(rounds.sum()) * lanes // 3


def test_a_page_that_one_round_places_runs_no_level(low_floor):
    """Few keys in a roomy table: round 0 places every lane, and the sort, the narrower
    loops and the hand-back stay in the conditional's untaken branch."""
    rng = np.random.default_rng(6)
    keys = _keys(rng, 16)
    packed = np.asarray(pack_keys((jnp.asarray(keys),), (BIGINT,))[0])
    first = np.asarray(splitmix64(jnp.asarray(packed))) & (SLOTS - 1)
    keys = keys[np.unique(first, return_index=True)[1]]  # no two share a first slot
    page = jnp.asarray(keys[rng.integers(0, len(keys), 1 << 12)])
    rounds, _, _ = _check(_empty(), page, jnp.ones((1 << 12,), bool))
    assert rounds.tolist() == [1] + [0] * (LEVELS - 1)


def test_below_the_floor_the_insert_is_the_one_loop_it_was():
    """The program's own floor, unpatched: a tier-1 page is under it."""
    lanes = 1 << 13
    assert lanes < hashing.INSERT_MIN_LANES and insert_widths(lanes) == (lanes,)
    rng = np.random.default_rng(8)
    page, valid = _page(rng, _keys(rng, SLOTS // 2), 4, "some")
    rounds, _, _ = _check(_empty(), page, valid)
    assert rounds.shape == (1,) and rounds[0] > 2


def test_the_narrowed_insert_traces_under_shard_map_with_a_constant_key(low_floor):
    """Inside `shard_map` with the varying-axis check ON every carry of every level has
    to inherit the axes of BOTH operands: a CONSTANT key (unvarying) against a
    per-worker table, and per-worker keys against a table made in the traced program
    (`groupby_init`: unvarying)."""
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as PS

    from trino_tpu.parallel.mesh import WORKER_AXIS, worker_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs four host devices")
    W, slots, lanes = 4, 1 << 10, 4 * FLOOR
    rng = np.random.default_rng(41)
    mesh = worker_mesh(W)
    tables = np.stack([np.asarray(jax.jit(old_loop)(
        _empty(slots), jnp.asarray(_keys(rng, slots // 2)), jnp.ones((slots // 2,), bool))[0])
        for _ in range(W)])
    varying = rng.integers(1, 1 << 7, (W, lanes)).astype(np.int64)
    sharded = NamedSharding(mesh, PS(WORKER_AXIS))

    def frag(tables, pkeys):
        valid = jnp.ones((lanes,), bool)
        out = []
        for table, keys in ((tables[0], jnp.full((lanes,), 7, jnp.int64)),
                            (tables[0], pkeys[0]),
                            (jnp.full((slots + 1,), EMPTY_KEY, jnp.int64), pkeys[0])):
            got = hashagg._probe_insert(table, keys, valid)
            out += [g[None] for g in got]
        return tuple(out)

    f = shard_map(frag, mesh=mesh, in_specs=(PS(WORKER_AXIS), PS(WORKER_AXIS)),
                  out_specs=(PS(WORKER_AXIS),) * 12)
    got = [np.asarray(g) for g in jax.jit(f)(
        jax.device_put(jnp.asarray(tables), sharded),
        jax.device_put(jnp.asarray(varying), sharded))]
    # (the old loop has no such guard: it answers for each worker outside the mesh)
    fresh = np.broadcast_to(np.asarray(_empty(slots)), (W, slots + 1))
    for k, (tabs, keys) in enumerate(((tables, np.full_like(varying, 7)),
                                      (tables, varying), (fresh, varying))):
        want = jax.vmap(old_loop)(jnp.asarray(tabs), jnp.asarray(keys),
                                  jnp.ones((W, lanes), bool))
        table, slot, placed, rounds = got[4 * k: 4 * k + 4]
        assert np.array_equal(table, want[0]) and np.array_equal(slot, want[1])
        assert np.array_equal(placed, want[2]) and placed.all()
        assert np.array_equal(rounds.sum(axis=1), want[3])
