"""The hash probe's rounds run at the width of what is still live (PR 37):
`ops/hashjoin._find_slots`, the one lookup of `probe` and `probe_slots`, leaves its
wide loop when the unfinished lanes fit the next narrower level, packs them, finishes
them there and hands the answers back.  The loop it replaced is kept HERE as the plain
reference: every lane of every answer is the old loop's, and the rounds it reports are
the old loop's count run at fewer lanes."""

import itertools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trino_tpu.ops import hashing, hashjoin
from trino_tpu.ops.hashing import (EMPTY_KEY, pack_keys, probe_step,
                                   splitmix64)
from trino_tpu.ops.hashjoin import MAX_PROBES
from trino_tpu.page import Page, Schema
from trino_tpu.types import BIGINT

FLOOR = 1 << 10  # the static floor, patched down: every tier-1 table is small
SLOTS = 1 << 12


def old_loop(table, packed, valid):
    """`probe_slots`' loop as it was before PR 37: every round gathers the table for
    every lane, and the batch ends with its longest chain.  (slot, matched, rounds)."""
    C = table.shape[0] - 1
    h0 = splitmix64(packed)
    stp = probe_step(h0)

    def cond(carry):
        return (carry[0] < MAX_PROBES) & ~jnp.all(carry[3])

    def body(carry):
        p, slot, matched, done = carry
        idx = ((h0 + p * stp) & (C - 1)).astype(jnp.int32)
        cur = table[idx]
        hit = (cur == packed) & ~done
        return (p + 1, jnp.where(hit, idx, slot), matched | hit,
                done | hit | (cur == EMPTY_KEY))

    p, slot, matched, _ = jax.lax.while_loop(
        cond, body, (jnp.zeros((), jnp.int32), jnp.zeros(packed.shape, jnp.int32),
                     valid & False, ~valid))
    return slot, matched, p


@pytest.fixture
def low_floor(monkeypatch):
    monkeypatch.setattr(hashing, "NARROW_MIN_LANES", FLOOR)


def _build(key_cols, slots=SLOTS):
    """A `JoinTable` over ``key_cols`` (unique rows), through the program's own build."""
    n = key_cols[0].shape[0]
    types = (BIGINT,) * len(key_cols)
    page = Page.from_arrays(
        Schema.of(*((f"k{i}", BIGINT) for i in range(len(key_cols)))), list(key_cols))
    jt = hashjoin.build_insert(hashjoin.build_table_init(slots, page), key_cols, types,
                               jnp.ones((n,), bool))
    assert not bool(jt.overflow) and int(jt.dup_count) == 0
    return jt, types


def _place(keys, slots=SLOTS):
    """A `JoinTable` over one bigint column placed row by row on the host, each key at the
    first empty slot of its own probe sequence however long that takes: what the
    program's insert builds below its round limit, and the only way to a FULL table.  (A
    key placed beyond `MAX_PROBES` is found by neither loop.)"""
    packed, _ = pack_keys((jnp.asarray(keys),), (BIGINT,))
    h0 = splitmix64(packed)
    table = np.full(slots + 1, EMPTY_KEY, np.int64)
    rows = np.full(slots + 1, 2**31 - 1, np.int32)
    rows[slots] = 0
    for row, (key, h, step) in enumerate(zip(
            np.asarray(packed).tolist(), np.asarray(h0).tolist(),
            np.asarray(probe_step(h0)).tolist())):
        p = 0
        while table[(h + p * step) & (slots - 1)] != EMPTY_KEY:
            p += 1
        table[(h + p * step) & (slots - 1)], rows[(h + p * step) & (slots - 1)] = key, row
    cols = (jnp.asarray(keys),)
    return hashjoin.JoinTable(
        jnp.asarray(table), jnp.asarray(rows), cols, (None,), jnp.int32(len(keys)),
        jnp.int32(0), jnp.zeros((), bool)), (BIGINT,)


def _keys(rng, count):
    return rng.choice(1 << 40, count, replace=False).astype(np.int64)


def _probe_side(rng, built, lanes, hits, live):
    """``lanes`` probe keys of which the share ``hits`` is in ``built``; validity by
    ``live`` ("all", "none", or a share)."""
    strangers = rng.integers(1 << 41, 1 << 42, lanes)
    keys = np.where(rng.random(lanes) < hits, built[rng.integers(0, len(built), lanes)],
                    strangers).astype(np.int64)
    valid = {"all": np.ones(lanes, bool), "none": np.zeros(lanes, bool)}.get(
        live, rng.random(lanes) < 0.7)
    return jnp.asarray(keys), jnp.asarray(valid)


def _check(jt, types, key_cols, valid):
    """`probe` and `probe_slots` against the old loop, every lane; the rounds of each."""
    packed, _ = pack_keys(key_cols, types)
    slot0, matched0, rounds0 = jax.jit(old_loop)(jt.table, packed, valid)
    slot, matched = jax.jit(partial(hashjoin.probe_slots, key_types=types))(
        jt.table, key_cols, valid=valid)
    assert np.array_equal(slot, slot0) and np.array_equal(matched, matched0)
    rows, pmatched, rounds = jax.jit(partial(hashjoin.probe_counted, key_types=types))(
        jt, key_cols, valid=valid)
    assert np.array_equal(pmatched, matched0)
    assert np.array_equal(rows, np.where(matched0, np.asarray(jt.rows)[slot0], 0))
    rows2, matched2 = jax.jit(partial(hashjoin.probe, key_types=types))(
        jt, key_cols, valid=valid)
    assert np.array_equal(rows2, rows) and np.array_equal(matched2, matched0)
    rounds = np.asarray(rounds)
    assert rounds.shape == (len(hashjoin.probe_widths(packed.shape[0])),)
    # no round is dropped and none is run twice: the levels' rounds are the old loop's
    assert int(rounds.sum()) == int(rounds0)
    return rounds, np.asarray(matched0)


CASES = {
    f"load{load}-{hname}-{live}": dict(load=load, hits=hits, live=live)
    for (load, (hname, hits), live) in itertools.product(
        (0.1, 0.5, 0.9), (("allhit", 1.0), ("allmiss", 0.0), ("tenth", 0.1)),
        ("all", "some", "none"))}
# lane counts on both sides of the static floor
CASES["under-the-floor"] = dict(load=0.5, hits=0.1, live="some", lanes=FLOOR - 1)
CASES["at-the-floor"] = dict(load=0.5, hits=0.1, live="some", lanes=FLOOR)
CASES["over-the-floor-odd"] = dict(load=0.5, hits=0.1, live="some", lanes=3 * FLOOR + 7)
# a full table never shows an empty slot: every miss runs MAX_PROBES rounds.  With
# every lane a miss the wide loop itself ends there (more than W lanes unfinished, the
# narrow levels run no round); with a few misses among hits the LAST level ends there
CASES["exhausted-wide"] = dict(load=1.0, hits=0.0, live="all")
CASES["exhausted-narrow"] = dict(load=1.0, hits=0.999, live="all")


@pytest.mark.parametrize("case", list(CASES))
def test_probe_answers_as_the_old_loop_does_on_every_lane(case, low_floor):
    c = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case))
    built = _keys(rng, int(SLOTS * c["load"]))
    jt, types = _place(built)
    keys, valid = _probe_side(rng, built, c.get("lanes", 1 << 13), c["hits"], c["live"])
    rounds, matched = _check(jt, types, (keys,), valid)
    if c["live"] == "none":
        assert not matched.any() and rounds.sum() == 0
    elif c["hits"] == 1.0:
        assert np.array_equal(matched, np.asarray(valid))
    elif c["hits"] == 0.0:
        assert not matched.any()
    if case == "under-the-floor":
        assert rounds.shape == (1,)
    if case == "exhausted-wide":
        assert rounds.tolist() == [MAX_PROBES, 0, 0]
    if case == "exhausted-narrow":
        assert rounds.sum() == MAX_PROBES and rounds[0] < 8 and rounds[-1] > 0
    if case == "load0.9-allmiss-all":
        # after one round nine lanes of ten are unfinished, more than the n/4 the next
        # level holds: the wide loop must go on
        assert rounds[0] > 1


def test_two_key_columns_as_q93_packs_them(low_floor):
    """q93 joins on (item, ticket): two bigint columns, packed to one word a row."""
    rng = np.random.default_rng(93)
    item = rng.integers(1, 102_001, 1800).astype(np.int64)
    ticket = np.arange(1800, dtype=np.int64) // 12 + 1  # twelve lines a ticket
    item = (item // 12) * 12 + np.arange(1800) % 12  # ...of twelve different items
    jt, types = _build((jnp.asarray(item), jnp.asarray(ticket)))
    pick = rng.integers(0, 1800, 1 << 13)
    hit = rng.random(1 << 13) < 0.1
    keys = (jnp.asarray(np.where(hit, item[pick], item[pick] + 1)),
            jnp.asarray(ticket[pick]))
    rounds, matched = _check(jt, types, keys, jnp.ones((1 << 13,), bool))
    assert 0 < matched.sum() and rounds[1:].sum() > 0


# -- the rounds the lookup reports ----------------------------------------------------
def _half_full(rng, lanes):
    built = _keys(rng, SLOTS // 2)
    jt, types = _build((jnp.asarray(built),))
    keys, valid = _probe_side(rng, built, lanes, 0.1, "all")
    return jt, types, keys, valid


def test_above_the_floor_the_later_rounds_run_narrow(low_floor):
    jt, types, keys, valid = _half_full(np.random.default_rng(5), 1 << 13)
    rounds, _ = _check(jt, types, (keys,), valid)
    widths = hashjoin.probe_widths(1 << 13)
    assert widths == (1 << 13, 1 << 11, 1 << 7)
    total = int(rounds.sum())
    assert rounds[1:].sum() > 0 and 0 < rounds[0] < total
    # at load 0.5 a round halves what is unfinished: two wide rounds leave a quarter
    assert rounds[0] <= 3
    gathered = int((rounds * np.asarray(widths)).sum())
    assert (1 << 13) <= gathered < total * (1 << 13) // 2


def test_below_the_floor_the_lookup_is_the_one_loop_it_was():
    """The program's own floor, unpatched: a tier-1 page is under it."""
    lanes = 1 << 13
    assert lanes < hashing.NARROW_MIN_LANES
    assert hashjoin.probe_widths(lanes) == (lanes,)
    assert len(hashjoin.probe_widths(hashing.NARROW_MIN_LANES)) \
        == 1 + len(hashing.NARROW_SHIFTS)
    jt, types, keys, valid = _half_full(np.random.default_rng(6), lanes)
    rounds, _ = _check(jt, types, (keys,), valid)  # one level: exactly the old count
    assert rounds.shape == (1,) and rounds[0] > 2


def test_the_narrowed_loop_traces_under_shard_map_with_a_constant_key(low_floor):
    """The r05 case the ``vzero`` comment guards: a CONSTANT join key (unvarying) against
    a per-worker table, inside `shard_map` with the varying-axis check ON; the packed
    levels' carries have to inherit both operands' axes too."""
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as PS

    from trino_tpu.parallel.mesh import WORKER_AXIS, worker_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs four host devices")
    W, per, lanes = 4, 512, 2 * FLOOR
    rng = np.random.default_rng(37)
    mesh = worker_mesh(W)
    built = rng.choice(np.arange(1, 1 << 20), (W, per), replace=False).astype(np.int64)
    built[:, 0] = 1  # the constant key is in every worker's table
    varying = rng.integers(1, 1 << 20, (W, lanes)).astype(np.int64)
    varying[:, ::10] = built[:, rng.integers(0, per, -(-lanes // 10))]
    sharded = NamedSharding(mesh, PS(WORKER_AXIS))
    tables = np.stack([np.asarray(_place(built[w])[0].table) for w in range(W)])

    def frag(tables, pkeys):
        valid = jnp.ones((lanes,), bool)
        out = []
        for keys in (jnp.ones((lanes,), jnp.int64), pkeys[0]):
            slot, matched = hashjoin.probe_slots(tables[0], (keys,), (BIGINT,), valid)
            out += [slot[None], matched[None]]
        return tuple(out)

    f = shard_map(frag, mesh=mesh, in_specs=(PS(WORKER_AXIS), PS(WORKER_AXIS)),
                  out_specs=(PS(WORKER_AXIS),) * 4)
    cslot, cmatched, vslot, vmatched = map(np.asarray, jax.jit(f)(
        jax.device_put(jnp.asarray(tables), sharded),
        jax.device_put(jnp.asarray(varying), sharded)))
    # (the old loop has no such guard: it answers for each worker outside the mesh)
    for keys, slot, matched in ((np.ones_like(varying), cslot, cmatched),
                                (varying, vslot, vmatched)):
        want_slot, want_matched, _ = jax.vmap(old_loop)(
            jnp.asarray(tables), pack_keys((jnp.asarray(keys),), (BIGINT,))[0],
            jnp.ones((W, lanes), bool))
        assert np.array_equal(slot, want_slot) and np.array_equal(matched, want_matched)
    assert cmatched.all() and 0 < vmatched.sum() < vmatched.size
