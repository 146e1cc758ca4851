"""SPMD distributed executor over a worker mesh.

The reference distributes a fragmented plan as stages of HTTP-connected tasks
(scheduler/PipelinedQueryScheduler.java:163; exchange data plane SURVEY.md §3.4).  The TPU
re-design runs one SPMD program over a 1-D worker mesh via shard_map:

- **sharded scan** (≈ split/data parallelism, SourcePartitionedScheduler.java:55): each
  worker generates/reads its own equal-shaped split, offset by its mesh position;
- **streaming fragment** (scan+filter+project+broadcast-join probe) traces into ONE jitted
  per-worker step — same fusion as the local executor;
- **broadcast join** (FIXED_BROADCAST, DetermineJoinDistributionType.java:51): the build
  table is built once and closed over — shard_map replicates it to every worker (the
  all-gather the reference does by POSTing the build side to every task);
- **partial aggregation** accumulates into per-worker group tables with NO exchange of raw
  rows (reference: partial-aggregation stage inserted by AddExchanges.java:145);
- **final aggregation**: group-table *entries* are hash-exchanged all-to-all so each worker
  owns a disjoint key range, then merged (reference: FIXED_HASH exchange + final
  aggregation; ops/exchange.py is the PagePartitioner/ExchangeOperator analog).

Distributed-specific state (group tables) lives as [n_workers, ...] arrays sharded on the
leading axis, so the whole multi-batch loop stays jit-compiled with no host round-trips.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as PS

from ..execution import faults, tracing
from ..execution.tracing import (QueryCounters, maybe_span, record_join_build,
                                 record_mesh_fragment, record_mesh_scan_batch,
                                 record_page_cache, record_probe_exchange,
                                 record_rows_generated,
                                 record_shard_stats)
from ..ops import hashagg
from ..ops.arrays import append_rows, compact_rows
from ..ops.exchange import bucketize, exchange_all_to_all, partition_ids
from ..ops.hashing import EMPTY_KEY, pack_keys
from ..ops.hashjoin import (MultiJoinTable, _multi_build_step, build_insert,
                            build_table_init, expand_counts, multi_build,
                            probe, probe_slots)
from ..ops.window import _window_kernel, _window_spec_dicts
from ..page import Page, Schema
from ..parallel.mesh import WORKER_AXIS, worker_mesh
from ..sql import plan as P
from ..sql.ir import FieldRef, evaluate, evaluate_predicate
from .boundary import _host, _jit, _page_to_device
from .groupby import (DEFAULT_GROUP_CAPACITY, MAX_GROUP_CAPACITY, _MERGE_KIND,
                      _acc_input_expr, _accumulators_for, _finalize_aggs)
from .local_executor import LocalExecutor
from .pages import (MaterializedResult, _build_null_stats, _compact_part,
                    _gather_build, _host_page, _limit_page, _materialize,
                    _null_aware_anti, _sort_page, _split_base_rows, _topn_page)


def _route_rows(cols, nulls, valid, pid, n_parts: int, bucket: int, axis_name):
    """Hash-route one page of rows across the mesh: pack columns + present null
    masks, bucketize by partition id, all_to_all, and re-slot the null masks on
    the receive side.  The one routing protocol both the partitioned-join build
    and its per-batch probe exchange speak.

    Returns (cols, nulls, valid, counts): ``counts`` [n_parts] is the rows this
    worker had BOUND for each destination (``bucketize``).  More than
    ``bucket`` of them is the SEND-side drop: the stream contract carries it
    to the driver, which retries at a bigger bucket — exchange backpressure,
    re-planned as a host-level retry."""
    payload = list(cols)
    null_slots = []
    for ci, nm in enumerate(nulls):
        if nm is not None:
            null_slots.append(ci)
            payload.append(nm)
    packed, pvalid, counts = bucketize(tuple(payload), valid, pid, n_parts, bucket)
    recv, recv_valid = exchange_all_to_all(packed, pvalid, axis_name, n_parts)
    rcols = list(recv[:len(cols)])
    rnulls = [None] * len(cols)
    for j, ci in enumerate(null_slots):
        rnulls[ci] = recv[len(cols) + j]
    return rcols, rnulls, recv_valid, counts


# The side channel of a stream, the ``of`` of ``transform -> (cols, nulls,
# valid, of)``: ONE int64 vector a worker.  Slot 0 is the overflow flag (an
# exchange or expansion bucket of the fragment dropped rows: the consumer
# retries the whole run one rung up, _retry_exchange); then, for each probe
# exchange of the fragment in plan order (_DStream.probes), _SIDE_FIELDS
# slots: the largest count it had bound for one destination (``need``), the
# bucket it ran at, the rows it routed and the lanes its receive tensor held.
# A consumer carries it across its batches (_side_merge) to the flags pull it
# makes anyway, where _settle reads it: no pull and no dispatch of its own.
_SIDE_FIELDS = 4


def _false(valid):
    """A worker-VARYING False scalar: under shard_map a fresh constant is
    unvarying and cannot join varying carries/outputs; deriving from the data
    inherits the axis."""
    return jnp.any(valid) & False


def _side(valid):
    """The channel of a fragment with no exchange yet."""
    return _false(valid).astype(jnp.int64)[None]


def _side_flag(of, flag):
    """``of`` with the overflow flag raised where ``flag`` is."""
    return of.at[0].max(flag.astype(of.dtype))


def _side_probe(of, counts, bucket: int):
    """``of`` with one probe exchange more: ``counts`` as _route_rows gave
    them, ``bucket`` the static bucket the exchange ran at."""
    need = jnp.max(counts).astype(of.dtype)
    here = need * 0  # the statics below take the worker axis from the data
    rec = jnp.stack([need, here + bucket, jnp.sum(counts).astype(of.dtype),
                     here + counts.shape[0] * bucket])
    return jnp.concatenate([_side_flag(of, need > bucket), rec])


def _side_merge(acc, of, xp=jnp):
    """Two batches' channels as one: flag, need and bucket by high-water, rows
    and lanes by sum (``xp=np`` for the host-spool paths)."""
    probes = (acc.shape[-1] - 1) // _SIDE_FIELDS
    summed = np.asarray([False] + [False, False, True, True] * probes)  # host-ok: static mask
    return xp.where(summed, acc + of, xp.maximum(acc, of))


def _learned_bucket(need: int) -> int:
    """The probe bucket a kept ``need`` asks for: the smallest power of two at
    or above need * 5 / 4, never under 1,024 (_probe_bucket holds it to the
    input's lanes)."""
    return max(1 << (max(need * 5 // 4, 1) - 1).bit_length(), 1024)


def _exchange_fault(point: str, site: str):
    """Chaos chokepoint for the mesh exchange — the ``exchange_write`` /
    ``exchange_read`` fault points previously fired only on the HTTP
    SpoolingExchange.  ``error``/``fatal``/``delay`` behave as everywhere
    else (raise through maybe_inject / sleep); any RETURNED action
    (drop/deny) also raises typed, because a mesh all-to-all is one SPMD
    program — it cannot drop a commit or defer a reader the way the spooled
    exchange can, so the clean-failure contract is a typed error."""
    act = faults.maybe_inject(point, site)
    if act:
        raise faults.InjectedFaultError(
            f"injected {point}:{act} at {site}: the mesh exchange cannot "
            "drop or defer rows")


# (probe_bucket_factor, expand_factor) retry ladder: probe exchange buckets
# start at ~2n/W (factor 2) instead of the always-safe n, trading a W/2-times
# smaller receive tensor for a rare retry under hash skew; expansion buckets
# for multi-match joins grow alongside.  ``None`` = exact (bucket = n, no
# probe-side overflow possible).
_EXCHANGE_LADDER = ((2, 4), (4, 8), (None, 16), (None, 64))

__all__ = ["DistributedExecutor"]

# a batch whose live rows fit a 16th of its lanes is packed before the group-by
# insert: a TPU scatter costs by WIDTH, sink writes included (the local
# executor's _run_hash_inserts makes the same cut, from a pulled count)
_SPARSE_INSERT_SHIFT = 4
# the merge's fresh table: a direct-indexed state can be a handful of slots
_MERGE_MIN_SLOTS = 64


def _groupby_insert_live(state, key_vals, key_types, valid, inputs, acc_kinds):
    """``hashagg.groupby_insert`` over one worker's batch, at the width of its
    live rows where they are few.  After a selective join (q3 keeps a 200th
    of the lanes its probe exchange receives) every scatter of the insert
    (one a key column and an accumulator, 74-290 ns a LANE) would run at
    full width; ``compact_rows`` is a sort and gathers.  The choice is a
    ``lax.cond`` on the live count, inside the step: no pull, no second
    program, and a dense batch pays one reduction."""
    n = valid.shape[0]
    bucket = n >> _SPARSE_INSERT_SHIFT
    if bucket < 1024:
        return hashagg.groupby_insert(state, key_vals, key_types, valid, inputs,
                                      acc_kinds)
    flat = tuple(key_vals) + tuple(x for vn in inputs for x in vn)
    nk = len(key_vals)

    def sparse(state):
        packed, total = compact_rows(flat, valid, bucket)
        live = jnp.arange(bucket, dtype=jnp.int32) < total
        rest = packed[nk:]
        cinputs = [(rest[2 * i], rest[2 * i + 1]) for i in range(len(inputs))]
        return hashagg.groupby_insert(state, packed[:nk], key_types, live,
                                      cinputs, acc_kinds)

    def dense(state):
        return hashagg.groupby_insert(state, key_vals, key_types, valid, inputs,
                                      acc_kinds)

    return jax.lax.cond(jnp.sum(valid, dtype=jnp.int32) <= bucket, sparse,
                        dense, state)


def _eval_project(exprs, cols, nulls, shape):
    """Evaluate projection expressions; scalar results broadcast to row shape."""
    out = [evaluate(e, cols, nulls) for e in exprs]
    vs = tuple(jnp.broadcast_to(v, shape) if v.ndim == 0 else v for v, _ in out)
    ns = tuple(None if n is None
               else (jnp.broadcast_to(n, shape) if n.ndim == 0 else n)
               for _, n in out)
    return vs, ns


def _resolve_project_dicts(node: P.Project, child_dicts):
    """Output dictionaries: planner-declared, else inherited through FieldRefs."""
    planner_dicts = node.dicts or tuple(None for _ in node.exprs)
    return tuple(
        pd if pd is not None
        else (child_dicts[e.index] if isinstance(e, FieldRef) else None)
        for pd, e in zip(planner_dicts, node.exprs))


def _pad_page(page: Page, cap: int) -> Page:
    """Pad a page to at least `cap` rows (new rows invalid) — lets zero-row build
    sides flow through the fixed-shape probe machinery."""
    n = page.capacity
    if n >= cap:
        return page
    cols = tuple(jnp.concatenate([c, jnp.zeros((cap - n,), c.dtype)]) for c in page.columns)
    nulls = tuple(None if m is None else jnp.concatenate([m, jnp.zeros((cap - n,), bool)])
                  for m in page.null_masks)
    valid = jnp.concatenate([page.valid_mask(), jnp.zeros((cap - n,), bool)]) \
        if n else jnp.zeros((cap,), bool)
    return Page(page.schema, cols, nulls, valid)


def _has_duplicate_keys(build_page: Page, key_channels, key_types) -> bool:
    """Duplicate-key check on the materialized build page (cheaper than
    building a throwaway device hash table just to read its dup counter):
    ONE jitted sort-reduction, and a single boolean pulled.  A fingerprint
    collision reads as a duplicate, the conservative direction (caller falls
    back to the general multi-match path)."""
    keys = tuple(build_page.columns[ch] for ch in key_channels)
    kmasks = tuple(build_page.null_masks[ch] for ch in key_channels
                   if build_page.null_masks[ch] is not None)

    def dupcheck(keys, kmasks, valid):
        kvalid = valid
        for nm in kmasks:
            kvalid = kvalid & ~nm
        packed, _ = pack_keys(keys, key_types)
        # valid rows first, sorted by packed key: any adjacent equal pair
        # of valid keys is a duplicate
        order = jnp.lexsort((packed, (~kvalid).astype(jnp.int8)))
        sp, sv = packed[order], kvalid[order]
        return jnp.any((sp[1:] == sp[:-1]) & sv[1:] & sv[:-1])

    dup = _jit(dupcheck, site="dist.build.dupcheck")(
        keys, kmasks, build_page.valid_mask())
    return bool(_host([dup], site="dist.build.dupcheck")[0])


def _multi_probe_expand(node, mt, build_key_types, cols, nulls, valid,
                        expand_size: int, build_null_stats, semi: bool):
    """Per-shard multi-match probe: slot-grouped lookup (ops/hashjoin
    MultiJoinTable — the position-links analog) + searchsorted expansion at a
    STATIC expansion bucket.  Data-dependent output size cannot sync to the
    host inside a shard_map step, so a too-small bucket reports overflow
    through the stream contract instead (driver retries bigger).  Returns
    (cols, nulls, valid, oflow); traced (runs inside the fragment jit)."""
    keys = tuple(cols[i] for i in node.left_keys)
    kvalid = valid
    for i in node.left_keys:
        if nulls[i] is not None:
            kvalid = kvalid & ~nulls[i]
    # probe_slots (and bucketize below in the partitioned path) pick their
    # round-13 backend (XLA while_loop vs Pallas kernel) at TRACE time from
    # static shapes + use_pallas(), so the choice bakes into the fragment
    # executable exactly like every other plan-shaping fact; inside shard_map
    # the Pallas path has no while_loop carry to seed, but table operands
    # still thread through _Stream.aux as JIT arguments (the round-5 rule)
    slot, matched = probe_slots(mt.table, keys, build_key_types, kvalid)
    matched = matched & kvalid
    cnt = jnp.where(matched, mt.counts[slot], 0)
    if semi and node.filter is None:
        # existence test only: no expansion needed
        if node.kind == "semi":
            out_valid = valid & matched
        else:
            out_valid = _null_aware_anti(node, valid & ~matched, nulls,
                                         *build_null_stats)
        return tuple(cols), tuple(nulls), out_valid, _false(valid)
    n = valid.shape[0]
    if node.kind == "left":
        out_cnt = jnp.where(valid, jnp.maximum(cnt, 1), 0)
    else:
        out_cnt = cnt
    incl = jnp.cumsum(out_cnt, dtype=jnp.int32)
    oflow = incl[n - 1] > expand_size
    pidx, k, in_range = expand_counts(incl, out_cnt, expand_size)
    is_match = matched[pidx] & (k < cnt[pidx]) & in_range
    brow = mt.order[jnp.clip(mt.starts[slot[pidx]] + k, 0,
                             mt.order.shape[0] - 1)]
    brow = jnp.where(is_match, brow, 0)
    ocols = tuple(c[pidx] for c in cols) \
        + tuple(c[brow] for c in mt.build_columns)
    onulls = tuple(None if nm is None else nm[pidx] for nm in nulls) \
        + tuple(None if nm is None else nm[brow]
                for nm in mt.build_null_masks)
    if node.filter is not None:
        passed = evaluate_predicate(node.filter, ocols, onulls, is_match)
    else:
        passed = is_match
    if semi:
        mark = jnp.zeros((n,), jnp.int32).at[pidx].max(
            passed.astype(jnp.int32)).astype(bool)
        if node.kind == "semi":
            out_valid = valid & mark
        else:
            out_valid = _null_aware_anti(node, valid & ~mark, nulls,
                                         *build_null_stats)
        return tuple(cols), tuple(nulls), out_valid, oflow
    if node.kind == "left":
        any_pass = jnp.zeros((n,), jnp.int32).at[pidx].max(
            passed.astype(jnp.int32)).astype(bool)
        keep = passed | ((k == 0) & ~any_pass[pidx] & in_range & valid[pidx])
        onulls = onulls[:len(cols)] + tuple(
            (jnp.zeros_like(passed) if nm is None else nm) | ~passed
            for nm in onulls[len(cols):])
        return ocols, onulls, keep, oflow
    return ocols, onulls, passed, oflow  # inner


def _slice_batch(batch_g):
    """Per-worker slice of a scan-batch pytree inside a shard_map body: a
    generator scan's batch is a resident (cols, valid) pytree of [W, lanes]
    arrays (_ShardedScan), a host-fed scan's a (cols, nulls, valid) pytree
    of [W, cap] arrays."""
    return jax.tree.map(lambda x: x[0], batch_g)


def _stream_batch(stream, lo_g, aux):
    """One per-worker scan+transform step inside a shard_map body."""
    cols, nulls, valid = stream.scan_fn(_slice_batch(lo_g))
    return stream.transform(cols, nulls, valid, aux)


class _HostFedBatches:
    """Lazy sequence of stacked scan batches for connectors WITHOUT traced
    on-device generation (parquet/hive/delta/iceberg/memory/...): batch b
    host-decodes W splits, pads rows to a pow2 bucket (bounded XLA shape
    classes) and stacks [W, cap] arrays — the fixed-shape re-entry that feeds
    file splits into the same shard_map/all-to-all machinery the generator
    scans use.  Reference: SourcePartitionedScheduler.java:55 assigning any
    connector's splits across nodes; here the split queue is consumed on the
    coordinator host and sharded onto the mesh.  Decoding is deferred to
    access (and the last batch cached) so retry ladders and capacity growths
    re-iterate without holding the whole table in host RAM."""

    def __init__(self, conn, table, columns, dtypes, W, start=0):
        self.conn, self.table = conn, table
        self.columns, self.dtypes, self.W = tuple(columns), tuple(dtypes), W
        self.splits = list(conn.splits(table))
        self.start = start
        self._n = max(0, -(-(len(self.splits) - start * W) // W))
        self._cache: dict = {}

    def __len__(self):
        return self._n

    def __iter__(self):
        return (self[i] for i in range(self._n))

    def __getitem__(self, i):
        if isinstance(i, slice):
            lo, hi, st = i.indices(self._n)
            assert st == 1 and hi == self._n, "only tail slices are used"
            return _HostFedBatches(self.conn, self.table, self.columns,
                                   self.dtypes, self.W, self.start + lo)
        if i < 0 or i >= self._n:
            raise IndexError(i)
        hit = self._cache.get(i)
        if hit is not None:
            return hit
        b = self._build(i)
        self._cache = {i: b}  # most-recent only: bounded host RAM
        return b

    def _build(self, i):
        W = self.W
        base = (self.start + i) * W
        group = self.splits[base:base + W]
        pages = [self.conn.generate(s, list(self.columns)) for s in group]
        rows = [p.capacity for p in pages]
        cap = max(1 << max(max(rows, default=1) - 1, 1).bit_length(), 1024)
        # ONE batched pull for the whole W-split group (was 2-3 loose pulls
        # per column, then one _host per page): each _host call is a
        # blocking device->host sync, so the group's W pages share one
        layout, flat = [], []
        for p in pages:
            nm_idx = [i for i, m in enumerate(p.null_masks) if m is not None]
            flat += list(p.columns) + [p.null_masks[i] for i in nm_idx]
            if p.valid is not None:
                flat.append(p.valid)
            layout.append((len(p.columns), nm_idx, p.valid is not None))
        got = _host(flat, site="dist.hostfed.pull")
        hpages, pos = [], 0
        for (ncols, nm_idx, has_valid), p in zip(layout, pages):
            pcols = got[pos:pos + ncols]
            pos += ncols
            pnulls = [None] * ncols
            for i in nm_idx:
                pnulls[i] = got[pos]
                pos += 1
            pv = got[pos] if has_valid else np.ones((p.capacity,), bool)
            pos += 1 if has_valid else 0
            hpages.append((pv, pcols, pnulls))
        cols, nulls = [], []
        for ci, dt in enumerate(self.dtypes):
            arr = np.zeros((W, cap), dt)
            nm = np.zeros((W, cap), bool)
            for w, (_, pcols, pnulls) in enumerate(hpages):
                arr[w, :rows[w]] = pcols[ci].astype(dt, copy=False)
                m = pnulls[ci]
                if m is not None:
                    nm[w, :rows[w]] = m
            cols.append(arr)
            nulls.append(nm)
        valid = np.zeros((W, cap), bool)
        for w, (pv, _, _) in enumerate(hpages):
            valid[w, :rows[w]] = pv
        return (tuple(cols), tuple(nulls), valid)


class _ShardedScan:
    """A generator connector's table scan, resident on the mesh: batch b is a
    (cols, valid) pytree of [W, lanes] arrays sharded on the worker axis
    (split b*W+d is chip d's row), generated by ONE jitted shard_map program
    a table and column set and consumed by the fragment steps as an
    argument.  The whole scan is one entry of the engine's page cache,
    accounted at the bytes ONE chip holds (a W-th of it: the budget is a
    chip's), so it is looked up once a statement, generated only when the
    pool does not hold it (first run, after an eviction or invalidation),
    and streamed batch by batch, never pinned, when the pool is off or the
    entry passes its cap.  The same sequence protocol as _HostFedBatches:
    retry ladders and capacity growths re-iterate it.  What it holds is
    single-statement state: ``release`` at the statement's end."""

    def __init__(self, ex, conn, catalog, table, columns, splits):
        self.ex, self.conn = ex, conn
        self.catalog, self.table, self.columns = catalog, table, tuple(columns)
        W = ex.n_workers
        self.rows = splits[0].hi - splits[0].lo  # lanes a worker a batch
        self._n = len(splits) // W
        self._lo = [np.asarray([splits[b * W + d].lo for d in range(W)],  # host-ok: split list
                               dtype=np.int64) for b in range(self._n)]
        base = _split_base_rows(conn, table, splits)
        self._base_rows = [sum(base[b * W:(b + 1) * W]) for b in range(self._n)]
        self._splits = splits
        self._held = None    # the resident batches, while a statement runs
        self._acc = None     # batches gathered for the pool, in order
        self._looked = False

    def __len__(self):
        return self._n

    def __iter__(self):
        return (self[i] for i in range(self._n))

    def __getitem__(self, i):
        if isinstance(i, slice):
            lo, hi, st = i.indices(self._n)
            assert st == 1 and hi == self._n, "only tail slices are used"
            return [self[j] for j in range(lo, hi)]
        if i < 0 or i >= self._n:
            raise IndexError(i)
        self._lookup()
        resident = self._held is not None
        record_mesh_scan_batch(resident)
        if resident:
            return self._held[i]
        batch = self._generate(i)
        if self._acc is not None and i == len(self._acc):
            self._gather(batch)
        return batch

    def release(self):
        self._held = self._acc = None
        self._looked = False

    def _key(self):
        bp = self.ex.buffer_pool
        if not self._n or bp is None or not self.ex.local._page_cache_on() \
                or not bp.cacheable(self.conn):
            return None
        key = bp.page_key(self.catalog, self.conn, self.table, self._splits,
                          self.columns)
        # not a Page: a local scan of the same splits must never read it
        return key[:3] + (("mesh", self.ex.n_workers) + key[3],) + key[4:]

    def _lookup(self):
        if self._looked:
            return
        self._looked = True
        self.ex._scans.append(self)
        key = self._key()
        if key is None:
            return
        site = f"dist.scan.{self.table}.cache"
        hit = self.ex.buffer_pool.get_page(key)
        if hit is not None:
            self._held, nbytes = hit
            record_page_cache(hits=1, bytes_saved=nbytes, site=site)
            return
        record_page_cache(misses=1, site=site)
        self._acc = []

    def _generate(self, i):
        gen = self.ex._scan_generator(self.conn, self.table, self.columns,
                                      self.rows)
        faults.maybe_inject("generate", f"scan.{self.table}")
        with maybe_span("split-generation", table=self.table, batch=i):
            lo = jax.device_put(self._lo[i], self.ex._sharded)  # device-ok: mesh-sharded placement
            batch = gen(lo)
        record_rows_generated(self._base_rows[i])
        return batch

    def _gather(self, batch):
        """One more batch of a scan the pool does not hold yet; the last one
        stores the entry.  A scan whose chip's share passes the entry cap
        pins nothing, as the local path's does not."""
        bp = self.ex.buffer_pool
        site = f"dist.scan.{self.table}.cache"
        per_chip = sum(int(a.nbytes) for a in jax.tree.leaves(batch)) \
            // self.ex.n_workers
        if per_chip * self._n > bp.page_entry_cap():
            self._acc = None
            record_page_cache(over_cap=1, site=site)
            return
        self._acc.append(batch)
        if len(self._acc) < self._n:
            return
        self._held, self._acc = tuple(self._acc), None
        try:
            stored = bp.put_page(self._key(), self._held,
                                 nbytes=per_chip * self._n)
        except Exception:  # noqa: BLE001 - an injected cache_store fault
            stored = False
        if not stored:
            # uncached, not failed: the next statement regenerates, and says so
            record_page_cache(store_failed=1, site=site)


def _collation_luts(sort_keys, fields, dicts):
    """id -> collation-rank LUTs for dictionary-encoded sort keys: ids are
    assigned in insertion order, so device sorts must compare decoded-value
    ranks instead (host-built once per query)."""
    luts = {}
    for sk in sort_keys:
        d = dicts[sk.channel]
        if d is not None and fields[sk.channel].type.is_string:
            vals = np.asarray(d.values).astype(str)  # host-ok: dict values
            rank = np.empty(len(vals), np.int64)
            rank[np.argsort(vals)] = np.arange(len(vals))
            luts[sk.channel] = jnp.asarray(rank)
    return luts


def _lex_indices(sort_keys, luts_t, cols, nulls, valid):
    """Full stable sort permutation by sort_keys with invalid rows last — the
    lex construction the distributed topN and full-sort paths share."""
    lex = []  # jnp.lexsort: LAST key is the primary sort key
    for sk in reversed(sort_keys):
        c = cols[sk.channel]
        if sk.channel in luts_t:
            lut = luts_t[sk.channel]
            c = lut[jnp.clip(c, 0, lut.shape[0] - 1)]
        if c.dtype == jnp.bool_:
            c = c.astype(jnp.int8)
        if not sk.ascending:
            # bitwise complement is order-reversing AND total on ints
            # (arithmetic negation wraps -INT64_MIN back to itself)
            c = ~c if jnp.issubdtype(c.dtype, jnp.integer) else -c
        nm = nulls[sk.channel]
        ni = nm.astype(jnp.int8) if nm is not None \
            else jnp.zeros(c.shape, jnp.int8)
        if sk.nulls_first:
            ni = -ni
        lex.append(c)
        lex.append(ni)  # null placement outranks the value for this key
    lex.append(~valid)  # invalid rows sort last, whatever the keys say
    return jnp.lexsort(tuple(lex))


def _stack_shards(per_cols, per_nulls, counts, fields):
    """Pad each worker's host buffers to a common length and stack into
    [W, nmax] arrays (the fixed-shape re-entry into the mesh)."""
    W = len(per_cols)
    nmax = max(max(counts), 1)
    cols_g, nulls_g = [], []
    for i, f in enumerate(fields):
        dt = np.dtype(f.type.dtype)
        cols_g.append(np.stack([
            np.concatenate([per_cols[w][i].astype(dt, copy=False),
                            np.zeros((nmax - counts[w],), dt)])
            for w in range(W)]))
        nulls_g.append(np.stack([
            np.concatenate([per_nulls[w][i],
                            np.zeros((nmax - counts[w],), bool)])
            for w in range(W)]))
    valid_g = np.stack([
        np.concatenate([np.ones((counts[w],), bool),
                        np.zeros((nmax - counts[w],), bool)])
        for w in range(W)])
    return tuple(cols_g), tuple(nulls_g), valid_g, nmax


def _page_from_shards(schema, cols_g, nulls_g, counts, keep=None):
    """Reassemble [W, nmax] shard results into one flat page: worker w
    contributes its counts[w] head rows, workers concatenated in mesh order.

    All-device shards assemble ON DEVICE (one fused order-preserving
    compaction over the flattened [W*nmax] layout — compact_rows keeps
    arrival order, so the result is byte-identical to the host concat) and
    the page never round-trips.  Mixed/host shards take the host concat,
    staged back through ``_page_to_device`` (counted, injectable H2D).
    ``keep(what, make)`` is the caller's fragment store for the jitted
    concat (its shape facts are the key)."""
    W = len(counts)
    cols_l, nulls_l = list(cols_g), list(nulls_g)
    if cols_l and all(isinstance(a, jax.Array) for a in cols_l + nulls_l):
        total = int(sum(counts))
        nmax = cols_l[0].shape[1]
        counts_t = jnp.asarray(counts).astype(jnp.int64)

        def concat(cols_t, nulls_t, counts_t):
            valid = (jnp.arange(nmax)[None, :]
                     < counts_t[:, None]).reshape(-1)
            arrs = tuple(c.reshape(-1) for c in cols_t) \
                + tuple(m.reshape(-1) for m in nulls_t)
            packed, _ = compact_rows(arrs, valid, max(total, 1))
            return packed[:len(cols_t)], packed[len(cols_t):]

        make = partial(_jit, concat, site="dist.shards.concat")
        fn = make() if keep is None else keep(
            ("shards.concat", total, nmax, len(cols_l)), make)
        out_cols, out_nulls = fn(tuple(cols_l), tuple(nulls_l), counts_t)
        if total == 0:
            # compact_rows needs out_len >= 1; trim the placeholder row
            out_cols = tuple(c[:0] for c in out_cols)
            out_nulls = tuple(m[:0] for m in out_nulls)
        return Page(schema, tuple(out_cols), tuple(out_nulls), None)
    out_cols, out_nulls = [], []
    got = _host(list(cols_l) + list(nulls_l),
                site="dist.shards.pull")  # one batched shard pull
    for a_np in got[:len(cols_l)]:
        out_cols.append(np.concatenate([a_np[w][:counts[w]] for w in range(W)]))
    for m_np in got[len(cols_l):]:
        out_nulls.append(np.concatenate([m_np[w][:counts[w]] for w in range(W)]))
    return _page_to_device(Page(
        schema, tuple(out_cols),
        tuple(m if m.any() else None for m in out_nulls), None))


@dataclasses.dataclass
class _DStream:
    """A distributed streaming fragment: per-worker scan source + fused transform."""

    schema: Schema
    dicts: tuple
    scan_lo_batches: list  # list of np.ndarray [n_workers] of per-worker row offsets
    scan_fn: Callable  # (lo_scalar) -> (cols, nulls, valid); traced per worker
    transform: Callable  # (cols, nulls, valid, aux) -> (cols, nulls, valid, of)
    # of: the side channel (_side): per worker, the flag that an exchange or
    # expansion bucket in the fragment dropped rows this batch (the consumer
    # retries the whole run at a bigger bucket, _retry_exchange) and what
    # each probe exchange carried
    aux: tuple = ()  # device state (join tables) threaded as a jit ARGUMENT —
    # closed over, a table is baked into the executable as a constant: every
    # new table is a recompile and its bytes live in the program
    aux_specs: object = PS()  # shard_map in_specs pytree (prefix) for aux:
    # PS() = replicated (broadcast tables); exchange-routed partitioned-join
    # tables are sharded [W, ...] on the worker axis and carry PS(WORKER_AXIS)
    probes: tuple = ()  # the Join nodes whose probe exchanges report on ``of``
    key: tuple = ()  # (ladder rung, learned probe buckets) it was compiled at:
    # part of the kept key of every step over it (_fragment, _step)


def _row_bytes(fields) -> int:
    """Bytes of one row of ``fields`` as an exchange routes it: the columns'
    widths (an object-dtype field never reaches the device)."""
    return sum(np.dtype(f.type.dtype).itemsize for f in fields
               if np.dtype(f.type.dtype) != object)


def _host_spooled(stream: _DStream) -> bool:
    """The ONE rule for where a blocking consumer's rows are received.  Routed
    (or kept) rows append into carried [W, cap] device buffers INSIDE the
    step's shard_map and the consumer reads sharded device buffers: per-run
    host traffic is scalar cursor/overflow flags.  The per-batch host spool
    takes a stream the device buffers cannot hold: one with no batch (nothing
    to size a receive buffer from) or with an object-dtype field (exact
    wide-decimal values must never reach the device)."""
    return not len(stream.scan_lo_batches) or any(
        np.dtype(f.type.dtype) == object for f in stream.schema.fields)


class DistributedExecutor:
    """Executes plans SPMD across the mesh; falls back to LocalExecutor for blocking
    sub-plans (join build sides, small inputs).

    It outlives a statement (the engine keeps one a mesh): what a plan node
    compiled to is KEPT under the node's identity with a strong reference,
    as LocalExecutor._stream_cache keeps its streams: the _DStream with its
    join tables in ``aux``, the jitted shard_map steps over it, the build
    side's page and facts, the rung of _EXCHANGE_LADDER and the group-by
    capacity that last held, the rows a partitioned join's probe exchange
    last had bound for one destination (its bucket follows them, _settle).
    A replay of one plan compiles nothing, builds nothing and pulls only the
    flags its exchanges need.  ``forget_plan``
    (the engine's version-stale path) and the engine's ``_invalidate`` (which
    drops the executor) are what forgets.  All of it is single-statement
    state: the engine runs one statement at a time under ``statement_lock``."""

    def __init__(self, catalogs: dict, mesh=None, partition_threshold: int = 1 << 17,
                 buffer_pool=None):
        self.catalogs = catalogs
        self.mesh = mesh if mesh is not None else worker_mesh()
        self.n_workers = self.mesh.devices.size
        self._sharded = NamedSharding(self.mesh, PS(WORKER_AXIS))
        self.statement_lock = threading.Lock()
        # the engine's device buffer pool: resident sharded scans are entries
        # of its page tier, and the embedded LocalExecutor's build sides read
        # and store their scans and tables there like any pooled executor's
        self.buffer_pool = buffer_pool
        # blocking sub-plans (join builds, small fragments) run here; the
        # engine sets its per-query knobs (dispatch_batch, page_cache) for the
        # statement, as it does on a pooled executor.  The SPMD paths are
        # already whole-mesh batched (one dispatch per batch of W splits)
        self.local = LocalExecutor(catalogs, buffer_pool=buffer_pool)
        # build sides at/above this row count join PARTITIONED (all-to-all probe
        # exchange) instead of broadcast (reference: DetermineJoinDistributionType's
        # size-based choice, iterative/rule/DetermineJoinDistributionType.java:51)
        self.partition_threshold = partition_threshold
        self._rung = 0
        self._probe_factor, self._expand_factor = _EXCHANGE_LADDER[0]
        # (id(node), what...) -> (node, value): everything kept for a plan
        # node (class docstring).  Build artifacts (pages, join tables, the
        # facts pulled from them) are rung-invariant: the retry ladder
        # recompiles only the probe side
        self._kept: dict = {}
        # (catalog, table, columns, lanes) -> the jitted shard_map generator
        self._generators: dict = {}
        self._scans: list = []  # _ShardedScans this statement touched
        self.exec_trace: list = []
        self._decline_reason = None
        # per-query device-boundary counters: mesh dispatches/pulls record
        # exactly like the local executor's so distributed EXPLAIN ANALYZE and
        # the engine totals see the SPMD half too (sites carry dist.* tags)
        self.counters = QueryCounters()
        # round 20: per-exchange shard skew keyed by plan-node id — the map
        # EXPLAIN ANALYZE's per-node [skew: ...] annotations and the plan-
        # history feed read.  Records are the SAME dicts appended to
        # counters.shard_stats; derived purely from the flag/occupancy pulls
        # the exchange already makes (zero new warm pull sites).
        self.skew_by_node: dict = {}

    # ------------------------------------------------------------------ public
    def execute(self, node: P.PlanNode) -> MaterializedResult:
        self.exec_trace = []  # [(node label, mode, reason)] — runtime truth of
        # which fragments ran on the mesh vs fell back (VERDICT r3 weak #3:
        # silent local fallback); EXPLAIN ANALYZE prints it
        self._decline_reason = None
        self.skew_by_node = {}
        self.counters.reset()
        try:
            with tracing.track_counters(self.counters):
                page, dicts = self._execute_to_page(node)
                return _materialize(page, dicts)
        finally:
            # blocking sub-plans run on the embedded LocalExecutor, which may
            # start prefetch producers: stop them on error paths too
            self.local.close_producers()
            # the resident batches stay the pool's to evict between statements
            for scan in self._scans:
                scan.release()
            self._scans = []

    # ------------------------------------------------------------ what is kept
    def _held(self, node, what: tuple):
        """The kept (node, value) entry of (node, what), or None."""
        hit = self._kept.get((id(node),) + what)
        return hit if hit is not None and hit[0] is node else None

    def _keep(self, node, what: tuple, make):
        """``make()`` once for (node, what), then the kept value."""
        hit = self._held(node, what)
        if hit is None:
            hit = self._kept[(id(node),) + what] = (node, make())
        return hit[1]

    def _fragment(self, node) -> Optional[_DStream]:
        """The compiled stream of ``node`` at the current rung and the probe
        buckets learned along its spine, kept; None (with its reason) when it
        has no distributable scan spine.  A fragment whose probe exchange
        learned a narrower bucket is a kept fragment of its own, compiled by
        the execution right after the one that learned.  THE lookup the
        fragment counters and the ``mesh.fragment`` span read: one a
        consumer's attempt."""
        what = ("stream", self._rung, self._narrowed(node))

        def compile_fragment():
            stream = self._compile_stream(node)
            if stream is not None:
                stream = dataclasses.replace(stream, key=what[1:])
            return stream, self._decline_reason

        hit = self._held(node, what) is not None
        with maybe_span("mesh.fragment", hit=hit, node=type(node).__name__):
            record_mesh_fragment(hit)
            stream, reason = self._keep(node, what, compile_fragment)
        if stream is None and self._decline_reason is None:
            self._decline_reason = reason
        return stream

    def _step(self, node, stream: _DStream, what: tuple, make):
        """A jitted step of ``node`` over ``stream``, kept under what the
        stream was compiled at."""
        return self._keep(node, what + stream.key, make)

    def forget_plan(self, plan: P.PlanNode) -> None:
        """Drop what is kept for a plan the engine is replacing or will not
        replay (LocalExecutor.forget_plan's contract)."""
        ids = set()

        def walk(n):
            ids.add(id(n))
            for c in n.children:
                walk(c)

        walk(plan)
        for key in [k for k in list(self._kept) if k[0] in ids]:
            self._kept.pop(key, None)
        self.local.forget_plan(plan)

    def _scan_generator(self, conn, table, columns, rows):
        """The one generator program of a table and column set: every worker
        generates its split of ``rows`` lanes at its own offset."""
        key = (id(conn), table, tuple(columns), rows)
        hit = self._generators.get(key)
        if hit is not None and hit[0] is conn:
            return hit[1]

        @partial(shard_map, mesh=self.mesh, in_specs=PS(WORKER_AXIS),
                 out_specs=PS(WORKER_AXIS))
        def generate(lo_g):
            cols, valid = conn.generate_traced(table, lo_g[0], rows, columns)
            if valid is None:
                valid = jnp.ones(cols[0].shape, bool)
            # a constant mask is unvarying: derive the worker axis from lo
            valid = valid | (lo_g[0] < 0)
            return tuple(c[None] for c in cols), valid[None]

        fn = _jit(generate, site="dist.scan.generate")
        self._generators[key] = (conn, fn)
        return fn

    def _decline(self, node, reason: str):
        """Record why a fragment cannot compile for the mesh (deepest cause
        wins: the first decline bubbling out of a recursive compile)."""
        if self._decline_reason is None:
            self._decline_reason = f"{type(node).__name__}: {reason}"
        return None

    def _trace(self, node, mode: str, reason: str = None):
        label = type(node).__name__
        if isinstance(node, P.TableScan):
            label = f"TableScan[{node.table}]"
        self.exec_trace.append((label, mode, reason))

    def _take_decline(self) -> str:
        r = self._decline_reason or "fragment shape not distributable"
        self._decline_reason = None
        return r

    def _note_skew(self, site: str, node, per_worker, wall_s: float,
                   kind: str = "exchange", fields=None, row_bytes=None):
        """Fold an already-pulled per-worker load vector into the query's
        shard_stats and key it by plan node for EXPLAIN ANALYZE (round 20).
        ``per_worker`` must be host ints the caller already synced — this is
        pure host arithmetic, never a new pull or dispatch."""
        bpr = row_bytes
        if fields:
            bpr = _row_bytes(fields) or None
        rec = record_shard_stats(
            site, per_worker, wall_s=wall_s, kind=kind,
            op=None if node is None else type(node).__name__,
            bytes_per_row=bpr)
        if node is not None and rec is not None:
            self.skew_by_node[id(node)] = rec
        return rec

    # ---------------------------------------------------------------- retries
    def _retry_exchange(self, node, run_once):
        """The overflow side-channel's host half: run a compiled fragment; when
        any worker reports an exchange/expansion bucket overflow, climb the
        ladder (bigger buckets) and re-run from scratch — the same
        grow-and-retry pattern as aggregation capacity growth.  The climb
        STARTS at the rung that last held for ``node`` (kept: a replay does
        not overflow its way up again) and is not capped there.  Returns the
        result, or None when the fragment is not distributable (caller falls
        back to local)."""
        start = self._keep(node, ("rung",), lambda: [0])
        for rung in range(start[0], len(_EXCHANGE_LADDER)):
            self._rung = rung
            self._probe_factor, self._expand_factor = _EXCHANGE_LADDER[rung]
            out = run_once()
            if out is None:
                return None
            result, oflow = out
            if not oflow:
                start[0] = rung
                return result
        return None  # pathological expansion: let the local executor handle it

    # ---------------------------------------------------------------- plan walk
    def _execute_to_page(self, node: P.PlanNode):
        if isinstance(node, P.Output):
            child, dicts = self._execute_to_page(node.child)
            return Page(node.schema, child.columns, child.null_masks, child.valid), dicts
        if isinstance(node, P.Sort):
            out = self._run_sort(node)
            if out is not None:
                self._trace(node, "mesh")
                return out
            self._trace(node, "coordinator", self._take_decline())
            child, dicts = self._execute_to_page(node.child)
            return _sort_page(child, node.keys, dicts), dicts
        if isinstance(node, P.Window):
            out = self._run_window_dist(node)
            if out is not None:
                self._trace(node, "mesh")
                return out
            self._trace(node, "local", self._take_decline())
            return self.local._execute_to_page(node)
        if isinstance(node, P.Limit):
            if isinstance(node.child, P.Sort):
                # TopN over a streamable fragment: per-worker topN + single
                # ordered merge (reference: TopNOperator per task +
                # MergeOperator at the gather stage)
                def once(node=node):
                    stream = self._fragment(node.child.child)
                    if stream is None:
                        return None
                    return self._run_topn(stream, node.child.keys, node.count,
                                          node=node)

                out = self._retry_exchange(node, once)
                if out is not None:
                    self._trace(node, "mesh")
                    return out
                self._trace(node, "coordinator", self._take_decline())
            child, dicts = self._execute_to_page(node.child)
            return _limit_page(child, node.count), dicts
        if isinstance(node, P.Aggregate):
            return self._run_aggregate(node)
        if isinstance(node, P.Union):
            # grouping sets (and set-op ALL) plan to a Union of aggregate
            # branches: run EACH branch distributed, gather the (small,
            # post-agg) pages on the coordinator — each grouping set is its
            # own aggregation stage in the reference too (grouping-set plans
            # via MarkDistinct/GroupId stages; the union edge is a gather)
            parts = [self._execute_to_page(c) for c in node.inputs]
            self._trace(node, "coordinator", "gather of distributed branches")
            cols_list, nulls_list = [], []
            for pg, _ in parts:
                v, pcols, pnulls = _host_page(pg)  # one batched pull per branch
                cols_list.append([c[v] for c in pcols])
                nulls_list.append([None if m is None else m[v]
                                   for m in pnulls])
            ncols = len(node.schema.fields)
            out_cols = tuple(np.concatenate([p[i] for p in cols_list])
                             for i in range(ncols))
            out_nulls = tuple(
                np.concatenate([
                    n[i] if n[i] is not None else np.zeros(len(c[i]), bool)
                    for n, c in zip(nulls_list, cols_list)])
                if any(n[i] is not None for n in nulls_list) else None
                for i in range(ncols))
            return (Page(node.schema, out_cols, out_nulls, None),
                    parts[0][1])

        def once(node=node):
            stream = self._fragment(node)
            if stream is None:
                return None
            return self._materialize_dstream(stream, node=node)

        out = self._retry_exchange(node, once)
        if out is not None:
            self._trace(node, "mesh")
            return out
        if isinstance(node, (P.Project, P.Filter)):
            # a Project/Filter ABOVE a blocking operator (post-aggregation
            # projections, HAVING filters) is not part of a scan-fed stream;
            # run the child distributed and apply the expressions to the
            # materialized (post-agg, small) page here instead of abandoning
            # the whole query to the local executor (round-1 VERDICT weak #3:
            # Q9/Q18 silently fell back because of exactly this shape)
            self._trace(node, "coordinator", self._take_decline())
            child, cdicts = self._execute_to_page(node.child)
            return self._apply_rowwise(node, child, cdicts)
        self._trace(node, "local", self._take_decline())
        return self.local._execute_to_page(node)

    def _apply_rowwise(self, node, child: Page, cdicts):
        """Evaluate a Project/Filter over one materialized page (eager, small)."""
        if isinstance(node, P.Filter):
            valid = evaluate_predicate(node.predicate, child.columns,
                                       child.null_masks, child.valid_mask())
            return Page(node.schema, child.columns, child.null_masks, valid), cdicts
        vs, ns = _eval_project(node.exprs, child.columns, child.null_masks,
                               child.valid_mask().shape)
        return (Page(node.schema, vs, ns, child.valid),
                _resolve_project_dicts(node, cdicts))

    # ---------------------------------------------------------------- streaming
    def _compile_stream(self, node: P.PlanNode) -> Optional[_DStream]:
        """Build the distributed streaming fragment, or None if the fragment has no
        distributable scan spine (executor then falls back to local)."""
        if isinstance(node, P.TableScan):
            conn = self.catalogs[node.catalog]
            dicts = tuple(conn.dictionaries(node.table).get(c)
                          for c in node.columns) \
                if hasattr(conn, "dictionaries") else \
                tuple(None for _ in node.columns)
            if not hasattr(conn, "generate_traced"):
                # host-fed sharded scan: coordinator-side split queue decoding
                # into stacked fixed-shape batches (SourcePartitionedScheduler
                # analog for file/memory connectors)
                if not (hasattr(conn, "generate") and hasattr(conn, "splits")):
                    return self._decline(node, "connector has no split scan "
                                               "surface (no splits/generate)")
                dtypes = tuple(np.dtype(f.type.dtype)
                               for f in node.schema.fields)
                if any(dt == object for dt in dtypes):
                    return self._decline(node, "wide-decimal (object) columns "
                                               "cannot cross to the device")
                batches = _HostFedBatches(conn, node.table, node.columns,
                                          dtypes, self.n_workers)

                def host_scan_fn(batch_w):
                    cols, nulls, valid = batch_w
                    return tuple(cols), tuple(nulls), valid

                return _DStream(node.schema, dicts, batches, host_scan_fn,
                                lambda c, n, v, aux: (c, n, v, _side(v)))
            # resident sharded scan: the steps read [W, lanes] column batches
            # as arguments; generation is a program of its own (_ShardedScan)
            scan = _ShardedScan(self, conn, node.catalog, node.table,
                                node.columns,
                                conn.splits(node.table, n_hint=self.n_workers))

            def scan_fn(batch_w):
                cols, valid = batch_w
                return tuple(cols), tuple(None for _ in cols), valid

            return _DStream(node.schema, dicts, scan, scan_fn,
                            lambda c, n, v, aux: (c, n, v, _side(v)))

        if isinstance(node, P.Filter):
            up = self._compile_stream(node.child)
            if up is None:
                return None

            def transform(cols, nulls, valid, aux, up=up, pred=node.predicate):
                cols, nulls, valid, of = up.transform(cols, nulls, valid, aux)
                return cols, nulls, evaluate_predicate(pred, cols, nulls, valid), of

            return dataclasses.replace(up, transform=transform)

        if isinstance(node, P.Project):
            up = self._compile_stream(node.child)
            if up is None:
                return None
            dicts = _resolve_project_dicts(node, up.dicts)

            def transform(cols, nulls, valid, aux, up=up, exprs=node.exprs):
                cols, nulls, valid, of = up.transform(cols, nulls, valid, aux)
                vs, ns = _eval_project(exprs, cols, nulls, valid.shape)
                return vs, ns, valid, of

            return dataclasses.replace(up, schema=node.schema, dicts=dicts,
                                       transform=transform)

        if isinstance(node, P.Join):
            if node.kind == "mark":
                return self._decline(
                    node, "mark joins (EXISTS in expression position) run "
                          "the local executor")
            up = self._compile_stream(node.left)
            if up is None:
                return None
            build_key_types = tuple(node.right.schema.fields[i].type for i in node.right_keys)

            def build(node=node):
                # build side: local (blocking) execution, kept with the facts
                # pulled from it (each is a dispatch or a pull) across ladder
                # rungs and statements
                build_page, build_dicts = \
                    self.local._execute_to_page_streamed(node.right)
                if build_page.capacity == 0:
                    # empty build joins flow through the normal probe path
                    # against a tiny all-invalid table: inner/semi match
                    # nothing, left/anti keep every probe row (round-1
                    # VERDICT weak #3: this shape silently fell back to local)
                    build_page = _pad_page(build_page, 16)
                multi = _has_duplicate_keys(build_page, node.right_keys,
                                            build_key_types)
                # NOT IN 3VL facts, host-side (shared with the local
                # executor's null-aware anti: _build_null_stats /
                # _null_aware_anti)
                build_null_stats = _build_null_stats(build_page,
                                                     node.right_keys)
                n_build = int(_host([jnp.sum(build_page.valid_mask(),
                                             dtype=jnp.int64)],
                                    site="dist.join.buildsize")[0])
                record_join_build(n_build)
                return (build_page, build_dicts, multi, build_null_stats,
                        n_build)

            build_page, build_dicts, multi, build_null_stats, n_build = \
                self._keep(node, ("build",), build)
            # distribution: the planner's stats-driven hint (CBO,
            # DetermineJoinDistributionType) decides when present; AUTOMATIC
            # plans ('replicated' hint) fall back to the actual build size
            hint = getattr(node, "distribution", "replicated")
            partitioned = (hint == "partitioned"
                           or (hint != "broadcast"
                               and n_build >= self.partition_threshold))
            if partitioned:
                if multi:
                    return self._compile_partitioned_multi_join(
                        node, up, build_page, build_dicts, build_key_types,
                        build_null_stats)
                return self._compile_partitioned_join(node, up, build_page, build_dicts,
                                                      build_key_types,
                                                      build_null_stats)
            if multi:
                return self._compile_broadcast_multi_join(
                    node, up, build_page, build_dicts, build_key_types,
                    build_null_stats)
            table = self._keep(
                node, ("table",), lambda: self.local._build_join_table(
                    build_page, node.right_keys, build_key_types))
            if table is None:
                return self._decline(node, "duplicate build keys with a "
                                           "residual filter shape the multi-"
                                           "join paths do not cover")
            semi = node.kind in ("semi", "anti")
            def transform(cols, nulls, valid, aux, up=up, node=node,
                          build_key_types=build_key_types, semi=semi,
                          build_null_stats=build_null_stats):
                up_aux, table = aux
                cols, nulls, valid, of = up.transform(cols, nulls, valid, up_aux)
                keys = tuple(cols[i] for i in node.left_keys)
                row_ids, matched = probe(table, keys, build_key_types, valid)
                for i in node.left_keys:
                    if nulls[i] is not None:
                        matched = matched & ~nulls[i]
                if node.filter is not None:
                    # residual filter is part of the MATCH condition for every
                    # join kind (unique build: one candidate row to test)
                    fcols, fnulls = _gather_build(table, row_ids, matched, "left")
                    matched = matched & evaluate_predicate(
                        node.filter, tuple(cols) + fcols, tuple(nulls) + fnulls,
                        matched)
                if node.kind == "anti":
                    valid = _null_aware_anti(node, valid & ~matched, nulls,
                                             *build_null_stats)
                elif node.kind in ("inner", "semi"):
                    valid = valid & matched
                if semi:
                    return cols, nulls, valid, of
                bcols, bnulls = _gather_build(table, row_ids, matched, node.kind)
                out_cols = tuple(cols) + bcols
                out_nulls = tuple(nulls) + bnulls
                return out_cols, out_nulls, valid, of

            dicts = up.dicts if semi else up.dicts + build_dicts
            return dataclasses.replace(
                up, schema=node.schema, dicts=dicts, transform=transform,
                aux=(up.aux, table), aux_specs=(up.aux_specs, PS()))

        return self._decline(node, "operator is not part of a streamable "
                                   "fragment (blocking or unsupported shape)")

    # ---------------------------------------------------------------- partitioned join
    def _compile_partitioned_join(self, node: P.Join, up: _DStream, build_page,
                                  build_dicts, build_key_types,
                                  build_null_stats=(False, True)) -> _DStream:
        """Hash-partitioned join: BOTH sides route through the same all-to-all
        hash exchange (SURVEY §2.8 mapping #3: FIXED_HASH exchange ->
        jax.lax.all_to_all over the ICI mesh).  The build page is sharded
        [W, chunk] across the mesh; one shard_map program routes each worker's
        chunk to its hash owner and builds that worker's table in place, so the
        resident table is O(build/W) per chip and stays SHARDED (out_specs on
        the worker axis) — not replicated, unlike round 1's host-looped build
        (VERDICT r1 weak #4).  Probe rows take the same exchange per batch."""
        W = self.n_workers
        semi = node.kind in ("semi", "anti")

        def make_table(ccols, cnulls, cvalid, cap_r, n_recv, node=node):
            rpage = Page(node.right.schema, ccols, cnulls, cvalid)
            jt = build_table_init(2 * cap_r, rpage)
            jt = build_insert(jt, tuple(ccols[ch] for ch in node.right_keys),
                              build_key_types, cvalid)
            # skew overflow: more rows hashed to this worker than cap_r holds
            return dataclasses.replace(jt, overflow=jt.overflow | (n_recv > cap_r))

        table_g = self._keep(node, ("ptable",), lambda:
                             self._sharded_build_exchange(node, build_page,
                                                          make_table))

        learned = self._learned(node)

        def transform(cols, nulls, valid, aux, up=up, node=node):
            up_aux, table_g = aux
            cols, nulls, valid, of = up.transform(cols, nulls, valid, up_aux)
            n = valid.shape[0]
            pkeys = tuple(cols[i] for i in node.left_keys)
            rpid = partition_ids(pkeys, W)
            # NULL probe keys never match but must SURVIVE for left/anti: route them
            # (to their hash bucket) like any other row; matching excludes them below.
            # The bucket starts at ~2n/W (a W/2-times smaller receive tensor than
            # the always-safe n) and follows the rows once a run has counted
            # them (_settle); skewed batches report overflow through the stream
            # contract and the driver retries bigger (_EXCHANGE_LADDER).
            bucket = self._probe_bucket(n, learned)
            rcols, rnulls, recv_valid, counts = _route_rows(
                tuple(cols), tuple(nulls), valid, rpid, W, bucket, WORKER_AXIS)
            of = _side_probe(of, counts, bucket)
            # this worker's table shard arrives as [1, ...] under aux_specs
            jt = jax.tree.map(lambda x: None if x is None else x[0], table_g,
                              is_leaf=lambda x: x is None)
            rkeys = tuple(rcols[i] for i in node.left_keys)
            kvalid = recv_valid
            for i in node.left_keys:
                if rnulls[i] is not None:
                    kvalid = kvalid & ~rnulls[i]
            row_ids, matched = probe(jt, rkeys, build_key_types, kvalid)
            matched = matched & kvalid
            if node.filter is not None:
                # match-condition residual for every join kind (unique build)
                fcols, fnulls = _gather_build(jt, row_ids, matched, "left")
                matched = matched & evaluate_predicate(
                    node.filter, tuple(rcols) + fcols, tuple(rnulls) + fnulls,
                    matched)
            if node.kind in ("inner", "semi"):
                out_valid = recv_valid & matched
            elif node.kind == "anti":
                out_valid = _null_aware_anti(node, recv_valid & ~matched, rnulls,
                                             *build_null_stats)
            else:  # left
                out_valid = recv_valid
            if semi:
                return tuple(rcols), tuple(rnulls), out_valid, of
            gcols, gnulls = _gather_build(jt, row_ids, matched, node.kind)
            out_cols = tuple(rcols) + gcols
            out_nulls = tuple(rnulls) + gnulls
            return (out_cols, out_nulls, out_valid, of)

        dicts = up.dicts if semi else up.dicts + build_dicts
        return dataclasses.replace(
            up, schema=node.schema, dicts=dicts, transform=transform,
            aux=(up.aux, table_g), aux_specs=(up.aux_specs, PS(WORKER_AXIS)),
            probes=up.probes + (node,))

    def _probe_bucket(self, n: int, learned: Optional[int] = None) -> int:
        """Per-partition probe-exchange bucket for an n-row batch: what the
        rows that join's exchange has carried ask for (_learned) once a run
        has counted them; until then ~(factor/W)·n on the ladder's adaptive
        rungs, exact n on the safe rung."""
        if learned is not None:
            return min(n, learned)
        pf = self._probe_factor
        if pf is None:
            return n
        return max(min(n, -(-n * pf // self.n_workers)), 1)

    def _learned(self, join) -> Optional[int]:
        """The probe bucket learned for ``join``, or None: from the ``need``
        _settle kept for the node."""
        hit = self._held(join, ("probe_need",))
        return None if hit is None else _learned_bucket(hit[1])

    def _narrowed(self, node) -> tuple:
        """The learned probe buckets along ``node``'s stream spine, from the
        top: with the rung, what a fragment is compiled at."""
        out = []
        while True:
            if isinstance(node, P.Join):
                out.append(self._learned(node))
                node = node.left
            elif isinstance(node, (P.Filter, P.Project)):
                node = node.child
            else:
                return tuple(out)

    def _overflowed(self, stream: _DStream, side) -> bool:
        """Whether a pulled side channel ([W, slots], host) raised its flag.
        A run that dropped rows forgets what its probe exchanges had learned:
        the retry runs the ladder's own buckets, as it always has."""
        if not np.any(side[:, 0]):
            return False
        for node in stream.probes:
            self._kept.pop((id(node), "probe_need"), None)
        return True

    def _settle(self, stream: _DStream, side) -> bool:
        """The side channel's host half, where a consumer's flags pull lands:
        True when the run dropped rows (ladder retry).  A sound run counts
        what its probe exchanges carried, and keeps ``need`` for a join whose
        exchange would run at half its bucket or less: the execution right
        after this one compiles that fragment narrow (_fragment's key), a
        dense probe keeps its program."""
        if self._overflowed(stream, side):
            return True
        for i, node in enumerate(stream.probes):
            at = 1 + _SIDE_FIELDS * i
            need, bucket = (int(side[:, at + j].max()) for j in (0, 1))
            record_probe_exchange(*(int(side[:, at + j].sum()) for j in (2, 3)),
                                  row_bytes=_row_bytes(node.left.schema.fields))
            if 2 * _learned_bucket(need) <= bucket:
                self._kept[(id(node), "probe_need")] = (node, need)
        return False

    def _side_init(self, stream: _DStream):
        """The zeroed [W, slots] carry of ``stream``'s side channel."""
        return jax.device_put(  # device-ok: mesh-sharded placement
            np.zeros((self.n_workers, 1 + _SIDE_FIELDS * len(stream.probes)),
                     np.int64), self._sharded)

    def _sharded_build_exchange(self, node: P.Join, build_page, make_table):
        """The partitioned-join build scaffold both table layouts share: shard
        the materialized build page [W, chunk] across workers; per worker,
        route the chunk to its hash owners, compact the received partition to
        cap_r rows, and call ``make_table(ccols, cnulls, cvalid, cap_r,
        n_recv)`` (traced, per shard) to build that worker's table.  The
        receive tensor is transiently [W*chunk] wide, but the RESIDENT state
        is O(cap_r) ≈ O(build/W) per chip — the point of sharding the build.
        cap_r grows on the host until no worker overflows."""
        W = self.n_workers
        mesh = self.mesh
        sharded = NamedSharding(mesh, PS(WORKER_AXIS))
        n_b = build_page.capacity
        chunk = max((n_b + W - 1) // W, 4)
        padded = _pad_page(build_page, W * chunk)
        bcols_g = tuple(jax.device_put(c.reshape(W, chunk), sharded)  # device-ok: mesh-sharded placement
                        for c in padded.columns)
        bnull_slots = [ci for ci, m in enumerate(padded.null_masks)
                       if m is not None]
        bnulls_g = tuple(
            jax.device_put(padded.null_masks[ci].reshape(W, chunk), sharded)  # device-ok: mesh-sharded placement
            for ci in bnull_slots)
        bvalid_g = jax.device_put(padded.valid_mask().reshape(W, chunk), sharded)  # device-ok: mesh-sharded placement
        ncols_b = len(padded.columns)

        def build_exchange(bcols_l, bnulls_l, bvalid_l, cap_r, node=node):
            # send bucket = chunk can never overflow: each worker sends at
            # most its chunk rows in total
            keys = tuple(bcols_l[ch] for ch in node.right_keys)
            kvalid = bvalid_l
            for j, ci in enumerate(bnull_slots):
                if ci in node.right_keys:
                    kvalid = kvalid & ~bnulls_l[j]
            pid = partition_ids(keys, W)
            full_nulls = [None] * ncols_b
            for j, ci in enumerate(bnull_slots):
                full_nulls[ci] = bnulls_l[j]
            rcols, rnulls, recv_valid, _ = _route_rows(
                tuple(bcols_l), tuple(full_nulls), kvalid, pid, W, chunk,
                WORKER_AXIS)
            n_recv = jnp.sum(recv_valid, dtype=jnp.int32)
            ccols, cnulls = _compact_part(tuple(rcols), tuple(rnulls),
                                          recv_valid, cap_r)
            # n_recv derives from the exchanged data, so cvalid already
            # carries the worker-varying axis
            cvalid = jnp.arange(cap_r, dtype=jnp.int32) < n_recv
            return make_table(ccols, cnulls, cvalid, cap_r, n_recv)

        # shared static per-worker capacity; grow together on any overflow
        # (host checks the per-worker flags once per attempt).  Start at ~2x
        # the balanced share to absorb moderate hash skew without a retry.
        cap_r = max(1 << max(2 * chunk - 1, 1).bit_length(), 32)
        while True:
            fn = partial(build_exchange, cap_r=cap_r)
            _exchange_fault("exchange_write", "dist.join.build_exchange")
            with maybe_span("exchange.route"):
                table_g = _jit(site="dist.join.build_exchange", fn=
                    shard_map(
                        lambda bc, bn, bv: jax.tree.map(
                            lambda x: None if x is None else x[None],
                            fn(tuple(c[0] for c in bc), tuple(m[0] for m in bn),
                               bv[0]),
                            is_leaf=lambda x: x is None),
                        mesh=mesh, in_specs=(PS(WORKER_AXIS),) * 3,
                        out_specs=PS(WORKER_AXIS)))(bcols_g, bnulls_g, bvalid_g)
            if not bool(np.any(_host([table_g.overflow],
                                     site="dist.join.overflow")[0])):
                break
            cap_r *= 4
        return table_g

    # ---------------------------------------------------------------- multi-match joins
    def _compile_broadcast_multi_join(self, node: P.Join, up: _DStream,
                                      build_page, build_dicts, build_key_types,
                                      build_null_stats) -> _DStream:
        """Duplicate-key build, replicated: one slot-grouped MultiJoinTable
        (ops/hashjoin.multi_build — the PositionLinks analog) broadcast to
        every worker; each worker expands its own probe batch at a static
        bucket (overflow -> driver retry)."""
        semi = node.kind in ("semi", "anti")
        # (no empty-build branch: _has_duplicate_keys needs >= 2 equal-key rows,
        # and the Join branch pads empty builds before the multi check)
        mt = self._keep(node, ("bmtable",), lambda: multi_build(
            max(1 << max(build_page.capacity - 1, 1).bit_length(), 16) * 2,
            build_page, node.right_keys, build_key_types))
        ef = self._expand_factor

        def transform(cols, nulls, valid, aux, up=up, node=node, ef=ef,
                      build_key_types=build_key_types, semi=semi,
                      build_null_stats=build_null_stats):
            up_aux, mt = aux
            cols, nulls, valid, of = up.transform(cols, nulls, valid, up_aux)
            E = max(ef * valid.shape[0], 1024)
            ocols, onulls, ovalid, m_of = _multi_probe_expand(
                node, mt, build_key_types, tuple(cols), tuple(nulls), valid,
                E, build_null_stats, semi)
            return ocols, onulls, ovalid, _side_flag(of, m_of)

        dicts = up.dicts if semi else up.dicts + build_dicts
        return dataclasses.replace(
            up, schema=node.schema, dicts=dicts, transform=transform,
            aux=(up.aux, mt), aux_specs=(up.aux_specs, PS()))

    def _compile_partitioned_multi_join(self, node: P.Join, up: _DStream,
                                        build_page, build_dicts,
                                        build_key_types,
                                        build_null_stats) -> _DStream:
        """Duplicate-key build, partitioned: the build page routes through the
        same all-to-all exchange as the unique path, but each worker builds a
        slot-grouped MultiJoinTable over ITS key partition; probe batches route
        per batch and expand per shard.  Resident state stays O(build/W) per
        chip.  (Reference: per-task PositionLinks over the FIXED_HASH
        exchange, DefaultPagesHash.java:159-197.)"""
        W = self.n_workers
        semi = node.kind in ("semi", "anti")

        def make_table(ccols, cnulls, cvalid, cap_r, n_recv, node=node):
            table0 = jnp.full((2 * cap_r + 1,), EMPTY_KEY, jnp.int64)
            ckeys = tuple(ccols[ch] for ch in node.right_keys)
            table, counts, starts, order, boflow = _multi_build_step(
                table0, ckeys, build_key_types, cvalid)
            return MultiJoinTable(table, counts, starts, order, ccols, cnulls,
                                  boflow | (n_recv > cap_r))

        mt_g = self._keep(node, ("pmtable",), lambda:
                          self._sharded_build_exchange(node, build_page,
                                                       make_table))

        learned = self._learned(node)
        ef = self._expand_factor

        def transform(cols, nulls, valid, aux, up=up, node=node, ef=ef,
                      build_key_types=build_key_types, semi=semi,
                      build_null_stats=build_null_stats):
            up_aux, mt_g = aux
            cols, nulls, valid, of = up.transform(cols, nulls, valid, up_aux)
            n = valid.shape[0]
            pkeys = tuple(cols[i] for i in node.left_keys)
            rpid = partition_ids(pkeys, W)
            bucket = self._probe_bucket(n, learned)
            rcols, rnulls, recv_valid, counts = _route_rows(
                tuple(cols), tuple(nulls), valid, rpid, W, bucket, WORKER_AXIS)
            mt = jax.tree.map(lambda x: None if x is None else x[0], mt_g,
                              is_leaf=lambda x: x is None)
            E = max(ef * n, 1024)
            ocols, onulls, ovalid, m_of = _multi_probe_expand(
                node, mt, build_key_types, tuple(rcols), tuple(rnulls),
                recv_valid, E, build_null_stats, semi)
            return ocols, onulls, ovalid, _side_flag(
                _side_probe(of, counts, bucket), m_of)

        dicts = up.dicts if semi else up.dicts + build_dicts
        return dataclasses.replace(
            up, schema=node.schema, dicts=dicts, transform=transform,
            aux=(up.aux, mt_g), aux_specs=(up.aux_specs, PS(WORKER_AXIS)),
            probes=up.probes + (node,))

    # ---------------------------------------------------------------- sort
    def _run_sort(self, node: P.Sort):
        """Distributed full ORDER BY: sample-based range partitioning (splitters
        from the first scan batch) routes every row to the worker owning its
        key range through the shared ``_route_rows`` exchange; each worker then
        lexsorts its range ON DEVICE in parallel and the host concatenates the
        W sorted ranges in rank order.  Ties on the primary key all hash to one
        worker (searchsorted is value-deterministic), so secondary keys resolve
        wholly within a shard.  Reference: per-task OrderByOperator + the
        merging exchange (operator/OrderByOperator.java, MergeOperator.java) —
        re-planned as range exchange + shard-parallel sort."""
        return self._retry_exchange(node, lambda: self._run_sort_once(node))

    def _run_sort_once(self, node: P.Sort):
        stream = self._fragment(node.child)
        if stream is None or not stream.scan_lo_batches:
            return None
        keys = node.keys
        if not keys:
            return None
        mesh, W = self.mesh, self.n_workers
        sharded = NamedSharding(mesh, PS(WORKER_AXIS))
        fields = stream.schema.fields
        luts = _collation_luts(keys, fields, stream.dicts)
        pk = keys[0]
        ch = pk.channel

        def rank_dev(c, lut):
            if lut is not None:
                c = lut[jnp.clip(c, 0, lut.shape[0] - 1)]
            if c.dtype == jnp.bool_:
                c = c.astype(jnp.int8)
            return -c if not pk.ascending else c

        # --- sample pass: materialize batch 0's primary-key ranks once; they
        # give the W-1 range splitters.  Only the key channel + validity are
        # pulled (the sample pull shrinks ~1/ncols) and batch 0 re-routes on
        # the mesh with every other batch.
        def make_sample_key(stream=stream):
            @partial(shard_map, mesh=mesh,
                     in_specs=(PS(WORKER_AXIS), stream.aux_specs),
                     out_specs=PS(WORKER_AXIS))
            def sample_key(lo_g, aux):
                cols, nulls, valid, of = _stream_batch(stream, lo_g, aux)
                nm = nulls[ch] if nulls[ch] is not None \
                    else jnp.zeros(valid.shape, bool)
                return cols[ch][None], nm[None], valid[None], of[None]

            return _jit(sample_key, site="dist.sort.sample_key")

        got = _host(list(self._step(node, stream, ("sort.sample_key",),
                                    make_sample_key)(
                        jax.device_put(stream.scan_lo_batches[0], sharded),  # device-ok: mesh-sharded placement
                        stream.aux))
                    + ([luts[ch]] if ch in luts else []),
                    site="dist.sort.sample")
        if self._overflowed(stream, got[3]):
            return None, True
        key0 = got[0].reshape(-1)
        keynull0 = got[1].reshape(-1)
        valid0 = got[2].reshape(-1)
        lut_np = None if ch not in luts else got[-1]

        def rank_host(c):
            if lut_np is not None:
                c = lut_np[np.clip(c, 0, len(lut_np) - 1)]
            if c.dtype == np.bool_:
                c = c.astype(np.int8)
            return -c if not pk.ascending else c

        rv0 = rank_host(key0)
        ok = valid0 & ~keynull0
        ranks = np.sort(rv0[ok])
        if ranks.size:
            splitters = ranks[[(i * ranks.size) // W for i in range(1, W)]]
        else:
            splitters = np.zeros((W - 1,), rv0.dtype)

        splitters_t = jnp.asarray(splitters)
        luts_t = dict(luts)

        def pid_fn(cols, nulls, valid, route_aux):
            luts_r, spl = route_aux
            rv = rank_dev(cols[ch], luts_r.get(ch))
            pid = jnp.searchsorted(spl.astype(rv.dtype), rv,
                                   side="left").astype(jnp.int32)
            nm = nulls[ch]
            if nm is not None:
                pid = jnp.where(nm, 0 if pk.nulls_first else W - 1, pid)
            return pid

        # exact per-partition bucket (= n): range keys are routinely CLUSTERED
        # (ORDER BY a key correlated with scan order sends whole batches to one
        # range), which would deterministically overflow the hash-uniform
        # ~2n/W heuristic and waste full ladder re-runs
        collected = self._exchange_collect(stream, pid_fn, (luts_t, splitters_t),
                                           bucket_of=lambda n: n, node=node)
        if collected is None:
            return None, True
        cols_g, nulls_g, valid_g, counts = collected
        if sum(counts) == 0:
            page = Page(stream.schema,
                        tuple(jnp.zeros((0,), np.dtype(f.type.dtype))
                              for f in fields),
                        tuple(None for _ in fields), None)
            return (page, stream.dicts), False

        def make_sort_shard():
            @partial(shard_map, mesh=mesh,
                     in_specs=(PS(WORKER_AXIS), PS(WORKER_AXIS), PS(WORKER_AXIS), PS()),
                     out_specs=PS(WORKER_AXIS))
            def sort_shard(cols_g, nulls_g, valid_g, luts_t):
                cols = tuple(c[0] for c in cols_g)
                nulls_ = tuple(m[0] for m in nulls_g)
                valid = valid_g[0]
                idx = _lex_indices(keys, luts_t, cols, nulls_, valid)
                return (tuple(c[idx][None] for c in cols),
                        tuple(m[idx][None] for m in nulls_), valid[idx][None])

            return _jit(sort_shard, site="dist.sort.shard")

        scols, snulls, _ = self._keep(node, ("sort.shard",), make_sort_shard)(
            tuple(jax.device_put(c, sharded) for c in cols_g),  # device-ok: mesh-sharded placement
            tuple(jax.device_put(m, sharded) for m in nulls_g),  # device-ok: mesh-sharded placement
            jax.device_put(valid_g, sharded), luts_t)  # device-ok: mesh-sharded placement
        # sorted shards: valid rows lead (``~valid`` is the last lex key), so
        # worker w contributes exactly its counts[w] head rows, in rank order
        page = _page_from_shards(stream.schema, scols, snulls, counts,
                                 keep=partial(self._keep, node))
        return (page, stream.dicts), False

    # ---------------------------------------------------------------- window
    def _run_window_dist(self, node: P.Window):
        """Distributed window evaluation: hash-route rows by the (shared)
        PARTITION BY key through ``_route_rows`` so each worker owns whole
        partitions, then run the local window kernel per shard — pad rows are
        isolated into their own partition by the kernel's ``valid`` support.
        Reference: the hash exchange AddExchanges inserts below WindowNode +
        per-task WindowOperator (operator/WindowOperator.java)."""
        specs = node.specs
        part = specs[0].partition
        if not part or any(s.partition != part for s in specs):
            return None  # no common non-empty PARTITION BY -> not routable
        return self._retry_exchange(node, lambda: self._run_window_once(node))

    def _run_window_once(self, node: P.Window):
        stream = self._fragment(node.child)
        if stream is None or not stream.scan_lo_batches:
            return None
        specs = node.specs
        part = specs[0].partition
        mesh, W = self.mesh, self.n_workers
        sharded = NamedSharding(mesh, PS(WORKER_AXIS))
        child_fields = stream.schema.fields
        spec_dicts = _window_spec_dicts(specs, stream.dicts)

        def pid_fn(cols, nulls, valid, route_aux):
            kc = []
            for c in part:
                v = cols[c]
                nm = nulls[c]
                if nm is not None:
                    v = jnp.where(nm, jnp.zeros((), v.dtype), v)
                    kc.append(nm)  # NULL is its own partition value
                kc.append(v)
            return partition_ids(tuple(kc), W)

        collected = self._exchange_collect(stream, pid_fn, (), node=node)
        if collected is None:
            return None, True
        cols_g, nulls_g, valid_g, counts = collected
        if sum(counts) == 0:
            cols = tuple(jnp.zeros((0,), np.dtype(f.type.dtype))
                         for f in node.schema.fields)
            page = Page(node.schema, cols,
                        tuple(None for _ in node.schema.fields), None)
            return (page, stream.dicts + spec_dicts), False

        def make_wstep():
            @partial(shard_map, mesh=mesh,
                     in_specs=(PS(WORKER_AXIS), PS(WORKER_AXIS), PS(WORKER_AXIS)),
                     out_specs=PS(WORKER_AXIS))
            def wstep(cols_g, nulls_g, valid_g):
                cols = tuple(c[0] for c in cols_g)
                nulls_ = tuple(m[0] for m in nulls_g)
                valid = valid_g[0]
                ocols, onulls = _window_kernel(specs, cols, nulls_, valid)
                onulls = tuple(jnp.zeros(valid.shape, bool) if m is None else m
                               for m in onulls)
                return (tuple(c[None] for c in ocols),
                        tuple(m[None] for m in onulls))

            return _jit(wstep, site="dist.window.step")

        ocols, onulls = self._keep(node, ("window.step",), make_wstep)(
            tuple(jax.device_put(c, sharded) for c in cols_g),  # device-ok: mesh-sharded placement
            tuple(jax.device_put(m, sharded) for m in nulls_g),  # device-ok: mesh-sharded placement
            jax.device_put(valid_g, sharded))  # device-ok: mesh-sharded placement
        page = _page_from_shards(node.schema, tuple(cols_g) + tuple(ocols),
                                 tuple(nulls_g) + tuple(onulls), counts,
                                 keep=partial(self._keep, node))
        return (page, stream.dicts + spec_dicts), False

    def _exchange_collect(self, stream: _DStream, pid_fn, route_aux,
                          bucket_of=None, node=None):
        """Run the stream batch by batch, hash/range-routing rows to their
        owning worker, and collect each worker's received rows — the blocking
        exchange both the full sort and the window path consume.

        Device-resident (round 18): routed batches append into carried
        [W, cap] device receive buffers inside the SAME shard_map that runs
        the all-to-all, and only scalar cursor/overflow flags sync per run;
        the host spool takes what ``_host_spooled`` names.
        ``_route_rows`` leaves invalid slot gaps in the receive layout, so the
        device path compacts via ``append_rows`` and the host path via the
        receive-side valid mask.  ``route_aux`` is threaded into the jitted
        step as an ARGUMENT (a closed-over device array would be baked into
        the executable as a constant).

        Returns (cols_g, nulls_g, valid_g, counts): [W, nmax] shard arrays —
        device-sharded jnp on the device path, host numpy on the spool path —
        plus per-worker host row counts; or None on bucket overflow (ladder
        retry)."""
        mesh, W = self.mesh, self.n_workers
        sharded = NamedSharding(mesh, PS(WORKER_AXIS))
        bucket_of = bucket_of if bucket_of is not None else self._probe_bucket
        fields = stream.schema.fields
        ncols = len(fields)
        if not _host_spooled(stream):
            return self._exchange_collect_device(stream, pid_fn, route_aux,
                                                 bucket_of, node=node)

        def make_step(stream=stream):
            @partial(shard_map, mesh=mesh,
                     in_specs=(PS(WORKER_AXIS), stream.aux_specs, PS()),
                     out_specs=PS(WORKER_AXIS))
            def step(lo_g, aux, route_aux):
                cols, nulls, valid, of = _stream_batch(stream, lo_g, aux)
                pid = pid_fn(cols, nulls, valid, route_aux)
                bucket = bucket_of(valid.shape[0])
                rcols, rnulls, rvalid, counts = _route_rows(
                    tuple(cols), tuple(nulls), valid, pid, W, bucket,
                    WORKER_AXIS)
                rnulls = tuple(jnp.zeros(c.shape, bool) if m is None else m
                               for c, m in zip(rcols, rnulls))
                return (tuple(c[None] for c in rcols),
                        tuple(m[None] for m in rnulls), rvalid[None],
                        _side_flag(of, jnp.any(counts > bucket))[None])

            return _jit(step, site="dist.exchange.spool")

        step = self._step(node, stream, ("exchange.spool",), make_step)
        side = None
        per_cols = [[[] for _ in range(ncols)] for _ in range(W)]
        per_nulls = [[[] for _ in range(ncols)] for _ in range(W)]
        t0 = time.perf_counter()
        for lo in stream.scan_lo_batches:
            _exchange_fault("exchange_write", "dist.exchange.route")
            with maybe_span("exchange.route"):
                rcols, rnulls, rvalid, of = step(
                    jax.device_put(lo, sharded), stream.aux, route_aux)  # device-ok: mesh-sharded placement
                got = _host(list(rcols) + list(rnulls) + [rvalid, of],
                            site="dist.exchange.collect")
            if self._overflowed(stream, got[-1]):
                return None
            side = got[-1] if side is None else _side_merge(side, got[-1], np)
            v = got[-2]
            cols_np = got[:len(rcols)]
            nulls_np = got[len(rcols):len(rcols) + len(rnulls)]
            for w in range(W):
                vw = v[w]
                for i in range(ncols):
                    per_cols[w][i].append(cols_np[i][w][vw])
                    per_nulls[w][i].append(nulls_np[i][w][vw])
        out_cols = [[np.concatenate(per_cols[w][i]) for i in range(ncols)]
                    for w in range(W)]
        out_nulls = [[np.concatenate(per_nulls[w][i]) for i in range(ncols)]
                     for w in range(W)]
        counts = [len(out_cols[w][0]) if ncols else 0 for w in range(W)]
        if side is not None:
            self._settle(stream, side)
        self._note_skew("dist.exchange.collect", node, counts,
                        time.perf_counter() - t0, fields=fields)
        _exchange_fault("exchange_read", "dist.exchange.read")
        cols_g, nulls_g, valid_g, _ = _stack_shards(out_cols, out_nulls,
                                                    counts, fields)
        return cols_g, nulls_g, valid_g, counts

    # ------------------------------------------------- device-resident exchange
    def _batch_rows(self, stream: _DStream) -> int:
        """Per-worker row capacity of one scan batch (static shape fact)."""
        if isinstance(stream.scan_lo_batches, _ShardedScan):
            return stream.scan_lo_batches.rows
        # host-fed: stacked [W, cap] pytree
        return int(stream.scan_lo_batches[0][2].shape[1])

    def _recv_capacity(self, stream: _DStream) -> int:
        """Initial receive-buffer capacity: 2x the scan's total per-worker rows
        (absorbs moderate routing skew without a growth retry), pow2-rounded
        for bounded jit shape classes."""
        est = self._batch_rows(stream) * max(len(stream.scan_lo_batches), 1)
        return max(1 << (max(2 * est, 1024) - 1).bit_length(), 1024)

    def _recv_state_init(self, stream: _DStream, cap: int, dtypes):
        """Zeroed receive-buffer carry, mesh-sharded: per-column [W, cap + 1]
        value + null-mask buffers (the +1 slot is append_rows' drop sink),
        [W] write cursors, the stream's side channel (ladder overflow, what
        its probe exchanges carried) and [W] capacity-overflow flags."""
        W = self.n_workers
        sharded = NamedSharding(self.mesh, PS(WORKER_AXIS))

        def put(a):
            return jax.device_put(a, sharded)  # device-ok: mesh-sharded placement

        return (tuple(put(np.zeros((W, cap + 1), dt)) for dt in dtypes),
                tuple(put(np.zeros((W, cap + 1), bool)) for _ in dtypes),
                put(np.zeros((W,), np.int64)),
                self._side_init(stream),
                put(np.zeros((W,), bool)))

    def _slim_shards(self, node, state, counts, site: str):
        """Trim carried [W, cap + 1] receive buffers to the smallest pow2 cover
        of the largest shard and derive per-row validity from the cursors —
        ONE dispatch, outputs stay device-sharded for the consumer."""
        nmax = max(max(counts), 1)
        nmax_p2 = 1 << (nmax - 1).bit_length()

        def make_slim():
            @partial(shard_map, mesh=self.mesh, in_specs=(PS(WORKER_AXIS),) * 3,
                     out_specs=PS(WORKER_AXIS))
            def slim(bufs_g, nbufs_g, cursor_g):
                cur = cursor_g[0]
                cols = tuple(b[0][:nmax_p2] for b in bufs_g)
                nulls = tuple(b[0][:nmax_p2] for b in nbufs_g)
                valid = jnp.arange(nmax_p2, dtype=cur.dtype) < cur
                return (tuple(c[None] for c in cols),
                        tuple(m[None] for m in nulls), valid[None])

            return _jit(slim, site=site)

        return self._keep(node, (site, nmax_p2), make_slim)(
            state[0], state[1], state[2])

    def _exchange_collect_device(self, stream: _DStream, pid_fn, route_aux,
                                 bucket_of, node=None):
        """The tentpole: route AND receive inside one shard_map program.  Each
        batch bucketizes + all-to-alls as before, then ``append_rows`` packs
        the received lanes into carried [W, cap + 1] device buffers at the
        write cursor — the same [W, ...] carry discipline as the agg path's
        group tables.  Host traffic per RUN (not per batch) is one scalar
        pull of cursors + overflow flags; receive-capacity overflow grows cap
        4x and re-runs (rows past cap collapsed into the drop sink, so no
        partial state ever leaks), ladder overflow returns None exactly like
        the host spool."""
        mesh, W = self.mesh, self.n_workers
        sharded = NamedSharding(mesh, PS(WORKER_AXIS))
        dtypes = [np.dtype(f.type.dtype) for f in stream.schema.fields]
        cap = self._recv_capacity(stream)
        while True:
            t0 = time.perf_counter()
            state = self._recv_state_init(stream, cap, dtypes)

            def make_step(stream=stream):
                @partial(shard_map, mesh=mesh,
                         in_specs=(PS(WORKER_AXIS), PS(WORKER_AXIS),
                                   stream.aux_specs, PS()),
                         out_specs=PS(WORKER_AXIS))
                def step(state_g, lo_g, aux, route_aux):
                    bufs = tuple(b[0] for b in state_g[0])
                    nbufs = tuple(b[0] for b in state_g[1])
                    cursor = state_g[2][0]
                    lad_of, recv_of = state_g[3][0], state_g[4][0]
                    cols, nulls, valid, of = _stream_batch(stream, lo_g, aux)
                    pid = pid_fn(cols, nulls, valid, route_aux)
                    bucket = bucket_of(valid.shape[0])
                    rcols, rnulls, rvalid, counts = _route_rows(
                        tuple(cols), tuple(nulls), valid, pid, W, bucket,
                        WORKER_AXIS)
                    of = _side_flag(of, jnp.any(counts > bucket))
                    # cast to the schema dtypes the buffers were allocated at
                    # (same cast _stack_shards applies on the host path)
                    rcols = tuple(c.astype(dt) for c, dt in zip(rcols, dtypes))
                    rnulls = tuple(jnp.zeros(c.shape, bool) if m is None else m
                                   for c, m in zip(rcols, rnulls))
                    new, ncur, b_of = append_rows(bufs + nbufs, cursor,
                                                  rcols + rnulls, rvalid)
                    k = len(bufs)
                    return (tuple(b[None] for b in new[:k]),
                            tuple(b[None] for b in new[k:]),
                            ncur[None], _side_merge(lad_of, of)[None],
                            (recv_of | b_of)[None])

                return _jit(step, site="dist.exchange.route")

            # one wrapper serves every receive capacity: a grown cap is a
            # new argument shape of it, not a new program object
            step = self._step(node, stream, ("exchange.route",), make_step)
            for lo in stream.scan_lo_batches:
                _exchange_fault("exchange_write", "dist.exchange.route")
                with maybe_span("exchange.route"):
                    state = step(state, jax.device_put(lo, sharded),  # device-ok: mesh-sharded placement
                                 stream.aux, route_aux)
            cursor, side, recv_of = _host(
                [state[2], state[3], state[4]], site="dist.exchange.flags")
            if self._settle(stream, side):
                return None  # exchange/expansion bucket overflow: ladder retry
            if not bool(np.any(recv_of)):
                break
            cap *= 4
            if cap > (1 << 28):
                return None  # pathological skew: ladder / local fallback
        counts = [int(c) for c in cursor]
        # skew from the cursors the flags pull ALREADY synced: per-worker
        # received-row counts, walled over the successful run's batch loop
        self._note_skew("dist.exchange.flags", node, counts,
                        time.perf_counter() - t0,
                        fields=stream.schema.fields)
        _exchange_fault("exchange_read", "dist.exchange.read")
        cols_g, nulls_g, valid_g = self._slim_shards(node, state, counts,
                                                     "dist.exchange.slim")
        return cols_g, nulls_g, valid_g, counts

    # ---------------------------------------------------------------- topN
    def _run_topn(self, stream: _DStream, sort_keys, count: int, node=None):
        """Distributed TopN: each worker keeps a running top-`count` page across
        its scan batches inside ONE jitted shard_map step (device lexsort over
        state+batch), then the W small per-worker results merge on the host
        (reference: per-task TopNOperator + ordered MergeOperator,
        operator/TopNOperator.java / operator/MergeOperator.java)."""
        mesh, W = self.mesh, self.n_workers
        sharded = NamedSharding(mesh, PS(WORKER_AXIS))
        fields = stream.schema.fields
        k = max(count, 1)

        # dictionary-encoded sort keys order by DECODED value, not id
        # (_collation_luts); the device sort then compares ranks
        luts = _collation_luts(sort_keys, fields, stream.dicts)

        state_cols = tuple(jnp.zeros((W, k), np.dtype(f.type.dtype))
                           for f in fields)
        state_nulls = tuple(jnp.zeros((W, k), bool) for _ in fields)
        state_valid = jnp.zeros((W, k), bool)
        state = (jax.device_put(state_cols, sharded),  # device-ok: mesh-sharded placement
                 jax.device_put(state_nulls, sharded),  # device-ok: mesh-sharded placement
                 jax.device_put(state_valid, sharded),  # device-ok: mesh-sharded placement
                 self._side_init(stream))
        luts_t = dict(luts)

        def make_step(stream=stream):
            @partial(shard_map, mesh=mesh,
                     in_specs=(PS(WORKER_AXIS), PS(WORKER_AXIS), stream.aux_specs, PS()),
                     out_specs=PS(WORKER_AXIS))
            def step(state_g, lo_g, aux, luts_t):
                scols = tuple(c[0] for c in state_g[0])
                snulls = tuple(m[0] for m in state_g[1])
                svalid = state_g[2][0]
                s_of = state_g[3][0]
                cols, nulls, valid, of = _stream_batch(stream, lo_g, aux)
                cat_cols = tuple(jnp.concatenate([sc, c.astype(sc.dtype)])
                                 for sc, c in zip(scols, cols))
                cat_nulls = tuple(
                    jnp.concatenate([sn, jnp.zeros(v.shape, bool) if nm is None else nm])
                    for sn, nm, v in zip(snulls, nulls, cols))
                cat_valid = jnp.concatenate([svalid, valid])
                idx = _lex_indices(sort_keys, luts_t, cat_cols, cat_nulls,
                                   cat_valid)[:k]
                return (tuple(c[idx][None] for c in cat_cols),
                        tuple(m[idx][None] for m in cat_nulls),
                        cat_valid[idx][None],
                        _side_merge(s_of, of)[None])

            return _jit(step, site="dist.topn.step")

        step = self._step(node, stream, ("topn.step",), make_step)
        t0 = time.perf_counter()
        for lo in stream.scan_lo_batches:
            state = step(state, jax.device_put(lo, sharded), stream.aux, luts_t)  # device-ok: mesh-sharded placement

        got = _host(list(state[0]) + list(state[1])
                    + [state[2], state[3]], site="dist.topn.states")
        oflow = self._settle(stream, got[-1])
        if not oflow:
            # per-worker surviving-candidate counts from the states pull the
            # merge already pays — the topN analog of receive-cursor skew
            self._note_skew("dist.topn.states", node,
                            got[-2].sum(axis=1).tolist(),
                            time.perf_counter() - t0, kind="topn",
                            fields=fields)
        # host merge: W*k candidate rows -> final top-k (ordered merge stage)
        nc = len(state[0])
        cols_np = [c.reshape(-1) for c in got[:nc]]
        nulls_np = [m.reshape(-1) for m in got[nc:nc + len(state[1])]]
        valid_np = got[-2].reshape(-1)
        page = _page_to_device(Page(
            stream.schema, tuple(cols_np),
            tuple(m if m.any() else None for m in nulls_np), valid_np))
        return (_topn_page(page, sort_keys, count, stream.dicts),
                stream.dicts), oflow

    # ---------------------------------------------------------------- aggregation
    def _run_aggregate(self, node: P.Aggregate):
        out = self._retry_exchange(node, lambda: self._run_aggregate_once(node))
        if out is None:
            self._trace(node, "local", self._take_decline())
            return self.local._run_aggregate(node)
        self._trace(node, "mesh")
        return out

    def _run_aggregate_once(self, node: P.Aggregate):
        """One ladder attempt: returns ((page, dicts), oflow) or None when the
        child has no distributable scan spine."""
        if any(s.kind in P.SORTED_AGG_KINDS for s in node.aggs):
            return self._decline(node, "sort-based aggregates run the "
                                       "local selection runner")
        stream = self._fragment(node.child)
        if stream is None:
            return None
        child_schema = stream.schema
        key_types = tuple(child_schema.fields[i].type for i in node.keys)
        if not node.keys:
            return self._run_global_aggregate(node, stream)

        acc_specs, acc_exprs, acc_kinds = [], [], []
        for spec in node.aggs:
            arg = _acc_input_expr(spec)
            for kind, dtype, init in _accumulators_for(spec):
                acc_specs.append((dtype, init))
                acc_exprs.append(arg)
                acc_kinds.append(kind)
        merge_kinds = [_MERGE_KIND[k] for k in acc_kinds]

        mesh = self.mesh
        W = self.n_workers
        sharded = NamedSharding(mesh, PS(WORKER_AXIS))
        # what last held: a replay does not grow (or fall back) its way up
        # again.  [capacity, direct]: keys that are all dictionary codes or
        # booleans over at most ONEHOT_CAP_MAX slots aggregate direct-indexed
        # (slot = packed key, masked reductions, no probe and no scatter: the
        # local executor's aggregate.direct, per worker); else hash mode
        held = self._keep(node, ("capacity",), lambda: [
            node.capacity or DEFAULT_GROUP_CAPACITY,
            self._direct_config(node, stream)])

        def make_step(cfg, stream=stream):
            @partial(shard_map, mesh=mesh,
                     in_specs=(PS(WORKER_AXIS),) * 2 + (PS(WORKER_AXIS), stream.aux_specs),
                     out_specs=PS(WORKER_AXIS))
            def step(state_g, of_g, lo_g, aux):
                state = jax.tree.map(lambda x: x[0], state_g,
                                     is_leaf=lambda x: x is None)
                cols, nulls, valid, of = _stream_batch(stream, lo_g, aux)
                key_vals = tuple(cols[i] for i in node.keys)
                inputs = [(None, None) if e is None else evaluate(e, cols, nulls)
                          for e in acc_exprs]
                if cfg is not None:
                    new = hashagg.direct_groupby_insert(
                        state, cfg, key_vals, valid, inputs, acc_kinds)
                else:
                    new = _groupby_insert_live(state, key_vals, key_types,
                                               valid, inputs, acc_kinds)
                return (jax.tree.map(lambda x: x[None], new,
                                     is_leaf=lambda x: x is None),
                        _side_merge(of_g[0], of)[None])

            return _jit(step, site="dist.agg.direct_step" if cfg is not None
                        else "dist.agg.step")

        while True:
            t0 = time.perf_counter()
            capacity, cfg = held
            # one wrapper serves every capacity: the state is an argument
            step = self._step(node, stream, ("agg.step", cfg is not None),
                              partial(make_step, cfg))
            state = self._global_state_init(capacity, key_types, acc_specs, cfg)
            of_acc = self._side_init(stream)
            for lo in stream.scan_lo_batches:
                state, of_acc = step(state, of_acc, jax.device_put(lo, sharded),  # device-ok: mesh-sharded placement
                                     stream.aux)

            # the merge is dispatched before the side channel is read, so
            # that ONE pull serves both (a short bucket wastes one merge)
            merged, nocc_g = self._merge_states(node, state, key_types,
                                                acc_specs, merge_kinds)
            of2 = _host([merged.overflow, state.overflow, nocc_g, of_acc],
                        site="dist.agg.overflow")
            if self._settle(stream, of2[3]):
                return None, True  # exchange bucket overflow: ladder retry
            overflow = bool(np.any(of2[0])) or bool(np.any(of2[1]))
            if overflow and cfg is not None:
                held[1] = None  # a key outside its static range: hash mode
                continue
            if not overflow or capacity >= MAX_GROUP_CAPACITY:
                agg_wall = time.perf_counter() - t0
                break
            held[0] = capacity * 4

        nk = len(merged.key_cols)
        _exchange_fault("exchange_read", "dist.agg.groups")
        # compact occupied groups ON DEVICE: the final pull is occupancy-
        # sized (live keys + accumulators), not the full [W, capacity]
        # tables.  compact_rows preserves slot order, so the concat below
        # is in slot order a worker.
        nocc = of2[2]  # [W] per-worker live-group counts
        # occupancy skew from the nocc the overflow pull ALREADY carries:
        # which worker owns the heavy key range after the group exchange
        self._note_skew("dist.agg.overflow", node,
                        [int(x) for x in nocc], agg_wall,
                        kind="occupancy", row_bytes=sum(
                            a.dtype.itemsize for a in
                            tuple(merged.key_cols) + tuple(merged.accs)))
        out_cap = 1 << (max(int(nocc.max()), 1) - 1).bit_length()

        def make_compact():
            @partial(shard_map, mesh=mesh, in_specs=PS(WORKER_AXIS),
                     out_specs=PS(WORKER_AXIS))
            def compact_groups(state_g):
                st = jax.tree.map(lambda x: x[0], state_g,
                                  is_leaf=lambda x: x is None)
                C = st.capacity
                occ = st.table[:C] != EMPTY_KEY
                packed, _ = compact_rows(
                    tuple(k[:C] for k in st.key_cols)
                    + tuple(a[:C] for a in st.accs), occ, out_cap)
                return tuple(p[None] for p in packed)

            return _jit(compact_groups, site="dist.agg.compact")

        got = _host(list(self._keep(node, ("agg.compact", out_cap),
                                    make_compact)(merged)),
                    site="dist.agg.groups")
        key_cols = [np.concatenate([k[w][:nocc[w]] for w in range(W)])
                    for k in got[:nk]]
        acc_cols = [np.concatenate([a[w][:nocc[w]] for w in range(W)])
                    for a in got[nk:]]
        n_groups = int(nocc.sum())
        fin_cols, fin_nulls = _finalize_aggs(node.aggs, acc_cols, n_groups)
        out_cols = key_cols + fin_cols
        # host output (exact wide-decimal columns must never reach the device)
        arrays = [np.asarray(c) for c in out_cols]  # host-ok: post-_host finalize
        # grouped keys from generator scans carry no nulls on this path
        page = Page(node.schema, tuple(arrays),
                    tuple(None for _ in key_cols) + tuple(fin_nulls), None)
        dicts = tuple(stream.dicts[i] for i in node.keys) + tuple(None for _ in node.aggs)
        return (page, dicts), False

    def _direct_config(self, node, stream):
        """The direct-indexed layout of ``node``'s keys, or None: every key a
        dictionary code or a boolean (static ranges, no connector statistic to
        go stale) and the whole table small enough for masked reductions."""
        ranges = []
        for i in node.keys:
            d = stream.dicts[i]
            if d is not None and getattr(d, "values", None) is not None:
                ranges.append((0, max(len(d.values) - 1, 0)))
            elif stream.schema.fields[i].type.name == "boolean":
                ranges.append((0, 1))
            else:
                return None
        cfg = hashagg.direct_config(tuple(ranges), (False,) * len(ranges))
        if cfg is None or cfg.capacity > hashagg.ONEHOT_CAP_MAX:
            return None
        return cfg

    def _global_state_init(self, capacity, key_types, acc_specs,
                           cfg=None) -> hashagg.GroupByState:
        """[n_workers, ...] sharded state with identical empty contents per worker."""
        W = self.n_workers
        sharded = NamedSharding(self.mesh, PS(WORKER_AXIS))

        def tile(x):
            return jax.device_put(jnp.broadcast_to(x[None], (W,) + x.shape), sharded)  # device-ok: mesh-sharded placement

        key_dtypes = tuple(t.dtype for t in key_types)
        local = hashagg.groupby_init(capacity, key_dtypes, acc_specs) \
            if cfg is None else hashagg.direct_groupby_init(cfg, key_dtypes,
                                                            acc_specs)
        return jax.tree.map(tile, local, is_leaf=lambda x: x is None)

    def _merge_states(self, node, state, key_types, acc_specs, merge_kinds):
        """Hash-exchange group entries across workers and re-insert (final
        aggregation).  Returns (merged state, [W] live-group counts) — the
        counts ride the overflow flag pull the driver already pays, sizing
        the device-side group compaction without an extra sync."""
        W = self.n_workers
        # worst case: every local group routes to one worker.  Use the ACTUAL
        # (pow2-rounded) table capacity, not the requested one — bucketize
        # truncates rows beyond the bucket, so an undersized bucket would
        # silently drop groups under skew
        bucket = state.table.shape[-1] - 1

        def make_merge():
            @partial(shard_map, mesh=self.mesh, in_specs=PS(WORKER_AXIS),
                     out_specs=PS(WORKER_AXIS))
            def merge(state_g):
                state = jax.tree.map(lambda x: x[0], state_g, is_leaf=lambda x: x is None)
                C = state.capacity
                occupied = state.table[:C] != EMPTY_KEY
                keys = tuple(k[:C] for k in state.key_cols)
                accs = tuple(a[:C] for a in state.accs)
                pid = partition_ids(keys, W)
                packed_cols, packed_valid, _ = bucketize(
                    keys + accs, occupied, pid, W, bucket)
                recv_cols, recv_valid = exchange_all_to_all(packed_cols, packed_valid,
                                                            WORKER_AXIS, W)
                rkeys = recv_cols[:len(keys)]
                raccs = recv_cols[len(keys):]
                fresh = hashagg.groupby_init(max(C, _MERGE_MIN_SLOTS),
                                             tuple(t.dtype for t in key_types), acc_specs)
                merged = hashagg.groupby_insert(
                    fresh, rkeys, key_types, recv_valid,
                    [(a, None) for a in raccs], merge_kinds)
                merged = dataclasses.replace(merged, overflow=merged.overflow | state.overflow)
                nocc = jnp.sum(merged.table[:merged.capacity] != EMPTY_KEY,
                               dtype=jnp.int64)
                return (jax.tree.map(lambda x: x[None], merged,
                                     is_leaf=lambda x: x is None), nocc[None])

            return _jit(merge, site="dist.agg.merge")

        _exchange_fault("exchange_write", "dist.agg.merge")
        with maybe_span("exchange.merge"):
            return self._keep(node, ("agg.merge", bucket), make_merge)(state)

    def _run_global_aggregate(self, node, stream: _DStream):
        """Ungrouped aggregation: per-worker jnp reductions + psum/pmin/pmax across the
        mesh (reference: partial+final AggregationOperator pair)."""
        acc_specs, acc_exprs, acc_kinds = [], [], []
        for spec in node.aggs:
            arg = _acc_input_expr(spec)
            for kind, dtype, init in _accumulators_for(spec):
                acc_specs.append((dtype, init))
                acc_exprs.append(arg)
                acc_kinds.append(kind)

        mesh = self.mesh
        W = self.n_workers
        sharded = NamedSharding(mesh, PS(WORKER_AXIS))
        state = tuple(
            jax.device_put(  # device-ok: mesh-sharded placement
                jnp.broadcast_to(
                    jnp.asarray(hashagg._extreme(dt, 1 if k == "min" else -1)
                                if k in ("min", "max") else (init or 0), dt)[None], (W,)),
                sharded)
            for (dt, init), k in zip(acc_specs, acc_kinds)
        ) + (self._side_init(stream),)

        @partial(shard_map, mesh=mesh,
                 in_specs=(PS(WORKER_AXIS), PS(WORKER_AXIS), stream.aux_specs),
                 out_specs=PS(WORKER_AXIS))
        def step(state_g, lo_g, aux, stream=stream, acc_exprs=acc_exprs,
                 acc_kinds=acc_kinds):
            st = tuple(s[0] for s in state_g[:-1])
            s_of = state_g[-1][0]
            cols, nulls, valid, of = _stream_batch(stream, lo_g, aux)
            out = []
            for s, e, kind in zip(st, acc_exprs, acc_kinds):
                if kind == "count_star":
                    out.append(s + jnp.sum(valid, dtype=s.dtype))
                    continue
                v, nu = evaluate(e, cols, nulls)
                mask = valid if nu is None else (valid & ~nu)
                if kind == "count":
                    out.append(s + jnp.sum(mask, dtype=s.dtype))
                elif kind == "sum":
                    out.append(s + jnp.sum(jnp.where(mask, v, 0), dtype=s.dtype))
                elif kind in ("sum_hi32", "sum_lo32"):
                    h = (v >> 32) if kind == "sum_hi32" else (v & 0xFFFFFFFF)
                    out.append(s + jnp.sum(jnp.where(mask, h, 0), dtype=s.dtype))
                elif kind == "sum_sq":
                    vv = v.astype(s.dtype)
                    out.append(s + jnp.sum(jnp.where(mask, vv * vv, 0),
                                           dtype=s.dtype))
                elif kind == "min":
                    out.append(jnp.minimum(s, jnp.min(jnp.where(mask, v, hashagg._extreme(s.dtype, 1)))))
                elif kind == "max":
                    out.append(jnp.maximum(s, jnp.max(jnp.where(mask, v, hashagg._extreme(s.dtype, -1)))))
                else:
                    raise NotImplementedError(f"global agg kind {kind}")
            return tuple(o[None] for o in out) + (_side_merge(s_of, of)[None],)

        step = self._step(node, stream, ("global.step",),
                          lambda: _jit(step, site="dist.global.step"))
        for lo in stream.scan_lo_batches:
            state = step(state, jax.device_put(lo, sharded), stream.aux)  # device-ok: mesh-sharded placement

        got = _host(list(state),
                    site="dist.agg.states")  # one batched pull
        if self._settle(stream, got[-1]):
            return None, True  # exchange bucket overflow: ladder retry
        # cross-worker combine on host (W scalars)
        finals = []
        for v, kind in zip(got[:-1], acc_kinds):
            if kind in ("sum", "count", "count_star", "sum_hi32", "sum_lo32"):
                finals.append(v.sum(axis=0, keepdims=False)[None] if v.ndim == 0 else
                              np.asarray([v.sum()]))  # host-ok
            elif kind == "min":
                finals.append(np.asarray([v.min()]))  # host-ok
            else:
                finals.append(np.asarray([v.max()]))  # host-ok
        out_cols, out_nulls = _finalize_aggs(node.aggs, finals, 1)
        # host output (exact wide-decimal columns must never reach the device)
        arrays = [np.asarray(c) for c in out_cols]  # host-ok: post-_host finalize
        page = Page(node.schema, tuple(arrays), tuple(out_nulls), None)
        return (page, tuple(None for _ in node.aggs)), False

    # ---------------------------------------------------------------- materialize
    def _materialize_dstream(self, stream: _DStream, node=None):
        """Run a streaming-only fragment: batch outputs append into carried
        [W, cap] device buffers (no routing — each worker keeps its own rows)
        and the page assembles from device shards; the per-batch host spool
        takes what ``_host_spooled`` names."""
        mesh = self.mesh
        sharded = NamedSharding(mesh, PS(WORKER_AXIS))
        fields = stream.schema.fields
        if not _host_spooled(stream):
            return self._materialize_dstream_device(stream, node=node)

        @partial(shard_map, mesh=mesh, in_specs=(PS(WORKER_AXIS), stream.aux_specs),
                 out_specs=PS(WORKER_AXIS))
        def run(lo_g, aux, stream=stream):
            cols, nulls, valid, of = _stream_batch(stream, lo_g, aux)
            nulls = tuple(jnp.zeros(c.shape, bool) if n is None else n
                          for c, n in zip(cols, nulls))
            return (tuple(c[None] for c in cols), tuple(n[None] for n in nulls),
                    valid[None], of[None])

        run = self._step(node, stream, ("stream.run",),
                         lambda: _jit(run, site="dist.stream.run"))
        # an empty part first: a stream with no batch is an empty page
        parts_cols = [[np.zeros((0,), np.dtype(f.type.dtype)) for f in fields]]
        parts_nulls = [[np.zeros((0,), bool) for _ in fields]]
        side = None
        rows_w = np.zeros((self.n_workers,), np.int64)
        t0 = time.perf_counter()
        for lo in stream.scan_lo_batches:
            cols, nulls, valid, of = run(jax.device_put(lo, sharded), stream.aux)  # device-ok: mesh-sharded placement
            got = _host(list(cols) + list(nulls) + [valid, of],
                        site="dist.stream.collect")
            if self._overflowed(stream, got[-1]):
                return None, True  # exchange bucket overflow: ladder retry
            side = got[-1] if side is None else _side_merge(side, got[-1], np)
            rows_w += got[-2].sum(axis=1)  # [W, cap] valid, pre-flatten
            v = got[-2].reshape(-1)
            parts_cols.append([c.reshape(-1)[v] for c in got[:len(cols)]])
            parts_nulls.append([n.reshape(-1)[v]
                                for n in got[len(cols):len(cols) + len(nulls)]])
        if side is not None:
            self._settle(stream, side)
        self._note_skew("dist.stream.collect", node, rows_w.tolist(),
                        time.perf_counter() - t0, kind="stream",
                        fields=fields)
        ncols = len(fields)
        cols = tuple(np.concatenate([p[i] for p in parts_cols])
                     for i in range(ncols))
        nulls_np = [np.concatenate([p[i] for p in parts_nulls]) for i in range(ncols)]
        nulls = tuple(n if n.any() else None for n in nulls_np)
        # staged, counted, injectable H2D — not a bare jnp.asarray re-upload
        page = _page_to_device(Page(stream.schema, cols, nulls, None))
        return (page, stream.dicts), False

    def _materialize_dstream_device(self, stream: _DStream, node=None):
        """Device-resident materialize: the same carried receive-buffer state
        as ``_exchange_collect_device`` minus the routing — each worker's
        batch output packs (``append_rows``) into its own shard, only scalar
        cursor/overflow flags sync per run, and the final page assembles on
        device via ``_page_from_shards``."""
        mesh, W = self.mesh, self.n_workers
        sharded = NamedSharding(mesh, PS(WORKER_AXIS))
        dtypes = [np.dtype(f.type.dtype) for f in stream.schema.fields]
        cap = self._recv_capacity(stream)
        while True:
            t0 = time.perf_counter()
            state = self._recv_state_init(stream, cap, dtypes)

            @partial(shard_map, mesh=mesh,
                     in_specs=(PS(WORKER_AXIS), PS(WORKER_AXIS),
                               stream.aux_specs),
                     out_specs=PS(WORKER_AXIS))
            def run(state_g, lo_g, aux, stream=stream):
                bufs = tuple(b[0] for b in state_g[0])
                nbufs = tuple(b[0] for b in state_g[1])
                cursor = state_g[2][0]
                lad_of, recv_of = state_g[3][0], state_g[4][0]
                cols, nulls, valid, of = _stream_batch(stream, lo_g, aux)
                cols = tuple(c.astype(dt) for c, dt in zip(cols, dtypes))
                nulls = tuple(jnp.zeros(c.shape, bool) if m is None else m
                              for c, m in zip(cols, nulls))
                new, ncur, b_of = append_rows(bufs + nbufs, cursor,
                                              cols + nulls, valid)
                k = len(bufs)
                return (tuple(b[None] for b in new[:k]),
                        tuple(b[None] for b in new[k:]),
                        ncur[None], _side_merge(lad_of, of)[None],
                        (recv_of | b_of)[None])

            run = self._step(node, stream, ("stream.route",), lambda run=run:
                             _jit(run, site="dist.stream.route"))
            for lo in stream.scan_lo_batches:
                state = run(state, jax.device_put(lo, sharded), stream.aux)  # device-ok: mesh-sharded placement
            cursor, side, recv_of = _host(
                [state[2], state[3], state[4]], site="dist.stream.flags")
            if self._settle(stream, side):
                return None, True  # exchange bucket overflow: ladder retry
            if not bool(np.any(recv_of)):
                break
            cap *= 4
            if cap > (1 << 28):
                return None, True
        counts = [int(c) for c in cursor]
        self._note_skew("dist.stream.flags", node, counts,
                        time.perf_counter() - t0, kind="stream",
                        fields=stream.schema.fields)
        if sum(counts) == 0:
            page = Page(stream.schema,
                        tuple(jnp.zeros((0,), dt) for dt in dtypes),
                        tuple(None for _ in dtypes), None)
            return (page, stream.dicts), False
        cols_g, nulls_g, valid_g = self._slim_shards(node, state, counts,
                                                     "dist.stream.slim")
        page = _page_from_shards(stream.schema, cols_g, nulls_g, counts,
                                 keep=partial(self._keep, node))
        return (page, stream.dicts), False
