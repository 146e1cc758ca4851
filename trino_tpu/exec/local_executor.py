"""Single-device fused-pipeline executor.

The reference pumps pages through an operator chain one page at a time
(operator/Driver.java:283,372-481) with per-operator compiled bytecode.  The TPU re-design
*fuses a whole pipeline into one jit-compiled step function* per page-shape class: scan
generation, filter, projections and the aggregation/join-build sink all trace into a single
XLA program, so elementwise work fuses into the scatter/gather kernels and pages never leave
HBM between "operators".  The Python driver loop only sequences splits and carries the
accumulated state pytree (the moral equivalent of Driver.process's loop, but per-split
instead of per-operator-call).

Pipeline boundaries match the reference's: an Aggregate or Join-build is a sink that
materializes state (reference: HashAggregationOperator / HashBuilderOperator); everything
between sources and sinks is streaming.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import time as _time
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..connectors.tpch import Dictionary
from ..execution import faults, tracing
from ..execution.history import estimate_plan_rows, plan_node_paths
from ..memory import MemoryPool
from ..ops import hashagg
from ..ops.arrays import (ArrayData, MapData, compact_rows, pack_span,
                          span_len, span_start, unnest_indices)
from ..ops.exchange import partition_ids
from ..ops.hashing import ceil_pow2
from ..ops.hashjoin import (DIRECT_JOIN_RANGE_MAX, DirectJoinTable,
                            DirectMultiJoinTable, JoinTable,
                            build_insert, build_table_init, direct_build,
                            direct_match, direct_multi_build, direct_probe,
                            direct_probe_slots,
                            expand_counts, multi_build, probe, probe_counted,
                            probe_slots, probe_widths,
                            stage_direct_table, GATHER_FIELDS, MATCH_FIELDS)
from ..ops.matcher import vector_match
from ..ops.window import (_window_kernel, _window_sort_passes,
                          _window_spec_dicts)
from ..page import Field, Page, Schema
from ..spi.predicate import UNION_LIMIT, Domain, Range
from ..types import BIGINT, INTEGER
from ..sql import ir as _ir
from ..sql import plan as P
from ..sql.analyzer import _coerce
from ..sql.domain_translator import (domain_to_split_pruner,
                                     extract_domains, split_conjuncts)
from ..sql.ir import FieldRef, evaluate, evaluate_predicate
from ..sql.plan import _plan_fingerprint
from .boundary import (DISPATCH_BATCH, _coalesced_batches,
                       _current_batch_host_params, _current_host_params,
                       _current_params, _generate, _host, _jit,
                       _page_batch_sig, _page_to_device, _params_scope,
                       _prefetched_pages, _stack_pages, _statement_scopes)
from .groupby import (DEFAULT_GROUP_CAPACITY, FIRST_CAPACITY_CAP,
                      MAX_GROUP_CAPACITY, _acc_input_expr, _accumulators_for,
                      _device_finalize_plan, _direct_init, _finalize_aggs,
                      _finalize_aggs_device, _global_agg_update,
                      _global_init_state, _group_count, _group_state_bytes,
                      _hash_init, _MERGE_KIND)
from .spill import SpilledPartitions
from .pages import (MaterializedResult, _build_key_stats, _build_null_stats,
                    _collation_rank_lut, _compact_page, _compact_part,
                    _concat_all, _concat_bindings_parts, _concat_stream,
                    _gather_build, _host_page, _limit_page, _materialize,
                    _materialize_host, _null_aware_anti, _page_bytes,
                    _sort_page, _sort_page_device, _split_base_rows,
                    _topn_page, _topn_page_device, _values_page)

__all__ = ["LocalExecutor", "BatchUnsupported"]


class BatchUnsupported(Exception):
    """A plan/page combination the fused bindings-batched path (round 21)
    cannot run: plan shape outside the streaming subset, or an untraceable
    object-dtype (exact wide-decimal) page mid-scan.  The engine marks the
    template unbatchable and the batcher re-runs every window member on its
    own serial path — byte-identically, just without the fusion win."""


# test seam for per-lane demux failures: when set, called (lane, nlanes)
# before each member's result decode — tests inject a one-lane error here to
# pin the "a batch member that errors fails ONLY its own request" contract
BATCH_LANE_TEST_HOOK = None


def _batchable_plan(node) -> bool:
    """Can this template plan run the fused bindings-batched path?  The
    subset is the scan/filter/project streaming core (plus Union/Values):
    one _compile_stream chain, no blocking operators.  Sort/Limit — although
    inside the TEMPLATE subset — stay serial: their device kernels consume a
    single [n] page, and a per-lane top-N over [R, n] is its own project
    (the batcher falls back per window, so they lose nothing)."""
    allowed = (P.Output, P.Project, P.Filter, P.TableScan, P.Union, P.Values)
    if not isinstance(node, allowed):
        return False
    return all(_batchable_plan(c) for c in node.children)


@dataclasses.dataclass
class _ScanInfo:
    """Provenance of a stream's page source: lets joins prune probe splits against
    build-side key domains (reference: DynamicFilterService split pruning)."""

    conn: object
    splits: list
    scan_columns: tuple  # column names requested from the connector
    columns: tuple  # per OUTPUT channel: source column name | None (through projects)
    catalog: str = ""  # catalog/table identity: split-pruning replacements
    table: str = ""  # rebuild their page source through the executor's
    # cache-aware _scan_pages_source, which keys the buffer pool on them
    over: Optional[Callable] = None  # set once a boundary (a split join's
    # compaction) sits between the scan and the stream's ``pages``: maps a raw
    # page source over another split list to the stream's pages over it, so
    # split pruning above the boundary still rebuilds the scan underneath it

    def pages_over(self, raw_pages: Callable) -> Callable:
        """The owning stream's page source rebuilt over ``raw_pages`` (a
        pruned split list of the same scan)."""
        return raw_pages if self.over is None else self.over(raw_pages)


@dataclasses.dataclass
class _Stream:
    """A streaming pipeline segment: a source of raw pages + a fused transform.

    ``aux`` carries the segment's device-resident state (join tables, build
    columns) and is passed to the transform as a JIT ARGUMENT.  It must never be
    closed over: a closed-over device array is baked into the executable as a
    constant, so every new table is a recompile and its bytes live in the
    program; as an argument the same executable serves every table."""

    schema: Schema
    dicts: tuple  # Dictionary|None per channel
    pages: Callable  # () -> iterator of raw source Pages
    transform: Callable  # (cols, nulls, valid, aux) -> (cols, nulls, valid)
    scan_info: Optional[_ScanInfo] = None
    aux: tuple = ()  # pytree of device state threaded through jit as an argument
    clustered_by: tuple = ()  # SOURCE column names whose equal-value rows
    # are CONTIGUOUS in scan order (connector-declared; weaker than sorted —
    # no cross-group order promise).  Filters/projects/compaction preserve
    # row order, so the flag survives them; joins clear it.  Gates the
    # streaming aggregation, which needs exactly group contiguity.
    compacted: bool = False  # the chain's first unique-key inner/semi join
    # has decided where its compaction boundary sits: between that join's
    # match and gather steps (_compacted_stream), or nowhere because its first
    # page stayed dense.  Later joins of the chain run fused at the width
    # that left
    passes_valid: bool = False  # the transform hands its input's validity
    # mask on untouched (a source, projections over one): a packed page that
    # knows its live count (Page.live) is still packed behind it
    materialised: bool = False  # the source is ONE finished page of a blocking
    # child (Aggregate, Sort, Window, ...), re-made every execution; Filter
    # and Project keep the mark, joins, unions, overrides and scans have
    # none.  A group-by over it reads its keys' bounds from that page
    _jitted: Callable = None  # cached jit of transform applied to a Page
    _batch_jitted: Callable = None  # cached jit of transform over a STACKED
    # group of uniform pages (dispatch coalescing; retraces per group arity)
    _bindings_jitted: Callable = None  # cached jit of transform vmapped over
    # a BINDINGS batch (round 21: one dispatch serves R template requests)

    def jitted(self):
        """Jit-compiled page->(cols,nulls,valid) function, cached on the stream so
        repeated executions of a cached plan reuse the XLA executable."""
        if self._jitted is None:
            def step(page, aux, params):
                # params bind INSIDE the trace: ir.Parameter leaves read the
                # traced argument, so bound values are runtime inputs — a
                # warm template dispatch reuses this executable with new
                # scalars instead of re-tracing (and never closes over them)
                with _ir.bind_params(params):
                    return self.transform(page.columns, page.null_masks,
                                          page.valid_mask(), aux)

            f = _jit(step, site="stream.page")

            def run(page, f=f):
                if any(isinstance(c, np.ndarray) and c.dtype == object
                       for c in page.columns):
                    # exact wide-decimal (object) columns cannot trace; run the
                    # transform eagerly — they only ever pass through FieldRef
                    # projections at the result surface (jnp ops on the other
                    # channels execute op-by-op)
                    try:
                        with _ir.bind_params(_current_params()):
                            return self.transform(page.columns,
                                                  page.null_masks,
                                                  page.valid_mask(), self.aux)
                    except (TypeError, OverflowError) as e:
                        raise NotImplementedError(
                            "expressions over an exact wide-decimal aggregate "
                            "(sum beyond 2^63) are not supported yet — such "
                            "sums can only be output directly") from e
                return f(page, self.aux, _current_params())

            self._jitted = run
        return self._jitted

    def jitted_batch(self):
        """One-dispatch transform of a GROUP of shape-uniform pages: the pages
        stack (concatenate) inside the trace and the fused transform runs once
        over the [K*n] rows — K splits, one dispatch.  Groups come
        from ``_coalesced_batches`` (object-dtype pages never group, so the
        eager wide-decimal path stays on ``jitted()``), which pads every group
        to exactly K pages with a ``live`` mask — fixed arity, so ONE compiled
        executable per page shape (do not "optimize" the padding away: size-
        shaped groups would retrace per arity and multiply cold compiles)."""
        if self._batch_jitted is None:
            def bstep(pages, live, aux, params):
                with _ir.bind_params(params):  # same contract as jitted()
                    return self.transform(*_stack_pages(pages, live), aux)

            f = _jit(bstep, site="stream.batch")

            def run(pages, live, f=f):
                return f(tuple(pages), live, self.aux, _current_params())

            self._batch_jitted = run
        return self._batch_jitted

    def jitted_bindings(self):
        """One-dispatch transform of one page under a BINDINGS batch (round
        21, continuous template batching): the stacked parameter slots carry
        a leading [R] requests axis, ``ir.bind_params`` opens per lane INSIDE
        the trace, and the step vmaps over that axis — R same-template
        requests, one dispatch.  The page and aux broadcast (they
        are identical across lanes; vmap closes over the outer trace's
        tracers), so outputs come back as [R, n] columns/nulls/validity the
        demux slices per request.  Callers pad R to a pow2 rung, so this
        compiles one executable per (plan, rung) — never per batch size."""
        if self._bindings_jitted is None:
            def bindings_step(page, aux, stacked):
                def one(params):
                    with _ir.bind_params(params):
                        return self.transform(page.columns, page.null_masks,
                                              page.valid_mask(), aux)

                return jax.vmap(one)(stacked)

            f = _jit(bindings_step, site="stream.bindings")

            def run(page, stacked, f=f):
                return f(page, self.aux, stacked)

            self._bindings_jitted = run
        return self._bindings_jitted


class LocalExecutor:
    """Executes a plan tree on the local device set (one chip or CPU).

    Compiled pipelines (fused stream transforms, jitted aggregation steps, join build
    tables) are cached per plan-node identity: re-executing a cached plan skips both
    tracing and XLA compilation (reference analog: PageFunctionCompiler's bytecode caches,
    sql/gen/PageFunctionCompiler.java:103).  Valid while connector data is immutable —
    true for generator connectors; mutating connectors must invalidate the engine's plan
    cache."""

    def __init__(self, catalogs: dict, memory_pool=None, buffer_pool=None):
        self.catalogs = catalogs
        # dispatch-coalescing width for this executor's queries: None resolves
        # to DISPATCH_BATCH.  The engine sets it per query from the
        # ``dispatch_batch`` session property, which rides the plan-cache key
        # — so a cached plan's compiled batch artifacts always match the
        # batch the plan was keyed under.
        self.dispatch_batch = None
        # bound plan-template parameters for the CURRENT query: tuple of
        # (0-d numpy value, isnull) pairs, one per template slot (engine
        # sets it per query like dispatch_batch; reset on release).  execute()
        # stages them to the device once and publishes them thread-locally
        # for the jitted step wrappers.
        self.exec_params = None
        # device buffer pool (execution/bufferpool.DeviceBufferPool), shared
        # across the engine's pooled executors (a WorkerServer passes its
        # own).  ``page_cache`` is the per-query session-property override
        # (None = the pool's TRINO_TPU_PAGE_CACHE gate) — NON-plan-shaping:
        # the cache only changes where scan pages come from, never the plan.
        self.buffer_pool = buffer_pool
        self.page_cache = None
        self._stream_cache: dict = {}  # id(node) -> (node, _Stream)
        self._agg_cache: dict = {}  # id(node) -> compiled aggregation artifacts
        self.stats: dict = {}  # id(node) -> {"rows": int, "wall_s": float}
        # plan-actuals addressing (round 15): structural node paths + CBO row
        # estimates for the CURRENT plan, stamped by begin_plan() so every
        # stats registration (_node_stats) can capture them.  _est_cache
        # memoizes per plan-root identity — warm executions of a cached plan
        # pay zero re-estimation (entries evict with forget_plan).
        self._node_paths: dict = {}
        self._node_ests: dict = {}
        self._est_cache: dict = {}  # id(root) -> (paths, ests)
        # compile-time advisory facts for plan-history: id(node) ->
        # (node, {"splits": n} | {"build_rows": <lazy device count>,
        # "wall_s": s}).  Scans and join build sides are streaming — the
        # stats dict never records them — but their observed shapes are
        # exactly what the adaptive advisor needs (dispatch_batch tuning,
        # broadcast-vs-partitioned truth).  Facts are static per compiled
        # stream, so capturing at compile time covers every warm execution;
        # the strong node ref keeps id() stable (the _stream_cache contract)
        # and forget_plan sweeps entries with the other id-keyed caches.
        self._plan_facts: dict = {}
        self._fp_cache: dict = {}  # id(root) -> structural fingerprint —
        # _plan_fingerprint is a content-based string walk; memoized so the
        # per-statement history record costs a dict lookup on warm plans
        # (same identity/eviction contract as _est_cache: plans are pinned
        # by the engine caches and forget_plan drops the entry)
        # per-query device-boundary counters (reset at execute()): dispatches
        # + host pulls recorded via execution/tracing while this executor runs
        self.counters = tracing.QueryCounters()
        # per-operator boundary attribution (reset at execute()): id(node) ->
        # {"label", "dispatches", "transfers", "bytes"}, plus a "result" entry
        # for the final materialization pull.  Innermost-scope-wins, so the
        # per-operator sums equal the query's counter totals exactly —
        # EXPLAIN ANALYZE renders these beside the per-node stats
        self.boundary: dict = {}
        self._op_labels: dict = {}  # id(node) -> stable "<Type>#<k>" label
        # node-result substitutions: id(node) -> (Page, dicts).  The FTE
        # executor installs durable (spooled) fragment outputs here so the
        # remainder of the plan consumes them instead of re-executing the
        # subtree (reference: ExchangeOperator reading spooled task output)
        self._overrides: dict = {}
        # HBM accounting: operators reserve before allocating device state and
        # switch to partitioned (Grace) strategies when the pool says no
        # (reference: MemoryPool + MemoryRevokingScheduler -> spill)
        self.memory_pool = memory_pool if memory_pool is not None else MemoryPool()
        # live prefetch producers started for the CURRENT query: (stop flag,
        # thread) pairs registered by _prefetched_pages.  close_producers()
        # stops them on every exit path — clean or error — so a mid-query
        # exception can never strand a producer thread behind its traceback
        self._producers: list = []
        # live tiered spills (exec/spill.SpilledPartitions) registered by the
        # Grace-partitioned paths: swept with the producers on every exit
        # path so an error unwind can never strand a "spill" reservation or
        # an on-disk partition file.  Persistent entries (the partitioned
        # join's build side, cached with its compiled stream) survive the
        # sweep and free via forget/GC.
        self._spills: list = []

    def _batch(self, stream=None) -> int:
        """Effective dispatch-coalescing width (>=1; 1 = per-split) for the
        consumers of ``stream``'s pages.  Behind a split join's boundary
        (``scan_info.over``) every page already is one coalesced group,
        packed or dense: stacking K of those again would cost K times the
        lanes for no saved launch, so their consumers take them singly."""
        if stream is not None and stream.scan_info is not None \
                and stream.scan_info.over is not None:
            return 1
        b = self.dispatch_batch
        if b is None or int(b) <= 0:
            return DISPATCH_BATCH
        return int(b)

    def _rewrap_pruned_pages(self, pages_fn, conn, n_splits: int,
                             table: str = ""):
        """Re-apply the scan's prefetch policy to a pruner-replaced page
        source: split pruning builds a bare generator, losing whichever wrap
        the TableScan compiled with.  HOST_DECODE connectors prefetch
        regardless of batch width (host decode must overlap device compute);
        device generators get the coalescing double buffer when multi-split
        and coalescing is on."""
        if conn is not None and getattr(conn, "HOST_DECODE", False):
            return _prefetched_pages(pages_fn, to_device=True, owner=self,
                                     table=table)
        if n_splits > 1 and self._batch() > 1:
            return _prefetched_pages(pages_fn, depth=self._batch(),
                                     to_device=True, warmup=2, owner=self,
                                     table=table)
        return pages_fn

    def _page_cache_on(self) -> bool:
        """Does THIS query consult the device buffer pool?  The ``page_cache``
        session property overrides per query; otherwise the pool's
        TRINO_TPU_PAGE_CACHE budget decides (0 = off, the CPU default).  A
        ``page_cache=true`` query against an unconfigured (zero-budget) pool
        still gets nothing to read — the property gates USE of a configured
        pool, it does not conjure a budget."""
        bp = self.buffer_pool
        if bp is None or not bp.enabled:
            return False
        if self.page_cache is not None:
            return bool(self.page_cache)
        return True

    def _scan_pages_source(self, conn, catalog: str, table: str, splits,
                           scan_cols):
        """Cache-aware page source for a (possibly split-pruned) table scan.

        Cache hit: the WHOLE completed scan is served as ONE device-resident
        page — no host generation, no H2D staging, and every downstream
        per-split consumer loop (stream transforms, agg inserts, compaction
        syncs) collapses to a single dispatch per stage.  Row order is split
        order, so the page computes exactly what the per-split stream would
        (the _stack_pages soundness argument, applied once at store time).

        Cache miss: the ordinary per-split path runs — with its prefetch /
        double-buffer wrap — while the consumer-side loop below accumulates
        the raw pages and stores the concatenated scan ONLY on clean
        exhaustion (a LIMIT short-circuit or error unwind must never cache a
        partial scan).  The lookup, the accounting and the store all run on
        the QUERY thread (generator bodies execute at the consumer's next()),
        so cache counters never race the prefetch producer."""
        splits = list(splits)
        scan_cols = tuple(scan_cols)

        def raw(conn=conn, splits=splits, scan_cols=scan_cols, table=table):
            for s in splits:
                # chaos chokepoint: per-split generation faults surface here —
                # on the PREFETCH PRODUCER thread when the scan is wrapped,
                # which is exactly the path whose cleanup the chaos suite pins
                faults.maybe_inject("generate", f"scan.{table}")
                yield _generate(conn, table, s, scan_cols)

        wrapped = self._rewrap_pruned_pages(raw, conn, len(splits), table)
        bp = self.buffer_pool
        split_rows = _split_base_rows(conn, table, splits)

        def pages(self=self):
            key = None
            if splits and bp is not None and self._page_cache_on() \
                    and bp.cacheable(conn):
                key = bp.page_key(catalog, conn, table, splits, scan_cols)
                site = f"scan.{table}.cache"
                hit = bp.get_page(key)
                if hit is not None:
                    page, nbytes = hit
                    tracing.record_page_cache(hits=1, bytes_saved=nbytes,
                                              site=site)
                    yield page
                    return
                tracing.record_page_cache(misses=1, site=site)
            acc = [] if key is not None and not bp.has_page(key) else None
            acc_bytes = 0
            for i, pg in enumerate(wrapped()):  # one page a split
                tracing.record_rows_generated(split_rows[i])
                if acc is not None:
                    # pin no page the pool would reject anyway: a scan whose
                    # splits (one shape class, so the first page prices them
                    # all) pass the pool's per-entry cap, or one with object
                    # columns that cannot live on device, is pure streaming —
                    # pages release as consumed, exactly like cache-off
                    nbytes = _page_bytes(pg)
                    acc_bytes += nbytes
                    if max(acc_bytes, nbytes * len(splits)) \
                            > bp.page_entry_cap() or any(
                            isinstance(c, np.ndarray) and c.dtype == object
                            for c in pg.columns):
                        acc = None
                    else:
                        acc.append(pg)
                yield pg
            if acc:
                # the store's staging can wedge like any other device work:
                # hold an in-flight registry entry so the stall watchdog sees
                # a hang here instead of an idle-looking query.  A store
                # FAILURE (injected fault, staging error) must not fail a
                # query whose scan already completed — and it must never
                # leave a partial entry behind, so the store is all-or-
                # nothing: put_page admits only the fully staged page
                try:
                    with tracing.inflight("cache-store",
                                          site=f"scan.{table}.store"):
                        bp.put_page(key, _stage_scan_entry(acc))
                except tracing.StallKilledError:
                    raise  # a watchdog kill must never be neutralized here
                except Exception:
                    pass  # uncached, not failed; the next query regenerates

        return pages

    def forget_plan(self, plan: P.PlanNode) -> None:
        """Evict compiled artifacts for a plan the engine is replacing (its
        version-stale plan-cache path).  Cache keys are id(node) or tuples
        containing one; entries pin node objects, jit executables, and device
        arrays, so a replan without eviction would leak a full compiled copy."""
        ids = set()

        def walk(n):
            ids.add(id(n))
            for c in n.children:
                walk(c)

        walk(plan)

        def dead(key):
            if isinstance(key, tuple):
                return any(k in ids for k in key if isinstance(k, int))
            return key in ids

        for cache in (self._stream_cache, self._agg_cache, self._est_cache,
                      self._fp_cache, self._plan_facts):
            # list() snapshots the keys atomically (C-level, GIL-held) so a
            # concurrent query inserting into the same dict cannot raise
            # "dictionary changed size during iteration"; pop() tolerates keys
            # already gone.  A running query that held the evicted node just
            # re-inserts on its next access.
            for key in [k for k in list(cache) if dead(k)]:
                cache.pop(key, None)
        # persistent spills (a partitioned join's build tier) live with the
        # compiled stream being evicted: close them HERE — jax's global jit
        # caches pin the closure graph, so waiting on GC/__del__ would leave
        # their disk partitions and "spill-build" reservations around for
        # the process lifetime
        keep = []
        for sp in self._spills:
            if sp.persistent and sp.node_id in ids:
                sp.close()
            else:
                keep.append(sp)
        self._spills = keep

    def close_producers(self, join_timeout: float = 2.0) -> int:
        """Stop every prefetch producer this executor started for the current
        query: set each stop flag, then briefly join the threads.  Called on
        every execute() exit (and by the FTE/cluster drivers that call
        _execute_to_page directly) — on the clean path the producers have
        already exited and this is a no-op sweep; on an error path it is what
        guarantees no producer thread survives the query.  Returns how many
        producers were registered (the chaos suite asserts on thread death
        separately)."""
        procs, self._producers = self._producers, []
        for stop, _t in procs:
            stop.set()
        deadline = _time.monotonic() + join_timeout
        for _stop, t in procs:
            if t.is_alive():
                t.join(timeout=max(deadline - _time.monotonic(), 0.05))
        # sweep per-query tiered spills on the same exit paths (execute()
        # clean/error, FTE/cluster drivers, engine release): close() is
        # idempotent, so the normal in-path close costs nothing here, and an
        # error unwind releases "spill" reservations + disk files instead of
        # leaking them behind the traceback
        spills, self._spills = self._spills, []
        for sp in spills:
            if sp.persistent:
                self._spills.append(sp)  # cached join-build state: lives
                # with the compiled stream, freed on forget/GC
            else:
                sp.close()
        return len(procs)

    def begin_plan(self, root: P.PlanNode) -> None:
        """Stamp the structural node-path and CBO row-estimate maps for the
        plan this executor is about to run (execution/history.py) — what lets
        ``_node_stats`` capture merge-stable addresses and estimates at
        registration time.  Host-only walk over the plan and connector stats
        surfaces: zero dispatches, zero pulls; memoized per plan-root
        identity so warm cached-plan executions pay a dict lookup.  Drivers
        that bypass execute() (cluster local finish, worker task bodies)
        call this before _execute_to_page for history coverage; skipping it
        only loses history, never correctness.  A plan seen for the first
        time also has its scans' generators started (_warm_scans)."""
        hit = self._est_cache.get(id(root))
        if hit is None:
            try:
                hit = (plan_node_paths(root),
                       estimate_plan_rows(root, self.catalogs))
            except Exception:
                hit = ({}, {})  # estimation is advisory: run without it
            self._est_cache[id(root)] = hit
            self._warm_scans(root)
        self._node_paths, self._node_ests = hit

    def plan_fingerprint(self, root: P.PlanNode) -> str:
        """Memoized structural fingerprint of ``root`` (the history-store
        key; see _plan_fingerprint for the identity argument)."""
        fp = self._fp_cache.get(id(root))
        if fp is None:
            fp = self._fp_cache[id(root)] = _plan_fingerprint(root,
                                                              self.catalogs)
        return fp

    # ------------------------------------------------------------------ public
    def execute(self, node: P.PlanNode) -> MaterializedResult:
        self.stats = {}
        self.boundary = {}
        self._op_labels = {}
        self.begin_plan(node)
        self.counters.reset()
        # sweep, don't discard: a producer somehow still registered (a driver
        # path without the finally, an async kill mid-registration) must get
        # its stop flag set, not be dropped to pump forever unseen
        self.close_producers()
        # bound template parameters: staged to the device ONCE per query
        # (scalars — a handful of bytes), then threaded into every dispatch
        # as jit arguments by the step wrappers.  jnp.asarray here is the
        # sanctioned staging point for these scalars; pages keep going
        # through _page_to_device.
        dev_params = tuple(
            (jnp.asarray(v), jnp.asarray(bool(isnull)))
            for v, isnull in (self.exec_params or ()))
        try:
            with _params_scope(dev_params, tuple(self.exec_params or ())), \
                    tracing.track_counters(self.counters):
                page, dicts = self._execute_to_page(node)
                # the result pull is real boundary spend outside any plan
                # node: attribute it to a synthetic "Result" operator so the
                # per-op sums still equal the query totals
                with tracing.operator_scope(
                        "Result", self._boundary_sink("result", "Result")):
                    return _materialize(page, dicts)
        finally:
            # clean or error exit: no prefetch producer outlives the query
            self.close_producers()

    def _warm_scans(self, root: P.PlanNode) -> None:
        """Tell the connector of every scan in the plan that the scan is coming
        (``warm_scan``, where a connector has it): the streams compile their
        scans in execution order, a probe side's last, and a generator's first
        compile is long enough to be worth starting beside the builds."""
        stack = [root]
        while stack:
            n = stack.pop()
            if isinstance(n, P.TableScan):
                conn = self.catalogs.get(n.catalog)
                warm = getattr(conn, "warm_scan", None)
                if warm is not None:
                    warm(n.table, tuple(n.columns),
                         generate=self._warm_generate(conn, n.table))
            stack.extend(n.children)

    def _warm_generate(self, conn, table: str):
        """``generate(split, columns)`` for a connector's warm thread: the
        launch goes through ``_generate`` under THIS statement's counters,
        query id and trace, so that the generator's first compile (the
        longest of a cold SF10 set-up) is a compile event of the statement
        that started it.  Not a ``generator_dispatches``: no scan source asked
        for the page."""
        qid = tracing.current_query_id()
        tracer = tracing.current_tracer()
        parent = tracer.current() if tracer is not None else None

        def generate(split, columns):
            with _statement_scopes(self.counters, qid, tracer), \
                    contextlib.nullcontext() if tracer is None else \
                    tracer.span("generate.warm", parent=parent, table=table):
                return _generate(conn, table, split, columns, count=False)

        return generate

    def execute_batched(self, node: P.PlanNode, runtimes) -> list:
        """Round 21 — continuous template batching: ONE fused execution of a
        template plan over R bound runtimes (each a tuple of per-slot
        ``(numpy value, isnull)`` pairs).  The parameter slots stack with a
        leading requests axis, the streaming chain runs once per page through
        ``jitted_bindings`` (vmap over the lane axis), and the result surface
        demultiplexes per lane from ONE batched pull.  Returns a list aligned
        with ``runtimes``: MaterializedResult per member, or that member's
        own Exception (per-lane decode failures never poison siblings).

        Raises BatchUnsupported when the plan/pages cannot take this path —
        the caller (execution/batcher via engine) re-runs every member
        serially.  R pads to a pow2 rung by repeating the LAST member's
        bindings (padding lanes are sliced away before decode), so the
        compile census sees one signature per rung, never one per batch
        size."""
        if not runtimes or not runtimes[0]:
            raise BatchUnsupported("empty batch / parameterless template")
        if not _batchable_plan(node):
            raise BatchUnsupported(
                "plan shape outside the streaming bindings-batch subset")
        self.stats = {}
        self.boundary = {}
        self._op_labels = {}
        self.begin_plan(node)
        self.counters.reset()
        self.close_producers()
        n = len(runtimes)
        rung = 1 << max(n - 1, 0).bit_length()
        padded = list(runtimes) + [runtimes[-1]] * (rung - n)
        nslots = len(runtimes[0])
        # stack the slots host-side (per-slot [R] value + [R] isnull), then
        # stage once — jnp.asarray is the sanctioned scalar-staging idiom
        # (same as execute()); np here touches only host-side bound scalars
        stacked = tuple(
            (jnp.asarray(np.stack([np.asarray(r[s][0]) for r in padded])),  # host-ok: pre-staging bound scalars
             jnp.asarray(np.array([bool(r[s][1]) for r in padded])))
            for s in range(nslots))
        out_schema = node.schema if isinstance(node, P.Output) else None
        inner = node.child if isinstance(node, P.Output) else node
        try:
            # device-value TLS stays EMPTY on purpose: any path that consumes
            # per-request scalars outside the bindings-vmapped step (an eager
            # object-column fallback, a stray _current_params() reader) fails
            # loudly, and the batcher re-runs the window serially — it can
            # never silently compute one member's answer for every lane
            with _params_scope((), batch_hosts=tuple(tuple(r)
                                                     for r in runtimes)), \
                    tracing.track_counters(self.counters):
                label = self._op_label(inner)
                parts = []
                with tracing.operator_scope(
                        label, self._boundary_sink(id(inner), label)):
                    stream = self._compile_stream(inner)
                    brun = stream.jitted_bindings()
                    for page in stream.pages():
                        if any(isinstance(c, np.ndarray)
                               and c.dtype == object for c in page.columns):
                            raise BatchUnsupported(
                                "object-dtype page cannot trace")
                        parts.append(brun(page, stacked))
                schema = out_schema if out_schema is not None \
                    else stream.schema
                with tracing.operator_scope(
                        "Result", self._boundary_sink("result", "Result")):
                    return self._demux_batched(schema, stream.dicts, parts,
                                               n)
        finally:
            self.close_producers()

    def _demux_batched(self, schema, dicts, parts, n: int) -> list:
        """Per-request result decode for a fused bindings batch: ONE batched
        pull of the [R, rows] columns/nulls/validity, then a per-lane numpy
        slice through the shared host-side decode.  A lane whose decode
        raises carries its own exception in the returned list."""
        if parts:
            if len(parts) == 1:
                cols, nulls, valid = parts[0]
            else:
                ncols = len(parts[0][0])
                has_null = tuple(any(p[1][ci] is not None for p in parts)
                                 for ci in range(ncols))
                cols, nulls, valid = _concat_bindings_parts(
                    tuple(parts), has_null)
            fetch = list(cols) + [m for m in nulls if m is not None] + [valid]
            got = _host(fetch, site="result.batched")
            ncols = len(cols)
            hcols, rest = got[:ncols], got[ncols:]
            hnulls = [None if m is None else rest.pop(0) for m in nulls]
            hvalid = rest.pop(0)
        results: list = []
        hook = BATCH_LANE_TEST_HOOK
        for lane in range(n):
            try:
                if hook is not None:
                    hook(lane, n)
                if not parts:
                    empty = [np.zeros((0,), f.type.dtype)
                             for f in schema.fields]
                    results.append(_materialize_host(
                        schema, np.ones((0,), bool), empty,
                        [None] * len(empty), dicts))
                    continue
                results.append(_materialize_host(
                    schema, hvalid[lane], [c[lane] for c in hcols],
                    [None if m is None else m[lane] for m in hnulls], dicts))
            except Exception as e:
                results.append(e)
        return results

    def _op_label(self, node) -> str:
        lbl = self._op_labels.get(id(node))
        if lbl is None:
            lbl = f"{type(node).__name__}#{len(self._op_labels)}"
            self._op_labels[id(node)] = lbl
        return lbl

    def _boundary_sink(self, key, label: str) -> dict:
        sink = self.boundary.get(key)
        if sink is None:
            sink = self.boundary[key] = {"label": label, "dispatches": 0,
                                         "transfers": 0, "bytes": 0}
        return sink

    def _node_stats(self, node) -> dict:
        """THE per-node stats registration point (test_boundary_lint bans a
        bare ``self.stats.setdefault`` outside this helper): first
        registration captures the node's structural path and CBO row estimate
        from the begin_plan maps, so clean-completion history collection
        (execution/history.collect_plan_actuals) is a host-side dict walk."""
        s = self.stats.get(id(node))
        if s is None:
            s = self.stats[id(node)] = {"rows": 0, "wall_s": 0.0}  # stats-ok: the helper IS the chokepoint
            s["op"] = type(node).__name__
            path = self._node_paths.get(id(node))
            if path is not None:
                s["path"] = path
            est = self._node_ests.get(id(node))
            if est is not None:
                s["est_rows"] = est
        return s

    def _record(self, node, page, t0) -> None:
        """Blocking-operator stats (reference: OperatorStats via OperationTimer,
        operator/OperatorContext.java).  Streaming operators fuse into their sink, so
        stats attach at pipeline-breaker granularity, and wall times are CUMULATIVE
        over the operator's subtree (each breaker includes everything beneath it)."""
        s = self._node_stats(node)
        # keep the row count ON DEVICE (async dispatch): forcing it here would pay a
        # device->host RTT per operator on the normal query path; EXPLAIN ANALYZE
        # materializes lazily when formatting
        # a page that knows its live count, or has no mask, costs no launch
        if page.live is not None:
            s["rows"] = page.live
        elif page.valid is None:
            s["rows"] = page.capacity
        else:
            s["rows"] = jnp.sum(page.valid, dtype=jnp.int64)
        s["wall_s"] += _time.perf_counter() - t0

    # ---------------------------------------------------------------- internal
    def _execute_to_page(self, node: P.PlanNode):
        """Run a (sub)plan to completion, returning one host-side Page + dicts.
        Every dispatch/pull recorded while a node executes attributes to that
        node's boundary record (innermost blocking operator wins — streaming
        chains charge the sink that drives them, the same pipeline-breaker
        granularity as ``stats``)."""
        if self._overrides:
            hit = self._overrides.get(id(node))
            if hit is not None:
                return hit
        label = self._op_label(node)
        with tracing.operator_scope(label,
                                    self._boundary_sink(id(node), label)):
            return self._execute_node(node)

    def _execute_node(self, node: P.PlanNode):
        # (no overrides check here: _execute_to_page, the only caller, already
        # returned any override hit before opening the operator scope)
        t0 = _time.perf_counter()
        if isinstance(node, P.Output):
            child, dicts = self._execute_to_page(node.child)
            return Page(node.schema, child.columns, child.null_masks, child.valid,
                        child.live), dicts
        if isinstance(node, P.Sort):
            child, dicts = self._execute_to_page(node.child)
            # device-resident input: sort on device and pull only live rows
            # (the host path pulls the whole capacity-padded page first)
            page = _sort_page_device(child, node.keys, dicts)
            tracing.record_tail(compiled=page is not None)
            if page is None:
                page = _sort_page(child, node.keys, dicts)
            self._record(node, page, t0)
            return page, dicts
        if isinstance(node, P.Limit):
            if isinstance(node.child, P.Sort):
                # TopN fusion (reference: LimitPushDown rewrites Sort+Limit to
                # TopNOperator): select the top N before the full ordering.
                # Device-resident inputs sort on device and transfer only the
                # top rows; host pages keep the argpartition path
                child, dicts = self._execute_to_page(node.child.child)
                page = _topn_page_device(child, node.child.keys, node.count,
                                         dicts)
                tracing.record_tail(compiled=page is not None)
                if page is None:
                    page = _topn_page(child, node.child.keys, node.count,
                                      dicts)
                self._record(node, page, t0)
                return page, dicts
            if not isinstance(node.child, (P.Aggregate, P.Sort, P.Output, P.Window,
                                           P.Limit)):
                # streaming child: stop pulling pages once the limit is reached
                # (reference: LimitOperator short-circuits the pipeline)
                page, dicts = self._limited_stream_page(node)
                self._record(node, page, t0)
                return page, dicts
            child, dicts = self._execute_to_page(node.child)
            return _limit_page(child, node.count), dicts
        if isinstance(node, P.Unnest):
            child, dicts = self._execute_to_page(node.child)
            page, odicts = _run_unnest(node, child, dicts)
            self._record(node, page, t0)
            return page, odicts
        if isinstance(node, P.MatchRecognize):
            child, dicts = self._execute_to_page(node.child)
            page, odicts = _run_match_recognize(node, child, dicts)
            self._record(node, page, t0)
            return page, odicts
        if isinstance(node, P.Aggregate):
            page, dicts = self._run_aggregate(node)
            self._record(node, page, t0)
            return page, dicts
        if isinstance(node, P.Window):
            page, dicts = self._run_window(node)
            self._record(node, page, t0)
            return page, dicts
        # streaming leaf reached directly (scan/filter/project/join-probe): materialize
        stream = self._compile_stream(node)
        page = _concat_stream(stream, self._batch(stream))
        self._record(node, page, t0)
        return page, stream.dicts

    # -- page compaction at pipeline boundaries ------------------------------
    def _compacted_stream(self, up: _Stream, hashed: bool) -> Optional[_Stream]:
        """The compaction boundary INSIDE a split join (_join_with_build): a
        selective probe (``hashed``: through the open-addressing loop of a
        ``JoinTable``) leaves most lanes invalid, and the fixed-shape fusion
        model would drag every dead lane through the join's build-column
        gathers and every later probe and insert.  ``up`` is the join's match
        step (the upstream chain, then only what decides ``matched``).  Per
        batch: run it as its own program, read the surviving-row count (one
        small pull; a hashed table's lookup sends the rounds it ran along,
        `tracing.record_probe_lanes`), and pack the live rows into the smallest quantized
        bucket (n/4, n/16, n/64) that holds them.  Buckets are pow2-quantized
        so the downstream pipeline compiles at most a handful of shape
        classes, and a batch that stays dense flows through untouched: the
        width is decided by what each batch holds, not by an estimate.
        Returns None when the boundary would not pack the scan's FIRST page
        (one page through the match step as the stream is compiled): a join
        that matches every lane gains nothing from a materialised boundary,
        and split in two its gathers met a worse placement than fused (q18 at
        SF10: 4-11 % slower, PERF.md PR 28), so the caller compiles it as one
        step, as it always was.
        Reference: operators emit DENSE pages after selective filters
        (FilterAndProjectOperator) — compaction is where the reference gets
        its selectivity win, re-planned for static shapes."""
        compact_jits: dict = {}

        def counted(cols, nulls, valid, rounds=None):
            # what the boundary pulls: the survivors' count, and behind it
            # (one small array, one pull) the rounds a hashed table's lookup
            # ran at each of its widths, the fourth value of the match step's
            # transform; a direct table has none (None) and pulls its scalar
            count = jnp.sum(valid, dtype=jnp.int32)
            return cols, nulls, valid, (
                count if rounds is None
                else jnp.concatenate([count[None], rounds]))

        def pulled(stat, site):
            """[count, *rounds] of ``counted``'s fourth value, on the host."""
            return np.atleast_1d(_host([stat], site=site)[0]).tolist()

        @partial(_jit, site="join.match")
        def match(page, aux, params, up=up):
            with _ir.bind_params(params):  # same contract as _Stream.jitted()
                return counted(*up.transform(
                    page.columns, page.null_masks, page.valid_mask(), aux))

        @partial(_jit, site="join.match_batch")
        def bmatch(pages, live, aux, params, up=up):
            with _ir.bind_params(params):
                return counted(*up.transform(*_stack_pages(pages, live), aux))

        si = up.scan_info
        if si.splits:  # (generated here, not pulled through the prefetcher)
            page = _generate(si.conn, si.table, si.splits[0], si.scan_columns)
            if _page_batch_sig(page) is not None:
                count = match(page, up.aux, _current_params())[3]
                if pulled(count, "join.match.sample")[0] > page.capacity >> 2:
                    return None

        def pages(source=up.pages, up=up, self=self):
            for group, live in _coalesced_batches(source(), self._batch()):
                if live is None and _page_batch_sig(group[0]) is None:
                    # an exact wide-decimal (object) column runs eagerly, an
                    # empty page has nothing to pack
                    yield Page(up.schema, *up.jitted()(group[0])[:3])
                    continue
                cols, nulls, valid, count = \
                    match(group[0], up.aux, _current_params()) if live is None \
                    else bmatch(tuple(group), live, up.aux, _current_params())
                n = int(valid.shape[0])
                count, *rounds = pulled(count, "join.match.count")
                bucket = n
                for sh in (6, 4, 2):  # smallest sufficient bucket wins
                    if count <= (n >> sh):
                        bucket = max(n >> sh, 1)
                        break
                tracing.record_join_probe(n, bucket)
                tracing.record_probe_lanes(n, hashed, sum(
                    r * w for r, w in zip(rounds, probe_widths(n))))
                if bucket >= n:
                    yield Page(up.schema, cols, nulls, valid)
                    continue
                jc = compact_jits.get(bucket)
                if jc is None:
                    jc = _jit(partial(_compact_page, bucket=bucket),
                              site="jc_fn")
                    compact_jits[bucket] = jc
                ccols, cnulls, cvalid = jc(cols, nulls, valid)
                tracing.record_compaction(n, bucket)
                yield Page(up.schema, ccols, cnulls, cvalid)

        # (no boundary sits under this one: only a chain that is not yet
        # ``compacted`` gets one; what does sit under it is a fused join's
        # count of its pages, _probed_pages)
        si = dataclasses.replace(
            si, over=lambda raw, below=si.pages_over: partial(pages, below(raw)))
        return _Stream(up.schema, up.dicts, pages,
                       lambda c, n, v, aux: (c, n, v), si,
                       clustered_by=up.clustered_by, compacted=True)

    # -- streaming segment compilation ---------------------------------------
    def _subtree_overridden(self, node) -> bool:
        return id(node) in self._overrides \
            or any(self._subtree_overridden(c) for c in node.children)

    def _compile_stream(self, node: P.PlanNode) -> _Stream:
        if self._overrides:
            if id(node) in self._overrides:
                # a durable fragment output (FTE spool / remote task)
                # substitutes for the subtree: stream it as one page so
                # streaming consumers (aggregates over joins, probe pipelines)
                # read the spooled result instead of re-executing the fragment.
                page, dicts = self._overrides[id(node)]
                return _Stream(node.schema, dicts,
                               lambda page=page: iter((page,)),
                               lambda c, n, v, aux: (c, n, v))
            if self._subtree_overridden(node):
                # anything composed over an override closes over THIS query's
                # spooled page — caching it would pin the page for the plan
                # lifetime and serve it to the next execution (overrides are
                # query-scoped; both caches are plan-lifetime)
                return self._compile_stream_uncached(node)
        hit = self._stream_cache.get(id(node))
        if hit is not None:
            return hit[1]
        stream = self._compile_stream_uncached(node)
        # the strong node ref keeps id() stable for the cache lifetime
        self._stream_cache[id(node)] = (node, stream)
        return stream

    def _compile_stream_uncached(self, node: P.PlanNode) -> _Stream:
        if isinstance(node, P.TableScan):
            conn = self.catalogs[node.catalog]
            dicts = tuple(conn.dictionaries(node.table).get(c) for c in node.columns)
            with tracing.maybe_span("split-generation", table=node.table) as sp, \
                    tracing.inflight("split-generation",
                                     site=f"scan.{node.table}"):
                splits = conn.splits(node.table)
                sp.attributes["splits"] = len(splits)
            # advisory fact for the history record: split count is what the
            # adaptive advisor tunes dispatch_batch K from (host int, static
            # per compiled stream)
            self._plan_facts[id(node)] = (node, {"splits": len(splits)})

            # cache-aware page source over the prefetch policy the scan needs:
            # HOST_DECODE connectors prefetch+device_put on a background
            # thread (decode overlaps device compute), device generators get
            # the dispatch-coalescing double buffer when multi-split (see
            # _rewrap_pruned_pages).  The buffer-pool layer sits OUTSIDE the
            # prefetch wrap, so a warm cache hit serves the whole scan as one
            # resident page without ever starting a producer thread, and the
            # double-buffer thread only runs for scans the pool cannot serve.
            pages = self._scan_pages_source(conn, node.catalog, node.table,
                                            splits, tuple(node.columns))
            si = _ScanInfo(conn, splits, tuple(node.columns),
                           tuple(node.columns), catalog=node.catalog,
                           table=node.table)
            clustered = tuple(conn.clustered_by(node.table)) \
                if hasattr(conn, "clustered_by") else ()
            return _Stream(node.schema, dicts, pages,
                           lambda c, n, v, aux: (c, n, v), si,
                           clustered_by=clustered)

        if isinstance(node, P.Filter):
            up = self._compile_stream(node.child)
            pred = node.predicate

            def transform(cols, nulls, valid, aux, up=up, pred=pred):
                cols, nulls, valid = up.transform(cols, nulls, valid, aux)
                return cols, nulls, evaluate_predicate(pred, cols, nulls, valid)

            pruned = _static_pruned_stream(up, pred)
            if pruned is not None:
                # the pruner replaces the scan's page source wholesale:
                # rebuild it through _scan_pages_source so the replacement
                # keeps the wrap the TableScan compiled with (HOST_DECODE
                # prefetch / coalescing double buffer) AND stays buffer-pool
                # aware — the pruned split list keys its own cache entry
                psi = pruned[1]
                pruned = (psi.pages_over(self._scan_pages_source(
                    psi.conn, psi.catalog, psi.table, psi.splits,
                    psi.scan_columns)), psi)
            pages, si = pruned if pruned is not None else (up.pages, up.scan_info)
            # bind-time split pruning (plan templates): a Parameter in the
            # predicate carries no plan-time value, so static pruning above
            # cannot see it — prune per EXECUTION from the bound values, or
            # the point-lookup class scans every split on exactly the path
            # templates exist to serve.  Composes WITH static pruning: the
            # runtime pass starts from the statically-kept split list (si is
            # the pruned scan info when static pruning fired).
            rt = self._param_pruned_source(up, pred, si)
            if rt is not None:
                pages = si.pages_over(rt)
            return _Stream(up.schema, up.dicts, pages, transform, si, aux=up.aux,
                           clustered_by=up.clustered_by, compacted=up.compacted,
                           materialised=up.materialised)

        if isinstance(node, P.Project):
            up = self._compile_stream(node.child)
            planner_dicts = node.dicts or tuple(None for _ in node.exprs)
            dicts = tuple(
                pd if pd is not None
                else (up.dicts[e.index] if isinstance(e, FieldRef) else None)
                for pd, e in zip(planner_dicts, node.exprs)
            )

            def transform(cols, nulls, valid, aux, up=up, exprs=node.exprs):
                cols, nulls, valid = up.transform(cols, nulls, valid, aux)
                out = [evaluate(e, cols, nulls) for e in exprs]
                # constant expressions evaluate to scalars: broadcast to row count so
                # downstream consumers (join keys, exchanges) see real columns
                vs = tuple(jnp.broadcast_to(v, valid.shape) if v.ndim == 0 else v
                           for v, _ in out)
                ns = tuple(None if n is None
                           else (jnp.broadcast_to(n, valid.shape) if n.ndim == 0 else n)
                           for _, n in out)
                return vs, ns, valid

            si = None
            if up.scan_info is not None:
                si = dataclasses.replace(up.scan_info, columns=tuple(
                    up.scan_info.columns[e.index] if isinstance(e, FieldRef) else None
                    for e in node.exprs))
            return _Stream(node.schema, dicts, up.pages, transform, si, aux=up.aux,
                           clustered_by=up.clustered_by, compacted=up.compacted,
                           passes_valid=up.passes_valid,
                           materialised=up.materialised)

        if isinstance(node, P.Join):
            return self._compile_join(node)

        if isinstance(node, P.Union):
            subs = [self._compile_stream(c) for c in node.inputs]

            def pages(subs=subs, node=node):
                for s in subs:
                    jt = s.jitted()
                    for pg in s.pages():
                        cols, nulls, valid = jt(pg)
                        yield Page(node.schema, cols, nulls, valid)

            dicts = subs[0].dicts
            return _Stream(node.schema, dicts, pages, lambda c, n, v, aux: (c, n, v))

        if isinstance(node, P.Values):
            page = _values_page(node)
            return _Stream(node.schema, tuple(None for _ in node.schema.fields),
                           lambda: iter([page]), lambda c, n, v, aux: (c, n, v))

        if isinstance(node, (P.Aggregate, P.Sort, P.Limit, P.Output, P.Window,
                             P.Unnest, P.MatchRecognize)):
            # blocking sub-plan feeding a streaming consumer: run it, emit its one
            # page.  The first execution (needed for dictionary metadata) is reused
            # once; later executions re-run the child so volatile sources (system
            # tables) and post-DML state stay fresh across cached-plan re-runs.
            page, dicts = self._execute_to_page(node)
            cell = [page]

            def pages(cell=cell, self=self, node=node):
                if cell:
                    yield cell.pop()
                else:
                    pg, _ = self._execute_to_page(node)
                    yield pg

            return _Stream(node.schema, dicts, pages, lambda c, n, v, aux: (c, n, v),
                           passes_valid=True, materialised=True)

        raise NotImplementedError(f"node {type(node).__name__}")

    # -- aggregation sink ----------------------------------------------------
    def _agg_cacheable(self, node) -> bool:
        """Aggregation caches (compiled steps closing over stream.transform,
        tuples pinning the stream's page source) must be BYPASSED — both lookup
        and store — while the child subtree is overridden: the override stream's
        transform differs from the plan's normal pipeline, so a step cached in
        one mode applied in the other computes garbage, and a cached stream
        would pin + replay this query's spooled page on the next execution."""
        return not (self._overrides and self._subtree_overridden(node.child))

    def _agg_compiled(self, node: P.Aggregate):
        """Per-node compiled aggregation artifacts (cached across executions)."""
        cacheable = self._agg_cacheable(node)
        hit = self._agg_cache.get(id(node)) if cacheable else None
        if hit is not None:
            return hit[1:]
        stream = self._compile_stream(node.child)
        key_types = tuple(stream.schema.fields[i].type for i in node.keys)

        # expand avg -> (sum, count); build accumulator specs
        acc_specs, acc_exprs, acc_kinds = [], [], []
        for spec in node.aggs:
            arg = _acc_input_expr(spec)
            for kind, dtype, init in _accumulators_for(spec):
                acc_specs.append((dtype, init))
                acc_exprs.append(arg)
                acc_kinds.append(kind)

        @partial(_jit, site="agg.hash.step")
        def step(state, page, aux, stream=stream, node=node, key_types=key_types,
                 acc_exprs=acc_exprs, acc_kinds=acc_kinds):
            cols, nulls, valid = stream.transform(
                page.columns, page.null_masks, page.valid_mask(), aux
            )
            key_vals = tuple(cols[i] for i in node.keys)
            key_nulls = tuple(nulls[i] for i in node.keys)
            inputs = [
                (None, None) if e is None else evaluate(e, cols, nulls) for e in acc_exprs
            ]
            return hashagg.groupby_insert(
                state, key_vals, key_types, valid, inputs, acc_kinds, key_nulls
            )

        out = (stream, key_types, acc_specs, acc_exprs, acc_kinds, step)
        if cacheable:
            self._agg_cache[id(node)] = (node,) + out
        return out

    def _key_ranges(self, stream, node):
        """Static (lo, hi) bounds per group key channel, from dictionaries, type, or
        connector stats (reference: stats-driven GroupByHash sizing +
        BigintGroupByHash fast-path selection, operator/GroupByHash.java:90)."""
        si = stream.scan_info
        table_name = None
        if si is not None and si.splits and hasattr(si.splits[0], "table"):
            table_name = si.splits[0].table
        out = []
        for i in node.keys:
            t = stream.schema.fields[i].type
            d = stream.dicts[i]
            if d is not None and getattr(d, "values", None) is not None:
                out.append((0, max(len(d.values) - 1, 0)))
            elif t.name == "boolean":
                out.append((0, 1))
            elif t.is_floating:
                out.append(None)
            else:
                rng = None
                if (si is not None and i < len(si.columns)
                        and si.columns[i] is not None and table_name is not None
                        and hasattr(si.conn, "column_range")):
                    r = si.conn.column_range(table_name, si.columns[i])
                    if r and r[0] is not None and r[1] is not None:
                        rng = (int(r[0]), int(r[1]))
                out.append(rng)
        return tuple(out)

    def _key_nullable(self, node, stream, first):
        """Per group key, whether the stream's transform hands it a null
        mask: a fact of the plan, which ``jax.eval_shape`` learns by tracing
        the whole transform over the first page's shapes.  Kept with the
        node's other artefacts, so a replay traces nothing."""
        cacheable = self._agg_cacheable(node)
        hit = self._agg_cache.get(("nullable", id(node))) if cacheable else None
        if hit is not None:
            return hit[1]
        _, onulls, _ = jax.eval_shape(
            lambda c, n, v, aux: stream.transform(c, n, v, aux),
            first.columns, first.null_masks, first.valid_mask(), stream.aux)
        key_nullable = tuple(onulls[i] is not None for i in node.keys)
        if cacheable:
            self._agg_cache[("nullable", id(node))] = (node, key_nullable)
        return key_nullable

    def _observed_direct_config(self, node, stream, first, key_ranges):
        """The direct config of a group-by over ONE materialised page of a
        blocking child, from what that page holds, or None (hash mode, as
        before).  Such a child is FINISHED before its consumer chooses a mode,
        and no connector states a range for what a group-by hands on (q65's
        avg by store hashed 4,194,304 lanes into 2^16 slots for 120 groups).
        For every key that `_key_ranges` left without bounds: min and max over
        the live non-NULL lanes and whether any is NULL, ONE program
        (``agg.key_bounds``: the stream's transform, then three reductions a
        key) and ONE pull an execution.  Nothing of the data outlives the
        execution: the config is a compile shape (`hashagg.
        observed_direct_config`), the bounds are read again next time.  The
        observed NULL flag stands in for `_key_nullable`'s "has a mask" (every
        key out of a group-by has one, and a flag bit doubles the table);
        `hashagg._direct_slot` sends a NULL that meets a config without the
        bit to the overflow flag.  Floating keys stay hashed."""
        if not stream.materialised or _page_batch_sig(first) is None:
            return None  # (no mark; or nothing to trace, nothing to read)
        read = tuple(i for i, r in zip(node.keys, key_ranges) if r is None)
        if any(not np.issubdtype(np.dtype(stream.schema.fields[i].type.dtype),
                                 np.integer) for i in read):
            return None
        cacheable = self._agg_cacheable(node)
        hit = self._agg_cache.get(("key_bounds", id(node))) if cacheable else None
        if hit is None:
            @partial(_jit, site="agg.key_bounds")
            def bounds(page, aux, stream=stream, read=read):
                cols, nulls, valid = stream.transform(
                    page.columns, page.null_masks, page.valid_mask(), aux)
                out = []
                for i in read:
                    k, kn = cols[i], nulls[i]
                    live = valid if kn is None else valid & ~kn
                    lim = jnp.iinfo(k.dtype)
                    out.append(jnp.stack([
                        jnp.min(jnp.where(live, k, lim.max)).astype(jnp.int64),
                        jnp.max(jnp.where(live, k, lim.min)).astype(jnp.int64),
                        jnp.zeros((), jnp.int64) if kn is None
                        else jnp.any(valid & kn).astype(jnp.int64)]))
                return jnp.stack(out)

            hit = (node, bounds)
            if cacheable:
                self._agg_cache[("key_bounds", id(node))] = hit
        seen = iter(_host([hit[1](first, stream.aux)],
                          site="agg.key_bounds")[0].tolist())
        key_bounds = []
        for r, has_mask in zip(key_ranges,
                               self._key_nullable(node, stream, first)):
            if r is None:
                lo, hi, any_null = next(seen)
                key_bounds.append((lo, hi, bool(any_null)))
            else:
                key_bounds.append((r[0], r[1], has_mask))
        return hashagg.observed_direct_config(key_bounds, first.capacity)

    def _direct_step(self, node, cfg, stream, key_types, acc_exprs, acc_kinds):
        """Jitted direct-indexed insert steps (cached per (node, cfg)):
        ``(dstep, bdstep)`` — per-page, and dispatch-coalesced over a group of
        shape-uniform pages (the group stacks inside the trace and inserts
        once; direct-indexed slots are key-determined, so batch width cannot
        change the result)."""
        cacheable = self._agg_cacheable(node)
        hit = self._agg_cache.get(("direct", id(node), cfg)) if cacheable else None
        if hit is not None:
            return hit[1], hit[2]

        def body(state, cols, nulls, valid, stream=stream, node=node, cfg=cfg,
                 acc_exprs=acc_exprs, acc_kinds=acc_kinds):
            key_vals = tuple(cols[i] for i in node.keys)
            key_nulls = tuple(nulls[i] for i in node.keys)
            inputs = [
                (None, None) if e is None else evaluate(e, cols, nulls) for e in acc_exprs
            ]
            return hashagg.direct_groupby_insert(
                state, cfg, key_vals, valid, inputs, acc_kinds, key_nulls
            )

        @partial(_jit, site="agg.direct.step")
        def dstep(state, page, aux, stream=stream):
            return body(state, *stream.transform(
                page.columns, page.null_masks, page.valid_mask(), aux))

        @partial(_jit, site="agg.direct.batch")
        def bdstep(state, pages, live, aux, stream=stream):
            return body(state, *stream.transform(*_stack_pages(pages, live),
                                                 aux))

        if cacheable:
            self._agg_cache[("direct", id(node), cfg)] = (node, dstep, bdstep)
        return dstep, bdstep

    def _agg_capacity_estimate(self, stream, node, key_ranges):
        """Upper-bound estimate of group count from static key ranges and the
        source table's row bound (reference: stats-driven GroupByHash
        expectedSize).  Estimates saturate at MAX_GROUP_CAPACITY."""
        est = None
        prod = 1
        for r in key_ranges:
            if r is None:
                prod = None
                break
            prod = min(prod * max(int(r[1]) - int(r[0]) + 1, 1),
                       MAX_GROUP_CAPACITY)
        if prod is not None:
            est = prod
        si = stream.scan_info
        if si is not None and si.splits \
                and hasattr(si.conn, "row_count") \
                and hasattr(si.splits[0], "table"):
            bound = int(si.conn.row_count(si.splits[0].table))
            est = bound if est is None else min(est, bound)
        return est

    def _run_percentile_aggregate(self, node: P.Aggregate):
        """approx_percentile via exact sort-based selection: one device
        lexsort over (group keys, value) + segmented nth-element gathers —
        the TPU-native replacement for the reference's t-digest sketches
        (operator/aggregation/ApproximateLongPercentileAggregations; exact
        selection is within the function's accuracy contract, and a device
        lexsort beats sketch maintenance when sorts are one fused kernel)."""
        for s in node.aggs:
            if s.kind not in P.SORTED_AGG_KINDS:
                raise NotImplementedError(
                    "sort-based aggregates (approx_percentile/listagg/"
                    "max_by/array_agg/...) cannot mix with other "
                    "aggregates yet")
            if not isinstance(s.arg, FieldRef):
                raise NotImplementedError(
                    f"{s.kind} argument must be a plain column")
        stream = self._compile_stream(node.child)
        page = _concat_stream(stream, self._batch(stream))
        n = page.capacity
        key_chs = list(node.keys)
        if n == 0:
            cols = tuple(np.zeros((0,), np.dtype(f.type.dtype))
                         for f in node.schema.fields)
            if not key_chs:  # global aggregate over empty input: one NULL row
                cols = tuple(np.zeros((1,), np.dtype(f.type.dtype))
                             for f in node.schema.fields)
                return (Page(node.schema, cols,
                             tuple(np.ones((1,), bool) for _ in cols), None),
                        tuple(None for _ in node.schema.fields))
            return (Page(node.schema, cols, tuple(None for _ in cols), None),
                    tuple(None for _ in node.schema.fields))
        valid = page.valid_mask()
        kcols = [page.columns[i] for i in key_chs]
        knulls = [page.null_masks[i] for i in key_chs]

        # ONE key-major sort orders every value channel identically, so the
        # per-agg segment structure is shared: sort by (~valid, keys...,
        # value_null, value) per agg — keys primary, null values last
        def live_counts(idx, vnull, starts, ends):
            """Non-null-value rows per [start, end) segment, computed ON
            DEVICE (g-sized result the caller batches into its one _host
            pull).  The old host-side version pulled the full n-sized cumsum
            per aggregate spec — a per-group-fetch bulk transfer the counters
            exposed (n*8 bytes each; megabytes at SF1 input scale)."""
            live = jnp.cumsum(((valid & ~vnull)[idx]).astype(jnp.int64))
            at = lambda i: jnp.where(i > 0, live[jnp.maximum(i - 1, 0)], 0)
            return at(jnp.asarray(ends)) - at(jnp.asarray(starts))

        def sorted_select(vch, p):
            v = page.columns[vch]
            vn = page.null_masks[vch]
            vnull = jnp.zeros((n,), bool) if vn is None else vn
            idx, sk, skn, starts, ends, m, g = seg_sort(v, vnull)
            if g == 0:
                gk, gn = empty_keys()
                return gk, gn, np.zeros((0,)), np.ones((0,), bool)
            counts = live_counts(idx, vnull, starts, ends)
            tgt = jnp.asarray(starts) + jnp.clip(
                jnp.round(p * jnp.maximum(counts - 1, 0)).astype(jnp.int64),
                0, jnp.maximum(counts - 1, 0))
            tgt = jnp.clip(tgt, 0, n - 1)
            got = _host([v[idx][tgt], counts]
                        + key_fetches(sk, skn, starts),
                        site="agg.sorted.select")
            vals = got[0]
            out_null = got[1] == 0
            gkeys, gknulls = host_group_keys(got, 2, sk, skn, starts)
            return gkeys, gknulls, vals, out_null

        def sorted_listagg(spec):
            """listagg(x, sep) WITHIN GROUP (ORDER BY o): the same key-major
            sort, then per-group decode + join on the host (the string result
            lives at the result surface only, like wide-decimal finals).
            Reference: operator/aggregation/listagg."""
            sep, order_ch, asc = spec.param
            vch = spec.arg.index
            d = stream.dicts[vch]
            if d is None:
                raise NotImplementedError(
                    "listagg needs a dictionary-encoded string channel")
            v = page.columns[vch]
            vn = page.null_masks[vch]
            vnull = jnp.zeros((n,), bool) if vn is None else vn
            okey = page.columns[order_ch] if order_ch is not None else v
            od = stream.dicts[order_ch] if order_ch is not None \
                else stream.dicts[vch]
            if od is not None and getattr(od, "values", None) is not None:
                rank = _collation_rank_lut(od)
                okey = jnp.asarray(rank)[jnp.clip(okey, 0, len(rank) - 1)]
            if not asc:
                okey = ~okey if jnp.issubdtype(okey.dtype, jnp.integer) \
                    else -okey
            idx, sk, skn, starts, ends, m, g = seg_sort(okey, vnull)
            if g == 0:
                gk, gn = empty_keys()
                return gk, gn, np.zeros((0,), np.int32), \
                    np.ones((0,), bool), \
                    Dictionary(values=np.array([], dtype=object))
            got = _host([v[idx], vnull[idx]]
                        + key_fetches(sk, skn, starts),
                        site="agg.sorted.fetch")
            sval_np, svnull_np = got[0], got[1]
            gkeys, gknulls = host_group_keys(got, 2, sk, skn, starts)
            joined, out_null = [], np.zeros(g, bool)
            for gi, (s0, e0) in enumerate(zip(starts, ends)):
                ids = sval_np[s0:e0][~svnull_np[s0:e0]]
                if len(ids) == 0:
                    out_null[gi] = True
                    joined.append("")
                else:
                    joined.append(sep.join(str(x) for x in d.decode(ids)))
            out_d = Dictionary(values=np.array(joined, dtype=object))
            return (gkeys, gknulls, np.arange(g, dtype=np.int32), out_null,
                    out_d)

        def sorted_amf(spec, buckets):
            """approx_most_frequent(buckets, v[, capacity]) / histogram(v)
            (buckets=None): value counts per group as a map(V, bigint).
            Reference: operator/aggregation/ApproximateMostFrequentHistogram
            (a stream-summary sketch; exact counting over the shared
            key-major sort is within the accuracy contract, the same trade
            approx_percentile makes) and MapHistogramAggregation."""
            vch = spec.arg.index
            d = stream.dicts[vch]
            v = page.columns[vch]
            vn = page.null_masks[vch]
            vnull = jnp.zeros((n,), bool) if vn is None else vn
            idx, sk, skn, starts, ends, m, g = seg_sort(v, vnull)
            if g == 0:
                gk, gn = empty_keys()
                return gk, gn, np.zeros((0,), np.int64), \
                    np.zeros((0,), bool), \
                    MapData(np.zeros((0,), np.dtype(v.dtype)),
                            np.zeros((0,), np.int64),
                            spec.arg.type, BIGINT, key_dict=d)
            got = _host([v[idx], vnull[idx]]
                        + key_fetches(sk, skn, starts),
                        site="agg.sorted.fetch")
            sval_np, svnull_np = got[0], got[1]
            gkeys, gknulls = host_group_keys(got, 2, sk, skn, starts)
            key_heap, cnt_heap, spans = [], [], np.zeros(g, np.int64)
            out_null = np.zeros(g, bool)
            max_len = 0
            for gi, (s0, e0) in enumerate(zip(starts, ends)):
                vv = sval_np[s0:e0][~svnull_np[s0:e0]]
                start = len(key_heap)
                if len(vv):
                    uniq, cnts = np.unique(vv, return_counts=True)
                    top = np.arange(len(uniq)) if buckets is None \
                        else np.lexsort((uniq, -cnts))[:buckets]
                    key_heap.extend(uniq[top].tolist())
                    cnt_heap.extend(cnts[top].tolist())
                else:
                    # NULL-only group: the reference's histogram state is
                    # never initialized -> NULL (not an empty map)
                    out_null[gi] = True
                spans[gi] = pack_span(start, len(key_heap) - start)
                max_len = max(max_len, len(key_heap) - start)
            md = MapData(np.asarray(key_heap,  # host-ok: python list
                                    dtype=sval_np.dtype),
                         np.asarray(cnt_heap, np.int64),  # host-ok: python list
                         spec.arg.type, BIGINT, key_dict=d, max_len=max_len)
            return gkeys, gknulls, spans, out_null, md

        def seg_sort(primary, pnull):
            """Shared segmentation: key-major lexsort with ``primary``
            ordered inside each group; returns the permutation, sorted keys,
            and [start, end) group segments."""
            lex = [primary, pnull]
            for k, kn in zip(reversed(kcols), reversed(knulls)):
                lex.append(k)
                if kn is not None:
                    lex.append(kn)
            lex.append(~valid)
            idx = jnp.lexsort(tuple(lex))
            sk = [k[idx] for k in kcols]
            skn = [None if kn is None else kn[idx] for kn in knulls]
            svalid = valid[idx]
            pos = jnp.arange(n)
            new_group = svalid & (pos == 0)
            for k, kn in zip(sk, skn):
                prev = jnp.concatenate([k[:1], k[:-1]])
                diff = (k != prev) & (pos > 0)
                if kn is not None:
                    pn2 = jnp.concatenate([kn[:1], kn[:-1]])
                    diff = (diff & ~(kn & pn2)) | ((kn != pn2) & (pos > 0))
                new_group = new_group | (svalid & diff)
            if not key_chs:
                new_group = svalid & (pos == 0)
            # ONE batched sync for both scalars (each bare int() is a
            # blocking device->host sync of its own)
            mg = _host([jnp.sum(valid, dtype=jnp.int64),
                        jnp.sum(new_group, dtype=jnp.int64)],
                       site="agg.sorted.counts")
            m = int(mg[0])
            g = int(mg[1]) if key_chs else (1 if m else 0)
            if g == 0:
                return (idx, sk, skn, np.zeros(0, np.int64),
                        np.zeros(0, np.int64), m, 0)
            starts = _host([jnp.nonzero(new_group, size=g,
                                        fill_value=n)[0]],
                           site="agg.sorted.starts")[0]
            ends = np.concatenate([starts[1:], [m]])
            return idx, sk, skn, starts, ends, m, g

        def host_group_keys(got, ofs, sk, skn, starts):
            gkeys = got[ofs:ofs + len(sk)]
            rest = list(got[ofs + len(sk):])
            gknulls = []
            for kn in skn:
                gknulls.append(None if kn is None else rest.pop(0))
            return gkeys, gknulls

        def key_fetches(sk, skn, starts):
            return [k[jnp.asarray(starts)] for k in sk] + \
                [kn[jnp.asarray(starts)] for kn in skn if kn is not None]

        def empty_keys():
            """Arity-correct zero-group key columns: every helper's g==0
            return must still carry one (empty) column per GROUP BY key or
            the assembled page's columns fall short of its schema."""
            gk = [np.zeros((0,), np.dtype(k.dtype)) for k in kcols]
            gn = [None if kn is None else np.zeros((0,), bool)
                  for kn in knulls]
            return gk, gn

        def sorted_extreme_by(spec):
            """max_by(x, y)/min_by(x, y): the payload x at each group's
            extreme ranking value y — the segment boundary of the shared
            key-major sort (reference:
            operator/aggregation/minmaxby/MaxByAggregationFunction)."""
            vch = spec.arg.index
            pch = int(spec.param)
            v = page.columns[vch]
            vd = stream.dicts[vch]
            if vd is not None and getattr(vd, "values", None) is not None:
                # string ranking: ids are insertion-ordered, not
                # lexicographic — remap so max_by orders by VALUE
                rank = _collation_rank_lut(vd)
                v = jnp.asarray(rank)[jnp.clip(v, 0, len(rank) - 1)]
            vn = page.null_masks[vch]
            vnull = jnp.zeros((n,), bool) if vn is None else vn
            idx, sk, skn, starts, ends, m, g = seg_sort(v, vnull)
            d_out = stream.dicts[pch]
            if g == 0:
                gk, gn = empty_keys()
                return gk, gn, np.zeros((0,), np.int64), \
                    np.zeros((0,), bool), d_out
            counts = live_counts(idx, vnull, starts, ends)
            tgt = jnp.asarray(starts) + jnp.maximum(counts - 1, 0) \
                if spec.kind == "max_by" else jnp.asarray(starts)
            tgt = jnp.clip(tgt, 0, n - 1)
            pl = page.columns[pch][idx]
            pn0 = page.null_masks[pch]
            fetch = [pl[tgt], counts]
            if pn0 is not None:
                fetch.append(pn0[idx][tgt])
            got = _host(fetch + key_fetches(sk, skn, starts),
                        site="agg.sorted.fetch")
            vals = got[0]
            out_null = got[1] == 0
            ofs = 2
            if pn0 is not None:
                out_null = out_null | got[2]
                ofs = 3
            gkeys, gknulls = host_group_keys(got, ofs, sk, skn, starts)
            return gkeys, gknulls, vals, out_null, d_out

        def sorted_array_agg(spec):
            """array_agg(v): per-group element lists as a span column over an
            ArrayData heap (reference: operator/aggregation/ArrayAggregation;
            deviation: NULL elements are dropped and element order is the
            value order — the spec leaves order undefined without WITHIN
            GROUP)."""
            vch = spec.arg.index
            d = stream.dicts[vch]
            elem_t = stream.schema.fields[vch].type
            v = page.columns[vch]
            vn = page.null_masks[vch]
            vnull = jnp.zeros((n,), bool) if vn is None else vn
            idx, sk, skn, starts, ends, m, g = seg_sort(v, vnull)
            if g == 0:
                empty = ArrayData(np.zeros((0,), np.dtype(v.dtype)),
                                  elem_t, elem_dict=d)
                gk, gn = empty_keys()
                return gk, gn, np.zeros((0,), np.int64), \
                    np.zeros((0,), bool), empty
            got = _host([v[idx], vnull[idx]]
                        + key_fetches(sk, skn, starts),
                        site="agg.sorted.fetch")
            sval_np, svnull_np = got[0], got[1]
            gkeys, gknulls = host_group_keys(got, 2, sk, skn, starts)
            heap, spans = [], np.zeros(g, np.int64)
            out_null = np.zeros(g, bool)
            max_len = 0
            for gi, (s0, e0) in enumerate(zip(starts, ends)):
                vv = sval_np[s0:e0][~svnull_np[s0:e0]]
                start = len(heap)
                if len(vv):
                    heap.extend(vv.tolist())
                else:
                    out_null[gi] = True
                spans[gi] = pack_span(start, len(heap) - start)
                max_len = max(max_len, len(heap) - start)
            ad = ArrayData(np.asarray(heap, dtype=sval_np.dtype),  # host-ok: python list
                           elem_t, elem_dict=d, max_len=max_len)
            return gkeys, gknulls, spans, out_null, ad

        def sorted_map_agg(spec):
            """map_agg(k, v): per-group key/value pairs as a span column over
            MapData heaps (reference: operator/aggregation/MapAggAggregation;
            deviations: NULL keys are skipped — as the reference does — and
            duplicate keys keep the FIRST value instead of raising)."""
            kch = spec.arg.index
            vch2 = int(spec.param)
            kcol = page.columns[kch]
            kn0 = page.null_masks[kch]
            knull = jnp.zeros((n,), bool) if kn0 is None else kn0
            idx, sk, skn, starts, ends, m, g = seg_sort(kcol, knull)
            key_t = stream.schema.fields[kch].type
            val_t = stream.schema.fields[vch2].type
            kd, vd = stream.dicts[kch], stream.dicts[vch2]
            if g == 0:
                empty = MapData(np.zeros((0,), np.dtype(kcol.dtype)),
                                np.zeros((0,), np.int64), key_t, val_t,
                                key_dict=kd, value_dict=vd)
                gk, gn = empty_keys()
                return gk, gn, np.zeros((0,), np.int64), \
                    np.zeros((0,), bool), empty
            vcol = page.columns[vch2][idx]
            vn0 = page.null_masks[vch2]
            fetch = [kcol[idx], knull[idx], vcol]
            if vn0 is not None:
                fetch.append(vn0[idx])
            got = _host(fetch + key_fetches(sk, skn, starts),
                        site="agg.sorted.fetch")
            skey, sknull, sval = got[0], got[1], got[2]
            ofs = 3
            if vn0 is not None:
                svnul = got[3]
                ofs = 4
            else:
                svnul = np.zeros(len(skey), bool)
            gkeys, gknulls = host_group_keys(got, ofs, sk, skn, starts)
            key_heap, val_heap, spans = [], [], np.zeros(g, np.int64)
            out_null = np.zeros(g, bool)
            max_len = 0
            for gi, (s0, e0) in enumerate(zip(starts, ends)):
                seg = slice(s0, e0)
                live = ~sknull[seg]
                kk = skey[seg][live]
                vv = sval[seg][live]
                vvn = svnul[seg][live]
                start = len(key_heap)
                if len(kk):
                    # segment is key-sorted: first occurrence of each key
                    uniq, first = np.unique(kk, return_index=True)
                    key_heap.extend(uniq.tolist())
                    # a NULL value decodes to None through the result path
                    vals = vv[first].astype(object)
                    vals[vvn[first]] = None
                    val_heap.extend(vals.tolist())
                else:
                    out_null[gi] = True
                spans[gi] = pack_span(start, len(key_heap) - start)
                max_len = max(max_len, len(key_heap) - start)
            vh = np.asarray(val_heap, dtype=object)  # host-ok: python list
            if not any(x is None for x in val_heap):
                vh = np.asarray(val_heap, dtype=sval.dtype)  # host-ok: python list
            md = MapData(np.asarray(key_heap, dtype=skey.dtype),  # host-ok: python list
                         vh, key_t, val_t, key_dict=kd, value_dict=vd,
                         max_len=max_len)
            return gkeys, gknulls, spans, out_null, md

        def sorted_bitwise(spec):
            """bitwise_and_agg/or_agg/xor_agg: host fold over the shared
            key-major segments (reference:
            operator/aggregation/BitwiseAndAggregation et al.)."""
            fold = {"bitwise_and_agg": np.bitwise_and,
                    "bitwise_or_agg": np.bitwise_or,
                    "bitwise_xor_agg": np.bitwise_xor}[spec.kind]
            vch = spec.arg.index
            v = page.columns[vch]
            vn = page.null_masks[vch]
            vnull = jnp.zeros((n,), bool) if vn is None else vn
            idx, sk, skn, starts, ends, m, g = seg_sort(v, vnull)
            if g == 0:
                gk, gn = empty_keys()
                return gk, gn, np.zeros((0,), np.int64), np.zeros((0,), bool)
            got = _host([v[idx], vnull[idx]]
                        + key_fetches(sk, skn, starts),
                        site="agg.sorted.fetch")
            sval_np, svnull_np = got[0], got[1]
            gkeys, gknulls = host_group_keys(got, 2, sk, skn, starts)
            vals = np.zeros(g, np.int64)
            out_null = np.zeros(g, bool)
            for gi, (s0, e0) in enumerate(zip(starts, ends)):
                vv = sval_np[s0:e0][~svnull_np[s0:e0]]
                if len(vv):
                    vals[gi] = fold.reduce(vv.astype(np.int64))
                else:
                    out_null[gi] = True
            return gkeys, gknulls, vals, out_null

        out_key_cols = out_key_nulls = None
        agg_vals, agg_nulls, agg_dicts = [], [], []
        for s in node.aggs:
            if s.kind == "listagg":
                gkeys, gknulls, vals, vnull, d_out = sorted_listagg(s)
            elif s.kind == "approx_most_frequent":
                gkeys, gknulls, vals, vnull, d_out = sorted_amf(
                    s, int(s.param))
            elif s.kind == "histogram":
                gkeys, gknulls, vals, vnull, d_out = sorted_amf(s, None)
            elif s.kind in ("max_by", "min_by"):
                gkeys, gknulls, vals, vnull, d_out = sorted_extreme_by(s)
            elif s.kind == "array_agg":
                gkeys, gknulls, vals, vnull, d_out = sorted_array_agg(s)
            elif s.kind == "map_agg":
                gkeys, gknulls, vals, vnull, d_out = sorted_map_agg(s)
            elif s.kind in ("bitwise_and_agg", "bitwise_or_agg",
                            "bitwise_xor_agg"):
                gkeys, gknulls, vals, vnull = sorted_bitwise(s)
                d_out = None
            else:
                gkeys, gknulls, vals, vnull = sorted_select(s.arg.index,
                                                            float(s.param))
                d_out = None
            if out_key_cols is None:
                out_key_cols, out_key_nulls = gkeys, gknulls
            agg_vals.append(vals)
            agg_nulls.append(vnull if vnull.any() else None)
            agg_dicts.append(d_out)
        cols = list(out_key_cols) + agg_vals
        nulls = [None if kn is None or not kn.any() else kn
                 for kn in out_key_nulls] + agg_nulls
        arrays = [np.asarray(c) for c in cols]  # host-ok: sorted-agg host outputs
        dicts = tuple(stream.dicts[i] for i in key_chs) + tuple(agg_dicts)
        return Page(node.schema, tuple(arrays), tuple(nulls), None), dicts

    def _run_aggregate(self, node: P.Aggregate):
        if any(s.kind in P.SORTED_AGG_KINDS for s in node.aggs):
            return self._run_percentile_aggregate(node)
        stream, key_types, acc_specs, acc_exprs, acc_kinds, step = self._agg_compiled(node)
        capacity = node.capacity or DEFAULT_GROUP_CAPACITY
        if not node.keys:
            return self._run_global_aggregate(node, stream, acc_exprs, acc_kinds)

        # direct-indexed fast path: slot = packed key when static ranges are narrow
        # (reference: BigintGroupByHash, operator/GroupByHash.java:90-99)
        page_iter = iter(stream.pages())
        first = next(page_iter, None)
        cfg = None
        observed = False  # cfg came from bounds read off the child's one page
        capped = False  # the estimate asked for more slots than its cap gives
        if first is not None:
            key_ranges = self._key_ranges(stream, node)
            if all(r is not None for r in key_ranges):
                cfg = hashagg.direct_config(
                    key_ranges, self._key_nullable(node, stream, first))
            else:
                cfg = self._observed_direct_config(node, stream, first,
                                                   key_ranges)
                observed = cfg is not None
            if cfg is None and not node.capacity:
                # hash mode: size the initial table from the key-range product
                # and/or the input row bound so huge group counts don't crawl
                # through grow-by-4x retries, each a full re-stream (reference:
                # stats-driven GroupByHash expectedSize).  Estimates saturate —
                # an overflowing product still sizes to the cap.
                est = self._agg_capacity_estimate(stream, node, key_ranges)
                if est is not None:
                    # cap the stats-derived size: estimates overshoot true NDV
                    # (post-filter group counts are unknown); growth-on-overflow
                    # covers undershoots
                    # modest cap: in-loop rehash makes undershoot cheap, while an
                    # oversized table costs a long cold compile
                    target = 1 << max(2 * est - 1, 1).bit_length()
                    capped = target > max(capacity, FIRST_CAPACITY_CAP)
                    capacity = max(capacity, min(target, FIRST_CAPACITY_CAP))
        pages_once = itertools.chain([first], page_iter) if first is not None else ()

        # streaming (sorted-input) aggregation: the scan's declared sort order
        # makes every group's rows CONTIGUOUS, so segmented reduces replace
        # the hash probe loop entirely (reference: the streaming aggregation
        # operator over pre-grouped input); the dense direct-index path still
        # wins when it applies, so this gates on cfg is None
        state_bytes = _group_state_bytes(key_types, acc_specs)
        if cfg is None and self._streaming_agg_order(stream, node) is not None:
            return self._run_streaming_aggregate(
                node, stream, key_types, acc_specs, acc_exprs, acc_kinds,
                capacity, pages_once, state_bytes)

        # memory gate: group-by state is device-resident; if it cannot fit the
        # pool, go to partitioned passes (the HBM spill analog).  Reservation is
        # re-checked on every capacity growth.
        # a replay of a cached plan starts at the capacity its last run ended
        # with: a regrow is paid once a plan, not once a run
        proven = self._agg_cache.get(("capacity", id(node)))
        if proven is not None:
            capacity = max(capacity, proven[1])
        capacity = ceil_pow2(capacity)  # groupby_init allocates the rounded
        # size; reserving the raw request would under-account by up to 2x
        if cfg is not None and not self.memory_pool.try_reserve(
                state_bytes(cfg.capacity), "group-by"):
            cfg = None  # direct table too large: try the (smaller) hash table
        resv = {"bytes": 0 if cfg is None else state_bytes(cfg.capacity)}
        if cfg is None:
            if not self.memory_pool.try_reserve(state_bytes(capacity), "group-by"):
                return self._run_aggregate_partitioned(node, parts=node.grace_parts or 4)
            resv = {"bytes": state_bytes(capacity)}

        peak = 0  # the largest reservation of this group-by (groupby_state_bytes)
        try:
            if cfg is not None:
                tracing.record_groupby(observed_direct=int(observed))
                with tracing.maybe_span("aggregate.direct", slots=cfg.capacity):
                    state = _direct_init(cfg, tuple(t.dtype for t in key_types),
                                         tuple(acc_specs))
                    dstep, bdstep = self._direct_step(node, cfg, stream,
                                                      key_types, acc_exprs,
                                                      acc_kinds)
                    for group, live in _coalesced_batches(
                            pages_once, self._batch(stream)):
                        state = dstep(state, group[0], stream.aux) \
                            if live is None \
                            else bdstep(state, tuple(group), live, stream.aux)
                    # the host waits HERE for every step it queued (a scan
                    # statement's one long wait): a pull like any other
                    out = self._finalize_groups(node, stream, state,
                                                "agg.direct.overflow")
                    if out is not None:
                        return out
                # stale stats put keys out of range: hash mode, over the
                # whole input again
                tracing.record_groupby(regrows=1)
                peak = resv["bytes"]
                self.memory_pool.free(resv["bytes"], "group-by")
                resv["bytes"] = 0
                if not self.memory_pool.try_reserve(state_bytes(capacity),
                                                    "group-by"):
                    return self._run_aggregate_partitioned(node, parts=node.grace_parts or 4)
                resv["bytes"] = state_bytes(capacity)
                pages_once = stream.pages()
            with tracing.maybe_span("aggregate.hash", slots=capacity):
                state = _hash_init(capacity, tuple(t.dtype for t in key_types),
                                   tuple(acc_specs))
                state = self._run_hash_inserts(node, stream, key_types, acc_exprs,
                                               acc_kinds, state, pages_once,
                                               state_bytes, resv,
                                               proven is not None, capped)
                # growth happens INSIDE the insert loop (snapshot + rehash + chunk
                # replay); a still-set overflow means the capacity/memory ceiling:
                # fall back to partitioned passes (the HBM analog of the
                # reference's SpillableHashAggregationBuilder)
                out = self._finalize_groups(node, stream, state,
                                            "agg.hash.overflow")
                if out is not None:
                    if self._agg_cacheable(node):
                        self._agg_cache[("capacity", id(node))] = \
                            (node, state.capacity)
                    return out
            tracing.record_groupby(regrows=1)
            return self._run_aggregate_partitioned(node, parts=node.grace_parts or 4)
        finally:
            tracing.record_groupby(state_bytes=max(peak, resv["bytes"]))
            self.memory_pool.free(resv["bytes"], "group-by")

    def _run_hash_inserts(self, node, stream, key_types, acc_exprs, acc_kinds,
                          state, pages_iter, state_bytes, resv, proven=False,
                          capped=False):
        """Insert a page stream into hash-mode group-by state, compacting live
        rows first when pages are sparse.  TPU scatters cost by page WIDTH (sink
        writes included), so a 5%-selective filter over a 4M-row page pays 20x
        the scatter it needs — compact with a cheap gather, then scatter at the
        live-row bucket (reference analog: SelectedPositions feeding the
        aggregator, operator/project/SelectedPositions.java).  The bucket is
        `hashagg.insert_bucket` of the page's pulled live count and its static
        width: the power of two that holds the live lanes where that is under
        half the width; else the least multiple of an eighth of the width that
        holds them, if that is at most half the width and never under
        `hashing.INSERT_MIN_LANES` (a packed page must still run the claim
        loop's narrowing levels); else the page goes in masked, at its width
        (the readings are at the function).  Live-row counts
        sync to the host in CHUNKS: every sync blocks the host on the device.
        Until the plan has ``proven`` a capacity (a run that ended without the
        ceiling), the overflow flag is read after every staged page and not
        only after the chunk: pages inserted into a table that has already
        overflowed run the probe loop to MAX_PROBES on every lane (TPC-DS q65
        at SF10: 78 of a group-by's 102 s, PERF.md PR 36) for a state the
        regrow throws away.  And a table that the estimate's cap cut short
        (``capped``) takes ONE step of four times the slots for room, before
        the first page that alone has more live lanes than the table has
        slots: it would else fill up under the page, run the lanes that find
        it full to MAX_PROBES, and be regrown from the chunk's start after
        all (q65 again: both of its (store, item) group-bys, 87 s of a cold
        statement's device time, PERF.md section 6, PR 40).  Live lanes are
        not groups: one step, for one page's lanes (not the lanes so far: q3
        at SF10 takes 3 M lanes for 113,513 groups), and only where the
        estimate had asked for more (a table sized by default or by hand
        grows by overflow alone: the avg over q65's 2.1 M pairs has 120
        groups).  A replay starts where the last run ended and reads nothing
        between its pages, as before.  Every insert step hands back the
        rounds its loop ran at each width (`hashagg.insert_widths` of its
        lanes), and the chunk's pull of the flag takes them along (``ran``):
        ``groupby_insert_round_lanes``."""
        cacheable = self._agg_cacheable(node)
        arts = self._agg_cache.get(("hashpage", id(node))) if cacheable else None
        if arts is None:
            def prep_body(cols, nulls, valid, node=node, acc_exprs=acc_exprs):
                keys = tuple(cols[i] for i in node.keys)
                knulls = tuple(nulls[i] for i in node.keys)
                inputs = tuple((None, None) if e is None else evaluate(e, cols, nulls)
                               for e in acc_exprs)
                return keys, knulls, inputs, valid, jnp.sum(valid, dtype=jnp.int32)

            @partial(_jit, site="agg.hash.prepare")
            def prepare(page, aux, stream=stream):
                return prep_body(*stream.transform(
                    page.columns, page.null_masks, page.valid_mask(), aux))

            @partial(_jit, site="agg.hash.prepare_batch")
            def bprepare(pages, live, aux, stream=stream):
                # dispatch coalescing: K uniform pages stack inside the trace
                # and the whole transform+staging runs as ONE dispatch
                return prep_body(*stream.transform(
                    *_stack_pages(pages, live), aux))

            @partial(_jit, site="agg.hash.insert_compact")
            def insert_compact(state, keys, knulls, inputs, n, key_types=key_types,
                               acc_kinds=acc_kinds):
                valid = jnp.arange(keys[0].shape[0], dtype=jnp.int32) < n
                return hashagg.groupby_insert(state, keys, key_types, valid, inputs,
                                              acc_kinds, knulls, with_rounds=True)

            @partial(_jit, site="agg.hash.insert_masked")
            def insert_masked(state, keys, knulls, inputs, valid,
                              key_types=key_types, acc_kinds=acc_kinds):
                return hashagg.groupby_insert(state, keys, key_types, valid, inputs,
                                              acc_kinds, knulls, with_rounds=True)

            arts = (node, prepare, bprepare, insert_compact, insert_masked)
            if cacheable:
                self._agg_cache[("hashpage", id(node))] = arts
        _, prepare, bprepare, insert_compact, insert_masked = arts
        staged: list = []
        ran: list = []  # (rounds an insert's loop ran at each width: a device vector, its lanes)
        room = capped and not proven  # the one step for room is still to take

        def grow(state, reserved):
            """``state`` re-inserted into a table of four times the ``reserved``
            slots (those of the table the reservation covers now); None at the
            capacity or memory ceiling."""
            grown = reserved * 4
            delta = state_bytes(grown) - state_bytes(reserved)
            if grown > MAX_GROUP_CAPACITY or not self.memory_pool.try_reserve(
                    delta, "group-by"):
                return None
            resv["bytes"] += delta
            out, rounds = hashagg.rehash(state, grown, tuple(acc_kinds),
                                         with_rounds=True)
            # (the rehash re-inserts every slot of the table it leaves)
            tracing.record_groupby_insert(state.capacity)
            ran.append((rounds, state.capacity))
            return out

        def insert_chunk(state, counts):
            nonlocal room
            for k, ((keys, knulls, inputs, valid, _), n) in enumerate(
                    zip(staged, counts)):
                if n == 0:
                    continue
                if k and not proven and _host([state.overflow],
                                              site="agg.hash.overflow")[0]:
                    break  # the regrow replays the chunk: spare it the rest
                if room and n > state.capacity:
                    room = False
                    state = grow(state, state.capacity) or state
                width = valid.shape[0]
                bucket = hashagg.insert_bucket(n, width)
                if bucket == width:
                    # over half live (or under the levels' floor): inserted masked
                    state, rounds = insert_masked(state, keys, knulls, inputs, valid)
                    tracing.record_groupby_insert(width)
                    ran.append((rounds, width))
                    continue
                cols_list = list(keys) + [v for v, _ in inputs if v is not None]
                nulls_list = list(knulls) + [nu for v, nu in inputs if v is not None]
                ccols, cnulls = _compact_part(tuple(cols_list), tuple(nulls_list),
                                              valid, bucket)
                tracing.record_compaction(width, bucket)
                nk = len(keys)
                rest_v, rest_n = list(ccols[nk:]), list(cnulls[nk:])
                cinputs = []
                for v, nu in inputs:
                    if v is None:
                        cinputs.append((None, None))
                    else:
                        cinputs.append((rest_v.pop(0), rest_n.pop(0)))
                state, rounds = insert_compact(state, ccols[:nk], cnulls[:nk],
                                               tuple(cinputs), np.int32(n))
                tracing.record_groupby_insert(bucket)
                ran.append((rounds, bucket))
            return state

        def drain(state):
            if not staged:
                return state, False
            counts = [int(c) for c in _host([st[-1] for st in staged],
                                            site="agg.stream.counts")]
            while True:
                # snapshot-and-replay growth (reference: FlatHash#rehash): jax
                # arrays are immutable, so the pre-chunk state is a free snapshot;
                # on overflow, rehash it into a 4x table and replay ONLY this
                # chunk — never the whole input stream
                start_state = state
                state = insert_chunk(state, counts)
                # the rounds the inserts' loops ran ride the flag's pull (what
                # the failed attempt of a regrow ran counts too)
                overflow, *rounds = _host(
                    [state.overflow] + [r for r, _ in ran],
                    site="agg.hash.overflow")
                tracing.record_groupby_insert(0, round_lanes=sum(
                    int(r) * w for level, (_, lanes) in zip(rounds, ran)
                    for r, w in zip(level, hashagg.insert_widths(lanes))))
                ran.clear()
                if not overflow:
                    staged.clear()
                    return state, False
                grown = grow(start_state, state.capacity)
                if grown is None:
                    staged.clear()
                    return state, True  # ceiling: caller falls back to partitioned
                state = grown

        for group, live in _coalesced_batches(pages_iter,
                                               self._batch(stream)):
            staged.append(prepare(group[0], stream.aux) if live is None
                          else bprepare(tuple(group), live, stream.aux))
            if len(staged) >= 4:
                state, ceiling = drain(state)
                if ceiling:
                    return state
        state, _ = drain(state)
        return state

    def _streaming_agg_order(self, stream, node):
        """Group-key source names when the stream's declared CLUSTERING makes
        every group's rows contiguous (the keys are a permutation of a
        clustering prefix), else None.  Filters/projects/compaction preserve
        row order, so clustered_by survives them; joins clear it."""
        if not stream.clustered_by or stream.scan_info is None:
            return None
        si = stream.scan_info
        names = []
        for ch in node.keys:
            nm = si.columns[ch] if ch < len(si.columns) else None
            if nm is None:
                return None
            names.append(nm)
        nk = len(names)
        if len(set(names)) != nk or set(names) != set(stream.clustered_by[:nk]):
            return None
        return tuple(names)

    def _run_streaming_aggregate(self, node, stream, key_types, acc_specs,
                                 acc_exprs, acc_kinds, capacity, pages_once,
                                 state_bytes):
        """Sorted-input aggregation (reference: streaming aggregation over
        pre-grouped input, operator/aggregation/).  Per page: valid rows
        compact to the front (order-preserving), key-change boundaries mark
        segments, and every accumulator reduces with ONE masked segmented
        scatter — no probe loop, no per-row hashing.  The per-segment partial
        rows (a handful per page) then merge through the ordinary hash insert
        with MERGE kinds, which also stitches groups spanning page
        boundaries."""
        merge_kinds = [_MERGE_KIND[k] for k in acc_kinds]
        key_dtypes = tuple(t.dtype for t in key_types)

        cacheable = self._agg_cacheable(node)
        hit = self._agg_cache.get(("streamagg", id(node))) if cacheable else None
        if hit is None:
            def pstep_body(cols, nulls, valid, node=node):
                n = valid.shape[0]
                # order-preserving compaction of EVERY array this step reads,
                # in one pack (ops/arrays.compact_rows: live-lane index then
                # gathers, or the round-13 Pallas kernel — one launch for the
                # whole page)
                vn_raw = []
                for e in acc_exprs:
                    if e is None:
                        vn_raw.append(None)
                        continue
                    v, nu = evaluate(e, cols, nulls)
                    v = jnp.broadcast_to(v, valid.shape) if v.ndim == 0 else v
                    if nu is not None and nu.ndim == 0:
                        nu = jnp.broadcast_to(nu, valid.shape)
                    vn_raw.append((v, nu))
                to_pack = [cols[ch] for ch in node.keys] \
                    + [nulls[ch] for ch in node.keys] \
                    + [a for vn in vn_raw if vn is not None for a in vn]
                packed, count = compact_rows(tuple(to_pack), valid, n)
                live = jnp.arange(n) < count
                it = iter(packed)
                kcols = [next(it) for _ in node.keys]
                knulls = [kn if kn is not None else jnp.zeros((n,), bool)
                          for kn in (next(it) for _ in node.keys)]
                # segment starts: first live row, or any key (value OR null
                # flag) differing from the previous live row
                new = jnp.zeros((n,), bool).at[0].set(True)
                for k, kn in zip(kcols, knulls):
                    kv = jnp.where(kn, jnp.zeros((), k.dtype), k)
                    d = jnp.concatenate([jnp.ones((1,), bool),
                                         (kv[1:] != kv[:-1])
                                         | (kn[1:] != kn[:-1])])
                    new = new | d
                new = new & live
                seg = (jnp.cumsum(new) - 1).astype(jnp.int32)
                seg = jnp.clip(seg, 0, n - 1)
                accs = []
                for vn_r, (dt, init), kind in zip(vn_raw, acc_specs, acc_kinds):
                    vn = None if vn_r is None else (next(it), next(it))
                    acc0 = jnp.full((n + 1,), init, dtype=dt)
                    # segment ids play the slot role: agg_update IS the
                    # segmented reduce (pads mask to the sink row)
                    total = hashagg.agg_update(acc0, kind, seg, live, vn)
                    accs.append(total[seg])  # per-row gather of its segment total
                return tuple(kcols), tuple(knulls), tuple(accs), new

            @partial(_jit, site="agg.sorted.step")
            def pstep(page, aux, stream=stream):
                return pstep_body(*stream.transform(
                    page.columns, page.null_masks, page.valid_mask(), aux))

            @partial(_jit, site="agg.sorted.batch")
            def bpstep(pages, live, aux, stream=stream):
                # dispatch coalescing: the stacked group keeps scan row order,
                # so clustering (group contiguity) holds across the K splits
                # and the segmented reduce even merges groups spanning the
                # original page boundaries before mstep sees them
                return pstep_body(*stream.transform(
                    *_stack_pages(pages, live), aux))

            @partial(_jit, site="agg.sorted.merge")
            def mstep(state, kcols, knulls, accs, new,
                      key_types=key_types, merge_kinds=tuple(merge_kinds)):
                return hashagg.groupby_insert(
                    state, kcols, key_types, new,
                    [(a, None) for a in accs], list(merge_kinds), knulls)

            if cacheable:
                self._agg_cache[("streamagg", id(node))] = (node, pstep,
                                                            bpstep, mstep)
        else:
            _, pstep, bpstep, mstep = hit

        capacity = ceil_pow2(capacity)
        if not self.memory_pool.try_reserve(state_bytes(capacity), "group-by"):
            return self._run_aggregate_partitioned(node, parts=node.grace_parts or 4)
        resv = state_bytes(capacity)
        try:
            pages = pages_once
            while True:
                with tracing.maybe_span("aggregate.sorted", slots=capacity):
                    state = hashagg.groupby_init(capacity, key_dtypes, acc_specs)
                    for group, live in _coalesced_batches(pages,
                                                          self._batch(stream)):
                        kcols, knulls, accs, new = \
                            pstep(group[0], stream.aux) if live is None \
                            else bpstep(tuple(group), live, stream.aux)
                        state = mstep(state, kcols, knulls, accs, new)
                        tracing.record_groupby_insert(new.shape[0])
                    if not _host([state.overflow],
                                 site="agg.sorted.overflow")[0]:
                        return self._finalize_groups(node, stream, state)
                # merge-state overflow: grow and re-stream (rare — capacity is
                # stats-sized upstream like the hash path)
                tracing.record_groupby(regrows=1)
                grown = ceil_pow2(capacity * 4)
                delta = state_bytes(grown) - resv
                if grown > MAX_GROUP_CAPACITY or \
                        not self.memory_pool.try_reserve(delta, "group-by"):
                    return self._run_aggregate_partitioned(node, parts=node.grace_parts or 4)
                resv += delta
                capacity = grown
                pages = stream.pages()
        finally:
            tracing.record_groupby(state_bytes=resv)
            self.memory_pool.free(resv, "group-by")

    def _device_finalize(self, node: P.Aggregate):
        """The group-by's epilogue as ONE program a (node, bucket): the
        occupied groups packed into ``bucket`` lanes, the accumulators
        finalized on the device, the wide-decimal envelope flag, the group
        count and the packed page's validity mask.  None when an agg kind
        needs the host-exact path.  Cached per node."""
        hit = self._agg_cache.get(("devfin", id(node)))
        if hit is not None:
            return hit[1]
        try:
            _device_finalize_plan(node.aggs)  # probe support outside jit
        except NotImplementedError:
            self._agg_cache[("devfin", id(node))] = (node, None)
            return None

        def finalize(state, bucket, aggs=node.aggs):
            keys, key_nulls, accs = hashagg.compact_groups(state, bucket)
            cols, nulls, bad = _finalize_aggs_device(aggs, accs)
            count = hashagg.group_count(state)
            return (keys + cols, key_nulls + nulls,
                    jnp.arange(bucket, dtype=jnp.int32) < count, count, bad)

        fin = _jit(finalize, site="agg.finalize", static_argnums=(1,))
        self._agg_cache[("devfin", id(node))] = (node, fin)
        return fin

    def _finalize_groups(self, node: P.Aggregate, stream, state, site=None):
        """The page of a finished group-by state, device-resident: packed
        into the pow2 bucket of its group count, with a validity mask and the
        count it already knows (``Page.live``), so nothing downstream pulls or
        reduces for it.  ``site`` names the pull that reads the state's
        overflow flag here, WITH the group count and the envelope flag; the
        answer is None when the flag is set.  A caller that has read the flag
        itself passes no site.

        The bucket is a static of the program and the count a device scalar,
        so a cached plan runs the program at the bucket its last run found
        and reads all three scalars in ONE pull after it; a first run (or a
        count that left its bucket) counts first and finalizes second."""
        dicts = tuple(stream.dicts[i] for i in node.keys) + tuple(None for _ in node.aggs)
        fin = self._device_finalize(node)
        cacheable = self._agg_cacheable(node)
        learned = self._agg_cache.get(("bucket", id(node))) \
            if cacheable and fin is not None else None
        guess = None if learned is None else learned[1]
        out, bad = None, fin is None  # (bad: the host-exact path answers)
        if guess is None:
            scalars = [_group_count(state)]
        else:
            out = fin(state, guess)
            scalars = [out[3], out[4]]
        got = _host(scalars + ([state.overflow] if site else []),
                    site=site or ("agg.group_count" if guess is None
                                  else "agg.finalize.envelope"))
        if site and got[-1]:
            return None
        n_groups = int(got[0])
        bucket = max(1 << max(n_groups - 1, 1).bit_length(), 64)
        if guess == bucket:
            bad = bool(got[1])
        elif fin is not None:
            out = fin(state, bucket)
            bad = bool(_host([out[4]], site="agg.finalize.envelope")[0])
            if cacheable:
                self._agg_cache[("bucket", id(node))] = (node, bucket)
        tracing.record_compaction(state.capacity, bucket)
        tracing.record_groupby(slots=state.capacity)

        # DEVICE-RESIDENT finalize (round 5): the aggregate output
        # stays on device, so a downstream projection/join/topn consumes it
        # without the pull-down + re-upload pair the host page costs
        # (the full-width _host pull here was the single largest Q3 transfer).
        # The envelope flag says a wide-decimal sum left exact int64: then,
        # and for agg kinds without a device finalize, the host-exact path
        # below (the _combine_limbs_vec fallback class).
        if not bad:
            tracing.record_tail(compiled=True)
            cols, nulls, valid = out[:3]
            return Page(node.schema, cols, nulls, valid, n_groups), dicts

        tracing.record_tail(compiled=False)
        keys, key_nulls, accs = hashagg.compact_groups(state, bucket)
        nk = len(keys)
        got = _host(list(keys) + list(key_nulls) + list(accs),
                    site="agg.groups")
        key_cols = [k[:n_groups] for k in got[:nk]]
        key_null_cols = [kn[:n_groups] for kn in got[nk:2 * nk]]
        acc_cols = [a[:n_groups] for a in got[2 * nk:]]
        fin_cols, fin_nulls = _finalize_aggs(node.aggs, acc_cols, n_groups)
        out_cols = key_cols + fin_cols
        arrays = [np.asarray(c) for c in out_cols]  # host-ok: post-_host finalize
        out_nulls = tuple(kn if kn.any() else None for kn in key_null_cols
                          ) + tuple(fin_nulls)
        page = Page(node.schema, tuple(arrays), out_nulls, None)
        return page, dicts

    def _run_aggregate_partitioned(self, node: P.Aggregate, parts: int):
        """Grace-partitioned aggregation over the TIERED spill
        (exec/spill.py, HBM -> host RAM -> disk): ONE pass transforms the
        input and hash-routes rows into per-partition tier buffers;
        partitions then aggregate one at a time — the input (a file-backed
        scan in the worst case) is read and decoded exactly once, unlike a
        Grace re-scan.  Device-resident (HBM-tier) partitions skip readback
        staging entirely; host/disk readback overlaps device compute through
        the round-6 prefetch double buffer.  Reference:
        SpillableHashAggregationBuilder + FileSingleStreamSpiller."""
        stream, key_types, acc_specs, acc_exprs, acc_kinds, _ = self._agg_compiled(node)

        @partial(_jit, site="agg.partitioned.route")
        def route(page, aux, stream=stream, node=node, parts=parts):
            cols, nulls, valid = stream.transform(
                page.columns, page.null_masks, page.valid_mask(), aux)
            key_vals = tuple(cols[i] for i in node.keys)
            key_nulls = tuple(nulls[i] for i in node.keys)
            # canonicalize NULL key lanes before hashing, exactly like
            # groupby_insert: the SQL NULL group must land in ONE partition
            routed = tuple(kv if kn is None
                           else jnp.where(kn, jnp.zeros((), kv.dtype), kv)
                           for kv, kn in zip(key_vals, key_nulls))
            return cols, nulls, valid, partition_ids(routed, parts)

        spill = SpilledPartitions(stream.schema, parts,
                                  memory_pool=self.memory_pool,
                                  buffer_pool=self.buffer_pool, owner=self)
        try:
            with tracing.maybe_span("aggregate.partitioned", parts=parts):
                out = self._consume_partitioned_agg(
                    node, stream, spill, parts, key_types, acc_specs, acc_exprs,
                    acc_kinds, route)
        finally:
            spill.close()
        if out is None:
            # a partition still blew the ceiling: restart with more partitions
            # (the one remaining source re-scan); THIS spill's buffers and
            # reservations are freed first — the restart re-spools the whole
            # input, and holding both doubles peak spill footprint in the one
            # path that runs under memory pressure
            tracing.record_groupby(regrows=1)
            return self._run_aggregate_partitioned(node, parts * 4)
        return out

    def _consume_partitioned_agg(self, node, stream, spill, parts, key_types,
                                 acc_specs, acc_exprs, acc_kinds, route):
        for page in stream.pages():
            cols, nulls, valid, pid = route(page, stream.aux)
            spill.add_page(cols, nulls, valid, pid)
        st = self._node_stats(node)
        st["spilled_bytes"] = spill.spilled_bytes
        st["spill_partitions"] = parts
        st["spill_tiers"] = dict(spill.tier_bytes)

        @partial(_jit, site="agg.partitioned.insert")
        def insert(state, page, node=node, key_types=key_types,
                   acc_exprs=acc_exprs, acc_kinds=acc_kinds):
            cols, nulls, valid = page.columns, page.null_masks, page.valid_mask()
            key_vals = tuple(cols[i] for i in node.keys)
            key_nulls = tuple(nulls[i] for i in node.keys)
            inputs = [(None, None) if e is None else evaluate(e, cols, nulls)
                      for e in acc_exprs]
            return hashagg.groupby_insert(state, key_vals, key_types, valid,
                                          inputs, acc_kinds, key_nulls)

        largest = 0  # slots of the largest partition table (not pool-reserved)
        pages_out, dicts = [], None
        for p in range(parts):
            # the spill pass counted this partition's rows EXACTLY: seed the
            # group table from them instead of the 2^23 worst-case (a 30k-row
            # partition used to pay an 8M-slot init + scatter).  Groups <=
            # rows always; 2x for probe headroom; the overflow retry loop
            # still covers an undershoot, MAX_GROUP_CAPACITY still caps.
            capacity = min(MAX_GROUP_CAPACITY // 4,
                           ceil_pow2(max(2 * spill.rows[p], 1024)))
            while True:
                state = hashagg.groupby_init(
                    capacity, tuple(t.dtype for t in key_types), acc_specs)
                # capacity retries replay from the spill tiers, never the
                # source.  Host/disk chunks stage through the prefetch double
                # buffer (decode/H2D overlaps the insert dispatches);
                # HBM-tier chunks are already device-resident — no wrap.
                src = partial(spill.partition_pages, p)
                if spill.needs_staging(p):
                    src = _prefetched_pages(src, to_device=True, owner=self)
                for page in src():
                    state = insert(state, page)
                    tracing.record_groupby_insert(page.capacity)
                if not _host([state.overflow],
                             site="agg.partitioned.overflow")[0]:
                    break
                if capacity >= MAX_GROUP_CAPACITY:
                    if parts >= 1 << 16:
                        raise MemoryError(
                            f"aggregation exceeds {MAX_GROUP_CAPACITY} groups per "
                            f"partition even at {parts} partitions")
                    return None  # the caller restarts with more partitions
                tracing.record_groupby(regrows=1)  # replays the partition
                capacity *= 4
            largest = max(largest, capacity)
            tracing.record_groupby(partitioned_passes=1)
            page, dicts = self._finalize_groups(node, stream, state)
            pages_out.append(page)
            # consumed: release this partition's host reservation + disk file
            spill.release_partition(p)
        # host-side concat.  Device-resident finalize makes partition outputs
        # jnp arrays: pull EVERY partition's columns in one batched _host
        # call (a serial per-column np.asarray would pay parts x columns
        # blocking syncs); exact wide-decimal (object) columns come
        # from the host-fallback finalize and pass through unchanged
        flat = []
        for p in pages_out:
            flat.extend(p.columns)
            flat.extend(p.null_masks)
        tracing.record_groupby(
            state_bytes=_group_state_bytes(key_types, acc_specs)(largest))
        flat = _host(flat, site="agg.stream.pull")
        w = len(node.schema.fields)
        host_pages = []
        for pi, p in enumerate(pages_out):
            base = pi * 2 * w
            # a packed partition page: its groups are its first ``live`` lanes
            host_pages.append(tuple(
                [a if a is None or p.live is None else a[:p.live] for a in part]
                for part in (flat[base:base + w], flat[base + w:base + 2 * w])))
        cols = tuple(np.concatenate([hp[0][i] for hp in host_pages])
                     for i in range(w))
        nulls = []
        for i in range(w):
            if any(hp[1][i] is not None for hp in host_pages):
                nulls.append(np.concatenate([
                    hp[1][i] if hp[1][i] is not None
                    else np.zeros((len(hp[0][i]),), bool)
                    for hp in host_pages]))
            else:
                nulls.append(None)
        return Page(node.schema, cols, tuple(nulls), None), dicts

    def _run_global_aggregate(self, node, stream, acc_exprs, acc_kinds):
        """Ungrouped aggregation (reference: AggregationOperator) — pure jnp reductions."""
        cacheable = self._agg_cacheable(node)
        hit = self._agg_cache.get(("global", id(node))) if cacheable else None
        if hit is not None:
            return self._finish_global(node, stream, acc_exprs, acc_kinds,
                                       hit[1], hit[2])

        @partial(_jit, site="agg.global.step")
        def step(state, page, aux, stream=stream, acc_exprs=acc_exprs,
                 acc_kinds=acc_kinds):
            cols, nulls, valid = stream.transform(page.columns, page.null_masks,
                                                  page.valid_mask(), aux)
            return _global_agg_update(state, cols, nulls, valid, acc_exprs,
                                      acc_kinds)

        @partial(_jit, site="agg.global.batch")
        def bstep(state, pages, live, aux, stream=stream, acc_exprs=acc_exprs,
                  acc_kinds=acc_kinds):
            # dispatch coalescing: fold a group of uniform pages in ONE
            # dispatch — reductions run over the stacked rows
            cols, nulls, valid = stream.transform(*_stack_pages(pages, live),
                                                  aux)
            return _global_agg_update(state, cols, nulls, valid, acc_exprs,
                                      acc_kinds)

        if cacheable:
            self._agg_cache[("global", id(node))] = (node, step, bstep)
        return self._finish_global(node, stream, acc_exprs, acc_kinds, step,
                                   bstep)

    def _finish_global(self, node, stream, acc_exprs, acc_kinds, step, bstep):
        state = _global_init_state(node)
        for group, live in _coalesced_batches(stream.pages(),
                                               self._batch(stream)):
            page = group[0]
            if live is not None:
                state = bstep(state, tuple(group), live, stream.aux)
            elif any(isinstance(c, np.ndarray) and c.dtype == object
                     for c in page.columns):
                # exact wide-decimal input channel (count over a wide-sum
                # subquery): jit cannot accept the page — run the step
                # eagerly; the untouched object channel passes through
                # (object pages never coalesce, so the eager path survives)
                state = step.__wrapped__(state, page, stream.aux)
            else:
                state = step(state, page, stream.aux)
        # ONE batched pull for every accumulator scalar (serial np.asarray
        # would pay one blocking sync per accumulator); exact
        # wide-decimal (object) accumulators pass through _host unchanged
        acc_cols = [np.asarray(a)[None]  # host-ok
                    for a in _host(list(state), site="agg.global.accs")]
        out_cols, out_nulls = _finalize_aggs(node.aggs, acc_cols, 1)
        # host output (exact wide-decimal columns must never reach the device)
        arrays = [np.asarray(c) for c in out_cols]  # host-ok: post-_host finalize
        page = Page(node.schema, tuple(arrays), tuple(out_nulls), None)
        return page, tuple(None for _ in node.aggs)

    # -- window functions ----------------------------------------------------
    def _run_window(self, node: P.Window):
        """Blocking window evaluation: materialize, sort, segmented scans, scatter back
        (ops/window.py; reference: WindowOperator over a sorted PagesIndex)."""
        with tracing.maybe_span(
                "window", functions=[s.kind for s in node.specs],
                partition_keys=sorted({c for s in node.specs for c in s.partition}),
                order_keys=sorted({k.channel for s in node.specs
                                   for k in s.order})) as sp:
            page, dicts = self._execute_to_page_streamed(node.child)
            n = page.capacity
            sp.attributes["lanes"] = n
            # the live count where the page knows it without a pull
            rows = n if page.valid is None else page.live
            if rows is not None:
                sp.attributes["rows"] = rows
            spec_dicts = _window_spec_dicts(node.specs, dicts)
            if n == 0:
                cols = tuple(page.columns) + tuple(
                    jnp.zeros((0,), s.type.dtype) for s in node.specs)
                return (Page(node.schema, cols,
                             tuple(page.null_masks) + tuple(None for _ in node.specs),
                             None),
                        tuple(dicts) + spec_dicts)

            hit = self._agg_cache.get(("window", id(node)))
            if hit is None:
                # valid matters: a partially-filled page's invalid rows must not
                # join real partitions (they'd inflate ranks/sums); the kernel
                # isolates them into a pad partition
                kernel = _jit(site="window.kernel",
                          fn=lambda cols, nulls, valid, specs=node.specs:
                                 _window_kernel(specs, cols, nulls, valid))
                self._agg_cache[("window", id(node))] = (node, kernel)
            else:
                kernel = hit[1]
            tracing.record_window(n, _window_sort_passes(
                node.specs, page.null_masks, page.valid is not None))
            out_cols, out_nulls = kernel(page.columns, page.null_masks, page.valid)
            cols = tuple(page.columns) + out_cols
            nulls = tuple(page.null_masks) + out_nulls
            return (Page(node.schema, cols, nulls, page.valid),
                    tuple(dicts) + spec_dicts)

    # -- join ---------------------------------------------------------------
    # maximum distinct probe keys shipped into a connector index lookup
    # (sqlite's default bound-parameter cap is 999; chunking past ~500 keys
    # rarely beats just scanning the remote table)
    INDEX_JOIN_MAX_KEYS = 500
    # probe-side row bound above which materializing the probe first (the
    # index join's inversion of build/probe order) is not worth attempting
    INDEX_JOIN_MAX_PROBE = 1 << 16

    def _index_lookup_stream(self, probe_stream, node: P.Join, build_page,
                             build_dicts):
        """Connector-backed index join (reference: operator/index/
        IndexLoader + IndexJoinOptimizer): when the PROBE side scans a
        connector with keyed-lookup support and the build side's distinct
        join keys are few, replace the probe's full-table splits with one
        WHERE-IN lookup split — dynamic filtering taken to the source (key
        SET pruning instead of min/max split pruning).  Returns a
        replacement probe stream or None."""
        if len(node.right_keys) != 1:
            return None
        si = probe_stream.scan_info
        if si is None or not si.splits \
                or not hasattr(si.splits[0], "table"):
            return None
        conn = si.conn
        table = si.splits[0].table
        if not getattr(conn, "supports_index_lookup", False) \
                or getattr(conn, "is_pushdown_handle", lambda t: False)(table):
            return None
        pk = node.left_keys[0]
        key_col = si.columns[pk] if pk < len(si.columns) else None
        if key_col is None:
            return None
        key_t = probe_stream.schema.fields[pk].type
        if not (key_t.is_integer or key_t.is_string):
            return None
        if build_page.capacity == 0 \
                or build_page.capacity > self.INDEX_JOIN_MAX_PROBE:
            return None
        try:
            remote_rows = int(conn.row_count(table))
        except Exception:
            return None
        bk = node.right_keys[0]
        v = build_page.columns[bk]
        nm = build_page.null_masks[bk]
        live = build_page.valid_mask()
        if nm is not None:
            live = live & ~nm
        # dead lanes collapse onto v[0]; a spurious key only over-fetches
        # (the local join still filters), truncation would LOSE rows — so
        # request MAX+1 distinct and bail when the budget fills.  The live
        # count and distinct set sync together (one batched transfer)
        uniq = jnp.unique(jnp.where(live, jnp.asarray(v), jnp.asarray(v)[0]),
                          size=min(int(build_page.capacity),
                                   self.INDEX_JOIN_MAX_KEYS + 1))
        got = _host([uniq, jnp.sum(live, dtype=jnp.int64)],
                    site="join.index.keys")
        if int(got[1]) == 0:
            # all-dead build: fall through to _dynamic_pruned_pages' empty-
            # build short-circuit (zero remote work) instead of shipping a
            # garbage lane value as a lookup key
            return None
        keys = np.unique(got[0])
        if len(keys) > self.INDEX_JOIN_MAX_KEYS:
            return None
        # profitability on the ACTUAL lookup size, not the lane count: a
        # sparse filtered build with few distinct keys is the ideal case
        if remote_rows < 4 * len(keys):
            return None
        bd = build_dicts[bk]
        if key_t.is_string:
            if bd is None or getattr(bd, "values", None) is None:
                return None
            keys = [str(x) for x in bd.decode(keys.astype(np.int64))]
        else:
            keys = [int(x) for x in keys.tolist()]
        handle = conn.apply_index_lookup(table, key_col, keys)
        new_splits = conn.splits(handle)
        scan_cols = si.scan_columns

        def pages(conn=conn, splits=new_splits, cols=scan_cols, table=table):
            for s in splits:
                yield _generate(conn, table, s, cols)

        st = self._node_stats(node)
        st["index_join_keys"] = len(keys)
        return dataclasses.replace(
            probe_stream, pages=si.pages_over(pages),
            scan_info=dataclasses.replace(si, splits=list(new_splits)))

    def _build_cache_key(self, node: P.Join):
        """Buffer-pool key for this join's build fragment, or None when the
        build must not be cached: pool off for this query, fragment reads a
        non-cacheable (volatile) connector, or the subtree is overridden by a
        spooled fragment output (query-scoped data — caching it would serve
        one query's spool to the next).  Key shape:
        ("build", fingerprint, right_keys, catalogs, filter-is-none) — the
        catalogs tuple at index 3 is what bufferpool.invalidate_catalog
        matches, and plan_versions fold into the fingerprint so growable
        catalogs never serve a stale build."""
        bp = self.buffer_pool
        if bp is None or not self._page_cache_on():
            return None
        if self._overrides and self._subtree_overridden(node.right):
            return None
        cats: set = set()
        cacheable = True

        def walk(n):
            nonlocal cacheable
            if isinstance(n, P.TableScan):
                conn = self.catalogs.get(n.catalog)
                if conn is None or not bp.cacheable(conn):
                    cacheable = False
                cats.add(n.catalog)
            for c in n.children:
                walk(c)

        walk(node.right)
        if not cacheable:
            return None
        fp = _plan_fingerprint(node.right, self.catalogs)
        return ("build", fp, tuple(node.right_keys), tuple(sorted(cats)),
                node.filter is None)

    def _compile_join(self, node: P.Join) -> _Stream:
        # build-cache tier: a structurally identical build fragment finished
        # by ANY executor sharing this pool (concurrent pooled queries, a
        # different statement over the same subquery) checks out the
        # materialized page + hash table instead of re-executing the fragment
        # and re-inserting every row.  The checked-out table threads through
        # _Stream.aux as a JIT ARGUMENT exactly like a fresh one (the
        # no-closed-over-aux rule).
        bkey = self._build_cache_key(node)
        cached = None
        if bkey is not None:
            cached = self.buffer_pool.get_build(bkey)
            tracing.record_build_cache(hits=1 if cached is not None else 0,
                                       misses=0 if cached is not None else 1,
                                       site="join.build.cache")
        if cached is not None:
            build_page, build_dicts = cached["page"], cached["dicts"]
            build_wall = 0.0
        else:
            t0 = _time.perf_counter()
            build_page, build_dicts = self._execute_to_page_streamed(node.right)
            build_wall = _time.perf_counter() - t0
        # advisory fact: the build side's ACTUAL row count (lazy device
        # scalar, same deferred-sum pattern as _record — it joins the history
        # collector's one batched value read, zero extra pulls).  Build
        # children are streaming, so nothing else records them, and their
        # est-vs-actual is precisely the broadcast-vs-partitioned input the
        # adaptive advisor needs.
        self._plan_facts[id(node.right)] = (node.right, {
            "build_rows": jnp.sum(build_page.valid_mask(), dtype=jnp.int64),
            "wall_s": build_wall})
        probe_stream = self._compile_stream(node.left)
        build_key_types = tuple(node.right.schema.fields[i].type for i in node.right_keys)
        if node.kind in ("inner", "semi") and node.filter is None:
            # connector index lookup first (key-SET pruning at the source);
            # falls back to min/max dynamic split pruning
            ix = self._index_lookup_stream(probe_stream, node, build_page,
                                           build_dicts)
            if ix is not None:
                probe_stream = ix
            # dynamic filtering: prune probe splits outside the build keys' min/max
            # domain (reference: DynamicFilterService.createDynamicFilter:260 narrowing
            # probe-side scans; here domains prune whole splits via connector ranges)
            pruned = None if ix is not None else \
                _dynamic_pruned_pages(probe_stream, node, build_page)
            if pruned is not None:
                pages_fn, kept = pruned
                psi = probe_stream.scan_info
                if psi is not None:
                    # rebuild the pruned replacement through the cache-aware
                    # source: it keeps the prefetch the original scan
                    # compiled with (round-6 double buffer / HOST_DECODE
                    # decode overlap) and the kept split list keys its own
                    # buffer-pool entry
                    pages_fn = psi.pages_over(self._scan_pages_source(
                        psi.conn, psi.catalog, psi.table, kept,
                        psi.scan_columns))
                repl = {"pages": pages_fn, "_jitted": None,
                        "_batch_jitted": None}
                if probe_stream.scan_info is not None:
                    repl["scan_info"] = dataclasses.replace(
                        probe_stream.scan_info, splits=list(kept))
                probe_stream = dataclasses.replace(probe_stream, **repl)
        # memory gate: build-side state (columns + table/order layout) is
        # device-resident and pinned by the stream cache.  When it cannot fit the
        # pool, switch to the Grace-partitioned strategy (the HBM analog of the
        # reference's spilling join, operator/join/spilling/HashBuilderOperator.java)
        # build page x2 (columns + compaction copies) + the 4x-pow2 probe table
        # (8B packed key + 4B row id per slot).  A build-cache hit skips the
        # gate: the pool already accounts the resident bytes, and a cached
        # build by definition fit when it was built.
        if cached is None:
            need = _page_bytes(build_page) * 2 \
                + 12 * 4 * ceil_pow2(max(build_page.capacity, 16))
            partitionable = (node.kind in ("inner", "left", "semi")
                            and node.left_keys and node.filter is None)
            if not self.memory_pool.try_reserve(need, "join-build"):
                if partitionable:
                    parts, free = 2, max(self.memory_pool.free_bytes(), 1)
                    while need // parts > free // 2 and parts < 64:
                        parts *= 2
                    return self._compile_partitioned_local_join(
                        node, build_page, build_dicts, probe_stream,
                        build_key_types, parts)
                # non-partitionable join shapes proceed best-effort (the pool
                # is advisory; XLA raises if HBM is truly exhausted)

        return self._join_with_build(node, build_page, build_dicts, probe_stream,
                                     build_key_types, cache_key=bkey,
                                     cached=cached)

    def _join_with_build(self, node: P.Join, build_page, build_dicts, probe_stream,
                         build_key_types, cache_key=None, cached=None) -> _Stream:
        # "mark" (reference: semi-join MARKER output, planner/plan/
        # SemiJoinNode's semiJoinOutput): probe channels + one boolean
        # matched channel, no lane filtering — EXISTS in expression position
        semi = node.kind in ("semi", "anti", "mark")
        if cached is not None:
            # build-cache hit: the null stats, direct-span probe and table
            # build (with their device syncs and insert dispatches) all
            # happened when the entry was stored — check the results out
            build_has_null, build_nonempty = cached["null_stats"]
            span = cached["span"]
            table = cached["table"]
        else:
            with tracing.maybe_span("join.build") as sp:
                build_has_null, build_rows = _build_key_stats(
                    build_page, node.right_keys)
                build_nonempty = build_rows > 0
                span = self._direct_join_span(build_page, node.right_keys,
                                              build_key_types)
                table = None
                if node.filter is None and build_page.capacity > 0:
                    table = self._build_join_table(
                        build_page, node.right_keys, build_key_types, span)
                # a unique-key table, or the attempt at one that met
                # duplicate keys and was dropped (``dups``: the table is then
                # _compile_multi_join's, under a span of its own)
                hashed = isinstance(table, JoinTable)
                slots = table.capacity if hashed else span[1] if span else 0
                tracing.record_join_build(build_rows, slots if hashed else 0)
                sp.attributes.update(kind="direct" if span else "hash",
                                     rows=build_rows, slots=slots)
                if table is None:
                    sp.attributes["dups"] = True
            if cache_key is not None:
                # store-on-failure hardening: a failed admission (injected
                # fault, pool error) must not fail a join whose build already
                # completed — the build is simply not shared
                try:
                    self.buffer_pool.put_build(cache_key, {
                        "page": build_page, "dicts": build_dicts,
                        "table": table, "span": span,
                        "null_stats": (build_has_null, build_nonempty)})
                except tracing.StallKilledError:
                    raise  # a watchdog kill must never be neutralized here
                except Exception:
                    pass
        if table is None or node.filter is not None:
            # duplicate build keys or residual join filter -> multi-match strategy
            return self._compile_multi_join(node, build_page, build_dicts, probe_stream,
                                            build_key_types, span)

        def probe_keys(cols, nulls, valid, aux, up=probe_stream, node=node):
            up_aux, table = aux
            cols, nulls, valid = up.transform(cols, nulls, valid, up_aux)
            return cols, nulls, valid, tuple(cols[i] for i in node.left_keys), table

        def non_null_keys(matched, nulls, node=node):
            for i in node.left_keys:  # NULL keys never match (SQL equi-join semantics)
                if nulls[i] is not None:
                    matched = matched & ~nulls[i]
            return matched

        dicts = (probe_stream.dicts + (None,) if node.kind == "mark"
                 else probe_stream.dicts if semi
                 else probe_stream.dicts + build_dicts)
        # propagate probe-side scan provenance: downstream aggregations use it for
        # row-bound table sizing, and further joins for dynamic split pruning
        si = probe_stream.scan_info
        if si is not None:
            n_build = (1 if node.kind == "mark"
                       else 0 if semi else len(build_page.columns))
            si = dataclasses.replace(
                si, columns=tuple(si.columns) + (None,) * n_build)

        decided = probe_stream.compacted
        if node.kind in ("inner", "semi") and si is not None and not decided:
            # a probe stream still at scan width: match first, gather after.
            # The match step is its own program: the upstream chain, then only
            # what decides ``matched`` (for a direct table the one gather of
            # ``occ``, carrying the slot; for a hashed one the probe loop,
            # carrying the build row).  The boundary packs each batch to what
            # survived (_compacted_stream); the gather step is the transform
            # of the returned stream, so it fuses into whatever consumes it,
            # and the chain's later joins run fused at the width the boundary
            # left.  left/anti/mark joins keep their unmatched lanes and stay
            # one step; so does a join whose first page the boundary would not
            # pack (below).
            def match_step(cols, nulls, valid, aux, node=node):
                cols, nulls, valid, keys, table = probe_keys(cols, nulls, valid, aux)
                if isinstance(table, DirectJoinTable):
                    carry, matched = direct_match(
                        stage_direct_table(table, MATCH_FIELDS), keys[0], valid)
                    rounds = None
                else:
                    carry, matched, rounds = probe_counted(
                        table, keys, build_key_types, valid)
                valid = valid & non_null_keys(matched, nulls)
                if node.kind == "semi":
                    return cols, nulls, valid, rounds
                return (tuple(cols) + (carry,), tuple(nulls) + (None,), valid,
                        rounds)

            def gather_step(cols, nulls, valid, table, node=node):
                carry = cols[-1]
                if isinstance(table, DirectJoinTable):
                    table = stage_direct_table(table, GATHER_FIELDS)
                    carry = table.rows[carry]
                bcols, bnulls = _gather_build(table, carry, valid, node.kind)
                return cols[:-1] + bcols, nulls[:-1] + bnulls, valid

            mschema = probe_stream.schema if semi else Schema(
                probe_stream.schema.fields + (Field("$carry", INTEGER),))
            mdicts = probe_stream.dicts if semi else probe_stream.dicts + (None,)
            packed = self._compacted_stream(_Stream(
                mschema, mdicts, probe_stream.pages, match_step,
                probe_stream.scan_info, aux=(probe_stream.aux, table)),
                isinstance(table, JoinTable))
            if packed is not None:
                si = dataclasses.replace(si, over=packed.scan_info.over)
                if semi:
                    return dataclasses.replace(packed, schema=node.schema,
                                               dicts=dicts, scan_info=si)
                return dataclasses.replace(
                    packed, schema=node.schema, dicts=dicts, scan_info=si,
                    transform=gather_step, aux=table)
            # the first page stayed dense: this chain has no selective join
            # here, and runs fused as one step, its later joins too
            decided = True

        def transform(cols, nulls, valid, aux, node=node):
            cols, nulls, valid, keys, table = probe_keys(cols, nulls, valid, aux)
            if isinstance(table, DirectJoinTable):
                table = stage_direct_table(table)
                row_ids, matched = direct_probe(table, keys[0], valid)
            else:
                row_ids, matched = probe(table, keys, build_key_types, valid)
            matched = non_null_keys(matched, nulls)
            if node.kind == "inner":
                valid = valid & matched
            elif node.kind == "semi":
                valid = valid & matched
            elif node.kind == "anti":
                valid = valid & ~matched
                valid = _null_aware_anti(node, valid, nulls, build_has_null,
                                         build_nonempty)
            if node.kind == "mark":
                return (tuple(cols) + (matched & valid,),
                        tuple(nulls) + (None,), valid)
            if semi:
                return cols, nulls, valid
            bcols, bnulls = _gather_build(table, row_ids, matched, node.kind)
            out_cols = tuple(cols) + bcols
            out_nulls = tuple(nulls) + bnulls
            return out_cols, out_nulls, valid

        pages, si = _probed_pages(probe_stream.pages, si,
                                  isinstance(table, JoinTable))
        return _Stream(node.schema, dicts, pages, transform, si,
                       aux=(probe_stream.aux, table), compacted=decided)

    def _compile_multi_join(self, node: P.Join, build_page, build_dicts, probe_stream,
                            build_key_types, span=None) -> _Stream:
        """Join with duplicate build keys and/or a residual match filter.

        Reference: position-linked JoinHash chains (operator/join/JoinHash.java:145) with
        JoinFilterFunction evaluated per candidate match.  Here: slot-grouped build layout
        (ops/hashjoin.multi_build) + searchsorted expansion; output page size is
        data-dependent, so the expansion crosses a host sync per page and re-jits per
        power-of-two output bucket (shape-class caching keeps recompiles bounded)."""
        semi = node.kind in ("semi", "anti", "mark")
        if build_page.capacity == 0:
            # empty build: pad one never-matching dummy row so gathers stay well-defined
            cols = tuple(jnp.zeros((1,), f.type.dtype) for f in node.right.schema.fields)
            build_page = Page(node.right.schema, cols, tuple(None for _ in cols),
                              jnp.zeros((1,), bool))
        with tracing.maybe_span("join.build", kind="multi",
                                rows=build_page.capacity) as sp:
            if span is not None:
                mt = _jit(direct_multi_build, static_argnums=(0, 1, 3))(
                    span[0], span[1], build_page, node.right_keys[0])
                sp.attributes["slots"] = span[1]
            else:
                capacity = max(1 << max(build_page.capacity - 1, 1).bit_length(), 16) * 4
                mt = multi_build(capacity, build_page, node.right_keys, build_key_types)
                sp.attributes["slots"] = mt.table.shape[0] - 1
                tracing.record_join_build(0, mt.table.shape[0] - 1)

        @_jit
        def count_step(page, mt, up_aux, up=probe_stream, node=node):
            cols, nulls, valid = up.transform(page.columns, page.null_masks,
                                              page.valid_mask(), up_aux)
            keys = tuple(cols[i] for i in node.left_keys)
            kvalid = valid
            for i in node.left_keys:
                if nulls[i] is not None:
                    kvalid = kvalid & ~nulls[i]
            if isinstance(mt, DirectMultiJoinTable):
                slot, matched = direct_probe_slots(mt, keys[0], kvalid)
            else:
                slot, matched = probe_slots(mt.table, keys, build_key_types, kvalid)
            matched = matched & kvalid
            cnt = jnp.where(matched, mt.counts[slot], 0)
            if node.kind == "left":
                out_cnt = jnp.where(valid, jnp.maximum(cnt, 1), 0)
            else:
                out_cnt = cnt
            incl = jnp.cumsum(out_cnt, dtype=jnp.int32)
            return cols, nulls, valid, slot, matched, cnt, out_cnt, incl

        def expand_step(size, cols, nulls, valid, slot, matched, cnt, out_cnt, incl, mt,
                        node=node):
            pidx, k, in_range = expand_counts(incl, out_cnt, size)
            is_match = matched[pidx] & (k < cnt[pidx]) & in_range
            brow = mt.order[jnp.clip(mt.starts[slot[pidx]] + k, 0, mt.order.shape[0] - 1)]
            brow = jnp.where(is_match, brow, 0)
            ocols = tuple(c[pidx] for c in cols) + tuple(c[brow] for c in mt.build_columns)
            onulls = tuple(None if n is None else n[pidx] for n in nulls) + tuple(
                None if n is None else n[brow] for n in mt.build_null_masks)
            if node.filter is not None:
                passed = evaluate_predicate(node.filter, ocols, onulls, is_match)
            else:
                passed = is_match
            n_probe = valid.shape[0]
            if semi:
                mark = jnp.zeros((n_probe,), jnp.int32).at[pidx].max(
                    passed.astype(jnp.int32))
                return mark.astype(bool)
            if node.kind == "left":
                any_pass = jnp.zeros((n_probe,), jnp.int32).at[pidx].max(
                    passed.astype(jnp.int32)).astype(bool)
                keep = passed | ((k == 0) & ~any_pass[pidx] & in_range & valid[pidx])
                onulls = onulls[:len(cols)] + tuple(
                    (jnp.zeros_like(passed) if n is None else n) | ~passed
                    for n in onulls[len(cols):])
                return ocols, onulls, keep
            return ocols, onulls, passed  # inner

        # ONE jit object per join stream: jax caches executables per static `size`
        # bucket internally, so power-of-two padding bounds recompiles
        expand_jit = _jit(expand_step, static_argnums=0)

        build_has_null, build_nonempty = _build_null_stats(build_page, node.right_keys)

        def pages(probe_stream=probe_stream):
            for page in probe_stream.pages():
                cols, nulls, valid, slot, matched, cnt, out_cnt, incl = \
                    count_step(page, mt, probe_stream.aux)
                tracing.record_probe_lanes(
                    page.capacity, not isinstance(mt, DirectMultiJoinTable))
                if semi and node.filter is None:
                    if node.kind == "mark":
                        yield Page(node.schema,
                                   tuple(cols) + (matched & valid,),
                                   tuple(nulls) + (None,), valid)
                        continue
                    if node.kind == "semi":
                        v = valid & matched
                    else:
                        v = _null_aware_anti(node, valid & ~matched, nulls,
                                             build_has_null, build_nonempty)
                    yield Page(probe_stream.schema, cols, nulls, v)
                    continue
                total = int(incl[-1]) if incl.shape[0] else 0
                size = max(1 << max(total - 1, 1).bit_length(), 1024)
                out = expand_jit(size, cols, nulls, valid, slot, matched, cnt, out_cnt,
                                 incl, mt)
                if semi:
                    mark = out
                    if node.kind == "mark":
                        yield Page(node.schema, tuple(cols) + (mark & valid,),
                                   tuple(nulls) + (None,), valid)
                        continue
                    v = valid & mark if node.kind == "semi" else valid & ~mark
                    yield Page(probe_stream.schema, cols, nulls, v)
                else:
                    ocols, onulls, ovalid = out
                    yield Page(node.schema, ocols, onulls, ovalid)

        dicts = (probe_stream.dicts + (None,) if node.kind == "mark"
                 else probe_stream.dicts if semi
                 else probe_stream.dicts + build_dicts)
        return _Stream(node.schema, dicts, pages, lambda c, n, v, aux: (c, n, v))

    def _compile_partitioned_local_join(self, node: P.Join, build_page, build_dicts,
                                        probe_stream, build_key_types,
                                        parts: int) -> _Stream:
        """Grace-partitioned join over the HOST-RAM spill tier (exec/spill.py):
        hash-partition BOTH sides on the join keys into host buffers — the
        build page immediately (freeing its HBM), the probe in ONE transformed
        pass — then join one partition at a time from host.  Each probe row
        belongs to exactly one partition, so inner/left/semi semantics hold
        part-locally, and the probe input (a file-backed scan in the worst
        case) is read and decoded exactly once instead of once per partition.
        Reference: the spilling join's partition-at-a-time consumption
        (operator/join/spilling/PartitionedConsumption.java) over
        FileSingleStreamSpiller partitions."""
        bkeys = tuple(build_page.columns[i] for i in node.right_keys)
        bknulls = tuple(build_page.null_masks[i] for i in node.right_keys)
        routed = tuple(kv if kn is None else jnp.where(kn, jnp.zeros((), kv.dtype), kv)
                       for kv, kn in zip(bkeys, bknulls))
        bpid = partition_ids(routed, parts)
        # the build side is PERSISTENT spill state: it lives with this
        # compiled stream across executions of a cached plan, so it skips
        # the HBM tier (the point of partitioning the build is freeing its
        # device residency) and stays UNACCOUNTED in the executor pool —
        # reserving plan-cache-lifetime bytes there would hold the pool past
        # BLOCKED_FRACTION forever, permanently engaging the admission gate
        # and feeding the cluster killer innocent victims (pool reservations
        # must mean live per-query state).  Its disk overflow still honors
        # the watermark; forget_plan reclaims everything with the stream.
        build_spill = SpilledPartitions(build_page.schema, parts,
                                        owner=self, persistent=True,
                                        tag="spill-build", node_id=id(node))
        build_spill.add_page(build_page.columns, build_page.null_masks,
                             build_page.valid_mask(), bpid)
        # from here the build lives off-device; its device arrays free with
        # this frame (the point of spilling: O(build/parts) resident HBM)

        @_jit
        def probe_route(page, aux, up=probe_stream, node=node, parts=parts):
            cols, nulls, valid = up.transform(page.columns, page.null_masks,
                                              page.valid_mask(), aux)
            keys = tuple(cols[i] for i in node.left_keys)
            knulls = tuple(nulls[i] for i in node.left_keys)
            rt = tuple(kv if kn is None
                       else jnp.where(kn, jnp.zeros((), kv.dtype), kv)
                       for kv, kn in zip(keys, knulls))
            return cols, nulls, valid, partition_ids(rt, parts)

        def pages(self=self, node=node):
            # spill pass: one read of the probe source per execution
            probe_spill = SpilledPartitions(probe_stream.schema, parts,
                                            memory_pool=self.memory_pool,
                                            buffer_pool=self.buffer_pool,
                                            owner=self)
            try:
                for page in probe_stream.pages():
                    cols, nulls, valid, pid = probe_route(page,
                                                          probe_stream.aux)
                    probe_spill.add_page(cols, nulls, valid, pid)
                st = self._node_stats(node)
                st["spilled_bytes"] = (build_spill.spilled_bytes
                                       + probe_spill.spilled_bytes)
                st["spill_partitions"] = parts
                st["spill_tiers"] = {
                    t: build_spill.tier_bytes[t] + probe_spill.tier_bytes[t]
                    for t in probe_spill.tier_bytes}
                for p in range(parts):
                    # host/disk probe partitions stage back through the
                    # prefetch double buffer; HBM-tier partitions are
                    # already device-resident
                    src = partial(probe_spill.partition_pages, p)
                    if probe_spill.needs_staging(p):
                        src = _prefetched_pages(src, to_device=True,
                                                owner=self)
                    sub_stream = _Stream(probe_stream.schema,
                                         probe_stream.dicts, src,
                                         lambda c, n, v, aux: (c, n, v))
                    sub = self._join_with_build(
                        node, build_spill.partition_page(p), build_dicts,
                        sub_stream, build_key_types)
                    jt = sub.jitted()
                    for page in sub.pages():
                        cols, nulls, valid = jt(page)
                        yield Page(node.schema, cols, nulls, valid)
                    probe_spill.release_partition(p)
            finally:
                probe_spill.close()

        semi = node.kind in ("semi", "anti")
        dicts = probe_stream.dicts if semi else probe_stream.dicts + build_dicts
        return _Stream(node.schema, dicts, pages, lambda c, n, v, aux: (c, n, v))

    def _param_pruned_source(self, up: _Stream, pred, si=None):
        """Page source with BIND-TIME split pruning for parameterized
        predicates, or None when not applicable.  A plan template's filter
        holds ir.Parameter where the substituted plan held the constant that
        _static_pruned_stream prunes on; this source re-derives the pruned
        split list per EXECUTION from the bound values (host-side numpy
        copies — no device sync) and routes the kept splits through the
        cache-aware _scan_pages_source, so each binding keys its own
        buffer-pool entry and keeps the scan's prefetch policy.  ``si``
        defaults to the stream's scan info; callers that already pruned
        statically pass the pruned info so both passes compose."""
        if si is None:
            si = up.scan_info
        if si is None or not hasattr(si.conn, "split_range"):
            return None
        def has_params(e) -> bool:
            if isinstance(e, _ir.Parameter):
                return True
            if isinstance(e, _ir.Call):
                return any(has_params(a) for a in e.args)
            return False

        if pred is None or not has_params(pred):
            return None

        class _NullParam(Exception):
            pass

        def subst(e, host):
            """Parameter -> Constant(bound value); constant casts fold so the
            domain translator sees the bare Constant it pattern-matches."""
            if isinstance(e, _ir.Parameter):
                v, isnull = host[e.slot]
                if isnull:
                    raise _NullParam()  # NULL never prunes (conservative)
                return _ir.Constant(v.item() if hasattr(v, "item") else v,
                                    e.type)
            if isinstance(e, _ir.Call):
                args = tuple(subst(a, host) for a in e.args)
                if e.op == "cast" and len(args) == 1 \
                        and isinstance(args[0], _ir.Constant) \
                        and not isinstance(args[0].value, np.ndarray) \
                        and args[0].value is not None:
                    folded = _coerce(args[0], e.type)
                    if isinstance(folded, _ir.Constant):
                        return folded
                return dataclasses.replace(e, args=args)
            return e

        def kept_idx_for(host, up=up, pred=pred, si=si):
            """Indices into si.splits kept for ONE binding's host values
            (split order preserved — the pruned scan must yield rows in the
            same order the full scan would)."""
            kept = list(range(len(si.splits)))
            resolved = []
            for c in split_conjuncts(pred):
                try:
                    resolved.append(subst(c, host))
                except (_NullParam, IndexError):
                    continue  # unprunable conjunct; the filter still applies
            if resolved:
                td = extract_domains(resolved).tuple_domain
                if td.is_none:
                    kept = []
                elif not td.is_all:
                    by_col: dict = {}
                    for ch, dom in td.domains.items():
                        col = si.columns[ch] if ch < len(si.columns) else None
                        if col is not None \
                                and not up.schema.fields[ch].type.is_floating:
                            by_col[col] = dom.intersect(by_col[col]) \
                                if col in by_col else dom
                    if by_col:
                        keep = domain_to_split_pruner(by_col, si.conn)
                        kept = [i for i, s in enumerate(si.splits)
                                if keep(s)]
            return kept

        def pages(self=self, si=si):
            batch = _current_batch_host_params()
            if batch:
                # fused template batch (round 21): one scan feeds every
                # stacked predicate — keep the UNION of the members' pruned
                # split lists, in split order.  Rows a member's predicate
                # would have pruned are masked invalid in that member's lane
                # by the filter itself, so the union scan is byte-identical
                # per lane to the member's own pruned scan.
                idx: set = set()
                for host in batch:
                    idx.update(kept_idx_for(host))
                kept = [si.splits[i] for i in sorted(idx)]
            else:
                kept = [si.splits[i]
                        for i in kept_idx_for(_current_host_params())]
            src = self._scan_pages_source(si.conn, si.catalog, si.table,
                                          kept, si.scan_columns)
            yield from src()

        return pages

    def _limited_stream_page(self, node: P.Limit):
        """LIMIT over a streaming child: pull pages only until `count` live rows
        exist, then stop the source entirely (reference: LimitOperator ending the
        pipeline early — the big win is scans that never run)."""
        stream = self._compile_stream(node.child)
        step = stream.jitted()
        parts, total = [], 0
        for page in stream.pages():
            cols, nulls, valid = step(page)
            n = int(jnp.sum(valid, dtype=jnp.int32))
            if n == 0:
                continue
            n = min(n, node.count - total)
            bucket = min(max(1 << max(n - 1, 1).bit_length(), 1024),
                         valid.shape[0])
            ccols, cnulls = _compact_part(cols, nulls, valid, bucket)
            tracing.record_compaction(valid.shape[0], bucket)
            parts.append((ccols, cnulls, n))
            total += n
            if total >= node.count:
                break
        if not parts:
            cols = tuple(jnp.zeros((0,), f.type.dtype) for f in stream.schema.fields)
            return Page(stream.schema, cols, tuple(None for _ in cols), None), \
                stream.dicts
        ncols = len(parts[0][0])
        has_null = tuple(any(cnulls[ci] is not None for _, cnulls, _ in parts)
                         for ci in range(ncols))
        ns = jnp.asarray([n for _, _, n in parts], jnp.int32)
        cols_out, nulls_out, valid = _concat_all(
            tuple((ccols, cnulls) for ccols, cnulls, _ in parts), ns, has_null)
        return Page(stream.schema, cols_out, nulls_out, valid), stream.dicts

    def _execute_to_page_streamed(self, node):
        """Materialize a sub-plan into one device page (join build side)."""
        if self._overrides and id(node) in self._overrides:
            return self._overrides[id(node)]
        if isinstance(node, (P.Aggregate, P.Sort, P.Limit, P.Output, P.Window)):
            return self._execute_to_page(node)
        stream = self._compile_stream(node)
        return _concat_stream(stream, self._batch(stream)), stream.dicts

    def _direct_join_span(self, build_page: Page, key_channels, key_types):
        """(lo, span) when the build keys form a single dense integer range small
        enough for direct addressing, else None.  Bounds come from the build page
        itself (exact, no stats needed) — one batched host sync."""
        if len(key_channels) != 1 or key_types[0].is_floating \
                or build_page.capacity == 0:
            return None
        ch = key_channels[0]
        valid = build_page.valid_mask()
        nm = build_page.null_masks[ch]
        if nm is not None:
            valid = valid & ~nm
        k64 = build_page.columns[ch].astype(jnp.int64)
        imax, imin = jnp.iinfo(jnp.int64).max, jnp.iinfo(jnp.int64).min
        got = _host([jnp.min(jnp.where(valid, k64, imax)),
                     jnp.max(jnp.where(valid, k64, imin)),
                     jnp.sum(valid, dtype=jnp.int64)],
                    site="join.direct.range")
        kmin, kmax, nlive = (int(x) for x in got)
        if nlive == 0 or kmax - kmin + 1 > DIRECT_JOIN_RANGE_MAX:
            return None
        return kmin, kmax - kmin + 1

    def _build_join_table(self, build_page: Page, key_channels, key_types, span=None):
        n = build_page.capacity
        # 4x build rows (load <= 0.25): the lockstep batch probe pays the WORST
        # row's chain length every round, and halving the load roughly halves
        # the max double-hash chain (measured 15 -> 8 rounds on a 6M-row probe)
        capacity = max(1 << max(n - 1, 1).bit_length(), 16) * 4
        keys = tuple(build_page.columns[i] for i in key_channels)
        # join keys never match NULL: drop null-keyed build rows
        valid = build_page.valid_mask()
        for ch in key_channels:
            nm = build_page.null_masks[ch]
            if nm is not None:
                valid = valid & ~nm
        if span is not None:
            dt = _jit(direct_build, static_argnums=(0, 1, 3))(
                span[0], span[1], build_page, key_channels[0])
            if int(dt.dup_count) > 0:
                return None  # caller falls back to the multi-match strategy
            return dt
        while True:
            table = build_table_init(capacity, build_page)
            table = _jit(build_insert, static_argnums=(2,))(table, keys, key_types, valid)
            # ONE batched sync for both flags (each separate int()/bool() is
            # a blocking device->host sync of its own)
            overflow, dups = (int(x) for x in
                              _host([table.overflow, table.dup_count],
                                    site="join.build.flags"))
            if not overflow:
                break
            capacity *= 4
        if dups > 0:
            return None  # caller falls back to the multi-match strategy
        return table


def _static_pruned_stream(up: _Stream, pred):
    """Compile-time split pruning from the pushed-down predicate's TupleDomain
    (reference: DomainTranslator.getExtractionResult feeding connector split pruning
    via ConnectorMetadata.applyFilter / per-split TupleDomain stats).  Returns
    (pages, scan_info) with the pruned split list, or None when nothing prunes."""
    si = up.scan_info
    if si is None or not hasattr(si.conn, "split_range"):
        return None
    td = extract_domains(split_conjuncts(pred)).tuple_domain
    if td.is_none:
        return (lambda: iter(()), dataclasses.replace(si, splits=[]))
    if td.is_all:
        return None
    by_col: dict = {}
    for ch, dom in td.domains.items():
        col = si.columns[ch] if ch < len(si.columns) else None
        # float stats exclude NaN (parquet spec), so NaN-holding splits could be
        # wrongly pruned — never prune on floating columns
        if col is not None and not up.schema.fields[ch].type.is_floating:
            by_col[col] = dom.intersect(by_col[col]) if col in by_col else dom
    if not by_col:
        return None
    keep = domain_to_split_pruner(by_col, si.conn)
    kept = [s for s in si.splits if keep(s)]
    if len(kept) == len(si.splits):
        return None
    conn, scan_cols = si.conn, si.scan_columns

    def pages(conn=conn, kept=kept, scan_cols=scan_cols, table=si.table):
        for s in kept:
            yield _generate(conn, table, s, scan_cols)

    return pages, dataclasses.replace(si, splits=kept)


def _dynamic_pruned_pages(probe_stream: _Stream, node, build_page: Page):
    """(page source, kept splits) skipping probe splits disjoint from the build
    keys' value domain (inner/semi joins only — outer/anti joins must keep
    unmatched probe rows).  Returns None when no pruning is possible."""
    si = probe_stream.scan_info
    if si is None or not hasattr(si.conn, "split_range"):
        return None
    exact_ok = build_page.capacity <= 65536
    bvalid = _host([build_page.valid_mask()],
                   site="join.prune.valid")[0] if (build_page.capacity
                                                     and exact_ok) else \
        np.zeros((0,), bool)
    nonempty = bvalid.any() if exact_ok else (
        build_page.capacity > 0 and bool(jnp.any(build_page.valid_mask())))
    if not nonempty:
        return (lambda: iter(())), ()  # empty build: no probe row can match

    domains = {}
    # large build sides never yield an exact value set (UNION_LIMIT), so don't
    # pull megabyte columns to the host to discover that: compute the
    # min/max span ON DEVICE and sync two scalars per key instead (reference:
    # DynamicFilterSourceOperator's value-set -> min/max fallback at its size
    # limits, applied before the device->host hop rather than after)
    span_stats, span_cols = [], []
    for pch, bch in zip(node.left_keys, node.right_keys):
        col = si.columns[pch] if pch < len(si.columns) else None
        if col is None:
            continue
        f = node.right.schema.fields[bch]
        if f.type.is_string or f.type.is_floating:
            continue
        if exact_ok:
            nm = build_page.null_masks[bch]
            got = _host([build_page.columns[bch]]
                        + ([nm] if nm is not None else []),
                        site="join.prune.keys")
            vals = got[0][bvalid]
            if nm is not None:
                vals = vals[~got[1][bvalid]]
            if len(vals) == 0:
                continue
            uniq = np.unique(vals)
            if len(uniq) <= UNION_LIMIT:
                domains[col] = Domain.multiple_values([int(v) for v in uniq])
            else:
                domains[col] = Domain.from_range(
                    Range.between(int(vals.min()), int(vals.max())))
        else:
            c = build_page.columns[bch]
            live = build_page.valid_mask()
            nm = build_page.null_masks[bch]
            if nm is not None:
                live = live & ~nm
            c64 = c.astype(jnp.int64)
            imax, imin = jnp.iinfo(jnp.int64).max, jnp.iinfo(jnp.int64).min
            span_stats.extend([jnp.min(jnp.where(live, c64, imax)),
                               jnp.max(jnp.where(live, c64, imin)),
                               jnp.any(live)])
            span_cols.append(col)
    if span_cols:
        got = _host(span_stats, site="join.prune.span")
        for i, col in enumerate(span_cols):
            lo, hi, any_live = (int(got[3 * i]), int(got[3 * i + 1]),
                                bool(got[3 * i + 2]))
            if any_live:
                domains[col] = Domain.from_range(Range.between(lo, hi))
    if not domains:
        return None
    keep = domain_to_split_pruner(domains, si.conn)
    conn, scan_cols = si.conn, si.scan_columns
    kept = tuple(s for s in si.splits if keep(s))

    def pages():
        for s in kept:
            yield _generate(conn, si.table, s, scan_cols)

    return pages, kept


def _run_match_recognize(node: P.MatchRecognize, child: Page, cdicts):
    """Row-pattern matching over sorted partitions (reference:
    operator/window/matcher/ — the compiled NFA programs of
    IrRowPatternToProgramRewriter + Matcher.java; this subset runs a
    backtracking matcher over per-row DEFINE condition vectors).

    Device side: sorting and DEFINE predicate evaluation (one boolean vector
    per pattern variable, navigation channels as shifted columns).  Host side:
    the sequential match assembly — non-overlapping greedy matches with
    skip-past-last-row are inherently order-dependent."""
    keys = tuple(P.SortKey(ch, True, False) for ch in node.partition) \
        + tuple(node.order)
    sorted_page = _sort_page(child, keys, cdicts)
    valid, cols, nulls = _host_page(sorted_page)
    cols = [c[valid] for c in cols]
    nulls = [None if nm is None else nm[valid] for nm in nulls]
    n = len(cols[0]) if cols else 0

    # partition boundaries over the sorted rows.  NULL keys group together
    # (one partition), so the raw-value comparison only applies where BOTH
    # rows are non-null — null lanes hold arbitrary fill values
    new_part = np.zeros(n, bool)
    if n:
        new_part[0] = True
        for ch in node.partition:
            c = cols[ch]
            diff = c[1:] != c[:-1]
            nm = nulls[ch]
            if nm is not None:
                diff = (diff & ~(nm[1:] | nm[:-1])) | (nm[1:] != nm[:-1])
            new_part[1:] |= diff

    # navigation channels: shifted within the partition, NULL across edges
    ext_cols = list(cols)
    ext_nulls = list(nulls)
    part_id = np.cumsum(new_part)
    for ch, off in node.nav:
        src_idx = np.arange(n) + off  # off<0 = PREV, >0 = NEXT
        ok = (src_idx >= 0) & (src_idx < n)
        safe = np.clip(src_idx, 0, max(n - 1, 0))
        if n:
            ok &= part_id[safe] == part_id
        shifted = cols[ch][safe] if n else cols[ch]
        nm = nulls[ch]
        base_null = np.zeros(n, bool) if nm is None else nm[safe]
        ext_cols.append(shifted)
        ext_nulls.append(base_null | ~ok)

    # one boolean vector per variable (undefined variables match any row);
    # device inputs convert once, not per variable
    conds = {}
    defined = dict(node.defines)
    jc = [jnp.asarray(c) for c in ext_cols]
    jn = [None if m is None else jnp.asarray(m) for m in ext_nulls]
    all_vars = [v for el, _ in node.pattern
                for v in (el if isinstance(el, tuple) else (el,))]
    for var in all_vars:
        e = defined.get(var)
        if e is None:
            conds[var] = np.ones(n, bool)
        else:
            v, nu = evaluate(e, jc, jn)
            # match_recognize's NFA walks rows on the host: one batched pull
            # per DEFINE variable (was two loose per-variable np.asarray)
            got = _host([jnp.broadcast_to(v, (n,))]
                        + ([jnp.broadcast_to(nu, (n,))] if nu is not None
                           else []), site="mr.define")
            arr = got[0]
            if nu is not None:
                arr = arr & ~got[1]
            conds[var] = arr.astype(bool)

    def elem_conds(el):
        """(row-acceptance vector, per-row matched variable).  Alternation
        prefers the LEFTMOST alternative whose condition holds at each row —
        the reference's alternation preference order."""
        if not isinstance(el, tuple):
            return conds[el], None
        ok = np.zeros(n, bool)
        who = np.empty(n, object)
        for v in reversed(el):
            c = conds[v]
            who[c] = v
            ok |= c
        return ok, who

    pat_info = [elem_conds(el) + (q, el) for el, q in node.pattern]

    def find_match(start, end):
        """Greedy with backtracking (regex semantics); returns
        (stop, [(row, var), ...]) or None."""
        pat = pat_info

        def rec(i, pi):
            if pi == len(pat):
                return i, []
            ok, who, q, el = pat[pi]

            def tag(k):
                return who[k] if who is not None else el

            if q is None:
                if i < end and ok[i]:
                    r = rec(i + 1, pi + 1)
                    if r is not None:
                        return r[0], [(i, tag(i))] + r[1]
                return None
            if q == "?":
                if i < end and ok[i]:
                    r = rec(i + 1, pi + 1)
                    if r is not None:
                        return r[0], [(i, tag(i))] + r[1]
                return rec(i, pi + 1)
            j = i
            while j < end and ok[j]:
                j += 1
            lo = i + (1 if q == "+" else 0)
            while j >= lo:
                r = rec(j, pi + 1)
                if r is not None:
                    return r[0], [(k, tag(k)) for k in range(i, j)] + r[1]
                j -= 1
            return None

        return rec(start, 0)

    # vectorized fast path: when greedy backtracking provably reduces to
    # run-length jumps (ops/matcher.py), match geometry for EVERY start
    # computes in one device pass and the host only walks actual matches
    vm = None
    if not getattr(node, "all_rows", False):
        measure_vars = {var for _, var, _, _ in node.measures
                        if var is not None}
        vm = vector_match(node.pattern, conds, np.asarray(new_part),  # host-ok
                          measure_vars)

    # non-overlapping matches, AFTER MATCH SKIP PAST LAST ROW
    starts = list(np.nonzero(new_part)[0]) + [n]
    out_rows: list = []
    for pi in range(len(starts) - 1):
        s, e = int(starts[pi]), int(starts[pi + 1])
        i = s
        while i < e:
            if vm is not None:
                i = int(vm.nxt[i])  # jump straight to the next usable start
                if i >= e:
                    break
                m = (int(vm.end[i]), None)
            else:
                m = find_match(i, e)
            if m is None or m[0] == i:  # no match / empty match: advance
                i += 1
                continue
            stop, assign = m
            if assign is None:  # vectorized: first/last rows per measure var
                by_var = vm.by_var(i)
            else:
                by_var = {}
                for row, var in assign:
                    by_var.setdefault(var, []).append(row)
            vals = []
            for kind, var, ch, _ in node.measures:
                if kind == "col":
                    row = stop - 1
                elif var is not None:
                    rows_v = by_var.get(var)
                    if not rows_v:
                        vals.append(None)
                        continue
                    row = rows_v[0] if kind == "first" else rows_v[-1]
                else:
                    row = i if kind == "first" else stop - 1
                nm = nulls[ch]
                vals.append(None if (nm is not None and nm[row])
                            else cols[ch][row])
            if getattr(node, "all_rows", False):
                # ALL ROWS PER MATCH: one output row per matched input row —
                # all input columns plus RUNNING-semantics measures (the
                # reference's default for ALL ROWS: each row sees the match
                # only up to itself, RowsPerMatch + RUNNING evaluation)
                for r, _var in assign:
                    vals_r = []
                    for kind, var, ch, _ in node.measures:
                        if kind == "col":
                            row = r
                        elif var is not None:
                            rows_v = [x for x in by_var.get(var, ())
                                      if x <= r]
                            if not rows_v:
                                vals_r.append(None)
                                continue
                            row = rows_v[0] if kind == "first" else rows_v[-1]
                        else:
                            row = i if kind == "first" else r
                        nm = nulls[ch]
                        vals_r.append(None if (nm is not None and nm[row])
                                      else cols[ch][row])
                    rvals = tuple(
                        None if (nulls[ch] is not None and nulls[ch][r])
                        else cols[ch][r] for ch in range(len(cols)))
                    out_rows.append(rvals + tuple(vals_r))
            else:
                pvals = tuple(
                    None if (nulls[ch] is not None and nulls[ch][i])
                    else cols[ch][i] for ch in node.partition)
                out_rows.append(pvals + tuple(vals))
            i = stop

    # assemble the output page
    n_out = len(out_rows)
    out_cols, out_nulls = [], []
    for j, f in enumerate(node.schema.fields):
        dt = np.dtype(f.type.dtype)
        arr = np.zeros(n_out, dt)
        nm = np.zeros(n_out, bool)
        for r, row in enumerate(out_rows):
            if row[j] is None:
                nm[r] = True
            else:
                arr[r] = row[j]
        out_cols.append(jnp.asarray(arr))
        out_nulls.append(jnp.asarray(nm) if nm.any() else None)
    measure_dicts = tuple(cdicts[ch] if cdicts and ch < len(cdicts) else None
                          for _, _, ch, _ in node.measures)
    if getattr(node, "all_rows", False):
        dicts = tuple(cdicts[ch] if cdicts and ch < len(cdicts) else None
                      for ch in range(len(cols))) + measure_dicts
    else:
        dicts = tuple(cdicts[ch] if cdicts and ch < len(cdicts) else None
                      for ch in node.partition) + measure_dicts
    page = Page(node.schema, tuple(out_cols), tuple(out_nulls), None)
    return page, dicts


def _run_unnest(node: P.Unnest, child: Page, cdicts):
    """Device-side UNNEST expansion (reference: operator/unnest/UnnestOperator.java,
    re-designed as the searchsorted expansion map of ops/arrays.unnest_indices —
    the same fixed-capacity pattern as the multi-match join).  Parallel arrays
    zip by position; shorter ones pad with NULL."""
    if child.capacity == 0:
        # zero-row child: expansion map has nothing to gather from; pad to one
        # invalid row so the fixed-shape kernel runs (yielding zero rows out)
        child = Page(child.schema,
                     tuple(jnp.zeros((1,), c.dtype) for c in child.columns),
                     tuple(None for _ in child.columns), jnp.zeros((1,), bool))
    valid = child.valid_mask()
    spans = [child.columns[ch] for ch in node.unnest_channels]
    span_nulls = [child.null_masks[ch] for ch in node.unnest_channels]
    lens = None
    per_ch_lens = []
    for sp, nm in zip(spans, span_nulls):
        ln = span_len(sp)
        if nm is not None:
            ln = jnp.where(nm, 0, ln)
        ln = jnp.where(valid, ln, 0)
        per_ch_lens.append(ln)
        lens = ln if lens is None else jnp.maximum(lens, ln)
    total = int(jnp.sum(lens))  # one host sync; unnest is a blocking operator
    cap = max(1 << max(total - 1, 1).bit_length(), 16)
    row, ordinal, in_range = unnest_indices(lens, cap)

    out_cols, out_nulls = [], []
    dicts = []
    for ch in node.replicate:
        out_cols.append(child.columns[ch][row])
        nm = child.null_masks[ch]
        out_nulls.append(None if nm is None else nm[row])
        dicts.append(cdicts[ch] if cdicts and ch < len(cdicts) else None)
    for sp, ln_c, data in zip(spans, per_ch_lens, node.array_datas):
        heap = jnp.asarray(data.values)
        start = span_start(sp)[row]
        pos = jnp.clip(start + ordinal, 0, max(heap.shape[0] - 1, 0))
        val = heap[pos] if heap.shape[0] else jnp.zeros(cap, heap.dtype)
        out_cols.append(val)
        # zipped shorter arrays pad with NULL; attaching the mask untested
        # avoids a per-channel device sync (all-False masks are harmless)
        out_nulls.append(ordinal >= ln_c[row])
        dicts.append(data.elem_dict)
    if node.ordinality:
        out_cols.append((ordinal + 1).astype(jnp.int64))
        out_nulls.append(None)
        dicts.append(None)
    page = Page(node.schema, tuple(out_cols), tuple(out_nulls), in_range)
    return page, tuple(dicts)


def _probed_pages(pages, si, hashed: bool):
    """The page source of a join that runs FUSED into whatever consumes its
    stream (no dispatch of its own to count at): each page it hands on is
    recorded, at its static width, under the loop the fused probe puts it
    through.  Returns the source and the scan provenance whose rebuilt sources
    (split pruning above the join) count the same way."""
    def counted(source):
        def probed():
            it = source()
            try:
                for page in it:
                    tracing.record_probe_lanes(page.capacity, hashed)
                    yield page
            finally:
                if hasattr(it, "close"):
                    it.close()
        return probed

    if si is not None:
        si = dataclasses.replace(
            si, over=lambda raw, below=si.pages_over: counted(below(raw)))
    return counted(pages), si


def _stage_scan_entry(pages):
    """One device-resident page from a completed scan's page list, for the
    buffer pool's page tier.  Host (HOST_DECODE / memory-connector) arrays
    stage through _page_to_device — the sanctioned H2D chokepoint — and the
    concatenation runs as ONE COUNTED _jit dispatch (row order = split
    order, the _stack_pages soundness argument), so the cold path's store
    cost shows up in the budget counters and per-site attribution instead of
    hiding as eager device work.  Returns None when any column is an object
    (exact wide-decimal) array — those cannot live on device."""
    pages = [_page_to_device(p) for p in pages]
    if any(isinstance(c, np.ndarray) and c.dtype == object
           for p in pages for c in p.columns):
        return None
    if len(pages) == 1:
        return pages[0]
    stack = _jit(lambda ps: _stack_pages(ps), site="cache.store")
    cols, nulls, valid = stack(tuple(pages))
    return Page(pages[0].schema, cols, nulls, valid)
