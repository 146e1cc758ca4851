"""Lightweight tracing spans (OpenTelemetry-shaped, dependency-free).

Reference: the coordinator opens spans per query phase — dispatch
(dispatcher/DispatchManager.java:190), planning/execution
(execution/SqlQueryExecution.java:478-481) — via airlift's TracingModule
(server/Server.java:113) and ScopedSpan/TrinoAttributes (tracing/).  Here spans
record to an in-memory tracer; ``spans_to_otlp`` renders them as OTLP-shaped
JSON for ``GET /v1/query/{id}/trace`` without engine changes.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import logging
import re
import threading
import time
from typing import Optional

try:  # the profiler's annotations and named scopes; the rest is jax-free
    import jax
except Exception:  # pragma: no cover - jax is the engine's own dependency
    jax = None

__all__ = ["Span", "Tracer", "QueryCounters", "track_counters",
           "current_counters", "record_dispatch", "record_host_pull",
           "record_coalesced", "record_page_cache", "record_build_cache",
           "record_fault", "record_task_retry", "record_spill",
           "SPILL_TIERS",
           "record_shard_stats", "shard_skew", "SHARD_STATS_MAX",
           "LatencyHistogram", "LATENCY_BUCKETS_S",
           "operator_scope", "activate_tracer", "current_tracer",
           "maybe_span", "span_dict", "spans_to_otlp",
           "InflightRegistry", "InflightEntry", "INFLIGHT", "inflight",
           "track_inflight", "current_inflight", "query_scope",
           "current_query_id", "live_query_counters", "StallWatchdog",
           "StallKilledError", "DISPATCH_TEST_HOOK",
           "WALL_BUCKETS", "wall_breakdown",
           "COMPILE_BUCKETS_S", "CompileLog", "COMPILE_LOG",
           "record_compile", "arg_signature", "signature_summary",
           "install_compile_listener",
           "begin_compile_capture", "end_compile_capture",
           "compile_capture_misses", "site_program", "wait_span",
           "statement_waits", "accepted_scope", "take_accepted",
           "record_wait", "annotate", "record_generate"]

_log = logging.getLogger("trino_tpu.stall")


# -- dispatch-latency histogram ------------------------------------------------
#
# Fixed buckets, Prometheus histogram semantics (per-bucket counts exported
# cumulatively with le= labels).  The buckets span sub-ms local-CPU dispatches
# through multi-second stalls: the stall signature — p99 blowing up while the
# dispatch COUNT stalls — is readable from one scrape.

LATENCY_BUCKETS_S = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005,
                     0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)

# XLA compilation wall-time buckets (round 17): compiles run seconds-to-
# minutes (cold SF1 Q1 ~110s on device), far past the dispatch buckets'
# 10s ceiling — the compile histogram needs its own scale
COMPILE_BUCKETS_S = (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                     10.0, 30.0, 60.0, 120.0, 300.0)


class LatencyHistogram:
    """Fixed-bucket latency histogram (non-cumulative counts internally; the
    Prometheus exporter cumulates).  Thread-safe: worker task threads and the
    engine's query threads record into shared per-engine totals.  ``buckets``
    defaults to the dispatch scale (LATENCY_BUCKETS_S); the compile census
    passes COMPILE_BUCKETS_S — merge only like-bucketed histograms."""

    __slots__ = ("buckets", "counts", "total", "sum_s", "_lock")

    def __init__(self, buckets=LATENCY_BUCKETS_S):
        self.buckets = tuple(buckets)
        self.counts = [0] * (len(self.buckets) + 1)  # last = +Inf
        self.total = 0
        self.sum_s = 0.0
        self._lock = threading.Lock()

    def record(self, seconds: float) -> None:
        i = 0
        for i, ub in enumerate(self.buckets):
            if seconds <= ub:
                break
        else:
            i = len(self.buckets)
        with self._lock:
            self.counts[i] += 1
            self.total += 1
            self.sum_s += seconds

    def merge(self, other: "LatencyHistogram") -> None:
        with other._lock:
            counts, total, sum_s = list(other.counts), other.total, other.sum_s
        with self._lock:
            for i, c in enumerate(counts):
                self.counts[i] += c
            self.total += total
            self.sum_s += sum_s

    def merge_dict(self, d: dict) -> None:
        counts = list(d.get("buckets", ()))
        with self._lock:
            for i, c in enumerate(counts[:len(self.counts)]):
                self.counts[i] += int(c)
            self.total += int(d.get("count", sum(counts)))
            self.sum_s += float(d.get("sum_s", 0.0))

    def snapshot(self) -> "LatencyHistogram":
        out = LatencyHistogram(self.buckets)
        out.merge(self)
        return out

    def quantile(self, q: float) -> Optional[float]:
        """Bucket-upper-bound estimate of the q-quantile (the wedge detector's
        p99); None when empty.  +Inf bucket reports the largest finite bound."""
        with self._lock:
            total = self.total
            counts = list(self.counts)
        if total == 0:
            return None
        target = q * total
        seen = 0
        for i, c in enumerate(counts):
            seen += c
            if seen >= target and c:
                return self.buckets[min(i, len(self.buckets) - 1)]
        return self.buckets[-1]

    def as_dict(self) -> dict:
        with self._lock:
            return {"buckets": list(self.counts), "count": self.total,
                    "sum_s": round(self.sum_s, 6)}


# -- per-query device-boundary counters ---------------------------------------
#
# Host<->device syncs and launches are a real part of a warm join query's
# wall, and the wins that cut them (device finalize, device TopN) are one
# stray np.asarray away from silently reverting.  These
# counters make the boundary a first-class, testable quantity: every jitted
# dispatch and every batched device->host pull in the local executor records
# here, the engine snapshots them per query, and tests/test_query_budgets.py
# pins warm TPC-H ceilings (the moral analog of Trino's zero-per-page driver
# pump, operator/Driver.java:372-481 — the scheduler cost budget is CODE, not
# a trace note).
#
# Round 7 adds ATTRIBUTION: each record carries a call-site tag (threaded from
# the _jit/_host wrappers) and lands under the active operator scope, so a
# budget failure names the exact site that regressed (the OperatorStats /
# per-operator kernel-launch attribution the GPU-Presto and TQP papers found
# essential), plus a per-query dispatch-latency histogram.


def _site_entry(sites: dict, key: str) -> dict:
    rec = sites.get(key)
    if rec is None:
        rec = sites[key] = {"dispatches": 0, "transfers": 0, "bytes": 0}
    return rec


@dataclasses.dataclass
class QueryCounters:
    """Cheap always-on counters at the two device-boundary chokepoints:
    jitted-function invocations (``device_dispatches`` — each is one XLA
    program launch) and batched
    device->host pulls (``host_transfers`` calls moving ``host_bytes_pulled``
    bytes through ``_host``).  ``sites`` breaks both down per
    "<operator>/<call-site tag>" and ``dispatch_latency`` histograms each
    dispatch's wall time."""

    device_dispatches: int = 0
    host_transfers: int = 0
    host_bytes_pulled: int = 0
    # splits whose per-page work ran inside a coalesced multi-split dispatch
    # (exec/boundary._coalesced_batches): the batching that turns K
    # per-split dispatches into one — visible so EXPLAIN ANALYZE / bench can
    # show HOW a query met its dispatch budget, not just that it did
    coalesced_splits: int = 0
    # round 9: device buffer pool (execution/bufferpool.DeviceBufferPool).
    # A page hit means the whole scan was served from HBM — no host
    # generation, no H2D staging, one page instead of K splits;
    # bytes_saved is the served entry's device footprint.  A build hit means
    # a join's build fragment (page + hash table) came from the pool.
    page_cache_hits: int = 0
    page_cache_misses: int = 0
    page_cache_bytes_saved: int = 0
    build_cache_hits: int = 0
    # round 12: result-cache tier (the buffer pool's third tier).  A result
    # hit means the WHOLE statement was answered from a cached
    # MaterializedResult — zero device dispatches, zero executor checkout,
    # zero host pulls; bytes_saved is the served result's host footprint.
    # Misses count only statements that were ADMISSIBLE (deterministic plan,
    # cacheable connectors, cache enabled) but not resident.
    result_cache_hits: int = 0
    result_cache_misses: int = 0
    result_cache_bytes_saved: int = 0
    # round 10: chaos accounting.  faults_injected counts fault-injector
    # firings (execution/faults) attributed to this query — a chaos run is
    # self-describing in EXPLAIN ANALYZE and bench output; task_retries
    # counts retry-loop re-attempts (FTE task retries, coordinator task
    # re-dispatches) charged to the query that paid them.
    faults_injected: int = 0
    task_retries: int = 0
    # round 11: the memory-pressure escalation ladder.  spilled_bytes is the
    # total the tiered spill (exec/spill.SpilledPartitions) routed out of the
    # operator's working set, broken down by the tier each chunk landed in
    # (hbm = device-resident under the buffer pool's budget — no readback
    # staging; host = RAM under the executor pool's "spill" tag; disk =
    # zstd-framed files in TRINO_TPU_SPILL_DIR).  admission_queued counts
    # queries the engine deferred at admission because executor pools sat
    # blocked (ladder rung: deny admission before anything is killed).
    spilled_bytes: int = 0
    spill_tier_hbm: int = 0
    spill_tier_host: int = 0
    spill_tier_disk: int = 0
    admission_queued: int = 0
    # round 13: plan templates (engine._template_cache).  A hit means the
    # statement was answered through an already-compiled parameterized plan
    # — zero parse/analyze/plan work, zero re-compilation; a miss counts a
    # template CREATION (the one planning that statement shape ever pays).
    plan_template_hits: int = 0
    plan_template_misses: int = 0
    # round 17: the compile observatory.  compiles counts first-seen arg
    # signatures at the _jit chokepoint (each is one XLA trace+compile on
    # this process); compile_s is their summed wall time, from the
    # jax.monitoring compile-event listener when the runtime exposes it
    # (fallback: the dispatch's own wall).  A WARM query records zero —
    # the recompile-regression guard test_query_budgets pins.
    compiles: int = 0
    compile_s: float = 0.0
    # PR 25: ``compiles`` counts REQUESTS (first-seen signatures at a wrapper,
    # persistent-cache serves included); compile_cache_misses counts the
    # programs XLA really compiled (backend-compile events of the
    # jax.monitoring listener less its persistent-cache hits, captured on
    # the dispatching thread like compile_s)
    compile_cache_misses: int = 0
    # PR 26: device-side row compactions (ops/arrays.live_indices + gathers:
    # the pipeline-boundary pack, _compact_part*, compact_groups) and the
    # lanes they read and keep: the static n and bucket of each dispatch,
    # host ints the caller holds
    compactions: int = 0
    compact_lanes_in: int = 0
    compact_lanes_out: int = 0
    # PR 27: how each group-by was sized and what it cost, host ints recorded
    # where the executor decides (no sync): slots of the final state and the
    # largest reservation of every group-by of the statement, overflows that
    # cost a re-scan of the input (an in-loop rehash that replays one chunk is
    # not one), Grace passes; rows inserted into join build tables when a
    # stream is compiled (0 on a replay: the tables live in the stream) and
    # base rows the connector generated for the statement (a scan served from
    # a resident page generates none)
    groupby_slots: int = 0
    groupby_state_bytes: int = 0
    groupby_regrows: int = 0
    groupby_partitioned_passes: int = 0
    # PR 44: group-bys whose direct index came from bounds READ off the one
    # materialised page of a blocking child (local_executor.
    # _observed_direct_config), not from a dictionary or the connector
    groupby_observed_direct: int = 0
    join_build_rows: int = 0
    rows_generated: int = 0
    # PR 28: how often a split join's boundary engages (local_executor.
    # _compacted_stream), host ints recorded where it yields a page, no sync:
    # static lanes that entered a match step, and static lanes at which that
    # join's build columns were then gathered (the pack's bucket, or the same
    # width for a batch that stayed dense)
    join_match_lanes: int = 0
    join_gather_lanes: int = 0
    # PR 36: which loops the lanes went through.  Static lanes of the pages
    # that entered a join's match or probe step over a hashed table
    # (ops/hashjoin.probe / probe_slots: the open-addressing loop) and over a
    # direct-indexed one (one gather), split joins and fused ones alike, so
    # over the split joins alone their sum is join_match_lanes; static lanes
    # of the dispatches that ran ops/hashagg.groupby_insert (every mode: hash,
    # sorted merge, Grace partitions, and a regrow's rehash); slots of each
    # hashed join table when it was built.  Host ints taken where the
    # executor dispatches (record_probe_lanes, record_groupby_insert)
    join_hash_probe_lanes: int = 0
    join_direct_probe_lanes: int = 0
    join_hash_table_slots: int = 0
    groupby_insert_lanes: int = 0
    # PR 37: the lanes the hashed lookup GATHERED for: rounds x width, summed
    # over the widths a batch's rounds ran at (ops/hashjoin.probe_widths: the
    # whole batch, then what was still unfinished, packed).  The rounds are
    # device scalars that ride a split join's survivor count
    # (local_executor._compacted_stream: the one pull that is there), so it is
    # recorded THERE only: a fused join's probe, the multi-match count step
    # and the mesh fragments count their join_hash_probe_lanes and leave this
    # 0, and so does the Pallas kernel (tables of 2^16 slots or fewer on the
    # chip), which has no rounds
    join_hash_probe_round_lanes: int = 0
    # PR 40: the lanes the group-by's hash insert PROBED for: the rounds of
    # ops/hashagg._probe_insert's open-addressing loop (two gathers, a
    # scatter-min and a set each) times the width each ran at, summed over
    # the widths of a page (PR 41, ops/hashagg.insert_widths: the whole page,
    # then what was still unplaced, packed).  Over groupby_insert_lanes it is
    # the rounds an inserted lane cost.  The rounds are device vectors, one
    # entry a width, that the insert steps
    # of a hash-mode group-by (local_executor._run_hash_inserts:
    # agg.hash.insert_compact, agg.hash.insert_masked, a regrow's rehash)
    # hand back, and they ride the agg.hash.overflow pull that ends each
    # chunk: no pull and no dispatch of its own.  Recorded THERE only:
    # the sorted merge, Grace partitions, FTE's partial step and the mesh
    # steps count their groupby_insert_lanes and leave this 0, and so does
    # the Pallas kernel (tables of 2^16 slots or fewer on the chip), which
    # has no rounds
    groupby_insert_round_lanes: int = 0
    # PR 39: how a group-by's finalize and a Sort or TopN ran, one count
    # each: as a compiled program over a device-resident page
    # (local_executor._device_finalize, exec/pages._sorted_rows), or on the eager/host
    # path (a host-resident page, an unrankable sort key, an agg kind or a
    # wide-decimal sum that needs the host-exact finalize)
    tail_compiled: int = 0
    tail_eager: int = 0
    # PR 42: what the window operator was handed (local_executor._run_window:
    # one kernel a Window node over one materialised page), host ints taken
    # where it dispatches, no sync: kernels, the static lanes of their pages
    # (live rows or not: every lane is sorted, scanned and scattered back),
    # and lanes times the stable sort passes of ops/window.window_order (one
    # a key column of each distinct (partition, order) clause, a NULL
    # indicator and the pad mask included)
    window_kernels: int = 0
    window_lanes: int = 0
    window_sort_lanes: int = 0
    # PR 32: the mesh path.  Rows the statement's all-to-all exchanges
    # delivered and the fullest worker's share of them, summed over its
    # exchanges from the receive cursors and occupancy counts the exchange
    # already pulls (record_shard_stats: no pull, no dispatch of their own);
    # kept fragments (exec/distributed.py: a plan node's compiled stream,
    # its jitted shard_map steps) served, and fragments compiled
    exchange_rows: int = 0
    exchange_rows_max_shard: int = 0
    mesh_fragment_hits: int = 0
    mesh_fragment_compiles: int = 0
    # PR 33: what the probe exchanges INSIDE the mesh steps carried (a
    # partitioned join's all-to-all of its probe side): rows routed, and the
    # lanes their receive tensors held (W x bucket a chip a batch).  They
    # ride the stream's side channel to the flags pull its consumer makes
    # anyway (exec/distributed.py _settle); exchange_rows above does not
    # hold them
    probe_exchange_rows: int = 0
    probe_exchange_lanes: int = 0
    # PR 46: payload bytes of the rows those exchanges delivered: the rows of
    # exchange_rows and of probe_exchange_rows, each times the width of its
    # routed columns (host ints from the plan's schemas; the receive
    # tensors' dead lanes are not in it: probe_exchange_lanes has those).
    # And the batches of the mesh's sharded scans (exec/distributed.py
    # _ShardedScan.__getitem__): handed to a step from the page cache's
    # entry, or generated for it (a first run, or a scan whose share a chip
    # passes the entry cap and streams, every statement)
    exchange_bytes: int = 0
    mesh_scan_batches_resident: int = 0
    mesh_scan_batches_generated: int = 0
    # PR 38: launches of a connector's page generator from the executor's scan
    # sources (record_generate: one a split, on whichever thread runs it, the
    # prefetch producer's mostly).  NOT part of device_dispatches, whose
    # ceilings count the executor's own programs
    generator_dispatches: int = 0
    # PR 25: the statement's wait states, seconds (each also a span of the
    # same name family: server.queued, batcher.wait, executor.checkout,
    # server.encode, server.deliver), recorded where the wait happens, and
    # the buckets of its wall_breakdown folded in at its end (wall_*_s), so
    # that concurrent statements' attribution sums in counters_total
    queued_s: float = 0.0
    batch_wait_s: float = 0.0
    executor_wait_s: float = 0.0
    encode_s: float = 0.0
    deliver_wait_s: float = 0.0
    wall_plan_s: float = 0.0
    wall_split_generation_s: float = 0.0
    wall_h2d_s: float = 0.0
    wall_dispatch_s: float = 0.0
    wall_host_pull_s: float = 0.0
    wall_exchange_wait_s: float = 0.0
    wall_unattributed_s: float = 0.0
    # PR 38: the scan_wait bucket (the consumer's waits on the prefetch queue,
    # spans "scan.wait"), and the CPU seconds of the statement's own thread
    # under its root span (time.thread_time at both ends: beside the wall it
    # tells a thread that computes from one that waits for a device, a queue
    # or the interpreter lock)
    wall_scan_wait_s: float = 0.0
    host_cpu_s: float = 0.0
    # round 19: adaptive execution.  A replan means the statement ran a
    # CORRECTED plan (the advisor's history-backed cardinality/capacity
    # facts re-planned it); a hold means a material misestimate existed but
    # the advisor declined — compile price above the predicted win, unknown
    # price, or a demoted correction cooling down.
    adaptive_replans: int = 0
    adaptive_holds: int = 0
    # round 21: continuous template batching (execution/batcher.py).  Each
    # request served THROUGH a fused same-template batch counts one here —
    # on the driver's counters (which also carry the batch's real device
    # spend) and on every rider's otherwise-empty per-statement snapshot,
    # so per-request accounting sums to the engine totals exactly (device
    # spend folds once, via the driver).
    batched_requests: int = 0
    # round 20: per-shard attribution for the distributed path.  Each entry
    # is one blocking exchange / shard consumer's per-worker load, DERIVED
    # from pulls the exchange already makes (receive cursors, occupancy
    # counts — zero new warm pull sites): {"site", "kind", "op"?, "workers",
    # "rows": [per-worker], "max", "mean", "ratio" (max/mean), "worker"
    # (argmax), "wall_s", "imbalance_s" ((max-mean)/max x wall), "bytes"?,
    # "labels"?}.  Bounded at SHARD_STATS_MAX per counter set (counters_total
    # merges every query forever).
    shard_stats: list = dataclasses.field(default_factory=list)
    # "<operator>/<site>" -> {"dispatches", "transfers", "bytes"} plus any
    # cache keys the site recorded: the attribution EXPLAIN ANALYZE prints
    # and budget failures dump
    sites: dict = dataclasses.field(default_factory=dict)
    dispatch_latency: LatencyHistogram = \
        dataclasses.field(default_factory=LatencyHistogram)

    _INT_FIELDS = ("device_dispatches", "host_transfers", "host_bytes_pulled",
                   "coalesced_splits", "page_cache_hits", "page_cache_misses",
                   "page_cache_bytes_saved", "build_cache_hits",
                   "result_cache_hits", "result_cache_misses",
                   "result_cache_bytes_saved",
                   "faults_injected", "task_retries",
                   "spilled_bytes", "spill_tier_hbm", "spill_tier_host",
                   "spill_tier_disk", "admission_queued",
                   "plan_template_hits", "plan_template_misses",
                   "compiles", "adaptive_replans", "adaptive_holds",
                   "batched_requests", "compile_cache_misses",
                   "compactions", "compact_lanes_in", "compact_lanes_out",
                   "groupby_slots", "groupby_state_bytes", "groupby_regrows",
                   "groupby_partitioned_passes", "groupby_observed_direct",
                   "join_build_rows",
                   "rows_generated", "join_match_lanes", "join_gather_lanes",
                   "join_hash_probe_lanes", "join_direct_probe_lanes",
                   "join_hash_table_slots", "groupby_insert_lanes",
                   "join_hash_probe_round_lanes",
                   "groupby_insert_round_lanes",
                   "tail_compiled", "tail_eager",
                   "window_kernels", "window_lanes", "window_sort_lanes",
                   "exchange_rows", "exchange_rows_max_shard",
                   "mesh_fragment_hits", "mesh_fragment_compiles",
                   "probe_exchange_rows", "probe_exchange_lanes",
                   "exchange_bytes", "mesh_scan_batches_resident",
                   "mesh_scan_batches_generated",
                   "generator_dispatches")
    _FLOAT_FIELDS = ("compile_s", "queued_s", "batch_wait_s",
                     "executor_wait_s", "encode_s", "deliver_wait_s",
                     "wall_plan_s", "wall_split_generation_s", "wall_h2d_s",
                     "wall_dispatch_s", "wall_host_pull_s",
                     "wall_exchange_wait_s", "wall_unattributed_s",
                     "wall_scan_wait_s", "host_cpu_s")

    def reset(self) -> None:
        for f in self._INT_FIELDS:
            setattr(self, f, 0)
        for f in self._FLOAT_FIELDS:
            setattr(self, f, 0.0)
        self.sites = {}
        self.shard_stats = []
        self.dispatch_latency = LatencyHistogram()

    def merge(self, other: "QueryCounters") -> None:
        for f in self._INT_FIELDS:
            setattr(self, f, getattr(self, f) + getattr(other, f, 0))
        for f in self._FLOAT_FIELDS:
            setattr(self, f, getattr(self, f) + getattr(other, f, 0.0))
        if getattr(other, "shard_stats", None):
            self.shard_stats.extend(dict(r) for r in other.shard_stats)
            del self.shard_stats[:-SHARD_STATS_MAX]
        for key, rec in other.sites.items():
            mine = _site_entry(self.sites, key)
            for k, v in rec.items():  # union of keys: cache sites carry extras
                mine[k] = mine.get(k, 0) + v
        self.dispatch_latency.merge(other.dispatch_latency)

    def merge_dict(self, d: dict) -> None:
        """Fold a JSON counters snapshot (``as_dict`` output — the form worker
        task responses carry over the wire) into this one."""
        if not d:
            return
        for f in self._INT_FIELDS:
            setattr(self, f, getattr(self, f) + int(d.get(f, 0)))
        for f in self._FLOAT_FIELDS:
            setattr(self, f, getattr(self, f) + float(d.get(f, 0.0)))
        for key, rec in (d.get("sites") or {}).items():
            mine = _site_entry(self.sites, str(key))
            for k, v in rec.items():
                # site extras may be float (compile_s) — don't truncate them
                mine[k] = mine.get(k, 0) + (float(v) if isinstance(v, float)
                                            else int(v))
        if d.get("shard_stats"):
            self.shard_stats.extend(dict(r) for r in d["shard_stats"])
            del self.shard_stats[:-SHARD_STATS_MAX]
        lat = d.get("dispatch_latency")
        if lat:
            self.dispatch_latency.merge_dict(lat)

    def snapshot(self) -> "QueryCounters":
        out = QueryCounters()
        for f in self._INT_FIELDS:
            setattr(out, f, getattr(self, f))
        for f in self._FLOAT_FIELDS:
            setattr(out, f, getattr(self, f))
        out.sites = {k: dict(v) for k, v in self.sites.items()}
        out.shard_stats = [dict(r) for r in self.shard_stats]
        out.dispatch_latency = self.dispatch_latency.snapshot()
        return out

    def as_dict(self) -> dict:
        d = {f: getattr(self, f) for f in self._INT_FIELDS}
        for f in self._FLOAT_FIELDS:
            d[f] = getattr(self, f)
        d["sites"] = {k: dict(v) for k, v in self.sites.items()}
        if self.shard_stats:
            d["shard_stats"] = [dict(r) for r in self.shard_stats]
        d["dispatch_latency"] = self.dispatch_latency.as_dict()
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "QueryCounters":
        out = cls()
        out.merge_dict(d)
        return out


_counter_local = threading.local()


def current_counters() -> Optional[QueryCounters]:
    return getattr(_counter_local, "counters", None)


# qid -> [QueryCounters...] currently recording (counters-so-far of RUNNING
# queries): track_counters registers the thread's counters here whenever a
# query scope is active, so /v1/status and system.runtime.queries can show a
# live query's spend without waiting for it to finish
_live_lock = threading.Lock()
_live_counters: dict = {}


@contextlib.contextmanager
def track_counters(counters: QueryCounters):
    """Make ``counters`` the recording target for this thread; on exit the
    previous target (or None) is restored, so nested executions on one
    thread each charge their own counters.  NOTE: plan-time eager subqueries
    run during PLANNING, before the outer executor enters its context — they
    charge the throwaway executor that runs them, not the outer query."""
    prev = getattr(_counter_local, "counters", None)
    _counter_local.counters = counters
    qid = getattr(_counter_local, "query_id", None)
    if qid is not None:
        with _live_lock:
            _live_counters.setdefault(qid, []).append(counters)
    try:
        yield counters
    finally:
        _counter_local.counters = prev
        if qid is not None:
            with _live_lock:
                lst = _live_counters.get(qid)
                if lst is not None:
                    try:
                        lst.remove(counters)
                    except ValueError:
                        pass
                    if not lst:
                        _live_counters.pop(qid, None)


def live_query_counters() -> dict:
    """query_id -> merged counters snapshot (``as_dict`` form) of every
    counter set currently recording for that query.  Poll-grade approximate:
    the owning threads keep incrementing while we read; a racing sites-dict
    insert just skips that query this pass."""
    with _live_lock:
        items = {q: list(v) for q, v in _live_counters.items()}
    out = {}
    for qid, lst in items.items():
        merged = QueryCounters()
        try:
            for c in lst:
                merged.merge(c.snapshot())
        except RuntimeError:  # sites dict resized mid-copy: skip this pass
            continue
        out[qid] = merged.as_dict()
    return out


@contextlib.contextmanager
def query_scope(query_id: str):
    """Tag this thread's boundary records and in-flight entries with the
    executing query/task id (the engine wraps each statement; worker task
    bodies wrap with their task id)."""
    prev = getattr(_counter_local, "query_id", None)
    _counter_local.query_id = query_id
    try:
        yield
    finally:
        _counter_local.query_id = prev


def current_query_id() -> Optional[str]:
    return getattr(_counter_local, "query_id", None)


@contextlib.contextmanager
def operator_scope(label: str, sink: Optional[dict] = None):
    """Attribute every dispatch/pull recorded on this thread to ``label``
    until exit (innermost scope wins — pipeline-breaker granularity, same as
    executor stats: a streaming chain's dispatches charge the sink driving
    it).  ``sink`` additionally accumulates {"dispatches","transfers","bytes"}
    in place — the executor hands the per-plan-node record EXPLAIN ANALYZE
    renders."""
    prev = getattr(_counter_local, "op", None)
    _counter_local.op = (label, sink)
    try:
        yield sink
    finally:
        _counter_local.op = prev


def full_site_label(site: str) -> str:
    """The "<Op>#<k>/<site>" form of a bare site tag — the label the
    in-flight registry shows and fault-rule site globs may address.  Bare
    when no operator scope is active on this thread (producer threads,
    engine-level pulls)."""
    op = getattr(_counter_local, "op", None)
    return f"{op[0]}/{site}" if op is not None else site


def _attribute(site: Optional[str], dispatches=0, transfers=0, nbytes=0):
    """Charge one record to the active op scope's sink and the counters' site
    table under "<op>/<site>"."""
    c = getattr(_counter_local, "counters", None)
    op = getattr(_counter_local, "op", None)
    tag = site or "untagged"
    if c is not None:
        key = f"{op[0]}/{tag}" if op is not None else tag
        rec = _site_entry(c.sites, key)
        rec["dispatches"] += dispatches
        rec["transfers"] += transfers
        rec["bytes"] += nbytes
    if op is not None and op[1] is not None:
        sink = op[1]
        sink["dispatches"] = sink.get("dispatches", 0) + dispatches
        sink["transfers"] = sink.get("transfers", 0) + transfers
        sink["bytes"] = sink.get("bytes", 0) + nbytes


def record_dispatch(n: int = 1, site: Optional[str] = None,
                    seconds: Optional[float] = None) -> None:
    c = getattr(_counter_local, "counters", None)
    if c is not None:
        c.device_dispatches += n
        if seconds is not None:
            c.dispatch_latency.record(seconds)
    _attribute(site, dispatches=n)
    if seconds is not None:
        tr = current_tracer()
        if tr is not None:
            # synthesized span per dispatch: the "each coalesced dispatch
            # group is a span" view — a batched jit invocation IS one dispatch
            tr.add_completed("dispatch", seconds, site=site or "")


def record_host_pull(nbytes: int, transfers: int = 1,
                     site: Optional[str] = None) -> None:
    c = getattr(_counter_local, "counters", None)
    if c is not None:
        c.host_transfers += transfers
        c.host_bytes_pulled += nbytes
    _attribute(site, transfers=transfers, nbytes=nbytes)


def record_coalesced(n_splits: int) -> None:
    c = getattr(_counter_local, "counters", None)
    if c is not None:
        c.coalesced_splits += n_splits


def record_compaction(lanes_in: int, lanes_out: int) -> None:
    c = getattr(_counter_local, "counters", None)
    if c is not None:
        c.compactions += 1
        c.compact_lanes_in += lanes_in
        c.compact_lanes_out += lanes_out


def record_tail(compiled: bool) -> None:
    c = getattr(_counter_local, "counters", None)
    if c is not None:
        if compiled:
            c.tail_compiled += 1
        else:
            c.tail_eager += 1


def record_window(lanes: int, sort_passes: int) -> None:
    """One window kernel over a page of ``lanes`` static lanes, whose sort
    permutations took ``sort_passes`` stable argsorts in all."""
    c = getattr(_counter_local, "counters", None)
    if c is not None:
        c.window_kernels += 1
        c.window_lanes += lanes
        c.window_sort_lanes += lanes * sort_passes


def record_join_probe(match_lanes: int, gather_lanes: int) -> None:
    c = getattr(_counter_local, "counters", None)
    if c is not None:
        c.join_match_lanes += match_lanes
        c.join_gather_lanes += gather_lanes


def record_groupby(slots: int = 0, state_bytes: int = 0, regrows: int = 0,
                   partitioned_passes: int = 0, observed_direct: int = 0) -> None:
    c = getattr(_counter_local, "counters", None)
    if c is not None:
        c.groupby_slots += slots
        c.groupby_state_bytes += state_bytes
        c.groupby_regrows += regrows
        c.groupby_partitioned_passes += partitioned_passes
        c.groupby_observed_direct += observed_direct


def record_join_build(rows: int, hash_slots: int = 0) -> None:
    c = getattr(_counter_local, "counters", None)
    if c is not None:
        c.join_build_rows += rows
        c.join_hash_table_slots += hash_slots


def record_probe_lanes(lanes: int, hashed: bool, round_lanes: int = 0) -> None:
    """Static lanes of one dispatch of a join's match or probe step, under the
    loop they went through: a hashed table's open addressing or a direct
    table's one gather (a host int the dispatch site already holds).
    ``round_lanes``: the lanes the hashed lookup's rounds gathered for, where
    the site pulls its rounds (a split join's boundary)."""
    c = getattr(_counter_local, "counters", None)
    if c is not None:
        if hashed:
            c.join_hash_probe_lanes += lanes
            c.join_hash_probe_round_lanes += round_lanes
        else:
            c.join_direct_probe_lanes += lanes


def record_groupby_insert(lanes: int, round_lanes: int = 0) -> None:
    """Static lanes of one dispatch that runs ``hashagg.groupby_insert``;
    ``round_lanes``: the lanes its open-addressing rounds ran over, where the
    site pulls them (a hash-mode group-by's insert loop)."""
    c = getattr(_counter_local, "counters", None)
    if c is not None:
        c.groupby_insert_lanes += lanes
        c.groupby_insert_round_lanes += round_lanes


def record_rows_generated(rows: int) -> None:
    c = getattr(_counter_local, "counters", None)
    if c is not None:
        c.rows_generated += rows


def record_generate(table: str, seconds: float, count: bool = True) -> None:
    """One launch of a connector's page generator, measured where the
    executor calls it (exec/boundary._generate): the count (a scan source's
    launches; not a warm thread's), and a finished ``generate`` span (bucket
    split_generation) under the thread's current span, the prefetch
    producer's ``prefetch`` span mostly."""
    c = getattr(_counter_local, "counters", None)
    if c is not None and count:
        c.generator_dispatches += 1
    tr = current_tracer()
    if tr is not None:
        tr.add_completed("generate", seconds, site="generate." + table)


def record_mesh_fragment(hit: bool) -> None:
    """One lookup of a kept mesh fragment (exec/distributed.py): served, or
    compiled now."""
    c = getattr(_counter_local, "counters", None)
    if c is not None:
        if hit:
            c.mesh_fragment_hits += 1
        else:
            c.mesh_fragment_compiles += 1


def record_probe_exchange(rows: int, lanes: int, row_bytes: int = 0) -> None:
    """One run of a mesh fragment's probe exchange (exec/distributed.py): the
    rows it routed, the lanes its receive tensors held and the width of a
    routed row."""
    c = getattr(_counter_local, "counters", None)
    if c is not None:
        c.probe_exchange_rows += rows
        c.probe_exchange_lanes += lanes
        c.exchange_bytes += rows * row_bytes


def record_mesh_scan_batch(resident: bool) -> None:
    """One batch of a sharded scan handed to a mesh step: from the page
    cache's entry, or generated for it."""
    c = getattr(_counter_local, "counters", None)
    if c is not None:
        if resident:
            c.mesh_scan_batches_resident += 1
        else:
            c.mesh_scan_batches_generated += 1


def _attribute_extra(site: Optional[str], **extras) -> None:
    """Charge non-boundary extras (cache hits/misses/bytes saved) to the
    active op scope's site record and boundary sink — same "<op>/<site>" key
    shape as dispatches, extra keys alongside them."""
    c = getattr(_counter_local, "counters", None)
    op = getattr(_counter_local, "op", None)
    tag = site or "untagged"
    if c is not None:
        key = f"{op[0]}/{tag}" if op is not None else tag
        rec = _site_entry(c.sites, key)
        for k, v in extras.items():
            rec[k] = rec.get(k, 0) + v
    if op is not None and op[1] is not None:
        sink = op[1]
        for k, v in extras.items():
            sink[k] = sink.get(k, 0) + v


def record_page_cache(hits: int = 0, misses: int = 0, bytes_saved: int = 0,
                      site: Optional[str] = None, over_cap: int = 0,
                      store_failed: int = 0) -> None:
    """One buffer-pool page-tier lookup outcome (recorded on the QUERY
    thread — the scan page source resolves the cache before any prefetch
    thread starts, so these never race the thread-local counters).
    ``over_cap`` and ``store_failed`` are why a scan that missed is not
    resident afterwards: its entry passes the pool's entry cap and it streams,
    or the pool refused the finished entry (the mesh's sharded scans record
    both; a site that records neither keeps its three keys)."""
    c = getattr(_counter_local, "counters", None)
    if c is not None:
        c.page_cache_hits += hits
        c.page_cache_misses += misses
        c.page_cache_bytes_saved += bytes_saved
    why = {k: v for k, v in (("page_cache_over_cap", over_cap),
                             ("page_cache_store_failed", store_failed)) if v}
    _attribute_extra(site, page_cache_hits=hits, page_cache_misses=misses,
                     page_cache_bytes_saved=bytes_saved, **why)


def record_build_cache(hits: int = 0, misses: int = 0,
                       site: Optional[str] = None) -> None:
    """One buffer-pool build-tier lookup outcome."""
    c = getattr(_counter_local, "counters", None)
    if c is not None:
        c.build_cache_hits += hits
    _attribute_extra(site, build_cache_hits=hits, build_cache_misses=misses)


def record_result_cache(hits: int = 0, misses: int = 0, bytes_saved: int = 0,
                        site: Optional[str] = None) -> None:
    """One result-tier lookup outcome (round 12).  Hits record on a fresh
    per-statement QueryCounters the engine accounts directly — a served
    statement never enters the executor path, so there is no executor
    counter set to attribute to; misses are stamped onto the statement's
    snapshot post-execution (engine._execute_admitted), same pattern as
    admission_queued."""
    c = getattr(_counter_local, "counters", None)
    if c is not None:
        c.result_cache_hits += hits
        c.result_cache_misses += misses
        c.result_cache_bytes_saved += bytes_saved
    _attribute_extra(site, result_cache_hits=hits, result_cache_misses=misses,
                     result_cache_bytes_saved=bytes_saved)


def record_fault(site: Optional[str] = None) -> None:
    """One fault-injector firing (execution/faults) — attributed like cache
    events so EXPLAIN ANALYZE's site table names where the chaos landed."""
    c = getattr(_counter_local, "counters", None)
    if c is not None:
        c.faults_injected += 1
    _attribute_extra(site, faults_injected=1)


SPILL_TIERS = ("hbm", "host", "disk")  # the ladder's tier vocabulary: the
# spill_tier_<t> counter fields and the /v1/metrics tier labels


def record_spill(tier: str, nbytes: int, site: Optional[str] = None) -> None:
    """One tiered-spill chunk admission (exec/spill): ``nbytes`` landed in
    ``tier`` (one of SPILL_TIERS).  Attributed like boundary records so
    EXPLAIN ANALYZE's site table names which operator spilled where.
    NOTE the admission_queued counter has no record_ helper on purpose: the
    deferral happens before any counters context exists, so the engine
    stamps it onto the finished query's snapshot directly (execute_sql)."""
    c = getattr(_counter_local, "counters", None)
    if c is not None:
        c.spilled_bytes += nbytes
        field = f"spill_tier_{tier}"
        setattr(c, field, getattr(c, field, 0) + nbytes)
    _attribute_extra(site or f"spill.{tier}", spilled_bytes=nbytes)


def record_task_retry(n: int = 1, site: Optional[str] = None) -> None:
    """A task retry/re-dispatch charged to the query that paid for it (FTE
    retry loop, coordinator task reassignment)."""
    c = getattr(_counter_local, "counters", None)
    if c is not None:
        c.task_retries += n
    _attribute_extra(site, task_retries=n)


# -- shard skew (round 20) -----------------------------------------------------
#
# Per-shard attribution for the distributed path: on an SPMD machine
# wall-clock is set by the SLOWEST shard, and the per-worker load that
# decides it ALREADY crosses the host boundary — receive cursors at
# dist.exchange.flags / dist.stream.flags, live-group occupancy at
# dist.agg.overflow.  These helpers fold those host-side ints into
# QueryCounters.shard_stats records (zero new pulls, zero device work);
# the exchange wall comes from a host perf_counter around the batch loop,
# so local statements and disarmed paths pay nothing.

SHARD_STATS_MAX = 64  # records retained per counter set: counters_total
# merges every query forever, so the list must be bounded (newest win)


def shard_skew(per_worker) -> dict:
    """Summarize a per-worker load vector (host ints — NEVER device arrays)
    into the skew core every ShardStats record shares: max/mean ratio and
    the argmax worker.  Empty or all-zero vectors read as balanced (1.0x)."""
    vals = [int(v) for v in per_worker]
    n = len(vals)
    mx = max(vals) if vals else 0
    mean = (sum(vals) / n) if n else 0.0
    ratio = (mx / mean) if mean > 0 else 1.0
    worker = vals.index(mx) if vals else 0
    return {"workers": n, "rows": vals, "max": mx, "mean": mean,
            "ratio": ratio, "worker": worker}


def record_shard_stats(site: str, per_worker, wall_s: float = 0.0,
                       kind: str = "exchange", op: Optional[str] = None,
                       bytes_per_row: Optional[int] = None,
                       labels=None) -> Optional[dict]:
    """One blocking exchange / shard consumer's per-worker load, derived
    from pulls the caller already made.  imbalance_s estimates the wall the
    skew cost: the span ran at the slowest shard's pace, so a perfectly
    rebalanced run would take mean/max of it — (max-mean)/max x wall is the
    recoverable slice.  Returns the record (also appended to the current
    query's counters) so callers can key it by plan node."""
    rec = shard_skew(per_worker)
    rec["site"] = site
    rec["kind"] = kind
    if op:
        rec["op"] = op
    rec["wall_s"] = float(wall_s)
    mx, mean = rec["max"], rec["mean"]
    rec["imbalance_s"] = ((mx - mean) / mx * float(wall_s)) if mx > 0 else 0.0
    if bytes_per_row:
        rec["bytes"] = [int(v) * int(bytes_per_row) for v in rec["rows"]]
    if labels:
        rec["labels"] = list(labels)
    c = getattr(_counter_local, "counters", None)
    if c is not None:
        c.shard_stats.append(dict(rec))
        del c.shard_stats[:-SHARD_STATS_MAX]
        if kind in ("exchange", "occupancy"):
            # what an all-to-all delivered: receive cursors, or the groups
            # each worker owns after the merge exchange
            c.exchange_rows += int(sum(rec["rows"]))
            c.exchange_rows_max_shard += int(mx)
            c.exchange_bytes += int(sum(rec.get("bytes", ())))
    return rec


# -- compile observatory -------------------------------------------------------
#
# Round 17.  XLA compilation is the dominant cold-path cost and was
# invisible: it hid inside the first dispatch span, inflated the
# device_dispatch wall bucket, and forced the round-8 "pick STALL_S well
# above cold-compile time" footgun.  The _jit chokepoint now detects a
# first-seen arg signature per wrapper (a host-side set lookup — zero
# dispatches, zero pulls) and records one compile event here: per-query
# counters + site attribution, a "compile" span the wall decomposition
# charges ABOVE device_dispatch, and the process-global CompileLog census
# (system.runtime.compilations, GET /v1/compiles, /v1/metrics) with
# recompile-storm detection.  The authoritative duration comes from jax's
# monitoring events (/jax/core/compile/* — trace, MLIR lowering, backend
# compile) captured thread-locally while the first-seen dispatch runs; the
# fallback is the dispatch's own wall.


def arg_signature(args, kw=None):
    """Hashable key of a call's ABSTRACT argument signature — pytree
    structure plus per-leaf shape/dtype (arrays) or value (hashable
    scalars/statics).  Two calls with equal keys re-use one XLA executable
    under jax.jit's caching rules; a first-seen key per wrapper is a
    compile.  Host-side only — never touches array contents — and runs on
    EVERY dispatch, so it builds no strings (``signature_summary`` renders
    the printable form lazily, cold-path only)."""
    try:
        import jax

        leaves, treedef = jax.tree_util.tree_flatten((args, kw or {}))
    except Exception:
        return ("opaque",)
    key: list = []
    for x in leaves:
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is not None and dtype is not None:
            # (the dtype itself, not its name: hashable, and naming it was a
            # quarter of a warm dispatch's host time)
            key.append(("a", tuple(shape), dtype))
        elif isinstance(x, (bool, int, float, str, bytes, type(None))):
            key.append(("v", x))
        else:
            # opaque static (frozen dataclass, Schema, ...): hash when
            # hashable, else collapse to the type name — a coarser key only
            # under-reports compiles, it never fabricates them
            try:
                key.append(("h", type(x).__name__, hash(x)))
            except TypeError:
                key.append(("t", type(x).__name__))
    return (treedef, tuple(key))


def signature_summary(sig_key) -> str:
    """Printable form of an ``arg_signature`` key ("int64[2097152], 4, ...")
    — rendered ONLY when a compile is actually recorded, never on the warm
    per-dispatch path."""
    if not isinstance(sig_key, tuple) or len(sig_key) != 2:
        return "opaque"
    parts: list = []
    leaves = sig_key[1]
    for leaf in leaves[:12]:
        if leaf[0] == "a":
            parts.append(f"{leaf[2]}[{','.join(map(str, leaf[1]))}]")
        elif leaf[0] == "v":
            parts.append(repr(leaf[1])[:24])
        else:
            parts.append(leaf[1])
    if len(leaves) > 12:
        parts.append(f"... {len(leaves) - 12} more")
    return ", ".join(parts) or "()"


# thread-local accumulator for jax compile-event durations: jax compiles on
# the CALLING thread, synchronously inside the jitted call, so capturing on
# the dispatching thread correlates the XLA durations with exactly the
# in-flight entry that triggered them
_compile_capture_tls = threading.local()
_COMPILE_LISTENER = {"installed": False, "failed": False}
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class _CompileCapture:
    """One dispatch's captured compile events: the summed phase seconds, and
    how many programs reached the backend-compile step against how many of
    those the persistent cache served."""

    __slots__ = ("seconds", "backend_compiles", "cache_hits")

    def __init__(self):
        self.seconds = 0.0
        self.backend_compiles = 0
        self.cache_hits = 0


def _on_compile_event(event: str, duration_s: float, **kw) -> None:
    # EXACT phase-event family only (trace, MLIR lowering, backend
    # compile).  A substring match would also catch
    # /jax/compilation_cache/compile_time_saved_sec — time SAVED by a
    # persistent-cache hit, not time spent — and stamp a phantom ~110s
    # compile on a 100ms cache-served dispatch.
    if not event.startswith("/jax/core/compile/"):
        return
    acc = getattr(_compile_capture_tls, "acc", None)
    if acc is not None:
        acc.seconds += duration_s
        # the backend-compile event wraps compile_or_get_cached: it fires
        # for a persistent-cache serve too, which the hit event tells apart
        if event == _BACKEND_COMPILE_EVENT:
            acc.backend_compiles += 1


def _on_cache_event(event: str, **kw) -> None:
    if event == _CACHE_HIT_EVENT:
        acc = getattr(_compile_capture_tls, "acc", None)
        if acc is not None:
            acc.cache_hits += 1


def install_compile_listener() -> bool:
    """Idempotently register the jax.monitoring listeners (the
    /jax/core/compile/* durations and the persistent cache's hit event).
    Called once at the _jit module's import; safe without jax (returns
    False, captures fall back to span wall)."""
    if _COMPILE_LISTENER["installed"]:
        return True
    if _COMPILE_LISTENER["failed"]:
        return False
    try:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(
            _on_compile_event)
        jax.monitoring.register_event_listener(_on_cache_event)
        _COMPILE_LISTENER["installed"] = True
        return True
    except Exception:
        _COMPILE_LISTENER["failed"] = True
        return False


def begin_compile_capture():
    """Start accumulating this thread's jax compile events; returns an
    opaque token for end_compile_capture.  Nestable (inner capture wins
    its own events — jit-of-jit compiles charge the innermost dispatch)."""
    prev = getattr(_compile_capture_tls, "acc", None)
    acc = _CompileCapture()
    _compile_capture_tls.acc = acc
    return prev, acc


def end_compile_capture(token) -> Optional[float]:
    """Stop the capture and return the summed XLA-reported compile seconds,
    or None when nothing was captured — listener unavailable OR zero events
    fired (event names drifted in a jax upgrade, persistent-cache serve
    without events).  None means the caller falls back to the dispatch
    wall; returning 0.0 here would silently zero the compile bucket and
    re-inflate device_dispatch, the exact misattribution this round
    fixes."""
    prev, acc = token
    _compile_capture_tls.acc = prev
    if not _COMPILE_LISTENER["installed"]:
        return None
    return acc.seconds or None


def compile_capture_misses(token) -> int:
    """Programs the capture saw XLA really compile: backend-compile events
    less the ones the persistent compilation cache served."""
    acc = token[1]
    return max(acc.backend_compiles - acc.cache_hits, 0)


def record_compile(seconds: float, site: Optional[str] = None,
                   signature: Optional[str] = None,
                   sig_key: Optional[str] = None,
                   exe_bytes: Optional[int] = None,
                   wrapper: Optional[int] = None,
                   cache_misses: int = 0) -> None:
    """One observed XLA compilation REQUEST (first-seen arg signature at a
    _jit wrapper; ``cache_misses`` of its programs were really compiled, the
    rest came from the persistent cache): per-query counters + "<op>/<site>" attribution, a "compile"
    span for the wall decomposition (priority above device_dispatch), and
    the process-global CompileLog census.  Host-side bookkeeping only — the
    budget suite runs with all of this enabled and its ceilings are
    unchanged."""
    c = getattr(_counter_local, "counters", None)
    if c is not None:
        c.compiles += 1
        c.compile_s += seconds
        c.compile_cache_misses += cache_misses
    _attribute_extra(site, compiles=1, compile_s=round(seconds, 6))
    tr = current_tracer()
    if tr is not None and seconds > 0:
        tr.add_completed("compile", seconds, site=site or "")
    COMPILE_LOG.record(site=site or "jit", label=full_site_label(site or "jit"),
                       query_id=getattr(_counter_local, "query_id", None),
                       signature=signature, sig_key=sig_key,
                       duration_s=seconds, exe_bytes=exe_bytes,
                       wrapper=wrapper)


DEFAULT_COMPILE_LOG_RECORDS = 512
DEFAULT_STORM_SIGNATURES = 8


class CompileLog:
    """Process-global bounded ring of per-compilation records — the
    executable cost census behind ``system.runtime.compilations``,
    ``GET /v1/compiles`` and the ``trino_tpu_compile_*`` metrics.  Each
    record: {site, label ("<Op>#<k>/<site>"), query_id, signature, sig_key,
    duration_s, exe_bytes, at}.  ``TRINO_TPU_COMPILE_LOG`` caps retained
    records (default 512; 0 disables retention — lifetime totals keep
    counting, they are a few ints).  Storm-detection state is FIFO-bounded
    too (``_MAX_SIG_ENTRIES`` wrappers): a long-lived serving process mints
    a fresh wrapper per compiled stream per statement shape, and an
    unbounded map would be a slow process-global leak.

    Recompile-storm detection: ONE compiled stream (a single _jit wrapper,
    identified by the ``wrapper`` token) compiling more than
    ``DEFAULT_STORM_SIGNATURES`` (8) DISTINCT argument
    signatures WITHIN ONE STATEMENT is a storm — shape churn (non-uniform
    splits defeating coalescing, un-quantized size buckets) multiplying
    cold-compile cost — and logs ONE named warning pointing at the
    offending operator site.  The key is (label, wrapper, query_id):
    wrapper keeps "Aggregate#3" labels from different plans from pooling,
    and query_id keeps process-lifetime MODULE-LEVEL wrappers
    (_compact_part_sized, the device TopN) from pooling legitimate shape
    diversity across a heterogeneous workload into a phantom storm — the
    churn signal is per execution, where split non-uniformity lives.
    Cross-execution recompilation of a warm plan is the OTHER detector's
    job (warm ``compiles != 0``, pinned by the budget suite).  Guard
    discipline: ``record`` never raises."""

    def __init__(self, max_records: Optional[int] = None,
                 storm_sigs: Optional[int] = None):
        import os

        def _env_int(name, default):
            try:
                v = os.environ.get(name, "")
                return int(v) if v != "" else default
            except ValueError:
                return default

        self.max_records = max_records if max_records is not None \
            else _env_int("TRINO_TPU_COMPILE_LOG", DEFAULT_COMPILE_LOG_RECORDS)
        self.storm_sigs = storm_sigs if storm_sigs is not None \
            else DEFAULT_STORM_SIGNATURES
        self._lock = threading.Lock()
        from collections import deque

        self._records: deque = deque(maxlen=max(self.max_records, 1))
        self.compiles_total = 0
        self.compile_s_total = 0.0
        self.storms_total = 0
        self.latency = LatencyHistogram(buckets=COMPILE_BUCKETS_S)
        # (label, wrapper, query_id) -> set of distinct signature keys,
        # FIFO-bounded; _stormed holds the keys already warned about
        # (bounded by the same sweep — evicting a finished execution's
        # entry is fine, a storm is a within-execution signal)
        self._sigs: dict = {}
        self._stormed: set = set()

    _MAX_SIG_ENTRIES = 4096  # wrappers tracked for storm detection

    @property
    def enabled(self) -> bool:
        return self.max_records > 0

    def record(self, site: str, label: str, query_id: Optional[str],
               signature: Optional[str], duration_s: float,
               sig_key: Optional[str] = None,
               exe_bytes: Optional[int] = None,
               wrapper: Optional[int] = None) -> Optional[dict]:
        storm_label = None
        try:
            rec = {"site": site, "label": label, "query_id": query_id,
                   "signature": signature, "duration_s": round(duration_s, 6),
                   "exe_bytes": exe_bytes, "at": time.time()}
            skey = (label, wrapper, query_id)
            with self._lock:
                self.compiles_total += 1
                self.compile_s_total += duration_s
                if self.enabled:
                    self._records.append(rec)
                sigs = self._sigs.setdefault(skey, set())
                sigs.add(sig_key if sig_key is not None else signature)
                if len(sigs) > self.storm_sigs \
                        and skey not in self._stormed:
                    self._stormed.add(skey)
                    self.storms_total += 1
                    storm_label = (label, len(sigs))
                # bound the detection state: evict the oldest-inserted
                # wrappers (dict preserves insertion order) and their
                # warned flags
                while len(self._sigs) > self._MAX_SIG_ENTRIES:
                    old = next(iter(self._sigs))
                    del self._sigs[old]
                    self._stormed.discard(old)
            self.latency.record(duration_s)
        except Exception:
            return None  # a census failure never fails the dispatch
        if storm_label is not None:
            _log.warning(
                "recompile storm: site %s has compiled %d distinct argument "
                "signatures — shape churn is defeating executable reuse "
                "(quantize the operator's shapes or check split uniformity)",
                storm_label[0], storm_label[1])
        return rec

    def for_query(self, query_id: str) -> list:
        """Retained records attributed to one query id, oldest first (the
        flight-record feed — a host-side list filter)."""
        with self._lock:
            return [dict(r) for r in self._records
                    if r.get("query_id") == query_id]

    def snapshot(self, limit: Optional[int] = None) -> list:
        with self._lock:
            recs = [dict(r) for r in self._records]
        return recs[-limit:] if limit else recs

    def info(self) -> dict:
        with self._lock:
            return {"enabled": self.enabled,
                    "records": len(self._records),
                    "compiles_total": self.compiles_total,
                    "compile_s_total": round(self.compile_s_total, 6),
                    "storms_total": self.storms_total,
                    "storm_threshold_sigs": self.storm_sigs,
                    "stormed_labels": sorted({k[0] for k in
                                              self._stormed})}

    def clear(self) -> None:
        """Test hook: drop retained records and storm state (lifetime totals
        keep counting — they are Prometheus counters)."""
        with self._lock:
            self._records.clear()
            self._sigs.clear()
            self._stormed.clear()


COMPILE_LOG = CompileLog()


# -- in-flight registry --------------------------------------------------------
#
# The counters/spans above are POST-HOC: a dispatch that never returns leaves
# no record at all — and a `_jit` call stuck for hours while the process
# looks idle is exactly the failure that needs one.  The registry is the ground truth for "what is the
# engine doing RIGHT NOW": every device dispatch, batched host pull,
# split-generation pass and exchange segment records an entry on the way in
# and retires it on the way out (the entry/exit lives INSIDE the _jit/_host
# chokepoints, so the boundary lint that forces all executor code through
# them guarantees registry coverage too).  The stall watchdog samples it;
# /v1/status and worker heartbeats surface it.


# Test hook: when set, called as hook(site_label) inside every in-flight
# dispatch entry BEFORE the compiled function runs — the "deliberately-slowed
# dispatch" the watchdog tests use.  Never set in production.
DISPATCH_TEST_HOOK = None


@dataclasses.dataclass
class InflightEntry:
    token: int
    kind: str  # dispatch | host_pull | generate | split-generation | exchange-segment
    site: str
    op: Optional[str]
    label: str  # "<Op>#<k>/<site>" — same key shape as QueryCounters.sites
    query_id: Optional[str]
    thread_id: int
    thread_name: str
    start_monotonic: float
    # round 17: a first-seen arg signature is (probably) compiling — the
    # stall watchdog judges it against TRINO_TPU_STALL_COMPILE_S instead of
    # STALL_S and verdicts "compiling", not "stalled"
    compiling: bool = False

    def as_dict(self, now: Optional[float] = None) -> dict:
        now = time.monotonic() if now is None else now
        return {"kind": self.kind, "site": self.site, "op": self.op,
                "label": self.label, "query_id": self.query_id,
                "thread_id": self.thread_id, "thread_name": self.thread_name,
                "compiling": self.compiling,
                "elapsed_s": round(now - self.start_monotonic, 4)}


class InflightRegistry:
    """Live entries for work currently inside a device-boundary chokepoint.
    Enter/exit cost is one lock + dict op each (microseconds against the
    >100us a dispatch already costs) and adds NO dispatches or pulls, so the
    warm-path budget ceilings are untouched."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: dict = {}
        self._next = 1

    def enter(self, kind: str, site: Optional[str] = None,
              compiling: bool = False) -> int:
        op = getattr(_counter_local, "op", None)
        tag = site or "untagged"
        label = f"{op[0]}/{tag}" if op is not None else tag
        t = threading.current_thread()
        with self._lock:
            tok = self._next
            self._next += 1
            self._entries[tok] = InflightEntry(
                tok, kind, tag, op[0] if op is not None else None, label,
                getattr(_counter_local, "query_id", None),
                t.ident, t.name, time.monotonic(), compiling)
        return tok

    def exit(self, token: int) -> None:
        with self._lock:
            self._entries.pop(token, None)

    def depth(self) -> int:
        with self._lock:
            return len(self._entries)

    def snapshot(self, now: Optional[float] = None) -> list:
        now = time.monotonic() if now is None else now
        with self._lock:
            entries = sorted(self._entries.values(),
                             key=lambda e: e.start_monotonic)
        return [e.as_dict(now) for e in entries]

    def stalled(self, threshold_s: float, now: Optional[float] = None) -> list:
        """Entries older than ``threshold_s`` (InflightEntry objects)."""
        now = time.monotonic() if now is None else now
        with self._lock:
            return [e for e in self._entries.values()
                    if now - e.start_monotonic >= threshold_s]


INFLIGHT = InflightRegistry()


def current_inflight() -> InflightRegistry:
    """The thread's registry: the process-global INFLIGHT unless a scope
    (an in-process WorkerServer's task body) installed its own."""
    return getattr(_counter_local, "inflight", None) or INFLIGHT


@contextlib.contextmanager
def track_inflight(registry: InflightRegistry):
    """Route this thread's in-flight entries to ``registry`` (worker task
    bodies use their server's own registry so in-process test clusters don't
    share stall state)."""
    prev = getattr(_counter_local, "inflight", None)
    _counter_local.inflight = registry
    try:
        yield registry
    finally:
        _counter_local.inflight = prev


@contextlib.contextmanager
def inflight(kind: str, site: Optional[str] = None):
    """Record one in-flight entry around a potentially-wedging operation
    (split generation, exchange segments; _jit/_host inline the same calls)."""
    reg = current_inflight()
    tok = reg.enter(kind, site)
    try:
        yield
    finally:
        reg.exit(tok)


# -- stall watchdog ------------------------------------------------------------

# one SAMPLING watchdog per registry (round-15 fix for the round-8 hazard):
# two Engines armed via TRINO_TPU_STALL_S in one process would each run a
# watchdog thread over the process-global INFLIGHT registry and cross-report
# each other's queries (duplicate logs, racing last_stall_report, double
# async-kills).  The first start() on a registry owns sampling; a second
# watchdog's start() logs a warning and skips instead of racing.  verdict()
# stays live everywhere — it recomputes from the registry, not the poll.
_ARMED_LOCK = threading.Lock()
_ARMED_WATCHDOGS: dict = {}  # id(registry) -> owning watchdog


class StallKilledError(RuntimeError):
    """Raised (asynchronously) in a thread whose in-flight entry exceeded
    TRINO_TPU_STALL_KILL_S.  Python async exceptions deliver when the
    interpreter resumes — a thread wedged inside one C-level XLA call dies
    the moment the call finally returns, not before."""


def _env_seconds(name: str) -> Optional[float]:
    import os

    try:
        v = float(os.environ.get(name, "") or 0)
    except ValueError:
        return None
    return v if v > 0 else None


class StallWatchdog:
    """Samples an InflightRegistry for entries older than ``stall_s``
    (TRINO_TPU_STALL_S; unset/0 = disabled, the CPU default) and emits a
    structured stall report: the stuck "<Op>#<k>/<site>" labels, elapsed,
    each stuck thread's ``sys._current_frames()`` stack, plus whatever
    ``extra_info`` supplies (memory-pool snapshots).  ``kill_s``
    (TRINO_TPU_STALL_KILL_S) optionally hard-aborts the stuck thread with an
    async StallKilledError.  ``clock`` is injectable for fake-clock tests;
    ``check(now=...)`` runs one sampling pass synchronously.

    Round 17 — compile-aware verdicts: an in-flight dispatch flagged
    ``compiling`` (first-seen arg signature at the _jit chokepoint) is
    judged against ``compile_stall_s`` (TRINO_TPU_STALL_COMPILE_S, default
    10x stall_s) instead of ``stall_s``: past stall_s but under the compile
    threshold it verdicts "compiling" — no stall report, no worker
    degradation — which retires the round-8 "pick STALL_S WELL ABOVE
    cold-compile time" footgun.  A compiling entry past compile_stall_s is
    a genuine wedge and reports stalled like any other."""

    def __init__(self, registry: Optional[InflightRegistry] = None,
                 stall_s: Optional[float] = None,
                 kill_s: Optional[float] = None,
                 poll_s: Optional[float] = None,
                 compile_stall_s: Optional[float] = None,
                 on_stall=None, clock=None, extra_info=None):
        self.registry = registry if registry is not None else INFLIGHT
        self.stall_s = stall_s if stall_s is not None \
            else _env_seconds("TRINO_TPU_STALL_S")
        self.kill_s = kill_s if kill_s is not None \
            else _env_seconds("TRINO_TPU_STALL_KILL_S")
        self.compile_stall_s = compile_stall_s if compile_stall_s is not None \
            else _env_seconds("TRINO_TPU_STALL_COMPILE_S")
        if self.compile_stall_s is None and self.stall_s:
            self.compile_stall_s = 10.0 * self.stall_s
        self.poll_s = poll_s if poll_s is not None else (
            min(max(self.stall_s / 4, 0.05), 1.0) if self.stall_s else 1.0)
        self.on_stall = on_stall
        self.clock = clock or time.monotonic
        self.extra_info = extra_info
        self.last_report: Optional[dict] = None
        self.stalled_now = 0  # gauge: entries over threshold at last check
        self.compiling_now = 0  # gauge: compiling entries past stall_s but
        # under compile_stall_s at last check (verdict "compiling")
        self.reports = 0  # sampling passes that found stalls
        self.kills = 0
        self._killed: set = set()  # entry tokens already async-killed
        self._last_labels: tuple = ()
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()

    @property
    def enabled(self) -> bool:
        return bool(self.stall_s)

    def classify(self, now: Optional[float] = None):
        """(stalled_entries, compiling_entries) live from the registry:
        entries past stall_s split into genuine stalls (not compiling, or
        compiling past compile_stall_s) and tolerated compiles."""
        if not self.enabled:
            return [], []
        now = self.clock() if now is None else now
        compile_s = self.compile_stall_s or self.stall_s
        stalled, compiling = [], []
        for e in self.registry.stalled(self.stall_s, now):
            if getattr(e, "compiling", False) \
                    and now - e.start_monotonic < compile_s:
                compiling.append(e)
            else:
                stalled.append(e)
        return stalled, compiling

    def status(self, now: Optional[float] = None):
        """("ok"|"compiling"|"stalled", stalled_n, compiling_n) recomputed
        LIVE from the registry — THE one place the verdict derivation
        lives; engine and worker health surfaces call this instead of each
        re-deriving it from classify().  "compiling" means everything over
        stall_s is a first-seen-signature dispatch still under the compile
        threshold: slow, expected, NOT a wedge."""
        stalled, compiling = self.classify(now)
        st = "stalled" if stalled else ("compiling" if compiling else "ok")
        return st, len(stalled), len(compiling)

    def verdict(self, now: Optional[float] = None):
        """("ok"|"compiling"|"stalled", count) — the two-tuple form the
        round-8 surfaces were built on; count is the entries behind the
        verdict."""
        st, stalled_n, compiling_n = self.status(now)
        return st, (stalled_n if st == "stalled"
                    else compiling_n if st == "compiling" else 0)

    def check(self, now: Optional[float] = None) -> Optional[dict]:
        """One sampling pass; returns (and stores) the report when any entry
        is genuinely stalled, else None.  Compiling entries under the
        compile threshold never produce a report (they set the compiling
        gauge only)."""
        if not self.enabled:
            return None
        now = self.clock() if now is None else now
        stalled, compiling = self.classify(now)
        self.stalled_now = len(stalled)
        self.compiling_now = len(compiling)
        if not stalled:
            self._last_labels = ()
            return None
        report = self._build_report(stalled, now)
        # context: concurrently-tolerated compiles (they are NOT in the
        # stalled list — a reader should know the engine is also compiling)
        report["compiling"] = self.compiling_now
        self.last_report = report
        self.reports += 1
        labels = tuple(sorted(e.label for e in stalled))
        if labels != self._last_labels:  # log on change, not every poll
            self._last_labels = labels
            _log.warning("stall watchdog: %d in-flight entr%s over %.1fs: %s",
                         len(stalled), "y" if len(stalled) == 1 else "ies",
                         self.stall_s, ", ".join(labels))
        if self.on_stall is not None:
            try:
                self.on_stall(report)
            except Exception:
                pass
        if self.kill_s:
            for e in stalled:
                if now - e.start_monotonic >= self.kill_s \
                        and e.token not in self._killed:
                    self._killed.add(e.token)
                    self._async_kill(e)
        return report

    def _build_report(self, stalled, now: float) -> dict:
        import sys
        import traceback

        frames = sys._current_frames()
        entries = []
        for e in sorted(stalled, key=lambda x: x.start_monotonic):
            f = frames.get(e.thread_id)
            d = e.as_dict(now)
            d["stack"] = "".join(traceback.format_stack(f)) \
                if f is not None else None
            entries.append(d)
        report = {"detected_at_s": time.time(),
                  "threshold_s": self.stall_s,
                  "stalled": entries,
                  "inflight_depth": self.registry.depth()}
        if self.extra_info is not None:
            try:
                report.update(self.extra_info() or {})
            except Exception:
                pass
        return report

    def _async_kill(self, entry: InflightEntry) -> None:
        import ctypes

        self.kills += 1
        _log.error("stall watchdog: hard-aborting thread %s (%s, wedged "
                   "past %.1fs kill threshold)", entry.thread_name,
                   entry.label, self.kill_s)
        try:
            ctypes.pythonapi.PyThreadState_SetAsyncExc(
                ctypes.c_long(entry.thread_id),
                ctypes.py_object(StallKilledError))
        except Exception:
            pass

    def start(self) -> None:
        if not self.enabled or self._thread is not None:
            return
        with _ARMED_LOCK:
            owner = _ARMED_WATCHDOGS.get(id(self.registry))
            if owner is not None and owner is not self:
                # second armed watchdog over the SAME registry (two env-armed
                # Engines in one process): skip sampling instead of racing —
                # the owner reports for everyone, and this instance's
                # verdict()/health surfaces still recompute live
                _log.warning(
                    "stall watchdog: registry already sampled by another "
                    "watchdog in this process; skipping (one armed Engine "
                    "per process samples the global registry)")
                return
            _ARMED_WATCHDOGS[id(self.registry)] = self
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop,
                                        name="stall-watchdog", daemon=True)
        self._thread.start()

    def _loop(self) -> None:
        while not self._stop.wait(self.poll_s):
            try:
                self.check()
            except Exception:  # a watchdog crash must never take the engine
                pass

    def stop(self) -> None:
        with _ARMED_LOCK:
            if _ARMED_WATCHDOGS.get(id(self.registry)) is self:
                del _ARMED_WATCHDOGS[id(self.registry)]
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=2.0)


@dataclasses.dataclass
class Span:
    name: str
    trace_id: str
    span_id: int
    parent_id: Optional[int]
    start_s: float
    end_s: Optional[float] = None
    attributes: dict = dataclasses.field(default_factory=dict)
    status: str = "OK"

    @property
    def duration_s(self) -> Optional[float]:
        return None if self.end_s is None else self.end_s - self.start_s


def annotate(name: str, query_id: Optional[str] = None):
    """``jax.profiler.TraceAnnotation("trino_tpu:<name>", query_id=...)``: the
    program's span on the profiler's clock, on this thread's line of a traced
    run's host plane.  One atomic flag test when no profiler session is
    active."""
    if jax is None:
        return contextlib.nullcontext()
    if query_id is None:
        query_id = getattr(_counter_local, "query_id", None)
    return jax.profiler.TraceAnnotation("trino_tpu:" + name,
                                        query_id=query_id or "")


class Tracer:
    """In-memory span sink.  Finished spans are indexed by trace id (a
    statement's tree is read back at its end and by the trace endpoint), and
    the ``max_finished`` bound evicts whole traces, oldest first."""

    def __init__(self, max_finished: int = 10_000):
        self._lock = threading.Lock()
        self._next_id = 1
        self.max_finished = max_finished
        self._by_trace: dict = {}  # trace id -> [Span...], oldest trace first
        self._count = 0
        self._local = threading.local()

    def clear(self) -> None:
        with self._lock:
            self._by_trace.clear()
            self._count = 0

    def _current(self) -> Optional[Span]:
        return getattr(self._local, "span", None)

    def current(self) -> Optional[Span]:
        """The span active on THIS thread (explicit parent handoff for
        background threads: capture on the owning thread, pass ``parent=``)."""
        return self._current()

    def _new_id(self) -> int:
        with self._lock:
            sid = self._next_id
            self._next_id += 1
            return sid

    def _finish(self, s: Span) -> None:
        with self._lock:
            self._by_trace.setdefault(s.trace_id, []).append(s)
            self._count += 1
            while self._count > self.max_finished:
                oldest = next(iter(self._by_trace))
                spans = self._by_trace[oldest]
                if len(self._by_trace) > 1:
                    del self._by_trace[oldest]
                    self._count -= len(spans)
                else:  # one trace alone over the bound: its oldest spans go
                    drop = self._count - self.max_finished
                    del spans[:drop]
                    self._count -= drop

    @contextlib.contextmanager
    def span(self, name: str, trace_id: str = "", parent: Optional[Span] = None,
             **attributes):
        """Open a child span of ``parent`` (explicit, for cross-thread
        parenting) or of this thread's current span.  Parenting used to be
        thread-local ONLY, so a prefetch/producer thread's spans were orphans;
        background-thread sites must pass the parent captured on the query
        thread.  The span is also a ``trino_tpu:<name>`` annotation of the
        profiler, when one is recording."""
        if parent is None:
            parent = self._current()
        s = Span(name=name,
                 trace_id=trace_id or (parent.trace_id if parent else ""),
                 span_id=self._new_id(),
                 parent_id=parent.span_id if parent else None,
                 start_s=time.time(), attributes=dict(attributes))
        prev = self._current()
        self._local.span = s
        try:
            with annotate(name, s.trace_id):
                yield s
        except BaseException as e:
            s.status = f"ERROR: {type(e).__name__}"
            raise
        finally:
            s.end_s = time.time()
            self._local.span = prev
            self._finish(s)

    def add_completed(self, name: str, duration_s: float,
                      parent: Optional[Span] = None,
                      end_s: Optional[float] = None, **attributes) -> Span:
        """Record an already-measured interval as a finished span ending now,
        or at ``end_s`` (the dispatch-span fast path: no context manager in
        the hot loop; the server's phases, appended after the fact)."""
        if parent is None:
            parent = self._current()
        end = time.time() if end_s is None else end_s
        s = Span(name=name,
                 trace_id=parent.trace_id if parent else "",
                 span_id=self._new_id(),
                 parent_id=parent.span_id if parent else None,
                 start_s=end - duration_s, end_s=end,
                 attributes=dict(attributes))
        self._finish(s)
        return s

    def spans_for(self, trace_id: str) -> list[Span]:
        with self._lock:
            return list(self._by_trace.get(trace_id, ()))


# -- tracer activation ---------------------------------------------------------
#
# The engine owns the Tracer; executors/exchanges are engine-agnostic.  The
# query thread ACTIVATES the engine's tracer for the duration of a statement,
# and any code on that thread (or handed a parent span explicitly) can open
# child spans through it.  Inactive (bare-executor tests, bench loops that
# opt out) means maybe_span/no-op — zero span overhead.


def current_tracer() -> Optional[Tracer]:
    return getattr(_counter_local, "tracer", None)


@contextlib.contextmanager
def activate_tracer(tracer: Tracer):
    prev = getattr(_counter_local, "tracer", None)
    _counter_local.tracer = tracer
    try:
        yield tracer
    finally:
        _counter_local.tracer = prev


@contextlib.contextmanager
def maybe_span(name: str, parent: Optional[Span] = None, **attributes):
    """Child span via the thread's active tracer, or a no-op span when none is
    active.  ``parent`` crosses threads (capture with tracer.current() on the
    owning thread)."""
    tr = current_tracer()
    if tr is None:
        yield Span(name, "", 0, None, time.time())
        return
    with tr.span(name, parent=parent, **attributes) as s:
        yield s


# -- wait states ---------------------------------------------------------------
#
# Where a statement waits for something other than its own work (the server's
# dispatch pool, a batcher lane, the executor semaphore) the wait is measured
# where it happens: two perf_counter reads, written as a span of the statement
# and as seconds on one float field of its QueryCounters.  The waits precede
# or outlive the executor's counters context, so they gather in a per-thread
# dict that ``Engine.execute_sql`` opens and folds into the statement's
# snapshot and the engine totals at its end (the admission_queued pattern).


@contextlib.contextmanager
def statement_waits():
    """Gather this thread's wait seconds by counter field for one statement."""
    prev = getattr(_counter_local, "waits", None)
    waits: dict = {}
    _counter_local.waits = waits
    try:
        yield waits
    finally:
        _counter_local.waits = prev


def record_wait(name: str, field: str, seconds: float,
                end_s: Optional[float] = None, **attributes) -> None:
    """One measured wait: a finished span ``name`` (ending now, or at
    ``end_s``) under the thread's current span, and ``seconds`` on the
    statement's ``field`` counter."""
    tr = current_tracer()
    if tr is not None:
        tr.add_completed(name, seconds, end_s=end_s, **attributes)
    waits = getattr(_counter_local, "waits", None)
    if waits is not None:
        waits[field] = waits.get(field, 0.0) + seconds


@contextlib.contextmanager
def wait_span(name: str, field: str, **attributes):
    """Measure the body as a wait state (``record_wait``), annotated on the
    profiler's timeline while it lasts."""
    t0 = time.perf_counter()
    try:
        with annotate(name):
            yield
    finally:
        record_wait(name, field, time.perf_counter() - t0, **attributes)


@contextlib.contextmanager
def accepted_scope(accepted_pc: float, **attributes):
    """The front end's handoff to ``Engine.execute_sql`` on this thread: the
    ``perf_counter`` reading at which it accepted the statement (``queued_s``
    runs from there to the root span's start) and the attributes its
    ``server.queued`` span carries (the server's own query id)."""
    prev = getattr(_counter_local, "accepted", None)
    _counter_local.accepted = (accepted_pc, attributes)
    try:
        yield
    finally:
        _counter_local.accepted = prev


def take_accepted():
    """The pending handoff, once: a statement nested in this one on the same
    thread starts its own queue phase."""
    accepted = getattr(_counter_local, "accepted", None)
    _counter_local.accepted = None
    return accepted


# -- device program names ------------------------------------------------------


def site_program(fn, site: str):
    """``fn`` as jax.jit should see it: named after its call site, so that the
    XLA module is ``jit_<site>`` (dots to underscores: ``join.probe`` ->
    ``jit_join_probe``, which the device plane's ``XLA Modules`` line and every
    op's module stat print), with its body under ``jax.named_scope(site)`` so
    op metadata carries the site too.  The STATIC site only, never the
    per-plan operator label: the lowered text, and with it every compile-cache
    key, is the same for every plan that uses the site."""
    @functools.wraps(fn)
    def program(*args, **kwargs):
        with jax.named_scope(site):
            return fn(*args, **kwargs)

    program.__name__ = program.__qualname__ = \
        re.sub(r"\W", "_", site).strip("_") or "jit"
    return program


# -- export --------------------------------------------------------------------
def span_dict(s: Span) -> dict:
    """JSON-ready span summary (engine.last_query_trace, worker task
    responses)."""
    return {"name": s.name, "trace_id": s.trace_id, "span_id": s.span_id,
            "parent_id": s.parent_id, "start_s": s.start_s, "end_s": s.end_s,
            "duration_s": s.duration_s, "attributes": dict(s.attributes),
            "status": s.status}


def _otlp_value(v):
    if isinstance(v, bool):
        return {"boolValue": v}
    if isinstance(v, int):
        return {"intValue": str(v)}
    if isinstance(v, float):
        return {"doubleValue": v}
    return {"stringValue": str(v)}


def spans_to_otlp(spans, service: str = "trino_tpu") -> dict:
    """OTLP/JSON-shaped trace payload (opentelemetry-proto trace/v1 field
    names) from Span objects or span_dict dicts — what
    ``GET /v1/query/{id}/trace`` serves, consumable by any OTLP JSON viewer."""
    import hashlib

    out = []
    for s in spans:
        d = s if isinstance(s, dict) else span_dict(s)
        trace_hex = hashlib.md5(
            str(d.get("trace_id", "")).encode()).hexdigest()
        end_s = d.get("end_s") or d.get("start_s", 0.0)
        out.append({
            "traceId": trace_hex,
            "spanId": f"{int(d.get('span_id', 0)):016x}",
            "parentSpanId": ("" if d.get("parent_id") is None
                             else f"{int(d['parent_id']):016x}"),
            "name": d.get("name", ""),
            "kind": 1,  # SPAN_KIND_INTERNAL
            "startTimeUnixNano": str(int(d.get("start_s", 0.0) * 1e9)),
            "endTimeUnixNano": str(int(end_s * 1e9)),
            "attributes": [{"key": k, "value": _otlp_value(v)}
                           for k, v in (d.get("attributes") or {}).items()],
            "status": ({"code": 1} if d.get("status", "OK") == "OK"
                       else {"code": 2, "message": str(d.get("status"))}),
        })
    return {"resourceSpans": [{
        "resource": {"attributes": [
            {"key": "service.name", "value": {"stringValue": service}}]},
        "scopeSpans": [{"scope": {"name": "trino_tpu.execution.tracing"},
                        "spans": out}],
    }]}


# -- wall-clock decomposition --------------------------------------------------
#
# Until round 16 nothing decomposed one query's wall into its causes
# (dispatch launches vs host pulls vs generation vs compile).  ``wall_breakdown`` attributes the query root span's
# window to named buckets from the finished span tree: each leaf span maps to
# a bucket (dispatch -> device_dispatch, host_pull -> host_pull, ...) and a
# sweep over the elementary time slices charges every covered slice to ONE
# bucket (foreground work outranks overlapped background staging — the
# prefetch double buffer h2d-stages WHILE the device executes, and time the
# device was busy anyway is not h2d cost).  Buckets are therefore DISJOINT
# and sum (with admission queue, retry backoff and the unattributed
# remainder) to the reported wall exactly — the property the acceptance
# criterion pins within 5%.

WALL_BUCKETS = ("plan", "compile", "admission_queue", "split_generation",
                "h2d", "device_dispatch", "host_pull", "scan_wait",
                "exchange_wait", "retry_backoff", "unattributed")

# span name -> bucket.  Container spans (query/execution/task) and
# unrecognized names stay out of the sweep: their time is the sum of their
# children plus host-side glue, which lands in "unattributed" honestly.
_SPAN_BUCKETS = {
    "planner": "plan",
    "compile": "compile",
    "dispatch": "device_dispatch",
    "host_pull": "host_pull",
    "split-generation": "split_generation",
    # PR 38: one a launch of a connector's page generator (record_generate),
    # inside the producer's "prefetch" span, whose other seconds (its blocked
    # puts: ``put_wait_s``; host decode and staging) stay h2d
    "generate": "split_generation",
    "prefetch": "h2d",
    # PR 38: the consumer's wait on the prefetch queue (_prefetched_pages)
    "scan.wait": "scan_wait",
    "exchange.read": "exchange_wait",
    "exchange.stream": "exchange_wait",
    # round 18: the mesh exchange (exec/distributed.py) opens these around its
    # shard_map route/merge steps, so distributed statements attribute
    # exchange time too (before, only the HTTP SpoolingExchange path did)
    "exchange.route": "exchange_wait",
    "exchange.merge": "exchange_wait",
}

# slice-attribution priority, highest first: when spans overlap (background
# prefetch under a foreground dispatch; worker dispatches under an exchange
# drain), the slice charges to the bucket that represents the FOREGROUND
# cause of the wall.  "compile" outranks "device_dispatch" (round 17): a
# compile span always nests inside the first-seen dispatch span, and a cold
# statement's wall is compilation, not execution — before this, cold walls
# silently inflated the dispatch bucket.
_BUCKET_PRIORITY = ("compile", "device_dispatch", "host_pull", "scan_wait",
                    "exchange_wait", "split_generation", "plan", "h2d")

# PR 38: the spans that CONTAIN work and are no bucket themselves, all opened
# on the statement's own thread.  The remainder of the sweep is split by the
# innermost one open at each uncovered slice (``unattributed_by``), so the
# tree that is there says where the unnamed host time sits.
_CONTAINER_SPANS = ("query", "execution", "join.build", "window", "mesh.fragment", "task",
                    # the two waits under the root span (counters of their
                    # own: batch_wait_s, executor_wait_s) are no bucket, so
                    # their seconds are the remainder's: named here
                    "batcher.wait", "executor.checkout")


def _is_container(name) -> bool:
    return name in _CONTAINER_SPANS or str(name).startswith("aggregate.")


def wall_breakdown(spans, window=None, queued_s: float = 0.0,
                   retry_backoff_s: float = 0.0) -> Optional[dict]:
    """Decompose a query's wall clock into WALL_BUCKETS seconds.

    ``spans``: Span objects or span_dict dicts (the last_query_trace form,
    worker spans included once stitched).  ``window``: explicit
    (start_s, end_s) wall window; default = the root "query" span.
    ``queued_s`` is measured OUTSIDE the window (admission wait precedes the
    root span) and adds to the reported wall; ``retry_backoff_s`` happens
    INSIDE it (the dispatch loop's backoff sleeps run under the root span),
    so it is carved out of the unattributed remainder — never added on top,
    which would double-count the same seconds.  Returns None when no
    closed window can be established.  Host-only arithmetic — zero device
    work (the flight-recorder feed discipline).

    ``unattributed_by`` (PR 38) splits the remainder by the innermost
    container span (_CONTAINER_SPANS, ``aggregate.*``) open at each slice
    that no leaf span covers: {span name: seconds}, summing to
    ``unattributed``; "outside" where none is open (an explicit window)."""
    dicts = [s if isinstance(s, dict) else span_dict(s) for s in spans]
    if window is None:
        root = next((s for s in dicts
                     if s.get("parent_id") is None
                     and s.get("name") == "query"), None)
        if root is None or root.get("end_s") is None:
            return None
        window = (root["start_s"], root["end_s"])
    lo, hi = window
    wall = max(float(hi) - float(lo), 0.0)
    rank = {b: i for i, b in enumerate(_BUCKET_PRIORITY)}
    # single event sweep with per-bucket active counts — O(n log n), not
    # O(slices x intervals): a SF100 capture query's trace holds thousands
    # of dispatch/generation/pull spans and this runs at every completion.
    # An event is (time, +1 | -1, bucket rank) for a leaf span and
    # (time, +1 | -1, (start, span id, name)) for a container span
    events: list = [(lo, 0, None), (hi, 0, None)]
    for s in dicts:
        name, end = s.get("name"), s.get("end_s")
        bucket = _SPAN_BUCKETS.get(name)
        if bucket is None:
            if not _is_container(name):
                continue
            if end is None:  # a container still open (EXPLAIN ANALYZE reads
                end = hi     # its window from inside the statement) holds it
        if end is None or s.get("start_s") is None:
            continue
        a = max(float(s["start_s"]), lo)
        z = min(float(end), hi)
        if z > a:
            what = rank[bucket] if bucket is not None else \
                (float(s["start_s"]), s.get("span_id") or 0, name)
            events.append((a, 1, what))
            events.append((z, -1, what))
    events.sort(key=lambda ev: ev[0])
    buckets = {b: 0.0 for b in WALL_BUCKETS}
    active = [0] * len(_BUCKET_PRIORITY)
    open_containers: set = set()
    by_container: dict = {}
    prev = None
    i, n = 0, len(events)
    while i < n:
        t = events[i][0]
        if prev is not None and t > prev:
            for j, b in enumerate(_BUCKET_PRIORITY):
                if active[j]:
                    buckets[b] += t - prev
                    break
            else:
                # no leaf span covers the slice: it is the remainder's, under
                # the innermost (latest opened) container that is open
                inner = max(open_containers)[2] if open_containers else "outside"
                by_container[inner] = by_container.get(inner, 0.0) + t - prev
        while i < n and events[i][0] == t:
            _, step, what = events[i]
            if isinstance(what, int):
                active[what] += step
            elif step > 0:
                open_containers.add(what)
            elif step < 0:
                open_containers.discard(what)
            i += 1
        prev = t
    attributed = sum(buckets.values())
    buckets["admission_queue"] = max(float(queued_s or 0.0), 0.0)
    remainder = max(wall - attributed, 0.0)
    # backoff sleeps are part of the window's otherwise-unattributed time:
    # name them, capped at what the remainder can actually hold
    buckets["retry_backoff"] = min(max(float(retry_backoff_s or 0.0), 0.0),
                                   remainder)
    buckets["unattributed"] = remainder - buckets["retry_backoff"]
    carve = buckets["retry_backoff"]  # out of the containers too, largest first
    for name in sorted(by_container, key=by_container.get, reverse=True):
        if carve <= 0.0:
            break
        took = min(by_container[name], carve)
        by_container[name] -= took
        carve -= took
    out = {b: round(v, 6) for b, v in buckets.items()}
    out["wall_s"] = round(wall + buckets["admission_queue"], 6)
    out["unattributed_by"] = {k: round(v, 6) for k, v in sorted(
        by_container.items(), key=lambda kv: -kv[1]) if v > 0.0}
    return out


def format_wall_breakdown(bd: dict) -> str:
    """One-line render for EXPLAIN ANALYZE / scripts: non-zero buckets in
    declaration order, milliseconds, total last."""
    parts = [f"{b} {bd.get(b, 0.0) * 1000:.1f}ms"
             for b in WALL_BUCKETS if bd.get(b, 0.0) > 0.0005]
    if not parts:
        parts = ["unattributed 0.0ms"]
    where = bd.get("unattributed_by")
    if where and parts[-1].startswith("unattributed") \
            and bd.get("unattributed", 0.0) > 0.05 * bd.get("wall_s", 0.0):
        # where the remainder sits, once it passes 5 % of the wall
        parts[-1] += " [" + ", ".join(
            f"{k} {v * 1000:.1f}ms" for k, v in where.items()) + "]"
    return ("Wall breakdown: " + ", ".join(parts)
            + f" (total {bd.get('wall_s', 0.0) * 1000:.1f}ms)")
