"""Fault-tolerant execution: retryable tasks over a spooled exchange.

Reference architecture (SURVEY.md §2.6/§3.5): the FTE scheduler
(scheduler/faulttolerant/EventDrivenFaultTolerantQueryScheduler.java:209) makes
the TASK the retryable unit — its input is a replayable TaskDescriptor
(splits), its output is written through the Exchange SPI to durable spooled
storage (spi/exchange/ExchangeManager.java, plugin/trino-exchange-filesystem/
FileSystemExchangeManager.java); a failed task re-runs from its descriptor and
duplicate attempt output is deduplicated
(operator/DeduplicatingDirectExchangeBuffer.java).  Failure injection hooks
mirror execution/FailureInjector.java:53.

TPU translation: every BLOCKING plan node (aggregate, join, window, sort,
unnest) is a retryable fragment — its inputs are replayable (leaf scans
re-generate from splits; interior fragments read their children's spooled
pages), its compacted output spools to the local filesystem with an atomic
first-commit-wins rename, and a failed attempt retries against the same
replayable inputs.  Scan-fed aggregations additionally decompose into
fine-grained per-split-batch tasks whose partial-state pages merge downstream
(the reference's partial/final aggregation pair over the spooled exchange).
"""

from __future__ import annotations

import dataclasses
import io
import os
import random
import zlib
from typing import Optional

import jax.numpy as jnp
import numpy as np

from ..execution import faults, tracing
from ..ops import hashagg
from ..page import Page, Schema
from ..sql import plan as P
from .boundary import _host
from .groupby import _MERGE_KIND, _accumulators_for, _finalize_aggs
from .local_executor import LocalExecutor
from .pages import _host_page, _materialize
from .spill import concat_host_chunks, padded_page

__all__ = ["FailureInjector", "InjectedFailure", "SpoolingExchange",
           "FaultTolerantExecutor", "serialize_page", "deserialize_page",
           "is_retryable_failure"]

_MAGIC = b"TTPG"

# Exchange codec (reference: execution/buffer/CompressionCodec.java:23 —
# NONE/LZ4/ZSTD; LZ4 is not in this environment, so ZSTD level 1 is the fast
# default) and optional authenticated encryption for pages that cross a shared
# filesystem or the wire (reference:
# CompressingEncryptingPageSerializer.java:58, AES — here AES-128/256-GCM,
# which also authenticates; the frame CRC covers the ciphertext).  The key
# comes from TRINO_TPU_EXCHANGE_KEY (hex, 16/24/32 bytes) — the cluster-secret
# model, like internal-communication.shared-secret.
_CODECS = {"none": 0, "zlib": 1, "zstd": 2}
_ENC_FLAG = 0x80
PAGE_CODEC = os.environ.get("TRINO_TPU_PAGE_CODEC", "zstd")
if PAGE_CODEC not in _CODECS:  # pragma: no cover - config error
    raise ValueError(f"TRINO_TPU_PAGE_CODEC must be one of {sorted(_CODECS)}")
if PAGE_CODEC == "zstd" and os.environ.get("TRINO_TPU_PAGE_CODEC") is None:
    # the zstd DEFAULT degrades to zlib when the python binding is absent
    # (stdlib-only container); an EXPLICIT zstd request still fails loudly at
    # use — a configured codec silently changing would corrupt expectations
    # about frames already on disk
    try:
        import zstandard  # noqa: F401
    except ImportError:
        PAGE_CODEC = "zlib"


def _exchange_key():
    h = os.environ.get("TRINO_TPU_EXCHANGE_KEY")
    if not h:
        return None
    key = bytes.fromhex(h)
    if len(key) not in (16, 24, 32):
        raise ValueError("TRINO_TPU_EXCHANGE_KEY must be 16/24/32 hex bytes")
    return key


def _compress(payload: bytes, codec: int) -> bytes:
    if codec == 1:
        return zlib.compress(payload, 1)
    if codec == 2:
        import zstandard

        return zstandard.ZstdCompressor(level=1).compress(payload)
    return payload


def _decompress(payload: bytes, codec: int) -> bytes:
    if codec == 1:
        return zlib.decompress(payload)
    if codec == 2:
        import zstandard

        return zstandard.ZstdDecompressor().decompress(payload)
    return payload


# ---------------------------------------------------------------------------- page serde
def serialize_page(columns: list, null_masks: list,
                   compress: bool = True, site: str = "fte.serialize") -> bytes:
    """Framed page wire format: magic, codec byte (low bits: NONE/ZLIB/ZSTD,
    high bit: AES-GCM encrypted), CRC32, length, npz payload (reference:
    PagesSerdeUtil.java:47 header + XXH64 checksum :84 with LZ4/ZSTD +
    optional AES, CompressingEncryptingPageSerializer.java:58).  ``site``
    labels the pull for callers outside the exchange (the disk spill tier
    frames its partition files through this codec)."""
    buf = io.BytesIO()
    arrays = {}
    # ONE batched device->host pull for the whole page (serialization is a
    # transfer chokepoint, and it must show on the counters)
    host = _host(list(columns) + [m for m in null_masks if m is not None],
                 site=site)
    hcols, rest = host[:len(columns)], host[len(columns):]
    for i, c in enumerate(hcols):
        arrays[f"c{i}"] = c
        if null_masks[i] is not None:
            arrays[f"n{i}"] = rest.pop(0)
    np.savez(buf, ncols=np.int64(len(columns)), **arrays)
    payload = buf.getvalue()
    codec = _CODECS[PAGE_CODEC] if compress else 0
    payload = _compress(payload, codec)
    flag = codec
    key = _exchange_key()
    if key is not None:
        from cryptography.hazmat.primitives.ciphers.aead import AESGCM

        nonce = os.urandom(12)
        # the frame prefix is the AAD: a frame cannot be re-labelled as a
        # different codec/flag without failing authentication
        flag = codec | _ENC_FLAG
        payload = nonce + AESGCM(key).encrypt(
            nonce, payload, _MAGIC + bytes([flag]))
    crc = zlib.crc32(payload)
    head = _MAGIC + bytes([flag]) + crc.to_bytes(4, "little") \
        + len(payload).to_bytes(8, "little")
    return head + payload


def serialize_fragment_output(cols, nulls, dicts) -> bytes:
    """Fragment output envelope: framed page + pickled output dictionaries
    (string columns are dictionary ids on the wire; the consumer needs the
    id->value mapping the producing plan derived).  The pickle rides the
    HMAC-authenticated internal channel / trusted spool directory only."""
    import pickle

    return serialize_page(cols, nulls) + pickle.dumps(dicts)


def _split_envelope(data: bytes):
    """-> (framed_page_bytes, tail) using the frame header's payload length —
    the ONE place that knows the envelope layout."""
    length = int.from_bytes(data[9:17], "little")
    return data[:17 + length], data[17 + length:]


def deserialize_fragment_output(data: bytes):
    """-> (columns, null_masks, dicts)."""
    import pickle

    frame, tail = _split_envelope(data)
    cols, nulls = deserialize_page(frame)
    return cols, nulls, pickle.loads(tail)


def deserialize_page(data: bytes):
    """-> (columns, null_masks) as numpy arrays; raises on checksum mismatch,
    missing key, or failed AES-GCM authentication."""
    if data[:4] != _MAGIC:
        raise ValueError("bad page frame magic")
    flag = data[4]
    crc = int.from_bytes(data[5:9], "little")
    length = int.from_bytes(data[9:17], "little")
    payload = data[17:17 + length]
    if zlib.crc32(payload) != crc:
        raise ValueError("page frame checksum mismatch")
    codec = flag & ~_ENC_FLAG
    if flag & _ENC_FLAG:
        from cryptography.hazmat.primitives.ciphers.aead import AESGCM

        key = _exchange_key()
        if key is None:
            raise ValueError("page frame is encrypted but "
                             "TRINO_TPU_EXCHANGE_KEY is not set")
        payload = AESGCM(key).decrypt(payload[:12], payload[12:],
                                      _MAGIC + bytes([flag]))
    payload = _decompress(payload, codec)
    # allow_pickle: exact wide-decimal (object) columns serialize via pickle
    # inside the npz; the spool/exchange is trusted (local disk or the
    # HMAC-authenticated internal channel)
    z = np.load(io.BytesIO(payload), allow_pickle=True)
    n = int(z["ncols"])
    cols = [z[f"c{i}"] for i in range(n)]
    nulls = [z[f"n{i}"] if f"n{i}" in z.files else None for i in range(n)]
    return cols, nulls


# ---------------------------------------------------------------------------- injection
class InjectedFailure(RuntimeError):
    pass


def is_retryable_failure(e: BaseException) -> bool:
    """Task-retry classification (reference: retry policies consult the error
    kind — StandardErrorCode USER_ERROR vs INTERNAL/EXTERNAL categories via
    ErrorType, spi/ErrorType.java; FailureInjector.java:53 models the injectable
    external kinds).  DETERMINISTIC errors — bad SQL, unsupported features,
    planner bugs — would fail identically on every attempt, so retrying them
    burns the budget and hides the real message; everything else (connector
    IO, transient device/runtime errors, injected faults) retries."""
    from ..memory import QueryKilledError, QueryMemoryLimitError
    from ..spi.security import AccessDeniedError
    from ..sql.frontend import SemanticError
    from ..sql.parser import ParseError

    from ..execution.faults import FatalInjectedFaultError

    deterministic = (SemanticError, ParseError, AccessDeniedError,
                     NotImplementedError, AssertionError, AttributeError,
                     NameError, QueryKilledError, QueryMemoryLimitError,
                     FatalInjectedFaultError)
    return isinstance(e, Exception) and not isinstance(e, deterministic)


class FailureInjector:
    """Deterministic fault injection at named points (reference:
    execution/FailureInjector.java:53-57 — TASK_FAILURE,
    TASK_MANAGEMENT_REQUEST_FAILURE, GET_RESULTS_FAILURE...)."""

    def __init__(self):
        self._plans: dict = {}  # (task_id, point) -> remaining failure count

    def inject(self, task_id, point: str, times: int = 1) -> None:
        self._plans[(task_id, point)] = times

    def maybe_fail(self, task_id, point: str) -> None:
        left = self._plans.get((task_id, point), 0)
        if left > 0:
            self._plans[(task_id, point)] = left - 1
            raise InjectedFailure(f"injected {point} on task {task_id}")


# ---------------------------------------------------------------------------- spooling
class SpoolingExchange:
    """Filesystem spool: one directory per exchange; each task commits exactly one
    output file via atomic rename (first commit wins — duplicate retry output is
    dropped, reference: DeduplicatingDirectExchangeBuffer)."""

    def __init__(self, directory: str):
        self.directory = directory
        os.makedirs(directory, exist_ok=True)

    def _final(self, task_id) -> str:
        return os.path.join(self.directory, f"task_{task_id}.page")

    def commit(self, task_id, attempt: int, data: bytes) -> bool:
        """Returns False when an earlier attempt already committed.  Chaos:
        ``exchange_write`` faults land here — ``drop`` silently loses the
        commit (the output never becomes visible, so the coordinator's
        deadline/re-dispatch path must recover it), raises surface as a
        retryable task failure."""
        if os.path.exists(self._final(task_id)):
            return False
        # inject only past the already-committed early-exit: a fire must mean
        # a real store was attempted (same rule as DeviceBufferPool.put_page),
        # or a speculative/retried duplicate commit burns the rule's budget
        if faults.maybe_inject("exchange_write",
                               f"task.{task_id}") == "drop":
            return False
        tmp = os.path.join(self.directory,
                           f".task_{task_id}.attempt_{attempt}.{random.random():.9f}")
        with open(tmp, "wb") as f:
            f.write(data)
        try:
            os.rename(tmp, self._final(task_id))  # atomic on POSIX
            return True
        except OSError:
            os.unlink(tmp)
            return False

    def is_committed(self, task_id) -> bool:
        return os.path.exists(self._final(task_id))

    def read(self, task_id) -> bytes:
        faults.maybe_inject("exchange_read", f"task.{task_id}")
        with open(self._final(task_id), "rb") as f:
            return f.read()


# ---------------------------------------------------------------------------- executor
@dataclasses.dataclass(frozen=True)
class TaskDescriptor:
    """Replayable task input (reference:
    scheduler/faulttolerant/TaskDescriptorStorage.java:66)."""

    task_id: int
    splits: tuple


class FaultTolerantExecutor:
    """Executes plans with task-level retries: every BLOCKING plan node
    (aggregate, join, window, sort, unnest) is a retryable fragment whose
    inputs are replayable — leaf scans re-generate from splits, interior
    fragments read their children's spooled output.  Scan-fed aggregations
    additionally split into fine-grained per-split-batch tasks (partial
    states spooled, merged downstream).  max_attempts mirrors the reference's
    task retry policy (RetryPolicy.TASK, task_retry_attempts_per_task;
    fragment scheduling: EventDrivenFaultTolerantQueryScheduler.java:209,
    replayable inputs: TaskDescriptorStorage.java:66)."""

    # fragment roots: blocking operators whose output spools durably
    _FRAGMENT_NODES = (P.Aggregate, P.Join, P.Window, P.Sort, P.Unnest)

    def __init__(self, catalogs: dict, spool_dir: str,
                 injector: Optional[FailureInjector] = None,
                 max_attempts: int = 4, splits_per_task: int = 2):
        self.catalogs = catalogs
        self.spool_dir = spool_dir
        self.injector = injector or FailureInjector()
        self.max_attempts = max_attempts
        self.splits_per_task = splits_per_task
        self.local = LocalExecutor(catalogs)
        self._exchange_seq = 0
        self.task_attempts: dict[int, int] = {}  # observability: task -> attempts used
        # fragment outputs install into the private LocalExecutor's overrides;
        # FTE execution is serialized (admission allows engine concurrency)
        import threading

        self._lock = threading.Lock()

    # -- public ----------------------------------------------------------------
    def execute(self, plan: P.PlanNode, dispatch_batch=None):
        with self._lock:
            # per-query dispatch-coalescing width (the executor is engine-
            # cached across queries; None = boundary.DISPATCH_BATCH)
            self.local.dispatch_batch = dispatch_batch
            self.local._overrides = {}
            self._task_seq = 0
            self._exchange_seq += 1
            self._exchange = SpoolingExchange(
                os.path.join(self.spool_dir, f"exchange_{self._exchange_seq}"))
            try:
                self.local.stats = {}
                self.local.boundary = {}
                self._exec_ft(plan)
                page, dd = self.local._execute_to_page(plan)
                return _materialize(page, dd)
            finally:
                self.local._overrides = {}
                # error or clean exit: no prefetch producer thread survives
                # the query (FTE drives _execute_to_page directly, so the
                # local executor's own execute()-time sweep never runs)
                self.local.close_producers()
                # fragment pages were deserialized into memory above; the
                # spool is query-scoped durable state, not a cache — a
                # long-lived server must not grow temp disk per query
                import shutil

                shutil.rmtree(self._exchange.directory, ignore_errors=True)

    # -- fragment decomposition --------------------------------------------------
    def _exec_ft(self, node: P.PlanNode) -> None:
        """Bottom-up: make every blocking fragment's output durable, so each
        fragment task's inputs are replayable (children are already spooled;
        leaf scans replay from splits)."""
        for c in node.children:
            self._exec_ft(c)
        if not isinstance(node, self._FRAGMENT_NODES):
            return
        # fragment task ids live in their own namespace ("frag0", "frag1", ...)
        # so the fine-grained split tasks inside an aggregation keep the plain
        # integer ids tests and operators address
        tid = f"frag{self._task_seq}"
        self._task_seq += 1
        if isinstance(node, P.Aggregate) and node.keys \
                and not any(s.kind in P.SORTED_AGG_KINDS
                            for s in node.aggs) \
                and self._scan_fed(node.child):
            # fine-grained path: per-split-batch partial-aggregation tasks,
            # merged into one durable page (the round-1 FTE shape, retained)
            page, agg_dicts = self._run_fte_aggregate(node)
            data = self._serialize_result(page)
            dicts = self._commit_with_retries(tid, lambda: (data, agg_dicts))
        else:
            exec_node = self._maybe_swap_join(node)

            def compute(node=exec_node, tid=tid):
                self.injector.maybe_fail(tid, "TASK_FAILURE")
                page, dd = self.local._execute_to_page(node)
                data = self._serialize_result(page)
                self.injector.maybe_fail(tid, "TASK_GET_RESULTS_FAILURE")
                return data, dd
            dicts = self._commit_with_retries(tid, compute)
        cols, nulls = deserialize_page(self._exchange.read(tid))
        page = Page(node.schema,
                    tuple(c if c.dtype == object else jnp.asarray(c)
                          for c in cols),
                    tuple(None if n is None else jnp.asarray(n) for n in nulls),
                    None)
        self.local._overrides[id(node)] = (page, dicts)

    def _maybe_swap_join(self, node):
        """Adaptive replanning (reference: AdaptivePlanner.java:121 — FTE
        re-optimizes remaining stages once upstream stages finish): when BOTH
        join children are materialized fragments, their ACTUAL row counts
        replace the optimizer's estimates.  A build side that materialized
        clearly LARGER than the probe swaps sides (join commutation) with a
        projection restoring the original column order; the swapped plan runs
        under the original fragment id, so parents are unaffected."""
        from ..sql import ir

        if not isinstance(node, P.Join) or node.kind != "inner" \
                or node.filter is not None or not node.left_keys:
            return node

        def actual_rows(child):
            # look through row-preserving wrappers (column-pruning projects)
            # to the materialized fragment beneath
            while isinstance(child, P.Project):
                child = child.child
            hit = self.local._overrides.get(id(child))
            if hit is None:
                return None
            page = hit[0]
            if page.valid is None:
                return page.capacity
            return int(jnp.sum(page.valid))

        lr, rr = actual_rows(node.left), actual_rows(node.right)
        if lr is None or rr is None or rr <= 2 * max(lr, 1):
            return node  # no inversion (or unknown): keep the planned sides
        self.adaptive_swaps = getattr(self, "adaptive_swaps", 0) + 1
        lf = tuple(node.left.schema.fields)
        rf = tuple(node.right.schema.fields)
        swapped = P.Join("inner", node.right, node.left, node.right_keys,
                         node.left_keys, Schema(rf + lf),
                         distribution=node.distribution,
                         est_rows=node.est_rows)
        exprs = tuple(ir.FieldRef(len(rf) + i, f.type, f.name)
                      for i, f in enumerate(lf)) \
            + tuple(ir.FieldRef(i, f.type, f.name) for i, f in enumerate(rf))
        return P.Project(swapped, exprs, node.schema)

    def _scan_fed(self, node) -> bool:
        """True when the subtree is a pure stream over one scan and contains NO
        blocking fragments anywhere below — a join-fed aggregate must read the
        join's spooled output (generic path), not replay the join from base
        scans (which would orphan the spooled fragment and run the most
        expensive operator twice)."""
        def has_fragment(n):
            return isinstance(n, self._FRAGMENT_NODES) \
                or any(has_fragment(c) for c in n.children)

        if has_fragment(node):
            return False
        try:
            stream = self.local._compile_stream(node)
        except NotImplementedError:
            return False
        return stream.scan_info is not None and bool(stream.scan_info.splits)

    def _serialize_result(self, page: Page) -> bytes:
        """Compact (valid rows only) + frame a fragment output page."""
        valid, pcols, pnulls = _host_page(page)
        cols = [c[valid] for c in pcols]
        nulls = [None if (n is None or not n[valid].any()) else n[valid]
                 for n in pnulls]
        return serialize_page(cols, nulls)

    def _commit_with_retries(self, task_id, compute):
        """Run a fragment task with the retry/dedup protocol; returns the side
        payload (dicts) from the last successful compute, or None when an
        earlier attempt already committed."""
        return self._retry_loop(task_id, self._exchange, compute)

    def _retry_loop(self, task_id, exchange, compute):
        """The one retry/classify/dedup/exhaust policy both task shapes share.
        ``compute`` returns bytes or (bytes, side_payload); the side payload of
        the successful attempt is returned (None when an earlier attempt's
        commit made this one redundant)."""
        from ..execution import tracing as _tracing

        last_error = None
        extra = None
        for attempt in range(self.max_attempts):
            self.task_attempts[task_id] = attempt + 1
            if attempt:  # observability: retries charge the paying query
                _tracing.record_task_retry(site="fte.task.retry")
            try:
                out = compute()
                data, extra = out if isinstance(out, tuple) else (out, None)
                exchange.commit(task_id, attempt, data)
                if not exchange.is_committed(task_id):
                    # the commit was LOST (chaos exchange_write drop, torn
                    # write): returning success here would hand the reader a
                    # missing file later — recompute and recommit instead
                    raise RuntimeError(
                        f"task {task_id} output commit did not become "
                        f"visible (attempt {attempt + 1})")
                # a post-commit failure must not duplicate output on retry
                self.injector.maybe_fail(task_id, "POST_COMMIT_FAILURE")
                return extra
            except Exception as e:
                # real failures retry too (connector IO, transient runtime) —
                # "fault tolerant" must not mean "tolerant only of test
                # faults"; deterministic errors surface immediately
                if not is_retryable_failure(e):
                    raise
                last_error = e
                if exchange.is_committed(task_id):
                    return extra  # output durable; a retry would dedup anyway
                continue
        raise RuntimeError(
            f"task {task_id} failed after {self.max_attempts} attempts: "
            f"{last_error}") from last_error

    # -- stage 1: partial aggregation tasks -------------------------------------
    def _run_fte_aggregate(self, node: P.Aggregate):
        stream, key_types, acc_specs, acc_exprs, acc_kinds, step = \
            self.local._agg_compiled(node)
        si = stream.scan_info
        splits = list(si.splits)
        tasks = [TaskDescriptor(i, tuple(splits[j] for j in
                                         range(i * self.splits_per_task,
                                               min((i + 1) * self.splits_per_task,
                                                   len(splits)))))
                 for i in range((len(splits) + self.splits_per_task - 1)
                                // self.splits_per_task)]
        # nested under the query's exchange directory so query-completion
        # cleanup removes the fine-grained partials too
        exchange = SpoolingExchange(
            os.path.join(self._exchange.directory, f"agg_{self._task_seq}"))

        for task in tasks:
            self._run_task_with_retries(task, exchange, node, stream, key_types,
                                        acc_specs, step)

        return self._merge_spooled(exchange, tasks, node, stream, key_types,
                                   acc_specs, acc_kinds)

    def _run_task_with_retries(self, task, exchange, node, stream, key_types,
                               acc_specs, step):
        def compute():
            self.injector.maybe_fail(task.task_id, "TASK_FAILURE")
            data = self._execute_task(task, node, stream, key_types, acc_specs,
                                      step)
            self.injector.maybe_fail(task.task_id, "TASK_GET_RESULTS_FAILURE")
            return data

        self._retry_loop(task.task_id, exchange, compute)

    def _execute_task(self, task: TaskDescriptor, node, stream, key_types, acc_specs,
                      step) -> bytes:
        return run_partial_aggregate_splits(node, stream, key_types, acc_specs,
                                            step, task.splits)

    # -- stage 2: merge ----------------------------------------------------------
    def _merge_spooled(self, exchange, tasks, node, stream, key_types, acc_specs,
                       acc_kinds):
        payloads = [exchange.read(t.task_id) for t in tasks]
        return merge_partial_pages(node, stream, key_types, acc_specs, acc_kinds,
                                   payloads)


# ---------------------------------------------------------------------------- task bodies
# Module-level so remote worker processes (server/cluster.py) run the SAME code
# the in-process FTE tasks run (reference: one binary, role split by config —
# server/CoordinatorModule.java vs WorkerModule.java).


def _is_memory_failure(e: BaseException) -> bool:
    """Device/host memory exhaustion (reference: the retry classification
    feeding ExponentialGrowthPartitionMemoryEstimator.java:57 — memory
    failures retry at a different memory footprint, not just again)."""
    from ..memory import (MemoryPoolExhaustedError, QueryKilledError,
                          QueryMemoryLimitError)

    if isinstance(e, (QueryKilledError, QueryMemoryLimitError)):
        # a policy kill / query limit is NOT shrinkable: bisecting the split
        # set would re-raise at the first reservation of every leaf while the
        # victim keeps pinning the blocked node
        return False
    if isinstance(e, (MemoryError, MemoryPoolExhaustedError)):
        return True
    return type(e).__name__ == "XlaRuntimeError" \
        and "RESOURCE_EXHAUSTED" in str(e)


def run_partial_aggregate_splits(node, stream, key_types, acc_specs, step,
                                 splits, tick=None) -> bytes:
    """Partial aggregation over a split subset -> serialized partial page
    (keys + raw accumulator columns).  A MEMORY failure bisects the split set
    and merges the halves' partial states — the task retries at half the
    working set instead of failing identically (the memory-growth retry of
    ExponentialGrowthPartitionMemoryEstimator, inverted: rather than asking
    the scheduler for a bigger node, the task shrinks itself)."""
    try:
        return _partial_once(node, stream, key_types, acc_specs, step, splits,
                             tick)
    except Exception as e:
        if not _is_memory_failure(e) or len(splits) <= 1:
            raise
        mid = len(splits) // 2
        a = run_partial_aggregate_splits(node, stream, key_types, acc_specs,
                                         step, splits[:mid], tick)
        b = run_partial_aggregate_splits(node, stream, key_types, acc_specs,
                                         step, splits[mid:], tick)
        return _merge_partial_raw(node, key_types, acc_specs, [a, b])


def _partial_once(node, stream, key_types, acc_specs, step, splits,
                  tick=None) -> bytes:
    si = stream.scan_info
    capacity = node.capacity or 1 << 16
    while True:
        state = hashagg.groupby_init(capacity, tuple(t.dtype for t in key_types),
                                     acc_specs)
        for split in splits:
            page = si.conn.generate(split, list(si.scan_columns))
            state = step(state, page, stream.aux)
            tracing.record_groupby_insert(page.capacity)
            if tick is not None:
                tick()  # split-boundary preemption point (fair scheduler)
        if not bool(state.overflow):
            break
        capacity *= 4
    return _serialize_partial_state(node, state, len(node.keys))


def _serialize_partial_state(node, state, nk) -> bytes:
    n_groups = int(hashagg.group_count(state))
    bucket = max(1 << max(n_groups - 1, 1).bit_length(), 64)
    keys, key_nulls, accs = hashagg.compact_groups(state, bucket)
    tracing.record_compaction(state.capacity, bucket)
    got = _host(list(keys) + list(key_nulls) + list(accs),
                site="fte.partial.groups")
    cols = [g[:n_groups] for g in got[:nk]] + [g[:n_groups] for g in got[2 * nk:]]
    nulls = [g[:n_groups] for g in got[nk:2 * nk]] + [None] * len(accs)
    nulls = [n if (n is not None and n.any()) else None for n in nulls]
    return serialize_page(cols, nulls)


def _merge_partial_state(key_types, acc_specs, merge_kinds, nk, payloads):
    """The one deserialize/insert/grow loop both merge shapes share: framed
    partial pages -> one populated group-by state."""
    capacity = 1 << 16
    while True:
        state = hashagg.groupby_init(capacity,
                                     tuple(t.dtype for t in key_types),
                                     acc_specs)
        for data in payloads:
            cols, nulls = deserialize_page(data)
            if cols[0].shape[0] == 0:
                continue
            kcols = tuple(jnp.asarray(c) for c in cols[:nk])
            knulls = tuple(None if n is None else jnp.asarray(n)
                           for n in nulls[:nk])
            accs = [(jnp.asarray(c), None) for c in cols[nk:]]
            valid = jnp.ones((cols[0].shape[0],), bool)
            state = hashagg.groupby_insert(state, kcols, key_types, valid,
                                           accs, merge_kinds, knulls)
        if not bool(state.overflow):
            return state
        capacity *= 4


def _merge_partial_raw(node, key_types, acc_specs, payloads) -> bytes:
    """Merge serialized PARTIAL pages into one serialized partial page
    (accumulators stay raw — the downstream final merge finalizes)."""
    acc_kinds = [kind for spec in node.aggs
                 for kind, _dt, _init in _accumulators_for(spec)]
    merge_kinds = [_MERGE_KIND[k] for k in acc_kinds]
    nk = len(node.keys)
    state = _merge_partial_state(key_types, acc_specs, merge_kinds, nk,
                                 payloads)
    return _serialize_partial_state(node, state, nk)


def run_partial_aggregate(local: LocalExecutor, node, splits,
                          exchange_dir: str = None, stream_sources=None,
                          fetch_stream=None, tick=None) -> bytes:
    """Worker entry: compile the aggregation on this process's executor and run
    the partial task over ``splits``; the output envelope carries the group
    keys' dictionaries so the coordinator can merge without compiling the
    child stream itself.  Like its sibling task bodies, it resolves the
    fragment's RemoteSource children itself when given the exchange."""
    import pickle

    saved = local._overrides
    if exchange_dir is not None:
        local._overrides = resolve_remote_sources(exchange_dir, node,
                                                  stream_sources, fetch_stream)
    try:
        stream, key_types, acc_specs, _, _, step = local._agg_compiled(node)
        data = run_partial_aggregate_splits(node, stream, key_types, acc_specs,
                                            step, splits, tick)
        key_dicts = tuple(stream.dicts[i] for i in node.keys)
    finally:
        local._overrides = saved
    return data + pickle.dumps(key_dicts)


# -- generic fragment task bodies (cluster plane) -------------------------------
def read_fragment_outputs(exchange: SpoolingExchange, task_ids, schema):
    """Concatenate the spooled outputs of a fragment's tasks into one override
    page (the ExchangeOperator's gather, filesystem edition), padded to a
    power-of-two shape bucket — spooled lengths are data-dependent, and every
    distinct raw shape would cost a fresh XLA compile in the consuming
    pipeline.  An empty task set (zero-split source) yields an empty page."""
    ncols = len(schema.fields)
    if not task_ids:
        cols = tuple(jnp.asarray(
            np.empty((0,), np.dtype(f.type.dtype))) for f in schema.fields)
        return (Page(schema, cols, tuple(None for _ in cols), None),
                tuple(None for _ in range(ncols)))
    with tracing.maybe_span("exchange.read", tasks=len(task_ids)):
        parts = []
        for t in task_ids:
            # one in-flight entry per task read: elapsed measures ONE
            # potentially-wedging operation, so a long fan-in that is
            # actively progressing never reads as a stall
            with tracing.inflight("exchange-segment", site="exchange.read"):
                data = exchange.read(t)
            parts.append(deserialize_fragment_output(data))
    cols, nulls = concat_host_chunks(schema, [(p[0], p[1]) for p in parts])
    return padded_page(schema, cols, nulls), parts[0][2]


def read_streamed_outputs(fetch_stream, task_ids, schema):
    """Gather a RemoteSource's output from the producing workers' STREAMING
    buffers (reference: ExchangeOperator over HttpPageBufferClient — the
    pipelined data plane) instead of the spool: ``fetch_stream(task_id)``
    yields page envelopes as the producer emits them; chunks concatenate into
    the same padded override page the spool path builds."""
    ncols = len(schema.fields)
    parts = []
    for t in task_ids:
        # one span per exchange stream segment (a producing task's page
        # stream): on a distributed profile this is where worker->worker
        # pipelining time lives, distinct from device dispatches
        with tracing.maybe_span("exchange.stream", task=str(t)) as sp:
            n0 = len(parts)
            it = iter(fetch_stream(t))
            while True:
                # in-flight entry per CHUNK fetch: a multi-minute stream that
                # keeps delivering pages must not age into a stall verdict —
                # only an individual long-poll that never returns should
                with tracing.inflight("exchange-segment",
                                      site="exchange.stream"):
                    chunk = next(it, None)
                if chunk is None:
                    break
                parts.append(deserialize_fragment_output(chunk))
            sp.attributes["pages"] = len(parts) - n0
    if not parts:
        cols = tuple(jnp.asarray(
            np.empty((0,), np.dtype(f.type.dtype))) for f in schema.fields)
        return (Page(schema, cols, tuple(None for _ in cols), None),
                tuple(None for _ in range(ncols)))
    cols, nulls = concat_host_chunks(schema, [(p[0], p[1]) for p in parts])
    return padded_page(schema, cols, nulls), parts[0][2]


def resolve_remote_sources(exchange_dir: str, node, stream_sources=None,
                           fetch_stream=None) -> dict:
    """Overrides for every RemoteSource in the subtree: each one's task outputs
    are read from the spool and concatenated (reference: ExchangeOperator
    reading the source stage's spooled output) — or, when the task ids appear
    in ``stream_sources``, fetched live from the producing worker's output
    buffer via ``fetch_stream`` (the pipelined exchange; no disk touched)."""
    from ..sql.plan import RemoteSource

    overrides = {}

    def walk(n):
        if isinstance(n, RemoteSource):
            if stream_sources and fetch_stream is not None \
                    and all(t in stream_sources for t in n.task_ids):
                overrides[id(n)] = read_streamed_outputs(
                    fetch_stream, n.task_ids, n.schema)
            else:
                ex = SpoolingExchange(exchange_dir)
                overrides[id(n)] = read_fragment_outputs(ex, n.task_ids,
                                                         n.schema)
        for c in n.children:
            walk(c)

    walk(node)
    return overrides


def run_fragment(local: LocalExecutor, node, exchange_dir: str,
                 stream_sources=None, fetch_stream=None) -> bytes:
    """Worker entry: execute a generic blocking fragment (sort, window, join,
    non-scan-fed aggregate...) whose RemoteSource leaves resolve from the
    spool or from upstream streaming buffers; returns the serialized output
    envelope.  The caller must hand this task its OWN executor (overrides are
    executor-global)."""
    saved = local._overrides
    local._overrides = resolve_remote_sources(exchange_dir, node,
                                              stream_sources, fetch_stream)
    try:
        page, dicts = local._execute_to_page(node)
    finally:
        local._overrides = saved
    valid, pcols, pnulls = _host_page(page)
    cols = [c[valid] for c in pcols]
    nulls = [None if (n is None or not n[valid].any()) else n[valid]
             for n in pnulls]
    return serialize_fragment_output(cols, nulls, dicts)


def run_stream_splits(local: LocalExecutor, node, exchange_dir: str,
                      splits, stream_sources=None, fetch_stream=None,
                      sink=None, tick=None) -> bytes:
    """Worker entry: run a STREAMING fragment (a join's probe pipeline) over a
    subset of its scan splits — the probe-side task shape (reference: one
    HttpRemoteTask per split batch through the fragment's pipeline).  Build
    sides execute on this worker; spooled children resolve via overrides.
    With ``sink``, each split's surviving rows ship as their own envelope the
    moment they exist (incremental page production into a streaming output
    buffer) and the return value is empty."""
    saved = local._overrides
    local._overrides = resolve_remote_sources(exchange_dir, node,
                                              stream_sources, fetch_stream)
    try:
        stream = local._compile_stream(node)
        si = stream.scan_info
        jitted = stream.jitted()
        parts = []
        for split in splits:
            # one split at a time THROUGH whatever sits between the scan and
            # the stream's pages (a split join's match step and pack)
            raw = si.conn.generate(split, list(si.scan_columns))
            for page in si.pages_over(lambda raw=raw: iter((raw,)))():
                cols, nulls, valid = jitted(page)
                got = _host([valid] + list(cols)
                            + [n for n in nulls if n is not None],
                            site="fte.stream.split")
                v = got[0]
                ncols = len(cols)
                ccols = [c[v] for c in got[1:1 + ncols]]
                rest = got[1 + ncols:]
                cnulls = []
                for n in nulls:
                    cnulls.append(None if n is None else rest.pop(0)[v])
                if sink is not None:
                    sink(serialize_fragment_output(ccols, cnulls, stream.dicts))
                else:
                    parts.append((ccols, cnulls))
            if tick is not None:
                tick()  # split-boundary preemption point (fair scheduler)
        dicts = stream.dicts
    finally:
        local._overrides = saved
    if sink is not None:
        return b""
    cols, nulls = concat_host_chunks(stream.schema, parts)
    return serialize_fragment_output(cols, nulls, dicts)


def merge_partial_outputs(node, payloads):
    """Final aggregation over partial-output ENVELOPES (coordinator side):
    merge configuration derives from the plan alone — key types from the
    child schema, accumulators from the agg specs, key dictionaries from the
    producing workers' envelopes — so the coordinator never compiles the
    child stream (which would build join tables locally just to merge)."""
    import pickle

    key_types = tuple(node.child.schema.fields[i].type for i in node.keys)
    acc_specs, acc_kinds = [], []
    for spec in node.aggs:
        for kind, dtype, init in _accumulators_for(spec):
            acc_specs.append((dtype, init))
            acc_kinds.append(kind)
    key_dicts = None
    pages = []
    for data in payloads:
        frame, tail = _split_envelope(data)
        pages.append(frame)
        if key_dicts is None:
            key_dicts = pickle.loads(tail)
    page, _ = _merge_partial_cols(node, key_types, acc_specs, acc_kinds, pages)
    dicts = tuple(key_dicts or (None,) * len(node.keys)) \
        + tuple(None for _ in node.aggs)
    return page, dicts


def merge_partial_pages(node, stream, key_types, acc_specs, acc_kinds,
                        payloads):
    """Final aggregation over serialized partial pages (coordinator side)."""
    page, _ = _merge_partial_cols(node, key_types, acc_specs, acc_kinds,
                                  payloads)
    dicts = tuple(stream.dicts[i] for i in node.keys) \
        + tuple(None for _ in node.aggs)
    return page, dicts


def _merge_partial_cols(node, key_types, acc_specs, acc_kinds, payloads):
    """Shared final-aggregation merge over framed partial pages."""
    merge_kinds = [_MERGE_KIND[k] for k in acc_kinds]
    nk = len(node.keys)
    state = _merge_partial_state(key_types, acc_specs, merge_kinds, nk,
                                 payloads)
    n_groups = int(hashagg.group_count(state))
    bucket = max(1 << max(n_groups - 1, 1).bit_length(), 64)
    keys, key_nulls, accs = hashagg.compact_groups(state, bucket)
    tracing.record_compaction(state.capacity, bucket)
    got = _host(list(keys) + list(key_nulls) + list(accs),
                site="fte.merge.groups")
    key_cols = [k[:n_groups] for k in got[:nk]]
    key_null_cols = [kn[:n_groups] for kn in got[nk:2 * nk]]
    acc_cols = [a[:n_groups] for a in got[2 * nk:]]
    fin_cols, fin_nulls = _finalize_aggs(node.aggs, acc_cols, n_groups)
    out_cols = key_cols + fin_cols
    arrays = [np.asarray(c) for c in out_cols]  # host-ok: post-_host finalize
    out_nulls = tuple(kn if kn.any() else None for kn in key_null_cols) \
        + tuple(fin_nulls)
    page = Page(node.schema, tuple(arrays), out_nulls, None)
    return page, None
