"""HBM device buffer pool: page & join-build caching across queries.

Trino-class engines treat a columnar buffer pool as table stakes; on a TPU
the payoff is double — a cached scan skips host generation AND the
host->device transfer, and (because the cached entry is the WHOLE scan as one
device page) every downstream per-split consumer loop collapses to a single
dispatch per stage.  TQP (arxiv 2203.01877) and "Accelerating Presto with
GPUs" (arxiv 2606.24647) both report that keeping hot columnar data resident
in accelerator memory, not re-staging it per query, is where warm wall-clock
goes.

Two tiers, one LRU:

- **Page tier** — a completed scan's pages, concatenated into ONE
  device-resident page, keyed on (catalog, table, split list, column set,
  connector plan_version).  Raw pre-transform pages, so queries with
  different filters/projections over the same scan share the entry.
  Entries are only stored when the scan ran to completion (a LIMIT
  short-circuit or error unwind must never cache a partial scan).
- **Build tier** — finished join build state (the materialized build page,
  its dictionaries, and the built hash table when the single-match strategy
  applies), keyed on a structural fingerprint of the build fragment plus the
  plan_versions of the catalogs it reads.  Checked out tables thread through
  ``_Stream.aux`` as JIT ARGUMENTS (the no-closed-over-aux rule) exactly like
  freshly built ones.
- **Result tier (round 12)** — completed ``MaterializedResult``s keyed on
  (structural plan fingerprint, catalogs, plan-shaping session props): a
  repeated dashboard-style statement is answered with ZERO device
  dispatches, zero executor checkout, and zero host pulls.  Entries are
  host-resident (numpy result columns), but accounting still rides this
  pool's labeled MemoryPool (tag ``result-cache``) so /v1/status, the
  metrics gauges and the leak checks see them next to the device tiers.
  The tier has its OWN byte budget (``TRINO_TPU_RESULT_CACHE``; unset = 0
  everywhere — results are host memory, there is no HBM fraction to steal,
  and a measurement must keep timing the execute path unless it
  explicitly opts in) and a per-entry size cap
  (``TRINO_TPU_RESULT_CACHE_MAX_ENTRY``, default budget/4).  Admission
  policy (deterministic plans only, no volatile functions, cacheable
  connectors) is the ENGINE's job — the pool stores what it is handed.

Reservations flow through a private labeled :class:`~..memory.MemoryPool`
(visible in ``/v1/status`` and ``/v1/metrics`` as pool "buffer-pool");
pressure LRU-evicts instead of raising, and ``clear()`` releases every
reservation (Engine._invalidate calls it, so DDL can never leak device
memory through the pool).

Gating: ``TRINO_TPU_PAGE_CACHE`` is the HBM byte budget (``0`` = off, the
CPU-backend default — regeneration is cheap there and host RAM is the
scarce resource); unset on an accelerator backend defaults to 25% of HBM.
The non-plan-shaping ``page_cache`` session property opts single queries in
or out of a configured pool.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Optional

from . import faults

__all__ = ["DeviceBufferPool", "page_cache_budget", "result_cache_budget"]


def page_cache_budget() -> int:
    """Resolve the pool byte budget: the TRINO_TPU_PAGE_CACHE env var when
    set (plain bytes; 0 disables), else 0 on the CPU backend and a quarter of
    the device memory budget on accelerators.  Resolved lazily (first use) so
    importing this module never forces jax backend initialization."""
    import os

    raw = os.environ.get("TRINO_TPU_PAGE_CACHE")
    if raw is not None:
        try:
            return max(int(raw), 0)
        except ValueError:
            return 0
    import jax

    if jax.default_backend() == "cpu":
        return 0
    from ..memory import device_memory_budget

    return device_memory_budget(0.25)


def result_cache_budget() -> int:
    """Result-tier byte budget: TRINO_TPU_RESULT_CACHE (plain bytes; 0
    disables), unset = 0 on EVERY backend.  Unlike the page tier there is no
    accelerator default: result entries live in host RAM (no HBM fraction to
    derive a default from) and an implicit default would silently turn
    a benchmark's replayed statements into cache hits — serving deployments
    opt in explicitly."""
    import os

    raw = os.environ.get("TRINO_TPU_RESULT_CACHE")
    if raw is None:
        return 0
    try:
        return max(int(raw), 0)
    except ValueError:
        return 0


def _result_nbytes(result) -> int:
    """Host bytes a cached MaterializedResult pins (decoded + raw columns,
    deduped by identity — non-decoded columns ALIAS their raw array, and
    double-counting them would halve the tier's effective capacity).
    Object (string) columns estimate per-value payload + pointer overhead —
    a conservative over-count, like _table_nbytes."""
    import numpy as np

    total = 0
    seen: set = set()
    for cols in (result.columns, result.raw_columns):
        for c in cols:
            if id(c) in seen:
                continue
            seen.add(id(c))
            a = np.asarray(c)
            if a.dtype == object:
                total += 8 * a.size + sum(
                    len(str(v)) for v in a.ravel() if v is not None)
            else:
                total += a.nbytes
    return total


def _page_nbytes(page) -> int:
    """Device bytes a cached page pins (columns + null masks + valid)."""
    import numpy as np

    total = 0
    n = page.capacity
    for c in page.columns:
        if getattr(c, "dtype", None) == object:
            continue
        total += n * np.dtype(c.dtype).itemsize
    total += sum(n for m in page.null_masks if m is not None)
    if page.valid is not None:
        total += n
    return total


def _table_nbytes(table) -> int:
    """Device bytes of a join table's array leaves (JoinTable /
    DirectJoinTable pytrees).  build_columns may alias the build page's
    buffers — the double count is a deliberate conservative over-estimate
    (earlier eviction, never silent overcommit)."""
    import dataclasses

    import numpy as np

    if table is None:
        return 0
    total = 0
    for f in dataclasses.fields(table):
        v = getattr(table, f.name)
        leaves = v if isinstance(v, (tuple, list)) else (v,)
        for leaf in leaves:
            shape = getattr(leaf, "shape", None)
            if shape is None or getattr(leaf, "dtype", None) == object:
                continue
            total += int(np.prod(shape, dtype=np.int64)) * \
                np.dtype(leaf.dtype).itemsize
    return total


class _Entry:
    __slots__ = ("kind", "catalog", "table", "payload", "nbytes")

    def __init__(self, kind, catalog, table, payload, nbytes):
        self.kind = kind  # "page" | "build" | "result"
        self.catalog = catalog
        self.table = table  # per-table breakdown / invalidation ("" for
        # multi-table build fragments — they invalidate via clear()/versions)
        self.payload = payload
        self.nbytes = nbytes


class DeviceBufferPool:
    """Engine-owned two-tier HBM cache (page tier + join-build tier) with LRU
    eviction accounted through a labeled MemoryPool.  One instance is shared
    by every pooled executor under this lock; a WorkerServer owns its own."""

    PAGE_TAG = "page-cache"
    BUILD_TAG = "build-cache"
    RESULT_TAG = "result-cache"
    SPILL_TAG = "spill"

    def __init__(self, budget_bytes: Optional[int] = None,
                 result_budget_bytes: Optional[int] = None):
        self._budget = budget_bytes  # None = resolve lazily from env/backend
        self._result_budget = result_budget_bytes  # None = lazy from env
        # per-tier-group resident bytes: the shared MemoryPool's max is the
        # SUM of both budgets, so each group enforces its own sub-budget —
        # device entries (page/build, plus spill reservations) may never
        # expand into the result budget's headroom (that would over-commit
        # HBM) and host-resident results may never displace device entries
        self._result_bytes = 0
        self._device_bytes = 0
        # invalidation epoch: clear()/invalidate_catalog bump it, and a
        # result store presents the epoch its statement STARTED under — a
        # DML that invalidated mid-execution makes the late store a no-op
        # (the entry would otherwise outlive the invalidation that should
        # have covered it; connectors without plan_version have no other
        # staleness defense)
        self.epoch = 0
        self._lock = threading.RLock()
        self._entries: OrderedDict = OrderedDict()  # key -> _Entry (LRU)
        self.memory_pool = None  # created when the budget resolves nonzero
        # lifetime stats (the /v1/metrics *_total series — independent of
        # per-query counters so worker-merged totals don't double-count)
        self.hits = 0
        self.misses = 0
        self.build_hits = 0
        self.build_misses = 0
        self.result_hits = 0
        self.result_misses = 0
        self.evictions = 0

    # -- gating ----------------------------------------------------------------
    def budget(self) -> int:
        with self._lock:
            if self._budget is None:
                self._budget = page_cache_budget()
            return self._budget

    @property
    def enabled(self) -> bool:
        return self.budget() > 0

    def result_budget(self) -> int:
        with self._lock:
            if self._result_budget is None:
                self._result_budget = result_cache_budget()
            return self._result_budget

    @property
    def result_enabled(self) -> bool:
        return self.result_budget() > 0

    def result_entry_cap(self) -> int:
        """Per-entry admission cap for the result tier: a single giant result
        (a full-table SELECT) must not monopolize — or thrash — the budget.
        TRINO_TPU_RESULT_CACHE_MAX_ENTRY overrides; default budget/4."""
        import os

        raw = os.environ.get("TRINO_TPU_RESULT_CACHE_MAX_ENTRY")
        if raw is not None:
            try:
                return max(int(raw), 0)
            except ValueError:
                pass
        return max(self.result_budget() // 4, 1)

    def page_entry_cap(self) -> int:
        """Per-entry admission cap for scan pages, as the result tier has one:
        a quarter of the budget.  An entry is one whole scan of one column
        set, and several statements' column sets of one large table each fit
        the budget alone but not together (TPC-H SF10 lineitem: 3.1 GB for
        q3's four columns, 1.8 GB for q18's two, of 4.2 GB), so each would
        evict the other's, the dimension scans and the join builds that every
        statement shares, and a statement would meet another page shape every
        time it ran.  A scan over the cap streams split by split, always."""
        return max(self.budget() // 4, 1)

    @staticmethod
    def cacheable(conn) -> bool:
        """Only connectors whose page generation is deterministic for a given
        plan_version may cache (the same assumption the engine's plan cache
        makes: immutable generators, DDL/DML invalidates).  Volatile sources
        (system runtime tables, external dbapi databases) never opt in."""
        return bool(getattr(conn, "CACHEABLE_SCANS", False))

    def _pool(self):
        if self.memory_pool is None:
            from ..memory import MemoryPool

            # one labeled pool spans the device tiers AND the host-resident
            # result tier: the result tier's own sub-budget (checked in
            # put_result) keeps host entries from displacing device entries,
            # while the shared pool keeps every tier visible/leak-checkable
            # under one reserved==resident invariant
            self.memory_pool = MemoryPool(
                max_bytes=self.budget() + self.result_budget())
        return self.memory_pool

    @classmethod
    def _tag_of(cls, kind: str) -> str:
        return {"page": cls.PAGE_TAG, "build": cls.BUILD_TAG,
                "result": cls.RESULT_TAG}[kind]

    # -- keys ------------------------------------------------------------------
    @staticmethod
    def page_key(catalog: str, conn, table: str, splits, columns) -> tuple:
        ver = conn.plan_version() if hasattr(conn, "plan_version") else 0
        return ("page", catalog, table,
                tuple((s.lo, s.hi) if hasattr(s, "lo") and hasattr(s, "hi")
                      else repr(s) for s in splits),
                tuple(columns), ver)

    # -- page tier -------------------------------------------------------------
    def get_page(self, key):
        """-> (page, nbytes) or None; a hit refreshes LRU recency.  Chaos:
        ``cache_checkout`` faults land here — ``deny`` serves a miss (the
        caller regenerates, the recoverable path), raises propagate."""
        if faults.maybe_inject("cache_checkout", f"page.{key[2]}") == "deny":
            with self._lock:
                self.misses += 1
            return None
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                self.misses += 1
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            return e.payload, e.nbytes

    def has_page(self, key) -> bool:
        """Presence probe WITHOUT recency/stat side effects — the store path
        uses it to skip staging an entry another executor already built."""
        with self._lock:
            return key in self._entries

    def put_page(self, key, page, nbytes: Optional[int] = None) -> bool:
        """Store a COMPLETED scan already staged as one device-resident page
        (or, with ``nbytes``, the mesh executor's row-sharded batches of one:
        exec.distributed._ShardedScan, priced at the bytes ONE chip holds)
        (exec.local_executor._stage_scan_entry does the staging: host arrays
        through the sanctioned _page_to_device chokepoint, concatenation as
        one COUNTED _jit dispatch — device work here would be invisible to
        the budget counters).  Chaos: ``cache_store`` faults land here —
        ``deny`` skips the admission (next query regenerates), raises
        propagate to the scan source's store guard, which treats the scan as
        uncacheable; either way no partial entry can be admitted."""
        if not self.enabled or page is None:
            return False
        with self._lock:
            if key in self._entries:
                return True  # another executor stored it first
        # inject only past the early-exits (duplicate store included): a fire
        # must mean a real store was attempted, or chaos "fires>=1"
        # assertions pass vacuously
        if faults.maybe_inject("cache_store", f"page.{key[2]}") == "deny":
            return False
        if nbytes is None:
            nbytes = _page_nbytes(page)
        if nbytes > self.page_entry_cap():
            return False
        return self._store(key, _Entry("page", key[1], key[2], page, nbytes),
                           self.PAGE_TAG)

    # -- build tier ------------------------------------------------------------
    def get_build(self, key):
        """-> payload dict or None.  Payload holds {"page", "dicts", "table",
        "span", "null_stats"} — everything _compile_join derives from the
        build fragment; "table" is None when the fragment needs the
        multi-match strategy (duplicate keys / residual filter)."""
        if faults.maybe_inject("cache_checkout", "build") == "deny":
            with self._lock:
                self.build_misses += 1
            return None
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                self.build_misses += 1
                return None
            self._entries.move_to_end(key)
            self.build_hits += 1
            return e.payload

    def put_build(self, key, payload) -> bool:
        """``key`` is ("build", fingerprint, right_keys, catalogs-tuple) —
        the catalogs tuple (key[3]) is what invalidate_catalog matches."""
        if not self.enabled:
            return False
        with self._lock:
            if key in self._entries:
                return True
        if faults.maybe_inject("cache_store", "build") == "deny":
            return False
        nbytes = _page_nbytes(payload["page"]) \
            + _table_nbytes(payload.get("table"))
        return self._store(
            key, _Entry("build", ",".join(key[3]), "", payload, nbytes),
            self.BUILD_TAG)

    # -- result tier (round 12) ------------------------------------------------
    def get_result(self, key):
        """-> (MaterializedResult, nbytes) or None; a hit refreshes LRU
        recency.  Chaos: ``cache_checkout`` faults with site ``result`` land
        here — ``deny`` serves a miss (the caller executes the statement,
        the recoverable path), raises propagate.  Served results are SHARED
        numpy arrays: every engine surface treats results as immutable."""
        if faults.maybe_inject("cache_checkout", "result") == "deny":
            with self._lock:
                self.result_misses += 1
            return None
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                self.result_misses += 1
                return None
            self._entries.move_to_end(key)
            self.result_hits += 1
            return e.payload, e.nbytes

    def put_result(self, key, result, epoch: Optional[int] = None) -> bool:
        """Store a completed MaterializedResult.  ``key`` is ("result",
        plan fingerprint, catalogs tuple, ...) — the catalogs tuple (key[2])
        is what invalidate_catalog matches.  ``epoch`` is the pool epoch the
        statement STARTED under: a mismatch means an invalidation landed
        while the statement executed, and admitting its (possibly pre-DML)
        result would resurrect state the invalidation cleared.  The
        ADMISSION decision (deterministic plan, cacheable connectors, no
        volatile functions) already happened in the engine; here only
        sizing/staleness applies: entries over the per-entry cap are
        skipped, and the tier LRU-evicts its own entries to stay inside its
        sub-budget before reserving under the shared pool.  Chaos:
        ``cache_store`` faults with site ``result`` — ``deny`` skips the
        admission, raises propagate to the engine's store guard (the query
        stays successful, the entry stays absent)."""
        if not self.result_enabled or result is None:
            return False
        with self._lock:
            if epoch is not None and epoch != self.epoch:
                return False  # invalidated mid-statement: never store
            if key in self._entries:
                return True  # a concurrent statement stored it first
        # past the early-exits: a fire must mean a real store was attempted
        if faults.maybe_inject("cache_store", "result") == "deny":
            return False
        nbytes = _result_nbytes(result)
        if nbytes > self.result_entry_cap():
            return False
        with self._lock:
            # the tier's own sub-budget: evict RESULT entries (oldest first)
            # until this one fits — device tiers are never displaced by a
            # host-resident result, and vice versa (_store's symmetric
            # device check)
            while self._result_bytes + nbytes > self.result_budget():
                if not self._evict_oldest(("result",)):
                    return False
            cats = ",".join(key[2]) if key[2] else ""
            return self._store(key, _Entry("result", cats, "", result,
                                           nbytes), self.RESULT_TAG)

    # -- storage / eviction ----------------------------------------------------
    def _store(self, key, entry: _Entry, tag: str) -> bool:
        pool = self._pool()
        with self._lock:
            if key in self._entries:
                return True
            if entry.nbytes > pool.max_bytes:
                return False  # can never fit: don't flush everyone else first
            if entry.kind in ("page", "build"):
                # device sub-budget: HBM entries plus device-resident spill
                # reservations stay under budget() even while the (host)
                # result budget sits underfull
                while self._device_usage() + entry.nbytes > self.budget():
                    if not self._evict_oldest(("page", "build")):
                        return False
            while not pool.try_reserve(entry.nbytes, tag):
                if not self._entries:
                    return False
                self._evict_lru()
            self._entries[key] = entry
            if entry.kind == "result":
                self._result_bytes += entry.nbytes
            else:
                self._device_bytes += entry.nbytes
            return True

    def _device_usage(self) -> int:
        """Caller holds the lock: resident page/build bytes + live
        device-resident spill reservations (the SPILL_TAG share of the
        shared pool) — the quantity the device sub-budget bounds."""
        spill = 0
        if self.memory_pool is not None:
            spill = self.memory_pool.info()["by_tag"].get(self.SPILL_TAG, 0)
        return self._device_bytes + spill

    def _forget(self, e: _Entry) -> None:
        """Caller holds the lock: update tier bytes + pool reservation for a
        removed entry."""
        if e.kind == "result":
            self._result_bytes -= e.nbytes
        else:
            self._device_bytes -= e.nbytes
        if self.memory_pool is not None:
            self.memory_pool.free(e.nbytes, self._tag_of(e.kind))

    def _evict_oldest(self, kinds) -> bool:
        """Caller holds the lock: evict the least-recently-used entry whose
        kind is in ``kinds``.  False when no such entry remains."""
        oldest = next((k for k, e in self._entries.items()
                       if e.kind in kinds), None)
        if oldest is None:
            return False
        e = self._entries.pop(oldest)
        self.evictions += 1
        self._forget(e)
        return True

    def _evict_lru(self) -> None:
        """Caller holds the lock.  Frees the oldest entry's reservation; the
        device arrays free when the last stream/aux reference drops (jax
        arrays are refcounted — an in-flight query holding the page keeps it
        alive exactly as long as it needs it)."""
        key, e = self._entries.popitem(last=False)
        self.evictions += 1
        self._forget(e)

    # -- spill tier / pressure eviction (round 11) -----------------------------
    def reserve_spill(self, nbytes: int) -> bool:
        """Claim HBM for a device-resident spill chunk (exec/spill's first
        tier).  Cache entries LRU-evict to make room — the escalation
        ladder's first rung: cache gives way to live query state before
        anything overflows to host RAM, queues, or dies — but spill can
        never push the pool past its budget (overflow goes to the next
        tier instead).  Reservations land under the "spill" tag of the
        pool's labeled MemoryPool, so /v1/status and the leak checks see
        device-resident spill alongside the cache tiers."""
        if not self.enabled or nbytes <= 0:
            return False
        pool = self._pool()
        with self._lock:
            # bounded by the DEVICE budget, not the pool's page+result sum:
            # spill chunks are HBM-resident, so they evict device entries
            # and may never expand into the host result tier's headroom
            if nbytes > self.budget():
                return False
            while self._device_usage() + nbytes > self.budget():
                if not self._evict_oldest(("page", "build")):
                    return False
            while not pool.try_reserve(nbytes, self.SPILL_TAG):
                if not self._entries:
                    return False
                self._evict_lru()
            return True

    def release_spill(self, nbytes: int) -> None:
        """Return a spill reservation (partition consumed / spill closed)."""
        if nbytes and self.memory_pool is not None:
            self.memory_pool.free(nbytes, self.SPILL_TAG)

    def evict_bytes(self, nbytes: int) -> int:
        """LRU-evict cache entries until ``nbytes`` are freed or the cache is
        empty (pressure shedding: worker admission refusal and the cluster
        memory killer both try this rung before anything harsher).  Returns
        the bytes actually freed."""
        freed = 0
        with self._lock:
            while freed < nbytes and self._entries:
                oldest = next(iter(self._entries.values()))
                freed += oldest.nbytes
                self._evict_lru()
        return freed

    # -- invalidation ----------------------------------------------------------
    def invalidate_catalog(self, catalog: str) -> None:
        """Drop every entry that reads ``catalog`` (version-stale plan
        eviction path).  Build and result entries fingerprint their versions,
        so a stale one would never SERVE — this releases its memory too."""
        with self._lock:
            self.epoch += 1
            dead = [k for k, e in self._entries.items()
                    if e.catalog == catalog
                    or (e.kind == "build" and catalog in k[3])
                    or (e.kind == "result" and catalog in k[2])]
            for k in dead:
                self._forget(self._entries.pop(k))

    def clear(self) -> None:
        """Release everything (Engine._invalidate / DDL / register_catalog).
        Reservations return to the pool so no device memory leaks across
        DDL."""
        with self._lock:
            self.epoch += 1
            for e in self._entries.values():
                if self.memory_pool is not None:
                    self.memory_pool.free(e.nbytes, self._tag_of(e.kind))
            self._entries.clear()
            self._result_bytes = 0
            self._device_bytes = 0

    # -- observability ---------------------------------------------------------
    def info(self) -> dict:
        """Snapshot for /v1/status's buffer_pool section and the
        /v1/metrics page-cache gauges."""
        with self._lock:
            per_table: dict = {}
            total = 0
            pages = builds = results = 0
            for e in self._entries.values():
                total += e.nbytes
                if e.kind == "page":
                    pages += 1
                elif e.kind == "build":
                    builds += 1
                else:
                    results += 1
                kind_label = "<build>" if e.kind == "build" else "<result>"
                label = f"{e.catalog}.{e.table}" if e.table else \
                    (f"{e.catalog}.{kind_label}" if e.catalog else kind_label)
                t = per_table.setdefault(label, {"entries": 0, "bytes": 0})
                t["entries"] += 1
                t["bytes"] += e.nbytes
            return {"budget_bytes": self._budget if self._budget is not None
                    else None,
                    "enabled": bool(self._budget) if self._budget is not None
                    else None,
                    "entries": len(self._entries),
                    "page_entries": pages, "build_entries": builds,
                    "result_entries": results,
                    "result_bytes": self._result_bytes,
                    "result_budget_bytes": self._result_budget,
                    "bytes": total,
                    "hits": self.hits, "misses": self.misses,
                    "build_hits": self.build_hits,
                    "build_misses": self.build_misses,
                    "result_hits": self.result_hits,
                    "result_misses": self.result_misses,
                    "evictions": self.evictions,
                    "per_table": per_table}
