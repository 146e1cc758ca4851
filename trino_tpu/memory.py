"""Memory accounting: hierarchical contexts + a device memory pool.

Reference: lib/trino-memory-context (AggregatedMemoryContext / LocalMemoryContext,
memory/context/), the node-level pool with per-query tracking
(memory/MemoryPool.java:46), and the revocation trigger
(execution/MemoryRevokingScheduler.java).  The TPU translation: the scarce
resource is HBM; "spill" means switching an operator to its partitioned
re-streaming strategy (Grace agg/join) whose buffers then walk the tiered
ladder (exec/spill: HBM -> host RAM under this pool's "spill" tag -> disk) —
the pool's job is to say WHEN, before an XLA allocation fails.
"""

from __future__ import annotations

import threading
from typing import Optional

from .execution import faults

__all__ = ["MemoryPool", "AggregatedMemoryContext", "LocalMemoryContext",
           "MemoryPoolExhaustedError", "QueryMemoryLimitError",
           "QueryKilledError", "device_memory_budget"]


class MemoryPoolExhaustedError(MemoryError):
    pass


class QueryMemoryLimitError(MemoryError):
    """The QUERY exceeded its query_max_memory limit — a hard kill, not a
    spill trigger (reference: ExceededMemoryLimitException +
    memory/MemoryPool per-query tracking feeding the kill policy)."""


class QueryKilledError(MemoryError):
    """The cluster low-memory policy chose this query as the victim
    (reference: memory/LowMemoryKiller + ClusterMemoryManager.java:92).
    Deterministic: retrying would hit the same cluster pressure."""


_SCOPE = threading.local()  # current query key for per-query attribution


def device_memory_budget(fraction: float = 0.75) -> int:
    """Usable bytes of accelerator memory: a fraction of the HBM the device
    itself reports.  Only the CPU backend, which reports no memory stats, gets
    the fixed 4 GiB default; an accelerator whose ``memory_stats()`` fails or
    comes back empty RAISES — the page cache and the spill ladder are sized
    from this number, and a guess would hide that the device is not there."""
    import jax

    d = jax.devices()[0]
    if d.platform == "cpu":
        return 4 << 30
    stats = d.memory_stats() or {}
    limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
    if not limit:
        raise RuntimeError(
            f"{d.platform} device {d.device_kind!r} reports no memory limit "
            f"(memory_stats() = {stats!r}); refusing to size device memory "
            "from a default")
    return int(limit * fraction)


class MemoryPool:
    """Node-level pool: operators reserve before allocating device state
    (reference: MemoryPool.reserve / tryReserve)."""

    def __init__(self, max_bytes: Optional[int] = None):
        self.max_bytes = max_bytes if max_bytes is not None else device_memory_budget()
        self.reserved = 0
        self._lock = threading.Lock()
        self._by_tag: dict[str, int] = {}
        # per-query accounting (one executor serves one query at a time):
        # exceeding the query limit is a KILL, while exceeding node capacity
        # merely returns False so operators fall back to their Grace strategy
        self.query_limit: Optional[int] = None
        self.query_reserved = 0
        # cluster-killer surfaces: per-query attribution via the thread's
        # query scope (reference: MemoryPool.java:46 taggedMemoryAllocations
        # feeding ClusterMemoryManager), and the killed-query poison entries.
        # Poison is BOUNDED-FIFO rather than cleared with the query's last
        # local task: clearing on task exit would un-poison a victim whose
        # sibling tasks are still being re-offered to this node, and a victim
        # that never returns would leak its entry forever.
        self._by_query: dict[str, int] = {}
        self._killed: dict = {}  # insertion-ordered; oldest evicted past cap
        self._killed_cap = 64

    def begin_query(self, limit: Optional[int]) -> None:
        with self._lock:
            self.query_limit = limit
            self.query_reserved = 0

    # -- per-query scope (cluster kill policy surfaces) -----------------------
    def query_scope(self, key: str):
        """Context manager: reservations on THIS THREAD attribute to ``key``
        (worker task bodies run inside their query's scope)."""
        import contextlib

        @contextlib.contextmanager
        def _scope():
            prev = getattr(_SCOPE, "key", None)
            _SCOPE.key = key
            try:
                yield
            finally:
                _SCOPE.key = prev

        return _scope()

    def kill_query(self, key: str) -> None:
        """Poison a query: its next reservation (any thread) raises
        QueryKilledError; held memory frees as its tasks unwind."""
        with self._lock:
            self._killed[key] = True
            while len(self._killed) > self._killed_cap:
                self._killed.pop(next(iter(self._killed)))

    def check_killed(self) -> None:
        """Raise if the current thread's query scope has been killed — called
        at preemption points so even reservation-free phases terminate."""
        key = getattr(_SCOPE, "key", None)
        with self._lock:
            if key is not None and key in self._killed:
                raise QueryKilledError(
                    f"query {key} killed by the cluster low-memory policy")

    def clear_query(self, key: str) -> None:
        """Drop a finished query's ATTRIBUTION on this node.  Poison entries
        deliberately survive (see _killed above) so re-offered sibling tasks
        of a killed query still die here; the bounded FIFO retires them."""
        with self._lock:
            self._by_query.pop(key, None)

    def try_reserve(self, nbytes: int, tag: str = "") -> bool:
        # chaos chokepoint: an armed ``reserve`` fault can deny this
        # reservation (the caller takes its Grace/partitioned fallback — the
        # recoverable path the chaos suite pins) or raise a typed error;
        # disarmed this is one module-global None test
        if faults.maybe_inject("reserve", tag) == "deny":
            return False
        qkey = getattr(_SCOPE, "key", None)
        with self._lock:
            if qkey is not None and qkey in self._killed:
                raise QueryKilledError(
                    f"query {qkey} killed by the cluster low-memory policy")
            if self.query_limit is not None \
                    and self.query_reserved + nbytes > self.query_limit:
                raise QueryMemoryLimitError(
                    f"query exceeded query_max_memory: requested {nbytes} "
                    f"bytes with {self.query_reserved} already reserved of "
                    f"{self.query_limit}")
            if self.reserved + nbytes > self.max_bytes:
                return False
            self.reserved += nbytes
            self.query_reserved += nbytes
            if tag:
                self._by_tag[tag] = self._by_tag.get(tag, 0) + nbytes
            if qkey is not None:
                self._by_query[qkey] = self._by_query.get(qkey, 0) + nbytes
            return True

    def reserve(self, nbytes: int, tag: str = "") -> None:
        if not self.try_reserve(nbytes, tag):
            raise MemoryPoolExhaustedError(
                f"memory pool exhausted: requested {nbytes} bytes, "
                f"{self.max_bytes - self.reserved} free of {self.max_bytes}")

    def free(self, nbytes: int, tag: str = "") -> None:
        # NOTE per-query attribution is POLL-GRADE approximate (the reference's
        # cluster view is too): frees attribute to the freeing THREAD's scope.
        # Out-of-scope frees (plan-cache eviction from coordinator threads)
        # leave the entry inflated until clear_query at the query's last task
        # exit; in-scope frees of another query's bytes clamp at zero.  Exact
        # attribution would need reservation handles at every call site.
        qkey = getattr(_SCOPE, "key", None)
        with self._lock:
            self.reserved = max(self.reserved - nbytes, 0)
            self.query_reserved = max(self.query_reserved - nbytes, 0)
            if tag and tag in self._by_tag:
                self._by_tag[tag] = max(self._by_tag[tag] - nbytes, 0)
            if qkey is not None and qkey in self._by_query:
                self._by_query[qkey] = max(self._by_query[qkey] - nbytes, 0)

    def free_bytes(self) -> int:
        with self._lock:
            return self.max_bytes - self.reserved

    def blocked(self, fraction: float) -> bool:
        """Is this pool past ``fraction`` of capacity?  The one definition of
        "blocked" the escalation ladder's rungs share: worker task admission
        (server/cluster), the engine's admission gate (queue new queries
        under pressure) and the cluster low-memory killer all read it."""
        with self._lock:
            return bool(self.max_bytes) \
                and self.reserved > fraction * self.max_bytes

    def by_query(self) -> dict:
        with self._lock:
            return dict(self._by_query)

    def info(self) -> dict:
        """Snapshot dict — the shape /v1/status, the /v1/metrics pool gauges
        and the stall watchdog's memory section all serve (round 8: this
        finally reaches the observability endpoints instead of only the UI
        overview)."""
        with self._lock:
            return {"max_bytes": self.max_bytes, "reserved": self.reserved,
                    "free": self.max_bytes - self.reserved,
                    "query_reserved": self.query_reserved,
                    "by_tag": dict(self._by_tag),
                    "by_query": dict(self._by_query)}


class AggregatedMemoryContext:
    """Parent context summing children (reference: AggregatedMemoryContext).
    The root aggregated context feeds a MemoryPool."""

    def __init__(self, pool: Optional[MemoryPool] = None,
                 parent: Optional["AggregatedMemoryContext"] = None, tag: str = ""):
        self.pool = pool
        self.parent = parent
        self.tag = tag
        self.bytes = 0
        self._lock = threading.Lock()

    def new_child(self, tag: str = "") -> "AggregatedMemoryContext":
        return AggregatedMemoryContext(parent=self, tag=tag or self.tag)

    def new_local(self, tag: str = "") -> "LocalMemoryContext":
        return LocalMemoryContext(self, tag or self.tag)

    def _update(self, delta: int) -> None:
        with self._lock:
            self.bytes += delta
        if self.parent is not None:
            self.parent._update(delta)
        elif self.pool is not None:
            if delta > 0:
                self.pool.reserve(delta, self.tag)
            elif delta < 0:
                self.pool.free(-delta, self.tag)

    def try_update(self, delta: int) -> bool:
        """Reserve without raising; used for spill decisions."""
        root = self
        while root.parent is not None:
            root = root.parent
        if delta > 0 and root.pool is not None \
                and not root.pool.try_reserve(delta, self.tag):
            return False
        node = self
        while node is not None:
            with node._lock:
                node.bytes += delta
            node = node.parent
        if delta < 0 and root.pool is not None:
            root.pool.free(-delta, self.tag)
        return True


class LocalMemoryContext:
    """Leaf context with setBytes semantics (reference: LocalMemoryContext)."""

    def __init__(self, parent: AggregatedMemoryContext, tag: str = ""):
        self.parent = parent
        self.tag = tag
        self.bytes = 0

    def set_bytes(self, nbytes: int) -> None:
        delta = nbytes - self.bytes
        self.bytes = nbytes
        self.parent._update(delta)

    def try_set_bytes(self, nbytes: int) -> bool:
        delta = nbytes - self.bytes
        if self.parent.try_update(delta):
            self.bytes = nbytes
            return True
        return False

    def close(self) -> None:
        self.set_bytes(0)
