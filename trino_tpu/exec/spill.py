"""Tiered spill for Grace-partitioned operators: HBM -> host RAM -> disk.

Reference: the spilling operators write partitions to disk and consume them
back one at a time — HashBuilderOperator's spill states
(operator/join/spilling/HashBuilderOperator.java:68), per-partition readback
(PartitionedConsumption.java), the spiller itself
(spiller/FileSingleStreamSpiller.java:59) — triggered by revocable memory
(execution/MemoryRevokingScheduler.java).

TPU translation: the scarce resource is HBM, and the unit of work is a PAGE,
not a row stream.  One device pass hash-routes every transformed page's rows
into per-partition buffers — a single stable sort by partition id plus at
most ONE device->host transfer per page (the rule: batch transfers,
never sync per partition) — then partitions stream back one at a time, each
fitting the memory pool.  Unlike a Grace re-scan, the input is read and
transformed EXACTLY ONCE: file-backed scans (Parquet/ORC) never re-decode.

Round 11 makes the spill TIERED (the memory-pressure escalation ladder):

- **HBM tier** — the routed page stays DEVICE-RESIDENT, claimed from the
  :class:`~..execution.bufferpool.DeviceBufferPool` budget under its "spill"
  tag (cache entries LRU-evict to make room: cache gives way to live query
  state).  Readback is a dynamic-slice dispatch — no host staging, no H2D
  restaging, the round-9 gap ROADMAP item 3 named.
- **Host tier** — numpy buffers as before, now RESERVED under a labeled
  ``"spill"`` tag in the executor's :class:`~..memory.MemoryPool` (visible in
  ``/v1/status`` and the stall watchdog's memory section) and bounded by the
  ``TRINO_TPU_SPILL_HOST_BYTES`` watermark (unset = pool-limited only).
- **Disk tier** — zstd-framed files through the exec/fte page codec, one
  append-only file per partition under ``TRINO_TPU_SPILL_DIR`` (default
  ``$TMPDIR/trino_tpu_spill``).  The last rung: when it refuses (real ENOSPC
  or an injected ``disk_full``), :class:`SpillCapacityError` surfaces typed.

Every device boundary goes through the sanctioned ``_jit``/``_host``
chokepoints, so spill dispatches/transfers are counted, span-attributed,
in-flight-visible and chaos-injectable for free (``spill_write`` /
``spill_read`` fault points).  Reservations release as partitions are
consumed (``release_partition``) and ``close()`` is idempotent — the
executor sweeps registered spills on every exit path, and the chaos leak
check asserts no live spill file and a zero "spill" tag afterwards.
"""

from __future__ import annotations

import os
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..execution import faults, tracing
from ..page import Page
from .boundary import _host, _jit

__all__ = ["SpilledPartitions", "SpillCapacityError", "concat_host_chunks",
           "padded_page", "padded_host_page", "spill_dir", "live_spill_files",
           "spill_host_budget"]


class SpillCapacityError(MemoryError):
    """Every spill tier refused (host watermark/pool denied and the disk
    tier is full or unavailable) — the ladder's typed terminal error.  A
    MemoryError subclass so the FTE memory-failure classifier re-plans with
    more partitions instead of burning plain retries."""


def spill_dir() -> str:
    """The disk tier's directory (TRINO_TPU_SPILL_DIR; default a
    ``trino_tpu_spill`` subdir of the system tempdir), created on demand."""
    d = os.environ.get("TRINO_TPU_SPILL_DIR")
    if not d:
        import tempfile

        d = os.path.join(tempfile.gettempdir(), "trino_tpu_spill")
    os.makedirs(d, exist_ok=True)
    return d


def spill_host_budget() -> Optional[int]:
    """Host-tier watermark in bytes (TRINO_TPU_SPILL_HOST_BYTES).  ``0``
    disables the host tier (every overflow goes to disk); unset means the
    executor MemoryPool's capacity is the only bound."""
    raw = os.environ.get("TRINO_TPU_SPILL_HOST_BYTES")
    if raw is None:
        return None
    try:
        return max(int(raw), 0)
    except ValueError:
        return None


# process-global registry of live PER-QUERY spill files: the chaos leak
# check's ground truth for "no orphaned spill file survived the scenario".
# Persistent (join-build) spills are exempt — their files legitimately live
# with the cached stream and are removed by close()/__del__ on forget/GC.
_files_lock = threading.Lock()
_LIVE_SPILL_FILES: set = set()


def live_spill_files() -> list:
    with _files_lock:
        return sorted(_LIVE_SPILL_FILES)


def _register_file(path: str) -> None:
    with _files_lock:
        _LIVE_SPILL_FILES.add(path)


def _unregister_file(path: str) -> None:
    with _files_lock:
        _LIVE_SPILL_FILES.discard(path)


def concat_host_chunks(schema, chunks):
    """Concatenate host-side row chunks ``[(cols, nulls, ...)]`` into one
    column set; a channel whose every chunk lacks a mask (or whose merged
    mask has no set bit) collapses to None.  The ONE implementation of the
    concat+null-merge rule (fragment gathers, spilled partitions, split
    streams all share it).  Chunks may carry extra trailing fields (the host
    tier appends its reserved byte count); only [0]/[1] are read."""
    ncols = len(schema.fields)
    if not chunks:
        return ([np.empty((0,), np.dtype(f.type.dtype))
                 for f in schema.fields], [None] * ncols)
    cols, nulls = [], []
    for i in range(ncols):
        cols.append(np.concatenate([c[0][i] for c in chunks]))
        ms = [c[1][i] for c in chunks]
        if all(m is None for m in ms):
            nulls.append(None)
        else:
            m = np.concatenate(
                [mm if mm is not None else np.zeros(c[0][i].shape[0], bool)
                 for mm, c in zip(ms, chunks)])
            nulls.append(m if m.any() else None)
    return cols, nulls


def _route_sorted_step(payload, valid, pid, parts):
    """Group a page's valid rows by partition id: one stable sort; invalid
    rows sink past the last partition boundary."""
    sort_key = jnp.where(valid, pid, parts).astype(jnp.int32)
    order = jnp.argsort(sort_key, stable=True)
    skey = sort_key[order]
    bounds = jnp.searchsorted(skey, jnp.arange(parts + 1, dtype=jnp.int32))
    return tuple(c[order] for c in payload), bounds


# the routing pass is a COUNTED dispatch now (round-11 satellite: the old
# partial(jax.jit, ...) form was invisible to the budget counters, the
# in-flight registry and the chaos injector)
_route_sorted = _jit(_route_sorted_step, site="spill.route",
                     static_argnames=("parts",))


def _arrays_nbytes(arrays) -> int:
    """Byte size of a tuple of (device or host) arrays, from shape/dtype —
    no transfer, no sync."""
    total = 0
    for a in arrays:
        if getattr(a, "dtype", None) == object:
            continue
        total += int(np.prod(a.shape, dtype=np.int64)) * \
            np.dtype(a.dtype).itemsize
    return total


def _read_fault(site: str) -> None:
    """spill_read chaos chokepoint: error/fatal raise inside maybe_inject;
    any RETURNED action (deny/disk_full/drop) is enacted as a typed read
    failure — the partition's rows exist only in this tier, there is no
    local fallback."""
    act = faults.maybe_inject("spill_read", site)
    if act is not None:
        raise faults.InjectedFaultError(
            f"injected {act} at spill_read/{site}")


class SpilledPartitions:
    """Per-partition buffers of compacted, ALREADY-TRANSFORMED rows, tiered
    HBM -> host RAM -> disk (module docstring).  ``memory_pool`` accounts the
    host tier (tag "spill"); ``buffer_pool`` lends the HBM tier its budget;
    ``owner`` (the executor) registers this spill for the exit-path sweep.
    ``persistent`` marks spills that legitimately outlive one query (the
    partitioned join's build side, cached with its compiled stream): the
    sweep skips them and ``__del__`` is their backstop."""

    def __init__(self, schema, parts: int, memory_pool=None, buffer_pool=None,
                 owner=None, persistent: bool = False, tag: str = "spill",
                 node_id: Optional[int] = None):
        self.schema = schema
        self.parts = parts
        self.memory_pool = memory_pool
        self.buffer_pool = buffer_pool
        self.persistent = persistent
        self.tag = tag
        self.node_id = node_id  # id(plan node) for persistent spills: the
        # executor's forget_plan closes them alongside the compiled stream
        # they live with (jax's global jit caches pin the closure graph, so
        # __del__ alone fires far too late on a live process)
        self.chunks: list = [[] for _ in range(parts)]  # host: (cols, nulls,
        # nbytes) triples; concat_host_chunks reads [0]/[1] only
        self.rows = [0] * parts
        self.spilled_bytes = 0
        self.tier_bytes = {"hbm": 0, "host": 0, "disk": 0}
        self._device_chunks: list = []  # {"payload","bounds","ncols",
        # "null_slots","nbytes"} — one per HBM-tier routed page, all
        # partitions contiguous at [bounds[p], bounds[p+1])
        self._disk: dict = {}  # p -> {"path","fh","bytes"}
        self._host_budget = spill_host_budget()
        self._host_reserved = 0
        self._hbm_reserved = 0
        self._slice_jits: dict = {}  # (bucket, cap, dtypes) -> jitted slice
        self._closed = False
        if owner is not None:
            owner._spills.append(self)

    # -- write path ------------------------------------------------------------
    def add_page(self, cols, nulls, valid, pid) -> None:
        """Route one device page into the partition tiers (one routing
        dispatch; at most one transfer)."""
        null_slots = [i for i, m in enumerate(nulls) if m is not None]
        payload = tuple(cols) + tuple(nulls[i] for i in null_slots)
        routed, bounds = _route_sorted(payload, valid, pid, parts=self.parts)
        nbytes = _arrays_nbytes(routed)
        if self._try_hbm(nbytes):
            (b,) = _host([bounds], site="spill.route.bounds")
            self._device_chunks.append(
                {"payload": routed, "bounds": b, "ncols": len(cols),
                 "null_slots": null_slots, "nbytes": nbytes})
            for p in range(self.parts):
                self.rows[p] += int(b[p + 1]) - int(b[p])
            self._hbm_reserved += nbytes
            self._account("hbm", nbytes)
            return
        got = _host(list(routed) + [bounds], site="spill.route")
        b = got[-1]
        got = got[:-1]
        ncols = len(cols)
        for p in range(self.parts):
            lo, hi = int(b[p]), int(b[p + 1])
            if hi <= lo:
                continue
            pcols = [np.asarray(c[lo:hi]) for c in got[:ncols]]  # host-ok: post-_host
            rest = list(got[ncols:])
            pnulls = []
            for i in range(ncols):
                if i in null_slots:
                    m = np.asarray(rest[null_slots.index(i)][lo:hi])  # host-ok
                    pnulls.append(m if m.any() else None)
                else:
                    pnulls.append(None)
            self._add_host_or_disk(p, pcols, pnulls)
            self.rows[p] += hi - lo

    def _try_hbm(self, nbytes: int) -> bool:
        """HBM tier admission: claim device residency from the buffer pool's
        budget (LRU-evicting cache entries).  A ``deny``/``disk_full`` fault
        here overflows to the next tier — recoverable by construction."""
        bp = self.buffer_pool
        if bp is None or not bp.enabled or nbytes <= 0:
            return False
        if faults.maybe_inject("spill_write", "spill.hbm") in (
                "deny", "disk_full"):
            return False
        return bp.reserve_spill(nbytes)

    def _add_host_or_disk(self, p: int, pcols, pnulls) -> None:
        nbytes = sum(c.nbytes for c in pcols) \
            + sum(m.nbytes for m in pnulls if m is not None)
        if self._admit_host(nbytes):
            self.chunks[p].append((pcols, pnulls, nbytes))
            self._host_reserved += nbytes
            self._account("host", nbytes)
        else:
            self._write_disk(p, pcols, pnulls, nbytes)

    def _admit_host(self, nbytes: int) -> bool:
        """Host tier admission: under the TRINO_TPU_SPILL_HOST_BYTES
        watermark AND reservable under the pool's "spill" tag.  A denial
        (watermark, pool pressure, injected fault) overflows to disk."""
        if faults.maybe_inject("spill_write", "spill.host") in (
                "deny", "disk_full"):
            return False
        if self._host_budget is not None \
                and self._host_reserved + nbytes > self._host_budget:
            return False
        if self.memory_pool is not None:
            return self.memory_pool.try_reserve(nbytes, self.tag)
        return True

    def _write_disk(self, p: int, pcols, pnulls, nbytes: int) -> None:
        """Disk tier (the last rung): append one codec frame to the
        partition's spill file.  Refusal here — injected ``disk_full`` or a
        real OS error — is terminal and typed."""
        act = faults.maybe_inject("spill_write", "spill.disk")
        if act in ("deny", "disk_full"):
            raise SpillCapacityError(
                f"spill disk tier refused partition {p} "
                f"({nbytes} bytes): injected {act}")
        from .fte import serialize_page

        frame = serialize_page(pcols, pnulls, site="spill.disk.write")
        rec = self._disk.get(p)
        try:
            if rec is None:
                path = os.path.join(
                    spill_dir(),
                    f"spill-{os.getpid()}-{id(self):x}-p{p}.pages")
                fh = open(path, "wb")
                if not self.persistent:
                    _register_file(path)
                rec = self._disk[p] = {"path": path, "fh": fh, "bytes": 0}
            rec["fh"].write(frame)
        except OSError as e:
            raise SpillCapacityError(
                f"spill disk write failed for partition {p}: {e}") from e
        rec["bytes"] += nbytes
        self._account("disk", nbytes)

    def _account(self, tier: str, nbytes: int) -> None:
        self.spilled_bytes += nbytes
        self.tier_bytes[tier] += nbytes
        tracing.record_spill(tier, nbytes, site=f"spill.{tier}")

    # -- read path -------------------------------------------------------------
    def needs_staging(self, p: int) -> bool:
        """Does partition ``p`` hold host/disk chunks (readback benefits from
        the prefetch double buffer)?  HBM-only partitions are already
        device-resident — wrapping them would buy nothing."""
        return bool(self.chunks[p]) or p in self._disk

    def partition_pages(self, p: int):
        """Stream partition ``p`` back, one page per stored chunk.  HBM
        chunks yield device-resident pages directly (one slice dispatch, no
        staging); host and disk chunks yield HOST pages padded to
        power-of-two buckets — raw chunk lengths are data-dependent, and
        every distinct shape would cost a fresh XLA compile downstream — for the consumer's prefetch double
        buffer to stage through ``_page_to_device``."""
        for ch in self._device_chunks:
            lo, hi = int(ch["bounds"][p]), int(ch["bounds"][p + 1])
            if hi <= lo:
                continue
            _read_fault("spill.hbm.read")
            yield self._device_partition_page(ch, lo, hi)
        if self.chunks[p]:
            _read_fault("spill.host.read")
            for pcols, pnulls, _nb in self.chunks[p]:
                yield padded_host_page(self.schema, pcols, pnulls)
        rec = self._disk.get(p)
        if rec is not None:
            _read_fault("spill.disk.read")
            for cols, nulls in self._disk_frames(rec):
                yield padded_host_page(self.schema, list(cols), list(nulls))

    def _device_partition_page(self, ch, lo: int, hi: int) -> Page:
        """Partition rows [lo, hi) of an HBM-resident routed page as one
        device page, padded to a power-of-two bucket: a dynamic slice at a
        traced offset, so ONE compiled step per (bucket, shape class) covers
        every partition of every chunk."""
        n = hi - lo
        payload = ch["payload"]
        cap = int(payload[0].shape[0])
        bucket = min(max(1 << max(n - 1, 1).bit_length(), 16), cap)
        key = (bucket, cap, tuple(str(a.dtype) for a in payload))
        step = self._slice_jits.get(key)
        if step is None:
            def spill_slice(payload, lo, hi, bucket=bucket, cap=cap):
                start = jnp.minimum(lo, cap - bucket)
                out = tuple(jax.lax.dynamic_slice_in_dim(a, start, bucket)
                            for a in payload)
                idx = start + jnp.arange(bucket)
                return out, (idx >= lo) & (idx < hi)
            step = self._slice_jits[key] = _jit(spill_slice,
                                                site="spill.hbm.read")
        out, valid = step(payload, lo, hi)
        ncols, null_slots = ch["ncols"], ch["null_slots"]
        rest = list(out[ncols:])
        nulls = tuple(rest[null_slots.index(i)] if i in null_slots else None
                      for i in range(ncols))
        return Page(self.schema, tuple(out[:ncols]), nulls, valid)

    def _disk_frames(self, rec):
        """Sequential codec frames of one partition file, read ONE FRAME AT
        A TIME (frames are length-prefixed; the disk tier engages exactly
        when host RAM is scarce, so materializing a whole multi-GB
        partition file would re-create the spike the tier exists to avoid).
        Flushes the write handle first — spill writes always complete
        before readback."""
        from .fte import deserialize_page

        fh = rec.get("fh")
        if fh is not None and not fh.closed:
            fh.flush()
        with open(rec["path"], "rb") as f:
            while True:
                head = f.read(17)
                if len(head) < 17:
                    return
                length = int.from_bytes(head[9:17], "little")
                yield deserialize_page(head + f.read(length))

    def partition_page(self, p: int) -> Page:
        """Partition ``p`` as ONE device page (host-side concat first) — the
        partitioned join's build-side readback.  HBM chunks pull their slice
        through ``_host`` (the table build is host-driven anyway); disk
        frames decode through the codec."""
        chunks = list(self.chunks[p])
        for ch in self._device_chunks:
            lo, hi = int(ch["bounds"][p]), int(ch["bounds"][p + 1])
            if hi <= lo:
                continue
            _read_fault("spill.hbm.read")
            # device slices are lazy views; ONE batched pull materializes them
            got = _host([a[lo:hi] for a in ch["payload"]],
                        site="spill.hbm.pull")
            ncols, null_slots = ch["ncols"], ch["null_slots"]
            rest = got[ncols:]
            pnulls = [rest[null_slots.index(i)] if i in null_slots else None
                      for i in range(ncols)]
            chunks.append((got[:ncols], pnulls))
        rec = self._disk.get(p)
        if rec is not None:
            _read_fault("spill.disk.read")
            for cols, nulls in self._disk_frames(rec):
                chunks.append((list(cols), list(nulls)))
        if self.chunks[p]:
            _read_fault("spill.host.read")
        if not chunks:
            cols = tuple(jnp.asarray(np.empty((0,), np.dtype(f.type.dtype)))
                         for f in self.schema.fields)
            return Page(self.schema, cols, tuple(None for _ in cols), None)
        cols, nulls = concat_host_chunks(self.schema, chunks)
        return padded_page(self.schema, cols, nulls)

    # -- release ---------------------------------------------------------------
    def release_partition(self, p: int) -> None:
        """Free partition ``p``'s host reservation and disk file (consumed).
        HBM chunks span partitions and release at ``close()``."""
        freed = sum(nb for _c, _n, nb in self.chunks[p])
        self.chunks[p] = []
        if freed:
            self._host_reserved -= freed
            if self.memory_pool is not None:
                self.memory_pool.free(freed, self.tag)
        self._remove_disk(p)

    def _remove_disk(self, p: int) -> None:
        rec = self._disk.pop(p, None)
        if rec is None:
            return
        try:
            if not rec["fh"].closed:
                rec["fh"].close()
        except Exception:
            pass
        try:
            os.remove(rec["path"])
        except OSError:
            pass
        _unregister_file(rec["path"])

    def close(self) -> None:
        """Release every tier (idempotent): HBM reservations back to the
        buffer pool, host reservations back to the memory pool, disk files
        removed.  Called by consumers on clean exit and swept by the
        executor's exit paths on error unwind."""
        if self._closed:
            return
        self._closed = True
        if self._hbm_reserved and self.buffer_pool is not None:
            self.buffer_pool.release_spill(self._hbm_reserved)
        self._hbm_reserved = 0
        self._device_chunks = []
        self._slice_jits = {}
        if self._host_reserved and self.memory_pool is not None:
            self.memory_pool.free(self._host_reserved, self.tag)
        self._host_reserved = 0
        self.chunks = [[] for _ in range(self.parts)]
        for p in list(self._disk):
            self._remove_disk(p)

    def __del__(self):  # backstop for persistent spills dropped with their
        try:            # cached stream (forget_plan / executor retirement)
            self.close()
        except Exception:
            pass


def padded_host_page(schema, cols, nulls) -> Page:
    """Host rows -> HOST-resident Page padded to a power-of-two shape
    bucket.  Staging to the device is the consumer's prefetch double
    buffer's job (``_page_to_device`` — counted, injectable), or implicit at
    the next dispatch."""
    n = cols[0].shape[0]
    bucket = max(1 << max(n - 1, 1).bit_length(), 16)
    pad = bucket - n
    if pad:
        cols = [np.concatenate([c, np.zeros((pad,), c.dtype)]) for c in cols]
        nulls = [None if m is None
                 else np.concatenate([m, np.zeros((pad,), bool)])
                 for m in nulls]
    valid = np.arange(bucket) < n
    return Page(schema, tuple(cols), tuple(nulls), valid)


def padded_page(schema, cols, nulls) -> Page:
    """Host rows -> device Page padded to a power-of-two shape bucket (the
    eager-staging form: fragment gathers and the join build path want the
    page on device immediately)."""
    page = padded_host_page(schema, cols, nulls)
    return Page(schema,
                tuple(jnp.asarray(c) if getattr(c, "dtype", None) != object
                      else c for c in page.columns),
                tuple(None if m is None else jnp.asarray(m)
                      for m in page.null_masks),
                jnp.asarray(page.valid))
