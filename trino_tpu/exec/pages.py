"""The steps over one page: pack, concatenate, sort, top-N, limit, pull, materialise.

Each is a free function of a page (or of a stream's pages) in a host and, where the page
lives there, a device form; the join's build facts that both executors read off a build
page are here too.  Everything that launches or pulls does so through ``exec/boundary``.
"""

from __future__ import annotations

import dataclasses
from decimal import Decimal
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..execution import tracing
from ..ops.arrays import compact_rows, first_rows, gather_rows, live_indices
from ..ops.hashjoin import JoinTable
from ..page import Page
from ..sql import plan as P
from ..types import ArrayType, DecimalType, MapType, TimestampType
from .boundary import _coalesced_batches, _host, _jit


@dataclasses.dataclass
class MaterializedResult:
    """Host-side query result (reference: testing MaterializedResult)."""

    names: tuple
    types: tuple
    columns: list  # numpy arrays, decoded (strings as objects, decimals as floats)
    raw_columns: list  # undecoded numpy arrays (dict ids / scaled ints)

    def __len__(self):
        return 0 if not self.columns else len(self.columns[0])

    def rows(self):
        return list(zip(*self.columns))

    def to_pandas(self):
        import pandas as pd

        return pd.DataFrame({n: c for n, c in zip(self.names, self.columns)})


def _compact_page(cols, nulls, valid, bucket: int):
    """_compacted_stream's step: the shared masked-lane pack
    (ops/arrays.compact_rows: live-lane index then gathers, or the round-13
    Pallas kernel) of a page into ``bucket`` lanes, with its validity mask."""
    packed, total = compact_rows(tuple(cols) + tuple(nulls), valid, bucket)
    cvalid = jnp.arange(bucket) < total
    return packed[:len(cols)], packed[len(cols):], cvalid


def _gather_part(cols, nulls, idx):
    return (tuple(gather_rows(c, idx) for c in cols),
            tuple(None if n is None else gather_rows(n, idx) for n in nulls))


@partial(_jit, static_argnums=(3,))
def _compact_part(cols, nulls, valid, size: int):
    """Gather valid rows into dense ``size``-bounded arrays (device-side);
    lanes beyond the live count hold a real row, the caller masks them."""
    return _gather_part(cols, nulls, live_indices(valid, size)[0])


@partial(_jit, static_argnums=(3,))
def _compact_part_sized(cols, nulls, valid, size: int):
    """_compact_part plus the compacted part's own validity mask
    (``arange(size) < live``), computed INSIDE the same dispatch — what lets
    _concat_stream's single-part fast path skip the _concat_all dispatch
    without any uncounted eager device work."""
    idx, live = live_indices(valid, size)
    return _gather_part(cols, nulls, idx) \
        + (jnp.arange(size, dtype=jnp.int32) < live,)


def _concat_stream(stream, batch: int = 1) -> Page:
    """Materialize a streaming segment into a single device page (compacted).

    Compaction runs ON DEVICE (live-lane index + gathers per page, then a device
    concat): pages never cross to the host between pipeline-breaking stages —
    device->host bandwidth is the scarce resource, not FLOPs (reference analog:
    pages stay in worker memory between operators).  ``batch``>1 coalesces shape-uniform pages: each group
    of K splits runs its transform in ONE dispatch (and its compaction and
    live-count sync amortize K-fold with it)."""
    step = stream.jitted()
    bstep = stream.jitted_batch() if batch > 1 else None
    parts = []
    staged, sums = [], []

    def _drain():
        # one batched host sync per chunk of pages (per-page int() is a
        # blocking device->host sync per page); chunking bounds how many
        # uncompacted pages sit on device at once.  A count the source page
        # already knew on the host (a host int among ``sums``) is not pulled
        unknown = [c for c in sums if not isinstance(c, int)]
        pulled = iter(_host(unknown, site="compact.counts") if unknown else ())
        for (cols, nulls, valid), c in zip(staged, sums):
            n = c if isinstance(c, int) else int(next(pulled))
            if n == 0:
                continue
            if any(isinstance(c, np.ndarray) and c.dtype == object
                   for c in cols):
                # exact wide-decimal columns: host compaction (cannot trace);
                # the object columns are host-resident — one batched pull
                # covers the masks (eager jnp ops may have produced them)
                got = _host([valid] + [m for m in nulls if m is not None],
                            site="compact.object")
                v, rest = got[0], got[1:]
                ccols = tuple(np.asarray(c)[v] for c in cols)  # host-ok: object cols
                cnulls = tuple(None if m is None else rest.pop(0)[v]
                               for m in nulls)
                parts.append((ccols, cnulls, None, n))
                continue
            bucket = min(max(1 << max(n - 1, 1).bit_length(), 1024),
                         valid.shape[0])
            if isinstance(c, int) and bucket == valid.shape[0]:
                # packed already, at this very bucket: the pack would move
                # no row
                parts.append((cols, nulls, valid, n))
                continue
            ccols, cnulls, pvalid = _compact_part_sized(
                cols, nulls, valid, bucket)
            tracing.record_compaction(valid.shape[0], bucket)
            parts.append((ccols, cnulls, pvalid, n))
        staged.clear()
        sums.clear()

    for group, live in _coalesced_batches(stream.pages(), batch):
        cols, nulls, valid = step(group[0]) if live is None \
            else bstep(group, live)
        staged.append((cols, nulls, valid))
        if live is None and group[0].live is not None and stream.passes_valid:
            sums.append(group[0].live)  # a packed page, still packed
        else:
            sums.append(jnp.sum(valid, dtype=jnp.int32))
        if len(staged) >= 8:
            _drain()
    _drain()
    if not parts:
        cols = tuple(jnp.zeros((0,), f.type.dtype) for f in stream.schema.fields)
        return Page(stream.schema, cols, tuple(None for _ in cols), None)
    # ONE jitted dispatch for the whole multi-column concat instead of one
    # top-level concat (a dispatch each) per column
    ncols = len(parts[0][0])
    has_null = tuple(any(cnulls[ci] is not None for _, cnulls, _, _ in parts)
                     for ci in range(ncols))
    if len(parts) == 1 and parts[0][2] is not None:
        # single part (single-page stream, or a buffer-pool hit serving the
        # whole scan as one page): there is nothing to concatenate — the
        # compacted part IS the page, and its validity mask was computed
        # inside the _compact_part_sized dispatch (no extra device op at all)
        ccols, cnulls, pvalid, n = parts[0]
        return Page(stream.schema, ccols, cnulls, pvalid, n)
    if any(isinstance(c, np.ndarray) and c.dtype == object
           for c in parts[0][0]):
        # host concat for exact wide-decimal parts (host-compacted above)
        cols_out = tuple(np.concatenate([p[0][ci] for p in parts])
                         for ci in range(ncols))
        nulls_out = tuple(
            np.concatenate([p[1][ci] if p[1][ci] is not None
                            else np.zeros(p[0][ci].shape[0], bool)
                            for p in parts]) if has_null[ci] else None
            for ci in range(ncols))
        return Page(stream.schema, cols_out, nulls_out, None)
    ns = jnp.asarray([n for _, _, _, n in parts], jnp.int32)
    cols_out, nulls_out, valid = _concat_all(
        tuple((ccols, cnulls) for ccols, cnulls, _, _ in parts), ns, has_null)
    return Page(stream.schema, cols_out, nulls_out, valid)


@partial(_jit, static_argnums=(1,))
def _concat_bindings_parts(parts, has_null):
    """ONE dispatch concatenating a fused bindings batch's per-page parts
    along the ROW axis (axis 1 — axis 0 is the requests lane, round 21).
    No per-part compaction: the batched path targets the pruned point-lookup
    shape (one or a few splits after union pruning), where a compaction's
    count sync would cost more round-trips than it saves lanes."""
    ncols = len(parts[0][0])
    cols = tuple(jnp.concatenate([p[0][ci] for p in parts], axis=1)
                 for ci in range(ncols))
    nulls = tuple(
        jnp.concatenate([p[1][ci] if p[1][ci] is not None
                         else jnp.zeros(p[0][ci].shape, bool)
                         for p in parts], axis=1)
        if has_null[ci] else None
        for ci in range(ncols))
    valid = jnp.concatenate([p[2] for p in parts], axis=1)
    return cols, nulls, valid


@partial(_jit, static_argnums=(2,))
def _concat_all(part_arrays, ns, has_null):
    """ONE dispatch for the whole multi-column concat.  Parts
    keep their pow2 bucket shapes — live-row counts stay TRACED (a validity mask
    marks the tail padding), so the executable caches per bucket-shape
    combination instead of recompiling per exact row count."""
    cols_out, nulls_out = [], []
    ncols = len(part_arrays[0][0])
    for ci in range(ncols):
        cols_out.append(jnp.concatenate(
            [ccols[ci] for (ccols, cnulls) in part_arrays]))
        if has_null[ci]:
            nulls_out.append(jnp.concatenate(
                [(cnulls[ci] if cnulls[ci] is not None
                  else jnp.zeros((ccols[ci].shape[0],), bool))
                 for (ccols, cnulls) in part_arrays]))
        else:
            nulls_out.append(None)
    valid = jnp.concatenate(
        [jnp.arange(part[0][0].shape[0], dtype=jnp.int32) < ns[i]
         for i, part in enumerate(part_arrays)])
    return tuple(cols_out), tuple(nulls_out), valid


def _build_key_stats(build_page: Page, key_channels):
    """(build_has_null_key, live build rows) — device reductions, ONE batched
    scalar sync (pulling capacity-sized masks to host costs megabytes)."""
    if build_page.capacity == 0:
        return False, 0
    valid = build_page.valid_mask()
    stats = [jnp.sum(valid, dtype=jnp.int64)]
    for ch in key_channels:
        nm = build_page.null_masks[ch]
        if nm is not None:
            stats.append(jnp.any(nm & valid))
    got = _host(stats, site="join.build.nulls")
    has_null = any(bool(x) for x in got[1:])
    return has_null, int(got[0])


def _build_null_stats(build_page: Page, key_channels):
    """(build_has_null_key, build_nonempty) for null-aware anti joins."""
    has_null, rows = _build_key_stats(build_page, key_channels)
    return has_null, rows > 0


def _null_aware_anti(node, anti_valid, nulls, build_has_null, build_nonempty):
    """NOT IN three-valued logic (reference: null-aware anti joins): a NULL among the
    build keys, or a NULL probe key vs a non-empty build, makes the predicate UNKNOWN
    (row rejected).  NOT EXISTS anti joins (null_aware=False) skip this."""
    if not node.null_aware:
        return anti_valid
    if build_has_null:
        return jnp.zeros_like(anti_valid)
    if build_nonempty:
        for i in node.left_keys:
            if nulls[i] is not None:
                anti_valid = anti_valid & ~nulls[i]
    return anti_valid


def _gather_build(table: JoinTable, row_ids, matched, kind):
    """Fetch build-side columns for probe matches; unmatched rows -> nulls (left join)."""
    safe = jnp.where(matched, row_ids, 0)
    cols, nulls = [], []
    for c, nmask in zip(table.build_columns, table.build_null_masks):
        cols.append(c[safe])
        base = jnp.zeros_like(matched) if nmask is None else nmask[safe]
        nulls.append((base | ~matched) if kind == "left" else (None if nmask is None else base))
    return tuple(cols), tuple(nulls)


def _values_page(node: P.Values) -> Page:
    cols = []
    for ci, f in enumerate(node.schema.fields):
        cols.append(jnp.asarray(np.array([r[ci] for r in node.rows]), f.type.dtype))
    return Page(node.schema, tuple(cols), tuple(None for _ in cols), None)


def _split_base_rows(conn, table: str, splits) -> list:
    """Base rows each split stands for, by the connector's own count (host
    ints: ``row_count`` shared out over the split ranges; lineitem's ranges
    are orders, at ``row_count``'s lines an order).  Zeros when the connector
    does not say."""
    if not (hasattr(conn, "row_count") and hasattr(conn, "table_bound")) \
            or not all(hasattr(s, "lo") and hasattr(s, "hi") for s in splits):
        return [0] * len(splits)
    bound = max(int(conn.table_bound(table)), 1)
    per = int(conn.row_count(table)) / bound
    return [int(max(min(int(s.hi), bound) - int(s.lo), 0) * per)
            for s in splits]


def _page_bytes(page: Page) -> int:
    """Device bytes held by a page's columns + null masks."""
    total = 0
    for c in page.columns:
        total += page.capacity * np.dtype(c.dtype).itemsize
    total += sum(page.capacity for n in page.null_masks if n is not None)
    return total


@partial(_jit, site="page.head", static_argnums=(2,))
def _head_rows(cols, nulls, count: int):
    return (tuple(c[:count] for c in cols),
            tuple(None if n is None else n[:count] for n in nulls))


def _host_page(page: Page, site="page"):
    """(valid, cols, nulls) as numpy, fetched in ONE batched transfer.  A page with
    no validity mask gets a host-side ones() — no device fetch fabricated for it."""
    if page.live is not None and page.live < page.capacity \
            and all(isinstance(c, jax.Array) for c in page.columns):
        # a packed device page: the wire carries its live rows, not its bucket
        page = Page(page.schema, *_head_rows(page.columns, page.null_masks,
                                             page.live))
    nc = len(page.columns)
    has_valid = page.valid is not None
    got = _host(list(page.columns) + list(page.null_masks)
                + ([page.valid] if has_valid else []), site=site)
    valid = got[-1] if has_valid else np.ones((page.capacity,), bool)
    return valid, got[:nc], got[nc:nc + len(page.null_masks)]


def _sort_page(page: Page, keys, dicts=None) -> Page:
    """Host-side lexicographic sort (result sets; large distributed sort is separate).

    Dictionary-encoded string channels sort by *decoded string order*, not id order
    (ids are assigned in dictionary, not collation, order)."""
    valid, pcols, pnulls = _host_page(page)
    cols = [c[valid] for c in pcols]
    nulls = [None if n is None else n[valid] for n in pnulls]
    sort_cols = list(cols)
    for k in keys:
        d = dicts[k.channel] if dicts is not None else None
        if d is not None and page.schema.fields[k.channel].type.is_string:
            sort_cols[k.channel] = d.decode(cols[k.channel]).astype(str)
    order = np.arange(len(cols[0]) if cols else 0)
    for k in reversed(keys):
        c = sort_cols[k.channel][order]
        nm_k = nulls[k.channel]
        if nm_k is not None and len(c):
            # NULL rows hold arbitrary fill values: pin them all to one value so the
            # secondary-key order among NULL rows survives this stable pass
            c = c.copy()
            c[nm_k[order]] = c[0]
        if not np.issubdtype(c.dtype, np.number):
            _, c = np.unique(c, return_inverse=True)  # string -> collation rank
        if not k.ascending:
            c = -c.astype(np.int64 if np.issubdtype(c.dtype, np.integer) else np.float64)
        order = order[np.argsort(c, kind="stable")]
        nm = nulls[k.channel]
        if nm is not None:
            # null placement outranks the value ordering for this key
            ind = nm[order].astype(np.int8)
            if k.nulls_first:
                ind = -ind
            order = order[np.argsort(ind, kind="stable")]
    # stay on the host: downstream consumers (limit/materialize) are host-side too,
    # so pushing back to the device would just buy extra round-trips
    new_cols = tuple(c[order] for c in cols)
    new_nulls = tuple(None if n is None else n[order] for n in nulls)
    return Page(page.schema, new_cols, new_nulls, None)


def _topn_page(page: Page, keys, count: int, dicts=None) -> Page:
    """ORDER BY + LIMIT: argpartition down to ~count candidates on the primary key,
    then full lexicographic sort of the survivors (host-side; result-set sized)."""
    valid, pcols, pnulls = _host_page(page)
    n = int(valid.sum())
    if n > max(4 * count, 1024) and len(keys) >= 1:
        k0 = keys[0]
        c = pcols[k0.channel][valid]
        nm = pnulls[k0.channel]
        d = dicts[k0.channel] if dicts is not None else None
        if nm is None and d is None and np.issubdtype(c.dtype, np.number) and not (
                np.issubdtype(c.dtype, np.floating) and np.isnan(c).any()):
            # (NaN keys skip the prefilter: partition would poison the cutoff)
            v = c if k0.ascending else (
                -c.astype(np.int64) if np.issubdtype(c.dtype, np.integer)
                else -c.astype(np.float64))
            # ties on the primary key require keeping ALL rows equal to the cutoff
            cutoff = np.partition(v, count - 1)[count - 1]
            keep_local = v <= cutoff
            idx = np.nonzero(valid)[0][keep_local]
            mask = np.zeros_like(valid)
            mask[idx] = True
            page = Page(page.schema,
                        tuple(col[mask] for col in pcols),
                        tuple(None if m is None else m[mask] for m in pnulls), None)
    return _limit_page(_sort_page(page, keys, dicts), count)


def _collation_rank_lut(d):
    """id -> collation-rank LUT for a values dictionary, cached on the
    Dictionary instance (ids are insertion-ordered, ORDER BY compares decoded
    values).  Shared by listagg ordering, max_by/min_by ranking, and device
    TopN."""
    lut = getattr(d, "_rank_lut", None)
    if lut is None or len(lut) != len(d.values):
        lut = np.empty(len(d.values), np.int64)
        order = np.argsort(np.asarray(d.values, dtype=object))  # host-ok: dict values
        lut[order] = np.arange(len(d.values))
        try:
            object.__setattr__(d, "_rank_lut", lut)
        except Exception:
            pass
    return lut


def _narrow_pull_dtype(d):
    """Narrowest integer dtype holding every id of a VALUES dictionary, known
    statically from the dictionary length (ids are non-negative and
    < len(values)) — no device sync needed.  Lets result pulls ship a
    25-value nation column as int8 instead of int64: the result transfer
    is the warm join query's dominant remaining pull, and
    dictionary ids are where its bytes are compressible for free."""
    if d is None or getattr(d, "values", None) is None:
        return None
    n = len(d.values)
    for dt in (np.int8, np.int16, np.int32):
        if n - 1 <= np.iinfo(dt).max:
            return dt
    return None


# a TopN of at most this many rows, over at most this many lane-rounds, is
# selected by ops/arrays.first_rows and not sorted: a round reads every lane
# twice a key, so 2^31 lane-rounds stay under a tenth of a second on a v5e
TOPN_SELECT_MAX = 1024
TOPN_SELECT_WORK = 1 << 31


def _sort_page_device(page: Page, keys, dicts=None):
    """Device-side FULL sort: lexsort on device, then pull exactly the live
    rows — no dead lanes or pow2 padding, no validity mask (every fetched row
    is live by construction), dictionary ids narrowed and bool masks
    bit-packed on the wire.  The host path (_sort_page) pulls every lane of
    the page at full width before sorting; for a device-resident aggregate
    output that is pure transfer waste (measured: warm SF1 q9's ORDER BY pull
    dropped 4200 -> 3041 bytes).  A page that does not know its live count
    (``Page.live``) pays one scalar sync for it.
    Returns None (host fallback) on host pages or unrankable keys, like
    _topn_page_device."""
    return _topn_page_device(page, keys, None, dicts)


def _rank_lut_device(d):
    """``_collation_rank_lut`` as the sort program's argument: a small one is
    kept on the device beside the host copy (a dashboard sorts by the same
    few-valued column every statement), a large one is handed over per sort
    as before and pins nothing."""
    rank = _collation_rank_lut(d)
    if rank.nbytes > 1 << 20:
        return rank
    dev = getattr(d, "_rank_lut_device", None)
    if dev is None or dev.shape[0] != len(rank):
        dev = jax.device_put(rank)  # device-ok: a kept id->rank table of at most 1 MiB, an argument of the sort program, not a scan's page
        try:
            object.__setattr__(d, "_rank_lut_device", dev)
        except Exception:
            pass
    return dev


@partial(_jit, site="sort.count")
def _live_count(valid):
    return jnp.sum(valid, dtype=jnp.int64)


@partial(_jit, site="sort.rows", static_argnums=(4, 5, 6, 7, 8))
def _sorted_rows(cols, nulls, valid, luts, keys, count, select, narrow,
                 fetch_valid):
    """The device part of a Sort or TopN as ONE program a (schema, sort keys,
    count, capacity, which masks exist): collation ranks, the lex keys, a
    keys-only sort (or ``first_rows``' selection) with the gathers of every
    column and mask behind it, the narrowing casts and the bit-packing: what
    the host then pulls, in the order it unpacks.  ``keys`` is ``(channel,
    ascending, nulls_first, position in luts or -1)`` in ORDER BY order,
    ``narrow`` the wire dtype of each column or None."""
    lex = []
    for channel, ascending, nulls_first, lut in reversed(keys):
        c = cols[channel]
        if lut >= 0:
            rank = luts[lut]
            c = rank[jnp.clip(c, 0, max(rank.shape[0] - 1, 0))]
        if c.dtype == bool:
            c = c.astype(jnp.int8)
        nm = nulls[channel]
        if nm is not None:
            # NULL lanes hold arbitrary fill values: pin them to one constant
            # so secondary keys keep breaking ties among NULL rows (the host
            # path's equivalent pin in _sort_page)
            c = jnp.where(nm, jnp.zeros((), c.dtype), c)
        if not ascending:
            c = ~c if jnp.issubdtype(c.dtype, jnp.integer) else -c
        lex.append(c)
        if nm is not None:
            # null placement outranks the value ordering for this key (a key
            # without a mask has a constant indicator, which moves no row)
            ind = nm.astype(jnp.int8)
            lex.append(-ind if nulls_first else ind)
    if valid is not None:
        lex.append(~valid)  # invalid lanes last — top-count rows are live ones
    if select:
        idx = first_rows(tuple(lex), count)
    else:
        idx = jnp.lexsort(tuple(lex))[:count]
    fetch = [c[idx] if nd is None else c[idx].astype(nd)
             for c, nd in zip(cols, narrow)]
    # boolean masks ship BIT-packed (8x): the result pull
    # is byte-priced, and masks are the compressible half of a narrow result
    fetch += [jnp.packbits(nm[idx]) for nm in nulls if nm is not None]
    if fetch_valid:
        fetch.append(jnp.packbits(valid[idx]))
    return fetch


def _topn_page_device(page: Page, keys, count, dicts=None):
    """Device-side TopN: one lexsort over collation-ranked keys, gather the
    top ``count`` rows, transfer ONLY those.  The host path pulls the whole
    input page (often a 100k+-row aggregate output) before sorting, and
    that transfer is most of what such a query pulls (round-5 Q3 finding).  Returns None when the page is host-resident or a sort key
    cannot rank on device (formatter dictionaries, object-dtype decimals);
    the caller falls back to the host path.  Everything the device does is
    the one program ``_sorted_rows``; here is what the host decides from the
    page and the plan, and the pull."""
    if not page.capacity \
            or not all(isinstance(c, jax.Array) for c in page.columns):
        return None
    luts, spec, floating = [], [], False
    for k in keys:
        t = page.schema.fields[k.channel].type
        d = dicts[k.channel] if dicts is not None else None
        lut = -1
        if t.is_string:
            if d is None or getattr(d, "values", None) is None:
                return None
            lut = len(luts)
            luts.append(_rank_lut_device(d))
        elif jnp.issubdtype(page.columns[k.channel].dtype, jnp.floating):
            floating = True
        spec.append((k.channel, bool(k.ascending), bool(k.nulls_first), lut))
    # count=None (full device sort): fetch exactly the live rows.  A page
    # that knows its live count says it; any other syncs it through _host
    # (counted, batched-API) and only AFTER every rankability check above —
    # a fallback to the host path must not pay a wasted round-trip first.
    n = page.capacity
    live = n if page.valid is None else page.live
    if count is None and live is None:
        live = int(_host([_live_count(page.valid)], site="sort.count")[0])
    select = count is not None and count <= TOPN_SELECT_MAX \
        and count * n <= TOPN_SELECT_WORK and not floating
    # every fetched row is live by construction when the live count bounds
    # the fetch: no validity fetch and no filter then
    fetch_valid = live is None
    count = min(n if count is None else count, n if live is None else live)
    nc = len(page.columns)
    if not count:
        return Page(page.schema,
                    tuple(np.zeros((0,), c.dtype) for c in page.columns),
                    tuple(None if nm is None else np.zeros((0,), bool)
                          for nm in page.null_masks), None)
    # transfer-narrow dictionary-id columns (id bound known from the dict, no
    # sync); the schema dtype is restored host-side after the pull, so only
    # the wire format shrinks
    narrow = []
    for ci, c in enumerate(page.columns):
        nd = None
        if page.schema.fields[ci].type.is_string:
            nd = _narrow_pull_dtype(dicts[ci] if dicts is not None else None)
        if nd is not None and jnp.issubdtype(c.dtype, jnp.integer) \
                and np.dtype(nd).itemsize < np.dtype(c.dtype).itemsize:
            narrow.append(np.dtype(nd))
        else:
            narrow.append(None)
    got = _host(_sorted_rows(page.columns, page.null_masks, page.valid,
                             tuple(luts), tuple(spec), count, select,
                             tuple(narrow), fetch_valid), site="sort.pull")
    m = len(got[0]) if nc else 0

    def unpack(b):
        return np.unpackbits(np.asarray(b, np.uint8))[:m].astype(bool)  # host-ok

    pos = nc
    nulls = []
    for nm in page.null_masks:
        if nm is None:
            nulls.append(None)
        else:
            nulls.append(unpack(got[pos]))
            pos += 1
    cols = tuple(c if nd is None else c.astype(pc.dtype)
                 for c, nd, pc in zip(got[:nc], narrow, page.columns))
    if fetch_valid:
        v = unpack(got[pos])
        cols = tuple(c[v] for c in cols)
        nulls = [None if nm is None else nm[v] for nm in nulls]
    return Page(page.schema, cols, tuple(nulls), None)


def _limit_page(page: Page, count: int) -> Page:
    valid, pcols, pnulls = _host_page(page)
    cols = tuple(c[valid][:count] for c in pcols)
    nulls = tuple(None if n is None else n[valid][:count] for n in pnulls)
    return Page(page.schema, cols, nulls, None)


def _materialize(page: Page, dicts) -> MaterializedResult:
    valid, pcols, pnulls = _host_page(page)
    return _materialize_host(page.schema, valid, pcols, pnulls, dicts)


def _materialize_host(schema, valid, pcols, pnulls, dicts) \
        -> MaterializedResult:
    """Host-side result decode over already-pulled numpy arrays — shared by
    the single-statement pull above and the batched demux (round 21), which
    slices one [R, rows] pull into per-request lanes and decodes each lane
    through this exact function (byte-identity with serial by construction)."""
    names, types, columns, raw = [], [], [], []
    for i, f in enumerate(schema.fields):
        arr = pcols[i][valid]
        raw.append(arr)
        dec = arr
        if isinstance(f.type, DecimalType):
            if arr.dtype == object:
                # exact wide-decimal sums (Python ints past 2^63): decode via
                # decimal.Decimal so no precision is lost at the surface
                q = Decimal(10) ** f.type.scale
                dec = np.array([Decimal(int(v)) / q for v in arr.tolist()],
                               dtype=object)
            else:
                dec = arr.astype(np.float64) / (10**f.type.scale)
        elif f.type.is_string and dicts[i] is not None:
            dec = dicts[i].decode(arr)
        else:
            if isinstance(f.type, (ArrayType, MapType)) and dicts[i] is not None:
                dec = dicts[i].decode(arr)  # spans -> python lists / dicts
            elif f.type.name == "date":
                # epoch days -> date at the result surface (reference: client
                # protocol returns DATE values, not their day encoding)
                dec = arr.astype("datetime64[D]")
            elif isinstance(f.type, TimestampType):
                p = f.type.precision
                dec = (arr * 10 ** (6 - p)).astype("datetime64[us]") \
                    if p <= 6 else \
                    (arr * 10 ** (9 - p)).astype("datetime64[ns]")
        if pnulls[i] is not None:
            nm = pnulls[i][valid]
            dec = np.array([None if m else v for v, m in zip(dec.tolist(), nm)], dtype=object) \
                if nm.any() else dec
        names.append(f.name)
        types.append(f.type)
        columns.append(dec)
    return MaterializedResult(tuple(names), tuple(types), columns, raw)
