"""Array/map value runtime: span-packed columns over element heaps.

TPU-first re-design of the reference's nested blocks (spi/block/ArrayBlock.java,
MapBlock.java): a column of array(T) is ONE fixed-width int64 device column of
packed spans (start << 24 | length) referencing an element heap.  The heap is
position-independent, so every row-shuffling operator (filter compaction, join
gather, sort, exchange) moves 8-byte spans and never touches elements — the
same late-materialization trick as dictionary strings.  Heaps ride the
planner's per-channel dictionary slot (ColumnInfo.dict / Project.dicts), whose
``decode`` hook the result path already calls.

Element access (subscript, contains, unnest) gathers from the heap, embedded in
the traced program as a constant — like the dictionary LUTs, acceptable for the
SQL-surface scale arrays run at (the columnar hot path stays span-only).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = ["SPAN_BITS", "ArrayData", "MapData", "pack_span", "span_start",
           "span_len", "encode_arrays", "live_indices", "gather_rows",
           "compact_rows", "append_rows"]

SPAN_BITS = 24  # max 16M elements per array; 2^39 heap rows
_LEN_MASK = (1 << SPAN_BITS) - 1


def pack_span(start, length):
    return (start << SPAN_BITS) | length


def span_start(span):
    return span >> SPAN_BITS


def span_len(span):
    return span & _LEN_MASK


@dataclasses.dataclass
class ArrayData:
    """Heap for one array(T) column (host-side numpy; device-transferred at the
    access sites).  ``elem_dict`` decodes string elements; plugged into the
    engine's dictionary slot so results decode through the normal path."""

    values: np.ndarray  # flattened element heap
    elem_type: object
    elem_dict: object = None
    max_len: int = 0

    def decode(self, spans: np.ndarray) -> np.ndarray:
        """Span column -> object array of python lists (result materialization)."""
        starts = np.asarray(span_start(spans))
        lens = np.asarray(span_len(spans))
        vals = self.values
        if self.elem_dict is not None:
            vals = self.elem_dict.decode(vals.astype(np.int64))
        elif getattr(self.elem_type, "is_decimal", False):
            vals = vals.astype(np.float64) / (10 ** self.elem_type.scale)
        out = np.empty(len(starts), dtype=object)
        for i, (s, l) in enumerate(zip(starts.tolist(), lens.tolist())):
            out[i] = list(vals[s:s + l].tolist())
        return out


@dataclasses.dataclass
class MapData:
    """Parallel key/value heaps for one map(K, V) column."""

    keys: np.ndarray
    values: np.ndarray
    key_type: object
    value_type: object
    key_dict: object = None
    value_dict: object = None
    max_len: int = 0

    @staticmethod
    def _decode_side(vals, d, t):
        """Dictionary ids -> strings; scaled decimals -> floats; DATE /
        TIMESTAMP epoch ints -> datetime64 (CLAUDE.md: temporal values decode
        at every result surface)."""
        if d is not None:
            return d.decode(vals.astype(np.int64))
        if getattr(t, "is_decimal", False):
            return vals.astype(np.float64) / (10 ** t.scale)
        name = getattr(t, "name", "")
        if name == "date":
            return vals.astype(np.int64).astype("datetime64[D]")
        if name.startswith("timestamp"):
            unit = {0: "s", 3: "ms", 6: "us", 9: "ns"}.get(
                getattr(t, "precision", None))
            if unit:
                return vals.astype(np.int64).astype(f"datetime64[{unit}]")
        return vals

    def decode(self, spans: np.ndarray) -> np.ndarray:
        starts = np.asarray(span_start(spans))
        lens = np.asarray(span_len(spans))
        ks = self._decode_side(self.keys, self.key_dict, self.key_type)
        vs = self._decode_side(self.values, self.value_dict, self.value_type)
        out = np.empty(len(starts), dtype=object)
        for i, (s, l) in enumerate(zip(starts.tolist(), lens.tolist())):
            out[i] = dict(zip(ks[s:s + l].tolist(), vs[s:s + l].tolist()))
        return out


def encode_arrays(rows, elem_dtype, encoder=None):
    """Python lists (None allowed) -> (spans int64, null mask, heap ndarray).

    The storage path (memory connector INSERT, literal folding): elements
    flatten into one heap in row order; each row's span points at its slice."""
    spans = np.zeros(len(rows), np.int64)
    nulls = np.zeros(len(rows), bool)
    flat: list = []
    for i, r in enumerate(rows):
        if r is None:
            nulls[i] = True
            continue
        vals = [encoder(v) for v in r] if encoder else list(r)
        spans[i] = pack_span(len(flat), len(vals))
        flat.extend(vals)
    heap = np.asarray(flat, dtype=elem_dtype) if flat else np.zeros(0, elem_dtype)
    return spans, (nulls if nulls.any() else None), heap


def live_indices(valid, size: int):
    """Lane numbers of the first ``size`` live lanes of ``valid``, ascending
    (arrival order kept), and the live count: ``(idx int32[size], count int32
    scalar)``.  ``idx`` beyond ``count`` is filler ``n - 1``: in bounds, and the
    whole vector stays sorted, so a gather may be promised both.

    THE index of every device-side row compaction (compact_rows,
    exec/pages._compact_part*, hashagg.compact_groups).  Scatter-free on
    purpose: on a v5e an XLA scatter (and ``jnp.nonzero(size=)``, whose
    ``bincount`` is a scatter-add) pays 175-290 ns for EVERY input lane, a
    sort key under 2 ns; the data then moves by gathers of the OUTPUT size.
    One single-key int32 sort (int32 on purpose: under x64 ``arange`` is int64
    and doubles the sort); dead lanes carry the key ``n`` and sort behind
    every live one.  The one form kept of three measured (PERF.md section 6,
    PR 26): at 6.3 M lanes 11.5 ms whatever ``size`` is, where ``nonzero``
    takes 400-440 ms; a ``searchsorted`` in ``cumsum(valid)`` wins only for
    ``size`` under about 32 K of millions of lanes, by at most 9 ms a call."""
    n = valid.shape[0]  # at least one lane
    lanes = jnp.where(valid, lax.iota(jnp.int32, n), jnp.int32(n))
    idx = jnp.minimum(jnp.sort(lanes)[:size], n - 1)
    if size > n:
        idx = jnp.concatenate([idx, jnp.full((size - n,), n - 1, jnp.int32)])
    return idx, jnp.sum(valid, dtype=jnp.int32)


@partial(jax.jit, static_argnums=(1,))  # compile-ok: module-level kernel; one program a (key dtypes, lanes, count)
def first_rows(keys, count: int):
    """Lane numbers of the first ``count`` rows in the order of
    ``jnp.lexsort(keys)`` (last key most significant, ties in lane order), by
    ``count`` rounds of a lexicographic arg-min: reductions only, no sort.  For
    the TopN of a few rows: the TPU compiler takes one to two minutes over a
    sort that carries a payload (126 s for the five-key lexsort of a cold
    TPC-H SF10 q3's 113,513 groups, 30-60 s for one ``argsort``; PERF.md PR
    27), and every new lane count is a new program.  Keys are integers or
    bools (a NaN never equals the minimum it poisons)."""
    n = keys[0].shape[0]
    lanes = lax.iota(jnp.int32, n)
    ordered = tuple(k.astype(jnp.int8) if k.dtype == bool else k
                    for k in reversed(keys))

    def pick(i, carry):
        alive, out = carry
        cand = alive
        for k in ordered:
            least = jnp.min(jnp.where(cand, k, jnp.iinfo(k.dtype).max))
            cand = cand & (k == least)
        j = jnp.min(jnp.where(cand, lanes, n))
        return alive & (lanes != j), out.at[i].set(j)

    _, out = lax.fori_loop(
        0, count, pick, (jnp.ones((n,), bool), jnp.zeros((count,), jnp.int32)))
    return out


def gather_rows(a, idx):
    """``a[idx]`` for an ``idx`` of `live_indices`: in bounds and sorted."""
    return a.at[idx].get(mode="promise_in_bounds", indices_are_sorted=True)


def compact_rows(arrays, valid, out_len: int):
    """Order-preserving masked-lane pack, THE shared filter->compaction step:
    live lanes move to the front of ``out_len``-sized outputs (zeros beyond
    the live count, overflow lanes dropped), ``None`` entries pass through.
    Returns (packed tuple, live-count device scalar).

    Consumers: the pipeline-boundary compaction and streaming-agg pre-pack
    (exec/local_executor) and the exchange bucketizer (ops/exchange).
    Round-13 backend split: `pallas_kernels.compact_columns` (block prefix-sum
    + one-hot matmul, one kernel launch for the whole page) when
    `use_pallas()` and the packed output fits the VMEM gate; the XLA
    index-then-gather below otherwise.  Byte-identical by contract
    (tests/test_pallas_kernels.py pins it)."""
    from . import pallas_kernels as pk

    arrs = [a for a in arrays if a is not None]
    if not arrs:
        return tuple(arrays), jnp.sum(valid)
    n = valid.shape[0]
    if pk.compact_enabled(n, out_len, arrs):
        packed, total = pk.compact_columns(tuple(arrs), valid, out_len)
        it = iter(packed)
        return tuple(None if a is None else next(it) for a in arrays), total
    # XLA path: `live_indices`, then one gather of min(out_len, n) rows per
    # array.  The filler lanes of idx gather a real row, so everything beyond
    # the live count is zeroed (and zero-padded when out_len > n)
    k = min(out_len, n)
    if k == 0:  # nothing to gather from, or nothing to keep
        return tuple(None if a is None else jnp.zeros((out_len,), a.dtype)
                     for a in arrays), jnp.sum(valid, dtype=jnp.int32)
    idx, total = live_indices(valid, k)
    live = jnp.arange(k, dtype=jnp.int32) < total
    packed = tuple(
        None if a is None
        else jnp.pad(jnp.where(live, gather_rows(a, idx), jnp.zeros((), a.dtype)),
                     (0, out_len - k))
        for a in arrays)
    return packed, total


def append_rows(bufs, cursor, arrays, valid):
    """Masked append into fixed-capacity receive buffers — the device-resident
    exchange's accumulation step.  ``bufs[i]`` is a [cap + 1] buffer whose last
    slot is a drop sink; live lanes of ``arrays`` (compacted via
    ``compact_rows``, so arrival order is preserved) land at
    ``cursor .. cursor + count - 1``.  Rows past ``cap`` collapse into the drop
    sink — slots below the cursor are never corrupted, the overflow flag is the
    only casualty — so the driver can discard the run and retry at a bigger
    capacity, exactly like the exchange bucket ladder.  ``arrays`` must be
    all-populated (callers fill absent null masks with zeros: buffer identity
    across batches needs a uniform pytree).  Returns (new_bufs, new_cursor,
    overflowed)."""
    packed, cnt = compact_rows(tuple(arrays), valid, valid.shape[0])
    cap = bufs[0].shape[0] - 1
    idx = jnp.arange(valid.shape[0], dtype=cursor.dtype)
    # live packed lanes (idx < cnt) write sequentially from the cursor; dead
    # lanes and overflow lanes route to the drop sink at cap.  Destinations
    # below cap are unique, so last-wins scatter is exact.
    dst = jnp.where(idx < cnt, jnp.minimum(cursor + idx, cap), cap)
    new_bufs = tuple(b.at[dst].set(p) for b, p in zip(bufs, packed))
    new_cursor = cursor + cnt
    return new_bufs, new_cursor, new_cursor > cap


def unnest_indices(lens, total: int):
    """Expansion map for UNNEST (device): output slot j -> (input row i,
    ordinal k, in_range).  Same searchsorted shape as the multi-match join
    expansion (reference: operator/unnest/UnnestOperator.java's per-position
    entry counts).  ``lens`` = per input row output count (0 for invalid rows);
    ``total`` is the static output capacity."""
    incl = jnp.cumsum(lens)
    j = jnp.arange(total, dtype=incl.dtype)
    row = jnp.searchsorted(incl, j, side="right").astype(jnp.int32)
    row_safe = jnp.minimum(row, lens.shape[0] - 1)
    before = incl[row_safe] - lens[row_safe]
    ordinal = (j - before).astype(jnp.int32)
    in_range = j < incl[-1] if lens.shape[0] else jnp.zeros((total,), bool)
    return row_safe, ordinal, in_range
