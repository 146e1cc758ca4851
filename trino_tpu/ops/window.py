"""Window function kernels: sorted segmented scans over partitions.

Reference: WindowOperator (operator/WindowOperator.java) sorts a PagesIndex by
(partition, order) keys and runs per-partition WindowFunction state machines row by row
(operator/window/*).  The TPU re-design computes ALL rows of a window function at once:

- one stable multi-key argsort puts partition rows adjacent and peer rows adjacent;
- partition / peer-group boundaries become boolean change masks;
- ranking functions are arithmetic over boundary prefix sums (cummax/cumsum);
- framed aggregates (default RANGE UNBOUNDED PRECEDING .. CURRENT ROW) are segmented
  prefix scans gathered at each row's peer-group end;
- results scatter back through the inverse permutation.

Everything is a dense sort/scan/gather — no per-row control flow, so XLA maps it onto
the TPU vector units directly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .hashagg import _extreme

__all__ = ["window_order", "segments", "row_number", "rank", "dense_rank",
           "segmented_scan_sum", "segmented_scan_minmax", "partition_total",
           "shift_in_partition"]


def window_order(key_cols, descending_flags):
    """Stable lexicographic sort permutation over key columns (first key primary)."""
    n = key_cols[0].shape[0]
    perm = jnp.arange(n, dtype=jnp.int32)
    for col, desc in reversed(list(zip(key_cols, descending_flags))):
        k = col[perm]
        if desc:
            if jnp.issubdtype(k.dtype, jnp.floating):
                k = -k
            else:
                k = -k.astype(jnp.int64)
        perm = perm[jnp.argsort(k, stable=True)]
    return perm


def segments(sorted_key_cols):
    """Boundary mask over sorted rows: True where a new group starts (row 0 included)."""
    n = sorted_key_cols[0].shape[0]
    new = jnp.zeros((n,), bool).at[0].set(True)
    for c in sorted_key_cols:
        new = new | jnp.concatenate([jnp.ones((1,), bool), c[1:] != c[:-1]])
    return new


def _starts(new):
    """Per-row index of its group's first row (cummax of marked starts)."""
    idx = jnp.arange(new.shape[0], dtype=jnp.int32)
    return jax.lax.cummax(jnp.where(new, idx, 0))


def _ends(new):
    """Per-row index of its group's last row (reverse cummin of marked ends)."""
    n = new.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    is_last = jnp.concatenate([new[1:], jnp.ones((1,), bool)])
    marked = jnp.where(is_last, idx, n - 1)
    return jnp.flip(jax.lax.cummin(jnp.flip(marked)))


def row_number(part_new):
    idx = jnp.arange(part_new.shape[0], dtype=jnp.int64)
    return idx - _starts(part_new) + 1


def rank(part_new, peer_new):
    return (_starts(peer_new) - _starts(part_new) + 1).astype(jnp.int64)


def dense_rank(part_new, peer_new):
    d = jnp.cumsum(peer_new.astype(jnp.int64))
    return d - d[_starts(part_new)] + 1


def segmented_scan_sum(vals, part_new, peer_new, dtype=None):
    """Running sum per row over RANGE UNBOUNDED PRECEDING .. CURRENT ROW (peers share
    the value at their group's last row)."""
    v = vals if dtype is None else vals.astype(dtype)
    csum = jnp.cumsum(v)
    start = _starts(part_new)
    base = jnp.where(start > 0, csum[jnp.maximum(start - 1, 0)], jnp.zeros((), v.dtype))
    return csum[_ends(peer_new)] - base


def segmented_scan_minmax(vals, part_new, peer_new, kind: str):
    """Running min/max with partition resets via an associative segmented scan."""
    seg_id = jnp.cumsum(part_new.astype(jnp.int32))
    op = jnp.minimum if kind == "min" else jnp.maximum

    def combine(a, b):
        sa, va = a
        sb, vb = b
        same = sa == sb
        return sb, jnp.where(same, op(va, vb), vb)

    _, scanned = jax.lax.associative_scan(combine, (seg_id, vals))
    return scanned[_ends(peer_new)]


def partition_total(vals, part_new, dtype=None):
    """Whole-partition aggregate broadcast to every partition row (no ORDER BY frame)."""
    v = vals if dtype is None else vals.astype(dtype)
    csum = jnp.cumsum(v)
    start = _starts(part_new)
    base = jnp.where(start > 0, csum[jnp.maximum(start - 1, 0)], jnp.zeros((), v.dtype))
    return csum[_ends(part_new)] - base


# ---------------------------------------------------------------------------- frames
# Explicit ROWS/RANGE BETWEEN frames (reference: operator/window/
# FramedWindowFunction.java + WindowPartition frame evaluation).  Bound kinds:
# "up" UNBOUNDED PRECEDING | "p" k PRECEDING | "cr" CURRENT ROW |
# "f" k FOLLOWING | "uf" UNBOUNDED FOLLOWING.


def frame_bounds(part_new, peer_new, frame, order_vals=None):
    """Per-row inclusive [lo, hi] global sorted indices of the frame.

    ROWS frames are index arithmetic clamped to the partition; RANGE frames
    with non-offset bounds use peer-group edges (CURRENT ROW in RANGE means
    "through my peers"); RANGE ``k PRECEDING/FOLLOWING`` bounds need
    ``order_vals`` — the single ORDER BY key's values in sorted order,
    ascending-normalized — and resolve by searchsorted over a
    partition-offset monotonic key (one global binary search instead of
    per-partition scans; reference: WindowPartition's value-based frame
    positions in operator/window/).  hi < lo encodes an empty frame."""
    unit, s_type, s_k, e_type, e_k = frame
    n = part_new.shape[0]
    i = jnp.arange(n, dtype=jnp.int32)
    p_start, p_end = _starts(part_new), _ends(part_new)
    if unit == "rows":
        lo = {"up": p_start, "p": i - s_k, "cr": i, "f": i + s_k}[s_type]
        hi = {"uf": p_end, "p": i - e_k, "cr": i, "f": i + e_k}[e_type]
    elif s_type in ("p", "f") or e_type in ("p", "f"):
        # value-offset RANGE bounds: build a globally-monotonic key
        # w = (v - vmin) + seg * span, where span exceeds any in-partition
        # value range plus the largest offset — values stay ordered within a
        # partition and every partition's keys sit strictly above the last
        v = order_vals
        seg = jnp.cumsum(part_new.astype(v.dtype if jnp.issubdtype(
            v.dtype, jnp.floating) else jnp.int64))
        vmin = jnp.min(v)
        span = (jnp.max(v) - vmin) + (max(s_k, e_k) + 1)
        base = (v - vmin) + seg * span
        w = base  # rows are sorted by (partition, v): w is non-decreasing

        def at(delta, side):
            q = base + delta
            r = jnp.searchsorted(w, q, side=side).astype(jnp.int32)
            return r if side == "left" else r - 1

        lo = {"up": p_start, "cr": _starts(peer_new)}.get(s_type)
        if lo is None:
            lo = at(-s_k if s_type == "p" else s_k, "left")
        hi = {"uf": p_end, "cr": _ends(peer_new)}.get(e_type)
        if hi is None:
            hi = at(e_k if e_type == "f" else -e_k, "right")
    else:  # range: peer-group granularity
        lo = {"up": p_start, "cr": _starts(peer_new)}[s_type]
        hi = {"uf": p_end, "cr": _ends(peer_new)}[e_type]
    lo = jnp.maximum(lo, p_start)
    hi = jnp.minimum(hi, p_end)
    return lo, hi


# ------------------------------------------------------------------ IGNORE NULLS
def nonnull_positions(valid):
    """(g, P): g[i] = 1-based count of non-null rows through i (global, sorted
    order); P[r] = global index of the r-th non-null row (P[0] is a sink).
    The navigation-function primitives below resolve IGNORE NULLS by rank
    arithmetic over (g, P) — dense cumsum + scatter + gather, no row loops
    (reference: the ignoreNulls paths of operator/window/LagFunction.java
    and friends, which walk row-by-row)."""
    n = valid.shape[0]
    g = jnp.cumsum(valid.astype(jnp.int32))
    P = jnp.zeros((n + 1,), jnp.int32).at[
        jnp.where(valid, g, 0)].set(jnp.arange(n, dtype=jnp.int32))
    return g, P


def shift_ignore_nulls(vals, valid, part_new, offset: int, default):
    """lag/lead over NON-NULL rows only: the k-th non-null row before (after)
    each row within its partition.  offset > 0 = lag, < 0 = lead."""
    if offset == 0:
        # offset 0 addresses the CURRENT row (reference: LagFunction with
        # offset 0); a NULL current value stays NULL even under IGNORE NULLS
        return vals, ~valid
    if offset < 0:
        # lead = lag over the reversed order; partition boundaries flip from
        # first-of-group marks to (reversed) last-of-group marks
        is_last = jnp.concatenate([part_new[1:], jnp.ones((1,), bool)])
        res, miss = shift_ignore_nulls(jnp.flip(vals), jnp.flip(valid),
                                       jnp.flip(is_last), -offset, default)
        return jnp.flip(res), jnp.flip(miss)
    n = vals.shape[0]
    g, P = nonnull_positions(valid)
    # rank of the target: non-nulls strictly before me, minus (offset-1)
    target = g - valid.astype(jnp.int32) - (offset - 1)
    cand = P[jnp.clip(target, 0, n)]
    ok = (target >= 1) & (cand >= _starts(part_new))
    return jnp.where(ok, vals[jnp.clip(cand, 0, n - 1)], default), ~ok


def framed_nth_nonnull(vals, valid, lo, hi, k: int, from_end: bool = False):
    """(value, missing): the k-th non-null row inside each row's [lo, hi]
    frame, counted from the start (or from the end for last_value)."""
    n = vals.shape[0]
    g, P = nonnull_positions(valid)
    before_lo = jnp.where(lo > 0, g[jnp.maximum(lo - 1, 0)], 0)
    in_frame = g[jnp.clip(hi, 0, n - 1)] - before_lo
    rank = jnp.where(jnp.asarray(from_end), before_lo + in_frame - (k - 1),
                     before_lo + k)
    cand = P[jnp.clip(rank, 0, n)]
    ok = (hi >= lo) & (in_frame >= k) & (rank >= 1)
    return jnp.where(ok, vals[jnp.clip(cand, 0, n - 1)],
                     jnp.zeros((), vals.dtype)), ~ok


def framed_sum(vals, lo, hi, dtype=None):
    """Sum over each row's [lo, hi] via difference of inclusive prefix sums
    (empty frames — hi < lo — yield 0)."""
    v = vals if dtype is None else vals.astype(dtype)
    csum = jnp.cumsum(v)
    hi_c = jnp.clip(hi, 0, v.shape[0] - 1)
    s = csum[hi_c] - jnp.where(lo > 0, csum[jnp.maximum(lo - 1, 0)],
                               jnp.zeros((), v.dtype))
    return jnp.where(hi >= lo, s, jnp.zeros((), v.dtype))


def framed_minmax(vals, lo, hi, kind: str):
    """Min/max over each row's [lo, hi] with a doubling sparse table:
    st[k][i] = min(v[i .. i+2^k-1]), query = combine of two overlapping
    power-of-two blocks — O(n log n) build, O(1) gathers per row, no
    data-dependent shapes.  Caller masks empty frames."""
    op = jnp.minimum if kind == "min" else jnp.maximum
    n = vals.shape[0]
    levels = max(int(n - 1).bit_length(), 1)
    st = [vals]
    for k in range(1, levels):
        half = 1 << (k - 1)
        prev = st[-1]
        shifted = jnp.concatenate([prev[half:], prev[-1:].repeat(half)])
        st.append(op(prev, shifted))
    stk = jnp.stack(st)  # [levels, n]
    length = jnp.maximum(hi - lo + 1, 1)
    # floor(log2(length)) via bit arithmetic (exact, unlike float log2)
    j = (jnp.ceil(jnp.log2(length.astype(jnp.float64) + 0.5)) - 1).astype(jnp.int32)
    j = jnp.clip(j, 0, levels - 1)
    lo_c = jnp.clip(lo, 0, n - 1)
    b = jnp.clip(hi - (1 << j) + 1, 0, n - 1)
    return op(stk[j, lo_c], stk[j, b])


def shift_in_partition(vals, part_new, offset: int, default):
    """lag (offset>0) / lead (offset<0) within the partition, sorted order."""
    n = vals.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    src = idx - offset
    src_clamped = jnp.clip(src, 0, n - 1)
    seg_id = jnp.cumsum(part_new.astype(jnp.int32))
    ok = (src >= 0) & (src < n) & (seg_id[src_clamped] == seg_id)
    return jnp.where(ok, vals[src_clamped], default), ~ok


def _window_spec_dicts(specs, dicts):
    """Output dictionaries per window spec: value-passing kinds inherit the
    argument channel's dictionary (shared by the local and distributed paths)."""
    return tuple(
        dicts[s.arg] if s.kind in ("min", "max", "lag", "lead", "first_value",
                                   "last_value") and s.arg is not None else None
        for s in specs)


def _window_sort_passes(specs, nulls, padded):
    """Stable argsorts ``_window_kernel`` runs over its page (``ops/window.
    window_order``: one a key column): for each distinct (partition, order)
    clause (specs that share one share its sort) the pad mask of a page with
    invalid rows, then every partition and order channel, a nullable one with
    its NULL indicator before it."""
    return sum(int(padded) + sum(1 + (nulls[c] is not None) for c in
                                 list(part) + [k.channel for k in order])
               for part, order in {(s.partition, s.order) for s in specs})


def _window_kernel(specs, cols, nulls, valid=None):
    """Evaluate all window specs over one materialized page (this module's primitives).

    Sort permutations are shared across specs with the same (partition, order) clause
    (reference: WindowOperator groups functions by window specification).

    ``valid`` (optional) marks live rows: invalid (pad) rows are isolated into
    their own partition — they sort last, never join a real partition's
    segments, and their outputs are garbage the caller drops.  This is what
    lets the distributed executor run the kernel per mesh shard over
    ragged-and-padded row counts."""
    n = cols[0].shape[0]
    pad = None if valid is None else ~valid
    cache: dict = {}

    def keyed(ch):
        """(indicator, value) sort/segment columns for a possibly-nullable channel:
        NULL rows group together and sort by the indicator, not the fill value."""
        nm = nulls[ch]
        if nm is None:
            return [(None, cols[ch])]
        return [(nm, jnp.where(nm, jnp.zeros((), cols[ch].dtype), cols[ch]))]

    out_cols, out_nulls = [], []
    for s in specs:
        ck = (s.partition, s.order)
        if ck not in cache:
            kcols, desc = [], []
            if pad is not None:
                kcols.append(pad)  # pads sort after every live row
                desc.append(False)
            for c in s.partition:
                for ind, v in keyed(c):
                    if ind is not None:
                        kcols.append(ind)
                        desc.append(False)
                    kcols.append(v)
                    desc.append(False)
            for k in s.order:
                for ind, v in keyed(k.channel):
                    if ind is not None:
                        # nulls_first -> null indicator sorts first (descending bool)
                        kcols.append(ind)
                        desc.append(bool(k.nulls_first))
                    kcols.append(v)
                    desc.append(not k.ascending)
            if kcols:
                perm = window_order(kcols, desc)
            else:
                perm = jnp.arange(n, dtype=jnp.int32)

            def seg_cols(channels):
                out = []
                for c in channels:
                    for ind, v in keyed(c):
                        if ind is not None:
                            out.append(ind[perm])
                        out.append(v[perm])
                return out

            pad_seg = [] if pad is None else [pad[perm]]
            if s.partition:
                part_new = segments(pad_seg + seg_cols(s.partition))
            elif pad is not None:
                part_new = segments(pad_seg)
            else:
                part_new = jnp.zeros((n,), bool).at[0].set(True)
            if s.order:
                peer_new = part_new | segments(
                    seg_cols([k.channel for k in s.order]))
            else:
                peer_new = part_new
            cache[ck] = (perm, part_new, peer_new)
        perm, part_new, peer_new = cache[ck]
        framed = bool(s.order)  # ORDER BY -> running frame; else whole partition
        # explicit ROWS/RANGE BETWEEN frame (reference: FramedWindowFunction):
        # per-row [lo, hi] bounds; empty frames (hi < lo) are legal and NULL
        frame = getattr(s, "frame", None)
        lo_f = hi_f = empty_f = None
        if frame is not None:
            order_vals = None
            if frame[0] == "range" and (frame[1] in ("p", "f")
                                        or frame[3] in ("p", "f")):
                # value-offset RANGE bounds: the single ORDER BY key's sorted
                # values, ascending-normalized, with NULL rows pushed past the
                # reachable range so they frame only among themselves
                k0 = s.order[0]
                ov = cols[k0.channel][perm]
                if not k0.ascending:
                    ov = -ov
                nm0 = nulls[k0.channel]
                if nm0 is not None:
                    nmv = nm0[perm]
                    gap = 2 * (max(frame[2], frame[4]) + 1)
                    nn_min = jnp.min(jnp.where(nmv, jnp.max(ov), ov))
                    nn_max = jnp.max(jnp.where(nmv, jnp.min(ov), ov))
                    sent = nn_min - gap if bool(k0.nulls_first) else nn_max + gap
                    ov = jnp.where(nmv, sent, ov)
                order_vals = ov
            lo_f, hi_f = frame_bounds(part_new, peer_new, frame, order_vals)
            empty_f = hi_f < lo_f

        def wsum(v, dt=None):
            if frame is not None:
                return framed_sum(v, lo_f, hi_f, dt)
            return (segmented_scan_sum(v, part_new, peer_new, dt) if framed
                    else partition_total(v, part_new, dt))

        def wminmax(v, kind):
            if frame is not None:
                return framed_minmax(v, lo_f, hi_f, kind)
            return segmented_scan_minmax(
                v, part_new, peer_new if framed else part_new, kind)

        vals = None
        vmask = None  # True where the input value counts
        if s.arg is not None:
            vals = cols[s.arg][perm]
            nm = nulls[s.arg]
            vmask = None if nm is None else ~nm[perm]

        null_out = None
        if s.kind == "row_number":
            res = row_number(part_new)
        elif s.kind == "rank":
            res = rank(part_new, peer_new)
        elif s.kind == "dense_rank":
            res = dense_rank(part_new, peer_new)
        elif s.kind in ("count", "count_star"):
            ones = jnp.ones((n,), jnp.int64)
            if s.kind == "count" and vmask is not None:
                ones = jnp.where(vmask, 1, 0)
            res = wsum(ones)  # empty frames count 0 (framed_sum yields 0)
        elif s.kind in ("sum", "avg"):
            acc_dt = jnp.float64 if s.type.is_floating else jnp.int64
            v = vals if vmask is None else jnp.where(vmask, vals, 0)
            total = wsum(v, acc_dt)
            nn_cnt = None
            if vmask is not None:
                nn_cnt = wsum(jnp.where(vmask, 1, 0))
                null_out = nn_cnt == 0  # all-NULL (or empty) frame -> NULL
            elif empty_f is not None:
                null_out = empty_f
            if s.kind == "sum":
                res = total
            else:
                cnt = nn_cnt
                if cnt is None:
                    cnt = wsum(jnp.ones((n,), jnp.int64))
                cnt_safe = jnp.maximum(cnt, 1)
                if s.type.is_floating:
                    res = total / cnt_safe
                else:  # decimal avg: HALF_UP like the aggregation path
                    q, r = jnp.divmod(jnp.abs(total), cnt_safe)
                    res = ((q + (2 * r >= cnt_safe)) * jnp.sign(total))
        elif s.kind in ("min", "max"):
            v = vals
            if vmask is not None:
                ident = _extreme(vals.dtype, 1 if s.kind == "min" else -1)
                v = jnp.where(vmask, vals, ident)
                nn_cnt = wsum(jnp.where(vmask, 1, 0))
                null_out = nn_cnt == 0  # all-NULL frame -> NULL, not the sentinel
            elif empty_f is not None:
                null_out = empty_f
            res = wminmax(v, s.kind)
        elif s.kind in ("lag", "lead"):
            off = s.offset if s.kind == "lag" else -s.offset
            fill = (jnp.zeros((), vals.dtype) if s.default is None
                    else jnp.asarray(s.default, vals.dtype))
            if getattr(s, "ignore_nulls", False) and vmask is not None:
                # navigate over NON-NULL rows only (reference: the ignoreNulls
                # walk of operator/window/LagFunction.java, here rank
                # arithmetic over a nonnull-position index)
                res, miss = shift_ignore_nulls(vals, vmask, part_new, off,
                                                 fill)
                if s.default is None:
                    null_out = miss
                else:
                    res = jnp.where(miss, fill, res)
                    null_out = jnp.zeros((n,), bool)
            else:
                res, miss = shift_in_partition(vals, part_new, off, fill)
                if s.default is None:
                    null_out = miss
                else:
                    res = jnp.where(miss, fill, res)
                    null_out = jnp.zeros((n,), bool)
                if vmask is not None:
                    shifted_null, _ = shift_in_partition(
                        (~vmask), part_new, off, jnp.zeros((), bool))
                    null_out = null_out | (shifted_null & ~miss)
        elif s.kind in ("percent_rank", "cume_dist"):
            size = partition_total(jnp.ones((n,), jnp.int64), part_new)
            if s.kind == "percent_rank":
                rk = rank(part_new, peer_new)
                res = jnp.where(size > 1,
                                (rk - 1) / jnp.maximum(size - 1, 1), 0.0)
            else:
                pos = _ends(peer_new) - _starts(part_new) + 1
                res = pos / size
        elif s.kind == "ntile":
            # reference: NTileFunction — the first (size % n) buckets take one
            # extra row
            nb = s.offset
            size = partition_total(jnp.ones((n,), jnp.int64), part_new)
            rn = row_number(part_new)
            q, r = size // nb, size % nb
            boundary = r * (q + 1)
            res = jnp.where(rn <= boundary,
                            (rn - 1) // jnp.maximum(q + 1, 1),
                            r + (rn - 1 - boundary) // jnp.maximum(q, 1)) + 1
        elif s.kind == "nth_value":
            # a row whose frame holds fewer than k rows yields NULL (reference:
            # operator/window/NthValueFunction.java frame bounds check); the
            # default frame is RANGE UNBOUNDED PRECEDING..CURRENT ROW
            k = s.offset
            starts = lo_f if frame is not None else _starts(part_new)
            frame_end = hi_f if frame is not None else _ends(peer_new)
            if getattr(s, "ignore_nulls", False) and vmask is not None:
                res, miss = framed_nth_nonnull(vals, vmask, starts,
                                                 frame_end, k)
                null_out = miss
            else:
                frame_size = frame_end - starts + 1
                idx = jnp.clip(starts + (k - 1), 0, n - 1)
                res = vals[idx]
                null_out = frame_size < k  # frame shorter than k -> NULL
                if vmask is not None:
                    null_out = null_out | ~vmask[idx]
        elif s.kind in ("first_value", "last_value"):
            starts = lo_f if frame is not None else _starts(part_new)
            frame_end = (hi_f if frame is not None
                         else _ends(peer_new if framed else part_new))
            if getattr(s, "ignore_nulls", False) and vmask is not None:
                res, miss = framed_nth_nonnull(
                    vals, vmask, starts, frame_end, 1,
                    from_end=(s.kind == "last_value"))
                null_out = miss
            else:
                idx = jnp.clip(starts if s.kind == "first_value" else frame_end,
                               0, n - 1)
                null_out = empty_f
                res = vals[idx]
                if vmask is not None:
                    miss = ~vmask[idx]
                    null_out = miss if null_out is None else (null_out | miss)
        else:
            raise NotImplementedError(s.kind)

        out = jnp.zeros((n,), res.dtype).at[perm].set(res.astype(res.dtype))
        out_cols.append(out.astype(s.type.dtype))
        if null_out is not None:
            out_nulls.append(jnp.zeros((n,), bool).at[perm].set(null_out))
        else:
            out_nulls.append(None)
    return tuple(out_cols), tuple(out_nulls)
