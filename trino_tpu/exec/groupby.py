"""The group-by's state: accumulators, their limbs, the init programs and both finalizes.

What an aggregate accumulates (``_accumulators_for``, ``_acc_input_expr``), how an
ungrouped one folds a page, how accumulator columns become output columns on the host
(``_finalize_aggs``) and on the device (``_finalize_aggs_device``), and the table sizes.
``LocalExecutor``, the mesh executor and the fault-tolerant one all build their
(acc_specs, acc_exprs) here, so they cannot disagree about a result.
"""

from __future__ import annotations

from functools import partial

import jax.numpy as jnp
import numpy as np

from ..ops import hashagg
from ..sql import plan as P
from ..sql.ir import Call, evaluate
from ..types import BIGINT, DecimalType
from .boundary import _jit


DEFAULT_GROUP_CAPACITY = 1 << 16
# ceiling sized for SF10-class group counts on one chip (15M distinct
# orderkeys need 32M slots to keep the probe load factor sane; ~40B/slot keeps
# the table under ~1.3GB of a 16GB-HBM budget — the memory pool still gates
# the actual reservation)
MAX_GROUP_CAPACITY = 1 << 25
# the most slots an ESTIMATE gives a hash group-by's first run: estimates
# overshoot (a row bound is no group count).  A table that was cut to it may
# take one step of four times the slots for ROOM, before a page with more live
# lanes than it has slots (_run_hash_inserts); else only an overflow grows it
FIRST_CAPACITY_CAP = 1 << 20

# accumulator kind -> the kind that merges two partial states of it (exchanged
# or spooled accumulator entries, a streaming aggregation's per-page segments)
_MERGE_KIND = {"sum": "sum", "count": "sum", "count_star": "sum", "min": "min",
               "max": "max", "sum_sq": "sum",
               # two-limb partial sums merge by PLAIN addition (the limbs are
               # already split; splitting again would corrupt them)
               "sum_hi32": "sum", "sum_lo32": "sum"}


def _global_agg_update(state, cols, nulls, valid, acc_exprs, acc_kinds):
    """One page folded into the ungrouped-aggregation accumulator tuple."""
    out = []
    for st, e, kind in zip(state, acc_exprs, acc_kinds):
        if kind == "count_star":
            out.append(st + jnp.sum(valid, dtype=st.dtype))
            continue
        v, nu = evaluate(e, cols, nulls)
        mask = valid if nu is None else (valid & ~nu)
        if kind == "count":
            out.append(st + jnp.sum(mask, dtype=st.dtype))
        elif kind == "sum":
            out.append(st + jnp.sum(jnp.where(mask, v, 0), dtype=st.dtype))
        elif kind in ("sum_hi32", "sum_lo32"):
            h = (v >> 32) if kind == "sum_hi32" else (v & 0xFFFFFFFF)
            out.append(st + jnp.sum(jnp.where(mask, h, 0), dtype=st.dtype))
        elif kind == "sum_sq":
            vv = v.astype(st.dtype)
            out.append(st + jnp.sum(jnp.where(mask, vv * vv, 0),
                                    dtype=st.dtype))
        elif kind == "min":
            out.append(jnp.minimum(st, jnp.min(jnp.where(
                mask, v, hashagg._extreme(st.dtype, 1))).astype(st.dtype)))
        elif kind == "max":
            out.append(jnp.maximum(st, jnp.max(jnp.where(
                mask, v, hashagg._extreme(st.dtype, -1))).astype(st.dtype)))
        else:
            raise NotImplementedError(kind)
    return tuple(out)


def _global_init_state(node):
    """Initial accumulator tuple for an ungrouped aggregation."""
    acc_specs = []
    for spec in node.aggs:
        acc_specs.extend(_accumulators_for(spec))
    state = tuple(
        jnp.asarray(init if init is not None else 0, dtype)
        for _, dtype, init in acc_specs
    )
    # min/max identity
    return tuple(
        jnp.asarray(hashagg._extreme(dtype, 1 if kind == "min" else -1), dtype)
        if kind in ("min", "max") else st
        for st, (kind, dtype, _) in zip(state, acc_specs)
    )


def _acc_input_expr(spec: P.AggSpec):
    """The expression accumulators actually consume for one agg call.

    Lives NEXT TO _accumulators_for because every executor building
    (acc_specs, acc_exprs) must apply the same transform: checksum
    accumulates the modular sum of per-row HASHES, not raw values — a
    builder using spec.arg directly would silently disagree with the
    local path's results."""
    arg = spec.arg
    if spec.kind == "checksum" and arg is not None:
        arg = Call("hash", (arg,), BIGINT)
    return arg


def _accumulators_for(spec: P.AggSpec):
    """(kind, dtype, init) accumulator list for one agg call."""
    t = spec.type
    if spec.kind == "count_star" or spec.kind == "count":
        return [(spec.kind, jnp.int64, 0)]
    if spec.kind == "sum":
        # the trailing count accumulator distinguishes an all-NULL (or empty)
        # group from a genuine zero sum: SQL sum over no non-null rows is
        # NULL, not 0 (reference: the null flag of LongSumAggregation state)
        if isinstance(t, DecimalType):
            # exact wide sum: two int64 limbs (hi = v>>32, lo = v&0xFFFFFFFF)
            # accumulate separately and recombine exactly at finalization
            # (reference: Int128 state, DecimalSumAggregation.java)
            return [("sum_hi32", jnp.int64, 0), ("sum_lo32", jnp.int64, 0),
                    ("count", jnp.int64, 0)]
        dtype = jnp.float64 if t.is_floating else jnp.int64
        return [("sum", dtype, 0), ("count", jnp.int64, 0)]
    if spec.kind == "avg":
        in_t = spec.arg.type
        if isinstance(in_t, DecimalType):
            return [("sum_hi32", jnp.int64, 0), ("sum_lo32", jnp.int64, 0),
                    ("count", jnp.int64, 0)]
        dtype = jnp.float64 if in_t.is_floating else jnp.int64
        return [("sum", dtype, 0), ("count", jnp.int64, 0)]
    if spec.kind in ("min", "max"):
        dtype = spec.arg.type.dtype
        return [(spec.kind, dtype, hashagg._extreme(dtype, 1 if spec.kind == "min" else -1))]
    if spec.kind in ("var_pop", "var_samp", "stddev_pop", "stddev_samp"):
        # (sum, sum of squares, count) — the reference's VarianceState
        # (operator/aggregation/state/VarianceState.java keeps mean/m2; sums are
        # the merge-friendly equivalent for partial aggregation)
        return [("sum", jnp.float64, 0), ("sum_sq", jnp.float64, 0),
                ("count", jnp.int64, 0)]
    if spec.kind == "bool_and":
        return [("min", jnp.int8, hashagg._extreme(jnp.int8, 1))]
    if spec.kind == "bool_or":
        return [("max", jnp.int8, hashagg._extreme(jnp.int8, -1))]
    if spec.kind == "arbitrary":
        dtype = spec.arg.type.dtype
        return [("min", dtype, hashagg._extreme(dtype, 1))]
    if spec.kind == "checksum":
        # order-insensitive MODULAR SUM of splitmix64 row hashes (reference:
        # ChecksumAggregationFunction combines xxhash64 values; wraparound
        # int64 sum is the same merge-friendly commutative algebra).
        # Documented deviations: bigint rendering instead of varbinary, and
        # string arguments hash their per-query dictionary ids
        return [("sum", jnp.int64, 0), ("count", jnp.int64, 0)]
    raise NotImplementedError(spec.kind)


def _combine_limbs_vec(hi, lo):
    """Recombine two-limb sums: vectorized int64 when every result fits (the
    int64 computation is exact mod 2^64, so intermediate wraps don't matter),
    else (None, exact-Python-int list).  The Python path only runs when a sum
    actually exceeds ~2^62 — a per-row host loop over a million groups was the
    dominant cost of decimal aggregation finalize."""
    hi = np.asarray(hi)
    lo = np.asarray(lo)
    approx = hi.astype(np.float64) * 4294967296.0 + lo.astype(np.float64)
    if np.all(np.abs(approx) < float(1 << 62)):
        return hi.astype(np.int64) * (1 << 32) + lo.astype(np.int64), None
    return None, [int(h) * (1 << 32) + int(l)
                  for h, l in zip(hi.tolist(), lo.tolist())]


def _finalize_aggs(aggs, acc_cols, n_groups):
    """Combine accumulator columns into final output columns (host-side, small).

    Wide decimal sums recombine their two limbs as EXACT Python ints; values
    still inside int64 emit a normal device-safe column, anything past 2^63
    emits an object column that lives on the host through the result surface
    (the reference's Int128 -> long-decimal block).

    Returns (columns, null_masks): SQL aggregates over an all-NULL (or empty)
    group are NULL — sums/avgs detect it from their count accumulator,
    min/max/arbitrary/bool_* from a surviving init sentinel (a real value
    colliding with the sentinel is the accepted int64-extreme collision
    class)."""
    out = []
    nulls = []
    i = 0
    for spec in aggs:
        if spec.kind == "avg" and spec.arg is not None \
                and isinstance(spec.arg.type, DecimalType):
            vec, exact = _combine_limbs_vec(acc_cols[i], acc_cols[i + 1])
            c = np.asarray(acc_cols[i + 2])
            i += 3
            if vec is not None:  # HALF_UP rounding, vectorized
                n = np.maximum(c.astype(np.int64), 1)
                q, r = np.divmod(np.abs(vec), n)
                out.append(((q + (2 * r >= n)) *
                            np.where(vec >= 0, 1, -1)).astype(np.int64))
            else:
                vals = []
                for s, n in zip(exact, c.tolist()):
                    n = max(int(n), 1)
                    q, r = divmod(abs(s), n)
                    vals.append((q + (2 * r >= n)) * (1 if s >= 0 else -1))
                out.append(np.array(vals, np.int64))  # avg fits the input type
            nulls.append(np.asarray(c) == 0)
        elif spec.kind == "avg":
            s, c = acc_cols[i], acc_cols[i + 1]
            i += 2
            c_safe = np.where(c == 0, 1, c)
            out.append((s / c_safe).astype(np.float64))
            nulls.append(np.asarray(c) == 0)
        elif spec.kind == "sum" and isinstance(spec.type, DecimalType):
            vec, exact = _combine_limbs_vec(acc_cols[i], acc_cols[i + 1])
            c = np.asarray(acc_cols[i + 2])
            i += 3
            if vec is not None:
                out.append(vec)
            elif all(-(1 << 63) <= v < (1 << 63) for v in exact):
                out.append(np.array(exact, np.int64))
            else:
                out.append(np.array(exact, dtype=object))
            nulls.append(c == 0)
        elif spec.kind in ("sum", "checksum"):
            s, c = acc_cols[i], acc_cols[i + 1]
            i += 2
            out.append(np.asarray(s).astype(np.dtype(spec.type.dtype)))
            nulls.append(np.asarray(c) == 0)
        elif spec.kind in ("var_pop", "var_samp", "stddev_pop", "stddev_samp"):
            s, ssq, c = acc_cols[i], acc_cols[i + 1], acc_cols[i + 2]
            i += 3
            c_safe = np.where(c == 0, 1, c).astype(np.float64)
            m2 = np.maximum(ssq - s * s / c_safe, 0.0)  # clamp fp cancellation
            if spec.kind.endswith("_pop"):
                var = m2 / c_safe
                null = np.asarray(c) == 0
            else:
                var = m2 / np.where(c < 2, 1, c - 1)
                var = np.where(c < 2, 0.0, var)
                null = np.asarray(c) < 2  # samp undefined below 2 rows
            out.append(np.sqrt(var) if spec.kind.startswith("stddev") else var)
            nulls.append(null)
        else:
            col = acc_cols[i]
            i += 1
            out.append(col.astype(np.dtype(spec.type.dtype)))
            if spec.kind in ("min", "max", "arbitrary", "bool_and", "bool_or"):
                k0, dt0, init0 = _accumulators_for(spec)[0][:3]
                nulls.append(np.asarray(col) == np.asarray(init0))
            else:  # counts are 0 for empty groups, never NULL
                nulls.append(None)
    return out, [None if (m is None or not m.any()) else m for m in nulls]


def _device_finalize_plan(aggs):
    """Raise NotImplementedError when any agg kind lacks a device finalize.
    Mirrors the branch structure of _finalize_aggs_device."""
    for spec in aggs:
        if spec.kind in ("avg", "sum", "checksum", "count", "count_star",
                         "var_pop", "var_samp", "stddev_pop", "stddev_samp",
                         "min", "max", "arbitrary", "bool_and", "bool_or"):
            continue
        raise NotImplementedError(spec.kind)


def _limbs_device(hi, lo):
    """Two-limb decimal sum recombination on device: exact int64 when the
    value is inside the +-2^62 envelope (same gate as _combine_limbs_vec);
    the returned flag marks the out-of-envelope case for host fallback."""
    approx = hi.astype(jnp.float64) * 4294967296.0 + lo.astype(jnp.float64)
    bad = jnp.any(jnp.abs(approx) >= float(1 << 62))
    return hi * (1 << 32) + lo, bad


def _finalize_aggs_device(aggs, acc_cols):
    """Device (jnp) analog of _finalize_aggs: returns (cols, nulls, bad)
    with ``bad`` a scalar bool — True when a wide-decimal sum leaves the
    exact-int64 envelope and the caller must redo finalization host-side.
    Keeping the output on device is the round-5 fix: the aggregate
    page feeds downstream jitted consumers without a host round-trip."""
    out, nulls = [], []
    bad = jnp.zeros((), bool)
    i = 0
    for spec in aggs:
        if spec.kind == "avg" and spec.arg is not None \
                and isinstance(spec.arg.type, DecimalType):
            hi, lo, c = acc_cols[i], acc_cols[i + 1], acc_cols[i + 2]
            i += 3
            v, b = _limbs_device(hi, lo)
            bad = bad | b
            n = jnp.maximum(c.astype(jnp.int64), 1)
            a = jnp.abs(v)
            q = a // n
            r = a - q * n
            res = (q + (2 * r >= n)) * jnp.where(v >= 0, 1, -1)
            out.append(res.astype(jnp.int64))
            nulls.append(c == 0)
        elif spec.kind == "avg":
            s, c = acc_cols[i], acc_cols[i + 1]
            i += 2
            out.append((s / jnp.where(c == 0, 1, c)).astype(jnp.float64))
            nulls.append(c == 0)
        elif spec.kind == "sum" and isinstance(spec.type, DecimalType):
            hi, lo, c = acc_cols[i], acc_cols[i + 1], acc_cols[i + 2]
            i += 3
            v, b = _limbs_device(hi, lo)
            bad = bad | b
            out.append(v)
            nulls.append(c == 0)
        elif spec.kind in ("sum", "checksum"):
            s, c = acc_cols[i], acc_cols[i + 1]
            i += 2
            out.append(s.astype(spec.type.dtype))
            nulls.append(c == 0)
        elif spec.kind in ("var_pop", "var_samp", "stddev_pop", "stddev_samp"):
            s, ssq, c = acc_cols[i], acc_cols[i + 1], acc_cols[i + 2]
            i += 3
            c_safe = jnp.where(c == 0, 1, c).astype(jnp.float64)
            m2 = jnp.maximum(ssq - s * s / c_safe, 0.0)
            if spec.kind.endswith("_pop"):
                var = m2 / c_safe
                null = c == 0
            else:
                var = jnp.where(c < 2, 0.0, m2 / jnp.where(c < 2, 1, c - 1))
                null = c < 2
            out.append(jnp.sqrt(var) if spec.kind.startswith("stddev")
                       else var)
            nulls.append(null)
        else:
            col = acc_cols[i]
            i += 1
            out.append(col.astype(spec.type.dtype))
            if spec.kind in ("min", "max", "arbitrary", "bool_and", "bool_or"):
                k0, dt0, init0 = _accumulators_for(spec)[0][:3]
                nulls.append(col == jnp.asarray(init0, col.dtype))
            else:  # counts are 0 for empty groups, never NULL
                nulls.append(None)
    return tuple(out), tuple(nulls), bad


@partial(_jit, site="agg.direct.init", static_argnums=(0, 1, 2))
def _direct_init(cfg, key_dtypes, acc_specs):
    """The direct group-by's initial state as one program a (config, key
    dtypes, accumulator specs): every statement starts from it, and eager it
    was a launch a fill.  Nothing is kept on the device between statements:
    a 2^24-slot state is not pinned."""
    return hashagg.direct_groupby_init(cfg, key_dtypes, acc_specs)


@partial(_jit, site="agg.hash.init", static_argnums=(0, 1, 2))
def _hash_init(capacity, key_dtypes, acc_specs):
    """The hash group-by's, one program a (capacity, key dtypes, specs)."""
    return hashagg.groupby_init(capacity, key_dtypes, acc_specs)


@partial(_jit, site="agg.group_count")
def _group_count(state):
    return hashagg.group_count(state)


def _group_state_bytes(key_types, acc_specs):
    """cap -> device bytes of a group-by state of ``cap`` slots (and its sink):
    the table word, each key with its null flag, each accumulator."""
    key_w = sum(np.dtype(t.dtype).itemsize + 1 for t in key_types)
    acc_w = sum(np.dtype(dt).itemsize for dt, _ in acc_specs)
    return lambda cap: (cap + 1) * (8 + key_w + acc_w)
