"""Typed expression IR + jax-traced evaluation.

Mirrors the reference's small typed expression IR (core/trino-main .../sql/ir/Expression.java —
Call/Constant/Reference/Case/...) and replaces its two bytecode backends
(sql/gen/ExpressionCompiler.java:40 row-oriented, sql/gen/columnar/ColumnarFilterCompiler
vectorized) with ONE columnar backend: ``evaluate`` traces the expression over whole-column jnp
arrays inside ``jax.jit``, so XLA fuses filter+project chains into single kernels (the fusion
the reference gets from PageProcessor + generated bytecode, operator/project/PageProcessor.java:53).

NULL semantics: every evaluation returns ``(values, null_mask_or_None)`` and operators
propagate/absorb nulls per SQL three-valued logic (reference encodes this in generated
bytecode wasNull checks).

Strings are dictionary ids: comparisons against literals and LIKE are *pre-resolved by the
planner* into id comparisons / id->bool lookup tables (Call("lut", ...)) — the device never
sees bytes (the TPU replacement for likematcher/LikeMatcher.java:26's NFA/DFA engine).
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..types import BOOLEAN, DOUBLE, BIGINT, DecimalType, Type

__all__ = ["Expr", "Constant", "FieldRef", "Call", "Parameter",
           "bind_params", "evaluate", "evaluate_predicate"]


class Expr:
    type: Type


@dataclasses.dataclass(frozen=True)
class Constant(Expr):
    """A literal. ``value`` is a python scalar (decimal literals pre-scaled to raw int),
    or a numpy array for planner-generated lookup tables."""

    value: Any
    type: Type

    def __repr__(self):
        return f"Const({self.value}:{self.type.name})"


@dataclasses.dataclass(frozen=True)
class FieldRef(Expr):
    """Positional reference into the input schema (reference: sql/ir/Reference.java,
    resolved to channels by LocalExecutionPlanner)."""

    index: int
    type: Type
    name: str = ""

    def __repr__(self):
        return f"${self.index}:{self.name}"


@dataclasses.dataclass(frozen=True)
class Call(Expr):
    """Operator/function call. ``op`` is the function name; result ``type`` was computed by
    the analyzer (decimal ops carry pre-computed rescale factors in ``meta``)."""

    op: str
    args: tuple
    type: Type
    meta: tuple = ()

    def __repr__(self):
        return f"{self.op}({', '.join(map(repr, self.args))})"


@dataclasses.dataclass(frozen=True)
class Parameter(Expr):
    """A runtime statement parameter: a value that is an INPUT to the
    compiled plan, not a trace-time literal (plan templates, engine
    ``EXECUTE``).  ``slot`` indexes the runtime binding tuple the executor
    threads into every dispatch as a JIT ARGUMENT — the same never-close-over
    rule as ``_Stream.aux`` (a closed-over device array is baked into the
    executable as a constant, so every new value would recompile).  The value's domain is whatever the
    planner resolved at this position (dictionary ids for strings, scaled
    ints for decimals, epoch days for dates); sql/params.Binder owns the
    bind-time encoding."""

    slot: int
    type: Type

    def __repr__(self):
        return f"?[{self.slot}]:{self.type.name}"


_PARAM_TLS = threading.local()


@contextlib.contextmanager
def bind_params(values):
    """Make runtime parameter values visible to ``evaluate`` on this thread.

    Entered INSIDE jitted step functions with the traced ``params`` argument
    (exec/local_executor threads it alongside aux), so the binding active
    while an expression traces is a tracer, never a baked-in constant.  Each
    slot is a ``(value, isnull)`` pair; the pair is always present so NULL vs
    non-NULL bindings share one executable (nullness is data, not pytree
    structure)."""
    old = getattr(_PARAM_TLS, "values", None)
    _PARAM_TLS.values = values
    try:
        yield
    finally:
        _PARAM_TLS.values = old


def _param_leaf(expr: "Parameter"):
    vals = getattr(_PARAM_TLS, "values", None)
    if vals is None or expr.slot >= len(vals):
        # reached from a PLAN-TIME evaluation (eager subquery, lambda-on-heap
        # fold, fused-path shape probe): the value is not available before
        # execution — template creation catches this and falls back to
        # substitution, and the fused-scan probe falls back to the page loop
        raise RuntimeError(
            f"parameter slot {expr.slot} evaluated outside bind_params "
            "(plan-time use of a runtime parameter)")
    v, isnull = vals[expr.slot]
    return (jnp.asarray(v, expr.type.dtype),
            jnp.asarray(isnull, dtype=bool))


def _combine_nulls(*masks):
    out = None
    for m in masks:
        if m is None:
            continue
        out = m if out is None else (out | m)
    return out


def evaluate(expr: Expr, columns: Sequence, null_masks: Sequence):
    """Trace ``expr`` over columnar inputs; returns (values, null_mask|None)."""
    if isinstance(expr, Constant):
        if isinstance(expr.value, np.ndarray):
            return jnp.asarray(expr.value), None
        if expr.value is None:
            return jnp.zeros((), expr.type.dtype), jnp.ones((), dtype=bool)
        return jnp.asarray(expr.value, expr.type.dtype), None
    if isinstance(expr, FieldRef):
        return columns[expr.index], null_masks[expr.index]
    if isinstance(expr, Parameter):
        return _param_leaf(expr)
    if isinstance(expr, Call):
        return _eval_call(expr, columns, null_masks)
    raise TypeError(f"unknown expression node {expr!r}")


def evaluate_predicate(expr: Optional[Expr], columns, null_masks, base_valid):
    """Filter evaluation: NULL counts as false (WHERE semantics)."""
    if expr is None:
        return base_valid
    vals, nulls = evaluate(expr, columns, null_masks)
    keep = vals
    if nulls is not None:
        keep = keep & ~nulls
    if base_valid is not None:
        keep = keep & base_valid
    return keep


_ARITH = {"add": jnp.add, "subtract": jnp.subtract, "multiply": jnp.multiply}
_CMP = {
    "eq": jnp.equal,
    "neq": jnp.not_equal,
    "lt": jnp.less,
    "lte": jnp.less_equal,
    "gt": jnp.greater,
    "gte": jnp.greater_equal,
}


def _eval_call(expr: Call, columns, null_masks):
    op = expr.op
    argv = [evaluate(a, columns, null_masks) for a in expr.args]
    vals = [v for v, _ in argv]
    nulls = [n for _, n in argv]

    if op in _ARITH:
        return _ARITH[op](vals[0], vals[1]).astype(expr.type.dtype), _combine_nulls(*nulls)
    if op == "negate":
        return (-vals[0]).astype(expr.type.dtype), nulls[0]
    if op == "divide":
        a, b = vals[0], vals[1]
        if isinstance(expr.type, DecimalType):
            # decimal division: planner computed numerator rescale in meta[0]
            scale_up = expr.meta[0] if expr.meta else 1
            num = a.astype(jnp.int64) * scale_up
            safe_b = jnp.where(b == 0, 1, b)
            q = _div_round_half_up(num, safe_b.astype(jnp.int64))
            return q, _combine_nulls(*nulls)
        if expr.type.is_integer:
            safe_b = jnp.where(vals[1] == 0, 1, vals[1])
            return (a // safe_b).astype(expr.type.dtype), _combine_nulls(*nulls)
        safe_b = jnp.where(b == 0, 1, b).astype(expr.type.dtype)
        return (a.astype(expr.type.dtype) / safe_b), _combine_nulls(*nulls)
    if op == "modulus":
        safe_b = jnp.where(vals[1] == 0, 1, vals[1])
        return jnp.remainder(vals[0], safe_b).astype(expr.type.dtype), _combine_nulls(*nulls)
    if op in _CMP:
        return _CMP[op](vals[0], vals[1]), _combine_nulls(*nulls)
    if op == "between":
        v, lo, hi = vals
        return (v >= lo) & (v <= hi), _combine_nulls(*nulls)
    if op == "and":
        # 3VL: F and NULL = F; T and NULL = NULL
        v = vals[0] & vals[1]
        n0, n1 = nulls
        if n0 is None and n1 is None:
            return v, None
        # a side with no null mask is never NULL: its known-truth is just its value
        f0 = ~vals[0] & (jnp.ones((), bool) if n0 is None else ~n0)
        f1 = ~vals[1] & (jnp.ones((), bool) if n1 is None else ~n1)
        known_false = f0 | f1
        anynull = _combine_nulls(n0, n1)
        return v & ~anynull, anynull & ~known_false
    if op == "or":
        v = vals[0] | vals[1]
        n0, n1 = nulls
        if n0 is None and n1 is None:
            return v, None
        # a side with no null mask is never NULL: its known-truth is just its value
        t0 = vals[0] & (jnp.ones((), bool) if n0 is None else ~n0)
        t1 = vals[1] & (jnp.ones((), bool) if n1 is None else ~n1)
        known_true = t0 | t1
        anynull = _combine_nulls(n0, n1)
        return v | known_true, anynull & ~known_true
    if op == "not":
        return ~vals[0], nulls[0]
    if op == "is_null":
        n = nulls[0]
        return (jnp.zeros_like(vals[0], dtype=bool) if n is None else n), None
    if op == "in":
        # planner keeps literal IN-lists as constants: vals[1:] are scalars
        acc = vals[0] == vals[1]
        for v in vals[2:]:
            acc = acc | (vals[0] == v)
        return acc, nulls[0]
    if op == "in_array":
        # membership in a SORTED unique plan-time table (eager uncorrelated
        # IN-subquery): one searchsorted probe per lane — the device-friendly
        # form of an arbitrarily long IN list
        table = vals[1]
        x = vals[0]
        if table.shape[0] == 0:
            return jnp.zeros(x.shape, bool), nulls[0]
        ix = jnp.clip(jnp.searchsorted(table, x), 0, table.shape[0] - 1)
        return table[ix] == x, nulls[0]
    if op == "lut":
        # id -> bool/value lookup table (LIKE, dictionary-predicate, CASE over strings).
        # args: (id_expr, table_const); planner guarantees ids in range.
        table = vals[1]
        ids = jnp.clip(vals[0], 0, table.shape[0] - 1)
        return table[ids], nulls[0]
    if op == "lut_nullable":
        # like "lut" with a parallel null table: entries whose transform
        # produced SQL NULL (regexp miss, unparsable date, absent URL part)
        # null out the row (args: id_expr, value_table, null_table)
        table, ntable = vals[1], vals[2]
        ids = jnp.clip(vals[0], 0, table.shape[0] - 1)
        return table[ids], _combine_nulls(nulls[0], ntable[ids])
    # -- array/map spans (ops/arrays.py layout; reference: ArrayBlock/MapBlock
    # element access, operator/scalar/ArraySubscriptOperator etc.) --------------
    if op == "span_len":
        from ..ops.arrays import span_len

        return span_len(vals[0]).astype(expr.type.dtype), nulls[0]
    if op == "span_id":
        # identity over the span; only the channel's heap/type changed
        # (array transform lambdas rewrite the plan-time-constant heap)
        return vals[0], nulls[0]
    if op == "span_filter":
        # args: (span, exclusive-cumsum-of-kept const, len(heap)+1 entries):
        # remap a span into the FILTERED heap — two gathers into the cumsum,
        # zero element traffic (operator/scalar/ArrayFilterFunction analog,
        # span-only re-design)
        from ..ops.arrays import pack_span, span_len, span_start

        span, excl = vals[0], vals[1]
        start, ln = span_start(span), span_len(span)
        hi = max(excl.shape[0] - 1, 0)
        s0 = excl[jnp.clip(start, 0, hi)]
        s1 = excl[jnp.clip(start + ln, 0, hi)]
        return pack_span(s0, s1 - s0), nulls[0]
    if op == "span_slice":
        # args: (span, start[1-based; negative = from tail], length) —
        # SQL slice() as pure span arithmetic, zero element traffic
        # (reference: operator/scalar/ArraySliceFunction)
        from ..ops.arrays import pack_span, span_len, span_start

        sp, st, ln = vals
        alen = span_len(sp)
        s0 = span_start(sp)
        begin = jnp.where(st > 0, st - 1, alen + st)
        begin_c = jnp.clip(begin, 0, alen)
        # a negative start past the head selects NOTHING (reference:
        # ArraySliceFunction), not the clipped prefix
        ln_c = jnp.where(begin < 0, 0, jnp.clip(ln, 0, alen - begin_c))
        bad = (st == 0) | (ln < 0)  # reference raises; NULL here (LUT rule)
        return pack_span(s0 + begin_c, ln_c), _combine_nulls(
            _combine_nulls(nulls[0], nulls[1]),
            _combine_nulls(nulls[2], bad))
    if op == "span_trim":
        # args: (span, n) — trim_array drops the last n elements
        from ..ops.arrays import pack_span, span_len, span_start

        sp, n = vals
        alen = span_len(sp)
        ln_c = jnp.clip(alen - n, 0, alen)
        bad = n < 0
        return pack_span(span_start(sp), ln_c), _combine_nulls(
            _combine_nulls(nulls[0], nulls[1]), bad)
    if op == "arrays_overlap":
        # args: (span_a, span_b, heap_a const, heap_b const);
        # meta = (max_len_a, max_len_b) — one [rows, La, Lb] masked compare
        from ..ops.arrays import span_len, span_start

        sa, sb, ha, hb = vals
        scalar = jnp.ndim(sa) == 0 and jnp.ndim(sb) == 0
        sa, sb = jnp.atleast_1d(sa), jnp.atleast_1d(sb)
        sa, sb = jnp.broadcast_arrays(sa, sb)
        la, lb = span_len(sa), span_len(sb)
        offs_a = jnp.arange(expr.meta[0])
        offs_b = jnp.arange(expr.meta[1])
        if ha.shape[0] and hb.shape[0]:
            pa = jnp.clip(span_start(sa)[:, None] + offs_a[None, :], 0,
                          ha.shape[0] - 1)
            pb = jnp.clip(span_start(sb)[:, None] + offs_b[None, :], 0,
                          hb.shape[0] - 1)
            ea = ha[pa]  # [rows, La]
            eb = hb[pb]  # [rows, Lb]
            in_a = offs_a[None, :] < la[:, None]
            in_b = offs_b[None, :] < lb[:, None]
            hit = jnp.any((ea[:, :, None] == eb[:, None, :])
                          & in_a[:, :, None] & in_b[:, None, :], axis=(1, 2))
        else:
            hit = jnp.zeros(sa.shape, bool)
        return hit[0] if scalar else hit, _combine_nulls(nulls[0], nulls[1])
    if op == "st_distance":
        # Euclidean distance of two planar points, kept as ONE canonical op
        # (args: ax, ay, bx, by) so the spatial-join rule can pattern-match
        # it in join filters (reference: operator/SpatialJoinOperator.java's
        # ST_Distance radius predicates; geometry here is planar points)
        ax, ay, bx, by = (v.astype(jnp.float64) for v in vals)
        return jnp.sqrt((ax - bx) ** 2 + (ay - by) ** 2), _combine_nulls(*nulls)
    if op == "as_timestamp":
        # reinterpret a double ALREADY in the target timestamp's epoch units
        # (from_unixtime builds epoch-ms then retypes to timestamp(3))
        return jnp.round(vals[0]).astype(jnp.int64), nulls[0]
    if op == "span_reduce_lambda":
        # reduce(array, init, lambda): UNROLLED masked fold of max_len steps
        # over the plan-time element heap — state vectorizes across rows,
        # step i gathers element i of every row's span and applies the
        # combiner body (an ir.Expr over channels [state, element]) where
        # i < span length.  Static trip count: XLA sees a straight-line
        # program (operator/scalar/ArrayReduceFunction re-design).
        from ..ops.arrays import span_len, span_start

        span, init, heap = vals[0], vals[1], vals[2]
        max_len, body = expr.meta
        start, ln = span_start(span), span_len(span)
        state = jnp.broadcast_to(init, span.shape) if init.ndim == 0 else init
        state = state.astype(expr.type.dtype)
        if heap.shape[0] == 0:  # all-empty arrays: the fold is the init
            return state, nulls[0]
        hi = max(heap.shape[0] - 1, 0)
        for i in range(max_len):
            elem = heap[jnp.clip(start + i, 0, hi)]
            new_state, _ = evaluate(body, (state, elem), (None, None))
            state = jnp.where(i < ln, new_state.astype(state.dtype), state)
        return state, nulls[0]
    if op == "array_get":
        # args: (span, index[1-based], heap_const)
        from ..ops.arrays import span_len, span_start

        span, idx, heap = vals[0], vals[1], vals[2]
        start, ln = span_start(span), span_len(span)
        i0 = idx.astype(jnp.int64) - 1
        ok = (i0 >= 0) & (i0 < ln)
        pos = jnp.clip(start + i0, 0, max(heap.shape[0] - 1, 0))
        val = heap[pos] if heap.shape[0] else jnp.zeros_like(pos)
        bad = ~ok
        return val.astype(expr.type.dtype), _combine_nulls(
            _combine_nulls(nulls[0], nulls[1]), bad)
    if op == "array_contains":
        # args: (span, needle, heap_const); meta[0] = static max array length.
        # One [rows, max_len] gather + masked reduce — NOT an unrolled python
        # loop, whose trace grew linearly in max_len (~4ms compile/element)
        from ..ops.arrays import span_len, span_start

        span, needle, heap = vals[0], vals[1], vals[2]
        scalar = jnp.ndim(span) == 0 and jnp.ndim(needle) == 0
        span, needle = jnp.atleast_1d(span), jnp.atleast_1d(needle)
        span, needle = jnp.broadcast_arrays(span, needle)
        start, ln = span_start(span), span_len(span)
        offs = jnp.arange(expr.meta[0])
        if heap.shape[0]:
            pos = jnp.clip(start[:, None] + offs[None, :], 0, heap.shape[0] - 1)
            hit = jnp.any((offs[None, :] < ln[:, None])
                          & (heap[pos] == needle[:, None]), axis=1)
        else:
            hit = jnp.zeros(span.shape, bool)
        return hit[0] if scalar else hit, _combine_nulls(nulls[0], nulls[1])
    if op == "array_reduce":
        # args: (span, heap_const); meta = (max_len, kind) with kind in
        # sum/min/max/avg/position-free reductions — one [rows, max_len]
        # gather + masked reduce, like array_contains
        from ..ops.arrays import span_len, span_start

        kind = expr.meta[1]
        span, heap = vals[0], vals[1]
        scalar = jnp.ndim(span) == 0
        span = jnp.atleast_1d(span)
        start, ln = span_start(span), span_len(span)
        offs = jnp.arange(expr.meta[0])
        in_row = offs[None, :] < ln[:, None]
        if heap.shape[0]:
            pos = jnp.clip(start[:, None] + offs[None, :], 0, heap.shape[0] - 1)
            elems = heap[pos]
        else:
            elems = jnp.zeros((span.shape[0], expr.meta[0]),
                              jnp.asarray(heap).dtype)
        empty = ln == 0
        if kind == "sum":
            out = jnp.sum(jnp.where(in_row, elems, 0), axis=1)
        elif kind == "avg":
            out = (jnp.sum(jnp.where(in_row, elems, 0), axis=1)
                   / jnp.maximum(ln, 1)).astype(jnp.float64)
        elif kind == "min":
            big = _extreme_for(elems.dtype, +1)
            out = jnp.min(jnp.where(in_row, elems, big), axis=1)
        else:  # max
            small = _extreme_for(elems.dtype, -1)
            out = jnp.max(jnp.where(in_row, elems, small), axis=1)
        out = out.astype(expr.type.dtype)
        null = _combine_nulls(nulls[0], empty)  # empty arrays -> NULL
        return (out[0] if scalar else out), null
    if op == "array_position":
        # args: (span, needle, heap_const); meta[0] = max_len; 1-based index
        # of the FIRST match, 0 when absent (reference: ArrayPositionFunction)
        from ..ops.arrays import span_len, span_start

        span, needle, heap = vals[0], vals[1], vals[2]
        scalar = jnp.ndim(span) == 0 and jnp.ndim(needle) == 0
        span, needle = jnp.broadcast_arrays(jnp.atleast_1d(span),
                                            jnp.atleast_1d(needle))
        start, ln = span_start(span), span_len(span)
        offs = jnp.arange(expr.meta[0])
        if heap.shape[0]:
            pos = jnp.clip(start[:, None] + offs[None, :], 0, heap.shape[0] - 1)
            hit = (offs[None, :] < ln[:, None]) & (heap[pos] == needle[:, None])
            first = jnp.argmax(hit, axis=1)
            out = jnp.where(jnp.any(hit, axis=1), first + 1, 0)
        else:
            out = jnp.zeros(span.shape, jnp.int64)
        return ((out[0] if scalar else out).astype(jnp.int64),
                _combine_nulls(nulls[0], nulls[1]))
    if op == "map_get":
        # args: (span, key, keys_heap_const, values_heap_const); meta[0] = max
        # len.  Same single-gather shape as array_contains; first match wins.
        from ..ops.arrays import span_len, span_start

        span, key, kheap, vheap = vals[0], vals[1], vals[2], vals[3]
        scalar = jnp.ndim(span) == 0 and jnp.ndim(key) == 0
        span, key = jnp.atleast_1d(span), jnp.atleast_1d(key)
        span, key = jnp.broadcast_arrays(span, key)
        start, ln = span_start(span), span_len(span)
        offs = jnp.arange(expr.meta[0])
        if kheap.shape[0]:
            pos = jnp.clip(start[:, None] + offs[None, :], 0, kheap.shape[0] - 1)
            m = (offs[None, :] < ln[:, None]) & (kheap[pos] == key[:, None])
            found = jnp.any(m, axis=1)
            first = jnp.argmax(m, axis=1)
            out = vheap[pos[jnp.arange(span.shape[0]), first]].astype(
                expr.type.dtype)
        else:
            found = jnp.zeros(span.shape, bool)
            out = jnp.zeros(span.shape, expr.type.dtype)
        null = _combine_nulls(_combine_nulls(nulls[0], nulls[1]), ~found)
        if scalar:
            return out[0], (None if null is None else null[0])
        return out, null
    if op == "if":
        c, t, f = vals
        cn = nulls[0]
        cond = c if cn is None else (c & ~cn)
        nt, nf = nulls[1], nulls[2]
        if nt is None and nf is None:
            outn = None
        else:
            # nullness follows the SELECTED branch (a NULL in the untaken branch must
            # not poison the row)
            nt_ = jnp.zeros_like(cond) if nt is None else jnp.broadcast_to(nt, cond.shape)
            nf_ = jnp.zeros_like(cond) if nf is None else jnp.broadcast_to(nf, cond.shape)
            outn = jnp.where(cond, nt_, nf_)
        return jnp.where(cond, t, f), outn
    if op == "coalesce":
        out, outn = vals[-1], nulls[-1]
        for v, n in zip(reversed(vals[:-1]), reversed(nulls[:-1])):
            if n is None:
                out, outn = v, None
            else:
                out = jnp.where(n, out, v)
                outn = (jnp.zeros_like(n) if outn is None else outn) & n
        return out, outn
    if op == "rescale":
        # decimal scale adjustment; meta=(factor,) multiply, meta=(-factor,) divide w/ rounding
        factor = expr.meta[0]
        v = vals[0].astype(jnp.int64)
        if factor >= 0:
            return v * (10**factor), nulls[0]
        return _div_round_half_up(v, 10 ** (-factor)), nulls[0]
    if op == "cast":
        return _eval_cast(expr, vals[0], nulls[0])
    if op == "null_if_flag":
        # (value, flag) -> value with NULL where flag (TRY_CAST failure lanes)
        v, flag = vals
        n0 = nulls[0]
        return v, (flag if n0 is None else (n0 | flag))
    if op == "date_add_unit":
        # reference: scalar/DateTimeFunctions.addFieldValueDate
        unit = expr.meta[0]
        n, d = vals[0].astype(jnp.int64), vals[1].astype(jnp.int64)
        if unit == "day":
            out = d + n
        elif unit == "week":
            out = d + 7 * n
        else:  # month | year
            months = n * (12 if unit == "year" else 1)
            y, m, day = _extract_ymd(d)
            total = y * 12 + (m - 1) + months
            ny, nm = total // 12, total % 12 + 1
            # clamp the day to the target month's length
            first = _days_from_civil(ny, nm, jnp.ones_like(day))
            nxt_total = total + 1
            first_next = _days_from_civil(nxt_total // 12, nxt_total % 12 + 1,
                                          jnp.ones_like(day))
            mlen = first_next - first
            out = _days_from_civil(ny, nm, jnp.minimum(day, mlen))
        return out.astype(expr.type.dtype), _combine_nulls(*nulls)
    if op == "date_diff_unit":
        # reference: scalar/DateTimeFunctions.diffDate
        unit = expr.meta[0]
        d1, d2 = vals[0].astype(jnp.int64), vals[1].astype(jnp.int64)
        if unit == "day":
            out = d2 - d1
        elif unit == "week":
            out = (d2 - d1) // 7
        else:
            y1, m1, day1 = _extract_ymd(d1)
            y2, m2, day2 = _extract_ymd(d2)
            months = (y2 * 12 + m2) - (y1 * 12 + m1)
            months = months - jnp.where(day2 < day1, 1, 0)
            out = months // 12 if unit == "year" else months
        return out.astype(expr.type.dtype), _combine_nulls(*nulls)
    if op == "extract_year":
        return _extract_ymd(vals[0])[0].astype(expr.type.dtype), nulls[0]
    if op == "extract_month":
        return _extract_ymd(vals[0])[1].astype(expr.type.dtype), nulls[0]
    if op == "extract_day":
        return _extract_ymd(vals[0])[2].astype(expr.type.dtype), nulls[0]
    if op == "hash":
        from ..ops.hashing import hash_columns

        return hash_columns(vals), _combine_nulls(*nulls)
    if op in ("abs", "sqrt", "floor", "ceil", "exp", "ln", "round", "sign", "trunc",
              "log10", "log2", "sin", "cos", "tan", "asin", "acos", "atan", "cbrt",
              "degrees", "radians", "sinh", "cosh", "tanh"):
        fn = {
            "abs": jnp.abs,
            "sqrt": jnp.sqrt,
            "floor": jnp.floor,
            "ceil": jnp.ceil,
            "exp": jnp.exp,
            "ln": jnp.log,
            # Trino rounds half away from zero, not half-even
            "round": lambda x: jnp.sign(x) * jnp.floor(jnp.abs(x) + 0.5),
            "sign": jnp.sign,
            "trunc": jnp.trunc,
            "log10": jnp.log10,
            "log2": jnp.log2,
            "sin": jnp.sin,
            "cos": jnp.cos,
            "tan": jnp.tan,
            "asin": jnp.arcsin,
            "acos": jnp.arccos,
            "atan": jnp.arctan,
            "cbrt": jnp.cbrt,
            "degrees": jnp.degrees,
            "radians": jnp.radians,
            "sinh": jnp.sinh,
            "cosh": jnp.cosh,
            "tanh": jnp.tanh,
        }[op]
        return fn(vals[0]).astype(expr.type.dtype), nulls[0]
    if op == "log_b":  # log(b, x) — reference: MathFunctions.log
        return (jnp.log(vals[1].astype(jnp.float64))
                / jnp.log(vals[0].astype(jnp.float64))), _combine_nulls(*nulls)
    if op in ("is_nan", "is_finite", "is_infinite"):
        fn = {"is_nan": jnp.isnan, "is_finite": jnp.isfinite,
              "is_infinite": jnp.isinf}[op]
        return fn(vals[0].astype(jnp.float64)), nulls[0]
    if op in ("bitwise_and", "bitwise_or", "bitwise_xor",
              "bitwise_left_shift", "bitwise_right_shift",
              "bitwise_right_shift_arithmetic"):
        a = vals[0].astype(jnp.int64)
        b = vals[1].astype(jnp.int64)
        if op == "bitwise_and":
            out = a & b
        elif op == "bitwise_or":
            out = a | b
        elif op == "bitwise_xor":
            out = a ^ b
        elif op == "bitwise_left_shift":
            out = jnp.where(b >= 64, 0, a << jnp.clip(b, 0, 63))
        elif op == "bitwise_right_shift":  # LOGICAL: zero-filling
            u = a.view(jnp.uint64) >> jnp.clip(b, 0, 63).astype(jnp.uint64)
            out = jnp.where(b >= 64, 0, u.view(jnp.int64))
        else:  # arithmetic: sign-extending
            out = jnp.where(b >= 64, jnp.where(a < 0, -1, 0),
                            a >> jnp.clip(b, 0, 63))
        return out, _combine_nulls(*nulls)
    if op == "bitwise_not":
        return ~vals[0].astype(jnp.int64), nulls[0]
    if op == "bit_count":
        # popcount of the low `bits` bits of the two's-complement value
        # (reference: BitwiseFunctions.bitCount).  The reference RAISES when
        # the value doesn't fit `bits`; a traced kernel cannot raise per row,
        # so out-of-range values yield NULL instead (documented deviation,
        # same policy as hamming_distance)
        bits = expr.meta[0]
        v = vals[0].astype(jnp.int64)
        u = v.view(jnp.uint64)
        if bits < 64:
            u = u & jnp.uint64((1 << bits) - 1)
            lo, hi = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
            bad = (v < lo) | (v > hi)
        else:
            bad = jnp.zeros(v.shape, bool)
        out = jax.lax.population_count(u).astype(jnp.int64)
        return out, _combine_nulls(nulls[0], bad)
    if op == "truncate_n":  # truncate(x, n): toward zero at n decimals
        n = expr.meta[0]
        f = 10.0 ** n
        return (jnp.trunc(vals[0] * f) / f).astype(expr.type.dtype), nulls[0]
    if op == "last_day_of_month":
        y, m, d = _extract_ymd(vals[0])
        ny = jnp.where(m == 12, y + 1, y)
        nm = jnp.where(m == 12, 1, m + 1)
        first_next = _days_from_civil(ny, nm, jnp.ones_like(d))
        return (first_next - 1).astype(expr.type.dtype), nulls[0]
    if op == "ts_extract":
        # timestamp part extraction: meta = (part, precision)
        part, prec = expr.meta
        unit = 10 ** prec
        v = vals[0].astype(jnp.int64)
        secs = jnp.floor_divide(v, unit)
        days = jnp.floor_divide(secs, 86400)
        if part in ("hour", "minute", "second"):
            tod = secs - days * 86400
            if part == "hour":
                out = tod // 3600
            elif part == "minute":
                out = (tod // 60) % 60
            else:
                out = tod % 60
            return out.astype(jnp.int64), nulls[0]
        if part == "millisecond":
            frac = v - secs * unit
            ms = frac * 1000 // unit if prec <= 3 else frac // (unit // 1000)
            return ms.astype(jnp.int64), nulls[0]
        y, m, d = _extract_ymd(days.astype(jnp.int32))
        if part == "year":
            return y.astype(jnp.int64), nulls[0]
        if part == "month":
            return m.astype(jnp.int64), nulls[0]
        if part == "day":
            return d.astype(jnp.int64), nulls[0]
        if part == "quarter":
            return ((m - 1) // 3 + 1).astype(jnp.int64), nulls[0]
        raise NotImplementedError(f"ts_extract {part}")
    if op == "ts_to_date":
        prec = expr.meta[0]
        v = vals[0].astype(jnp.int64)
        return jnp.floor_divide(jnp.floor_divide(v, 10 ** prec),
                                86400).astype(expr.type.dtype), nulls[0]
    if op in ("week_of_year", "year_of_week"):
        # ISO-8601 week numbering (reference: DateTimeFunctions.weekFromDate —
        # Joda ISOChronology.weekOfWeekyear/weekyear)
        days = vals[0].astype(jnp.int64)
        y, m, d = _extract_ymd(vals[0])
        dow = jnp.remainder(days + 3, 7) + 1  # ISO Monday=1
        jan1 = _days_from_civil(y, jnp.ones_like(m), jnp.ones_like(d))
        doy = days - jan1 + 1

        def weeks_in(yy):
            p = (yy + yy // 4 - yy // 100 + yy // 400) % 7
            pm = ((yy - 1) + (yy - 1) // 4 - (yy - 1) // 100
                  + (yy - 1) // 400) % 7
            return 52 + ((p == 4) | (pm == 3)).astype(jnp.int64)

        w = (doy - dow + 10) // 7
        under = w < 1
        over = w > weeks_in(y)
        if op == "week_of_year":
            out = jnp.where(under, weeks_in(y - 1), jnp.where(over, 1, w))
        else:
            out = jnp.where(under, y - 1, jnp.where(over, y + 1, y))
        return out.astype(jnp.int64), nulls[0]
    if op == "power":
        return jnp.power(vals[0].astype(jnp.float64),
                         vals[1].astype(jnp.float64)), _combine_nulls(*nulls)
    if op in ("normal_cdf", "inverse_normal_cdf", "beta_cdf",
              "wilson_interval_lower", "wilson_interval_upper"):
        # statistical CDF family (reference: operator/scalar/MathFunctions —
        # normalCdf/inverseNormalCdf/betaCdf/wilsonInterval*): XLA-native via
        # jax.scipy.special, no host round-trips
        from jax.scipy import special as _sp

        fv = [v.astype(jnp.float64) for v in vals]
        if op == "normal_cdf":  # (mean, sd, value)
            out = _sp.ndtr((fv[2] - fv[0]) / fv[1])
        elif op == "inverse_normal_cdf":  # (mean, sd, p)
            out = fv[0] + fv[1] * _sp.ndtri(fv[2])
        elif op == "beta_cdf":  # (a, b, value)
            out = _sp.betainc(fv[0], fv[1], fv[2])
        else:  # wilson score interval: (successes, trials, z)
            s, t, z = fv
            p = s / t
            z2n = z * z / t
            center = p + z2n / 2.0
            # z^2/(4t^2) written as (z/t)^2/4 — the z2n^2/(4z^2) form is
            # 0/0 = NaN at z = 0, where the interval should collapse to p
            half = z * jnp.sqrt(p * (1 - p) / t + (z / t) * (z / t) / 4.0)
            out = (center - half if op == "wilson_interval_lower"
                   else center + half) / (1.0 + z2n)
        return out, _combine_nulls(*nulls)
    if op == "atan2":
        return jnp.arctan2(vals[0].astype(jnp.float64),
                           vals[1].astype(jnp.float64)), _combine_nulls(*nulls)
    if op == "round_n":
        # round(x, n) to n decimal places, half AWAY from zero (Trino semantics,
        # not numpy's banker's rounding)
        n = expr.meta[0]
        f = 10.0 ** n
        x = vals[0] * f
        r = jnp.sign(x) * jnp.floor(jnp.abs(x) + 0.5)
        return (r / f).astype(expr.type.dtype), nulls[0]
    if op == "nullif":
        # NULL if a = b is KNOWN true; otherwise a (b being NULL -> comparison
        # unknown -> a survives)
        an, bn = nulls
        known = jnp.ones_like(vals[0], dtype=bool)
        if an is not None:
            known = known & ~an
        if bn is not None:
            known = known & ~bn
        eq = (vals[0] == vals[1]) & known
        newnull = eq if an is None else (an | eq)
        return vals[0], newnull
    if op == "width_bucket":
        v, lo, hi, n = vals
        frac = (v.astype(jnp.float64) - lo) / jnp.where(hi == lo, 1, hi - lo)
        b = jnp.floor(frac * n).astype(jnp.int64) + 1
        return jnp.clip(b, 0, n.astype(jnp.int64) + 1), _combine_nulls(*nulls)
    if op in ("date_trunc_year", "date_trunc_quarter", "date_trunc_month",
              "date_trunc_week", "date_trunc_day"):
        y, m, d = _extract_ymd(vals[0])
        if op == "date_trunc_day":
            return vals[0], nulls[0]
        if op == "date_trunc_week":  # ISO Monday
            dow = jnp.remainder(vals[0].astype(jnp.int64) + 3, 7)  # 0=Monday
            return (vals[0].astype(jnp.int64) - dow).astype(expr.type.dtype), nulls[0]
        if op == "date_trunc_month":
            mm = m
        elif op == "date_trunc_quarter":
            mm = ((m - 1) // 3) * 3 + 1
        else:
            mm = jnp.ones_like(m)
        return _days_from_civil(y, mm, jnp.ones_like(d)).astype(expr.type.dtype), nulls[0]
    if op == "day_of_week":  # ISO: Monday=1..Sunday=7
        return (jnp.remainder(vals[0].astype(jnp.int64) + 3, 7) + 1), nulls[0]
    if op == "day_of_year":
        y, m, d = _extract_ymd(vals[0])
        jan1 = _days_from_civil(y, jnp.ones_like(m), jnp.ones_like(d))
        return (vals[0].astype(jnp.int64) - jan1 + 1), nulls[0]
    if op == "extract_quarter":
        _, m, _ = _extract_ymd(vals[0])
        return ((m - 1) // 3 + 1).astype(expr.type.dtype), nulls[0]
    if op == "greatest":
        out = vals[0]
        for v in vals[1:]:
            out = jnp.maximum(out, v)
        return out.astype(expr.type.dtype), _combine_nulls(*nulls)
    if op == "least":
        out = vals[0]
        for v in vals[1:]:
            out = jnp.minimum(out, v)
        return out.astype(expr.type.dtype), _combine_nulls(*nulls)
    raise NotImplementedError(f"scalar op {op!r}")


def _extreme_for(dtype, sign):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.inf * sign
    info = jnp.iinfo(dtype)
    return info.max if sign > 0 else info.min


def _eval_cast(expr: Call, v, n):
    src = expr.args[0].type
    dst = expr.type
    if isinstance(dst, DecimalType):
        if isinstance(src, DecimalType):
            diff = dst.scale - src.scale
            v64 = v.astype(jnp.int64)
            out = v64 * (10**diff) if diff >= 0 else _div_round_half_up(v64, 10**-diff)
            return out, n
        if src.is_integer:
            return v.astype(jnp.int64) * (10**dst.scale), n
        if src.is_floating:
            return jnp.round(v * (10**dst.scale)).astype(jnp.int64), n
    if dst.is_floating and isinstance(src, DecimalType):
        return (v.astype(dst.dtype) / (10**src.scale)), n
    if dst.is_floating or dst.is_integer or dst.name == "boolean":
        if isinstance(src, DecimalType) and dst.is_integer:
            return _div_round_half_up(v.astype(jnp.int64), 10**src.scale).astype(dst.dtype), n
        return v.astype(dst.dtype), n
    if dst.name.startswith("timestamp") or src.name.startswith("timestamp"):
        from ..types import TimestampType

        sp = src.precision if isinstance(src, TimestampType) else None
        dp = dst.precision if isinstance(dst, TimestampType) else None
        v64 = v.astype(jnp.int64)
        if sp is not None and dp is not None:  # rescale between precisions
            diff = dp - sp
            if diff >= 0:
                return v64 * (10 ** diff), n
            # reference rounds HALF UP when narrowing timestamp precision
            return _div_round_half_up(v64, 10 ** -diff), n
        if sp is None and dp is not None and src.name == "date":
            return v64 * (86400 * 10 ** dp), n
        if sp is not None and dst.name == "date":
            return jnp.floor_divide(jnp.floor_divide(v64, 10 ** sp),
                                    86400).astype(dst.dtype), n
        raise NotImplementedError(
            f"cast {src.name} -> {dst.name} not supported (a fall-through "
            f"would reinterpret raw storage as timestamp units)")
    if dst.name.startswith("date") and src.name.startswith("varchar"):
        raise NotImplementedError("runtime varchar->date cast; planner folds literals")
    return v.astype(dst.dtype), n


def _div_round_half_up(num, den):
    """Decimal division with HALF_UP rounding (reference: DecimalOperators#divide)."""
    q, r = jnp.divmod(num, den)
    # jnp.divmod floors; adjust to round-half-up on magnitude
    half = den // 2
    adjust = (r > half) | ((r == half) & (den % 2 == 0) & (num >= 0))
    # for negatives, floor division already moved down; recompute symmetric rounding:
    neg = num < 0
    absq, absr = jnp.divmod(jnp.abs(num), den)
    rounded = absq + (absr * 2 >= den)
    del q, adjust, half, r
    return jnp.where(neg, -rounded, rounded)


def _days_from_civil(y, m, d):
    """Inverse of _extract_ymd (Howard Hinnant's days_from_civil), vectorized."""
    y = jnp.where(m <= 2, y - 1, y)
    era = jnp.where(y >= 0, y, y - 399) // 400
    yoe = y - era * 400
    mp = jnp.where(m > 2, m - 3, m + 9)
    doy = (153 * mp + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


def _extract_ymd(days):
    """Civil-from-days (Howard Hinnant's algorithm), vectorized; days since 1970-01-01."""
    z = days.astype(jnp.int64) + 719468
    era = jnp.where(z >= 0, z, z - 146096) // 146097
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = jnp.where(mp < 10, mp + 3, mp - 9)
    y = jnp.where(m <= 2, y + 1, y)
    return y, m, d
