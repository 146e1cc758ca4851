"""Aggregation planning helpers: post-aggregation scope, sugar rewrites
(count_if / geometric_mean / the covar-regr-corr moment family), agg call
classification and typing.

Reference: AggregationNode planning in sql/planner/QueryPlanner.java plus the
operator/aggregation/ sugar the analyzer resolves — split out of the one-pass
frontend (round-4 verdict item 5).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Optional

import numpy as np

from ..page import Field, Schema
from ..types import (BIGINT, BOOLEAN, DATE, DOUBLE, INTEGER, UNKNOWN, DecimalType, Type,
                     VarcharType, common_super_type, parse_date_literal)
from . import ir
from . import parser as A
from . import plan as P
from .analyzer import (AGG_FUNCS, ColumnInfo, SemanticError,
                       _add_months_const, _arith, _coerce, _interval_days,
                       _interval_months, _interval_seconds, _literal_number,
                       _resolve_column, _rewrite_ast, _type_from_name)

from .planbase import RelPlan, _split_conjuncts, _and_all, _derive_name


class _PostAggScope:
    """Rewrites post-aggregation expressions over (group keys + agg calls) channels."""

    def __init__(self, group_asts, agg_asts, agg_cols, planner):
        self.group_asts = group_asts
        self.agg_asts = agg_asts
        self.agg_cols = agg_cols
        self.planner = planner
        # id(returned Constant) -> Dictionary for string literals in the
        # output list (global-agg channel tags: select 'tot', count(*) ...)
        self.const_dicts: dict = {}

    def translate_output(self, ast) -> ir.Expr:
        """A SELECT-list item: like translate(), plus top-level string
        literals (channel tags) whose dictionary the caller recovers from
        const_dicts by the returned Constant's id()."""
        if isinstance(ast, A.StringLit):
            from .analyzer import _string_const

            e, d = _string_const(ast.value)
            self.const_dicts[id(e)] = d
            return e
        return self.translate(ast)

    def _dict_of(self, e):
        """ENUMERABLE dictionary of a translated channel ref, if any.  A
        formatter/pattern dictionary (values=None) cannot resolve a literal
        — returning it would turn the caller's SemanticError into a bare
        KeyError from Dictionary.lookup."""
        if isinstance(e, ir.FieldRef) and e.index < len(self.agg_cols):
            d = self.agg_cols[e.index].dict
            if d is not None and getattr(d, "values", None) is not None:
                return d
        return None

    def translate(self, ast) -> ir.Expr:
        for i, g in enumerate(self.group_asts):
            if ast == g:
                c = self.agg_cols[i]
                return ir.FieldRef(i, c.type, c.name)
        for j, a in enumerate(self.agg_asts):
            if ast == a:
                ch = len(self.group_asts) + j
                c = self.agg_cols[ch]
                return ir.FieldRef(ch, c.type, c.name)
        # recurse structurally
        if isinstance(ast, A.BinaryOp):
            if ast.op in ("eq", "neq") and (
                    isinstance(ast.left, A.StringLit)
                    ^ isinstance(ast.right, A.StringLit)):
                # HAVING min(status) = 'shipped': resolve the literal against
                # the channel's dictionary (ordering comparisons stay
                # unsupported — id order is not collation order)
                lit, other_ast = (ast.left, ast.right) \
                    if isinstance(ast.left, A.StringLit) \
                    else (ast.right, ast.left)
                other = self.translate(other_ast)
                d = self._dict_of(other)
                if d is None:
                    raise SemanticError(
                        "string comparison needs a dictionary-backed channel")
                c = ir.Constant(d.lookup(lit.value), other.type)
                return ir.Call(ast.op, (other, c), BOOLEAN)
            l = self.translate(ast.left)
            r = self.translate(ast.right)
            if ast.op in ("and", "or"):
                return ir.Call(ast.op, (l, r), BOOLEAN)
            if ast.op in ("eq", "neq", "lt", "lte", "gt", "gte"):
                t = common_super_type(l.type, r.type)
                return ir.Call(ast.op, (_coerce(l, t), _coerce(r, t)), BOOLEAN)
            return _arith(ast.op, l, r)
        if isinstance(ast, A.NumberLit):
            return _literal_number(ast.text)
        if isinstance(ast, A.StringLit):
            # nested string literals would need the enclosing expression to
            # thread a dictionary; only top-level output tags
            # (translate_output) and dictionary-resolved comparisons
            # (_translate_cmp) support them
            raise SemanticError(
                f"string literal {ast.value!r} in post-aggregation "
                "expression context")
        if isinstance(ast, A.UnaryOp) and ast.op == "negate":
            e = self.translate(ast.operand)
            return ir.Call("negate", (e,), e.type)
        if isinstance(ast, A.UnaryOp) and ast.op == "not":
            return ir.Call("not", (self.translate(ast.operand),), BOOLEAN)
        if isinstance(ast, A.Between):
            # HAVING count(*) BETWEEN a AND b and friends: desugar over the
            # translated aggregate channel
            v = self.translate(ast.value)
            lo, hi = self.translate(ast.low), self.translate(ast.high)
            t = common_super_type(v.type, common_super_type(lo.type, hi.type))
            cond = ir.Call("and", (
                ir.Call("gte", (_coerce(v, t), _coerce(lo, t)), BOOLEAN),
                ir.Call("lte", (_coerce(v, t), _coerce(hi, t)), BOOLEAN)),
                BOOLEAN)
            return ir.Call("not", (cond,), BOOLEAN) if ast.negated else cond
        if isinstance(ast, A.InList):
            v = self.translate(ast.value)
            cond = None
            for item in ast.items:
                x = self.translate(item)
                t = common_super_type(v.type, x.type)
                eq = ir.Call("eq", (_coerce(v, t), _coerce(x, t)), BOOLEAN)
                cond = eq if cond is None else ir.Call("or", (cond, eq),
                                                       BOOLEAN)
            if cond is None:
                cond = ir.Constant(False, BOOLEAN)
            return ir.Call("not", (cond,), BOOLEAN) if ast.negated else cond
        if isinstance(ast, A.IsNull):
            v = self.translate(ast.value)
            cond = ir.Call("is_null", (v,), BOOLEAN)
            return ir.Call("not", (cond,), BOOLEAN) if ast.negated else cond
        if isinstance(ast, A.CaseExpr) and ast.operand is None:
            whens = [(self.translate(c), self.translate(v))
                     for c, v in ast.whens]
            default = self.translate(ast.default) \
                if ast.default is not None else None
            t = whens[0][1].type
            for _, v in whens[1:]:
                t = common_super_type(t, v.type)
            if default is not None:
                t = common_super_type(t, default.type)
            out = _coerce(default, t) if default is not None \
                else ir.Constant(None, t)
            for c, v in reversed(whens):
                out = ir.Call("if", (c, _coerce(v, t), out), t)
            return out
        if isinstance(ast, A.Cast):
            return _coerce(self.translate(ast.value), _type_from_name(ast.type_name, ast.params))
        if isinstance(ast, A.ScalarSubquery):
            return self.planner._eager_scalar(ast.query)
        if isinstance(ast, A.FuncCall) and len(ast.args) == 1 \
                and ast.name in ("exp", "ln", "sqrt", "abs", "floor", "ceil",
                                 "round", "sign", "log10", "log2"):
            # scalar math over aggregate results (sqrt(variance),
            # exp(avg(ln)) from the geometric_mean rewrite, ...)
            e = self.translate(ast.args[0])
            if ast.name in ("abs", "round", "sign"):
                return ir.Call(ast.name, (e,), e.type)
            return ir.Call(ast.name, (_coerce(e, DOUBLE),), DOUBLE)
        if isinstance(ast, A.FuncCall) and ast.name == "round" \
                and len(ast.args) == 2:
            # round(aggregate expr, literal integer scale)
            scale_ast = ast.args[1]
            neg = isinstance(scale_ast, A.UnaryOp) \
                and scale_ast.op in ("-", "negate")
            if neg:
                scale_ast = scale_ast.operand
            if not (isinstance(scale_ast, A.NumberLit)
                    and scale_ast.text.lstrip("-").isdigit()):
                raise SemanticError("round() scale must be an integer literal")
            e = _coerce(self.translate(ast.args[0]), DOUBLE)
            n = int(scale_ast.text)
            return ir.Call("round_n", (e,), DOUBLE,
                           meta=(-n if neg else n,))
        if isinstance(ast, A.FuncCall) and ast.name in ("power", "pow") \
                and len(ast.args) == 2:
            a = _coerce(self.translate(ast.args[0]), DOUBLE)
            b = _coerce(self.translate(ast.args[1]), DOUBLE)
            return ir.Call("power", (a, b), DOUBLE)
        if isinstance(ast, A.FuncCall) and ast.name == "coalesce" \
                and ast.args:
            args = [self.translate(a) for a in ast.args]
            t = args[0].type
            for a in args[1:]:
                t = common_super_type(t, a.type)
            return ir.Call("coalesce", tuple(_coerce(a, t) for a in args), t)
        if isinstance(ast, A.FuncCall) and ast.name == "nullif" \
                and len(ast.args) == 2:
            # the statistical-aggregate finalizers divide by nullif(n, 0)
            a = self.translate(ast.args[0])
            b = self.translate(ast.args[1])
            t = common_super_type(a.type, b.type)
            return ir.Call("nullif", (_coerce(a, t), _coerce(b, t)), t)
        raise SemanticError(f"expression must appear in GROUP BY: {ast}")


_STATS2_AGGS = {"covar_pop", "covar_samp", "corr", "regr_slope",
                "regr_intercept", "regr_count", "regr_avgx", "regr_avgy",
                "regr_sxx", "regr_syy", "regr_sxy", "regr_r2"}
_AGG_SUGAR = {"count_if", "geometric_mean", "skewness", "kurtosis"} \
    | _STATS2_AGGS


def _stats2_rewrite(name: str, y: A.Node, x: A.Node) -> A.Node:
    """Two-argument statistical aggregates decomposed into MOMENT SUMS over
    pairwise-non-null rows + a finalize expression (reference:
    operator/aggregation/ CovarianceAggregation / RegressionAggregation /
    CorrelationAggregation keep the same running moments in their state; on
    TPU the moments are plain sum/count aggregates the partial-aggregation
    machinery already distributes, and the finalize is a scalar expression).

    Signature order matches the reference: f(y, x) — y dependent, x
    independent (AggregationUtils.java's y/x naming)."""
    pair = A.BinaryOp("and", A.IsNull(y, True), A.IsNull(x, True))

    def when(v):
        return A.CaseExpr(None, ((pair, v),), None)

    def dbl(e):
        return A.Cast(e, "double")

    xd, yd = dbl(x), dbl(y)
    n = A.Cast(A.FuncCall("count", (when(A.NumberLit("1")),)), "double")
    sx = A.FuncCall("sum", (when(xd),))
    sy = A.FuncCall("sum", (when(yd),))
    sxy = A.FuncCall("sum", (when(A.BinaryOp("multiply", xd, yd)),))
    sxx = A.FuncCall("sum", (when(A.BinaryOp("multiply", xd, xd)),))
    syy = A.FuncCall("sum", (when(A.BinaryOp("multiply", yd, yd)),))

    def sub(a, b):
        return A.BinaryOp("subtract", a, b)

    def mul(a, b):
        return A.BinaryOp("multiply", a, b)

    def div(a, b):
        # NULL on a zero denominator (SQL contract: undefined moments = NULL)
        return A.BinaryOp("divide", a, A.FuncCall("nullif", (b, A.NumberLit("0"))))

    c_sxy = sub(sxy, div(mul(sx, sy), n))  # n*cov_pop
    c_sxx = sub(sxx, div(mul(sx, sx), n))  # n*var_pop(x)
    c_syy = sub(syy, div(mul(sy, sy), n))  # n*var_pop(y)
    if name == "regr_count":
        return A.FuncCall("count", (when(A.NumberLit("1")),))
    if name == "regr_avgx":
        return div(sx, n)
    if name == "regr_avgy":
        return div(sy, n)
    if name == "regr_sxx":
        return c_sxx
    if name == "regr_syy":
        return c_syy
    if name == "regr_sxy":
        return c_sxy
    if name == "covar_pop":
        return div(c_sxy, n)
    if name == "covar_samp":
        return div(c_sxy, sub(n, A.NumberLit("1")))
    if name == "regr_slope":
        return div(c_sxy, c_sxx)
    if name == "regr_intercept":
        return div(sub(sy, mul(div(c_sxy, c_sxx), sx)), n)
    if name == "corr":
        return div(c_sxy, A.FuncCall("sqrt", (mul(c_sxx, c_syy),)))
    if name == "regr_r2":
        # r² = corr², except a CONSTANT dependent variable (var(y)=0 with
        # var(x)>0) is a perfect fit: 1.0 (SQL contract); var(x)=0 stays NULL
        # through the nullif-guarded division
        r = div(c_sxy, A.FuncCall("sqrt", (mul(c_sxx, c_syy),)))
        # "var(y)=0" must tolerate catastrophic cancellation in syy - sy²/n,
        # but ONLY at the float64 rounding floor (~20 ulp of the raw second
        # moment): a looser bound (1e-12) fabricated perfect fits for data
        # with mean/stddev beyond ~1e6 (epoch millis, large ids)
        const_y = A.BinaryOp(
            "and",
            A.BinaryOp("lte", c_syy, mul(A.NumberLit("4e-15"), syy)),
            A.BinaryOp("gt", c_sxx, mul(A.NumberLit("4e-15"), sxx)))
        return A.CaseExpr(None, ((const_y, A.NumberLit("1.0")),), mul(r, r))
    raise SemanticError(f"unknown statistical aggregate {name}")


def _moments_rewrite(name: str, x: A.Node) -> A.Node:
    """skewness/kurtosis from raw moments (reference:
    operator/aggregation/CentralMomentsAggregation — same moments, here as
    plain distributable sums + a finalize expression)."""
    xd = A.Cast(x, "double")
    n = A.Cast(A.FuncCall("count", (x,)), "double")
    s1 = A.FuncCall("sum", (xd,))
    s2 = A.FuncCall("sum", (A.BinaryOp("multiply", xd, xd),))
    s3 = A.FuncCall("sum", (A.BinaryOp("multiply", A.BinaryOp("multiply", xd, xd), xd),))

    def div(a, b):
        return A.BinaryOp("divide", a, A.FuncCall("nullif", (b, A.NumberLit("0"))))

    mean = div(s1, n)
    m2 = A.BinaryOp("subtract", div(s2, n), A.BinaryOp("multiply", mean, mean))  # var_pop
    if name == "skewness":
        # E[x³] - 3·mean·E[x²] + 2·mean³, normalized by var_pop^{3/2}
        ex3 = div(s3, n)
        ex2 = div(s2, n)
        m3 = A.BinaryOp(
            "subtract",
            A.BinaryOp("add", ex3,
                       A.BinaryOp("multiply", A.NumberLit("2.0"),
                                  A.BinaryOp("multiply", mean, A.BinaryOp(
                                      "multiply", mean, mean)))),
            A.BinaryOp("multiply", A.NumberLit("3.0"), A.BinaryOp("multiply", mean, ex2)))
        return div(m3, A.FuncCall(
            "power", (m2, A.NumberLit("1.5"))))
    if name == "kurtosis":
        x2 = A.BinaryOp("multiply", xd, xd)
        s4 = A.FuncCall("sum", (A.BinaryOp("multiply", x2, x2),))
        ex4, ex3, ex2 = div(s4, n), div(s3, n), div(s2, n)
        m4 = A.BinaryOp(
            "subtract",
            A.BinaryOp(
                "add", ex4,
                A.BinaryOp(
                    "subtract",
                    A.BinaryOp("multiply", A.NumberLit("6.0"),
                               A.BinaryOp("multiply", A.BinaryOp("multiply", mean, mean),
                                          ex2)),
                    A.BinaryOp("multiply", A.NumberLit("3.0"),
                               A.BinaryOp("multiply", A.BinaryOp("multiply", mean, mean),
                                          A.BinaryOp("multiply", mean, mean))))),
            A.BinaryOp("multiply", A.NumberLit("4.0"), A.BinaryOp("multiply", mean, ex3)))
        # excess-kurtosis-free definition (the reference's kurtosis):
        # n*m4/m2² - 3 with the sample correction folded by the caller; we
        # return the population kurtosis m4/m2² (documented deviation)
        return div(m4, A.BinaryOp("multiply", m2, m2))
    raise SemanticError(f"unknown moment aggregate {name}")


def _rewrite_agg_sugar(node):
    """Aggregate sugar rewrites to supported compositions (reference:
    operator/aggregation/CountIfAggregation, GeometricMeanAggregations,
    CovarianceAggregation family — all reduce to existing aggregates):
      count_if(x)       -> sum(CASE WHEN x THEN 1 ELSE 0 END)
      geometric_mean(x) -> exp(avg(ln(x)))
      covar_/regr_/corr -> moment sums + finalize (_stats2_rewrite)
      skewness/kurtosis -> raw moments + finalize (_moments_rewrite)
    Deterministic over frozen ASTs, so repeated rewrites of equal expressions
    stay structurally equal (the post-aggregation scope matches by equality)."""
    if isinstance(node, A.FuncCall) and node.name in _AGG_SUGAR:
        args = tuple(_rewrite_agg_sugar(a) for a in node.args)
        if node.name == "count_if" and len(args) == 1:
            # coalesce: count_if of ZERO rows is 0 (a count), while the
            # underlying sum over an empty group is SQL NULL
            return A.FuncCall("coalesce", (A.FuncCall("sum", (A.CaseExpr(
                None, ((args[0], A.NumberLit("1")),), A.NumberLit("0")),)),
                A.NumberLit("0")))
        if node.name == "geometric_mean" and len(args) == 1:
            return A.FuncCall("exp", (A.FuncCall(
                "avg", (A.FuncCall("ln", (args[0],)),)),))
        if node.name in _STATS2_AGGS and len(args) == 2:
            return _stats2_rewrite(node.name, args[0], args[1])
        if node.name in ("skewness", "kurtosis") and len(args) == 1:
            return _moments_rewrite(node.name, args[0])
        return dataclasses.replace(node, args=args)
    if dataclasses.is_dataclass(node) and not isinstance(node, type):
        changes = {}
        for f in dataclasses.fields(node):
            v = getattr(node, f.name)
            nv = _rewrite_sugar_any(v)
            if nv is not v:
                changes[f.name] = nv
        return dataclasses.replace(node, **changes) if changes else node
    return node


def _rewrite_sugar_any(v):
    if isinstance(v, tuple):
        out = tuple(_rewrite_sugar_any(x) for x in v)
        return v if out == v else out
    if dataclasses.is_dataclass(v) and not isinstance(v, type):
        return _rewrite_agg_sugar(v)
    return v


def _rewrite_agg_sugar_query(q):
    """Rewrite sugar in the query's own expressions (items/having/order_by);
    subqueries rewrite when their own planning reaches _plan_select."""
    items = tuple(dataclasses.replace(it, expr=_rewrite_agg_sugar(it.expr))
                  for it in q.items)
    having = None if q.having is None else _rewrite_agg_sugar(q.having)
    order_by = tuple(dataclasses.replace(s, expr=_rewrite_agg_sugar(s.expr))
                     for s in q.order_by)
    if items == q.items and having == q.having and order_by == q.order_by:
        return q
    return dataclasses.replace(q, items=items, having=having,
                               order_by=order_by)


def _collect_aggs(ast, out: list):
    if isinstance(ast, A.FuncCall) and ast.name in AGG_FUNCS:
        out.append(ast)
        return
    if isinstance(ast, (A.ScalarSubquery, A.InSubquery, A.Exists, A.SubqueryRef, A.Select,
                        A.WindowCall)):
        return  # subquery scopes own their aggregates; sum() OVER is a window, not an agg
    for f in dataclasses.fields(ast) if dataclasses.is_dataclass(ast) else ():
        v = getattr(ast, f.name)
        if isinstance(v, A.Node):
            _collect_aggs(v, out)
        elif isinstance(v, tuple):
            for x in v:
                if isinstance(x, A.Node):
                    _collect_aggs(x, out)
                elif isinstance(x, tuple):
                    for y in x:
                        if isinstance(y, A.Node):
                            _collect_aggs(y, out)


def _collect_windows(ast, out: list):
    if isinstance(ast, A.WindowCall):
        out.append(ast)
        return
    if isinstance(ast, (A.ScalarSubquery, A.InSubquery, A.Exists, A.SubqueryRef, A.Select)):
        return
    for f in dataclasses.fields(ast) if dataclasses.is_dataclass(ast) else ():
        v = getattr(ast, f.name)
        if isinstance(v, A.Node):
            _collect_windows(v, out)
        elif isinstance(v, tuple):
            for x in v:
                if isinstance(x, A.Node):
                    _collect_windows(x, out)


def _replace_nodes(ast, mapping: dict):
    """Structurally rebuild an AST with ``mapping`` substitutions (frozen
    dataclasses).  Recurses through NESTED tuples too — CaseExpr.whens holds
    (cond, value) pairs, so a substitution target can sit two tuples deep."""
    if isinstance(ast, tuple):
        nv = tuple(_replace_nodes(x, mapping) for x in ast)
        return ast if nv == ast else nv
    if not dataclasses.is_dataclass(ast):
        return ast
    if ast in mapping:
        return mapping[ast]
    changes = {}
    for f in dataclasses.fields(ast):
        v = getattr(ast, f.name)
        if isinstance(v, (A.Node, tuple)):
            nv = _replace_nodes(v, mapping)
            if nv is not v and nv != v:
                changes[f.name] = nv
    return dataclasses.replace(ast, **changes) if changes else ast


_AGG_ALIASES = {"every": "bool_and", "any_value": "arbitrary",
                "variance": "var_samp", "stddev": "stddev_samp"}


def _agg_kind(ast: A.FuncCall):
    name = _AGG_ALIASES.get(ast.name, ast.name)
    if name == "count":
        if not ast.args or isinstance(ast.args[0], A.Star):
            return "count_star", None
        return "count", ast.args[0]
    if name == "approx_most_frequent":
        # approx_most_frequent(buckets, value, capacity): VALUE is arg 2
        if len(ast.args) < 2:
            raise SemanticError(
                "approx_most_frequent(buckets, value[, capacity]) needs a "
                "value argument")
        return name, ast.args[1]
    if name in ("max_by", "min_by"):
        # max_by(x, y): the RANKING argument y drives the segment sort; the
        # payload x rides an extra projected channel (aggplan)
        if len(ast.args) != 2:
            raise SemanticError(f"{name}(x, y) takes exactly two arguments")
        return name, ast.args[1]
    if name == "map_agg":
        if len(ast.args) != 2:
            raise SemanticError("map_agg(key, value) takes two arguments")
        return name, ast.args[0]
    if not ast.args:
        raise SemanticError(f"{name} requires an argument")
    return name, ast.args[0]


def _agg_type(kind: str, in_type: Type) -> Type:
    if kind in ("count", "count_star", "approx_distinct"):
        return BIGINT
    if kind == "sum":
        if isinstance(in_type, DecimalType):
            # reference: sum(decimal(p,s)) -> decimal(38,s)
            # (DecimalSumAggregation with Int128 state); the two-limb
            # accumulators make the wide sum exact
            return DecimalType.of(38, in_type.scale)
        return DOUBLE if in_type.is_floating else BIGINT
    if kind == "avg":
        if isinstance(in_type, DecimalType):
            return in_type
        return DOUBLE
    if kind in ("var_pop", "var_samp", "stddev_pop", "stddev_samp"):
        return DOUBLE
    if kind in ("bool_and", "bool_or"):
        return BOOLEAN
    if kind == "listagg":
        return VarcharType.of(None)
    if kind == "approx_most_frequent":
        from ..types import MapType

        return MapType.of(in_type, BIGINT)
    if kind == "histogram":
        from ..types import MapType

        return MapType.of(in_type, BIGINT)
    if kind == "array_agg":
        from ..types import ArrayType

        return ArrayType.of(in_type)
    if kind in ("checksum", "bitwise_and_agg", "bitwise_or_agg",
                "bitwise_xor_agg"):
        return BIGINT
    # max_by/min_by/map_agg output types depend on the OTHER argument's
    # channel; aggplan overrides the spec type after planning it
    return in_type  # min/max/arbitrary/approx_percentile


