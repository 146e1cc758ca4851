"""Iterative rule-based optimizer over a Memo.

Reference architecture: sql/planner/iterative/IterativeOptimizer.java:66 runs a
rule set to FIXPOINT over a Memo (iterative/Memo.java:64) — each plan node
lives in a GROUP whose children are group references, so a rule rewrite
replaces one group's content without copying the whole tree, and the rules
pattern-match through a Lookup that resolves group references on demand
(iterative/Lookup.java, lib/trino-matching patterns).

TPU translation: identical control plane, minimal surface.  Rules here are
the rewrites whose payoff on this engine is real kernel time: merged filters
fuse into one predicate evaluation, limit-zero short-circuits whole
pipelines, redundant sorts skip device lexsorts (sorts are blocking
materializations on this executor), identity projects remove a fused-map
layer, and join-key filter inference cuts scatter lanes on the other side of
an exchange before the join runs.  Global passes that need whole-tree channel
bookkeeping (column pruning, optimizer.py) stay plan-level passes, the
reference's PlanOptimizer-vs-Rule split.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Optional

from ..page import Field, Schema
from . import ir
from . import plan as P
from ..types import BIGINT, BOOLEAN

__all__ = ["Memo", "GroupRef", "Rule", "IterativeOptimizer", "DEFAULT_RULES",
           "optimize_plan"]


# ---------------------------------------------------------------------------- memo
@dataclasses.dataclass(frozen=True)
class GroupRef(P.PlanNode):
    """Placeholder child pointing at a memo group (reference:
    iterative/GroupReference.java)."""

    group_id: int
    schema: Schema

    @property
    def children(self):
        return ()


def _replace_children(node: P.PlanNode, kids: tuple) -> P.PlanNode:
    """Rebuild ``node`` with new children (schema-preserving)."""
    if not node.children:
        return node
    if isinstance(node, P.Join):
        return dataclasses.replace(node, left=kids[0], right=kids[1])
    if isinstance(node, P.Union):
        return dataclasses.replace(node, inputs=tuple(kids))
    return dataclasses.replace(node, child=kids[0])


class Memo:
    """Groups of plan nodes; children stored as GroupRefs (Memo.java:64)."""

    def __init__(self, root: P.PlanNode):
        self._ids = itertools.count()
        self.groups: dict[int, P.PlanNode] = {}
        self.root_group = self._insert(root)

    def _insert(self, node: P.PlanNode) -> int:
        gid = next(self._ids)
        kids = tuple(GroupRef(self._insert(c), c.schema)
                     for c in node.children)
        self.groups[gid] = _replace_children(node, kids)
        return gid

    def node(self, gid: int) -> P.PlanNode:
        """Group content, following alias chains (a rule that returns a bare
        GroupRef — e.g. splicing a child group in place of its parent —
        aliases the group)."""
        n = self.groups[gid]
        while isinstance(n, GroupRef):
            n = self.groups[n.group_id]
        return n

    def resolve(self, node: P.PlanNode) -> P.PlanNode:
        """Lookup: a GroupRef becomes its group's node (children stay refs) —
        rules use this for depth-2 patterns (Lookup.java)."""
        if isinstance(node, GroupRef):
            return self.node(node.group_id)
        return node

    def replace(self, gid: int, new_node: P.PlanNode) -> None:
        """Swap a group's content.  Concrete children of the replacement are
        inserted as fresh groups; GroupRef children are kept (so a rule can
        splice existing groups into the new shape)."""
        if isinstance(new_node, GroupRef):
            self.groups[gid] = new_node  # alias; node() follows the chain
            return
        kids = tuple(c if isinstance(c, GroupRef)
                     else GroupRef(self._insert(c), c.schema)
                     for c in new_node.children)
        self.groups[gid] = _replace_children(new_node, kids)

    def extract(self, gid: Optional[int] = None) -> P.PlanNode:
        """Rebuild the concrete plan from the memo."""
        node = self.node(self.root_group if gid is None else gid)
        kids = tuple(self.extract(c.group_id) if isinstance(c, GroupRef)
                     else c for c in node.children)
        return _replace_children(node, kids)


# ---------------------------------------------------------------------------- rule protocol
class Rule:
    """Pattern-matched rewrite (reference: iterative/Rule.java + the
    lib/trino-matching Pattern).  ``pattern`` is the node class(es) the rule
    roots at; ``apply`` returns a replacement node (whose children may be the
    matched node's GroupRefs, or fresh concrete subtrees) or None."""

    pattern: tuple = (P.PlanNode,)

    def apply(self, node: P.PlanNode, memo: Memo) -> Optional[P.PlanNode]:
        raise NotImplementedError


class IterativeOptimizer:
    """Run rules to fixpoint over the memo (IterativeOptimizer.java:66
    exploreGroup/exploreNode: re-explore a group until no rule fires, then its
    children; re-explore the parent when a child changed)."""

    def __init__(self, rules: tuple, max_iterations: int = 10_000):
        self.rules = tuple(rules)
        self.max_iterations = max_iterations

    def run(self, plan: P.PlanNode) -> P.PlanNode:
        memo = Memo(plan)
        self._budget = self.max_iterations
        self._explore_group(memo, memo.root_group)
        return memo.extract()

    def _explore_group(self, memo: Memo, gid: int) -> bool:
        progress = self._explore_node(memo, gid)
        done = False
        while not done:
            done = True
            if self._explore_children(memo, gid):
                progress = True
                # a child rewrite can expose a new match at this node
                if self._explore_node(memo, gid):
                    done = False
        return progress

    def _explore_node(self, memo: Memo, gid: int) -> bool:
        progress = False
        fired = True
        while fired:
            fired = False
            node = memo.node(gid)
            for rule in self.rules:
                if not isinstance(node, tuple(rule.pattern)):
                    continue
                if self._budget <= 0:
                    return progress
                self._budget -= 1
                out = rule.apply(node, memo)
                if out is not None:
                    memo.replace(gid, out)
                    node = memo.node(gid)
                    fired = progress = True
        return progress

    def _explore_children(self, memo: Memo, gid: int) -> bool:
        progress = False
        for c in memo.node(gid).children:
            if isinstance(c, GroupRef) and self._explore_group(memo, c.group_id):
                progress = True
        return progress


# ---------------------------------------------------------------------------- helpers
def _conjuncts(e: ir.Expr) -> list:
    if isinstance(e, ir.Call) and e.op == "and":
        return [c for a in e.args for c in _conjuncts(a)]
    return [e]


def _and_all(conjuncts) -> ir.Expr:
    out = conjuncts[0]
    for c in conjuncts[1:]:
        out = ir.Call("and", (out, c), BOOLEAN)
    return out


_CMP_OPS = ("eq", "lt", "lte", "gt", "gte")


def _key_comparison(conjunct, key_channels: tuple):
    """-> (key_position, op, constant) when the conjunct is a comparison of a
    single join-key channel against a PYTHON-SCALAR constant (LUT/array
    constants and string dictionary ids are side-local and must not cross)."""
    if not (isinstance(conjunct, ir.Call) and conjunct.op in _CMP_OPS):
        return None
    a, b = conjunct.args
    flip = {"lt": "gt", "lte": "gte", "gt": "lt", "gte": "lte", "eq": "eq"}
    if isinstance(a, ir.Constant) and isinstance(b, ir.FieldRef):
        a, b = b, a
        op = flip[conjunct.op]
    elif isinstance(a, ir.FieldRef) and isinstance(b, ir.Constant):
        op = conjunct.op
    else:
        return None
    if not isinstance(b.value, (int, float, bool)) or a.type.is_string:
        return None
    if a.index not in key_channels:
        return None
    return key_channels.index(a.index), op, b


# ---------------------------------------------------------------------------- rules
class MergeFilters(Rule):
    """Filter(Filter(x, p1), p2) -> Filter(x, p1 AND p2) — one fused predicate
    evaluation (reference: iterative/rule/MergeFilters.java)."""

    pattern = (P.Filter,)

    def apply(self, node, memo):
        child = memo.resolve(node.child)
        if not isinstance(child, P.Filter):
            return None
        pred = ir.Call("and", (child.predicate, node.predicate), BOOLEAN)
        return P.Filter(child.child, pred)


class MergeLimits(Rule):
    """Limit(Limit(x, a), b) -> Limit(x, min(a, b)) (reference:
    iterative/rule/MergeLimits.java)."""

    pattern = (P.Limit,)

    def apply(self, node, memo):
        child = memo.resolve(node.child)
        if not isinstance(child, P.Limit):
            return None
        return P.Limit(child.child, min(node.count, child.count))


class EliminateLimitZero(Rule):
    """LIMIT 0 -> empty Values: the whole pipeline under it never runs
    (reference: iterative/rule/EvaluateZeroLimit... -> empty ValuesNode)."""

    pattern = (P.Limit,)

    def apply(self, node, memo):
        if node.count != 0:
            return None
        child = memo.resolve(node.child)
        if isinstance(child, P.Values) and not child.rows:
            return None  # already done
        return P.Values((), node.schema)


class RemoveIdentityProject(Rule):
    """Project that forwards every child channel unchanged -> child
    (reference: iterative/rule/RemoveRedundantIdentityProjections.java)."""

    pattern = (P.Project,)

    def apply(self, node, memo):
        child = memo.resolve(node.child)
        if len(node.exprs) != len(child.schema.fields):
            return None
        for i, e in enumerate(node.exprs):
            if not (isinstance(e, ir.FieldRef) and e.index == i):
                return None
        if node.dicts and any(d is not None for d in node.dicts):
            return None  # projection installs derived dictionaries: load-bearing
        if tuple(f.type for f in node.schema.fields) != tuple(
                f.type for f in child.schema.fields):
            return None
        if tuple(f.name for f in node.schema.fields) != tuple(
                f.name for f in child.schema.fields):
            return None  # renames feed name resolution above (Output hiding)
        return node.child  # splice the child GROUP, not a copy


class EliminateSortUnderOrderDestroyer(Rule):
    """A Sort feeding a hash aggregation or a hash join input is wasted work:
    both destroy order, and this executor's sort is a blocking device lexsort
    (reference: iterative/rule/RemoveRedundantSort... family; SQL makes no
    ordering guarantee through these operators)."""

    pattern = (P.Aggregate, P.Join)

    def apply(self, node, memo):
        new_kids = []
        changed = False
        for c in node.children:
            stripped = self._strip_sort(c, memo)
            if stripped is not None:
                new_kids.append(stripped)
                changed = True
            else:
                new_kids.append(c)
        if not changed:
            return None
        return _replace_children(node, tuple(new_kids))

    def _strip_sort(self, c, memo):
        """Remove the topmost Sort reachable through order-transparent unary
        nodes (Project/Filter — NOT Limit: Limit(Sort) is TopN semantics).
        Returns the rewritten child, or None when there is nothing to do."""
        rc = memo.resolve(c)
        if isinstance(rc, P.Sort):
            return rc.child  # splice the sort's input group
        if isinstance(rc, (P.Project, P.Filter)):
            inner = self._strip_sort(rc.child, memo)
            if inner is not None:
                return _replace_children(rc, (inner,))
        return None


class InferJoinSideFilters(Rule):
    """Transitive filter inference across equi-join keys: a constant
    comparison on one side's key implies the same comparison on the other
    side's key (reference: PredicatePushDown's equality-inference via
    EqualityInference.java — here the memo-rule slice of it).  Cuts the other
    side's rows BEFORE the join/exchange, which on TPU means fewer scatter
    lanes and a smaller routed build."""

    pattern = (P.Join,)

    def apply(self, node, memo):
        if node.kind not in ("inner", "semi"):
            return None
        left = memo.resolve(node.left)
        right = memo.resolve(node.right)
        out = None
        inferred_r = self._inferred(left, node.left_keys, node.right_keys,
                                    right, memo)
        if inferred_r is not None:
            out = dataclasses.replace(
                node, right=P.Filter(node.right, inferred_r))
        inferred_l = self._inferred(right, node.right_keys, node.left_keys,
                                    left, memo)
        if inferred_l is not None:
            out = dataclasses.replace(
                out or node, left=P.Filter(node.left, inferred_l))
        return out

    def _inferred(self, src, src_keys, dst_keys, dst, memo) -> Optional[ir.Expr]:
        if not isinstance(src, P.Filter):
            return None
        # dedup key: (channel, op, constant value) — structural repr would
        # never match planner-built refs (they carry column names)
        have = set()
        n = dst
        while isinstance(n, P.Filter):
            for c in _conjuncts(n.predicate):
                kc = _key_comparison(c, dst_keys)
                if kc is not None:
                    have.add((dst_keys[kc[0]], kc[1], kc[2].value))
            n = memo.resolve(n.child)
        new = []
        for c in _conjuncts(src.predicate):
            kc = _key_comparison(c, src_keys)
            if kc is None:
                continue
            pos, op, const = kc
            dst_ch = dst_keys[pos]
            if (dst_ch, op, const.value) in have:
                continue
            have.add((dst_ch, op, const.value))
            dst_type = dst.schema.fields[dst_ch].type
            new.append(ir.Call(op, (ir.FieldRef(dst_ch, dst_type), const),
                               BOOLEAN))
        return _and_all(new) if new else None


def _substitute_refs(e: ir.Expr, exprs: tuple) -> Optional[ir.Expr]:
    """Rewrite ``e`` with every FieldRef i replaced by ``exprs[i]`` (the
    inverse projection).  Returns None when the expression holds a node kind
    we cannot substitute through."""
    if isinstance(e, ir.FieldRef):
        if e.index >= len(exprs):
            return None
        return exprs[e.index]
    if isinstance(e, ir.Constant):
        return e
    if isinstance(e, ir.Call):
        args = []
        for a in e.args:
            s = _substitute_refs(a, exprs)
            if s is None:
                return None
            args.append(s)
        return dataclasses.replace(e, args=tuple(args))
    return None


class PushFilterThroughProject(Rule):
    """Filter(Project(x)) -> Project(Filter'(x)) with the predicate rewritten
    through the projection (reference: iterative/rule/
    PushDownFilterThroughProject / PredicatePushDown) — moves predicates next
    to the scan where static split pruning and lane masking see them."""

    pattern = (P.Filter,)

    def apply(self, node, memo):
        child = memo.resolve(node.child)
        if not isinstance(child, P.Project):
            return None
        pred = _substitute_refs(node.predicate, child.exprs)
        if pred is None:
            return None
        return _replace_children(child, (P.Filter(child.child, pred),))


class PushLimitThroughProject(Rule):
    """Limit(Project(x)) -> Project(Limit(x)) (reference:
    iterative/rule/PushLimitThroughProject) — lets the limit short-circuit
    the page stream below the projection."""

    pattern = (P.Limit,)

    def apply(self, node, memo):
        child = memo.resolve(node.child)
        if not isinstance(child, P.Project):
            return None
        inner = memo.resolve(child.child)
        if isinstance(inner, P.Sort):
            return None  # keep Limit(Sort) visible: that shape IS TopN
        return _replace_children(
            child, (dataclasses.replace(node, child=child.child),))


class RemoveTrivialFilter(Rule):
    """Filter(TRUE) -> child; Filter(FALSE) -> empty Values (reference:
    iterative/rule/RemoveTrivialFilters)."""

    pattern = (P.Filter,)

    def apply(self, node, memo):
        p = node.predicate
        if isinstance(p, ir.Constant):
            if p.value:
                return memo.resolve(node.child)
            return P.Values((), node.schema)
        return None


class MergeUnions(Rule):
    """Union(Union(a, b), c) -> Union(a, b, c) (reference:
    iterative/rule/MergeUnion) — one gather instead of a cascade."""

    pattern = (P.Union,)

    def apply(self, node, memo):
        new_inputs, changed = [], False
        for c in node.children:
            rc = memo.resolve(c)
            if isinstance(rc, P.Union):
                new_inputs.extend(rc.children)
                changed = True
            else:
                new_inputs.append(c)
        if not changed:
            return None
        return dataclasses.replace(node, inputs=tuple(new_inputs))


class PushLimitThroughUnion(Rule):
    """Limit(n, Union(a, b)) -> Limit(n, Union(Limit(n, a), Limit(n, b)))
    (reference: iterative/rule/PushLimitThroughUnion) — each branch stops
    producing after n rows instead of materializing fully."""

    pattern = (P.Limit,)

    def apply(self, node, memo):
        child = memo.resolve(node.child)
        if not isinstance(child, P.Union):
            return None
        if any(isinstance(memo.resolve(c), P.Limit)
               for c in child.children):
            return None  # already pushed (fixpoint guard)
        limited = tuple(P.Limit(c, node.count) for c in child.children)
        return dataclasses.replace(
            node, child=dataclasses.replace(child, inputs=limited))


class RemoveRedundantLimit(Rule):
    """Limit over a source that cannot exceed the count: ungrouped aggregates
    yield one row; Values yields len(rows) (reference:
    iterative/rule/RemoveRedundantLimit)."""

    pattern = (P.Limit,)

    def apply(self, node, memo):
        child = memo.resolve(node.child)
        if isinstance(child, P.Aggregate) and not child.keys \
                and node.count >= 1:
            return child
        if isinstance(child, P.Values) and len(child.rows) <= node.count:
            return child
        return None


def _map_refs(e: ir.Expr, mapping: dict) -> Optional[ir.Expr]:
    """Rewrite FieldRef channels through ``mapping`` (old index -> new index);
    None when a referenced channel has no image (the expression cannot move
    across this boundary)."""
    if isinstance(e, ir.FieldRef):
        if e.index not in mapping:
            return None
        return dataclasses.replace(e, index=mapping[e.index])
    if isinstance(e, ir.Constant):
        return e
    if isinstance(e, ir.Call):
        args = []
        for a in e.args:
            m = _map_refs(a, mapping)
            if m is None:
                return None
            args.append(m)
        return dataclasses.replace(e, args=tuple(args))
    return None


def _ref_channels(e: ir.Expr, out: set) -> None:
    if isinstance(e, ir.FieldRef):
        out.add(e.index)
    elif isinstance(e, ir.Call):
        for a in e.args:
            _ref_channels(a, out)


class PushFilterThroughJoin(Rule):
    """Split a filter above an equi-join into side-local conjuncts pushed
    below the join (reference: optimizations/PredicatePushDown.java:113 — the
    rule slice that moves single-side conjuncts to their input).  Probe-side
    conjuncts cut scatter lanes before the join; build-side conjuncts shrink
    the routed/replicated table.  Outer-join build conjuncts stay put (the
    NULL-extended rows they see do not exist below the join)."""

    pattern = (P.Filter,)

    def apply(self, node, memo):
        join = memo.resolve(node.child)
        if not isinstance(join, P.Join):
            return None
        n_left = len(memo.resolve(join.left).schema.fields)
        push_left, push_right, keep = [], [], []
        right_ok = join.kind == "inner"  # outer/semi/anti: build rows differ
        left_ok = join.kind in ("inner", "left", "semi", "anti")
        for c in _conjuncts(node.predicate):
            chans: set = set()
            _ref_channels(c, chans)
            if chans and max(chans) < n_left and left_ok:
                push_left.append(c)
            elif chans and min(chans) >= n_left and right_ok:
                m = _map_refs(c, {i: i - n_left for i in chans})
                if m is not None:
                    push_right.append(m)
                else:
                    keep.append(c)
            else:
                keep.append(c)
        if not push_left and not push_right:
            return None
        left = P.Filter(join.left, _and_all(push_left)) if push_left \
            else join.left
        right = P.Filter(join.right, _and_all(push_right)) if push_right \
            else join.right
        out = dataclasses.replace(join, left=left, right=right)
        return P.Filter(out, _and_all(keep)) if keep else out


_NULL_REJECTING = ("eq", "neq", "lt", "lte", "gt", "gte")


class OuterJoinToInner(Rule):
    """A LEFT join whose NULL-extended rows cannot survive what sits right
    above it is an INNER join (reference: PredicatePushDown.java's
    outer-to-inner conversion, ``canConvertOuterToInner``): an inner equi-join
    keyed on a column of the outer join's build side (a NULL key never
    matches), or a filter with a comparison on such a column (a comparison
    with NULL is not true).  TPC-DS q93: ``store_sales left outer join
    store_returns ... where sr_reason_sk = r_reason_sk``.  On this engine the
    inner form is what lets the join split into match, boundary and gather
    (local_executor._join_with_build): a left join keeps every lane."""

    pattern = (P.Join, P.Filter)

    def apply(self, node, memo):
        if isinstance(node, P.Filter):
            lj = memo.resolve(node.child)
            if not self._left_join(lj):
                return None
            n_probe = len(memo.resolve(lj.left).schema.fields)
            for c in _conjuncts(node.predicate):
                if isinstance(c, ir.Call) and c.op in _NULL_REJECTING and any(
                        isinstance(a, ir.FieldRef) and a.index >= n_probe
                        for a in c.args):
                    return dataclasses.replace(
                        node, child=dataclasses.replace(lj, kind="inner"))
            return None
        if node.kind != "inner":
            return None
        for side, keys in (("left", node.left_keys), ("right", node.right_keys)):
            lj = memo.resolve(getattr(node, side))
            if self._left_join(lj) and any(
                    k >= len(memo.resolve(lj.left).schema.fields) for k in keys):
                return dataclasses.replace(
                    node, **{side: dataclasses.replace(lj, kind="inner")})
        return None

    @staticmethod
    def _left_join(node) -> bool:
        return isinstance(node, P.Join) and node.kind == "left"


class PushSemiJoinThroughJoin(Rule):
    """Move a filtering semi-join onto the input of the join below it that
    owns its key channels (reference: PredicatePushDown's visitSemiJoin /
    the semi-join-as-filter reading of TransformFilteringSemiJoinToInnerJoin):
    SemiJoin(Join(a, b), s) on a's channels -> Join(SemiJoin(a, s), b), and
    on b's channels of an inner join -> Join(a, SemiJoin(b, s)).

    A filtering semi-join is a predicate over its probe's key channels and the
    build's key set only: a row passes where IN is TRUE, and a NULL key or an
    UNKNOWN never passes, at whichever level it is asked.  So it commutes with
    the join exactly as a single-side conjunct does in PushFilterThroughJoin,
    under the same side table (the probe side of inner/left/anti, the build
    side of inner only: an outer join's NULL-extended build rows do not exist
    below it), less the probe side of a semi-join: two filtering semi-joins
    over one input would pass each other for ever, and neither gains by it.
    The lower join's schema is unchanged (a semi-join adds no channel), so
    nothing above is remapped.  On a build side the semi-join becomes part of
    the build, made once; on a probe side the lower join's first probe is the
    selective one, which the executor's match-then-gather boundary packs.
    Not ``mark`` (it adds a channel), not ``anti`` (NOT IN's null rules), not
    a semi-join with a residual filter, not keys from both sides.  Each
    application lowers the semi-join by one join, so it ends."""

    pattern = (P.Join,)

    def apply(self, node, memo):
        keys = node.left_keys
        if node.kind != "semi" or node.filter is not None or not keys:
            return None
        join = memo.resolve(node.left)
        if not isinstance(join, P.Join):
            return None
        n_left = len(memo.resolve(join.left).schema.fields)
        if max(keys) < n_left and join.kind in ("inner", "left", "anti"):
            semi = dataclasses.replace(node, left=join.left,
                                       schema=join.left.schema)
            return dataclasses.replace(join, left=semi)
        if min(keys) >= n_left and join.kind == "inner":
            semi = dataclasses.replace(
                node, left=join.right, schema=join.right.schema,
                left_keys=tuple(k - n_left for k in keys))
            return dataclasses.replace(join, right=semi)
        return None


class PushFilterThroughAggregate(Rule):
    """Conjuncts over GROUP BY key channels filter the groups' input rows
    identically (reference: iterative/rule/PushPredicateThroughProjectIntoRowNumber
    family / PredicatePushDown through aggregations): push them below so the
    group table never materializes pruned groups."""

    pattern = (P.Filter,)

    def apply(self, node, memo):
        agg = memo.resolve(node.child)
        if not isinstance(agg, P.Aggregate) or not agg.keys:
            return None
        nk = len(agg.keys)
        mapping = {i: agg.keys[i] for i in range(nk)}
        push, keep = [], []
        for c in _conjuncts(node.predicate):
            chans: set = set()
            _ref_channels(c, chans)
            m = _map_refs(c, mapping) if chans and max(chans) < nk else None
            if m is not None:
                push.append(m)
            else:
                keep.append(c)
        if not push:
            return None
        out = _replace_children(agg, (P.Filter(agg.child, _and_all(push)),))
        return P.Filter(out, _and_all(keep)) if keep else out


class PushFilterThroughWindow(Rule):
    """Conjuncts over channels partitioning EVERY window spec remove whole
    partitions, so they commute with the window computation (reference:
    iterative/rule/PushPredicateThroughProjectIntoWindow.java /
    PushdownFilterIntoWindow)."""

    pattern = (P.Filter,)

    def apply(self, node, memo):
        win = memo.resolve(node.child)
        if not isinstance(win, P.Window) or not win.specs:
            return None
        shared = set(win.specs[0].partition)
        for s in win.specs[1:]:
            shared &= set(s.partition)
        if not shared:
            return None
        n_child = len(node.schema.fields) - len(win.specs)
        push, keep = [], []
        for c in _conjuncts(node.predicate):
            chans: set = set()
            _ref_channels(c, chans)
            if chans and chans <= shared and max(chans) < n_child:
                push.append(c)
            else:
                keep.append(c)
        if not push:
            return None
        out = _replace_children(win, (P.Filter(win.child, _and_all(push)),))
        return P.Filter(out, _and_all(keep)) if keep else out


class PushFilterThroughUnion(Rule):
    """Filter(Union(a, b, ...)) -> Union(Filter(a), Filter(b), ...)
    (reference: iterative/rule/PushFilterThroughUnion via PredicatePushDown):
    each branch masks its own lanes; set-op dictionary merge projections sit
    at the branch roots, so dictionary-id constants stay valid per branch."""

    pattern = (P.Filter,)

    def apply(self, node, memo):
        u = memo.resolve(node.child)
        if not isinstance(u, P.Union):
            return None
        # fixpoint guard: skip only when THIS predicate already sits at a
        # branch root (repr proxy — structural eq can trip on array-valued
        # LUT constants); a branch's own unrelated filter must not block the
        # push (MergeFilters collapses the stack below)
        want = repr(node.predicate)
        if any(isinstance(rc := memo.resolve(c), P.Filter)
               and repr(rc.predicate) == want for c in u.children):
            return None
        filtered = tuple(P.Filter(c, node.predicate) for c in u.children)
        return dataclasses.replace(u, inputs=filtered)


class PushFilterThroughSort(Rule):
    """Filter(Sort(x)) -> Sort(Filter(x)): same multiset, same order, fewer
    rows through the blocking device lexsort (reference: PredicatePushDown —
    sorts are order-transparent for predicates)."""

    pattern = (P.Filter,)

    def apply(self, node, memo):
        s = memo.resolve(node.child)
        if not isinstance(s, P.Sort):
            return None
        return _replace_children(s, (P.Filter(s.child, node.predicate),))


def _empty(node) -> bool:
    return isinstance(node, P.Values) and not node.rows


class PropagateEmptyUnary(Rule):
    """A row-preserving-or-reducing unary node over zero rows is zero rows
    (reference: the iterative/rule/EvaluateEmpty* / RemoveEmpty* family, e.g.
    EvaluateZeroSample, PruneEmptyUnionBranches groundwork).  Ungrouped
    aggregates are excluded: they emit one row from empty input."""

    pattern = (P.Filter, P.Project, P.Sort, P.Limit, P.Window, P.Unnest)

    def apply(self, node, memo):
        if not _empty(memo.resolve(node.children[0])):
            return None
        return P.Values((), node.schema)


class EliminateEmptyJoin(Rule):
    """Joins with a statically-empty input collapse (reference:
    iterative/rule/EvaluateEmptyIntersect / RemoveRedundantJoin family):
    inner/semi with either side empty, left-outer/anti with an empty probe."""

    pattern = (P.Join,)

    def apply(self, node, memo):
        lempty = _empty(memo.resolve(node.left))
        rempty = _empty(memo.resolve(node.right))
        if node.kind == "inner" and (lempty or rempty):
            return P.Values((), node.schema)
        if node.kind == "semi" and (lempty or rempty):
            return P.Values((), node.schema)
        if node.kind in ("left", "anti") and lempty:
            return P.Values((), node.schema)
        return None


class DropEmptyUnionInputs(Rule):
    """Union inputs that are statically empty contribute nothing (reference:
    iterative/rule/PruneEmptyUnionBranches analog)."""

    pattern = (P.Union,)

    def apply(self, node, memo):
        live = [c for c in node.children if not _empty(memo.resolve(c))]
        if len(live) == len(node.children):
            return None
        if not live:
            return P.Values((), node.schema)
        if len(live) == 1:
            # single survivor must still present the union's channel names
            survivor = memo.resolve(live[0])
            if survivor.schema == node.schema:
                return live[0]
            exprs = tuple(ir.FieldRef(i, f.type)
                          for i, f in enumerate(survivor.schema.fields))
            return P.Project(live[0], exprs, node.schema)
        return dataclasses.replace(node, inputs=tuple(live))


class MergeAdjacentProjects(Rule):
    """Project(Project(x)) -> one Project with outer expressions substituted
    through the inner ones (reference: iterative/rule/InlineProjections.java).
    Guarded on dictionary channels: planner-derived dictionaries ride the
    projection, so merging only happens when they provably carry through."""

    pattern = (P.Project,)

    def apply(self, node, memo):
        inner = memo.resolve(node.child)
        if not isinstance(inner, P.Project):
            return None
        # use-count guard (InlineProjections.java's rule): a non-trivial
        # inner expression referenced more than once would be DUPLICATED by
        # substitution — chained merges then grow the tree exponentially
        uses: dict = {}

        def count(e):
            if isinstance(e, ir.FieldRef):
                uses[e.index] = uses.get(e.index, 0) + 1
            elif isinstance(e, ir.Call):
                for a in e.args:
                    count(a)

        for e in node.exprs:
            count(e)
        for c, n in uses.items():
            if n > 1 and c < len(inner.exprs) \
                    and not isinstance(inner.exprs[c],
                                       (ir.FieldRef, ir.Constant)):
                return None
        inner_dicts = inner.dicts if inner.dicts else \
            tuple(None for _ in inner.exprs)
        outer_dicts = node.dicts if node.dicts else \
            tuple(None for _ in node.exprs)
        exprs, dicts = [], []
        for j, e in enumerate(node.exprs):
            sub = _substitute_refs(e, inner.exprs)
            if sub is None:
                return None
            d = outer_dicts[j]
            if d is None and isinstance(e, ir.FieldRef) \
                    and e.index < len(inner_dicts):
                d = inner_dicts[e.index]  # pass-through keeps the derived dict
            elif d is None and not isinstance(e, ir.FieldRef):
                # a computed outer expr consuming a dict-deriving inner
                # channel: the substituted tree still sees the same ids, but
                # only merge when the consumed channels derive NO dictionary
                chans: set = set()
                _ref_channels(e, chans)
                if any(c < len(inner_dicts) and inner_dicts[c] is not None
                       for c in chans):
                    return None
            exprs.append(sub)
            dicts.append(d)
        use_dicts = tuple(dicts) if any(d is not None for d in dicts) else ()
        return P.Project(inner.child, tuple(exprs), node.schema, use_dicts)


# -- constant folding ----------------------------------------------------------
_FOLD_SCALARS = (bool, int, float)


def _kleene_and(vals):
    if any(v is False for v in vals):
        return False
    if any(v is None for v in vals):
        return None
    return True


def _kleene_or(vals):
    if any(v is True for v in vals):
        return True
    if any(v is None for v in vals):
        return None
    return False


def _fold(e: ir.Expr):
    """-> (value, ok): evaluate a constant expression over whitelisted pure
    ops with SQL three-valued logic (None = NULL).  ok=False when the tree
    holds anything non-constant or outside the whitelist."""
    if isinstance(e, ir.Constant):
        v = e.value
        if v is None or isinstance(v, _FOLD_SCALARS):
            return v, True
        return None, False
    if not isinstance(e, ir.Call):
        return None, False
    vals = []
    for a in e.args:
        v, ok = _fold(a)
        if not ok:
            return None, False
        vals.append(v)
    op = e.op
    if op == "and":
        return _kleene_and(vals), True
    if op == "or":
        return _kleene_or(vals), True
    if op == "not":
        return (None if vals[0] is None else not vals[0]), True
    if any(v is None for v in vals):  # NULL propagates through scalar ops
        return None, True
    try:
        if op == "add":
            return vals[0] + vals[1], True
        if op == "sub":
            return vals[0] - vals[1], True
        if op == "mul":
            return vals[0] * vals[1], True
        if op == "eq":
            return vals[0] == vals[1], True
        if op == "neq":
            return vals[0] != vals[1], True
        if op == "lt":
            return vals[0] < vals[1], True
        if op == "lte":
            return vals[0] <= vals[1], True
        if op == "gt":
            return vals[0] > vals[1], True
        if op == "gte":
            return vals[0] >= vals[1], True
    except TypeError:
        return None, False
    return None, False


class SimplifyFilterPredicate(Rule):
    """Fold constant conjuncts at plan time (reference:
    iterative/rule/SimplifyExpressions.java + ExpressionInterpreter): TRUE
    conjuncts vanish, a FALSE/NULL conjunct empties the filter (NULL predicate
    drops the row in SQL), constant comparisons collapse."""

    pattern = (P.Filter,)

    def apply(self, node, memo):
        changed = False
        keep = []
        for c in _conjuncts(node.predicate):
            v, ok = _fold(c)
            if not ok:
                keep.append(c)
                continue
            changed = True
            if v is True:
                continue  # TRUE conjunct: drop
            # FALSE or NULL conjunct: no row survives
            return P.Values((), node.schema)
        if not changed:
            return None
        if not keep:
            return node.child  # every conjunct was TRUE: splice the child
        return P.Filter(node.child, _and_all(keep))


class RemoveRedundantDistinct(Rule):
    """DISTINCT over DISTINCT: the outer grouping re-groups rows that are
    already unique on the same keys (reference:
    iterative/rule/RemoveRedundantDistinct... / MultipleDistinctAggregationToMarkDistinct
    groundwork).  Matches Aggregate(keys=identity, aggs=()) over
    Aggregate(aggs=()) whose key fields ARE the child schema."""

    pattern = (P.Aggregate,)

    def apply(self, node, memo):
        if node.aggs or not node.keys:
            return None
        inner = memo.resolve(node.child)
        if not isinstance(inner, P.Aggregate) or inner.aggs:
            return None
        # inner distinct output schema = its key fields; the outer is
        # redundant when it groups by exactly those channels (any order)
        if sorted(node.keys) != list(range(len(inner.schema.fields))):
            return None
        if tuple(node.keys) == tuple(range(len(inner.schema.fields))):
            return node.child  # identical key order: splice
        return None  # reordered keys change the output schema: keep


class EvaluateFilterOverValues(Rule):
    """Filter(Values) with a foldable predicate evaluates at plan time
    (reference: iterative/rule/EvaluateFilterOverValues... the
    ValuesNode-folding family).  Only literal scalar rows participate —
    string channels carry dictionary ids and stay untouched."""

    pattern = (P.Filter,)

    def apply(self, node, memo):
        vals = memo.resolve(node.child)
        if not isinstance(vals, P.Values) or not vals.rows:
            return None
        chans: set = set()
        _ref_channels(node.predicate, chans)
        if any(vals.schema.fields[c].type.is_string for c in chans):
            return None
        kept = []
        for row in vals.rows:
            sub = _substitute_refs(
                node.predicate,
                tuple(ir.Constant(v, vals.schema.fields[i].type)
                      for i, v in enumerate(row)))
            if sub is None:
                return None
            v, ok = _fold(sub)
            if not ok:
                return None
            if v is True:
                kept.append(row)
        if len(kept) == len(vals.rows):
            return node.child  # nothing filtered: splice
        return dataclasses.replace(vals, rows=tuple(kept))


class EvaluateLimitOverValues(Rule):
    """Limit(Values) truncates the literal rows at plan time (reference:
    iterative/rule/EvaluateLimitOverValues analog; RemoveRedundantLimit
    already handles len <= count)."""

    pattern = (P.Limit,)

    def apply(self, node, memo):
        vals = memo.resolve(node.child)
        if not isinstance(vals, P.Values) or len(vals.rows) <= node.count:
            return None
        return dataclasses.replace(vals, rows=tuple(vals.rows[:node.count]))


class DedupSortKeys(Rule):
    """Sorting twice by the same channel is one comparator (reference:
    the RemoveRedundantSort family's key normalization): later duplicates
    can never break ties the first occurrence left."""

    pattern = (P.Sort,)

    def apply(self, node, memo):
        seen: set = set()
        keys = []
        for k in node.keys:
            if k.channel in seen:
                continue
            seen.add(k.channel)
            keys.append(k)
        if len(keys) == len(node.keys):
            return None
        return dataclasses.replace(node, keys=tuple(keys))


class DedupJoinKeys(Rule):
    """Duplicate equi-key pairs state the same constraint twice; dropping
    them narrows the hashed key tuple (reference: join-clause normalization
    in PredicatePushDown/EqualityInference)."""

    pattern = (P.Join,)

    def apply(self, node, memo):
        seen: set = set()
        lk, rk = [], []
        for a, b in zip(node.left_keys, node.right_keys):
            if (a, b) in seen:
                continue
            seen.add((a, b))
            lk.append(a)
            rk.append(b)
        if len(lk) == len(node.left_keys):
            return None
        return dataclasses.replace(node, left_keys=tuple(lk),
                                   right_keys=tuple(rk))


class SpatialDistanceJoin(Rule):
    """Rewrite a CROSS join filtered by ``st_distance(...) <= r`` into a
    grid-bucketed equi-join (reference: operator/SpatialJoinOperator.java +
    SpatialJoinUtils — the reference partitions geometries with a KDB tree;
    the TPU re-design buckets points into r-sized grid CELLS and joins on
    cell id, which is one equi-join the existing hash machinery runs).

    Shape: probe side gains a cell-id channel floor(x/r)*2^32 + floor(y/r);
    the build side expands 9x (a UNION of the 3x3 neighbor shifts) so every
    candidate pair shares exactly ONE cell id — no duplicate pairs, since
    the nine shifted copies of a build row land in nine DISTINCT cells.  The
    original distance conjunct stays as the join's residual filter for
    exactness.  O(n*m) cross-join work becomes O(n + 9m + matches).

    Matches Filter(cross Join) — the planner leaves the two-sided distance
    conjunct as a residual filter ABOVE the cross join — and fires only on
    the cross-join shape (constant equi keys) so the rewritten join, whose
    keys are real cell ids, can never re-match."""

    pattern = (P.Filter,)

    _CELL = 1 << 32  # collision-free int64 (cx, cy) packing for |cy| < 2^31

    def apply(self, fnode, memo):
        node = memo.resolve(fnode.child)
        if not isinstance(node, P.Join) or node.kind != "inner" \
                or node.filter is not None:
            return None
        if not self._is_cross_shape(node, memo):
            return None
        left = memo.resolve(node.left)
        right = memo.resolve(node.right)
        n_left = len(left.schema.fields)
        n_right = len(right.schema.fields)
        # no instance state: DEFAULT_RULES instances are shared across
        # concurrently-planning threads
        hit, dist_conjunct, rest = None, None, []
        for c in _conjuncts(fnode.predicate):
            if hit is None:
                hit = self._match_distance(c, n_left)
                if hit is not None:
                    dist_conjunct = c
                    continue
            rest.append(c)
        if hit is None:
            return None
        (ax, ay), (bx, by), r = hit

        def cell(x, y, dx, dy):
            # floor(x/r) (+shift) packed with floor(y/r).  The PACKING runs
            # in INT64 (cast each floored cell first): packing in doubles
            # loses ulps past |cell| ~ 2^21 and two neighbor shifts could
            # round to one id — duplicate pairs both passing the residual.
            # int64 packing is exact for |cell| < 2^31.
            fx = ir.Call("cast", (ir.Call("floor", (ir.Call(
                "divide", (x, ir.Constant(float(r), x.type)), x.type),),
                x.type),), BIGINT)
            fy = ir.Call("cast", (ir.Call("floor", (ir.Call(
                "divide", (y, ir.Constant(float(r), y.type)), y.type),),
                y.type),), BIGINT)
            if dx:
                fx = ir.Call("add", (fx, ir.Constant(int(dx), BIGINT)),
                             BIGINT)
            if dy:
                fy = ir.Call("add", (fy, ir.Constant(int(dy), BIGINT)),
                             BIGINT)
            return ir.Call("add", (ir.Call(
                "multiply", (fx, ir.Constant(int(self._CELL), BIGINT)),
                BIGINT), fy), BIGINT)

        idf = Field("#cell", BIGINT)  # hidden by the restoring projection
        lproj = P.Project(
            node.left,
            tuple(ir.FieldRef(i, f.type)
                  for i, f in enumerate(left.schema.fields))
            + (cell(ax, ay, 0, 0),),
            Schema(tuple(left.schema.fields) + (idf,)))
        branches = []
        bschema = Schema(tuple(right.schema.fields) + (idf,))
        for dx in (-1, 0, 1):
            for dy in (-1, 0, 1):
                branches.append(P.Project(
                    node.right,
                    tuple(ir.FieldRef(i, f.type)
                          for i, f in enumerate(right.schema.fields))
                    + (cell(bx, by, dx, dy),),
                    bschema))
        union = P.Union(tuple(branches), bschema)
        # the distance conjunct becomes the join's RESIDUAL filter (cell
        # neighbors can exceed r): left channels unchanged, right channels
        # shift past the probe-side cell channel
        remap = {i: i for i in range(n_left)}
        remap.update({n_left + j: n_left + 1 + j for j in range(n_right)})
        filt = _map_refs(dist_conjunct, remap)
        if filt is None:
            return None
        jschema = Schema(tuple(lproj.schema.fields)
                         + tuple(bschema.fields))
        inner = dataclasses.replace(
            node, left=lproj, right=union,
            left_keys=(n_left,), right_keys=(n_right,),
            schema=jschema, filter=filt)
        # restore the original channel layout for consumers
        out_exprs = tuple(
            ir.FieldRef(i, f.type)
            for i, f in enumerate(left.schema.fields)) + tuple(
            ir.FieldRef(n_left + 1 + j, f.type)
            for j, f in enumerate(right.schema.fields))
        out = P.Project(inner, out_exprs, node.schema)
        # remaining conjuncts stay above the restored layout
        return P.Filter(out, _and_all(rest)) if rest else out

    def _is_cross_shape(self, node, memo) -> bool:
        """Both equi keys resolve to appended CONSTANT projection channels
        (the _make_cross_join shape)."""
        if len(node.left_keys) != 1 or len(node.right_keys) != 1:
            return False
        lv = self._key_const(memo.resolve(node.left), node.left_keys[0])
        rv = self._key_const(memo.resolve(node.right), node.right_keys[0])
        # both keys constant AND equal non-NULL: ON 1 = 2 is a degenerate
        # always-empty join, NOT a cross join — rewriting it would invent rows
        return lv is not None and rv is not None and lv == rv

    @staticmethod
    def _key_const(child, ch):
        if isinstance(child, P.Project) and ch < len(child.exprs) \
                and isinstance(child.exprs[ch], ir.Constant):
            return child.exprs[ch].value
        return None

    def _match_distance(self, c, n_left):
        """-> ((ax, ay), (bx, by), r) with a-side strictly left channels and
        b-side strictly right (remapped to right-child coordinates)."""
        if not (isinstance(c, ir.Call) and c.op in ("lt", "lte")):
            return None
        d, lim = c.args
        if not (isinstance(d, ir.Call) and d.op == "st_distance"
                and isinstance(lim, ir.Constant)
                and isinstance(lim.value, (int, float)) and lim.value > 0):
            return None
        ax, ay, bx, by = d.args

        def side(e):
            chans: set = set()
            _ref_channels(e, chans)
            if not chans:
                return None
            if max(chans) < n_left:
                return "l"
            if min(chans) >= n_left:
                return "r"
            return None

        sides = tuple(side(e) for e in (ax, ay, bx, by))
        if sides == ("l", "l", "r", "r"):
            pass
        elif sides == ("r", "r", "l", "l"):
            ax, ay, bx, by = bx, by, ax, ay
        else:
            return None
        bmap = {}
        for e in (bx, by):
            chans: set = set()
            _ref_channels(e, chans)
            bmap.update({ch: ch - n_left for ch in chans})
        bx = _map_refs(bx, bmap)
        by = _map_refs(by, bmap)
        if bx is None or by is None:
            return None
        return (ax, ay), (bx, by), float(lim.value)


DEFAULT_RULES = (MergeFilters(), MergeLimits(), EliminateLimitZero(),
                 RemoveIdentityProject(), EliminateSortUnderOrderDestroyer(),
                 InferJoinSideFilters(), PushFilterThroughProject(),
                 PushLimitThroughProject(), RemoveTrivialFilter(),
                 MergeUnions(), PushLimitThroughUnion(),
                 RemoveRedundantLimit(),
                 # round-5 expansion (VERDICT item 4): pushdown + folding
                 OuterJoinToInner(), PushFilterThroughJoin(),
                 PushSemiJoinThroughJoin(),
                 PushFilterThroughAggregate(),
                 PushFilterThroughWindow(), PushFilterThroughUnion(),
                 PushFilterThroughSort(), PropagateEmptyUnary(),
                 EliminateEmptyJoin(), DropEmptyUnionInputs(),
                 MergeAdjacentProjects(), SimplifyFilterPredicate(),
                 RemoveRedundantDistinct(), EvaluateFilterOverValues(),
                 EvaluateLimitOverValues(), DedupSortKeys(), DedupJoinKeys(),
                 SpatialDistanceJoin())


def optimize_plan(root: P.PlanNode) -> P.PlanNode:
    """The optimizer pipeline: iterative rules to fixpoint, then the global
    column-pruning pass (reference: PlanOptimizers.java ordering — rule sets
    first, then passes needing whole-tree bookkeeping)."""
    from .optimizer import prune_columns

    out = IterativeOptimizer(DEFAULT_RULES).run(root)
    return prune_columns(out)
