"""AddExchanges: the global distribution-planning pass.

Reference: sql/planner/optimizations/AddExchanges.java:145 walks the plan
assigning PartitioningHandles and inserting ExchangeNodes, cost-comparing
REPLICATED vs PARTITIONED for each join (with DetermineJoinDistributionType's
stats input).  TPU translation: data movement is not an operator here — it is
an XLA collective inside the jitted fragment (bucketize + ``all_to_all`` for
hash routing, implicit replication for broadcast builds) — so this pass has
two products:

1. ``resolve_distributions(plan, catalogs, props)``: the EXECUTION plan with
   every equi-join's ``distribution`` attribute resolved by a cost comparison
   of broadcast traffic (build x mesh-width) against partitioned traffic
   (probe + build routed once).  'broadcast' is only forced when the build
   estimate is HIGH-CONFIDENCE (derived without default coefficients) AND
   under an absolute size cap — a coefficient-derived guess must never
   bypass the executor's actual-size threshold, which stays the safety net
   for everything else.  Joins with residual filters or null-aware semantics
   keep the planner's setting (the executor constrains their strategy).
2. ``physical_plan(plan, catalogs, props)``: the same tree with explicit
   ``plan.Exchange`` markers for EXPLAIN — 'hash'/'broadcast' where the
   placement is decided, 'auto' where the executor's actual-size rule will
   pick at runtime — the placement surface AddExchanges prints.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from . import plan as P
from .rules import _replace_children
from .stats import PARTITIONED_JOIN_THRESHOLD, UNKNOWN_FILTER_COEFFICIENT

__all__ = ["estimate_rows", "resolve_distributions", "physical_plan"]

MESH_WIDTH = 8  # nominal device count for the traffic model (v5e-8 host)
BROADCAST_ABS_CAP = 1 << 22  # never force-broadcast a build above 4M rows
AGG_DEFAULT_SELECTIVITY = 0.1


class _Estimator:
    """Bottom-up cardinality estimates, memoized by node identity (one pass
    walks every join; without the cache the leaf recursion is quadratic and
    re-probes connector stats per join).  Each estimate carries a CONFIDENCE
    bit: False once a default coefficient (filter/aggregate guess) entered
    the derivation — the same contract as RelStats.known, which must rank
    alternatives but not force distribution decisions."""

    def __init__(self, catalogs: dict):
        self.catalogs = catalogs
        self._cache: dict = {}  # id(node) -> (rows|None, confident)

    def rows(self, node) -> Optional[float]:
        return self.estimate(node)[0]

    def set_fact(self, node, rows: float) -> None:
        """Adaptive-advisor cardinality override: an OBSERVED row count for
        this node from plan-actuals history — recorded truth, so it is
        CONFIDENT (it may force a distribution the coefficient-derived guess
        could only rank)."""
        self._cache[id(node)] = (float(rows), True)

    def estimate(self, node) -> tuple:
        hit = self._cache.get(id(node))
        if hit is None:
            hit = self._cache[id(node)] = self._compute(node)
        return hit

    def _compute(self, node) -> tuple:
        if isinstance(node, P.TableScan):
            from ..spi.statistics import connector_table_stats

            conn = self.catalogs.get(node.catalog)
            st = None if conn is None \
                else connector_table_stats(conn, node.table)
            if st is None or st.row_count is None:
                return None, False
            return float(st.row_count), True
        if isinstance(node, P.Values):
            return float(len(node.rows)), True
        if isinstance(node, P.Filter):
            child, _ = self.estimate(node.child)
            if child is None:
                return None, False
            return child * UNKNOWN_FILTER_COEFFICIENT, False  # coefficient
        if isinstance(node, P.Limit):
            child, conf = self.estimate(node.child)
            if child is None:
                return float(node.count), True  # a limit bounds the unknown
            return min(float(node.count), child), conf
        if isinstance(node, P.Aggregate):
            if not node.keys:
                return 1.0, True
            child, _ = self.estimate(node.child)
            if child is None:
                return None, False
            return max(child * AGG_DEFAULT_SELECTIVITY, 1.0), False
        if isinstance(node, P.Join):
            if node.est_rows is not None:
                return float(node.est_rows), False  # CBO estimate: rankable
            l, _ = self.estimate(node.left)
            r, _ = self.estimate(node.right)
            if l is None or r is None:
                return None, False
            return max(l, r), False
        if isinstance(node, P.Union):
            total, conf = 0.0, True
            for c in node.children:
                e, cconf = self.estimate(c)
                if e is None:
                    return None, False
                total += e
                conf = conf and cconf
            return total, conf
        if len(node.children) == 1:
            return self.estimate(node.children[0])
        return None, False


def estimate_rows(node: P.PlanNode, catalogs: dict) -> Optional[float]:
    """Output-cardinality estimate; None = unknown."""
    return _Estimator(catalogs).rows(node)


def _decide(node: P.Join, est: _Estimator, props: dict) -> str:
    """The DetermineJoinDistributionType cost comparison (reference:
    iterative/rule/DetermineJoinDistributionType.java:51): session forcing
    wins; an explicit 'broadcast' needs a confident build estimate under the
    absolute cap; 'partitioned' engages at the shared threshold; everything
    else stays automatic (the executor's actual-size rule)."""
    mode = str((props or {}).get("join_distribution_type", "AUTOMATIC")).upper()
    if mode == "BROADCAST":
        return "broadcast"
    if mode == "PARTITIONED":
        return "partitioned"
    if node.filter is not None or node.null_aware:
        return node.distribution  # executor constrains these strategies
    l, _lconf = est.estimate(node.left)
    r, rconf = est.estimate(node.right)
    if l is None or r is None or not rconf:
        # unknown or coefficient-derived build size: the frontend's per-join
        # call used COLUMN-stats selectivities this pass does not recompute —
        # defer to it (and to the executor's actual-size rule at runtime)
        return node.distribution
    if r * MESH_WIDTH < l + r and r < BROADCAST_ABS_CAP:
        return "broadcast"
    if r >= PARTITIONED_JOIN_THRESHOLD:
        return "partitioned"
    return "replicated"  # small build: executor's actual-size auto path


def resolve_distributions(plan: P.PlanNode, catalogs: dict,
                          props: dict = None) -> P.PlanNode:
    """Rewrite every Join's ``distribution`` from the global cost model
    (product 1 of AddExchanges).

    When the session carries ``_adaptive_corrections`` (the adaptive
    advisor's frozen facts, keyed by structural node path "<Op>#<chain>" —
    the plan-history address), this pass is also where they apply:

    - ``rows``: observed row counts become CONFIDENT estimator facts, so the
      broadcast/partitioned thresholds below re-decide from recorded truth
      (a corrected Join additionally has ``est_rows`` stamped, making the
      correction durable in the plan content — and in the structural
      fingerprint, so corrected plans key separately everywhere);
    - ``capacity`` / ``grace_parts``: Aggregate hash-table capacity and
      Grace partition seeds from observed group counts.

    The ``group_by_capacity`` session property sets the initial capacity of
    every Aggregate that has no such correction.

    The chain walk here mirrors ``history.plan_node_paths`` (pre-order,
    child-index chains from root "0") by construction — the corrections'
    addresses are those paths."""
    est = _Estimator(catalogs)
    corr = (props or {}).get("_adaptive_corrections") or {}
    rows_facts = corr.get("rows") or {}
    cap_facts = corr.get("capacity") or {}
    grace_facts = corr.get("grace_parts") or {}

    def walk(node, chain="0"):
        kids = tuple(walk(c, f"{chain}.{i}")
                     for i, c in enumerate(node.children))
        if kids != tuple(node.children):
            node = _replace_children(node, kids)
        path = f"{type(node).__name__}#{chain}"
        fact = rows_facts.get(path)
        if isinstance(node, P.Aggregate):
            cap = int(cap_facts.get(path)
                      or (props or {}).get("group_by_capacity") or 0)
            gp = int(grace_facts.get(path) or 0)
            if (cap and cap != node.capacity) \
                    or (gp and gp != node.grace_parts):
                node = dataclasses.replace(
                    node, capacity=cap or node.capacity,
                    grace_parts=gp or node.grace_parts)
        if isinstance(node, P.Join):
            if fact is not None and float(fact) != node.est_rows:
                node = dataclasses.replace(node, est_rows=float(fact))
            dist = _decide(node, est, props)
            if dist != node.distribution:
                node = dataclasses.replace(node, distribution=dist)
        if fact is not None:
            # children's facts were set when their walk returned, so the
            # parent's _decide above already saw them; set this node's own
            # fact LAST — dataclasses.replace minted a new object
            est.set_fact(node, fact)
        return node

    return walk(plan)


def physical_plan(plan: P.PlanNode, catalogs: dict,
                  props: dict = None) -> P.PlanNode:
    """Insert Exchange markers where the compiled program moves data across
    the mesh (product 2: the EXPLAIN surface AddExchanges prints):

    - partitioned join: Exchange[hash(keys)] on BOTH sides (the bucketize +
      all_to_all route both sides share);
    - broadcast join: Exchange[broadcast] under the build side;
    - automatic ('replicated') join: Exchange[auto] — the executor's
      actual-size rule picks broadcast or the partitioned route at runtime,
      so EXPLAIN must not assert a placement the program may not perform;
    - grouped aggregation: Exchange[gather] above the per-device partial;
    - global Sort: Exchange[gather] beneath (range-partitioned sort collects
      for the final ordered surface)."""
    resolved = resolve_distributions(plan, catalogs, props)

    def walk(node):
        kids = tuple(walk(c) for c in node.children)
        if kids != tuple(node.children):
            node = _replace_children(node, kids)
        if isinstance(node, P.Join):
            if node.distribution == "partitioned":
                left = P.Exchange(node.left, "hash", tuple(node.left_keys))
                right = P.Exchange(node.right, "hash",
                                   tuple(node.right_keys))
            elif node.distribution == "broadcast":
                left = node.left
                right = P.Exchange(node.right, "broadcast")
            else:
                left = node.left
                right = P.Exchange(node.right, "auto")
            return dataclasses.replace(node, left=left, right=right)
        if isinstance(node, P.Aggregate) and node.keys:
            return _replace_children(
                node, (P.Exchange(node.children[0], "gather"),))
        if isinstance(node, P.Sort):
            return _replace_children(
                node, (P.Exchange(node.children[0], "gather"),))
        return node

    return walk(resolved)
