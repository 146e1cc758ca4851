"""Relational plan nodes.

Mirrors the reference's plan-node vocabulary (core/trino-main .../sql/planner/plan — 66 node
types; we grow toward that set) with positional (channel-based) expressions like the
reference's post-LocalExecutionPlanner form: every node exposes an output ``Schema`` and its
expressions are FieldRefs into the child's output channels.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from ..page import Schema
from ..types import Type
from .ir import Expr

__all__ = ["PlanNode", "TableScan", "Filter", "Project", "AggSpec", "Aggregate",
           "SortKey", "Sort", "Limit", "Join", "Union", "Values", "Output",
           "WindowSpec", "Window", "RemoteSource"]


class PlanNode:
    schema: Schema

    @property
    def children(self) -> tuple:
        return ()


@dataclasses.dataclass(frozen=True)
class TableScan(PlanNode):
    """reference: sql/planner/plan/TableScanNode.java

    ``source_tables``: (catalog, table) provenance when ``table`` is a
    VIRTUAL connector handle from an optimizer pushdown (applyTopN /
    applyJoin) — access control checks these instead of the handle."""

    catalog: str
    table: str
    columns: tuple  # column names in the connector table
    schema: Schema
    source_tables: tuple = ()


@dataclasses.dataclass(frozen=True)
class Filter(PlanNode):
    """reference: sql/planner/plan/FilterNode.java"""

    child: PlanNode
    predicate: Expr

    @property
    def schema(self) -> Schema:
        return self.child.schema

    @property
    def children(self):
        return (self.child,)


@dataclasses.dataclass(frozen=True)
class Project(PlanNode):
    """reference: sql/planner/plan/ProjectNode.java

    ``dicts``: optional planner-resolved Dictionary per output channel (None entries =
    derive from child for plain FieldRefs).  Dictionary-typed projections (substring and
    friends compile to id->id lookup tables) produce NEW dictionaries only the planner
    knows — the executor's channel-level dictionary tracking reads them from here."""

    child: PlanNode
    exprs: tuple  # Expr per output channel
    schema: Schema
    dicts: tuple = ()

    @property
    def children(self):
        return (self.child,)


# Aggregates executed by the sort-based local selection runner (one key-major
# device lexsort + segment walks) rather than the scatter hash-aggregation
# path; distributed/FTE planners decline these and route to the local runner.
SORTED_AGG_KINDS = frozenset({
    "approx_percentile", "listagg", "approx_most_frequent",
    "max_by", "min_by", "array_agg", "histogram", "map_agg",
    "bitwise_and_agg", "bitwise_or_agg", "bitwise_xor_agg",
})


@dataclasses.dataclass(frozen=True)
class AggSpec:
    """One aggregate call (reference: plan/AggregationNode.Aggregation)."""

    kind: str  # count_star | count | sum | avg | min | max
    arg: Optional[Expr]  # channel expr into child schema (None for count_star)
    name: str
    type: Type
    distinct: bool = False
    param: object = None  # extra static argument (approx_percentile's p)


@dataclasses.dataclass(frozen=True)
class Aggregate(PlanNode):
    """reference: sql/planner/plan/AggregationNode.java; keys are child channel indices."""

    child: PlanNode
    keys: tuple  # int channel indices
    aggs: tuple  # AggSpec...
    schema: Schema  # key fields then agg fields
    capacity: int = 0  # group-table capacity bucket; 0 = planner default
    grace_parts: int = 0  # Grace-fallback partition seed; 0 = executor default

    @property
    def children(self):
        return (self.child,)


@dataclasses.dataclass(frozen=True)
class SortKey:
    channel: int
    ascending: bool = True
    nulls_first: bool = False


@dataclasses.dataclass(frozen=True)
class Sort(PlanNode):
    """reference: sql/planner/plan/SortNode.java"""

    child: PlanNode
    keys: tuple  # SortKey...

    @property
    def schema(self):
        return self.child.schema

    @property
    def children(self):
        return (self.child,)


@dataclasses.dataclass(frozen=True)
class Limit(PlanNode):
    """reference: sql/planner/plan/LimitNode.java"""

    child: PlanNode
    count: int

    @property
    def schema(self):
        return self.child.schema

    @property
    def children(self):
        return (self.child,)


@dataclasses.dataclass(frozen=True)
class Join(PlanNode):
    """reference: sql/planner/plan/JoinNode.java; equi-join with optional residual filter.

    ``distribution``: 'replicated' (auto/default — the executor may still pick the
    partitioned strategy from the actual build size) | 'partitioned' (stats-driven
    or session-forced) | 'broadcast' (session-forced replication).  Reference:
    DistributionType chosen by DetermineJoinDistributionType.java:51.
    """

    kind: str  # inner | left | semi | anti
    left: PlanNode  # probe side
    right: PlanNode  # build side
    left_keys: tuple  # channel indices into left schema
    right_keys: tuple  # channel indices into right schema
    schema: Schema  # left fields then right fields (semi/anti: left only)
    filter: Optional[Expr] = None  # over concatenated channels
    distribution: str = "replicated"
    null_aware: bool = False  # IN/NOT IN 3VL semantics (NULL build keys -> UNKNOWN)
    est_rows: Optional[float] = None  # CBO output-cardinality estimate
    # (EXPLAIN surface; reference: PlanNodeStatsEstimate in PlanPrinter)

    @property
    def children(self):
        return (self.left, self.right)


@dataclasses.dataclass(frozen=True)
class WindowSpec:
    """One window function call (reference: plan/WindowNode.Function)."""

    kind: str  # row_number | rank | dense_rank | sum | avg | min | max | count |
    # count_star | lag | lead | first_value | last_value
    arg: Optional[int]  # child channel (None for row_number/rank/.../count_star)
    partition: tuple  # child channel indices
    order: tuple  # SortKey over child channels
    name: str
    type: Type
    offset: int = 1  # lag/lead distance
    default: object = None  # lag/lead third argument (raw constant), None = NULL
    frame: tuple = None  # explicit (unit, s_type, s_k, e_type, e_k) frame spec
    # (parser.WindowCall.frame); None = default RANGE UNBOUNDED..CURRENT ROW
    ignore_nulls: bool = False  # navigation functions skip NULL inputs


@dataclasses.dataclass(frozen=True)
class Window(PlanNode):
    """reference: sql/planner/plan/WindowNode.java; output = child channels + one
    channel per spec."""

    child: PlanNode
    specs: tuple  # WindowSpec...
    schema: Schema

    @property
    def children(self):
        return (self.child,)


@dataclasses.dataclass(frozen=True)
class MatchRecognize(PlanNode):
    """reference: sql/planner/plan/PatternRecognitionNode.java + the matcher
    programs of operator/window/matcher/ (compiled NFA over sorted partitions).

    Subset semantics: linear PATTERN of variables with ?/*/+ quantifiers
    (greedy, with backtracking), per-row DEFINE conditions evaluated over the
    sorted input extended with PREV/NEXT-shifted navigation channels, ONE ROW
    PER MATCH output (partition keys + measures), AFTER MATCH SKIP PAST LAST
    ROW; empty matches are skipped."""

    child: PlanNode
    partition: tuple  # child channel indices
    order: tuple  # SortKey over child channels
    pattern: tuple  # ((element, quantifier|None), ...); element = var name or
    # tuple of var names (alternation group, leftmost-preferred like the
    # reference's pattern alternation)
    defines: tuple  # ((var, ir.Expr over extended channels), ...)
    nav: tuple  # ((base_channel, offset), ...) appended shifted channels
    measures: tuple  # ((kind 'first'|'last'|'col', var|None, channel, name), ...)
    schema: Schema  # ONE ROW: partition + measure fields;
    # ALL ROWS: child fields + measure fields
    all_rows: bool = False  # ALL ROWS PER MATCH output mode

    @property
    def children(self):
        return (self.child,)


@dataclasses.dataclass(frozen=True)
class Unnest(PlanNode):
    """reference: sql/planner/plan/UnnestNode.java / operator/unnest/UnnestOperator.java.

    Expands array-typed channels into one output row per element: replicate
    channels repeat per element (the CROSS JOIN UNNEST shape), unnest channels
    emit their elements; optional ordinality channel appends the 1-based
    element index.  Expansion uses the searchsorted map of ops/arrays.py —
    the same device pattern as the multi-match join."""

    child: PlanNode
    replicate: tuple  # child channel indices carried through (repeated)
    unnest_channels: tuple  # child channel indices of array columns to expand
    array_datas: tuple  # ops.arrays.ArrayData per unnest channel (element heaps)
    ordinality: bool
    schema: Schema

    @property
    def children(self):
        return (self.child,)


@dataclasses.dataclass(frozen=True)
class Union(PlanNode):
    """UNION ALL: concatenates child streams (reference: sql/planner/plan/UnionNode.java;
    distinct/intersect/except are planned as aggregation/joins on top, like the
    reference's SetOperationNodeTranslator)."""

    inputs: tuple  # PlanNode...
    schema: Schema

    @property
    def children(self):
        return self.inputs


@dataclasses.dataclass(frozen=True)
class Values(PlanNode):
    """reference: sql/planner/plan/ValuesNode.java; rows of python literals."""

    rows: tuple
    schema: Schema
    source_tables: tuple = ()  # (catalog, table) provenance when an optimizer
    # rewrite (count(*) pushdown) replaced a scan: access control must still
    # see the table it came from


@dataclasses.dataclass(frozen=True)
class RemoteSource(PlanNode):
    """A fragment input read from the exchange: the subtree it replaces ran as
    remote task(s) whose spooled outputs concatenate to this node's rows
    (reference: sql/planner/plan/RemoteSourceNode.java — a fragment's leaf
    standing for the exchange from its source stage).  The executor never
    evaluates this node directly; the task runner resolves it to an override
    page before execution."""

    task_ids: tuple  # spooled task outputs to concatenate, in order
    schema: Schema


@dataclasses.dataclass(frozen=True)
class Exchange(PlanNode):
    """PHYSICAL data-movement marker (reference: sql/planner/plan/ExchangeNode.java
    placed by optimizations/AddExchanges.java:145).  The execution plan never
    contains these — on TPU the movement is an XLA collective fused into the
    surrounding jitted program (all_to_all / all_gather over the mesh), not an
    operator.  ``exchanges.physical_plan`` inserts them for EXPLAIN so the
    chosen placement and partitioning handle are visible and testable.

    kind: 'broadcast' (replicate to every device) | 'hash' (route by key
    hash — the bucketize + all_to_all protocol) | 'gather' (collect partials
    to the merge site)."""

    child: PlanNode
    kind: str
    keys: tuple = ()  # child channel indices for 'hash'

    @property
    def schema(self):
        return self.child.schema

    @property
    def children(self):
        return (self.child,)


@dataclasses.dataclass(frozen=True)
class Output(PlanNode):
    """reference: sql/planner/plan/OutputNode.java; renames channels for the client."""

    child: PlanNode
    names: tuple

    @property
    def schema(self):
        from ..page import Field

        return Schema(tuple(Field(n, f.type) for n, f in zip(self.names, self.child.schema.fields)))

    @property
    def children(self):
        return (self.child,)


def _plan_fingerprint(node: PlanNode, catalogs: dict) -> str:
    """Structural fingerprint of a plan subtree — the build-cache key.

    Two structurally identical build fragments (same operators, expressions,
    schemas, scanned tables) must collide even when they come from DIFFERENT
    plan objects (another executor compiling the same cached plan, a second
    statement sharing the subquery), so the walk is content-based: dataclass
    leaves print by value, plan children recurse, and TableScans carry their
    catalog/table/columns plus the connector's plan_version (growable
    catalogs — the system tables' dictionaries — never serve a stale build).
    Opaque payloads (dictionary value arrays) print by IDENTITY: they are
    connector-owned singletons, stable for the life of this process, and
    printing megabyte arrays by content would be both slow and collision-
    prone under numpy's truncating repr."""
    def val(v):
        if v is None or isinstance(v, (str, int, float, bool, bytes)):
            return repr(v)
        if isinstance(v, (tuple, list)):
            return "(" + ",".join(val(x) for x in v) + ")"
        if isinstance(v, PlanNode):
            return fp(v)
        if isinstance(v, np.ndarray):
            return f"nd#{id(v)}"
        if dataclasses.is_dataclass(v) and not isinstance(v, type):
            return f"{type(v).__name__}(" + ",".join(
                val(getattr(v, f.name)) for f in dataclasses.fields(v)) + ")"
        return f"{type(v).__name__}#{id(v)}"

    def fp(n):
        if isinstance(n, TableScan):
            conn = catalogs.get(n.catalog)
            ver = conn.plan_version() if hasattr(conn, "plan_version") else 0
            return (f"TableScan({n.catalog},{n.table},"
                    f"{','.join(n.columns)},v{ver})")
        return f"{type(n).__name__}(" + ";".join(
            val(getattr(n, f.name)) for f in dataclasses.fields(n)) + ")"

    return fp(node)
