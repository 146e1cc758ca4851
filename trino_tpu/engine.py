"""Engine facade: catalogs + session + query entry point.

Mirrors the coordinator entry path of the reference (dispatcher/DispatchManager.java:176 →
execution/SqlQueryExecution.java) minus the HTTP/queueing layers (those live in
trino_tpu.server): parse → analyze → plan → optimize → execute.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import re
from typing import Optional

from .exec.boundary import DISPATCH_BATCH
from .exec.local_executor import BatchUnsupported, LocalExecutor
from .exec.pages import MaterializedResult
from .sql.plan import _plan_fingerprint

__all__ = ["Engine", "Session"]

_query_ids = itertools.count(1)

# wall_breakdown bucket -> the QueryCounters seconds field it folds into at
# the statement's end (compile has compile_s from the XLA events)
_WALL_COUNTERS = (("plan", "wall_plan_s"),
                  ("split_generation", "wall_split_generation_s"),
                  ("h2d", "wall_h2d_s"),
                  ("device_dispatch", "wall_dispatch_s"),
                  ("host_pull", "wall_host_pull_s"),
                  ("scan_wait", "wall_scan_wait_s"),
                  ("exchange_wait", "wall_exchange_wait_s"),
                  ("unattributed", "wall_unattributed_s"))


@dataclasses.dataclass
class Session:
    """reference: core/trino-main .../Session.java (subset)."""

    catalog: Optional[str] = None
    schema: Optional[str] = None
    user: str = "user"
    properties: dict = dataclasses.field(default_factory=dict)
    prepared: dict = dataclasses.field(default_factory=dict)  # name -> sql text


class Engine:
    def __init__(self, mesh=None):
        self.catalogs: dict = {}
        # a deployment on a worker mesh (parallel.mesh.worker_mesh(n)): every
        # statement whose caller names neither ``distributed`` nor ``mesh``
        # runs on this mesh's executor.  None: the one-chip engine.
        self.mesh = mesh
        # mesh -> its DistributedExecutor.  One per mesh, made at the first
        # statement on it and kept: compiled fragments, join tables and the
        # ladder rung that last held outlive the statement, as a pooled
        # LocalExecutor's caches do.  Its caches are single-statement state,
        # so ``ex.statement_lock`` serializes the statements on one mesh.
        self._mesh_executors: dict = {}
        self.sql_routines: dict = {}  # name -> (params, return_type, body AST)
        self.views: dict = {}  # name -> (column_aliases, Select AST); reference:
        # coordinator-only DataDefinitionTask CreateViewTask (execution/CreateViewTask.java)
        # compiled-query caches — the reference caches generated bytecode per expression
        # (sql/gen/PageFunctionCompiler.java:103 projectionCache); here the unit is the
        # whole plan + its jitted pipelines, keyed by normalized SQL text (valid while
        # catalogs are immutable generators; DDL invalidates)
        self._plan_cache: dict = {}
        # plan TEMPLATES (round 13): parameterized statements compile once and
        # bind constants per request — entries are _PlanTemplate (plan with
        # ir.Parameter runtime inputs + per-slot Binders) or the
        # _TEMPLATE_UNBINDABLE negative sentinel, keyed like the plan cache
        # on the PARAMETERIZED text.  A warm EXECUTE (or auto-parameterized
        # point SELECT) performs zero parse/analyze/plan work and zero
        # re-compilation: its cost is the dispatches themselves.
        self._template_cache: dict = {}
        self.plan_templates_enabled = True
        self._executor_pool: list = []  # idle executors (checkout/release)
        self._all_executors: list = []  # every live executor (invalidation)
        self._executor_sem = None  # lazy: created at first checkout
        import threading

        self._init_lock = threading.Lock()
        # serializes plan-cache lookup/plan/store AND system-dictionary growth
        # (the system connector takes it in dictionaries()/generate()).  RLock:
        # planning itself calls connector.dictionaries() under this lock.
        self._plan_lock = threading.RLock()
        # control plane (reference: DispatchManager + QueryTracker + resource groups
        # + EventListenerManager + TracingModule, assembled in server/Server.java)
        from .execution.eventlistener import EventListenerManager
        from .execution.query_state import QueryTracker
        from .execution.resourcegroups import ResourceGroupManager
        from .execution.session_properties import SessionPropertyManager
        from .execution.tracing import COMPILE_LOG, INFLIGHT, QueryCounters, \
            StallWatchdog, Tracer

        self.materialized_views: dict = {}  # name -> {"query": AST,
        # "storage": table, "catalog": name} (reference: MV definitions +
        # storage tables, e.g. IcebergMetadata's materialized view support)
        # device buffer pool (round 9, execution/bufferpool.py): HBM-resident
        # scan pages + finished join builds shared by every pooled executor.
        # Engine-owned so concurrent queries share one cache under its lock;
        # _invalidate clears it (DDL must release every cached device byte),
        # and the version-stale plan path invalidates per catalog.  Gated by
        # TRINO_TPU_PAGE_CACHE (byte budget; 0 = off, the CPU default) plus
        # the non-plan-shaping ``page_cache`` session property.
        from .execution.bufferpool import DeviceBufferPool

        self.buffer_pool = DeviceBufferPool()
        self.query_tracker = QueryTracker()
        # admission gate (round 11, the ladder's "deny admission" rung): new
        # statements queue at their resource group while any executor pool
        # sits blocked, instead of racing a pressured pool straight into
        # Grace fallbacks; finished queries drain the queue as memory frees
        self.resource_groups = ResourceGroupManager(
            admission_gate=self._memory_admission_ok)
        self.event_listeners = EventListenerManager()
        self.session_properties = SessionPropertyManager()
        self.tracer = Tracer()
        # device-boundary accounting (exec/boundary's counters): lifetime
        # totals for the /v1/metrics Prometheus surface plus the most recent
        # local plan execution's snapshot (what EXPLAIN ANALYZE and the
        # benchmark report per query)
        self.counters_total = QueryCounters()
        self.last_query_counters = QueryCounters()
        # span-tree summary of the most recent statement (execute_sql paths):
        # {"query_id", "root_span_s", "spans": [span_dict...]} — the payload
        # behind GET /v1/query/{id}/trace
        self.last_query_trace: dict = {}
        # live in-flight introspection (round 8): every _jit dispatch / _host
        # pull / split-generation / exchange segment holds a registry entry
        # while it runs; the stall watchdog samples it (TRINO_TPU_STALL_S,
        # default off on CPU — unset means no watchdog thread) and publishes
        # structured reports here for /v1/status and the stalled gauge
        self.inflight = INFLIGHT
        # plan-actuals history (round 15, execution/history.py): per-node
        # est-vs-actual cardinality records keyed by structural plan
        # fingerprint + structural node path, appended on clean completion
        # only, from actuals the executor already computed (zero extra
        # dispatches/pulls — test_query_budgets runs with this ON).
        # TRINO_TPU_PLAN_HISTORY caps retained plans (0 disables, unset=256).
        # Surfaces: EXPLAIN ANALYZE ratio annotations + "Misestimates:",
        # system.runtime.plan_history, GET /v1/history, /v1/metrics gauges,
        # QueryCompletedEvent.plan_actuals.  The store is the input substrate
        # the adaptive advisor (round 19, execution/adaptive.py) turns into
        # plan decisions at statement admission.
        from .execution.history import PlanHistoryStore

        self.plan_history = PlanHistoryStore()
        self.last_plan_actuals: Optional[dict] = None
        # query flight recorder (round 16, execution/flightrecorder.py): one
        # durable record per completed OR errored statement — normalized SQL,
        # counters+sites, the finished span tree, the wall-clock breakdown,
        # plan-actuals, admission wait — plus stall-report events, appended
        # off the hot path under cache-store guard discipline (a recorder
        # failure never fails the query; zero _jit/_host traffic — the
        # budget suite runs with it ENABLED).  TRINO_TPU_FLIGHT_RECORDS caps
        # the in-memory ring (0 disables); TRINO_TPU_FLIGHT_DIR (+
        # TRINO_TPU_FLIGHT_BYTES) adds the on-disk JSONL ring a dead
        # process's post-mortem reads back (scripts/flight.py).
        from .execution.flightrecorder import FlightRecorder

        self.flight_recorder = FlightRecorder()
        # compile observatory (round 17, execution/tracing.CompileLog): the
        # process-global census of every XLA compilation observed at the
        # _jit chokepoint — {site, op label, query id, arg signature,
        # duration, exe size} per compile, with recompile-storm detection.
        # Surfaces: system.runtime.compilations, GET /v1/compiles,
        # /v1/metrics trino_tpu_compile_*, EXPLAIN ANALYZE "Compile:" line,
        # flight records.  TRINO_TPU_COMPILE_LOG caps the ring (default 512).
        self.compile_log = COMPILE_LOG
        # adaptive execution (round 19, execution/adaptive.py): THE
        # chokepoint where plan-actuals history + compile costs turn into
        # plan decisions.  Consulted at statement admission (after the
        # plan-cache key resolves), fed by _record_plan_history on every
        # clean completion.  Host-only — zero _jit/_host traffic; the
        # budget suite runs with it enabled.  The adaptive_execution session
        # property disables per statement.
        from .execution.adaptive import AdaptiveAdvisor

        self.adaptive_advisor = AdaptiveAdvisor(
            history=self.plan_history, compile_log=self.compile_log)
        # continuous template batching (round 21, execution/batcher.py): a
        # per-template-key rendezvous that fuses N concurrent EXECUTEs of
        # one CONFIRMED template into ONE batched device program
        # (LocalExecutor.execute_batched) with per-request result demux.
        # Zero cost when idle — the first request on an idle lane runs the
        # unmodified serial path.
        from .execution.batcher import TemplateBatcher

        self.template_batcher = TemplateBatcher()
        self.last_stall_report: Optional[dict] = None
        # the watchdog is compile-aware (round 17): a first-seen-signature
        # dispatch is judged against TRINO_TPU_STALL_COMPILE_S (default 10x
        # STALL_S) and verdicts "compiling", not "stalled" — STALL_S can
        # finally sit at tight wedge scale instead of above cold-compile time
        self.stall_watchdog = StallWatchdog(on_stall=self._on_stall,
                                            extra_info=self._stall_extra)
        if self.stall_watchdog.enabled:
            self.stall_watchdog.start()
        # the system catalog is always present (reference: GlobalSystemConnector
        # registered at server bootstrap, connector/system/)
        from .connectors.system import (InformationSchemaConnector,
                                        SystemConnector)
        from .spi.security import AllowAllAccessControl

        # reference: AccessControlManager assembled at server bootstrap
        # (spi/security SystemAccessControl); enforcement points below mirror
        # the reference's query admission / analyzer / statement-task checks
        self.access_control = AllowAllAccessControl()
        self.catalogs["system"] = SystemConnector(self)
        # the introspection surface BI tools expect (reference: every catalog
        # gets an information_schema; flat namespace here -> one shared one)
        self.catalogs["information_schema"] = InformationSchemaConnector(self)

    def register_catalog(self, name: str, connector) -> None:
        self.catalogs[name] = connector
        self._invalidate()

    def _invalidate(self) -> None:
        """Drop cached plans AND the executor's compiled pipelines/build tables (the
        executor caches hold device arrays keyed by plan-node identity — dropping only
        the plans would leak them).  The device buffer pool clears with them:
        cached scan pages / join builds of the old catalog state must neither
        serve stale data nor keep their HBM reservations."""
        self._plan_cache.clear()
        self._template_cache.clear()
        with self._init_lock:
            # under the pool lock: a concurrent _release_executor must not
            # re-pool an executor retired by this invalidation
            self._executor_pool = []
            self._all_executors = []
            self._mesh_executors = {}
        self._fte_executor = None
        bp = getattr(self, "buffer_pool", None)
        if bp is not None:
            bp.clear()

    def create_session(self, catalog: Optional[str] = None, schema: str = "default") -> Session:
        return Session(catalog=catalog, schema=schema)

    def _check_select_access(self, plan, user: str) -> None:
        """Every table the plan scans needs SELECT (reference: the analyzer's
        per-table checkCanSelectFromColumns during StatementAnalyzer)."""
        from .sql import plan as P

        storage_to_mv = {m["storage"]: name
                         for name, m in self.materialized_views.items()}

        def walk(n):
            if isinstance(n, P.TableScan) and not n.source_tables:
                # MV storage tables check under the MV's own name, so rules
                # and grants naming the view apply.  Scans of VIRTUAL pushdown
                # handles check their source_tables (below) instead — the
                # handle name itself is not a grantable object
                self.access_control.check_can_select(
                    user, n.catalog, storage_to_mv.get(n.table, n.table))
            for cat, tab in getattr(n, "source_tables", ()):
                # scans folded away by optimizer rewrites (count(*) pushdown)
                # still carry their table for the access check
                self.access_control.check_can_select(
                    user, cat, storage_to_mv.get(tab, tab))
            for c in n.children:
                walk(c)

        walk(plan)

    def _catalog_name_of(self, conn) -> str:
        for name, c in self.catalogs.items():
            if c is conn:
                return name
        return "?"

    def _plan_versions(self, plan) -> tuple:
        """Versions of the versioned catalogs this plan actually scans.
        Connectors whose visible data/dictionaries mutate without DDL (the
        system catalog's growable dictionaries) expose a plan_version counter;
        the snapshot is stored alongside the cached plan and compared at
        lookup, so growth replans only the affected plans (a stale plan would
        gather new dictionary ids past its embedded LUT bounds) while plans
        over immutable catalogs hit the cache unconditionally."""
        from .sql import plan as P

        names: set = set()

        def walk(n):
            if isinstance(n, P.TableScan):
                names.add(n.catalog)
            for c in n.children:
                walk(c)

        walk(plan)
        return tuple(
            (nm, self.catalogs[nm].plan_version()) for nm in sorted(names)
            if nm in self.catalogs and hasattr(self.catalogs[nm], "plan_version"))

    def _stale_catalog(self, versions):
        """First catalog whose plan_version moved past ``versions``'s
        snapshot, or None when the snapshot is still current."""
        for name, ver in versions:
            conn = self.catalogs.get(name)
            if conn is None or conn.plan_version() != ver:
                return name
        return None

    def _evict_stale_plan(self, plan, name) -> None:
        """Version-stale eviction shared by the plan and template caches: the
        executors' compiled-pipeline caches are keyed by id(node) and pin jit
        executables + device arrays, so dropping only the cache entry would
        leak a full compiled copy per replan (same reasoning as
        _invalidate)."""
        for ex in self._all_executors + list(self._mesh_executors.values()):
            ex.forget_plan(plan)
        fte = getattr(self, "_fte_executor", None)
        if fte is not None:
            fte.local.forget_plan(plan)
        # buffer-pool entries over the stale catalog release their device
        # bytes here too (their version-bearing keys would never serve
        # again, but unevicted they'd pin HBM)
        self.buffer_pool.invalidate_catalog(name)

    def _cache_lookup(self, key):
        """-> cached plan, or None when absent or version-stale (stale
        entries evict through _evict_stale_plan).  Caller holds
        _plan_lock."""
        entry = self._plan_cache.get(key)
        if entry is None:
            return None
        plan, versions = entry
        stale = self._stale_catalog(versions)
        if stale is not None:
            self._evict_stale_plan(plan, stale)
            self._plan_cache.pop(key, None)
            return None
        return plan

    def _cache_store(self, key, plan) -> None:
        # version snapshot taken AFTER planning: planning itself encodes the
        # current rows into the growable dictionaries, so a pre-planning
        # snapshot would be stale the moment it was stored
        self._plan_cache[key] = (plan, self._plan_versions(plan))

    # -- plan templates (round 13: compile once, bind constants per request) ----
    def _template_key(self, text: str, sess) -> tuple:
        from .sql.params import normalize_sql

        return (normalize_sql(text), sess.catalog, sess.user,
                _plan_shape_props(sess))

    def _template_lookup(self, key, kinds=None):
        """-> _PlanTemplate, _TEMPLATE_UNBINDABLE, or None (absent/stale).
        Same version handling as _cache_lookup (shared _evict_stale_plan).
        The negative sentinel is scoped to the literal KINDS that failed —
        an ill-typed binding must not poison the well-typed shape sharing
        the same parameterized text (different kinds retry creation).
        Caller holds _plan_lock."""
        entry = self._template_cache.get(key)
        if entry is None:
            return None
        tpl, versions = entry
        if tpl is _TEMPLATE_UNBINDABLE:
            # ``versions`` holds the failing kinds tuple for sentinels
            if kinds is None or versions == kinds:
                return tpl
            return None
        stale = self._stale_catalog(versions)
        if stale is not None:
            self._evict_stale_plan(tpl.plan, stale)
            self._template_cache.pop(key, None)
            return None
        return tpl

    def _maybe_template_execute(self, text: str, param_asts, sess,
                                qsm=None):
        """Try the plan-template path for parameterized statement ``text``
        with the given EXECUTE literal ASTs.  Returns (handled, result);
        (False, None) means the caller must run the substitution path.

        A warm hit performs zero parse/analyze/plan work: the cache probe is
        the only pre-execution cost, and execution re-dispatches the already-
        compiled pipelines with the bound values as runtime inputs."""
        if not self.plan_templates_enabled:
            return False, None
        from .sql import params as PRM

        key = self._template_key(text, sess)
        kinds = PRM.literal_kinds(param_asts)
        with self._plan_lock:
            tpl = self._template_lookup(key, kinds)
        if tpl is _TEMPLATE_UNBINDABLE:
            return False, None
        created = False
        if tpl is None:
            tpl, created = self._create_template(key, text, param_asts,
                                                 sess, qsm)
            if tpl is None:
                return False, None
        if tpl.n_params != len(param_asts):
            # arity errors surface through the substitution path, whose
            # messages ("not enough parameters") are the established contract
            return False, None
        try:
            runtime = PRM.bind_values(tpl.binders, param_asts)
        except PRM.BindError:
            # binding-specific impossibility (type-width overflow, finer
            # timestamp): substitution re-plans with the literal inline; the
            # template stays cached for bindable executions
            return False, None
        try:
            result = self._execute_template(tpl, runtime, param_asts, sess,
                                            qsm, created, key=key)
        except BaseException as e:
            from .execution.tracing import StallKilledError

            if isinstance(e, (KeyboardInterrupt, SystemExit, GeneratorExit,
                              StallKilledError)):
                # never convert an interpreter exit — or the stall
                # watchdog's kill — into a silent substitution re-run (the
                # re-run would land straight back on the wedged device)
                raise
            if not tpl.confirmed:
                # probation: a template whose FIRST execution fails is never
                # trusted — drop it (evicting compiled artifacts) and let
                # the substitution path produce the result (or the honest
                # error).  DETERMINISTIC failures negative-cache (scoped to
                # these literal kinds); transient ones (device faults,
                # injected errors) just retry creation next time.
                from .exec.fte import is_retryable_failure

                with self._plan_lock:
                    if is_retryable_failure(e):
                        self._template_cache.pop(key, None)
                    else:
                        self._template_cache[key] = (_TEMPLATE_UNBINDABLE,
                                                     kinds)
                with self._init_lock:
                    executors = list(self._all_executors)
                for ex in executors:
                    ex.forget_plan(tpl.plan)
                return False, None
            raise
        tpl.confirmed = True
        self._stamp_template(hits=0 if created else 1,
                             misses=1 if created else 0)
        return True, result

    def _create_template(self, key, text, param_asts, sess, qsm=None):
        """Plan ``text`` with its markers bound to representative literals;
        store a _PlanTemplate on success.  Returns (template|None, created):
        ``created`` is False when a concurrent creator's template was reused
        (the caller stamps a HIT, not a miss).  Structural failures (literal
        consumed at plan time, non-streaming plan shape, parse/semantic
        errors) negative-cache; transient ones (NULL first binding, a
        malformed literal VALUE) and arity problems just fall back."""
        from .execution.query_state import QueryState
        from .sql import params as PRM
        from .sql import parser as A
        from .sql.frontend import Planner

        kinds = PRM.literal_kinds(param_asts)

        def reject():
            # negative entries are scoped to the literal KINDS that failed
            # (the sentinel slot that normally holds versions carries them)
            with self._plan_lock:
                self._template_cache[key] = (_TEMPLATE_UNBINDABLE, kinds)
            return None, False

        if _VOLATILE_SQL_RE.search(text):
            # volatile builtins (now(), current_date, random()) fold to
            # plan-time Constants: a template would serve the FIRST
            # execution's fold frozen to every later binding, for the
            # process lifetime.  Reject, so each distinct statement re-plans
            # (the same conservative text test the result tier uses).
            return reject()
        try:
            ast = A.parse(text)
        except A.ParseError:
            return reject()
        if not isinstance(ast, (A.Select, A.SetOp)):
            return reject()  # prepared DML substitutes; only SELECTs template
        ords = PRM.marker_ordinals(ast)
        if not ords:
            return None, False  # nothing to bind: the plan cache owns it
        if ords != set(range(len(param_asts))):
            return None, False  # arity mismatch: substitution reports it
        if isinstance(ast, A.Select) and (ast.group_by or ast.distinct
                                          or ast.having is not None):
            # aggregation plans are outside the template subset (the plan-
            # shape check below is authoritative; this skips planning twice
            # for the common shapes)
            return reject()
        if qsm is not None:
            qsm.transition(QueryState.PLANNING)
        reg = PRM.ParamRegistry(len(param_asts))
        try:
            bound = PRM.bind_markers(ast, tuple(param_asts))
            span = (self.tracer.span("planner", trace_id=qsm.query_id)
                    if qsm is not None else _null_ctx())
            with span:
                # same atomicity contract as the plan cache: planning +
                # version snapshot under _plan_lock (dictionary growth)
                with self._plan_lock:
                    # a concurrent creator may have won while this thread
                    # waited on the lock: reuse its template rather than
                    # overwriting (the evicted plan's compiled pipelines
                    # would leak in every executor that traced them)
                    cur = self._template_lookup(key, kinds)
                    if isinstance(cur, _PlanTemplate):
                        return cur, False  # the winner's execution = a hit
                    if cur is _TEMPLATE_UNBINDABLE:
                        return None, False
                    planner = Planner(self, sess)
                    planner.param_registry = reg
                    plan = planner.plan_query(bound)
                    if not _template_plan_ok(plan):
                        raise PRM.Unbindable(
                            "plan shape outside the streaming template "
                            "subset")
                    tpl = _PlanTemplate(text=text, plan=plan,
                                        binders=tuple(reg.binders),
                                        n_params=len(param_asts))
                    self._template_cache[key] = (tpl,
                                                 self._plan_versions(plan))
        except PRM.Unbindable as e:
            return (None, False) if e.transient else reject()
        except Exception:
            # parse/semantic/fold errors during template planning: the
            # substitution path either succeeds (plan-time value was needed)
            # or reports the real user error
            return reject()
        return tpl, True

    def _execute_template(self, tpl, runtime, param_asts, sess, qsm,
                          created, key=None):
        """Execute a bound template: result-cache consult first (keys carry
        the BOUND VALUES, so a hit can never serve another binding's answer;
        volatility was tested on the TEMPLATE text — a bound string
        containing 'random(' does not disqualify), then local execution with
        the runtime slot values threaded into every dispatch."""
        from .execution.query_state import QueryState
        from .sql import params as PRM

        self._check_select_access(tpl.plan, sess.user)
        if qsm is not None:
            if created:
                qsm.transition(QueryState.STARTING)
            qsm.transition(QueryState.RUNNING)
        rkey = self._result_cache_key(tpl.text, tpl.plan, sess)
        if rkey is not None:
            rkey = rkey + (PRM.values_cache_key(param_asts),)
        epoch = self.buffer_pool.epoch if rkey is not None else None
        if rkey is not None:
            served = self._result_cache_fetch(rkey)
            if served is not None:
                return served
        span = (self.tracer.span("execution", trace_id=qsm.query_id)
                if qsm is not None else _null_ctx())
        self._thread_accounting.history_sql = tpl.text
        with span:
            bt = getattr(self, "template_batcher", None)
            if (bt is not None and bt.enabled and key is not None
                    and tpl.confirmed and tpl.batchable and runtime):
                result, batched_with = bt.execute(
                    key, runtime,
                    lambda rt: self._template_serial_execute(tpl, rt, sess),
                    lambda rts: self._execute_template_batch(tpl, rts,
                                                             sess))
                if batched_with:
                    # a RIDER served from another thread's fused batch has
                    # no counters of its own: publish an empty per-statement
                    # snapshot so completion events / flight records stay
                    # per-request (the DRIVER's snapshot carries the batch's
                    # real device spend, merged into totals exactly once)
                    if self._thread_accounting.snap is None:
                        from .execution.tracing import QueryCounters

                        self._account_counters(QueryCounters())
                    self._stamp_batched(batched_with)
            else:
                result = self._template_serial_execute(tpl, runtime, sess)
        self._result_cache_finish(rkey, result, epoch=epoch)
        return result

    def _template_serial_execute(self, tpl, runtime, sess):
        """The unmodified single-statement template execution (round 14):
        the bound values ride every dispatch as runtime inputs."""
        return self.execute_plan(
            tpl.plan, params=runtime,
            query_max_memory=_query_mem_limit(self, sess),
            dispatch_batch=_effective_dispatch_batch(sess),
            page_cache=_effective_page_cache(sess))

    def _execute_template_batch(self, tpl, runtimes, sess):
        """One fused device execution for a whole gather window (round 21):
        the driver's thread checks out an executor and runs the template's
        plan ONCE with every member's bindings stacked on a leading lane
        axis (LocalExecutor.execute_batched).  Returns per-member
        results/exceptions in member order; the batcher demuxes.  A
        BatchUnsupported plan (shape outside the batchable subset,
        object-dtype pages) permanently demotes the template to serial
        lanes — the batcher re-runs every member serially, byte-identically.

        Deliberately NO _record_plan_history: fused actuals are batch-shaped
        (N lanes in one page) and would corrupt the per-statement EWMAs the
        adaptive advisor reads — the serial path remains the history feeder."""
        ex = self._checkout_executor()
        ex.dispatch_batch = _effective_dispatch_batch(sess)
        ex.page_cache = _effective_page_cache(sess)
        try:
            pool = getattr(ex, "memory_pool", None)
            if pool is not None:
                pool.begin_query(_query_mem_limit(self, sess))
            try:
                return ex.execute_batched(tpl.plan, runtimes)
            except BatchUnsupported:
                tpl.batchable = False
                raise
        finally:
            ex.dispatch_batch = None
            ex.page_cache = None
            self._account_counters(ex.counters)
            self._release_executor(ex)

    def _stamp_batched(self, batched_with: int) -> None:
        """Fused-batch accounting (round 21), stamped after execution like
        _stamp_template: EVERY request served through a fused batch — driver
        and riders alike — counts one ``batched_requests`` on its own
        snapshot and the totals, so per-request snapshots sum to the engine
        totals exactly.  The batch size itself rides the flight record via
        the thread slot, not a counter."""
        with self._init_lock:
            self.counters_total.batched_requests += 1
        snap = self._thread_accounting.snap
        if snap is not None:
            snap.batched_requests += 1
        self._thread_accounting.batched_with = batched_with

    def _stamp_template(self, hits: int = 0, misses: int = 0) -> None:
        """Template-path accounting, stamped AFTER execution onto the
        statement's counters snapshot (the execute path accounted before any
        counters context carried the probe — same pattern as
        admission_queued)."""
        with self._init_lock:
            self.counters_total.plan_template_hits += hits
            self.counters_total.plan_template_misses += misses
        snap = self._thread_accounting.snap
        if snap is not None:
            snap.plan_template_hits += hits
            snap.plan_template_misses += misses

    # -- result cache (round 12: the buffer pool's third tier) -----------------
    def _result_cache_key(self, sql: str, plan, sess,
                          distributed: bool = False,
                          fault_tolerant: bool = False):
        """Result-tier admission + key, or None when the statement must not
        be served/stored from the cache.  Admissible = the tier is enabled
        (TRINO_TPU_RESULT_CACHE budget + the non-plan-shaping ``result_cache``
        session property), every catalog the plan scans is a deterministic
        generator (CACHEABLE_SCANS — the same opt-in the page tier uses;
        system/dbapi stay volatile), and the SQL contains no volatile
        function.  Volatility is tested on the TEXT: volatile builtins fold
        to plan-time constants (now() becomes a Constant during planning), so
        the plan itself cannot reveal them — the conservative word-match may
        over-exclude a column literally named "random", never under-exclude.

        The key is (structural plan fingerprint, catalogs tuple, execution
        mode, plan-shaping session props): the fingerprint embeds each
        scanned table's plan_version, so a catalog-version bump can never
        SERVE stale rows — invalidate_catalog / _invalidate release the dead
        entries' bytes on top of that."""
        if plan is None or not isinstance(sql, str):
            return None
        bp = self.buffer_pool
        if bp is None or not bp.result_enabled:
            return None
        if _effective_result_cache(sess) is False:
            return None
        if _VOLATILE_SQL_RE.search(sql):
            return None
        from .sql import plan as P

        catalogs: set = set()

        def walk(n):
            if isinstance(n, P.TableScan):
                catalogs.add(n.catalog)
            for cat, _tab in getattr(n, "source_tables", ()):
                catalogs.add(cat)
            for c in n.children:
                walk(c)

        walk(plan)
        from .execution.bufferpool import DeviceBufferPool

        for name in catalogs:
            conn = self.catalogs.get(name)
            if conn is None or not DeviceBufferPool.cacheable(conn):
                return None
        fp = _plan_fingerprint(plan, self.catalogs)
        return ("result", fp, tuple(sorted(catalogs)),
                bool(distributed), bool(fault_tolerant),
                _plan_shape_props(sess))

    def _result_cache_fetch(self, rkey):
        """Serve ``rkey`` from the result tier, or None.  A hit is accounted
        as its own per-statement QueryCounters (result_cache_hits=1 and
        NOTHING else — the zero-dispatch/zero-pull claim is counter-verified
        by construction) folded into the engine totals like any executed
        plan's counters."""
        hit = self.buffer_pool.get_result(rkey)
        if hit is None:
            return None
        result, nbytes = hit
        from .execution import tracing
        from .execution.tracing import QueryCounters

        counters = QueryCounters()
        with tracing.track_counters(counters):
            tracing.record_result_cache(hits=1, bytes_saved=nbytes,
                                        site="result.cache")
        self._account_counters(counters)
        return result

    def _result_cache_finish(self, rkey, result,
                             epoch: Optional[int] = None) -> None:
        """Post-execution bookkeeping for an admissible statement that
        MISSED: stamp the miss onto the statement's counters snapshot (the
        executor accounted before any counters context carried the lookup —
        same pattern as admission_queued) and store the completed result.
        ``epoch`` is the pool epoch captured BEFORE execution: a concurrent
        DML's invalidation mid-statement refuses the late store (the result
        may predate the DML).  Reached only on clean completion: an error
        unwinds past it, so errored queries never cache.  The store is
        guarded like the page tier's: a store failure (injected fault,
        sizing race) leaves the query successful and the entry absent."""
        if rkey is None:
            return
        snap = self._thread_accounting.snap
        if snap is not None:
            snap.result_cache_misses += 1
        with self._init_lock:
            self.counters_total.result_cache_misses += 1
        if not isinstance(result, MaterializedResult):
            return
        try:
            self.buffer_pool.put_result(rkey, result, epoch=epoch)
        except Exception:
            pass  # uncached, not failed; the next statement re-executes

    # -- plan-level execution (SQL front-end sits on top, sql/frontend.py) --------------
    def execute_plan(self, plan, distributed: bool = False, mesh=None,
                     cache: bool = True, fault_tolerant: bool = False,
                     query_max_memory: Optional[int] = None,
                     dispatch_batch: Optional[int] = None,
                     page_cache: Optional[bool] = None,
                     params: Optional[tuple] = None):
        """``cache=False`` executes with a throwaway executor: plan-time eager subquery
        plans are discarded after one run, so caching their compiled pipelines/build
        pages would pin device memory for nothing.  ``fault_tolerant`` routes through
        the task-retrying FTE executor over a spooled exchange (reference:
        RetryPolicy.TASK -> EventDrivenFaultTolerantQueryScheduler).
        ``params`` is a plan template's bound runtime slot tuple — templates
        execute LOCALLY only (the FTE/distributed drivers' step functions do
        not thread parameters)."""
        if params is not None and (distributed or fault_tolerant):
            raise ValueError(
                "parameterized template plans execute locally only")
        if fault_tolerant:
            import tempfile

            from .exec.fte import FaultTolerantExecutor
            from .execution import tracing
            from .execution.tracing import QueryCounters

            with self._init_lock:  # admission allows concurrent queries
                if getattr(self, "_fte_executor", None) is None:
                    self._fte_spool = tempfile.mkdtemp(prefix="trino_tpu_spool_")
                    self._fte_executor = FaultTolerantExecutor(
                        self.catalogs, self._fte_spool)
            # FTE drives its private local executor through _execute_to_page
            # (never execute()), so the query's boundary spend records on an
            # engine-held counter set here and accounts like any other path
            counters = QueryCounters()
            try:
                with tracing.track_counters(counters):
                    return self._fte_executor.execute(
                        plan, dispatch_batch=dispatch_batch)
            finally:
                self._account_counters(counters)
        if distributed:
            with self._mesh_executor(mesh) as ex:
                ex.local.dispatch_batch = dispatch_batch
                ex.local.page_cache = page_cache
                try:
                    result = ex.execute(plan)
                    # clean completion only: fold per-exchange shard skew
                    # into the plan-history store under the same structural
                    # node paths the cardinality actuals use (round 20 —
                    # record and expose; the advisor's consumer lands later)
                    self._record_shard_skew(plan, ex)
                    return result
                finally:
                    ex.local.dispatch_batch = None
                    ex.local.page_cache = None
                    if not cache:
                        ex.forget_plan(plan)  # a plan nobody will replay
                    self._account_counters(ex.counters)
        if not cache:
            # throwaway executors still share the engine's buffer pool: an
            # eager subquery / DML row-eval over a hot table reads the cached
            # scan, and anything it stores outlives the executor usefully
            ex = LocalExecutor(self.catalogs, buffer_pool=self.buffer_pool)
            ex.dispatch_batch = dispatch_batch
            ex.page_cache = page_cache
            ex.exec_params = params
            try:
                return ex.execute(plan)
            finally:
                self._account_counters(ex.counters)
        # executor pool: each concurrent query checks out its OWN executor, so
        # one query's host-side gaps (planning, finalize, np conversion)
        # overlap another's device dispatches — the intra-chip concurrency the
        # reference gets from executor/timesharing, re-planned at query
        # granularity (kernel-level timesharing on one accelerator is XLA's
        # job).  The pool also makes concurrent execute_sql calls SAFE: a
        # LocalExecutor's caches/overrides/stats are single-query state.
        ex = self._checkout_executor()
        # per-query settings on a POOLED executor: reset on release so the
        # next query (possibly a different session) starts from its own values
        ex.dispatch_batch = dispatch_batch
        ex.page_cache = page_cache
        ex.exec_params = params
        try:
            pool = getattr(ex, "memory_pool", None)
            if pool is not None:
                pool.begin_query(query_max_memory)
            result = ex.execute(plan)
            # clean completion only (an exception unwinds past this): fold
            # the per-node actuals into the plan-history store while the
            # executor's stats are still this query's
            self._record_plan_history(plan, ex)
            return result
        finally:
            ex.dispatch_batch = None
            ex.page_cache = None
            ex.exec_params = None
            self._account_counters(ex.counters)
            self._release_executor(ex)

    MAX_CONCURRENT_EXECUTORS = 3  # compiled pipelines + build pages are
    # device-resident per executor: each pool slot multiplies that footprint

    # -- live health / introspection (round 8) ---------------------------------
    def _on_stall(self, report: dict) -> None:
        self.last_stall_report = report
        # stall reports are flight EVENTS: the post-mortem artifact survives
        # the process in the recorder's disk ring instead of only in a live
        # /v1/status poll
        fr = getattr(self, "flight_recorder", None)
        if fr is not None:
            fr.record_event(dict(report, kind="stall"))

    def _stall_extra(self) -> dict:
        """Memory context attached to every stall report (a stall under HBM
        pressure reads differently from one without)."""
        return {"memory": self.memory_info()}

    def memory_info(self) -> list:
        """Snapshot of every executor-held MemoryPool (labeled) — the
        /v1/status + /v1/metrics pool surface.  Labels are STABLE per pool
        object for its lifetime (a monotonic sequence, not a positional
        index): an invalidation retiring an earlier executor must not
        re-label the survivors' Prometheus series."""
        # snapshot, label assignment and prune all under ONE lock hold:
        # executor-list mutations happen under the same lock, so every pool
        # alive at this instant is in the snapshot and a concurrent caller's
        # prune can never drop a label this caller just assigned
        with self._init_lock:
            exs = list(self._all_executors)
            fte = getattr(self, "_fte_executor", None)
            if fte is not None and getattr(fte, "local", None) is not None:
                exs.append(fte.local)
            labels = getattr(self, "_pool_labels", None)
            if labels is None:
                labels = self._pool_labels = {}
                self._pool_label_seq = 0
            pools, seen = [], set()
            for ex in exs:
                pool = getattr(ex, "memory_pool", None)
                if pool is None or id(pool) in seen:
                    continue
                seen.add(id(pool))
                lbl = labels.get(id(pool))
                if lbl is None:
                    lbl = labels[id(pool)] = f"pool{self._pool_label_seq}"
                    self._pool_label_seq += 1
                pools.append((pool, lbl))
            # prune labels of retired pools (ids can be reused after GC; a
            # stale entry would hand a NEW pool an old pool's label)
            self._pool_labels = {pid: l for pid, l in labels.items()
                                 if pid in seen}
        out = []
        for pool, lbl in pools:  # pool locks taken OUTSIDE the engine lock
            d = pool.info()
            d["pool"] = lbl
            out.append(d)
        # the device buffer pool's labeled reservations (page-cache /
        # build-cache tags) — the acceptance surface for "cache bytes are
        # visible in /v1/status", with a STABLE label (never pool<N>)
        bp = getattr(self, "buffer_pool", None)
        bpool = getattr(bp, "memory_pool", None) if bp is not None else None
        if bpool is not None:
            d = bpool.info()
            d["pool"] = "buffer-pool"
            out.append(d)
        return out

    def _memory_admission_ok(self) -> bool:
        """The resource-group admission gate: False while any executor pool
        is past the killer's BLOCKED_FRACTION — the escalation ladder defers
        new work (queue) before anything harsher fires.  Reads the executor
        list without the engine lock (called under the group manager's lock;
        taking _init_lock here could deadlock against a submit racing an
        invalidation) — a torn read just defers/admits one query one pass
        early, which the next drain corrects."""
        from .execution.memory_killer import BLOCKED_FRACTION

        for ex in list(self._all_executors):
            pool = getattr(ex, "memory_pool", None)
            if pool is not None and pool.blocked(BLOCKED_FRACTION):
                return False
        return True

    def health(self) -> dict:
        """Live health verdict: "stalled" when the watchdog's threshold is
        configured and any in-flight entry exceeds it (recomputed from the
        registry, not the last poll); "compiling" when everything over the
        threshold is a first-seen-signature dispatch still under the compile
        threshold (slow but expected — round 17); else "ok"."""
        wd = self.stall_watchdog
        status, stalled_n, compiling_n = wd.status()
        return {"status": status, "stalled": stalled_n,
                "compiling": compiling_n,
                "inflight": self.inflight.depth(),
                "watchdog": {"enabled": wd.enabled, "stall_s": wd.stall_s,
                             "compile_stall_s": wd.compile_stall_s,
                             "kill_s": wd.kill_s, "reports": wd.reports,
                             "kills": wd.kills}}

    def _record_plan_history(self, plan, ex,
                             extra_records: dict = None) -> None:
        """Fold one CLEANLY completed execution's per-node actuals into
        ``plan_history`` and publish the statement's payload (thread-local
        for the completion event + ``last_plan_actuals``).  Reads only what
        the executor already computed — the collection is a host-side dict
        walk plus one batched pull of the row counters through ``_host``
        (site ``history.actuals``; a few scalars), so it adds zero _jit
        dispatches (the budget suite runs with the store enabled).  ``extra_records`` lets the
        cluster coordinator fold harvested worker records in under the same
        fingerprint.  History is observability: any failure here leaves the
        query successful and the store unchanged."""
        # one-shot statement text: read AND clear unconditionally up front —
        # an errored/empty-record statement must not leave its text behind
        # for a later direct execute_plan caller's record to inherit
        sql = getattr(self._thread_accounting, "history_sql", None)
        self._thread_accounting.history_sql = None
        ph = getattr(self, "plan_history", None)
        if ph is None or not ph.enabled:
            return
        try:
            from .execution import tracing
            from .execution.history import collect_plan_actuals, fold_records

            # the row counters' pull is the statement's: on its counters,
            # which execute_plan accounts after this returns
            with tracing.track_counters(ex.counters):
                recs = collect_plan_actuals(
                    plan, ex.stats, boundary=ex.boundary,
                    catalogs=self.catalogs,
                    paths=getattr(ex, "_node_paths", None),
                    ests=getattr(ex, "_node_ests", None),
                    facts=getattr(ex, "_plan_facts", None))
            for path, rec in (extra_records or {}).items():
                fold_records(recs, path, rec)
            if not recs:
                return
            fp = ex.plan_fingerprint(plan)
            payload = ph.record(fp, recs, sql=sql)
            self._thread_accounting.plan_actuals = payload
            with self._init_lock:
                self.last_plan_actuals = payload
            self._adaptive_observe(fp, recs, ex, sql)
        except Exception:
            pass  # never fail a completed statement over its history record

    def _record_shard_skew(self, plan, ex) -> None:
        """Fold one CLEANLY completed DISTRIBUTED execution's per-exchange
        shard skew (DistributedExecutor.skew_by_node) into ``plan_history``
        under the same structural node paths the cardinality actuals use
        (round 20).  Pure host dict walk over records the exchange already
        derived — zero dispatches, zero pulls; any failure leaves the query
        successful and the store unchanged."""
        skew = getattr(ex, "skew_by_node", None)
        ph = getattr(self, "plan_history", None)
        if not skew or ph is None or not ph.enabled:
            return
        try:
            from .execution.history import plan_node_paths

            paths = plan_node_paths(plan)
            recs = {}
            for nid, rec in skew.items():
                path = paths.get(nid)
                if path is None:
                    continue
                recs[path] = {"skew": {
                    "ratio": float(rec.get("ratio", 1.0)),
                    "worker": int(rec.get("worker", 0)),
                    "workers": int(rec.get("workers", 0)),
                    "imbalance_s": float(rec.get("imbalance_s", 0.0)),
                }}
            if not recs:
                return
            fp = _plan_fingerprint(plan, self.catalogs)
            ph.record(fp, recs)
        except Exception:
            pass  # observability must never fail the statement

    def _adaptive_observe(self, fp, recs, ex, sql) -> None:
        """Feed one cleanly completed execution back to the adaptive
        advisor: the node-wall sum is the wall metric (the same quantity the
        win model integrates, so prediction and regression detection use one
        currency), compile counters separate cold runs from warm.  The
        key/corrected slots are one-shot — read AND cleared here so a later
        direct execute_plan caller cannot inherit them."""
        akey = getattr(self._thread_accounting, "adaptive_key", None)
        corrected = bool(getattr(self._thread_accounting,
                                 "adaptive_corrected", False))
        self._thread_accounting.adaptive_key = None
        self._thread_accounting.adaptive_corrected = False
        adv = getattr(self, "adaptive_advisor", None)
        if adv is None or akey is None:
            return
        try:
            wall = sum(float(r.get("wall_s") or 0.0)
                       for r in recs.values() if isinstance(r, dict))
            c = getattr(ex, "counters", None)
            adv.observe(akey, fp, corrected=corrected, wall_s=wall,
                        compiles=int(getattr(c, "compiles", 0) or 0),
                        compile_s=float(getattr(c, "compile_s", 0.0) or 0.0),
                        sql=sql)
        except Exception:
            pass  # advisory machinery must never fail the statement

    def _account_counters(self, counters) -> None:
        """Fold one finished plan execution's device-boundary counters into
        the engine totals and publish the per-query snapshot.  Under the pool
        lock: concurrent queries merge serially (snapshot-then-merge on plain
        ints would lose increments).  The snapshot is ALSO published on a
        thread-local slot: ``last_query_counters`` is shared state a
        concurrent query can overwrite, but every execute path accounts on
        the statement's own thread, so the thread-local is what this
        statement definitely spent (execute_sql reads it for the completion
        event)."""
        with self._init_lock:
            self.counters_total.merge(counters)
            snap = counters.snapshot()
            self.last_query_counters = snap
        self._thread_accounting.snap = snap

    @property
    def _thread_accounting(self):
        local = getattr(self, "_account_local", None)
        if local is None:
            import threading

            with self._init_lock:
                local = getattr(self, "_account_local", None)
                if local is None:
                    local = self._account_local = threading.local()
        return local

    def _checkout_executor(self):
        with self._init_lock:
            if self._executor_sem is None:
                import threading

                self._executor_sem = threading.Semaphore(
                    self.MAX_CONCURRENT_EXECUTORS)
        from .execution import tracing

        with tracing.wait_span("executor.checkout", "executor_wait_s"):
            self._executor_sem.acquire()
        with self._init_lock:
            if self._executor_pool:
                return self._executor_pool.pop()
            ex = LocalExecutor(self.catalogs, buffer_pool=self.buffer_pool)
            self._all_executors.append(ex)
            return ex

    @contextlib.contextmanager
    def _mesh_executor(self, mesh):
        """The engine's executor for ``mesh`` (None: the engine's own mesh,
        else the default worker mesh), made once and held for one statement
        at a time; a second concurrent mesh statement waits here, and the
        wait is its ``executor.checkout`` span and ``executor_wait_s``."""
        from .execution import tracing

        mesh = mesh if mesh is not None else self.mesh
        with self._init_lock:
            ex = self._mesh_executors.get(mesh)
            if ex is None:
                from .exec.distributed import DistributedExecutor

                ex = self._mesh_executors[mesh] = DistributedExecutor(
                    self.catalogs, mesh=mesh, buffer_pool=self.buffer_pool)
        with tracing.wait_span("executor.checkout", "executor_wait_s"):
            ex.statement_lock.acquire()
        try:
            yield ex
        finally:
            ex.statement_lock.release()

    def _release_executor(self, ex) -> None:
        # belt-and-braces: execute() already sweeps its prefetch producers on
        # every exit, but an executor going back into circulation (where the
        # next query may start instantly) must NEVER carry a live producer —
        # a stranded thread from an errored query would race the next scan
        ex.close_producers()
        with self._init_lock:
            # an invalidation while this query ran retired the whole pool;
            # a retired executor must not re-enter circulation
            if ex in self._all_executors:
                self._executor_pool.append(ex)
        self._executor_sem.release()

    def execute_sql(self, sql: str, session: Optional[Session] = None,
                    distributed: Optional[bool] = None, mesh=None,
                    fault_tolerant: bool = False,
                    parameters: Optional[list] = None):
        """Statement entry point (reference: DispatchManager.createQuery:176 ->
        resource-group admission -> SqlQueryExecution for queries; coordinator-only
        DataDefinitionTasks for DDL).  Every statement moves through the query
        state machine and fires listener events.

        ``parameters`` is the protocol-level EXECUTE form: ``sql`` is a
        parameterized statement with ``?`` markers and ``parameters`` the
        python values to bind — the plan-template path serves it without
        re-planning when a template exists; otherwise the values substitute
        as literals."""
        import threading
        import time

        from .execution import tracing
        from .execution.query_state import QueryState, QueryStateMachine

        if distributed is None:
            # an engine on a mesh runs a statement there unless told otherwise
            distributed = self.mesh is not None and mesh is None \
                and not fault_tolerant
        distributed = bool(distributed)
        # where the statement's queue phase starts: where the front end says
        # it accepted it (the server's POST), else here
        accepted = tracing.take_accepted() or (time.perf_counter(), {})
        sess = session or Session()
        # per-thread accounting slots cleared FIRST — even a statement that
        # fails admission/access below must not leave a pooled server thread
        # holding the PREVIOUS statement's counters/trace (the server reads
        # these slots for attribution-safe capture under concurrent reads)
        self._thread_accounting.snap = None
        self._thread_accounting.trace = None
        self._thread_accounting.plan_actuals = None
        # adaptive slots: the admission-time decision (counter stamping +
        # flight record), the statement key the advisor's feedback keys on,
        # and whether THIS execution ran a corrected plan (failure demotion)
        self._thread_accounting.adaptive = None
        self._thread_accounting.adaptive_key = None
        self._thread_accounting.adaptive_corrected = False
        self._thread_accounting.stmt_sql = None
        # round 21: the fused-batch size that served this request (flight
        # record field; None = not batched)
        self._thread_accounting.batched_with = None
        self.access_control.check_can_execute_query(sess.user)
        qid = f"query_{next(_query_ids)}"
        group = self.resource_groups.get_or_create(f"global.{sess.user}")
        qsm = QueryStateMachine(qid, sql, sess.user, sess.catalog, group.full_name)
        self.query_tracker.register(qsm)
        self.event_listeners.query_created(qsm)
        admitted = threading.Event()
        queued_on_memory: list = []  # the ladder's per-query rung record
        self.resource_groups.submit(
            group, admitted.set,
            queued=lambda: qsm.transition(QueryState.WAITING_FOR_RESOURCES),
            queued_on_memory=lambda: queued_on_memory.append(1))
        admitted.wait()
        waits: dict = {}  # the statement's wait seconds by counter field
        try:
            qsm.transition(QueryState.DISPATCHING)
            # activate the engine tracer for this thread: executor dispatch
            # spans, prefetch-thread spans (explicit parent handoff), and
            # exchange segments all parent into this root span.  The query
            # scope tags this thread's in-flight registry entries and live
            # counters with the query id (the /v1/status "what is this query
            # doing right now" surface)
            with tracing.query_scope(qid), \
                    tracing.activate_tracer(self.tracer), \
                    tracing.statement_waits() as waits, \
                    self.tracer.span("query", trace_id=qid, sql=sql) as root:
                # CPU seconds of this thread under the root span: beside
                # its wall they tell computing from waiting
                cpu0 = time.thread_time()
                try:
                    # accepted -> the root span opens: the server's pool queue
                    # and statement lock, session set-up, admission
                    tracing.record_wait(
                        "server.queued", "queued_s",
                        max(time.perf_counter() - accepted[0], 0.0),
                        end_s=root.start_s, **accepted[1])
                    result = self._execute_admitted(
                        sql, sess, qsm, distributed, mesh, fault_tolerant,
                        parameters=parameters)
                finally:
                    waits["host_cpu_s"] = time.thread_time() - cpu0
                    root.attributes["cpu_s"] = round(waits["host_cpu_s"], 6)
            if result is not None and hasattr(result, "__len__"):
                qsm.rows = len(result)
            qsm.transition(QueryState.FINISHING)
            qsm.transition(QueryState.FINISHED)
            return result
        except BaseException as e:
            qsm.fail(str(e))
            # a corrected plan that FAILS is demoted immediately (the
            # advisor's negative-cache cooldown — r14 probation rule);
            # guarded: demotion bookkeeping never masks the real error
            try:
                if getattr(self._thread_accounting, "adaptive_corrected",
                           False):
                    akey = getattr(self._thread_accounting, "adaptive_key",
                                   None)
                    adv = getattr(self, "adaptive_advisor", None)
                    if adv is not None and akey is not None:
                        adv.failed(akey)
            except Exception:
                pass
            raise
        finally:
            # an errored statement must not leave its text for a later
            # direct execute_plan caller's history record to inherit
            self._thread_accounting.history_sql = None
            root = self._finish_query_trace(qid,
                                            queued_s=qsm.info().queued_s or 0)
            # boundary data for the completion event: only when THIS statement
            # executed a plan (DDL leaves the slot None)
            snap = self._thread_accounting.snap
            # the statement's waits, and the buckets of its wall breakdown as
            # wall_*_s seconds: stamped like admission_queued, since they
            # were measured outside the executor's counters context
            bd = self._thread_accounting.trace.get("wall_breakdown") or {}
            for bucket, field in _WALL_COUNTERS:
                if bd.get(bucket):
                    waits[field] = bd[bucket]
            with self._init_lock:
                for field, seconds in waits.items():
                    setattr(self.counters_total, field,
                            getattr(self.counters_total, field) + seconds)
            if snap is not None:
                for field, seconds in waits.items():
                    setattr(snap, field, getattr(snap, field) + seconds)
            if queued_on_memory:
                # the admission deferral happened BEFORE any counters context
                # existed: stamp it onto the per-query snapshot (the same
                # object last_query_counters holds) and the engine totals here
                with self._init_lock:
                    self.counters_total.admission_queued += 1
                if snap is not None:
                    snap.admission_queued += 1
            adec = getattr(self._thread_accounting, "adaptive", None)
            if adec is not None:
                # same pattern as admission_queued: the decision happened at
                # admission, before any counters context existed
                field = "adaptive_replans" if adec.get("verdict") == \
                    "replan" else "adaptive_holds"
                with self._init_lock:
                    setattr(self.counters_total, field,
                            getattr(self.counters_total, field) + 1)
                if snap is not None:
                    setattr(snap, field, getattr(snap, field) + 1)
            if snap is not None:
                qsm.counters = snap.as_dict()
            # the statement's own est-vs-actual record (round 15): what
            # QueryCompletedEvent.plan_actuals carries; None for DDL /
            # history-disabled / non-local paths
            qsm.plan_actuals = getattr(self._thread_accounting,
                                       "plan_actuals", None)
            qsm.root_span_duration_s = root.duration_s if root else None
            # flight record: completed AND errored statements both land in
            # the recorder (an errored query is when the record is most
            # wanted); guarded — recording failure never fails the statement
            try:
                self._record_flight(qsm, sess)
            except Exception:
                pass
            self.resource_groups.finish(group)
            self.event_listeners.query_completed(qsm)

    def _finish_query_trace(self, qid: str, queued_s: float = 0.0):
        """Publish the finished span tree as ``last_query_trace`` (span
        summaries — what GET /v1/query/{id}/trace surfaces)
        plus its wall-clock decomposition (``wall_breakdown``: root-span
        window attributed to named buckets — plan / split generation / h2d /
        device dispatch / host pull / exchange wait / admission queue /
        unattributed); returns the root span."""
        from .execution import tracing

        spans = self.tracer.spans_for(qid)
        root = next((s for s in spans
                     if s.parent_id is None and s.name == "query"), None)
        self.last_query_trace = {
            "query_id": qid,
            "root_span_s": root.duration_s if root else None,
            "spans": [tracing.span_dict(s) for s in spans]}
        bd = tracing.wall_breakdown(self.last_query_trace["spans"],
                                    queued_s=queued_s)
        if bd is not None:
            self.last_query_trace["wall_breakdown"] = bd
        # thread-local copy: last_query_trace is shared state a CONCURRENT
        # statement can overwrite (the server runs read statements in
        # parallel since round 12) — the statement's own thread reads this
        # slot for an attribution-safe capture
        self._thread_accounting.trace = self.last_query_trace
        self._thread_accounting.root = root
        return root

    def _record_flight(self, qsm, sess) -> None:
        """One flight record per finished statement (execute_sql's finally):
        everything already computed on this thread — the counters snapshot,
        the thread-local trace (span tree + wall breakdown), plan-actuals —
        plus the state machine's timings.  Host-only dict assembly, guarded
        by the caller: the recorder adds zero dispatches, zero pulls, and a
        failure never fails the statement."""
        fr = getattr(self, "flight_recorder", None)
        if fr is None or not fr.enabled:
            return
        from .execution.flightrecorder import pressure_rung as _flight_rung

        info = qsm.info()
        snap = self._thread_accounting.snap
        trace = getattr(self._thread_accounting, "trace", None) or {}
        try:
            sql = _normalize_statement(qsm.sql)
        except Exception:
            sql = qsm.sql
        # the statement's compile events from the census ring (host-side
        # list filter — guard discipline: zero _jit/_host traffic, and a
        # census miss just leaves the list empty).  COORDINATOR-LOCAL:
        # worker task bodies record under their task ids (and, multi-
        # process, in the worker's own COMPILE_LOG), so a distributed
        # statement's `compiles` COUNT (merged from worker counters) can
        # exceed len(compile_events) — the count is the cluster truth, the
        # events are this process's detail
        try:
            compile_events = self.compile_log.for_query(qsm.query_id) \
                if getattr(self, "compile_log", None) is not None else []
        except Exception:
            compile_events = []
        fr.record_query({
            "query_id": qsm.query_id,
            "state": info.state,
            "sql": sql,
            "user": info.user,
            "catalog": info.catalog,
            "error": info.error,
            "created_s": info.created_s,
            "ended_s": info.ended_s,
            "wall_s": info.wall_s,
            "queued_s": info.queued_s,
            "rows": info.rows,
            "counters": snap.as_dict() if snap is not None else None,
            "compiles": snap.compiles if snap is not None else None,
            "compile_s": round(snap.compile_s, 6)
            if snap is not None else None,
            "compile_events": compile_events,
            # round 20: per-shard attribution (worker load per exchange +
            # the cluster's per-task straggler record) — None when the
            # statement never crossed a mesh/cluster exchange
            "shard_stats": ([dict(r) for r in snap.shard_stats] or None)
            if snap is not None else None,
            "pressure_rung": _flight_rung(
                snap.as_dict() if snap is not None else None),
            "trace": {"root_span_s": trace.get("root_span_s"),
                      "spans": trace.get("spans") or []},
            "wall_breakdown": trace.get("wall_breakdown"),
            "plan_actuals": getattr(self._thread_accounting,
                                    "plan_actuals", None),
            # why this statement's plan changed (or didn't): the advisor's
            # admission-time decision with its win-vs-price arithmetic
            "adaptive": getattr(self._thread_accounting, "adaptive", None),
            # round 21: how many same-template requests shared this one's
            # fused device execution (None = served serially)
            "batched_with": getattr(self._thread_accounting, "batched_with",
                                    None),
            "stall_report": self.last_stall_report
            if self.health().get("status") == "stalled" else None,
        })

    def _execute_admitted(self, sql: str, sess: Session, qsm, distributed, mesh,
                          fault_tolerant=False, parameters=None):
        from .execution.query_state import QueryState
        from .sql import parser as A
        from .sql.frontend import Planner

        # user in the key: row filters / column masks make plans
        # user-specific (reference: access-control ViewExpressions);
        # plan-shaping session properties likewise.  The text is NORMALIZED
        # (comments stripped, whitespace collapsed) so trivially reformatted
        # repeats of one statement share a plan.
        key = (_normalize_statement(sql), sess.catalog, distributed,
               sess.user, _plan_shape_props(sess))
        # adaptive consult BEFORE the plan-cache lookup: a frozen "replan"
        # decision must divert even a warm statement to its corrected plan
        # (the corrected plan keys separately under the decision token)
        adaptive = self._adaptive_consult(key, sess) \
            if parameters is None and not distributed \
            and not fault_tolerant else None
        if adaptive is not None and adaptive.get("verdict") == "replan":
            res = self._execute_corrected(sql, key, adaptive, sess, qsm,
                                          mesh)
            if res is not NotImplemented:
                return res
            adaptive = None  # not a plannable SELECT after all
        if adaptive is not None:
            # a counted hold: material misestimate existed, the advisor
            # declined — visible in counters and the flight record
            self._thread_accounting.adaptive = adaptive
        if parameters is None:
            with self._plan_lock:
                plan = self._cache_lookup(key)
            if plan is not None:
                self._check_select_access(plan, sess.user)
                self._adaptive_note_base(key, sess)
                return self._run_select(sql, plan, sess, qsm, distributed,
                                        mesh, fault_tolerant)
        else:
            # protocol-level EXECUTE: ``sql`` carries ? markers, the values
            # arrived out of band.  Template path first; else substitute the
            # values as literals and run the ordinary statement flow.
            from .server.dbapi import _substitute
            from .sql import params as PRM

            param_asts = tuple(PRM.value_to_literal_ast(v)
                               for v in parameters)
            if not distributed and not fault_tolerant:
                handled, res = self._maybe_template_execute(
                    sql, param_asts, sess, qsm=qsm)
                if handled:
                    return res
            vals = [PRM.literal_param_value(p) for p in param_asts]
            return self._execute_admitted(_substitute(sql, vals), sess, qsm,
                                          distributed, mesh, fault_tolerant)
        if not distributed and not fault_tolerant \
                and self.plan_templates_enabled:
            # auto-parameterization: point-shaped ad-hoc SELECTs identical
            # up to constants normalize to one template — the literal
            # extraction is token-level and conservative (sql/params), and
            # any misjudgment fails template creation and falls through to
            # the ordinary path below
            from .sql import params as PRM

            ext = PRM.parameterize_text(sql)
            if ext is not None:
                handled, res = self._maybe_template_execute(
                    ext[0], ext[1], sess, qsm=qsm)
                if handled:
                    return res

        if qsm.state != QueryState.RUNNING:
            # a failed template probation already drove the machine to
            # RUNNING; transitions are unconditional sets, so re-entering
            # PLANNING here would REGRESS the visible state mid-statement
            qsm.transition(QueryState.PLANNING)
        ast = A.parse(sql)
        if isinstance(ast, (A.Select, A.SetOp)):
            with self.tracer.span("planner", trace_id=qsm.query_id):
                # planning + version snapshot are atomic w.r.t. dictionary
                # growth: a concurrent execution cannot grow a system
                # dictionary between LUT construction and _cache_store
                with self._plan_lock:
                    plan = Planner(self, sess).plan_query(ast)
                    self._cache_store(key, plan)
            self._check_select_access(plan, sess.user)
            if not distributed and not fault_tolerant:
                self._adaptive_note_base(key, sess)
            if qsm.state != QueryState.RUNNING:
                qsm.transition(QueryState.STARTING)
            return self._run_select(sql, plan, sess, qsm, distributed, mesh,
                                    fault_tolerant)
        qsm.transition(QueryState.RUNNING)
        # statement text for consumers that only see the AST (EXPLAIN
        # recovers its inner statement's plan-cache key from this)
        self._thread_accounting.stmt_sql = sql
        return self._execute_statement(ast, sess, distributed=distributed,
                                       mesh=mesh)

    def _adaptive_consult(self, key, sess, peek: bool = False):
        """Ask the advisor for this statement key's decision — None unless
        adaptive execution is on for the session AND history holds a
        material, actionable misestimate.  Guarded: the advisor is
        observability-adjacent machinery and must never fail a statement."""
        adv = getattr(self, "adaptive_advisor", None)
        if adv is None or not _effective_adaptive(sess):
            return None
        try:
            return adv.consult(key, peek=peek)
        except Exception:
            return None

    def _adaptive_note_base(self, key, sess) -> None:
        """Mark this execution as the statement's UNCORRECTED run: the
        feedback hook in _record_plan_history anchors the advisor's history
        address (base fingerprint), wall EWMA and observed compile price on
        these slots."""
        if getattr(self, "adaptive_advisor", None) is None \
                or not _effective_adaptive(sess):
            return
        self._thread_accounting.adaptive_key = key
        self._thread_accounting.adaptive_corrected = False

    def _execute_corrected(self, sql, key, adaptive, sess, qsm, mesh):
        """Run the statement on its CORRECTED plan: the frozen corrections
        ride the planner session as ``_adaptive_corrections`` (consumed by
        sql/exchanges.resolve_distributions — cardinality facts, forced
        capacities, Grace seeds), and the corrected plan keys under
        key + the correction token, so corrected and uncorrected plans
        never collide in the plan cache (nor, via the token-extended result
        key, in the result cache).  Returns NotImplemented when the
        statement turns out not to be a plannable SELECT — the caller falls
        through to the ordinary path."""
        from .execution.query_state import QueryState
        from .sql import parser as A
        from .sql.frontend import Planner

        akey = key + (("adaptive", adaptive["token"]),)
        with self._plan_lock:
            plan = self._cache_lookup(akey)
        if plan is None:
            if qsm.state != QueryState.RUNNING:
                qsm.transition(QueryState.PLANNING)
            ast = A.parse(sql)
            if not isinstance(ast, (A.Select, A.SetOp)):
                return NotImplemented
            corr_sess = _session_with_corrections(
                sess, adaptive.get("corrections") or {})
            with self.tracer.span("planner", trace_id=qsm.query_id):
                with self._plan_lock:
                    plan = Planner(self, corr_sess).plan_query(ast)
                    self._cache_store(akey, plan)
        self._check_select_access(plan, sess.user)
        self._thread_accounting.adaptive = adaptive
        self._thread_accounting.adaptive_key = key
        self._thread_accounting.adaptive_corrected = True
        if qsm.state != QueryState.RUNNING:
            qsm.transition(QueryState.STARTING)
        return self._run_select(sql, plan, sess, qsm, False, mesh, False,
                                adaptive=adaptive)

    def _run_select(self, sql, plan, sess, qsm, distributed, mesh,
                    fault_tolerant, adaptive=None):
        """Select execution shared by the warm (cached-plan) and cold
        (just-planned) branches: consult the result tier BEFORE the executor
        path (round 12 — a hit answers the statement with zero dispatches,
        zero executor checkout, zero host pulls, while the admission/state
        machinery already ran, so the statement is tracked, access-checked
        and listener-visible like any other), else execute and store on
        clean completion.  The cold path hits too: the fingerprint is
        structural, so ANOTHER session's identical statement (different SQL
        spacing, different default catalog resolving to the same tables)
        may already have populated the tier."""
        from .execution.query_state import QueryState

        rkey = self._result_cache_key(sql, plan, sess,
                                      distributed=distributed,
                                      fault_tolerant=fault_tolerant)
        if rkey is not None and adaptive is not None:
            # corrected executions key their results separately — a
            # demotion must be able to fall back to the uncorrected
            # entry, and vice versa, byte-identically
            rkey = rkey + (("adaptive", adaptive["token"]),)
        epoch = self.buffer_pool.epoch if rkey is not None else None
        if rkey is not None:
            served = self._result_cache_fetch(rkey)
            if served is not None:
                qsm.transition(QueryState.RUNNING)
                return served
        qsm.transition(QueryState.RUNNING)
        # statement text for the plan-history record (read+cleared one-shot
        # by _record_plan_history)
        self._thread_accounting.history_sql = sql
        # every select gets an execution span, so the span tree keeps its
        # root -> execution -> dispatches shape when the planner span is
        # skipped (warm statements)
        adv_batch = ((adaptive or {}).get("corrections") or {}) \
            .get("dispatch_batch")
        with self.tracer.span("execution", trace_id=qsm.query_id):
            result = self.execute_plan(
                plan, distributed=distributed, mesh=mesh,
                query_max_memory=_query_mem_limit(self, sess),
                fault_tolerant=fault_tolerant,
                dispatch_batch=int(adv_batch) if adv_batch
                else _effective_dispatch_batch(sess),
                page_cache=_effective_page_cache(sess))
        self._result_cache_finish(rkey, result, epoch=epoch)
        return result

    def _execute_statement(self, ast, sess: Session, distributed=False,
                           mesh=None):
        from .sql import parser as A

        if isinstance(ast, A.Prepare):
            # session-scoped prepared statements (reference: QueryPreparer +
            # Session.preparedStatements; parameters substitute at EXECUTE)
            sess.prepared[ast.name] = ast.sql
            return None
        if isinstance(ast, A.ExecutePrepared):
            return self._execute_prepared(ast, sess)
        if isinstance(ast, A.Deallocate):
            if ast.name not in sess.prepared:
                raise ValueError(f"prepared statement {ast.name} not found")
            del sess.prepared[ast.name]
            return None
        if isinstance(ast, A.Delete):
            return self._delete(ast, sess)
        if isinstance(ast, A.Update):
            return self._update(ast, sess)
        if isinstance(ast, A.Merge):
            return self._merge(ast, sess)
        if isinstance(ast, A.SetSession):
            self.access_control.check_can_set_session_property(sess.user, ast.name)
            self.session_properties.set_property(sess, ast.name, ast.value)
            return None
        if isinstance(ast, A.ResetSession):
            self.session_properties.reset_property(sess, ast.name)
            return None
        if isinstance(ast, A.Show):
            return self._show(ast, sess)
        if isinstance(ast, A.CreateView):
            if not ast.or_replace and ast.name in self.views:
                raise ValueError(f"view {ast.name} already exists")
            self.views[ast.name] = ((), ast.query)
            self._invalidate()
            return None
        if isinstance(ast, A.CreateFunction):
            # inline SQL routine (reference: CreateFunctionTask + sql/routine/
            # SqlRoutineCompiler — an expression body inlines at call sites)
            from .sql import functions as FN
            from .sql.frontend import AGG_FUNCS, Planner

            if FN.lookup(ast.name) is not None or ast.name in AGG_FUNCS \
                    or ast.name in Planner._COLLECTION_FUNCS:
                # built-ins resolve first at call sites, so a shadowing
                # routine would be silently dead — reject it up front
                raise ValueError(
                    f"function {ast.name} conflicts with a built-in")
            if not ast.or_replace and ast.name in self.sql_routines:
                raise ValueError(f"function {ast.name} already exists")
            self.sql_routines[ast.name] = (ast.params, ast.return_type,
                                           ast.body)
            self._invalidate()  # cached plans may now resolve differently
            return None
        if isinstance(ast, A.DropFunction):
            if ast.name not in self.sql_routines and not ast.if_exists:
                raise ValueError(f"function {ast.name} does not exist")
            self.sql_routines.pop(ast.name, None)
            self._invalidate()
            return None
        if isinstance(ast, A.DropView):
            if ast.name not in self.views and not ast.if_exists:
                raise ValueError(f"view {ast.name} does not exist")
            self.views.pop(ast.name, None)
            self._invalidate()
            return None
        if isinstance(ast, A.Explain):
            from .sql import parser as AP

            if isinstance(ast.query, AP.ExecutePrepared):
                return self._explain_execute(ast, sess)
            if not isinstance(ast.query, (AP.Select, AP.SetOp)):
                raise ValueError(
                    f"EXPLAIN of {type(ast.query).__name__} is not supported")
            return self._explain(ast, sess, distributed=distributed, mesh=mesh)
        if isinstance(ast, A.CreateTable):
            return self._create_table(ast, sess)
        if isinstance(ast, A.InsertInto):
            return self._insert(ast, sess)
        if isinstance(ast, A.CreateMaterializedView):
            return self._create_materialized_view(ast, sess)
        if isinstance(ast, A.RefreshMaterializedView):
            return self._refresh_materialized_view(ast, sess)
        if isinstance(ast, A.DropMaterializedView):
            mv = self.materialized_views.get(ast.name)
            if mv is None:
                if ast.if_exists:
                    return None
                raise ValueError(f"materialized view {ast.name} does not exist")
            self.access_control.check_can_write(sess.user, mv["catalog"],
                                                ast.name, "drop table")
            del self.materialized_views[ast.name]
            self.catalogs[mv["catalog"]].drop_table(mv["storage"], True)
            self._invalidate()
            return None
        if isinstance(ast, A.Grant):
            conn = None
            cat = sess.catalog
            if cat in self.catalogs and ast.table in self.catalogs[cat].tables():
                conn = self.catalogs[cat]
            else:
                for name, c in self.catalogs.items():
                    if ast.table in c.tables():
                        cat, conn = name, c
                        break
            if conn is None:
                raise ValueError(f"table {ast.table!r} not found")
            op = self.access_control.revoke if ast.revoke \
                else self.access_control.grant
            try:
                op(sess.user, ast.grantee, cat, ast.table,
                   set(ast.privileges))
            except NotImplementedError as e:
                raise ValueError(str(e))
            return None
        if isinstance(ast, A.DropTable):
            conn = self._writable_catalog(sess)
            self.access_control.check_can_write(
                sess.user, self._catalog_name_of(conn), ast.name, "drop table")
            conn.drop_table(ast.name, ast.if_exists)
            self._invalidate()
            return None
        raise ValueError(f"unsupported statement {type(ast).__name__}")

    def _execute_prepared(self, ast, sess: Session):
        from .server.dbapi import _substitute
        from .sql import params as PRM

        text = sess.prepared.get(ast.name)
        if text is None:
            raise ValueError(f"prepared statement {ast.name} not found")
        # plan-template path: a warm EXECUTE re-dispatches the compiled plan
        # with the bound values as runtime inputs — zero parse/analyze/plan
        # work, zero re-compilation
        handled, result = self._maybe_template_execute(
            text, tuple(ast.parameters), sess)
        if handled:
            return result
        # substitution fallback (unbindable template, impossible binding, or
        # non-SELECT prepared statement): literal extraction raises a typed
        # error for unsupported parameter AST kinds (sql/params) instead of
        # silently mis-substituting
        vals = [PRM.literal_param_value(p) for p in ast.parameters]
        sql = _substitute(text, vals)
        return self._run_sql_inline(sql, sess)

    def _run_sql_inline(self, sql: str, sess: Session):
        """Run a statement without re-entering admission (EXECUTE runs inside the
        outer statement's resource-group slot)."""
        from .sql import parser as A
        from .sql.frontend import Planner

        key = (_normalize_statement(sql), sess.catalog, False, sess.user,
               _plan_shape_props(sess))
        with self._plan_lock:
            plan = self._cache_lookup(key)
            if plan is None:
                ast = A.parse(sql)
                if not isinstance(ast, (A.Select, A.SetOp)):
                    return self._execute_statement(ast, sess)
                plan = Planner(self, sess).plan_query(ast)
                self._cache_store(key, plan)
        return self.execute_plan(plan)

    # -- row-level DML (reference: spi/connector/ConnectorMergeSink — DELETE and
    # UPDATE funnel through the merge surface; here the engine evaluates the
    # per-row predicate/assignments and the connector applies them) -------------
    def _eval_rowwise(self, table: str, exprs, sess: Session):
        """Evaluate expressions per row of `table` in storage order."""
        from .sql import parser as A
        from .sql.frontend import Planner

        items = tuple(A.SelectItem(e, f"c{i}") for i, e in enumerate(exprs))
        sel = A.Select(items=items, from_=A.TableRef((table,), None), where=None,
                       group_by=(), having=None, order_by=(), limit=None)
        plan = Planner(self, sess).plan_query(sel)
        return self.execute_plan(plan, cache=False)

    def _dml_target(self, table: str, sess: Session):
        conn = self._writable_catalog(sess)
        if table not in conn.tables():
            raise ValueError(f"table {table} does not exist")
        return conn

    @staticmethod
    def _dml_mask(col):
        import numpy as np

        return np.array([bool(v) if v is not None else False for v in list(col)])

    def _delete(self, ast, sess: Session):
        from .sql import parser as A

        conn = self._dml_target(ast.table, sess)
        self.access_control.check_can_write(
            sess.user, self._catalog_name_of(conn), ast.table, "delete from")
        res = self._eval_rowwise(ast.table, [ast.where or A.BoolLit(True)], sess)
        n = conn.delete_rows(ast.table, self._dml_mask(res.columns[0]))
        self._invalidate()
        return _dml_count_result(n)

    def _update(self, ast, sess: Session):
        from .sql import parser as A

        conn = self._dml_target(ast.table, sess)
        self.access_control.check_can_write(
            sess.user, self._catalog_name_of(conn), ast.table, "update")
        schema = conn.schema(ast.table)
        # constant string/NULL assignments bypass row-wise planning (the device
        # page model has no free-standing string projections — strings live as
        # dictionary ids bound to a column)
        const_assigns, expr_assigns = {}, []
        for col, e in ast.assignments:
            f = schema.field(col)  # raises on unknown column
            if isinstance(e, A.StringLit):
                const_assigns[col] = e.value
            elif isinstance(e, A.NullLit):
                const_assigns[col] = None
            else:
                base = f.type.name.split("(")[0]
                params = ()
                if "(" in f.type.name:
                    params = tuple(int(x) for x in
                                   f.type.name[f.type.name.index("(") + 1:-1]
                                   .split(","))
                expr_assigns.append((col, A.Cast(e, base, params)))
        exprs = [ast.where or A.BoolLit(True)] + [e for _, e in expr_assigns]
        res = self._eval_rowwise(ast.table, exprs, sess)
        mask = self._dml_mask(res.columns[0])
        n = len(mask)
        values = {col: [v] * n for col, v in const_assigns.items()}
        for j, (col, _) in enumerate(expr_assigns):
            t = res.types[j + 1]
            decoded = list(res.columns[j + 1])
            raw = list(res.raw_columns[j + 1])
            if t.is_string:
                values[col] = decoded
            else:
                values[col] = [None if d is None else r
                               for d, r in zip(decoded, raw)]
        n = conn.update_rows(ast.table, mask, values)
        self._invalidate()
        return _dml_count_result(n)

    def _merge(self, ast, sess: Session):
        """MERGE INTO: match target rows against a source relation on equi-keys,
        then apply WHEN MATCHED updates/deletes and WHEN NOT MATCHED inserts.

        Reference: sql/tree/Merge planned through MergeWriterOperator's
        RowChangeOperations (a right join + case routing).  Here the pairing is
        hashed on the coordinator and each clause's conditions/assignments are
        evaluated by the ENGINE ITSELF over scratch memory-connector tables
        (one row per target row with the matched source row beside it, one row
        per unmatched source row) — MERGE compiles onto the same device
        expression pipeline every SELECT uses."""
        from .connectors.memory import MemoryConnector
        from .page import Field, Schema
        from .sql import parser as A

        conn = self._dml_target(ast.target, sess)
        self.access_control.check_can_write(
            sess.user, self._catalog_name_of(conn), ast.target, "merge into")
        tgt_schema = conn.schema(ast.target)
        tgt_cols = list(tgt_schema.names)

        # ---- materialize target + source relations (decoded + raw)
        tgt_res = self._eval_rowwise(
            ast.target, [A.Identifier((c,)) for c in tgt_cols], sess)
        if isinstance(ast.source, str):
            if ast.source not in conn.tables():
                raise ValueError(f"table {ast.source} does not exist")
            self.access_control.check_can_select(
                sess.user, self._catalog_name_of(conn), ast.source)
            src_cols = list(conn.schema(ast.source).names)
            src_res = self._eval_rowwise(
                ast.source, [A.Identifier((c,)) for c in src_cols], sess)
        else:
            from .sql.frontend import Planner

            plan = Planner(self, sess).plan_query(ast.source)
            self._check_select_access(plan, sess.user)
            src_res = self.execute_plan(plan, cache=False)
            src_cols = list(src_res.names)

        # ---- equi-key pairs from the ON condition
        def conjuncts(e):
            if isinstance(e, A.BinaryOp) and e.op == "and":
                return conjuncts(e.left) + conjuncts(e.right)
            return [e]

        def classify(ident):
            parts = ident.parts
            if len(parts) == 2:
                alias, c = parts
                if alias == ast.target_alias and c in tgt_cols:
                    return ("t", c)
                if alias == ast.source_alias and c in src_cols:
                    return ("s", c)
                raise ValueError(f"MERGE: cannot resolve {'.'.join(parts)}")
            c = parts[0]
            in_t, in_s = c in tgt_cols, c in src_cols
            if in_t and in_s:
                raise ValueError(f"MERGE: column {c} is ambiguous")
            if in_t:
                return ("t", c)
            if in_s:
                return ("s", c)
            raise ValueError(f"MERGE: unknown column {c}")

        keys = []
        for cj in conjuncts(ast.on):
            if not (isinstance(cj, A.BinaryOp) and cj.op == "eq"
                    and isinstance(cj.left, A.Identifier)
                    and isinstance(cj.right, A.Identifier)):
                raise ValueError(
                    "MERGE ON must be a conjunction of column equalities")
            a, b = classify(cj.left), classify(cj.right)
            if a[0] == b[0]:
                raise ValueError("MERGE ON equality must pair target with source")
            keys.append((a[1], b[1]) if a[0] == "t" else (b[1], a[1]))

        def col_values(res, names, cols):
            from .types import ArrayType

            out = {}
            for c in cols:
                i = names.index(c)
                t = res.types[i]
                dec = list(res.columns[i])
                if t.is_string or isinstance(t, ArrayType):
                    # decoded forms re-encode cleanly (raw span ints would
                    # point into a heap the scratch table doesn't have)
                    out[c] = dec
                else:
                    raw = list(res.raw_columns[i])
                    out[c] = [None if d is None else r
                              for d, r in zip(dec, raw)]
            return out

        def key_values(res, names, cols):
            """DECODED values for ON-key comparison: raw storage reprs differ
            across decimal scales (1.00 stores as 100 at scale 2, 10 at scale
            1), so logically-equal keys must compare post-decode.  Python
            numeric equality/hashing already treats 1 == 1.0 exactly, so no
            float flattening (which would collide int64 keys past 2^53)."""
            return {c: list(res.columns[names.index(c)]) for c in cols}

        tgt_vals = col_values(tgt_res, tgt_cols, tgt_cols)
        src_vals = col_values(src_res, src_cols, src_cols)
        tgt_keys = key_values(tgt_res, tgt_cols, [tc for tc, _ in keys])
        src_keys = key_values(src_res, src_cols, [sc for _, sc in keys])
        n_tgt = len(tgt_vals[tgt_cols[0]]) if tgt_cols else 0
        n_src = len(src_vals[src_cols[0]]) if src_cols else 0

        src_index: dict = {}
        for j in range(n_src):
            k = tuple(src_keys[sc][j] for _, sc in keys)
            if any(v is None for v in k):
                continue  # NULL keys never match
            src_index.setdefault(k, []).append(j)

        match_of = [None] * n_tgt
        src_matched = [False] * n_src
        for i in range(n_tgt):
            k = tuple(tgt_keys[tc][i] for tc, _ in keys)
            if any(v is None for v in k):
                continue
            js = src_index.get(k)
            if js is None:
                continue
            if len(js) > 1:
                raise ValueError(
                    "MERGE: one target row matched more than one source row")
            match_of[i] = js[0]
            src_matched[js[0]] = True

        # ---- scratch relations the engine evaluates clause exprs over
        import numpy as np

        scratch = MemoryConnector()
        pairs_fields = [Field(f"t_{c}", tgt_schema.field(c).type) for c in tgt_cols] \
            + [Field(f"s_{c}", src_res.types[src_cols.index(c)]) for c in src_cols]
        scratch.create_table("pairs", Schema(tuple(pairs_fields)))
        pair_cols = [list(tgt_vals[c]) for c in tgt_cols]
        for c in src_cols:
            sv = src_vals[c]
            pair_cols.append([None if match_of[i] is None else sv[match_of[i]]
                              for i in range(n_tgt)])
        if n_tgt:
            scratch.append("pairs", pair_cols)
        src_fields = [Field(f"s_{c}", src_res.types[src_cols.index(c)])
                      for c in src_cols]
        scratch.create_table("src", Schema(tuple(src_fields)))
        if n_src:
            scratch.append("src", [list(src_vals[c]) for c in src_cols])

        import uuid

        # unique per statement: the engine admits queries concurrently, and a
        # shared name would let one MERGE read another's scratch rows
        cat_name = f"__merge_scratch_{uuid.uuid4().hex[:12]}__"
        self.catalogs[cat_name] = scratch
        scratch_sess = dataclasses.replace(sess, catalog=cat_name)
        try:
            self._invalidate()

            def rewrite(e, in_matched):
                if isinstance(e, A.Identifier):
                    side, c = classify(e)
                    if side == "t" and not in_matched:
                        raise ValueError(
                            "WHEN NOT MATCHED cannot reference the target")
                    return A.Identifier(((f"{side}_{c}"),))
                if dataclasses.is_dataclass(e) and not isinstance(e, type):
                    ch = {}
                    for f in dataclasses.fields(e):
                        v = getattr(e, f.name)
                        nv = rewrite_any(v, in_matched)
                        if nv is not v:
                            ch[f.name] = nv
                    return dataclasses.replace(e, **ch) if ch else e
                return e

            def rewrite_any(v, in_matched):
                if isinstance(v, tuple):
                    return tuple(rewrite_any(x, in_matched) for x in v)
                if dataclasses.is_dataclass(v) and not isinstance(v, type):
                    return rewrite(v, in_matched)
                return v

            for cl in ast.clauses:  # arity errors must precede ANY mutation
                if cl.action == "insert":
                    names = list(cl.columns) or tgt_cols
                    if len(cl.values) != len(names):
                        raise ValueError(
                            f"MERGE INSERT has {len(names)} columns but "
                            f"{len(cl.values)} values")

            matched_np = np.array([m is not None for m in match_of], bool)
            remaining = matched_np.copy()
            delete_mask = np.zeros(n_tgt, bool)
            update_ops = []  # (mask, values) applied after clause routing
            src_remaining = np.array(
                [not m for m in src_matched], bool)
            inserts = []  # (mask, clause)
            for cl in ast.clauses:
                if cl.matched:
                    if not remaining.any():
                        continue
                    if cl.condition is not None:
                        cres = self._eval_rowwise(
                            "pairs", [rewrite(cl.condition, True)], scratch_sess)
                        cm = self._dml_mask(cres.columns[0])
                    else:
                        cm = np.ones(n_tgt, bool)
                    mask = remaining & cm
                    remaining &= ~mask
                    if cl.action == "delete":
                        delete_mask |= mask
                    else:
                        update_ops.append((mask, cl))
                else:
                    if cl.condition is not None and n_src:
                        cres = self._eval_rowwise(
                            "src", [rewrite(cl.condition, False)], scratch_sess)
                        cm = self._dml_mask(cres.columns[0])
                    else:
                        cm = np.ones(n_src, bool)
                    mask = src_remaining & cm
                    src_remaining &= ~mask
                    inserts.append((mask, cl))

            # ---- apply updates (row count unchanged), then deletes, then inserts
            for mask, cl in update_ops:
                if not mask.any():
                    continue
                values = {}
                expr_cols, exprs = [], []
                for col, e in cl.assignments:
                    f = tgt_schema.field(col)
                    if isinstance(e, A.StringLit):
                        values[col] = [e.value] * n_tgt
                        continue
                    if isinstance(e, A.NullLit):
                        values[col] = [None] * n_tgt
                        continue
                    base = f.type.name.split("(")[0]
                    params = ()
                    if "(" in f.type.name:
                        params = tuple(
                            int(x) for x in
                            f.type.name[f.type.name.index("(") + 1:-1].split(","))
                    expr_cols.append(col)
                    exprs.append(A.Cast(rewrite(e, True), base, params))
                if exprs:  # one scan evaluates every assignment of the clause
                    vres = self._eval_rowwise("pairs", exprs, scratch_sess)
                    for j, col in enumerate(expr_cols):
                        t = vres.types[j]
                        dec = list(vres.columns[j])
                        if t.is_string:
                            values[col] = dec
                        else:
                            raw = list(vres.raw_columns[j])
                            values[col] = [None if d is None else r
                                           for d, r in zip(dec, raw)]
                conn.update_rows(ast.target, mask, values)
            if delete_mask.any():
                conn.delete_rows(ast.target, delete_mask)
            for mask, cl in inserts:
                if not mask.any():
                    continue
                names = list(cl.columns) or tgt_cols
                vres = self._eval_rowwise(
                    "src", [rewrite(e, False) for e in cl.values], scratch_sess)
                cols_by_name = {}
                for j, nm in enumerate(names):
                    f = tgt_schema.field(nm)
                    vals = [v for v, keep in
                            zip(list(vres.columns[j]), mask) if keep]
                    cols_by_name[nm] = _coerce_host(vals, vres.types[j], f.type)
                n_ins = int(mask.sum())
                conn.append(ast.target,
                            [cols_by_name.get(f.name, [None] * n_ins)
                             for f in tgt_schema.fields])
        finally:
            del self.catalogs[cat_name]
            self._invalidate()
        return None

    def _show(self, ast, sess: Session):
        """SHOW SESSION/CATALOGS/TABLES/COLUMNS/FUNCTIONS (reference: the
        ShowQueriesRewrite-backed SHOW statements over information_schema)."""
        import numpy as np

        from .types import VarcharType

        def result(names, rows):
            cols = [np.array([r[i] for r in rows], dtype=object)
                    for i in range(len(names))]
            return MaterializedResult(tuple(names),
                                      tuple(VarcharType.of(None) for _ in names),
                                      cols, cols)

        if ast.what == "session":
            return result(("name", "value", "default", "type", "description"),
                          self.session_properties.rows(sess))
        if ast.what == "catalogs":
            return result(("catalog",), [(c,) for c in sorted(self.catalogs)])
        if ast.what == "tables":
            conn = self.catalogs.get(sess.catalog)
            if conn is None:
                raise ValueError(f"catalog {sess.catalog!r} not found")
            names = sorted((set(conn.tables()) | set(self.views)
                            | set(self.materialized_views))
                           - {m["storage"] for m in
                              self.materialized_views.values()})
            names = self.access_control.filter_tables(sess.user, sess.catalog,
                                                      names)
            return result(("table",), [(t,) for t in names])
        if ast.what == "schemas":
            return result(("schema",),
                          [(n,) for n in sorted(self.catalogs)])
        if ast.what == "columns":
            conn = self.catalogs.get(sess.catalog)
            if conn is None or ast.target not in conn.tables():
                raise ValueError(f"table {ast.target!r} not found")
            schema = conn.schema(ast.target)
            return result(("column", "type"),
                          [(f.name, f.type.name) for f in schema.fields])
        if ast.what == "stats":
            # reference: SHOW STATS FOR table — the same TableStats the CBO
            # consumes (spi/statistics.py), per-column rows + summary row
            from .spi.statistics import connector_table_stats

            conn = None
            for c in self.catalogs.values():
                if ast.target in c.tables():
                    conn = c
                    break
            if sess.catalog in self.catalogs and \
                    ast.target in self.catalogs[sess.catalog].tables():
                conn = self.catalogs[sess.catalog]
            if conn is None:
                raise ValueError(f"table {ast.target!r} not found")
            ts = connector_table_stats(conn, ast.target)
            fmt = lambda v: "" if v is None else (
                str(int(v)) if float(v).is_integer() else str(v))
            rows = []
            for f in conn.schema(ast.target).fields:
                cs = ts.column(f.name)
                rows.append((f.name, None if cs.ndv is None else fmt(cs.ndv),
                             fmt(cs.lo), fmt(cs.hi), ""))
            rows.append(("", None, "", "", fmt(ts.row_count)))
            return result(("column_name", "distinct_values_count", "low_value",
                           "high_value", "row_count"), rows)
        if ast.what == "create_table":
            # reference: ShowQueriesRewrite SHOW CREATE TABLE -> the DDL the
            # metadata reconstructs
            conn = None
            cat = sess.catalog
            if cat in self.catalogs and ast.target in self.catalogs[cat].tables():
                conn = self.catalogs[cat]
            else:
                for name, c in self.catalogs.items():
                    if ast.target in c.tables():
                        cat, conn = name, c
                        break
            if conn is None:
                raise ValueError(f"table {ast.target!r} not found")
            cols = ",\n".join(f"   {f.name} {f.type.name}"
                               for f in conn.schema(ast.target).fields)
            ddl = f"CREATE TABLE {cat}.{ast.target} (\n{cols}\n)"
            return result(("create table",), [(ddl,)])
        if ast.what == "functions":
            # one source of truth: the function registry (reference: SHOW
            # FUNCTIONS over the catalog SystemFunctionBundle registers)
            from .sql import functions as FN

            FN.ensure_legacy_registered()
            rows = FN.catalog_rows()
            for name, (params, rt, _body) in sorted(self.sql_routines.items()):
                rows.append((name, "routine", str(len(params)),
                             f"SQL routine returning {rt[0]}"))
            return result(("function", "category", "arity", "description"),
                          rows)
        raise ValueError(f"unsupported SHOW {ast.what!r}")

    # -- DDL/DML -----------------------------------------------------------------
    def _writable_catalog(self, sess: Session):
        cand = self.catalogs.get(sess.catalog)
        if cand is not None and hasattr(cand, "create_table"):
            return cand
        for c in self.catalogs.values():
            if hasattr(c, "create_table"):
                return c
        raise ValueError("no writable catalog registered (register a MemoryConnector)")

    def _explain(self, ast, sess: Session, distributed=False, mesh=None):
        import time

        import numpy as np

        from .sql.frontend import Planner
        from .sql.planprinter import format_plan
        from .types import VarcharType

        plan = Planner(self, sess).plan_query(ast.query)
        self._check_select_access(plan, sess.user)  # ANALYZE executes the query
        # the adaptive advisor's view of the INNER statement: recover its
        # plan-cache key from the statement text (the advisor keys on it),
        # consult read-only for plain EXPLAIN (peek — no state transition),
        # fully for ANALYZE (which executes like any other statement)
        decision, akey = None, None
        stmt = getattr(self._thread_accounting, "stmt_sql", None)
        if stmt is not None and not distributed:
            m = re.match(r"\s*EXPLAIN\s+(?:ANALYZE\s+)?", stmt, re.IGNORECASE)
            if m:
                akey = (_normalize_statement(stmt[m.end():]), sess.catalog,
                        False, sess.user, _plan_shape_props(sess))
                decision = self._adaptive_consult(akey, sess,
                                                  peek=not ast.analyze)
        if ast.analyze and distributed:
            # run through the mesh executor and report each fragment's ACTUAL
            # execution mode — mesh / coordinator / local with the fallback
            # reason (VERDICT r3 weak #3: fallbacks must be visible, reference:
            # sql/planner/planprinter fragment output)
            with self._mesh_executor(mesh) as ex:
                t0 = time.perf_counter()
                try:
                    res = ex.execute(plan)
                finally:
                    # this plan was made for the EXPLAIN and is never replayed
                    ex.forget_plan(plan)
                dt = time.perf_counter() - t0
                self._account_counters(ex.counters)
                self._record_shard_skew(plan, ex)
                lines = format_plan(plan, counters=ex.counters,
                                    skew=ex.skew_by_node).split("\n")
                lines += ["", "Fragment execution (distributed run):"]
                for label, mode, reason in ex.exec_trace:
                    note = f" — {reason}" if reason else ""
                    lines += [f"  [{mode}] {label}{note}"]
            lines += [f"-- executed in {dt * 1000:.1f} ms, {len(res)} output rows"]
        elif ast.analyze:
            if decision is not None and decision.get("verdict") == "replan":
                # ANALYZE executes what the serving path would: the frozen
                # corrections re-plan here too, and the feedback slots mark
                # this execution corrected for the advisor's observe hook
                plan = Planner(self, _session_with_corrections(
                    sess, decision.get("corrections") or {})) \
                    .plan_query(ast.query)
                self._check_select_access(plan, sess.user)
                self._thread_accounting.adaptive = decision
                self._thread_accounting.adaptive_key = akey
                self._thread_accounting.adaptive_corrected = True
            elif akey is not None:
                if decision is not None:
                    self._thread_accounting.adaptive = decision
                self._adaptive_note_base(akey, sess)
            # one-shot plan: a throwaway executor keeps its compiled state
            # collectable; it still reads/feeds the shared buffer pool and
            # honors the session's page_cache override like every other path
            ex = LocalExecutor(self.catalogs, buffer_pool=self.buffer_pool)
            ex.page_cache = _effective_page_cache(sess)
            t0 = time.perf_counter()
            t0_wall = time.time()
            res = ex.execute(plan)
            dt = time.perf_counter() - t0
            t1_wall = time.time()
            # an ANALYZE run is a clean completion like any other: it feeds
            # the plan-history store (before the accounting: its pull of the
            # row counters is the statement's), and the plan print carries
            # the per-node est-vs-actual annotations + "Misestimates:" summary
            # from the same begin_plan maps the executor stamped
            self._record_plan_history(plan, ex)
            self._account_counters(ex.counters)
            lines = format_plan(plan, ex.stats, counters=ex.counters,
                                boundary=ex.boundary, ests=ex._node_ests,
                                paths=ex._node_paths,
                                breakdown=self._analyze_breakdown(
                                    t0_wall, t1_wall),
                                adaptive=decision).split("\n")
            lines += [f"-- executed in {dt * 1000:.1f} ms, {len(res)} output rows"]
        else:
            # plain EXPLAIN renders the PHYSICAL plan: Exchange markers show
            # the distribution pass's chosen placement (AddExchanges surface)
            from .sql.exchanges import physical_plan

            props = getattr(sess, "properties", None)
            if decision is not None and decision.get("verdict") == "replan":
                # a frozen correction means the next execution runs the
                # corrected plan — EXPLAIN must show that plan, not the
                # uncorrected one
                props = dict(props or {})
                props["_adaptive_corrections"] = \
                    decision.get("corrections") or {}
            phys = physical_plan(plan, self.catalogs, props)
            lines = format_plan(phys, adaptive=decision).split("\n")
        rows = np.array(lines, dtype=object)
        return MaterializedResult(("query plan",), (VarcharType.of(None),),
                                  [rows], [rows])

    def _analyze_breakdown(self, t0_wall: float, t1_wall: float):
        """Wall decomposition of an EXPLAIN ANALYZE execution window from the
        spans the run just emitted (the statement's tracer is active — the
        dispatch/host_pull/generation spans are already in the tree).  None
        when no query scope is active (bare-executor drivers)."""
        from .execution import tracing

        qid = tracing.current_query_id()
        if qid is None:
            return None
        try:
            spans = [tracing.span_dict(s) for s in self.tracer.spans_for(qid)]
            return tracing.wall_breakdown(spans, window=(t0_wall, t1_wall))
        except Exception:
            return None

    def _explain_execute(self, ast, sess: Session):
        """EXPLAIN [ANALYZE] EXECUTE name [USING ...]: renders the statement's
        template disposition — the template plan when one exists (ANALYZE runs
        it and the counters line shows the hit), or the substitution verdict.
        The 'Plan template:' line is the observable the serving path exposes."""
        import time

        import numpy as np

        from .sql.planprinter import format_plan
        from .types import VarcharType

        ex_ast = ast.query
        text = sess.prepared.get(ex_ast.name)
        if text is None:
            raise ValueError(f"prepared statement {ex_ast.name} not found")
        key = self._template_key(text, sess)
        lines: list = []
        if ast.analyze:
            t0 = time.perf_counter()
            res = self._execute_prepared(ex_ast, sess)
            dt = time.perf_counter() - t0
            snap = self._thread_accounting.snap
            with self._plan_lock:
                tpl = self._template_lookup(key)
            if isinstance(tpl, _PlanTemplate):
                lines = format_plan(tpl.plan, counters=snap).split("\n")
            else:
                lines = ["Plan template: unbindable — substitution path"]
            lines += [f"-- executed in {dt * 1000:.1f} ms, "
                      f"{len(res) if res is not None else 0} output rows"]
        else:
            with self._plan_lock:
                tpl = self._template_lookup(key)
            if isinstance(tpl, _PlanTemplate):
                lines = format_plan(tpl.plan).split("\n")
                lines.append(
                    f"Plan template: cached, {len(tpl.binders)} bound "
                    f"slot(s) over {tpl.n_params} parameter(s)")
            elif tpl is _TEMPLATE_UNBINDABLE:
                lines = ["Plan template: unbindable — substitution path"]
            else:
                lines = ["Plan template: not yet created "
                         "(first EXECUTE plans it)"]
        rows = np.array(lines, dtype=object)
        return MaterializedResult(("query plan",), (VarcharType.of(None),),
                                  [rows], [rows])

    def _create_table(self, ast, sess: Session):
        from .page import Field, Schema
        from .sql.frontend import Planner, _type_from_name

        conn = self._writable_catalog(sess)
        self.access_control.check_can_write(
            sess.user, self._catalog_name_of(conn), ast.name, "create table")
        if ast.as_query is not None:  # CTAS
            src_plan = Planner(self, sess).plan_query(ast.as_query)
            self._check_select_access(src_plan, sess.user)
            res = self.execute_plan(src_plan, cache=False)
            schema = Schema(tuple(Field(n, t) for n, t in zip(res.names, res.types)))
            created = conn.create_table(ast.name, schema, ast.if_not_exists)
            if created is not False:  # IF NOT EXISTS on an existing table: skip insert
                conn.append(ast.name, [_coerce_host(c.tolist(), t, t)
                                       for c, t in zip(res.columns, res.types)])
        else:
            fields = tuple(Field(n, _type_from_name(tn, params))
                           for n, tn, params in ast.columns)
            props = dict(getattr(ast, "properties", ()) or ())
            part = props.pop("partitioned_by", None)
            if props:
                raise ValueError(
                    f"unsupported table properties: {sorted(props)}")
            if part is not None and not isinstance(part, (tuple, list)):
                raise ValueError(
                    "partitioned_by must be ARRAY['col', ...]")
            if part is not None:
                import inspect

                if "partitioned_by" not in inspect.signature(
                        conn.create_table).parameters:
                    raise ValueError(
                        "this catalog does not support partitioned_by")
                conn.create_table(ast.name, Schema(fields),
                                  partitioned_by=tuple(part),
                                  if_not_exists=ast.if_not_exists)
            else:
                conn.create_table(ast.name, Schema(fields),
                                  if_not_exists=ast.if_not_exists)
        self._invalidate()
        return None

    def _create_materialized_view(self, ast, sess: Session):
        """Materialize the query into a storage table in the writable catalog
        and record the definition (reference: CreateMaterializedViewTask —
        definition + storage table; queries read the STORAGE table, so results
        are as of the last refresh)."""
        from .page import Field, Schema
        from .sql.frontend import Planner

        existing = self.materialized_views.get(ast.name)
        if existing is not None and not ast.or_replace:
            raise ValueError(f"materialized view {ast.name} already exists")
        if existing is not None:
            # replace/refresh stays in the DEFINING catalog: a refresher's
            # session catalog must not relocate the storage table or
            # re-resolve the definition against different tables
            conn = self.catalogs[existing["catalog"]]
            cat = existing["catalog"]
        else:
            conn = self._writable_catalog(sess)
            cat = self._catalog_name_of(conn)
        storage = f"__mv_{ast.name}"
        self.access_control.check_can_write(sess.user, cat, ast.name,
                                            "create table")
        src_plan = Planner(self, sess).plan_query(ast.query)
        self._check_select_access(src_plan, sess.user)
        res = self.execute_plan(src_plan, cache=False)
        conn.drop_table(storage, True)
        schema = Schema(tuple(Field(n, t) for n, t in zip(res.names, res.types)))
        conn.create_table(storage, schema)
        conn.append(storage, [_coerce_host(c.tolist(), t, t)
                              for c, t in zip(res.columns, res.types)])
        self.materialized_views[ast.name] = {
            "query": ast.query, "storage": storage, "catalog": cat,
            "session_catalog": sess.catalog}
        self._invalidate()
        return None

    def _refresh_materialized_view(self, ast, sess: Session):
        mv = self.materialized_views.get(ast.name)
        if mv is None:
            raise ValueError(f"materialized view {ast.name} does not exist")
        from .sql import parser as A

        # the definition re-plans under the DEFINING session catalog so its
        # unqualified table references keep resolving to the same tables
        refresh_sess = Session(catalog=mv.get("session_catalog"),
                               user=sess.user)
        return self._create_materialized_view(
            A.CreateMaterializedView(ast.name, mv["query"], or_replace=True),
            refresh_sess)

    def _insert(self, ast, sess: Session):
        from .sql import parser as A
        from .sql.frontend import Planner

        conn = self._writable_catalog(sess)
        self.access_control.check_can_write(
            sess.user, self._catalog_name_of(conn), ast.name, "insert into")
        schema = conn.schema(ast.name)
        names = ast.columns or schema.names
        if isinstance(ast.query, A.ValuesRows):
            cols = _literal_rows_to_columns(ast.query.rows,
                                            [schema.field(n).type for n in names])
        else:
            src_plan = Planner(self, sess).plan_query(ast.query)
            self._check_select_access(src_plan, sess.user)
            res = self.execute_plan(src_plan, cache=False)
            if len(res.columns) != len(names):
                raise ValueError(
                    f"INSERT has {len(names)} target columns but the query "
                    f"produces {len(res.columns)}")
            cols = [_coerce_host(res.columns[i].tolist(), res.types[i],
                                 schema.field(n).type)
                    for i, n in enumerate(names)]
        if tuple(names) != schema.names:
            by_name = dict(zip(names, cols))
            n_rows = len(cols[0]) if cols else 0
            cols = [by_name.get(f.name, [None] * n_rows) for f in schema.fields]
        conn.append(ast.name, cols)
        self._invalidate()
        return _dml_count_result(len(cols[0]) if cols else 0)


@dataclasses.dataclass
class _PlanTemplate:
    """A compiled plan template: the plan's constants-at-parameter-positions
    are ir.Parameter runtime inputs; ``binders`` (sql/params.Binder, slot
    order) encode each EXECUTE literal into the value domain its slot was
    planned in.  ``confirmed`` flips after the first successful execution —
    until then the template is on probation and any failure demotes the text
    to the substitution path."""

    text: str
    plan: object
    binders: tuple
    n_params: int
    confirmed: bool = False
    # round 21: flips False the first time a fused batch raises
    # BatchUnsupported (plan shape outside the batchable subset, object-
    # dtype pages) — the template keeps serving serially, it just stops
    # entering the batcher's fused path
    batchable: bool = True


_TEMPLATE_UNBINDABLE = object()  # negative-cache sentinel: this text plans
# with at least one parameter whose value is consumed at plan time (LIMIT
# counts, LUT folds, plan-shaping literals) — don't re-attempt creation


def _template_plan_ok(plan) -> bool:
    """Conservative bindability shape check: templates cover the streaming
    point-lookup subset (scan/filter/project chains, TopN/Sort/Limit,
    Values, Union).  Blocking operators whose step functions fuse the
    upstream transform under their OWN jits (Aggregate, Join builds, Window)
    stay on the substitution path until those entry points thread params."""
    from .sql import plan as P

    allowed = (P.Output, P.Project, P.Filter, P.TableScan, P.Limit, P.Sort,
               P.Union, P.Values)

    def walk(n) -> bool:
        if not isinstance(n, allowed):
            return False
        return all(walk(c) for c in n.children)

    return walk(plan)


def _null_ctx():
    import contextlib

    return contextlib.nullcontext()


def _normalize_statement(sql: str) -> str:
    """Plan-cache key normalization: strip comments, collapse whitespace
    (token-stream re-serialization), so trivially reformatted repeats of the
    same statement stop re-planning.  The ORIGINAL text still parses and
    still feeds the volatile-function test."""
    from .sql.params import normalize_sql

    return normalize_sql(sql)


def _plan_shape_props(sess) -> tuple:
    """The session properties that change the PLAN (not just execution):
    they must participate in every plan-cache key, or SET SESSION silently
    no-ops for already-cached statements (reference: the session carried
    inside CachingPlannerContext keys).  ``dispatch_batch`` qualifies because
    a cached plan's node identities key the executor's compiled batch
    artifacts — resolving the env default here also means a cached statement
    re-plans if the process default changes between sessions."""
    props = getattr(sess, "properties", None) or {}
    return (str(props.get("join_distribution_type", "AUTOMATIC")).upper(),
            int(props.get("group_by_capacity") or 0),
            _effective_dispatch_batch(sess),
            _effective_adaptive(sess))


def _effective_adaptive(sess) -> bool:
    """``adaptive_execution`` tri-state session property: the session's
    setting when present, else ON.
    PLAN-SHAPING (rides _plan_shape_props): the advisor diverts statements
    to corrected plans keyed off the base plan-cache key, so sessions with
    adaptive on and off must never share a key — SET SESSION
    adaptive_execution=false must actually escape the corrected plan."""
    props = getattr(sess, "properties", None) or {}
    v = props.get("adaptive_execution")
    return True if v is None else bool(v)


def _session_with_corrections(sess, corrections):
    """A shallow session copy carrying the advisor's frozen corrections for
    the planner pass (sql/exchanges.resolve_distributions reads
    ``_adaptive_corrections``).  The original session is never mutated —
    the key under which the corrected plan caches already carries the
    correction token, so the marker property must not leak into
    _plan_shape_props (it is read by name, not enumerated)."""
    import copy

    corr_sess = copy.copy(sess)
    props = dict(getattr(sess, "properties", None) or {})
    props["_adaptive_corrections"] = corrections
    corr_sess.properties = props
    return corr_sess


def _effective_page_cache(sess):
    """Tri-state ``page_cache`` session property: True/False when the session
    set it, None (pool default — the TRINO_TPU_PAGE_CACHE gate) otherwise.
    Deliberately NOT in _plan_shape_props: serving a scan from the buffer
    pool changes where pages come from, never the plan or its compiled
    artifacts, so cached statements keep their plans across SET SESSION
    page_cache flips."""
    props = getattr(sess, "properties", None) or {}
    v = props.get("page_cache")
    return None if v is None else bool(v)


# Volatile-function word test for result-cache admission (engine.
# _result_cache_key).  Matched against the SQL TEXT: the builtins below fold
# to plan-time Constants during planning (sql/functions*._build_current_*),
# so post-plan inspection cannot see them.  Word-boundary match is
# deliberately conservative — a column named "random" merely opts its
# statement out of the result cache, which is always safe.
_VOLATILE_SQL = ("now", "current_timestamp", "current_date", "current_time",
                 "localtimestamp", "localtime", "random", "rand", "uuid")
_VOLATILE_SQL_RE = re.compile(
    r"\b(" + "|".join(_VOLATILE_SQL) + r")\b", re.IGNORECASE)


def _effective_result_cache(sess):
    """Tri-state ``result_cache`` session property, same contract as
    ``page_cache``: True/False when the session set it, None (pool default —
    the TRINO_TPU_RESULT_CACHE gate) otherwise.  NON-plan-shaping: serving a
    whole statement from the result tier never changes the plan or its
    compiled artifacts."""
    props = getattr(sess, "properties", None) or {}
    v = props.get("result_cache")
    return None if v is None else bool(v)


def _effective_dispatch_batch(sess) -> int:
    """The dispatch-coalescing width a session's queries run at: the
    ``dispatch_batch`` session property when set (>0), else
    ``boundary.DISPATCH_BATCH``."""
    props = getattr(sess, "properties", None) or {}
    try:
        v = int(props.get("dispatch_batch", 0) or 0)
    except (TypeError, ValueError):
        v = 0
    return v if v > 0 else DISPATCH_BATCH


def _coerce_host(values, src_t, dst_t):
    """Host-side value coercion for writes (decoded python objects -> storage repr:
    decimals as raw scaled ints, strings as str, dates as epoch days)."""
    import datetime

    import numpy as np

    from .types import DecimalType, TimestampType

    if isinstance(dst_t, DecimalType):
        from decimal import Decimal

        s = dst_t.scale
        out = []
        for v in values:
            if v is None:
                out.append(None)
                continue
            if isinstance(v, float):
                raw = round(v * 10**s)
            else:
                # int / str / Decimal: exact scaling, no float round-trip
                # (declared wide precisions would corrupt past 2^53)
                raw = int(Decimal(str(v)).scaleb(s).to_integral_value())
            if not -(1 << 63) <= raw < (1 << 63):
                raise ValueError(
                    f"decimal value {v} beyond 2^63: Int128 column storage "
                    "is not supported (declared wide precision is, for "
                    "values that fit)")
            out.append(raw)
        return out
    if dst_t.is_string:
        return [None if v is None else str(v) for v in values]
    if dst_t.is_floating:
        return [None if v is None else float(v) for v in values]
    if dst_t.name == "date":
        out = []
        for v in values:
            if v is None:
                out.append(None)
            elif isinstance(v, np.datetime64):
                out.append(int(v.astype("datetime64[D]").astype(np.int64)))
            elif isinstance(v, datetime.date):
                out.append((v - datetime.date(1970, 1, 1)).days)
            else:
                out.append(int(v))
        return out
    if isinstance(dst_t, TimestampType):
        unit = "us" if dst_t.precision <= 6 else "ns"
        back = 10 ** ((6 if unit == "us" else 9) - dst_t.precision)
        return [None if v is None else
                (int(v.astype(f"datetime64[{unit}]").astype(np.int64)) // back
                 if isinstance(v, np.datetime64) else int(v))
                for v in values]
    return [None if v is None else int(v) for v in values]


def _literal_rows_to_columns(rows, types):
    """INSERT ... VALUES literal evaluation (coordinator-side, like the reference's
    ValuesNode constant folding)."""
    cols = [[] for _ in types]
    for row in rows:
        if len(row) != len(types):
            raise ValueError(f"VALUES row has {len(row)} fields, expected {len(types)}")
        for i, (e, t) in enumerate(zip(row, types)):
            cols[i].append(_literal_value(e, t))
    return cols


def _dml_count_result(n: int, label: str = "rows"):
    """Affected-row count surface for DML (reference: the protocol's
    updateCount / UPDATE n rows output)."""
    import numpy as np

    from .types import BIGINT

    col = np.array([int(n)], np.int64)
    return MaterializedResult((label,), (BIGINT,), [col], [col])


def _query_mem_limit(engine, sess):
    """query_max_memory session property -> bytes limit (None = unlimited)."""
    try:
        v = int(engine.session_properties.get(sess, "query_max_memory"))
    except Exception:
        return None
    return v if v > 0 else None


def _ts_rescale(text: str, t):
    """Parse a timestamp literal and rescale to the column's precision."""
    from .types import parse_timestamp_literal

    v, ty = parse_timestamp_literal(text)
    diff = t.precision - ty.precision
    return (v * 10 ** diff if diff >= 0 else
            round(v / 10 ** -diff)), t


def _literal_value(e, t):
    from .sql import parser as A
    from .types import DecimalType, parse_date_literal

    neg = False
    while isinstance(e, A.UnaryOp) and e.op == "negate":
        neg = not neg
        e = e.operand
    if isinstance(e, A.NullLit):
        return None
    if isinstance(e, A.NumberLit):
        if isinstance(t, DecimalType):
            # exact decimal parse: float64 loses digits past ~15 significant
            # figures, silently corrupting wide-decimal literals
            from decimal import ROUND_HALF_UP, Decimal

            d = Decimal(e.text)
            if neg:
                d = -d
            raw = int((d * (10 ** t.scale))
                      .to_integral_value(rounding=ROUND_HALF_UP))
            if not -(1 << 63) <= raw < (1 << 63):
                raise ValueError(
                    f"decimal value {d} beyond 2^63 at scale {t.scale}: "
                    "Int128 column storage is not supported")
            return raw
        v = float(e.text) if ("." in e.text or "e" in e.text.lower()) else int(e.text)
        if neg:
            v = -v
        return float(v) if t.is_floating else int(v)
    if isinstance(e, A.StringLit):
        from .types import CharType

        if isinstance(t, CharType):
            # char(n) stores SPACE-PADDED to n (Chars.padSpaces), which is
            # what makes char comparisons trailing-space-blind
            return e.value[:t.length].ljust(t.length)
        if t.name == "date":
            return parse_date_literal(e.value)
        if t.name.startswith("timestamp"):
            from .types import parse_timestamp_literal

            v, ty = _ts_rescale(e.value, t)
            return v
        return e.value
    if isinstance(e, A.DateLit):
        return parse_date_literal(e.value)
    if isinstance(e, A.TimestampLit):
        if t.name.startswith("timestamp"):
            v, _ = _ts_rescale(e.value, t)
            return v
        return parse_date_literal(e.value.split(" ")[0])
    if isinstance(e, A.BoolLit):
        return bool(e.value)
    if isinstance(e, A.ArrayLiteral):
        from .types import ArrayType

        et = t.element if isinstance(t, ArrayType) else t
        return [_literal_value(item, et) for item in e.items]
    raise ValueError(f"unsupported VALUES literal {e}")
