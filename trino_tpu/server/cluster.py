"""Multi-process control plane: coordinator + worker processes over HTTP.

Reference architecture (SURVEY.md §2.6/§2.7/§3.2-3.3):
- worker registration/announcement -> CoordinatorNodeManager
  (node/CoordinatorNodeManager.java:56) + Airlift announcements;
- fragment dispatch -> HttpRemoteTask POSTing a TaskUpdateRequest
  (server/remotetask/HttpRemoteTask.java:137,743; the fragment ships once,
  split batches address it);
- task REST surface -> /v1/task create + status poll
  (server/TaskResource.java:142,229);
- heartbeat failure detection -> HeartbeatFailureDetector
  (failuredetector/HeartbeatFailureDetector.java:77), simplified from the
  exponential-decay ratio to a consecutive-miss threshold;
- inter-process data plane -> the spooled filesystem exchange
  (plugin/trino-exchange-filesystem), shared with the FTE executor: workers
  commit partial pages first-commit-wins; the coordinator merges.

TPU translation: one worker process = one accelerator's host runtime.  The
fragment a worker receives is a pickled plan subtree (this engine's
TaskUpdateRequest; trusted-cluster transport, like the reference's
internal-communication channel) plus its split assignment; the worker runs the
same jit-compiled partial-aggregation task body the in-process FTE uses
(exec/fte.run_partial_aggregate), so coordinator-local and remote execution
share one code path — the reference's single-binary role split.
"""

from __future__ import annotations

import dataclasses
import hashlib
import hmac
import itertools
import json
import os as _os
import pickle
import threading
import time
import traceback
import urllib.error
import urllib.parse
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

# workers are separate OS processes and take their platform from the
# environment they inherit (JAX_PLATFORMS=cpu for the CPU backend).
# x64 is unconditional: the whole engine (int64 accumulators, splitmix64 key
# hashing, serialized page dtypes) assumes the global x64 session.
import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

from ..exec.fte import (FaultTolerantExecutor, SpoolingExchange,
                        is_retryable_failure, merge_partial_outputs,
                        read_fragment_outputs, run_fragment,
                        run_partial_aggregate, run_stream_splits,
                        serialize_fragment_output)
from ..exec.local_executor import LocalExecutor
from ..exec.pages import _host_page, _materialize
from ..execution import faults, tracing
from ..execution.faults import InjectedFaultError
from ..execution.tracing import (InflightRegistry, QueryCounters,
                                 StallWatchdog, Tracer)
from ..sql import plan as P

__all__ = ["WorkerServer", "ClusterCoordinator", "build_catalogs"]

_cluster_qids = itertools.count(1)  # coordinator query/trace ids (cluster_N)


def build_catalogs(config: dict) -> dict:
    """Instantiate connectors from a declarative config — the analog of
    catalog properties files loaded by the CatalogManager at bootstrap
    (connector/CoordinatorDynamicCatalogManager.java)."""
    from ..connectors.tpch import TpchConnector

    factories = {"tpch": TpchConnector}
    try:
        from ..connectors.tpcds import TpcdsConnector

        factories["tpcds"] = TpcdsConnector
    except ImportError:  # pragma: no cover
        pass
    out = {}
    for name, spec in config.items():
        kind = spec["connector"]
        kwargs = {k: v for k, v in spec.items() if k != "connector"}
        out[name] = factories[kind](**kwargs)
    return out


def _http(url: str, data: Optional[bytes] = None, timeout: float = 10.0,
          secret: Optional[str] = None) -> bytes:
    req = urllib.request.Request(url, data=data,
                                 method="POST" if data is not None else "GET")
    if secret and data is not None:
        req.add_header("X-Trino-Internal-Signature", _sign(secret, data))
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.read()


def _sign(secret: str, body: bytes) -> str:
    return hmac.new(secret.encode(), body, hashlib.sha256).hexdigest()


def _backoff_s(key: str, attempt: int, base: float = 0.25,
               cap: float = 5.0) -> float:
    """Exponential backoff with DETERMINISTIC jitter for retry scheduling
    (task re-dispatch, heartbeat probes of a failing worker).  ``base *
    2^(attempt-1)`` grows the spacing; the jitter factor (in [1.0, 1.5)) is a
    hash of (key, attempt) — seeded from the task/node id, so two coordinators
    retrying the same task space identically and a chaos run is reproducible,
    while distinct tasks still de-synchronize instead of thundering back
    together (reference: the backoff in HttpPageBufferClient / failure
    detector probes, with the randomness made deterministic)."""
    # attempt is UNBOUNDED on the heartbeat-misses path (a worker that dies
    # without announcing keeps accumulating misses); 2**(attempt-1) crosses
    # float range around attempt 1025 and the OverflowError would kill the
    # heartbeat daemon thread.  base * 2**30 is already orders of magnitude
    # past any sane cap, so clamping the exponent never changes the result.
    d = base * (2 ** min(max(attempt - 1, 0), 30))
    h = int.from_bytes(
        hashlib.blake2b(f"{key}:{attempt}".encode(), digest_size=8).digest(),
        "big")
    return min(d * (1.0 + 0.5 * (h / 2.0 ** 64)), cap)


_LOOPBACK = ("127.0.0.1", "localhost", "::1")


def _http_stream_get(url: str, secret: Optional[str], timeout: float = 10.0):
    """GET with a path signature (streamed page reads carry no body to sign).
    Returns (body bytes, headers)."""
    req = urllib.request.Request(url, method="GET")
    if secret:
        path = urllib.parse.urlsplit(url).path
        req.add_header("X-Trino-Internal-Signature",
                       _sign(secret, path.encode()))
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.read(), dict(r.headers)


class _OutputBuffer:
    """In-memory task output buffer with long-poll reads and token
    acknowledgement (reference: execution/buffer/PartitionedOutputBuffer.java
    + the TaskResource long-poll protocol, server/TaskResource.java:331-383):
    GET of token T by reader R acknowledges every page below T *for that
    reader* and waits up to the poll budget for page T.  A page's memory
    frees once EVERY (non-abandoned) reader has acknowledged it — with
    ``n_readers`` > 1 this is the broadcast buffer a split-fanout consumer
    stage reads (reference: execution/buffer/BroadcastOutputBuffer.java).
    ``add`` blocks while the buffer holds more than ``max_bytes`` of
    unacknowledged pages — the producer-side backpressure the reference gets
    from OutputBuffer.isFull()."""

    def __init__(self, max_bytes: int = 64 << 20, n_readers: int = 1):
        self.pages: dict = {}  # index -> serialized page envelope
        self.next_index = 0
        self.bytes = 0
        self.max_bytes = max_bytes
        self.done = False
        self.failed: Optional[str] = None
        self.n_readers = n_readers
        self.acked = [0] * n_readers  # per reader: pages < acked[r] are free
        self.completed = [False] * n_readers  # reader saw the complete marker
        self.abandoned = [False] * n_readers  # reader gone; don't retain for it
        self.cv = threading.Condition()

    def _free_acked(self) -> None:
        """Drop every page all live readers acknowledged (call under cv)."""
        floors = [a for a, gone in zip(self.acked, self.abandoned) if not gone]
        floor = min(floors) if floors else self.next_index
        for i in [i for i in self.pages if i < floor]:
            self.bytes -= len(self.pages.pop(i))

    def add(self, data: bytes, stall_timeout: float = 120.0) -> None:
        """Blocks while the buffer is full of unacknowledged pages.  A
        consumer that vanished mid-stream would otherwise pin this producer
        (and its executor slot) forever — after ``stall_timeout`` with no ack
        the buffer fails and the producer unwinds."""
        deadline = time.time() + stall_timeout
        with self.cv:
            while self.bytes > 0 and self.bytes + len(data) > self.max_bytes \
                    and not self.failed:
                if time.time() > deadline:
                    self.failed = "consumer stalled: no acknowledgement " \
                                  f"for {stall_timeout:.0f}s"
                    self.cv.notify_all()
                    break
                self.cv.wait(0.05)
            if self.failed:
                raise RuntimeError(f"output buffer failed: {self.failed}")
            self.pages[self.next_index] = data
            self.next_index += 1
            self.bytes += len(data)
            self.cv.notify_all()

    def finish(self) -> None:
        with self.cv:
            self.done = True
            self.cv.notify_all()

    def fail(self, error: str) -> None:
        with self.cv:
            self.failed = error
            self.cv.notify_all()

    def abandon(self, reader: int) -> None:
        """A consumer died and will be retried against a FRESH producer: stop
        retaining pages for its reader slot so the surviving readers' floor
        governs memory again."""
        with self.cv:
            if 0 <= reader < self.n_readers:
                self.abandoned[reader] = True
                self._free_acked()
                self.cv.notify_all()

    @property
    def fully_delivered(self) -> bool:
        return all(c or a for c, a in zip(self.completed, self.abandoned))

    def get(self, token: int, max_wait: float = 1.0, reader: int = 0):
        """(page | None, complete, failed): acknowledge pages < token for
        ``reader``, then long-poll for page ``token``."""
        deadline = time.time() + max_wait
        with self.cv:
            if not 0 <= reader < self.n_readers:
                return None, False, f"unknown reader {reader}"
            self.acked[reader] = max(self.acked[reader], token)
            self._free_acked()
            self.cv.notify_all()  # acks may unblock the producer
            while True:
                if self.failed:
                    return None, False, self.failed
                if token in self.pages:
                    return self.pages[token], False, None
                if self.done and token >= self.next_index:
                    self.completed[reader] = True
                    self.cv.notify_all()
                    return None, True, None
                left = deadline - time.time()
                if left <= 0:
                    return None, False, None  # poll timeout: client retries
                self.cv.wait(left)


def stream_task_pages(url: str, task_id: str, secret: Optional[str] = None,
                      timeout: float = 60.0, reader: int = 0):
    """Client half of the streaming exchange (reference:
    operator/HttpPageBufferClient.java:100): long-poll the producing worker's
    output buffer, yielding page envelopes; advancing the token acknowledges
    delivery *for this reader slot* so the producer can free (and keep
    producing past) them once every reader of a broadcast buffer has."""
    token = 0
    deadline = time.time() + timeout
    while True:
        try:
            body, headers = _http_stream_get(
                f"{url}/v1/task/{task_id}/results/{reader}/{token}", secret)
        except urllib.error.HTTPError as he:
            if he.code == 404 and time.time() < deadline:
                # the producer task was dispatched but its thread has not
                # registered the buffer yet (or a respawned producer is still
                # starting): poll again within the no-progress budget
                time.sleep(0.1)
                continue
            raise
        if headers.get("X-Trino-Buffer-Failed"):
            raise RuntimeError(
                f"stream source {task_id} failed: "
                f"{headers.get('X-Trino-Buffer-Failed')}")
        if headers.get("X-Trino-Buffer-Complete") == "1":
            return
        if headers.get("X-Trino-Has-Page") == "1":
            token += 1
            deadline = time.time() + timeout
            yield body
        elif time.time() > deadline:
            raise TimeoutError(
                f"stream source {task_id} produced nothing for {timeout:.0f}s")


class _WorkerBusy(Exception):
    """Task admission refused: queue depth at max (backpressure)."""


class _WorkerDraining(Exception):
    """Task admission refused: graceful shutdown in progress."""


# ---------------------------------------------------------------------------- worker
@dataclasses.dataclass
class _TaskState:
    state: str = "running"  # running | done | failed
    error: Optional[str] = None
    retryable: bool = True  # False: deterministic failure, do not re-dispatch
    # device-boundary profile of the task (QueryCounters.as_dict(), set BEFORE
    # the output commits so a coordinator that just observed the commit reads
    # it) and the task's finished span tree — the worker half of the
    # cluster-wide counter flow the coordinator merges per query
    counters: Optional[dict] = None
    spans: Optional[list] = None
    # round 15: fragment-relative est-vs-actual node records
    # (execution/history.collect_plan_actuals over the task's executor stats,
    # node paths anchored at the FRAGMENT root) — the coordinator re-anchors
    # them at the fragment's full-plan path and folds them into the engine's
    # plan-history store
    plan_stats: Optional[dict] = None


def _span_subtree(tracer, trace_id: str, root_span_id: int) -> list:
    """Finished spans of ``trace_id`` reachable from ``root_span_id``
    (inclusive), start-ordered.  Scopes a task's shipped spans to its OWN
    subtree even when sibling tasks of the same query share the worker
    tracer's trace id (round-16 stitched traces)."""
    spans = tracer.spans_for(trace_id)
    children: dict = {}
    by_id: dict = {}
    for s in spans:
        by_id[s.span_id] = s
        children.setdefault(s.parent_id, []).append(s)
    out, stack, seen = [], [root_span_id], set()
    while stack:
        sid = stack.pop()
        if sid in seen:
            continue
        seen.add(sid)
        s = by_id.get(sid)
        if s is not None:
            out.append(s)
        stack.extend(c.span_id for c in children.get(sid, ()))
    out.sort(key=lambda s: s.start_s)
    return out


def _subtree_ids(node) -> list:
    """Every id() in a plan subtree (stale-stats scoping for pooled worker
    executors, which reset stats only in execute())."""
    out: list = []

    def walk(n):
        out.append(id(n))
        for c in n.children:
            walk(c)

    walk(node)
    return out


class WorkerServer:
    """A worker process: executes dispatched fragments over its own executor
    and spools output pages to the shared exchange directory."""

    def __init__(self, catalogs_config: dict, spool_dir: str,
                 host: str = "127.0.0.1", port: int = 0,
                 coordinator_url: Optional[str] = None, node_id: str = "worker",
                 announce_interval: float = 0.5, secret: Optional[str] = None,
                 stall_s: Optional[float] = None):
        # the fragment envelope is pickled (arbitrary-code-execution on
        # deserialize), so the task endpoints are authenticated like the
        # reference's internal communication channel
        # (internal-communication.shared-secret): every POST body carries an
        # HMAC of the cluster secret.  Without a secret the worker refuses to
        # listen beyond loopback.
        self.secret = secret if secret is not None \
            else _os.environ.get("TRINO_TPU_CLUSTER_SECRET")
        if self.secret is None and host not in _LOOPBACK:
            raise ValueError(
                f"refusing to serve unauthenticated task endpoints on {host}: "
                "set TRINO_TPU_CLUSTER_SECRET (or pass secret=) to bind "
                "beyond loopback")
        self.catalogs = build_catalogs(catalogs_config)
        # ONE node-level pool shared by every pooled executor: per-executor
        # pools would overcommit the single accelerator's HBM (reference:
        # memory/MemoryPool.java is per-node, not per-driver)
        from ..memory import MemoryPool

        self.memory_pool = MemoryPool()
        # worker-local device buffer pool (round 9): tasks over the same
        # table share scan pages / join builds across this worker's executor
        # pool — each node caches what IT scans (the coordinator's engine
        # pool is separate by design; there is no cross-node cache protocol).
        # No DDL-invalidation protocol is needed YET: build_catalogs only
        # instantiates immutable generator connectors (tpch/tpcds), whose
        # pages never go stale.  A future MUTABLE worker connector must ship
        # cache invalidation alongside its writes (clear this pool on the
        # coordinator's invalidation broadcast) before it may set
        # CACHEABLE_SCANS.
        from ..execution.bufferpool import DeviceBufferPool

        self.buffer_pool = DeviceBufferPool()
        self.local = LocalExecutor(self.catalogs, memory_pool=self.memory_pool,
                                   buffer_pool=self.buffer_pool)
        # worker-local tracer: each task runs under a root span (trace id =
        # task id) whose finished tree rides the status response back to the
        # coordinator
        self.tracer = Tracer()
        # worker-local in-flight registry + stall watchdog (round 8): task
        # bodies route their _jit/_host entries here (NOT the process-global
        # INFLIGHT — in-process test clusters must not share stall state);
        # the health verdict piggybacks on /v1/info and announces, so a
        # wedged-but-HTTP-alive worker reads as "stalled" to the coordinator
        # (reference: HeartbeatFailureDetector reading real node state, not
        # just socket liveness).  stall_s falls back to TRINO_TPU_STALL_S;
        # unset = watchdog off, health always "ok".
        self.inflight = InflightRegistry()
        self.last_stall_report: Optional[dict] = None
        self.stall_watchdog = StallWatchdog(
            registry=self.inflight, stall_s=stall_s,
            on_stall=self._on_stall,
            extra_info=lambda: {"memory": [self.memory_pool.info()]})
        self.spool_dir = spool_dir
        self.host, self.port = host, port
        self.node_id = node_id
        self.coordinator_url = coordinator_url
        self.announce_interval = announce_interval
        from collections import OrderedDict

        # the fragment ships ONCE per query (reference: HttpRemoteTask sends
        # the PlanFragment once, then split batches address it); tasks carry a
        # fragment id.  Both registries are bounded so a long-lived worker's
        # memory does not grow with queries served; evicting a fragment also
        # evicts its compiled artifacts from the executor caches.
        self.fragments: OrderedDict = OrderedDict()  # fragment_id -> plan node
        self.tasks: OrderedDict = OrderedDict()  # task_id -> _TaskState
        self.max_fragments = 32
        self.max_task_states = 256
        self._wlock = threading.Lock()  # handler threads + task threads share
        # the registries; eviction must also never drop state still in use
        # executor POOL (reference: executor/TaskExecutor.java time-shares
        # fragments across driver threads; here each concurrent task checks
        # out its OWN LocalExecutor — overrides/caches are single-query state,
        # and XLA interleaves the device work): round-3 VERDICT weak — the
        # worker ran one fragment at a time behind a global lock
        self.max_exec_concurrency = int(_os.environ.get(
            "TRINO_TPU_WORKER_EXEC_SLOTS", "2"))
        # time-shared slots with multilevel feedback per query (reference:
        # executor/timesharing/ — round-4 verdict item 6: a long fragment must
        # not occupy its slot until done while a point query waits)
        from ..execution.fair_scheduler import FairScheduler

        self.scheduler = FairScheduler(self.max_exec_concurrency)
        self._executor_pool: list = [self.local]
        self._all_executors: list = [self.local]
        self._running_frags: dict = {}  # fragment_id -> running task count
        self._running_queries: dict = {}  # exchange_dir -> running task count
        self._running_tasks = 0
        self._executing = 0  # tasks currently holding an executor
        self.peak_concurrency = 0  # high-water mark of _executing (observable)
        self.out_buffers: dict = {}  # task_id -> _OutputBuffer (streaming
        # output mode; bounded below)
        self.max_out_buffers = 16
        # admission backpressure: tasks beyond this queue depth are refused
        # with 429 and the coordinator re-offers them (the OutputBuffer-full /
        # isFull() producer blocking of the reference, re-planned as admission
        # control at the task boundary)
        self.max_concurrent_tasks = 8
        self.memory_admission_fraction = 0.9  # refuse tasks past this pool use
        self.admission_denials = 0  # tasks refused at the memory rung
        self.cache_sheds = 0  # buffer-pool evictions forced by pressure
        self._draining = False  # graceful shutdown: no NEW work, finish running
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._stop = threading.Event()

    # -- lifecycle -------------------------------------------------------------
    def start(self) -> str:
        worker = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):  # quiet
                pass

            def _reply(self, code, obj):
                body = json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/v1/info":
                    state = "shutting_down" if worker._draining else "active"
                    pool = worker.memory_pool
                    # health verdict rides the heartbeat: a wedged dispatch
                    # flips this while the HTTP thread still answers.  The
                    # stall report (stacks + memory dump) ships only WHILE
                    # stalled — the coordinator keeps its last-seen copy, so
                    # a resolved stall's post-mortem survives there without
                    # every later heartbeat hauling a stale multi-KB dump
                    health = worker._health()
                    return self._reply(200, {"node_id": worker.node_id,
                                             "state": state,
                                             "peak_concurrency":
                                                 worker.peak_concurrency,
                                             "mem_reserved": pool.reserved,
                                             "mem_max": pool.max_bytes,
                                             "mem_by_query": pool.by_query(),
                                             "scheduler":
                                                 worker.scheduler.info(),
                                             **health,
                                             "stall_report":
                                                 worker.last_stall_report
                                                 if health["health"]
                                                 == "stalled" else None})
                if "/results/" in self.path and self.path.startswith("/v1/task/"):
                    # streamed page read:
                    #   /v1/task/{tid}/results/{reader}/{token}
                    # (legacy single-reader form /v1/task/{tid}/results/{token}
                    # maps to reader 0).  Reference: TaskResource.java:331
                    # long-poll page fetch; page data is cluster-internal —
                    # the path must be signed
                    if worker.secret is not None:
                        got = self.headers.get("X-Trino-Internal-Signature", "")
                        want = _sign(worker.secret, self.path.encode())
                        if not hmac.compare_digest(got, want):
                            return self._reply(403, {"error": "bad signature"})
                    parts = self.path.split("/")
                    tid = parts[3]
                    if len(parts) >= 7:
                        reader, token = int(parts[5]), int(parts[6])
                    else:
                        reader, token = 0, int(parts[5])
                    buf = worker.out_buffers.get(tid)
                    if buf is None:
                        return self._reply(404, {"error": "no such buffer"})
                    page, complete, failed = buf.get(token, max_wait=1.0,
                                                     reader=reader)
                    body = page or b""
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     "application/octet-stream")
                    self.send_header("Content-Length", str(len(body)))
                    self.send_header("X-Trino-Has-Page",
                                     "1" if page is not None else "0")
                    self.send_header("X-Trino-Buffer-Complete",
                                     "1" if complete else "0")
                    if failed:
                        self.send_header("X-Trino-Buffer-Failed",
                                         failed.splitlines()[0][:200])
                    self.end_headers()
                    self.wfile.write(body)
                    if complete and buf.fully_delivered:
                        worker.out_buffers.pop(tid, None)  # all readers done
                    return
                if self.path.startswith("/v1/task/"):
                    tid = self.path.rsplit("/", 1)[-1]
                    st = worker.tasks.get(tid)
                    if st is None:
                        return self._reply(404, {"error": "no such task"})
                    # the task's QueryCounters snapshot + finished spans ride
                    # the status response so the coordinator's per-query merge
                    # sees the whole cluster (reference: TaskStatus carrying
                    # task stats back to the coordinator)
                    return self._reply(200, {"state": st.state, "error": st.error,
                                             "retryable": st.retryable,
                                             "counters": st.counters,
                                             "spans": st.spans,
                                             "plan_stats": st.plan_stats})
                self._reply(404, {"error": "not found"})

            def _read_verified(self):
                """Read the body and verify its HMAC BEFORE unpickling —
                pickle.loads on an unauthenticated body is arbitrary code
                execution."""
                n = int(self.headers.get("Content-Length", 0))
                body = self.rfile.read(n)
                if worker.secret is not None:
                    got = self.headers.get("X-Trino-Internal-Signature", "")
                    want = _sign(worker.secret, body)
                    if not hmac.compare_digest(got, want):
                        return None
                return pickle.loads(body)

            def do_POST(self):
                if self.path == "/v1/fragment":
                    req = self._read_verified()
                    if req is None:
                        return self._reply(403, {"error": "bad signature"})
                    worker._register_fragment(req["fragment_id"], req["plan"])
                    return self._reply(200, {"ok": True})
                if self.path == "/v1/task":
                    req = self._read_verified()
                    if req is None:
                        return self._reply(403, {"error": "bad signature"})
                    try:
                        worker._start_task(req)
                    except KeyError:
                        return self._reply(409, {"error": "unknown fragment"})
                    except _WorkerDraining:
                        return self._reply(503, {"error": "shutting down"})
                    except _WorkerBusy:
                        return self._reply(429, {"error": "task queue full"})
                    return self._reply(200, {"accepted": req["task_id"]})
                if self.path == "/v1/shutdown":
                    req = self._read_verified()
                    if req is None:
                        return self._reply(403, {"error": "bad signature"})
                    worker.shutdown_gracefully()
                    return self._reply(200, {"state": "shutting_down"})
                if self.path == "/v1/kill_query":
                    # cluster low-memory policy chose a victim: poison its
                    # reservations + preemption points node-wide (reference:
                    # ClusterMemoryManager -> worker killQuery RPC)
                    req = self._read_verified()
                    if req is None:
                        return self._reply(403, {"error": "bad signature"})
                    worker.memory_pool.kill_query(req["query_key"])
                    return self._reply(200, {"killed": req["query_key"]})
                if self.path == "/v1/evict_cache":
                    # the coordinator's pre-kill rung: shed this node's
                    # device buffer pool (cache is droppable; victims are
                    # not) before the low-memory killer picks anyone
                    req = self._read_verified()
                    if req is None:
                        return self._reply(403, {"error": "bad signature"})
                    freed = worker.buffer_pool.evict_bytes(1 << 62)
                    if freed:
                        worker.cache_sheds += 1
                    return self._reply(200, {"freed_bytes": freed})
                if self.path.startswith("/v1/task/") \
                        and self.path.endswith("/abandon"):
                    # /v1/task/{tid}/results/{reader}/abandon — a consumer
                    # died and retries against a fresh producer; release this
                    # reader slot so surviving readers govern page retention.
                    # Signed like the stream reads (path signature, no body).
                    if worker.secret is not None:
                        got = self.headers.get("X-Trino-Internal-Signature", "")
                        want = _sign(worker.secret, self.path.encode())
                        if not hmac.compare_digest(got, want):
                            return self._reply(403, {"error": "bad signature"})
                    parts = self.path.split("/")
                    buf = worker.out_buffers.get(parts[3])
                    if buf is not None:
                        buf.abandon(int(parts[5]))
                        if buf.fully_delivered:
                            worker.out_buffers.pop(parts[3], None)
                    return self._reply(200, {"ok": True})
                self._reply(404, {"error": "not found"})

        self._httpd = ThreadingHTTPServer((self.host, self.port), Handler)
        self.port = self._httpd.server_port
        threading.Thread(target=self._httpd.serve_forever, daemon=True).start()
        self.stall_watchdog.start()  # no-op unless a threshold is configured
        if self.coordinator_url:
            threading.Thread(target=self._announce_loop, daemon=True).start()
        return self.url

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def stop(self):
        self._stop.set()
        self.stall_watchdog.stop()
        if self._httpd:
            self._httpd.shutdown()

    def _on_stall(self, report: dict) -> None:
        self.last_stall_report = report

    def _health(self) -> dict:
        """Live node-health verdict for heartbeats/announces: "stalled" when
        any in-flight entry on THIS worker's registry exceeds the watchdog
        threshold, recomputed per request (no watchdog-poll latency).
        Round 17: a worker whose over-threshold entries are all first-seen-
        signature COMPILES (under TRINO_TPU_STALL_COMPILE_S) reports
        "compiling" — the coordinator only degrades on "stalled", so a
        cold-compiling worker keeps receiving work instead of being gated
        out mid-warmup."""
        verdict, stalled_n, compiling_n = self.stall_watchdog.status()
        return {"health": verdict, "stalled": stalled_n,
                "compiling": compiling_n,
                "inflight": self.inflight.depth()}

    def _announce_loop(self):
        while not self._stop.is_set():
            try:
                state = "shutting_down" if self._draining else "active"
                _http(f"{self.coordinator_url}/v1/announce",
                      json.dumps({"node_id": self.node_id,
                                  "url": self.url,
                                  "state": state,
                                  "mem_reserved": self.memory_pool.reserved,
                                  "mem_max": self.memory_pool.max_bytes,
                                  **self._health(),
                                  }).encode(),
                      secret=self.secret)
            except Exception:
                pass  # coordinator not up yet / transient
            self._stop.wait(self.announce_interval)

    # -- task execution ----------------------------------------------------------
    def _checkout_executor(self, query_key: str = "q", token: str = ""):
        """Per-task executor checkout: overrides/compiled caches are
        single-query state, so concurrent fragments need their own.  The
        concurrency gate is the fair scheduler's slot grant — a task also
        yields its slot at split boundaries via tick (executor state stays
        with the task; only the slot token moves)."""
        self.scheduler.acquire(query_key, token)
        with self._wlock:
            if self._executor_pool:
                return self._executor_pool.pop()
            ex = LocalExecutor(self.catalogs, memory_pool=self.memory_pool,
                               buffer_pool=self.buffer_pool)
            self._all_executors.append(ex)
            return ex

    def _release_executor(self, ex, token: str = "") -> None:
        with self._wlock:
            self._executor_pool.append(ex)
        self.scheduler.release(token)

    def _register_fragment(self, frag_id: str, plan) -> None:
        with self._wlock:
            if frag_id in self.fragments:
                return
            self.fragments[frag_id] = plan
            evictable = [f for f in self.fragments
                         if not self._running_frags.get(f)]
            while len(self.fragments) > self.max_fragments and evictable:
                old_id = evictable.pop(0)
                if old_id == frag_id:
                    continue
                old = self.fragments.pop(old_id)
                for ex in self._all_executors:  # drop compiled artifacts too
                    ex.forget_plan(old)

    def _collect_task_plan_stats(self, node, ex) -> Optional[dict]:
        """Fragment-relative plan-actuals for the task snapshot: whatever
        blocking-operator stats this task's run left on its executor, keyed
        by node paths anchored at the FRAGMENT root (the coordinator
        re-anchors).  Best-effort and host-only — a collection failure loses
        history, never the task."""
        try:
            from ..execution.history import collect_plan_actuals

            return collect_plan_actuals(
                node, ex.stats, boundary=ex.boundary, catalogs=self.catalogs,
                paths=ex._node_paths, ests=ex._node_ests) or None
        except Exception:
            return None

    def _start_task(self, req: dict):
        tid = str(req["task_id"])
        frag_id = req["fragment_id"]
        with self._wlock:
            # under _wlock: the drain thread checks _running_tasks under the
            # same lock, so no task can slip in after it observed zero
            if self._draining:
                raise _WorkerDraining()
            node = self.fragments.get(frag_id)
            if node is None:
                raise KeyError(frag_id)
            if self._running_tasks >= self.max_concurrent_tasks:
                raise _WorkerBusy()
            # memory-aware admission (the node half of the reference's
            # ClusterMemoryManager: a nearly-full pool refuses work instead of
            # OOMing it; the coordinator re-offers elsewhere).  Ladder order:
            # shed this node's device cache FIRST (rung 1 — cached pages
            # share the accelerator with live query state even though their
            # budgets are separate pools), THEN refuse (rung: deny admission)
            if self.memory_pool.blocked(self.memory_admission_fraction):
                if self.buffer_pool.evict_bytes(1 << 62):
                    self.cache_sheds += 1
                self.admission_denials += 1
                raise _WorkerBusy()
            self._running_tasks += 1
            self.tasks[tid] = st = _TaskState()
            self._running_frags[frag_id] = self._running_frags.get(frag_id, 0) + 1
            # prune only TERMINAL task states: a running entry evicted here
            # would read as lost to the coordinator and burn a retry
            done = [t for t, s in self.tasks.items() if s.state != "running"]
            while len(self.tasks) > self.max_task_states and done:
                self.tasks.pop(done.pop(0), None)

        def run():
            stream_out = req.get("output") == "stream"
            buf = None
            if stream_out:
                buf = _OutputBuffer(n_readers=int(req.get("n_readers", 1)))
                with self._wlock:
                    self.out_buffers[tid] = buf
                    # evict buffers nothing will read again first; if the
                    # registry is still over its bound, fall back to oldest
                    # DONE buffers (a consumer stage that never dispatched —
                    # degraded query — would otherwise pin them forever)
                    dead = [t for t, b in self.out_buffers.items()
                            if b.failed or b.fully_delivered]
                    done = [t for t, b in self.out_buffers.items()
                            if b.done and t not in dead]
                    while len(self.out_buffers) > self.max_out_buffers \
                            and (dead or done):
                        victim = dead.pop(0) if dead else done.pop(0)
                        self.out_buffers.pop(victim, None)
            sources = req.get("stream_sources") or {}
            fetch = None
            if sources:
                def fetch(t, sources=sources):
                    # source values: plain url (reader 0 of task t) or a dict
                    # {"url", "task", "reader"} — the broadcast/retry form
                    # where the serving task id and reader slot differ
                    v = sources[t]
                    if isinstance(v, str):
                        return stream_task_pages(v, t, secret=self.secret)
                    return stream_task_pages(
                        v["url"], v.get("task", t), secret=self.secret,
                        reader=int(v.get("reader", 0)))
            xdir = req["exchange_dir"]
            # unique token per EXECUTION: a speculative duplicate or a
            # wedged-task re-dispatch of the same tid must hold its own slot
            token = self.scheduler.new_token(tid)
            ex = self._checkout_executor(query_key=xdir, token=token)
            # the session's coalescing width rides the task request: worker
            # executors batch per-split dispatches like the coordinator's
            ex.dispatch_batch = req.get("dispatch_batch")
            # the session's page_cache override rides the task request too
            # (None = this worker's TRINO_TPU_PAGE_CACHE gate)
            ex.page_cache = req.get("page_cache")
            # plan-actuals scoping (round 15): pooled worker executors reset
            # stats/boundary only in execute(), which the task drivers
            # bypass — drop this fragment's stale entries (stats AND the
            # boundary sinks cache_hits ride on) and stamp fragment-relative
            # node paths + estimates so the task snapshot ships exactly THIS
            # task's actuals
            for _nid in _subtree_ids(node):
                ex.stats.pop(_nid, None)
                ex.boundary.pop(_nid, None)
            ex.begin_plan(node)

            def tick(t=token):
                # preemption point doubles as the kill checkpoint: a query
                # the cluster policy poisoned dies here even between
                # reservations (reference: driver yield + query-killed check)
                self.memory_pool.check_killed()
                self.scheduler.tick(t)

            try:
                with self._wlock:
                    self._executing += 1
                    self.peak_concurrency = max(self.peak_concurrency,
                                                self._executing)
                    self._running_queries[xdir] = \
                        self._running_queries.get(xdir, 0) + 1
                kind = req.get("kind", "partial_agg")
                # worker half of the cluster counter flow: the task body runs
                # under its own QueryCounters + a task root span, so every
                # _jit dispatch / _host pull on this worker is attributed and
                # shippable back to the coordinator
                counters = QueryCounters()
                # stitched traces (round 16): the coordinator propagates the
                # QUERY's trace id in the task request, so this task's span
                # tree records under it (one trace per query, not one per
                # task — the pod-as-one-machine view); tasks without a trace
                # field (old coordinators, direct drivers) keep trace_id=tid
                trace_req = req.get("trace") or {}
                qtrace = str(trace_req.get("trace_id") or tid)
                # track_inflight: this task's dispatches/pulls register on
                # the WORKER's registry (per-node stall attribution);
                # query_scope tags the entries with the task id so a stall
                # report names the wedged task
                with tracing.track_inflight(self.inflight), \
                        tracing.query_scope(tid), \
                        tracing.activate_tracer(self.tracer), \
                        self.tracer.span("task", trace_id=qtrace, task=tid,
                                         kind=kind, node=self.node_id) \
                        as task_span, \
                        tracing.track_counters(counters), \
                        self.memory_pool.query_scope(xdir):
                    # chaos chokepoint: the worker task body.  kill_worker
                    # simulates a crashed node (HTTP goes dark, heartbeats
                    # fail, the coordinator re-dispatches elsewhere on its
                    # backoff curve); error/fatal fail just this task.
                    act = faults.maybe_inject("task", f"{kind}.{tid}")
                    if act == "kill_worker":
                        self._simulate_crash()
                        raise InjectedFaultError(
                            f"injected worker crash during task {tid}")
                    if kind == "partial_agg":
                        data = run_partial_aggregate(ex, node, req["splits"],
                                                     xdir, sources, fetch,
                                                     tick=tick)
                    elif kind == "stream_splits":
                        data = run_stream_splits(
                            ex, node, xdir, req["splits"], sources, fetch,
                            sink=buf.add if buf is not None else None,
                            tick=tick)
                    elif kind == "fragment":
                        data = run_fragment(ex, node, xdir, sources, fetch)
                    else:
                        raise ValueError(f"unknown task kind {kind!r}")
                # snapshot BEFORE the output becomes visible: a coordinator
                # that just observed the commit must find the stats populated
                st.plan_stats = self._collect_task_plan_stats(node, ex)
                st.counters = counters.as_dict()
                # ship exactly THIS task's span subtree: several tasks of one
                # query on one worker share the query trace id, so a flat
                # spans_for(trace) would double-ship sibling tasks' spans on
                # every harvest
                st.spans = [tracing.span_dict(s)
                            for s in _span_subtree(self.tracer, qtrace,
                                                   task_span.span_id)]
                if stream_out:
                    # pipelined output: pages live in the in-memory buffer
                    # behind the long-poll endpoint; nothing touches disk
                    if data:
                        buf.add(data)
                    buf.finish()
                else:
                    SpoolingExchange(xdir).commit(
                        req["task_id"], req.get("attempt", 0), data)
                st.state = "done"
            except Exception as e:
                st.state = "failed"
                if st.counters is None and "counters" in locals():
                    st.counters = counters.as_dict()  # partial spend: still real
                # streaming no longer forces non-retryable: the coordinator
                # replays the streaming subtree (fresh producers) on retry
                st.retryable = is_retryable_failure(e)
                st.error = f"{type(e).__name__}: {e}\n{traceback.format_exc()}"
                if buf is not None:
                    buf.fail(st.error)
            finally:
                with self._wlock:
                    self._executing -= 1
                    self._running_tasks -= 1
                    n = self._running_frags.get(frag_id, 1) - 1
                    if n <= 0:
                        self._running_frags.pop(frag_id, None)
                    else:
                        self._running_frags[frag_id] = n
                    nq = self._running_queries.get(xdir, 1) - 1
                    if nq <= 0:
                        self._running_queries.pop(xdir, None)
                        # last task of the query on this node: drop its
                        # attribution + poison entries (compiled-state caches
                        # may still hold device memory; they free through
                        # forget_plan eviction, tracked under op tags)
                        self.memory_pool.clear_query(xdir)
                    else:
                        self._running_queries[xdir] = nq
                ex.dispatch_batch = None  # per-task settings; executor is pooled
                ex.page_cache = None
                # no prefetch producer outlives its task: the executor is
                # re-pooled the moment this releases, and a stranded producer
                # from a FAILED task would race the next task's scan
                ex.close_producers()
                self._release_executor(ex, token=token)

        threading.Thread(target=run, daemon=True).start()

    def _simulate_crash(self) -> None:
        """Chaos ``kill_worker`` action: make this worker look CRASHED, not
        drained — the HTTP server stops answering (status polls and heartbeat
        probes fail, so the failure detector marks the node dead on its
        backoff schedule) while the process and its in-flight task threads
        live on, exactly like a wedged host whose socket died."""
        self._stop.set()  # halt the announce loop
        with self._wlock:
            self._draining = True  # refuse anything that still gets through
        httpd = self._httpd
        if httpd is not None:
            try:
                httpd.shutdown()
                httpd.server_close()
            except Exception:
                pass

    # -- graceful shutdown (reference: server/GracefulShutdownHandler.java:
    # SHUTTING_DOWN gates new work, active tasks drain, then the process
    # exits; the coordinator drains the node out of scheduling on its next
    # announce/heartbeat) ------------------------------------------------------
    def shutdown_gracefully(self, poll: float = 0.1) -> None:
        if self._draining:
            return
        self._draining = True

        def drain():
            while True:
                with self._wlock:
                    if self._running_tasks == 0:
                        break
                time.sleep(poll)
            # halt the periodic announce loop BEFORE 'gone': a
            # shutting_down announce landing after it would re-register
            # the departed worker as a ghost entry
            self._stop.set()
            if self.coordinator_url:  # final notice: leave the cluster NOW
                try:
                    _http(f"{self.coordinator_url}/v1/announce",
                          json.dumps({"node_id": self.node_id,
                                      "url": self.url,
                                      "state": "gone"}).encode(),
                          secret=self.secret)
                except Exception:
                    pass  # heartbeats will notice eventually
            self.stop()

        threading.Thread(target=drain, daemon=True).start()


# ---------------------------------------------------------------------------- coordinator
@dataclasses.dataclass
class _WorkerInfo:
    node_id: str
    url: str
    last_seen: float
    misses: int = 0
    alive: bool = True
    draining: bool = False  # graceful shutdown: reachable but not schedulable
    mem_reserved: int = 0  # last announced pool reservation (bytes)
    mem_max: int = 0  # last announced pool capacity (bytes)
    mem_by_query: dict = dataclasses.field(default_factory=dict)  # per-query
    # attribution from the worker pool (feeds the low-memory kill policy)
    # round 8: the worker's self-reported stall verdict.  degraded = the
    # worker's watchdog says a device-boundary operation is wedged — its
    # HTTP thread still answers (so `alive` stays True and running streams
    # keep draining / retrying) but NEW tasks schedule elsewhere
    health: str = "ok"
    degraded: bool = False
    inflight: int = 0  # worker-reported in-flight depth (observability)
    stall_report: Optional[dict] = None  # last report seen on a heartbeat
    # round 10: heartbeat probes of a FAILING worker back off exponentially
    # (deterministic jitter seeded from the node id) instead of paying a
    # fixed-interval 2s timeout against a dead node every pass — the probe
    # is skipped until next_probe; success resets it to "every interval"
    next_probe: float = 0.0


class ClusterCoordinator:
    """Coordinator process: accepts worker announcements, detects failures by
    heartbeat, plans queries, dispatches scan-fed aggregation fragments as
    remote tasks, merges spooled partials, finishes the plan locally."""

    def __init__(self, engine, spool_dir: str, host: str = "127.0.0.1",
                 port: int = 0, heartbeat_interval: float = 0.5,
                 max_misses: int = 3, max_attempts: int = 3,
                 splits_per_task: int = 2, task_timeout: float = 120.0,
                 secret: Optional[str] = None,
                 speculative_factor: float = 3.0,
                 stream_exchange: bool = True,
                 low_memory_killer=None,
                 retry_backoff_s: float = 0.25,
                 retry_backoff_cap_s: float = 5.0,
                 max_query_retries: int = 16):
        # stream_exchange: nested fragments ship their output through
        # in-memory worker buffers (long-poll + token ack) instead of the
        # spool — the reference's default PIPELINED data plane.  Single-task
        # consumers read reader slot 0; split-FANOUT consumers read a
        # broadcast buffer (n_readers = task count, one reader slot per
        # consumer task).  A failed streaming task retries by REPLAYING its
        # producer chain: fresh dedicated producers re-execute (outputs are
        # deterministic, the FTE invariant), the dead reader slot is
        # abandoned on any surviving old producer, and first-commit-wins
        # dedup absorbs stragglers from the earlier attempt (reference:
        # HttpPageBufferClient + DeduplicatingDirectExchangeBuffer).
        self.stream_exchange = stream_exchange
        self._stream_pending: dict = {}  # id(plan node) -> substituted frag
        self._stream_producers: dict = {}  # task_id -> replay record
        self.streamed_tasks = 0  # observability: producers launched streaming
        self.stream_retries = 0  # observability: replayed producer chains
        self.broadcast_streams = 0  # observability: fan-out producers launched
        self.local_fallbacks = 0  # observability: queries degraded to local
        self.last_fallback_error: Optional[str] = None  # why (traceback)
        # cluster low-memory kill policy (reference:
        # ClusterMemoryManager.java:92 + LowMemoryKiller): consulted from the
        # heartbeat loop once a node has sat blocked for two consecutive
        # passes (debounce — transient spikes resolve via Grace fallbacks)
        from ..execution.memory_killer import \
            TotalReservationOnBlockedNodesKiller

        self.low_memory_killer = low_memory_killer \
            if low_memory_killer is not None \
            else TotalReservationOnBlockedNodesKiller()
        self._blocked_streak = 0
        self.oom_kills = 0  # observability: victims chosen
        self.last_oom_victim: Optional[str] = None
        # the escalation ladder's record (round 11): per-pass rung decisions
        # ({"rung": "evict-cache"|"kill", ...}, bounded) and the rung each
        # affected query landed on (victims -> "kill") — "the chosen rung
        # recorded per query"; spill/queue rungs live on the query counters
        self.pressure_events: list = []
        self.query_pressure_rung: dict = {}
        self._pressure_cap = 64
        self.engine = engine
        self.spool_dir = spool_dir
        self.secret = secret if secret is not None \
            else _os.environ.get("TRINO_TPU_CLUSTER_SECRET")
        if self.secret is None and host not in _LOOPBACK:
            raise ValueError(
                f"refusing to serve unauthenticated announcements on {host}: "
                "set TRINO_TPU_CLUSTER_SECRET (or pass secret=) to bind "
                "beyond loopback")
        self.host, self.port = host, port
        self.workers: dict[str, _WorkerInfo] = {}
        self.max_workers = 256  # announce registry bound (untrusted input)
        self.heartbeat_interval = heartbeat_interval
        self.max_misses = max_misses
        self.max_attempts = max_attempts
        self.splits_per_task = splits_per_task
        self.task_timeout = task_timeout
        # straggler mitigation: once every task of a fragment is dispatched, a
        # task running longer than speculative_factor x the median completed
        # duration re-dispatches to ANOTHER worker; first-commit-wins dedup
        # keeps duplicates harmless (reference: TaskExecutionClass.java's
        # SPECULATIVE class in the FTE scheduler)
        self.speculative_factor = speculative_factor
        self.speculative_tasks = 0  # observability counter
        # round 10: re-dispatch backoff + per-query retry budget.  A retried
        # task waits _backoff_s(task_id, attempt) before re-offering (spacing
        # GROWS per attempt, jitter deterministic from the task id), and a
        # query whose tasks burn more than max_query_retries retries IN TOTAL
        # fails with the budget in the error — immediate fixed-interval
        # retries against a sick cluster were indistinguishable from a hang.
        self.retry_backoff_s = retry_backoff_s
        self.retry_backoff_cap_s = retry_backoff_cap_s
        self.max_query_retries = max_query_retries
        self._query_retries = 0  # retries burned by the CURRENT query
        self.last_retry_schedule: list = []  # (task_id, attempt, backoff_s)
        # per query — the chaos suite asserts spacing grows
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._query_lock = threading.Lock()  # one distributed query at a time
        self._exchange_seq = 0
        # long-lived executor + sql->plan cache: repeated queries reuse one
        # plan object, so the id(node)-keyed compiled-pipeline caches hit
        # instead of re-tracing per query
        # shares the engine's buffer pool: the coordinator's local finish
        # (and the all-workers-degraded local fallback) caches like any
        # pooled executor, and the per-query page_cache stash below applies
        self._local = LocalExecutor(engine.catalogs,
                                    buffer_pool=engine.buffer_pool)
        self._compile_lock = threading.Lock()  # shared-executor stream compiles
        self._query_abort = threading.Event()  # fail-fast across sibling stages
        from collections import OrderedDict

        # (sql, catalog) -> (plan, version snapshot): same identity + staleness
        # rules as Engine._plan_cache, plus an LRU bound (the coordinator is a
        # long-lived process; an unbounded text-keyed dict pins one compiled
        # pipeline set per distinct query string forever)
        self._plan_cache: OrderedDict = OrderedDict()
        self._plan_cache_max = 128
        # cluster-wide per-query profile: worker task counters merge here as
        # their commits are observed (plus the coordinator's own local spend),
        # published per query as last_query_counters and folded into
        # engine.counters_total so /v1/metrics sees the whole cluster
        self.last_query_counters = QueryCounters()
        self.last_query_worker_spans: list = []
        # stitched distributed trace (round 16): the coordinator opens ONE
        # root span per query on the ENGINE's tracer, ships its trace id +
        # root span id inside every task request, and re-parents harvested
        # worker spans under it at harvest time (worker span ids are remapped
        # through the engine tracer's id space — two workers' local ids
        # collide otherwise).  last_query_trace is the engine-shaped payload
        # (query_id, root_span_s, spans incl. stitched worker spans,
        # wall_breakdown) GET /v1/query/{id}/trace and the flight record
        # serve for distributed queries.
        self.last_query_trace: dict = {}
        self._trace_qid = None  # set under _query_lock per query
        self._trace_parent = None  # coordinator root span id (int)
        self.stitched_spans_total = 0  # observability: worker spans stitched
        self._qc_workers = QueryCounters()
        self._qc_children: list = []  # sibling-stage threads' coordinator-side
        # counters (thread-local recording: each dispatch thread tracks its
        # own and the query merge folds them in)
        self._worker_spans: list = []
        self._harvested: set = set()  # task ids already merged this query
        self._task_plan_stats: dict = {}  # task id -> fragment-relative
        # plan-actuals records harvested with the task counters (round 15)
        self._task_walls: dict = {}  # worker url -> [task wall s] (round 20)
        self._fragment_rows: dict = {}  # id(node) -> nested-fragment rows

    # -- lifecycle ---------------------------------------------------------------
    def start(self) -> str:
        coord = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def _reply(self, code, obj):
                body = json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_POST(self):
                if self.path == "/v1/announce":
                    n = int(self.headers.get("Content-Length", 0))
                    body = self.rfile.read(n)
                    if coord.secret is not None:
                        # registration feeds the scheduler: a poisoned entry
                        # burns task attempts, so announcements authenticate
                        # with the same cluster secret as task dispatch
                        got = self.headers.get("X-Trino-Internal-Signature", "")
                        if not hmac.compare_digest(got,
                                                   _sign(coord.secret, body)):
                            return self._reply(403, {"error": "bad signature"})
                    msg = json.loads(body)
                    coord._announce(msg["node_id"], msg["url"],
                                    msg.get("state", "active"),
                                    msg.get("mem_reserved"),
                                    msg.get("mem_max"),
                                    health=msg.get("health"),
                                    inflight=msg.get("inflight"))
                    return self._reply(200, {"ok": True})
                self._reply(404, {"error": "not found"})

            def do_GET(self):
                if self.path == "/v1/nodes":
                    with coord._lock:
                        nodes = [{"node_id": w.node_id, "url": w.url,
                                  "alive": w.alive, "health": w.health,
                                  "degraded": w.degraded,
                                  "inflight": w.inflight} for w in
                                 coord.workers.values()]
                    return self._reply(200, {"nodes": nodes})
                if self.path == "/v1/memory":
                    # cluster-wide memory view (reference:
                    # memory/ClusterMemoryManager.java:92 polling worker
                    # pools into one aggregate the kill policy reads)
                    return self._reply(200, coord.cluster_memory())
                self._reply(404, {"error": "not found"})

        self._httpd = ThreadingHTTPServer((self.host, self.port), Handler)
        self.port = self._httpd.server_port
        threading.Thread(target=self._httpd.serve_forever, daemon=True).start()
        threading.Thread(target=self._heartbeat_loop, daemon=True).start()
        return f"http://{self.host}:{self.port}"

    def stop(self):
        self._stop.set()
        if self._httpd:
            self._httpd.shutdown()

    def cluster_memory(self) -> dict:
        """Aggregate worker pool state (ClusterMemoryManager's cluster view);
        workers report through their periodic announces, so this is poll-free
        on the read path."""
        with self._lock:
            per = [{"node_id": w.node_id, "mem_reserved": w.mem_reserved,
                    "mem_max": w.mem_max, "alive": w.alive}
                   for w in self.workers.values()]
        live = [w for w in per if w["alive"]]
        return {"workers": per,
                "total_reserved": sum(w["mem_reserved"] for w in live),
                "total_max": sum(w["mem_max"] for w in live),
                "blocked_nodes": [w["node_id"] for w in live
                                  if w["mem_max"]
                                  and w["mem_reserved"] > 0.9 * w["mem_max"]]}

    def _announce(self, node_id: str, url: str, state: str = "active",
                  mem_reserved=None, mem_max=None, health=None, inflight=None):
        with self._lock:
            if state == "gone":  # graceful exit: leave the cluster NOW
                self.workers.pop(node_id, None)
                return
            draining = (state == "shutting_down")
            w = self.workers.get(node_id)
            if w is None:
                if len(self.workers) >= self.max_workers:
                    # shed long-dead entries before refusing a fresh node
                    for nid in [n for n, i in self.workers.items()
                                if not i.alive]:
                        self.workers.pop(nid)
                if len(self.workers) >= self.max_workers:
                    return
                w = self.workers[node_id] = _WorkerInfo(
                    node_id, url, time.time(), draining=draining)
            else:
                w.url, w.last_seen, w.misses, w.alive = url, time.time(), 0, True
                # a recovered worker must be probe-able again NOW — a stale
                # backoff deadline would blind the failure detector to a
                # second death for the rest of the window
                w.next_probe = 0.0
                w.draining = draining
            if mem_reserved is not None:
                w.mem_reserved = int(mem_reserved)
            if mem_max is not None:
                w.mem_max = int(mem_max)
            if health is not None:
                w.health = str(health)
                w.degraded = (w.health == "stalled")
            if inflight is not None:
                w.inflight = int(inflight)

    def _heartbeat_loop(self):
        """HeartbeatFailureDetector (simplified): probe /v1/info; max_misses
        consecutive failures gates the worker out of scheduling.  The same
        pass feeds the cluster memory view and, after a debounced blocked
        streak, the low-memory kill policy."""
        while not self._stop.is_set():
            with self._lock:
                snapshot = list(self.workers.values())
            for w in snapshot:
                if w.next_probe > time.time():
                    # failing worker: probe on its backoff schedule, not every
                    # pass — a dead node otherwise costs a 2s connect timeout
                    # per heartbeat forever
                    continue
                try:
                    info = json.loads(_http(f"{w.url}/v1/info", timeout=2.0))
                    with self._lock:
                        w.misses, w.alive, w.last_seen = 0, True, time.time()
                        w.next_probe = 0.0
                        w.draining = info.get("state") == "shutting_down"
                        if "mem_reserved" in info:
                            w.mem_reserved = int(info["mem_reserved"])
                            w.mem_max = int(info.get("mem_max", 0))
                        w.mem_by_query = info.get("mem_by_query") or {}
                        # the worker's self-reported stall verdict: a wedged
                        # worker whose HTTP thread still answers must NOT
                        # keep receiving tasks (reference: the failure
                        # detector reading node state, not socket liveness)
                        w.health = str(info.get("health", "ok"))
                        w.degraded = (w.health == "stalled")
                        w.inflight = int(info.get("inflight", 0) or 0)
                        if info.get("stall_report"):
                            rep = info["stall_report"]
                            # fold NEW worker stall reports into the engine's
                            # flight recorder (once per report — the worker
                            # re-ships the same dict every heartbeat while
                            # stalled), node-attributed: the cluster's
                            # post-mortems land in one durable ring
                            prev = w.stall_report or {}
                            if rep.get("detected_at_s") \
                                    != prev.get("detected_at_s"):
                                fr = getattr(self.engine, "flight_recorder",
                                             None)
                                if fr is not None:
                                    fr.record_event(dict(
                                        rep, kind="stall",
                                        node_id=w.node_id))
                            w.stall_report = rep
                except Exception:
                    with self._lock:
                        w.misses += 1
                        if w.misses >= self.max_misses:
                            w.alive = False
                        # exponential probe backoff, jitter seeded from the
                        # node id (deterministic; capped so a recovered node
                        # is re-admitted within a bounded window)
                        w.next_probe = time.time() + _backoff_s(
                            w.node_id, w.misses, self.heartbeat_interval,
                            max(self.heartbeat_interval * 16, 8.0))
            self._run_memory_killer()
            self._stop.wait(self.heartbeat_interval)

    def _run_memory_killer(self) -> None:
        """One ClusterMemoryManager pass, walking the escalation ladder:
        blocked nodes for one heartbeat -> wait (Grace fallbacks + the
        workers' own spill tiers get a beat); two -> ask the blocked nodes
        to SHED THEIR DEVICE CACHES (evict, the cheapest rung); three ->
        only then ask the policy for a victim and poison it on every live
        worker (reference: ClusterMemoryManager.java:92 callOomKiller —
        eviction + spill + queueing must have failed to free enough before
        anyone dies).  Each rung decision is recorded on pressure_events,
        and a victim's rung lands in query_pressure_rung."""
        from ..execution.memory_killer import BLOCKED_FRACTION

        with self._lock:
            nodes = [{"node_id": w.node_id, "url": w.url,
                      "mem_reserved": w.mem_reserved, "mem_max": w.mem_max,
                      "mem_by_query": w.mem_by_query}
                     for w in self.workers.values() if w.alive]
        blocked = [n for n in nodes
                   if n["mem_max"]
                   and n["mem_reserved"] > BLOCKED_FRACTION * n["mem_max"]]
        if not blocked:
            self._blocked_streak = 0
            return
        self._blocked_streak += 1
        if self._blocked_streak < 2:  # debounce: give Grace/spill a beat
            return
        if self._blocked_streak == 2:
            # rung: evict — shed the blocked nodes' buffer pools.  This
            # frees real device memory (the cache's labeled pool, not the
            # executor pool the blocked signal reads), so it relieves HBM
            # headroom for running queries and buys one more heartbeat of
            # debounce; an executor pool still blocked at streak 3 holds
            # LIVE per-query state that only a kill can free — the kill
            # proceeding then is correct, not a failed eviction
            self._record_pressure({"rung": "evict-cache",
                                   "nodes": [n["node_id"] for n in blocked]})
            for n in blocked:
                try:
                    _http(f"{n['url']}/v1/evict_cache", pickle.dumps({}),
                          secret=self.secret)
                except Exception:
                    pass  # an unreachable node is the failure detector's job
            return
        victim = self.low_memory_killer.pick_victim(nodes)
        if victim is None:
            return
        self._blocked_streak = 0
        with self._lock:
            self.oom_kills += 1
            self.last_oom_victim = victim
        self._record_pressure({"rung": "kill", "query": victim})
        for n in nodes:
            try:
                _http(f"{n['url']}/v1/kill_query",
                      pickle.dumps({"query_key": victim}),
                      secret=self.secret)
            except Exception:
                pass  # a dead worker frees its memory with its process

    def _record_pressure(self, event: dict) -> None:
        import time as _time

        with self._lock:
            event = dict(event, at=_time.time())
            self.pressure_events.append(event)
            del self.pressure_events[:-self._pressure_cap]
            if event["rung"] == "kill":
                self.query_pressure_rung[event["query"]] = "kill"
                while len(self.query_pressure_rung) > self._pressure_cap:
                    self.query_pressure_rung.pop(
                        next(iter(self.query_pressure_rung)))

    def live_workers(self) -> list:
        """Schedulable workers: alive, not draining, and not DEGRADED (a
        gracefully shutting-down node finishes its running tasks but takes
        no new ones — reference: NodeState.SHUTTING_DOWN excluded from
        scheduling).  A degraded worker (its watchdog reported a stalled
        in-flight entry) stays `alive` — status polls and stream drains keep
        working, the existing timeout/stream-RETRY paths recover its running
        tasks — but receives no new work until its verdict clears."""
        with self._lock:
            return [w for w in self.workers.values()
                    if w.alive and not w.draining and not w.degraded]

    def wait_for_workers(self, n: int, timeout: float = 20.0):
        deadline = time.time() + timeout
        while time.time() < deadline:
            if len(self.live_workers()) >= n:
                return
            time.sleep(0.1)
        raise TimeoutError(f"{n} workers not registered within {timeout}s")

    # -- distributed query -------------------------------------------------------
    # fragment roots: the SAME decomposition the in-process FTE uses (every
    # blocking node runs as remote task(s) whose inputs are replayable — leaf
    # scans from splits, interior fragments from children's spooled outputs)
    _FRAGMENT_NODES = FaultTolerantExecutor._FRAGMENT_NODES

    def execute_sql(self, sql: str, session=None, parameters=None):
        """Plan on the coordinator; schedule EVERY blocking fragment as remote
        tasks across live workers (scan-fed aggregates and join probes fan out
        by split batches; other fragments run as single tasks), with the
        spooled filesystem exchange between fragments; finish the streaming
        remainder locally (reference: SqlQueryExecution.planDistribution ->
        per-stage task scheduling, EventDrivenFaultTolerantQueryScheduler's
        spooled inter-stage exchange, SURVEY §3.2/§3.5).

        Round 12: the result cache is COORDINATOR-side — a repeated
        deterministic statement is answered from the engine's buffer-pool
        result tier before any fragment is scheduled (zero worker tasks,
        zero exchange traffic, zero dispatches), and a clean completion
        stores through the same engine guard the local path uses.

        Round 14: ``parameters`` (protocol-level EXECUTE) substitute as
        literals here — plan templates are a coordinator/local-engine
        optimization and the cluster task protocol does not ship bindings,
        so the distributed path runs the substituted text.

        Round 16: ONE trace per distributed query.  The coordinator opens
        the query root span on the engine's tracer, ships the trace context
        inside every task request, and harvested worker spans re-parent
        under the root (``last_query_trace``); completion — clean or errored
        — lands a flight record in the engine's recorder."""
        if parameters is not None:
            from .dbapi import _substitute

            sql = _substitute(sql, list(parameters))
        sess = session or self.engine.create_session(
            next(iter(self.engine.catalogs)))
        qid = f"cluster_{next(_cluster_qids)}"
        # clear the engine thread-accounting slot this statement will read at
        # publish time (a pooled caller thread may hold a previous
        # statement's snapshot)
        self.engine._thread_accounting.snap = None
        t_created = time.time()
        state, error = "FINISHED", None
        try:
            with tracing.query_scope(qid), \
                    tracing.activate_tracer(self.engine.tracer), \
                    self.engine.tracer.span("query", trace_id=qid, sql=sql):
                return self._execute_sql_admitted(sql, sess)
        except BaseException as e:
            state, error = "FAILED", f"{type(e).__name__}: {e}"
            # a failed CORRECTED execution demotes its correction (same
            # contract as engine.execute_sql); guarded — bookkeeping never
            # masks the real error
            try:
                ta = self.engine._thread_accounting
                if getattr(ta, "adaptive_corrected", False):
                    akey = getattr(ta, "adaptive_key", None)
                    adv = getattr(self.engine, "adaptive_advisor", None)
                    if adv is not None and akey is not None:
                        adv.failed(akey)
            except Exception:
                pass
            raise
        finally:
            self._publish_cluster_trace(qid, sql, sess, state, error,
                                        t_created)

    def _execute_sql_admitted(self, sql: str, sess):
        plan, adaptive = self._consulted_plan(sql, sess)
        rkey = self.engine._result_cache_key(sql, plan, sess)
        if rkey is not None and adaptive is not None \
                and adaptive.get("verdict") == "replan":
            # corrected results key separately, same contract as the local
            # path — a demotion must find the uncorrected entry intact
            rkey = rkey + (("adaptive", adaptive["token"]),)
        epoch = self.engine.buffer_pool.epoch if rkey is not None else None
        if rkey is not None:
            served = self.engine._result_cache_fetch(rkey)
            if served is not None:
                # the fetch accounted a hit-only counter set; mirror the
                # THREAD-LOCAL snapshot as this query's cluster profile
                # (engine.last_query_counters is shared state a concurrent
                # statement can overwrite between fetch and here)
                snap = self.engine._thread_accounting.snap
                if snap is not None:
                    with self._lock:
                        self.last_query_counters = snap
                return served
        out = self._execute_plan_cluster(plan, sess)
        self.engine._result_cache_finish(rkey, out, epoch=epoch)
        if rkey is not None:
            # the miss was stamped onto the engine's thread-local SNAPSHOT
            # (a copy taken by _account_counters) — mirror it so the
            # coordinator's per-query counters show misses like they show
            # hits, not an asymmetric zero
            snap = self.engine._thread_accounting.snap
            if snap is not None:
                with self._lock:
                    self.last_query_counters = snap
        return out

    def _execute_plan_cluster(self, plan, sess):
        import shutil

        from ..engine import _effective_dispatch_batch

        local = self._local
        with self._query_lock:  # overrides are executor-global
            # session dispatch-coalescing width: applied to the coordinator's
            # local finish AND shipped inside every task request so worker
            # executors coalesce the same way (queries serialize on
            # _query_lock, so the per-query stash is race-free)
            self._dispatch_batch = _effective_dispatch_batch(sess)
            adec = getattr(self.engine._thread_accounting, "adaptive", None)
            if adec is not None and adec.get("verdict") == "replan":
                # advisor-tuned coalescing width rides the SAME stash the
                # session property uses: applied to the local finish and
                # shipped inside every task request below
                k = (adec.get("corrections") or {}).get("dispatch_batch")
                if k:
                    self._dispatch_batch = int(k)
            local.dispatch_batch = self._dispatch_batch
            from ..engine import _effective_page_cache

            self._page_cache = _effective_page_cache(sess)
            local.page_cache = self._page_cache
            # stitched-trace context (round 16): the root span execute_sql
            # opened on THIS thread; task dispatch ships it so worker task
            # spans record under the query's trace id and harvest re-parents
            # them under this root.  None when a driver calls
            # _execute_plan_cluster directly (no root span): dispatch then
            # ships no trace field and worker spans pass through unstitched.
            self._trace_qid = tracing.current_query_id()
            _cur = self.engine.tracer.current()
            self._trace_parent = _cur.span_id \
                if (_cur is not None and self._trace_qid) else None
            # per-query cluster profile: worker counters merge in as commits
            # are observed; the finally below publishes coordinator + workers
            self._qc_workers = QueryCounters()
            self._qc_children = []
            self._worker_spans = []
            self._harvested = set()
            # harvested fragment-relative plan-actuals per task id (round
            # 15): folded into the engine's plan-history store at clean
            # completion, re-anchored at each fragment root's full-plan path
            self._task_plan_stats = {}
            # round 20: per-worker task walls (url -> [seconds]) observed at
            # commit detection — the straggler record in the finally below
            self._task_walls = {}
            self._fragment_rows = {}  # id(node) -> merged final row count
            # for NESTED fragment roots (consumed remotely, so never in the
            # local finish's overrides)
            # per-query retry budget + backoff schedule (queries serialize on
            # _query_lock, so plain resets are race-free)
            self._query_retries = 0
            self.last_retry_schedule = []
            try:
                if not self.live_workers():
                    out = local.execute(plan)
                    self.engine._record_plan_history(plan, local)
                    return out
                with self._lock:
                    self._exchange_seq += 1
                    seq = self._exchange_seq
                exchange_dir = _os.path.join(self.spool_dir,
                                             f"cluster_exchange_{seq}")
                exchange = SpoolingExchange(exchange_dir)
                self._task_seq = 0
                self._query_abort.clear()
                self._stream_pending = {}
                self._stream_producers = {}
                spooled: dict = {}  # id(node) -> (task_ids, node)
                self._mem_results = {}  # id(node) -> (page, dicts) merged locally
                local.counters.reset()
                try:
                    with tracing.track_counters(local.counters):
                        try:
                            self._exec_fragments(plan, exchange, exchange_dir,
                                                 spooled, nested=False)
                        except Exception as exc:
                            if "QueryKilledError" in str(exc):
                                # the cluster low-memory policy killed THIS
                                # query: rerunning it locally would defeat the
                                # kill (and likely OOM the coordinator too) —
                                # surface it
                                from ..memory import QueryKilledError

                                raise QueryKilledError(str(exc)) from exc
                            # a fragment the workers cannot run (unsupported
                            # shape, exhausted retries, cluster-wide death)
                            # must not fail a query the local executor can
                            # answer — degrade to local; genuine query errors
                            # re-raise from there identically
                            self.local_fallbacks += 1
                            self.last_fallback_error = traceback.format_exc()
                            local._overrides = {}
                            # local.execute resets local.counters: carry the
                            # coordinator-side spend already recorded for the
                            # failed fragment run into the final snapshot
                            pre = local.counters.snapshot()
                            out = local.execute(plan)
                            local.counters.merge(pre)
                            # a degraded-to-local run is still a clean local
                            # completion: feed the history store like the
                            # engine's own local path
                            self.engine._record_plan_history(plan, local)
                            return out
                        if not spooled:
                            pre = local.counters.snapshot()
                            out = local.execute(plan)
                            local.counters.merge(pre)
                            self.engine._record_plan_history(plan, local)
                            return out
                        overrides = {}
                        for nid in self._top_fragments(plan, spooled):
                            hit = self._mem_results.get(nid)
                            if hit is None:
                                task_ids, n = spooled[nid]
                                hit = read_fragment_outputs(exchange, task_ids,
                                                            n.schema)
                            overrides[nid] = hit
                        local._overrides = overrides
                        # plan-actuals scoping for the SHARED coordinator
                        # executor (stats/boundary reset only in execute(),
                        # which this path bypasses): drop this plan's stale
                        # entries — boundary too, or warm repeats would fold
                        # CUMULATIVE cache-hit sinks into each run's record —
                        # and stamp full-plan paths/estimates for the finish
                        for _nid in _subtree_ids(plan):
                            local.stats.pop(_nid, None)
                            local.boundary.pop(_nid, None)
                        local.begin_plan(plan)
                        out_page, dd = local._execute_to_page(plan)
                        out = _materialize(out_page, dd)
                        # clean cluster completion: coordinator local-finish
                        # stats + harvested worker records + fragment-root
                        # finals fold into the engine's plan-history store
                        self._record_cluster_history(plan, spooled, local,
                                                     overrides)
                        return out
                finally:
                    local._overrides = {}
                    self._mem_results = {}
                    # the coordinator drives _execute_to_page directly for
                    # the local finish: stop any prefetch producer the query
                    # started before releasing the shared executor
                    local.close_producers()
                    self._harvest_stream_producers()
                    shutil.rmtree(exchange_dir, ignore_errors=True)
            finally:
                # publish the merged cluster profile (coordinator local spend
                # + sibling-stage dispatch threads + every harvested worker
                # task) and fold it into the engine totals /v1/metrics reads
                merged = local.counters.snapshot()
                with self._lock:
                    for sub in self._qc_children:
                        merged.merge(sub)
                    merged.merge(self._qc_workers)
                    walls = {u: sum(ds)
                             for u, ds in self._task_walls.items()}
                    # round 20: one kind="task" straggler record built from
                    # the per-worker walls the commit poll already observed —
                    # coordinator-held state only, zero extra worker traffic.
                    # Load vector = summed task wall per worker url (ms ints
                    # so shard_skew's arithmetic applies unchanged).
                    if walls:
                        urls = sorted(walls)
                        rec = tracing.shard_skew(
                            [int(walls[u] * 1000.0) for u in urls])
                        wall = max(walls.values())
                        rec["site"] = "cluster.task.walls"
                        rec["kind"] = "task"
                        rec["wall_s"] = float(wall)
                        mx, mean = rec["max"], rec["mean"]
                        rec["imbalance_s"] = \
                            ((mx - mean) / mx * wall) if mx > 0 else 0.0
                        rec["labels"] = urls
                        merged.shard_stats.append(rec)
                        del merged.shard_stats[:-tracing.SHARD_STATS_MAX]
                    self.last_query_counters = merged
                    self.last_query_worker_spans = list(self._worker_spans)
                self.engine._account_counters(merged)

    def _record_cluster_history(self, plan, spooled, local,
                                overrides) -> None:
        """Fold one clean cluster execution's actuals into the engine's
        plan-history store under the FULL plan's fingerprint: the
        coordinator's local-finish stats, every harvested worker task's
        fragment-relative records re-anchored at its fragment root's
        full-plan path (split tasks of one fragment SUM — they partition one
        logical node's input), and each consumed fragment root's FINAL row
        count read from the override page the finish just materialized (the
        coordinator-merged count, which worker partials can't supply).
        Best-effort like every history feed: a failure here loses the
        record, never the query."""
        ph = getattr(self.engine, "plan_history", None)
        if ph is None or not ph.enabled:
            return
        try:
            from ..execution.history import (fold_records, plan_node_paths,
                                             translate_path)

            paths = plan_node_paths(plan)
            ests = getattr(local, "_node_ests", None) or {}
            extra: dict = {}
            with self._lock:
                task_stats = dict(self._task_plan_stats)
            for nid, (tids, node) in spooled.items():
                root_path = paths.get(id(node))
                if root_path is None:
                    continue
                root_chain = root_path.partition("#")[2]
                for tid in tids:
                    for rel, rec in (task_stats.get(tid) or {}).items():
                        fold_records(extra, translate_path(rel, root_chain),
                                     rec)
            # fragment-root FINALS: override pages the local finish consumed
            # (top fragments) and the merged row counts stashed when nested
            # fragment outputs spooled.  OVERWRITE, don't fold: worker
            # partial counts sum to more than the merged output (partial-agg
            # groups repeat per task).
            finals: dict = dict(self._fragment_rows)
            for nid, hit in (overrides or {}).items():
                try:
                    finals[nid] = int(hit[0].num_rows())
                except Exception:
                    pass
            for nid, rows in finals.items():
                root_path = paths.get(nid)
                if root_path is None:
                    continue
                rec = extra.setdefault(root_path, {
                    "op": root_path.partition("#")[0], "est_rows": None,
                    "actual_rows": 0, "wall_s": 0.0, "spilled_bytes": 0,
                    "spill_tiers": {}, "cache_hits": 0})
                rec["actual_rows"] = rows
            # worker-side estimates are fragment-blind (RemoteSource inputs
            # estimate unknown): backfill from the coordinator's full-plan
            # estimate map so harvested records carry ratios too
            est_by_path: dict = {}
            for nid, path in paths.items():
                v = ests.get(nid)
                if v is not None:
                    est_by_path.setdefault(path, v)
            for path, rec in extra.items():
                if rec.get("est_rows") is None:
                    rec["est_rows"] = est_by_path.get(path)
            self.engine._record_plan_history(plan, local,
                                             extra_records=extra)
        except Exception:
            pass

    # -- cluster counter flow --------------------------------------------------
    def _harvest_task_stats(self, worker_url: str, tid: str) -> None:
        """Pull a finished task's QueryCounters snapshot + spans from its
        worker and merge them into this query's cluster profile (best-effort:
        a worker that died after committing keeps its output but loses its
        stats).  Idempotent per task id — speculation and replay must not
        double-count a task's spend."""
        with self._lock:
            if tid in self._harvested:
                return
        try:
            st = json.loads(_http(f"{worker_url}/v1/task/{tid}", timeout=2.0))
        except Exception:
            return
        counters = st.get("counters")
        with self._lock:
            if tid in self._harvested:
                return
            if counters is None and st.get("state") == "running":
                # a speculated duplicate committed elsewhere while this
                # worker's attempt still runs: nothing to merge from here
                return
            self._harvested.add(tid)
            self._qc_workers.merge_dict(counters or {})
            ps = st.get("plan_stats")
            if ps:
                self._task_plan_stats[tid] = ps
            for s in self._stitch_spans(st.get("spans") or ()):
                self._worker_spans.append(s)

    def _stitch_spans(self, spans) -> list:
        """Re-key one harvested task's span dicts into the query's stitched
        trace (round 16): trace id becomes the QUERY's, span ids remap
        through the ENGINE tracer's id space (two workers' local id
        sequences collide), and task roots re-parent under the coordinator's
        root span — the "every worker task span carries the query's trace id
        and parents under the query root" invariant.  Without trace context
        (a driver calling _execute_plan_cluster directly) spans pass through
        untouched.  Caller holds self._lock."""
        qid, parent = self._trace_qid, self._trace_parent
        if qid is None or parent is None:
            return [dict(s) for s in spans]
        idmap = {s.get("span_id"): self.engine.tracer._new_id()
                 for s in spans}
        out = []
        for s in spans:
            d = dict(s)
            d["trace_id"] = qid
            d["span_id"] = idmap[s.get("span_id")]
            d["parent_id"] = idmap.get(s.get("parent_id"), parent)
            out.append(d)
        self.stitched_spans_total += len(out)
        return out

    def _publish_cluster_trace(self, qid, sql, sess, state, error,
                               t_created) -> None:
        """Assemble the query's ONE stitched trace (coordinator spans +
        re-parented worker spans), decompose its wall (retry-backoff sleeps
        come from the dispatch loop's recorded schedule), publish it as
        ``last_query_trace``, and land the flight record.  Guarded end to
        end: trace/record assembly failure never fails the query."""
        try:
            spans = [tracing.span_dict(s)
                     for s in self.engine.tracer.spans_for(qid)]
            with self._lock:
                wspans = [s for s in self.last_query_worker_spans
                          if s.get("trace_id") == qid]
                # retry schedule belongs to the query that DISPATCHED it:
                # a result-cache hit (or a failure before dispatch) leaves
                # the previous query's schedule in place — _trace_qid only
                # matches when _execute_plan_cluster ran for THIS query
                backoff = sum(d for _t, _a, d in self.last_retry_schedule) \
                    if self._trace_qid == qid else 0.0
                counters = self.last_query_counters.snapshot()
            spans += wspans
            root = next((s for s in spans if s.get("parent_id") is None
                         and s.get("name") == "query"), None)
            bd = tracing.wall_breakdown(spans, retry_backoff_s=backoff)
            root_s = None
            if root is not None and root.get("end_s") is not None:
                root_s = root["end_s"] - root["start_s"]
            trace = {"query_id": qid, "root_span_s": root_s, "spans": spans}
            if bd is not None:
                trace["wall_breakdown"] = bd
            self.last_query_trace = trace
            fr = getattr(self.engine, "flight_recorder", None)
            if fr is None or not fr.enabled:
                return
            from ..execution.flightrecorder import pressure_rung
            from ..sql.params import normalize_sql

            snap = self.engine._thread_accounting.snap
            cd = (snap.as_dict() if snap is not None
                  else counters.as_dict())
            try:
                norm = normalize_sql(sql)
            except Exception:
                norm = sql
            fr.record_query({
                "query_id": qid, "state": state, "sql": norm,
                "user": sess.user, "catalog": sess.catalog,
                "error": error, "created_s": t_created,
                "ended_s": time.time(),
                "wall_s": time.time() - t_created,
                "queued_s": 0.0,
                "distributed": True,
                "counters": cd,
                "worker_spans": len(wspans),
                "retry_backoff_s": backoff,
                "pressure_rung": pressure_rung(cd),
                "trace": {"root_span_s": root_s, "spans": spans},
                "wall_breakdown": bd,
            })
        except Exception:
            pass

    def _harvest_stream_producers(self) -> None:
        """Streaming producers commit no spool entry, so the dispatch loop
        never observes them — collect their stats at query end (they have
        finished by then: their consumers drained).  Workers the failure
        detector already gated out are skipped: best-effort stats must not
        add a per-dead-worker HTTP timeout to a query that has its answer."""
        with self._lock:
            producers = list(self._stream_producers.items())
            dead = {w.url for w in self.workers.values() if not w.alive}
        for tid, rec in producers:
            if rec["url"] in dead:
                continue
            self._harvest_task_stats(rec["url"], tid)

    # -- fragment scheduling -----------------------------------------------------
    def _exec_fragments(self, node, exchange, exchange_dir, spooled,
                        nested: bool) -> None:
        """Bottom-up: schedule every blocking fragment's tasks; descendants'
        outputs are already spooled, so each fragment plan replaces them with
        RemoteSource leaves (the PlanFragmenter's RemoteSourceNode).
        ``nested``: a fragment ancestor exists — this fragment's output will
        be consumed REMOTELY, so coordinator-merged results must spool."""
        child_nested = nested or isinstance(node, self._FRAGMENT_NODES)
        kids = list(node.children)
        if len(kids) > 1:
            # independent sibling subtrees (join sides, set-op inputs)
            # schedule CONCURRENTLY: their tasks interleave across workers
            # instead of one stage idling the cluster while the other runs
            # (reference: stages run in parallel under
            # PipelinedQueryScheduler; this walk previously serialized them)
            import concurrent.futures as _futures

            def run_child(c):
                # counter recording is thread-local: each sibling-stage thread
                # tracks its own coordinator-side spend (partial merges, spool
                # reads) and the query-end merge folds it in
                sub = QueryCounters()
                try:
                    with tracing.track_counters(sub):
                        self._exec_fragments(c, exchange, exchange_dir,
                                             spooled, child_nested)
                except BaseException:
                    # fail-fast: siblings stop dispatching instead of running
                    # their whole stage for a query that will be abandoned
                    self._query_abort.set()
                    raise
                finally:
                    with self._lock:
                        self._qc_children.append(sub)

            with _futures.ThreadPoolExecutor(max_workers=len(kids)) as pool:
                futs = [pool.submit(run_child, c) for c in kids]
                for f in futs:
                    f.result()
        else:
            for c in kids:
                self._exec_fragments(c, exchange, exchange_dir, spooled,
                                     child_nested)
        if not isinstance(node, self._FRAGMENT_NODES):
            return
        frag = self._substitute(node, spooled, root=True)
        if isinstance(node, P.Aggregate) and node.keys \
                and not any(s.kind in ("approx_percentile", "listagg",
                                       "approx_most_frequent")
                            for s in node.aggs):
            spine = self._scan_spine(frag.child)
            if spine is not None:
                # stream-pending children broadcast-stream into the fanout
                # tasks (one reader slot per task); with the fanout-stream
                # knob off they materialize through the spool instead
                task_ids = self._run_split_tasks(frag, spine, exchange_dir,
                                                 "partial_agg", fanout=node,
                                                 spooled=spooled)
                if task_ids is not None:
                    page, dicts = merge_partial_outputs(
                        frag, [exchange.read(t) for t in task_ids])
                    tid = self._next_tid()
                    if nested:
                        # a remote parent consumes this: spool the merged page
                        valid, pcols, pnulls = _host_page(page)
                        # plan-actuals: the merged fragment output's FINAL
                        # row count, free from the host mask this spool
                        # already pulled (nested roots never appear in the
                        # local finish's overrides)
                        self._fragment_rows[id(node)] = int(valid.sum())
                        cols = [c[valid] for c in pcols]
                        nulls = [None if (m is None or not m[valid].any())
                                 else m[valid] for m in pnulls]
                        exchange.commit(
                            tid, 0,
                            serialize_fragment_output(cols, nulls, dicts))
                    else:
                        # only the local finish reads it: skip the
                        # serialize/spool/deserialize round trip
                        self._mem_results[id(node)] = (page, dicts)
                    spooled[id(node)] = ((tid,), node)
                    return
        if isinstance(node, P.Join):
            spine = self._scan_spine(frag.left)
            if spine is not None:
                task_ids = self._run_split_tasks(frag, spine, exchange_dir,
                                                 "stream_splits", fanout=node,
                                                 spooled=spooled)
                if task_ids is not None:
                    spooled[id(node)] = (task_ids, node)
                    return
        if self.stream_exchange and nested:
            # single-task fragment with a remote consumer: DEFER — when the
            # consuming fragment dispatches, this one launches as a streaming
            # producer feeding the consumer's long-poll reads (pipelined
            # worker->worker exchange, no disk); a split-fanout consumer
            # materializes it through the spool instead
            tid = self._next_tid()
            self._stream_pending[id(node)] = frag
            spooled[id(node)] = ((tid,), node)
            return
        sources = self._dispatch_stream_tree(node, spooled, exchange_dir)
        task_ids = self._run_single_task(frag, exchange_dir, sources=sources)
        spooled[id(node)] = (task_ids, node)

    def _substitute(self, node, spooled, root=False):
        """Copy a subtree with spooled descendant fragments replaced by
        RemoteSource leaves."""
        if not root:
            hit = spooled.get(id(node))
            if hit is not None:
                return P.RemoteSource(tuple(hit[0]), node.schema)
        kids = tuple(self._substitute(c, spooled) for c in node.children)
        if all(k is c for k, c in zip(kids, node.children)):
            return node
        from ..sql.rules import _replace_children

        return _replace_children(node, kids)

    def _scan_spine(self, node):
        """The fragment's probe-side TableScan, reached through streaming
        nodes (Filter/Project and join probe sides) — the split-parallel
        spine.  Returns (scan, chain_top): ``chain_top`` is the highest node
        of the PURE Filter/Project chain directly over the scan, used to
        compile a cheap scan-only stream whose static split pruning
        (tuple-domain vs split stats) the dispatcher inherits.  None when the
        stream is fed by a RemoteSource (the fragment then runs as one task
        over the spooled input)."""
        return self._spine_walk(node)

    def _spine_walk(self, node):
        # (no Join case: every Join is itself a fragment root, so by the time
        # a fragment plan reaches here its joins are already RemoteSources)
        if isinstance(node, P.TableScan):
            return node, node
        if isinstance(node, (P.Filter, P.Project)):
            sub = self._spine_walk(node.child)
            if sub is None:
                return None
            scan, _ = sub
            return scan, node
        return None

    def _top_fragments(self, plan, spooled) -> list:
        """Fragment roots the LOCAL finish consumes (not nested under another
        fragment — nested ones are consumed remotely via RemoteSource)."""
        out: list = []

        def walk(n):
            if id(n) in spooled:
                out.append(id(n))
                return
            for c in n.children:
                walk(c)

        walk(plan)
        return out

    def _next_tid(self) -> str:
        """Task ids under the lock: sibling fragments dispatch concurrently."""
        with self._lock:
            tid = f"t{self._task_seq}"
            self._task_seq += 1
            return tid

    def _trace_ctx(self):
        """The query's trace context as shipped in every /v1/task request
        (round 16): the trace id worker task spans record under plus the
        coordinator root span id harvest re-parents them to.  None outside a
        traced query (direct _execute_plan_cluster drivers)."""
        if self._trace_qid is None or self._trace_parent is None:
            return None
        return {"trace_id": self._trace_qid,
                "parent_span_id": self._trace_parent}

    def _run_split_tasks(self, frag, spine, exchange_dir, kind,
                         fanout=None, spooled=None):
        """Fan a fragment out across workers by split batches (reference:
        SourcePartitionedScheduler split placement + the dynamic-filter split
        pruning the scan-only stream compile provides).  Returns the task ids,
        or None for a zero-split source (caller degrades to a single task).
        ``fanout``/``spooled``: the original plan node — its stream-pending
        child fragments launch as BROADCAST producers (one reader slot per
        split task) instead of materializing through the spool."""
        scan, chain_top = spine
        splits = None
        try:
            # compiling ONLY the Filter/Project chain over the scan is cheap
            # (no join builds) and inherits the executor's tuple-domain split
            # pruning: a selective predicate ships fewer splits to workers
            with self._compile_lock:  # shared executor: one compile at a
                # time — NOT self._lock, which heartbeats/announce/dispatch
                # bookkeeping need while a trace runs
                stream = self._local._compile_stream(chain_top)
            if stream.scan_info is not None:
                splits = list(stream.scan_info.splits)
        except NotImplementedError:
            pass
        if splits is None:
            splits = list(self.engine.catalogs[scan.catalog].splits(scan.table))
        if not splits:
            return None
        n_tasks = (len(splits) + self.splits_per_task - 1) \
            // self.splits_per_task
        base_sources = None
        if fanout is not None and self._collect_pending(fanout, spooled):
            base_sources = self._stream_fanout_sources(
                fanout, spooled, exchange_dir, n_readers=n_tasks)
        tasks = []
        for i in range(n_tasks):
            tid = self._next_tid()
            sp = tuple(splits[j] for j in
                       range(i * self.splits_per_task,
                             min((i + 1) * self.splits_per_task, len(splits))))
            extra = {"splits": sp}
            if base_sources:
                extra["stream_sources"] = {
                    pt: {"url": u, "task": pt, "reader": i}
                    for pt, u in base_sources.items()}
            tasks.append((tid, extra))
        self._dispatch_tasks(frag, tasks, exchange_dir, kind)
        return tuple(t for t, _ in tasks)

    def _run_single_task(self, frag, exchange_dir, tid=None,
                         sources=None) -> tuple:
        tid = tid if tid is not None else self._next_tid()
        extra = {"stream_sources": sources} if sources else {}
        self._dispatch_tasks(frag, [(tid, extra)], exchange_dir, "fragment")
        return (tid,)

    # -- streaming (pipelined) exchange orchestration -------------------------
    def _collect_pending(self, node, spooled) -> list:
        """Directly stream-pending child fragments of the fragment rooted at
        ``node`` (walk stops at any materialized fragment boundary)."""
        out: list = []

        def walk(n):
            for c in n.children:
                if id(c) in self._stream_pending:
                    out.append(c)
                elif id(c) in spooled:
                    pass  # materialized boundary: its subtree is done
                else:
                    walk(c)

        walk(node)
        return out

    def _dispatch_stream_tree(self, node, spooled, exchange_dir) -> dict:
        """Launch every stream-pending descendant fragment of ``node`` as a
        streaming producer (deepest first — a pending fragment's own pending
        children stream INTO it), returning {task_id: producer worker url}
        for the consumer's fetches."""
        sources: dict = {}
        for c in self._collect_pending(node, spooled):
            frag = self._stream_pending.pop(id(c))
            child_sources = self._dispatch_stream_tree(c, spooled,
                                                       exchange_dir)
            tid = spooled[id(c)][0][0]
            url = self._dispatch_stream_producer(frag, tid, exchange_dir,
                                                 child_sources)
            sources[tid] = url
        return sources

    def _stream_fanout_sources(self, node, spooled, exchange_dir,
                               n_readers: int) -> dict:
        """Launch each directly-pending child fragment as a BROADCAST
        streaming producer whose buffer serves ``n_readers`` consumer tasks
        (reference: BroadcastOutputBuffer feeding a replicated-exchange
        consumer stage).  Returns {task_id: producer url}; the caller assigns
        one reader slot per consumer task."""
        sources: dict = {}
        for c in self._collect_pending(node, spooled):
            frag = self._stream_pending.pop(id(c))
            child_sources = self._dispatch_stream_tree(c, spooled,
                                                       exchange_dir)
            tid = spooled[id(c)][0][0]
            sources[tid] = self._dispatch_stream_producer(
                frag, tid, exchange_dir, child_sources, n_readers=n_readers)
            with self._lock:
                self.broadcast_streams += 1
        return sources

    def _dispatch_stream_producer(self, frag, tid, exchange_dir,
                                  sources, n_readers: int = 1) -> str:
        """Ship a fragment + streaming-output task to one worker WITHOUT
        waiting for completion — the consumer's long-poll reads drive overlap;
        delivery is confirmed by the consumer finishing (reference: pipelined
        stages run concurrently under PipelinedQueryScheduler).  Returns the
        producer's url.  Records a replay entry so a failed consumer can
        respawn the producer chain.

        INVARIANT the broadcast mode relies on: these producers run kind
        "fragment", which emits ONE envelope page (the first ``add`` into an
        empty buffer always succeeds regardless of size), so a reader set
        larger than the cluster's concurrent admission capacity cannot
        deadlock the producer against its max_bytes backpressure.  An
        INCREMENTAL multi-page producer (run_stream_splits' sink) must never
        be dispatched with n_readers > 1 without revisiting that backpressure
        (undispatched readers hold the retention floor at zero)."""
        live = self.live_workers()
        if not live:
            raise RuntimeError("no live workers")
        with self._lock:
            self._frag_seq = getattr(self, "_frag_seq", 0) + 1
            frag_id = f"frag_{self._frag_seq}"
        frag_blob = pickle.dumps({"fragment_id": frag_id, "plan": frag})
        req = {"task_id": tid, "fragment_id": frag_id, "kind": "fragment",
               "attempt": 0, "exchange_dir": exchange_dir,
               "output": "stream", "n_readers": n_readers,
               "trace": self._trace_ctx(),
               "dispatch_batch": getattr(self, "_dispatch_batch", None),
               "page_cache": getattr(self, "_page_cache", None)}
        if sources:
            req["stream_sources"] = sources
        last_err = None
        for w in live:
            try:
                _http(f"{w.url}/v1/fragment", frag_blob, secret=self.secret)
                _http(f"{w.url}/v1/task", pickle.dumps(req),
                      secret=self.secret)
                with self._lock:
                    self.streamed_tasks += 1
                    self._stream_producers[tid] = {
                        "frag": frag, "child_tids": list(sources or ()),
                        "exchange_dir": exchange_dir, "url": w.url}
                return w.url
            except Exception as e:  # busy/draining/unreachable: try the next
                last_err = e
        raise RuntimeError(f"no worker accepted streaming task {tid}: "
                           f"{last_err}")

    # -- streaming retry (replay) ---------------------------------------------
    def _replay_stream_sources(self, sources: dict, attempt: int,
                               consumer: str = "") -> dict:
        """A stream-consumer task failed mid-drain.  Its producers' buffers
        are partially acknowledged (pages already freed for its reader slot),
        so the retried consumer cannot re-read them: re-dispatch a FRESH
        dedicated producer chain per source — fragment outputs are
        deterministic (the same FTE invariant speculation relies on), so the
        replacement produces identical pages — and abandon the dead reader
        slot on any surviving old producer so its retention floor recovers.
        (Reference: HttpPageBufferClient failure handling +
        DeduplicatingDirectExchangeBuffer replay dedup.)"""
        new = {}
        for ptid, v in sources.items():
            old = v if isinstance(v, dict) \
                else {"url": v, "task": ptid, "reader": 0}
            self._abandon_reader(old)
            new[ptid] = self._respawn_producer(ptid, attempt, consumer)
        with self._lock:
            self.stream_retries += 1
        return new

    def _abandon_reader(self, src: dict) -> None:
        path = (f"/v1/task/{src.get('task')}/results/"
                f"{int(src.get('reader', 0))}/abandon")
        try:
            req = urllib.request.Request(src["url"] + path, data=b"",
                                         method="POST")
            if self.secret:
                req.add_header("X-Trino-Internal-Signature",
                               _sign(self.secret, path.encode()))
            urllib.request.urlopen(req, timeout=2.0).read()
        except Exception:
            pass  # best-effort: the old producer may be dead with its worker

    def _respawn_producer(self, ptid: str, attempt: int,
                          consumer: str = "") -> dict:
        """Fresh dedicated (n_readers=1) instance of producer ``ptid`` under a
        new task id, recursively respawning its own producer chain.  The id
        embeds the retried CONSUMER's task id: two consumers of one broadcast
        producer failing at the same attempt number must not collide on the
        respawned task id (a collision overwrites the worker's buffer and
        cross-drains reader 0)."""
        rec = self._stream_producers[ptid]
        child_sources = {c: self._respawn_producer(c, attempt, consumer)
                         for c in rec["child_tids"]}
        newtid = f"{ptid}~{consumer}a{attempt}"
        url = self._dispatch_stream_producer(rec["frag"], newtid,
                                             rec["exchange_dir"],
                                             child_sources, n_readers=1)
        return {"url": url, "task": newtid, "reader": 0}

    def _consulted_plan(self, sql: str, sess):
        """The adaptive advisor's cluster entry (round 19): consult on the
        coordinator's own statement key before planning — a frozen "replan"
        decision compiles and caches the CORRECTED plan under the decision
        token (corrected fragments then ship through the ordinary pickled-
        plan dispatch; workers execute what they receive, the decision never
        rides the task protocol).  Feedback slots on the engine's thread
        accounting mark the execution for the observe hook inside
        ``engine._record_plan_history`` — the cluster's clean completions
        already route through it.  Returns (plan, decision-or-None)."""
        from ..engine import _normalize_statement, _plan_shape_props

        eng = self.engine
        # cluster statements bypass engine.execute_sql: clear/claim the
        # thread slots here (same discipline, one-shot consumers)
        eng._thread_accounting.adaptive = None
        eng._thread_accounting.adaptive_key = None
        eng._thread_accounting.adaptive_corrected = False
        eng._thread_accounting.history_sql = sql
        key = (_normalize_statement(sql), sess.catalog, "cluster",
               sess.user, _plan_shape_props(sess))
        decision = eng._adaptive_consult(key, sess)
        if decision is None:
            eng._adaptive_note_base(key, sess)
            return self._cached_plan(sql, sess), None
        eng._thread_accounting.adaptive = decision
        replan = decision.get("verdict") == "replan"
        # the engine's execute_sql finally is not on this path: stamp the
        # decision counter directly on the engine totals
        field = "adaptive_replans" if replan else "adaptive_holds"
        with eng._init_lock:
            setattr(eng.counters_total, field,
                    getattr(eng.counters_total, field) + 1)
        if not replan:
            eng._adaptive_note_base(key, sess)
            return self._cached_plan(sql, sess), decision
        eng._thread_accounting.adaptive_key = key
        eng._thread_accounting.adaptive_corrected = True
        return self._cached_plan(sql, sess, adaptive=decision), decision

    def _cached_plan(self, sql: str, sess, adaptive=None):
        """Versioned, bounded plan cache keyed by (sql, catalog) — the same
        identity/staleness rules as Engine._cache_lookup (a plan embeds the
        session catalog's table resolution and dictionary LUTs).
        ``adaptive``: a frozen advisor "replan" decision — the key extends
        with the correction token and compilation runs under a session
        carrying the corrections (corrected and uncorrected plans never
        collide)."""
        from ..sql.frontend import compile_sql

        from ..engine import _plan_shape_props, _session_with_corrections

        key = (sql, sess.catalog, sess.user, _plan_shape_props(sess))
        if adaptive is not None:
            key = key + (("adaptive", adaptive["token"]),)
            sess = _session_with_corrections(
                sess, adaptive.get("corrections") or {})
        with self._lock:
            entry = self._plan_cache.get(key)
            if entry is not None:
                plan, versions = entry
                stale = any(
                    self.engine.catalogs.get(name) is None
                    or self.engine.catalogs[name].plan_version() != ver
                    for name, ver in versions)
                if stale:
                    self._plan_cache.pop(key, None)
                    self._local.forget_plan(plan)
                else:
                    self._plan_cache.move_to_end(key)
                    return plan
        plan = compile_sql(sql, self.engine, sess)
        with self._lock:
            raced = self._plan_cache.get(key)
            if raced is not None:
                # another thread compiled the same key meanwhile: keep ITS
                # entry (its compiled artifacts may already be in _local's
                # caches) and use it; our duplicate was never executed, so it
                # left nothing to forget
                return raced[0]
            self._plan_cache[key] = (plan, self.engine._plan_versions(plan))
            while len(self._plan_cache) > self._plan_cache_max:
                _, (old, _v) = self._plan_cache.popitem(last=False)
                self._local.forget_plan(old)
        return plan

    def _dispatch_tasks(self, frag_plan, tasks, exchange_dir, kind) -> None:
        """Dispatch a fragment's tasks across live workers and drive them to
        committed outputs: round-robin placement, status polling, timeout/
        death reassignment under an attempt budget, deterministic-failure
        fast-fail.  (Reference: HttpRemoteTask.java:137,743 — the fragment
        ships once per worker, split batches address it — plus the
        coordinator's task tracking.)  ``tasks``: [(task_id, extra_fields)]."""
        exchange = SpoolingExchange(exchange_dir)
        with self._lock:
            self._frag_seq = getattr(self, "_frag_seq", 0) + 1
            frag_id = f"frag_{self._frag_seq}"
        frag_blob = pickle.dumps({"fragment_id": frag_id, "plan": frag_plan})
        frag_sent: set = set()  # worker URLs (a restart changes the url)

        pending = dict(tasks)
        attempts: dict = {tid: 0 for tid, _ in tasks}
        refused_since: dict = {}  # tid -> first 429/503 of the current streak
        not_before: dict = {}  # tid -> earliest re-offer time (backoff)
        spin = 0  # placement rotation: re-offered tasks must try OTHER workers
        assigned: dict = {}  # task_id -> (worker, extra, deadline)
        started: dict = {}  # task_id -> dispatch time (speculation baseline)
        durations: list = []  # completed task durations this fragment
        speculated: set = set()

        def burn(tid: str, what: str) -> None:
            """One retry burned: bump the task's attempt, charge the QUERY's
            retry budget (surfaced in the error when exhausted), and schedule
            the re-offer on the exponential-backoff curve — replacing the
            old immediate fixed-interval re-dispatch."""
            attempts[tid] += 1
            tracing.record_task_retry(site="task.redispatch")
            with self._lock:
                self._query_retries += 1
                burned = self._query_retries
            if burned > self.max_query_retries:
                raise RuntimeError(
                    f"query retry budget exhausted: {burned} task retries > "
                    f"max_query_retries={self.max_query_retries} "
                    f"(last: task {tid} {what}, attempt {attempts[tid]})")
            if attempts[tid] >= self.max_attempts:
                raise RuntimeError(
                    f"task {tid} {what} after {attempts[tid]} attempts")
            delay = _backoff_s(tid, attempts[tid], self.retry_backoff_s,
                               self.retry_backoff_cap_s)
            not_before[tid] = time.time() + delay
            with self._lock:
                self.last_retry_schedule.append((tid, attempts[tid], delay))

        while pending or assigned:
            if self._query_abort.is_set():
                raise RuntimeError(
                    "sibling stage failed: aborting this stage's dispatch")
            # (re)assign pending tasks round-robin over live workers; the
            # fragment ships once per worker URL, tasks address it by id
            live = self.live_workers()
            if not live:
                raise RuntimeError("no live workers")
            spin += 1
            for i, (tid, extra) in enumerate(list(pending.items())):
                if not_before.get(tid, 0.0) > time.time():
                    continue  # backing off: re-offer when the window opens
                w = live[(i + spin) % len(live)]
                try:
                    if w.url not in frag_sent:
                        _http(f"{w.url}/v1/fragment", frag_blob,
                              secret=self.secret)
                        frag_sent.add(w.url)
                    req = pickle.dumps({"task_id": tid, "fragment_id": frag_id,
                                        "kind": kind,
                                        "attempt": attempts[tid],
                                        "exchange_dir": exchange_dir,
                                        "trace": self._trace_ctx(),
                                        "dispatch_batch":
                                            getattr(self, "_dispatch_batch",
                                                    None),
                                        "page_cache":
                                            getattr(self, "_page_cache",
                                                    None), **extra})
                    _http(f"{w.url}/v1/task", req, secret=self.secret)
                    assigned[tid] = (w, extra, time.time() + self.task_timeout)
                    started[tid] = time.time()
                    refused_since.pop(tid, None)
                    del pending[tid]
                except urllib.error.HTTPError as he:
                    if he.code in (429, 503):
                        # backpressure/draining, not failure: leave the task
                        # pending; the next loop pass re-offers it (likely to
                        # another worker as the rotation advances).  Sustained
                        # refusal past task_timeout burns an attempt so a
                        # permanently-full cluster cannot spin this loop
                        # forever
                        t0 = refused_since.setdefault(tid, time.time())
                        if time.time() - t0 > self.task_timeout:
                            refused_since.pop(tid, None)
                            burn(tid, "refused by every worker")
                        continue
                    frag_sent.discard(w.url)
                    burn(tid, "failed to dispatch")
                    continue
                except Exception:
                    # unreachable worker, or 409 after a restart/fragment
                    # eviction: the fragment must re-ship.  The failure also
                    # counts as a missed heartbeat so a dead worker gates out
                    # of scheduling IMMEDIATELY instead of the dispatch loop
                    # burning the whole attempt budget against it before the
                    # detector notices; a worker that stays alive (reachable
                    # but broken) still burns an attempt so a permanently
                    # broken worker set cannot spin this loop forever.
                    frag_sent.discard(w.url)
                    with self._lock:
                        w.misses += 1
                        if w.misses >= self.max_misses:
                            w.alive = False
                        still_alive = w.alive
                    if still_alive:
                        burn(tid, "failed to dispatch")
                    continue
            # poll assigned tasks
            time.sleep(0.05)
            for tid, (w, extra, deadline) in list(assigned.items()):
                if exchange.is_committed(tid):
                    if tid not in speculated:
                        # rescued stragglers would inflate the median and
                        # weaken later straggler detection
                        dur = time.time() - started.get(tid, time.time())
                        durations.append(dur)
                        # round 20: per-worker wall accumulation feeds the
                        # kind="task" straggler record at query completion —
                        # coordinator-held state only, no new worker traffic
                        with self._lock:
                            self._task_walls.setdefault(w.url,
                                                        []).append(dur)
                    # worker-side counters ride back on the status response
                    # the moment the commit is visible (the snapshot is
                    # stored pre-commit on the worker)
                    self._harvest_task_stats(w.url, tid)
                    del assigned[tid]
                    continue
                # speculation: every task dispatched, siblings finishing, this
                # one a straggler -> duplicate it on a DIFFERENT worker (the
                # spool dedups whichever commit lands second)
                if not pending and durations and tid not in speculated \
                        and "stream_sources" not in extra:
                    # (a speculated stream consumer would double-drain the
                    # producer's ack-once buffer)
                    med = sorted(durations)[len(durations) // 2]
                    if time.time() - started.get(tid, 0) \
                            > self.speculative_factor * max(med, 0.2):
                        others = [o for o in self.live_workers()
                                  if o.url != w.url]
                        if others:
                            o = others[(len(speculated))
                                       % len(others)]
                            try:
                                if o.url not in frag_sent:
                                    _http(f"{o.url}/v1/fragment", frag_blob,
                                          secret=self.secret)
                                    frag_sent.add(o.url)
                                req = pickle.dumps(
                                    {"task_id": tid, "fragment_id": frag_id,
                                     "kind": kind,
                                     "attempt": attempts[tid] + 100,
                                     "trace": self._trace_ctx(),
                                     "exchange_dir": exchange_dir, **extra})
                                _http(f"{o.url}/v1/task", req,
                                      secret=self.secret)
                                speculated.add(tid)
                                with self._lock:
                                    self.speculative_tasks += 1
                            except Exception:
                                # best-effort, but a failed ship means the
                                # fragment must re-send next time (409 loop
                                # otherwise — same rule as the main dispatch)
                                frag_sent.discard(o.url)
                failed = time.time() > deadline  # wedged task: reassign
                try:
                    st = json.loads(_http(f"{w.url}/v1/task/{tid}", timeout=2.0))
                    failed = failed or st.get("state") == "failed"
                    if st.get("state") == "failed" \
                            and not st.get("retryable", True):
                        # deterministic failure: every re-dispatch would hit
                        # the identical error — surface it now instead of
                        # burning the attempt budget across workers
                        raise RuntimeError(
                            f"task {tid} failed deterministically: "
                            f"{st.get('error')}")
                except RuntimeError:
                    raise
                except Exception:
                    # unreachable OR task unknown (404: the worker restarted
                    # and lost its in-memory state) -> the attempt is gone
                    failed = True
                if failed and not exchange.is_committed(tid):
                    del assigned[tid]
                    burn(tid, "failed")
                    if extra.get("stream_sources"):
                        # the consumer partially drained its producers'
                        # ack-once buffers: replay the producer chain fresh
                        # and point the retried consumer at the replacements
                        extra = dict(extra)
                        extra["stream_sources"] = self._replay_stream_sources(
                            extra["stream_sources"], attempts[tid],
                            consumer=tid)
                    pending[tid] = extra


def main(argv=None):  # pragma: no cover - exercised via subprocess in tests
    """Worker process entry: ``python -m trino_tpu.server.cluster --port N
    --coordinator URL --catalogs JSON --spool DIR --node-id ID``."""
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--coordinator", default=None)
    ap.add_argument("--catalogs", required=True)
    ap.add_argument("--spool", required=True)
    ap.add_argument("--node-id", default="worker")
    args = ap.parse_args(argv)
    w = WorkerServer(json.loads(args.catalogs), args.spool, port=args.port,
                     coordinator_url=args.coordinator, node_id=args.node_id)
    url = w.start()
    print(f"worker {args.node_id} listening on {url}", flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        w.stop()


if __name__ == "__main__":  # pragma: no cover
    main()
