"""Coordinator HTTP server: the client statement protocol.

Reference: the v1 statement protocol — POST /v1/statement returns a queued query with a
``nextUri``; the client follows nextUri until results are exhausted
(dispatcher/QueuedStatementResource.java:110,170, server/protocol/ExecutingStatementResource,
client paging loop StatementClientV1.java:403).  Query lifecycle mirrors QueryStateMachine
(execution/QueryState.java:21: QUEUED -> PLANNING -> RUNNING -> FINISHING -> FINISHED/FAILED).

Implementation: stdlib ThreadingHTTPServer + a thread-pool dispatch (the reference's
dispatch executor); results are paged DATA_ROWS_PER_FETCH rows per GET like the
reference's token-addressed result pages (server/TaskResource.java:331 token protocol).
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import threading
import time
import traceback
from concurrent.futures import ThreadPoolExecutor
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

__all__ = ["CoordinatorServer"]

DATA_ROWS_PER_FETCH = 4096

# QueryCounters seconds field -> /v1/metrics series stem and its HELP text
_SECONDS_SERIES = (
    ("queued_s", "queued", "Seconds from a statement's acceptance to its "
     "root span (dispatch pool, statement lock, session, admission)."),
    ("batch_wait_s", "batch_wait", "Seconds statements waited on a template "
     "batcher lane, gather windows included."),
    ("executor_wait_s", "executor_wait", "Seconds statements waited for an "
     "executor of the pool."),
    ("encode_s", "encode", "Seconds from the engine's answer to FINISHED "
     "published (rows to JSON, spooling)."),
    ("deliver_wait_s", "deliver_wait", "Seconds from FINISHED published to "
     "the response carrying the last page."),
    ("host_cpu_s", "host_cpu", "CPU seconds of the statements' own threads "
     "under their root spans (time.thread_time)."),
)

_qids = itertools.count(1)


_UI_STYLE = ("<!doctype html><title>trino-tpu</title>"
             "<style>body{font-family:sans-serif;margin:2em}"
             "table{border-collapse:collapse}td,th{border:1px solid #ccc;"
             "padding:4px 8px;text-align:left}"
             "pre{background:#f6f6f6;padding:8px;overflow-x:auto}</style>")

# the single-page web UI (reference: core/trino-web-ui's React SPA, reduced
# to one dependency-free page): client-side rendering over /ui/api/*, a
# query drill-down, and a SQL console that speaks the public /v1/statement
# protocol (nextUri paging) like every other client.
_UI_APP = """<!doctype html><html><head><meta charset="utf-8">
<title>trino-tpu</title><style>
body{font-family:system-ui,sans-serif;margin:0;background:#f4f5f7;color:#172b4d}
header{background:#172b4d;color:#fff;padding:10px 24px;display:flex;gap:24px;
  align-items:baseline}
header h1{font-size:18px;margin:0}
header .stat{font-size:13px;opacity:.85}
main{padding:16px 24px;display:grid;grid-template-columns:1fr 1fr;gap:16px}
section{background:#fff;border-radius:6px;padding:12px 16px;
  box-shadow:0 1px 2px rgba(9,30,66,.15)}
section h2{font-size:14px;margin:0 0 8px;text-transform:uppercase;
  letter-spacing:.04em;color:#6b778c}
table{border-collapse:collapse;width:100%;font-size:13px}
td,th{border-bottom:1px solid #ebecf0;padding:5px 8px;text-align:left}
tr.q{cursor:pointer}tr.q:hover{background:#f0f4ff}
.st{padding:1px 7px;border-radius:9px;font-size:11px;font-weight:600}
.st-FINISHED{background:#e3fcef;color:#006644}
.st-FAILED,.st-CANCELED{background:#ffebe6;color:#bf2600}
.st-RUNNING,.st-QUEUED{background:#deebff;color:#0747a6}
pre{background:#f6f6f6;padding:8px;overflow-x:auto;font-size:12px;
  white-space:pre-wrap}
textarea{width:100%;box-sizing:border-box;font-family:ui-monospace,monospace;
  font-size:13px;min-height:70px}
button{background:#0052cc;color:#fff;border:0;border-radius:4px;
  padding:6px 14px;cursor:pointer}
#results{max-height:320px;overflow:auto}
</style></head><body>
<header><h1>trino-tpu</h1><span class="stat" id="stats">loading…</span></header>
<main>
<section style="grid-column:1/3"><h2>SQL console</h2>
<textarea id="sql" placeholder="select …"></textarea>
<p><button onclick="run()">Run</button> <span id="runstate"></span></p>
<div id="results"></div></section>
<section><h2>Queries</h2><table id="qs"><tr><th>id</th><th>state</th>
<th>user</th><th>elapsed</th><th>rows</th><th>sql</th></tr></table></section>
<section><h2>Query detail</h2><div id="detail">select a query…</div></section>
</main><script>
const esc = s => String(s ?? '').replace(/[&<>"]/g,
  c => ({'&':'&amp;','<':'&lt;','>':'&gt;','"':'&quot;'}[c]));
async function refresh(){
  try{
    const o = await (await fetch('/ui/api/overview')).json();
    const mb = o.memory.max_bytes ?
      ` | memory ${(o.memory.reserved/1e6).toFixed(0)}/` +
      `${(o.memory.max_bytes/1e6).toFixed(0)} MB` : '';
    document.getElementById('stats').textContent =
      `${o.queries.length} queries | catalogs: ${o.catalogs.join(', ')}${mb}`;
    const t = document.getElementById('qs');
    t.querySelectorAll('tr.q').forEach(r => r.remove());
    for(const q of o.queries){
      const tr = document.createElement('tr');
      tr.className = 'q';
      tr.onclick = () => detail(q.query_id);
      tr.innerHTML = `<td>${esc(q.query_id)}</td>` +
        `<td><span class="st st-${esc(q.state)}">${esc(q.state)}</span></td>` +
        `<td>${esc(q.user)}</td><td>${q.elapsed}s</td>` +
        `<td>${q.rows ?? ''}</td><td><code>${esc(q.sql)}</code></td>`;
      t.appendChild(tr);
    }
  }catch(e){ /* poll again */ }
}
async function detail(id){
  const d = await (await fetch('/ui/api/query/' + encodeURIComponent(id)))
    .json();
  let h = `<table><tr><th>state</th><td>${esc(d.state)}</td></tr>` +
    `<tr><th>user</th><td>${esc(d.user)}</td></tr>` +
    `<tr><th>elapsed</th><td>${d.elapsed}s</td></tr>` +
    (d.rows != null ? `<tr><th>rows</th><td>${d.rows}</td></tr>` : '') +
    `</table><h3>sql</h3><pre>${esc(d.sql)}</pre>`;
  if(d.error) h += `<h3>error</h3><pre>${esc(d.error)}</pre>`;
  if(d.plan) h += `<h3>plan</h3><pre>${esc(d.plan)}</pre>`;
  document.getElementById('detail').innerHTML = h;
}
async function run(){
  const sql = document.getElementById('sql').value.trim();
  if(!sql) return;
  const rs = document.getElementById('runstate');
  rs.textContent = 'running…';
  try{
    let r = await (await fetch('/v1/statement',
      {method:'POST', body: sql})).json();
    let cols = null, rows = [];
    while(true){
      if(r.columns) cols = r.columns;
      if(r.data) rows.push(...r.data);
      if(r.error){ rs.textContent = ''; document.getElementById('results')
        .innerHTML = `<pre>${esc(r.error.message || r.error)}</pre>`; return; }
      if(!r.nextUri) break;
      if(!r.data) await new Promise(s => setTimeout(s, 200));  // poll pacing
      r = await (await fetch(r.nextUri)).json();
    }
    rs.textContent = `${rows.length} rows`;
    let h = '<table><tr>' + (cols||[]).map(
      c => `<th>${esc(c.name)}</th>`).join('') + '</tr>';
    for(const row of rows.slice(0, 200))
      h += '<tr>' + row.map(v => `<td>${esc(v)}</td>`).join('') + '</tr>';
    document.getElementById('results').innerHTML =
      h + '</table>' + (rows.length > 200 ?
        `<p>… ${rows.length - 200} more rows</p>` : '');
    refresh();
  }catch(e){ rs.textContent = String(e); }
}
refresh(); setInterval(refresh, 3000);
</script></body></html>"""


@dataclasses.dataclass
class _Query:
    query_id: str
    sql: str
    state: str = "QUEUED"  # QUEUED|PLANNING|RUNNING|FINISHED|FAILED|CANCELED
    error: Optional[str] = None
    columns: Optional[list] = None  # [{name, type}]
    rows: Optional[list] = None  # list of row tuples (json-ready)
    segments: Optional[list] = None  # spooled result descriptors
    user: str = "user"  # submitting principal: result reads require it
    created_at: float = dataclasses.field(default_factory=time.time)
    finished_at: Optional[float] = None
    # engine span-tree summary captured at completion (engine.last_query_trace
    # under the engine lock) — served OTLP-shaped by /v1/query/{id}/trace
    trace: Optional[dict] = None
    # protocol-level EXECUTE (round 13): python values bound into a
    # parameterized statement (sql carries ? markers) — served through the
    # engine's plan-template path when one exists
    params: Optional[list] = None
    lock: threading.Lock = dataclasses.field(default_factory=threading.Lock)
    # the statement's timeline outside the engine (perf_counter readings):
    # the POST accepted, FINISHED published (None again once the last page
    # is delivered); ``root`` is the engine's root span, under which the
    # server's phases are recorded
    accepted_pc: float = dataclasses.field(default_factory=time.perf_counter)
    finished_pc: Optional[float] = None
    root: Optional[object] = None


class _StatementLock:
    """Shared/exclusive gate for engine access (round 12).

    The engine has been safe for CONCURRENT read statements since round 9
    (per-query pooled executors, the plan lock, the shared buffer pool under
    its own lock — tests/test_page_cache drives 4 threads through
    execute_sql), but this server still serialized every statement behind
    one mutex, which made the coordinator protocol single-file and any
    concurrency benchmark meaningless.  Read statements (SELECT/SHOW/
    EXPLAIN/VALUES/WITH) now run SHARED; DDL/DML and anything unrecognized
    runs EXCLUSIVE (memory-connector writes + catalog mutation still assume
    single-writer).  Writer-preference: a waiting writer blocks new readers,
    so a stream of dashboard SELECTs cannot starve an INSERT."""

    READ_KEYWORDS = ("select", "with", "show", "explain", "describe",
                     "values", "table")

    def __init__(self):
        self._cond = threading.Condition()
        self._readers = 0
        self._writer = False
        self._writers_waiting = 0

    @classmethod
    def is_read_statement(cls, sql: str) -> bool:
        head = sql.lstrip().lstrip("(").lstrip()[:12].lower()
        return any(head.startswith(k) for k in cls.READ_KEYWORDS)

    def acquire_shared(self):
        with self._cond:
            while self._writer or self._writers_waiting:
                self._cond.wait()
            self._readers += 1

    def release_shared(self):
        with self._cond:
            self._readers -= 1
            if self._readers == 0:
                self._cond.notify_all()

    def acquire_exclusive(self):
        with self._cond:
            self._writers_waiting += 1
            try:
                while self._writer or self._readers:
                    self._cond.wait()
                self._writer = True
            finally:
                self._writers_waiting -= 1

    def release_exclusive(self):
        with self._cond:
            self._writer = False
            self._cond.notify_all()

    def statement_scope(self, sql: str):
        import contextlib

        @contextlib.contextmanager
        def scope():
            shared = self.is_read_statement(sql)
            (self.acquire_shared if shared else self.acquire_exclusive)()
            try:
                yield
            finally:
                (self.release_shared if shared
                 else self.release_exclusive)()

        return scope()


_device_stats_lock = threading.Lock()
_device_stats_cache = {"stats": None, "at": 0.0, "probe_started": 0.0,
                       "probing": False}


def _device_memory_stats(max_age: float = 15.0, timeout: float = 2.0,
                         rearm_s: float = 600.0):
    """Device memory stats WITHOUT blocking the caller: the PJRT
    ``memory_stats()`` call can itself block on a stalled device — exactly when
    /v1/status is being polled for a post-mortem — so the probe runs on a
    background thread with a join timeout and callers get the last good
    snapshot.  A probe that never returns parks the ``probing`` flag;
    ``rearm_s`` re-arms probing after a hang so a RECOVERED device becomes
    visible again (each re-arm risks one more parked thread, so the cap is
    generous: a 3h stall parks at most ~18)."""
    now = time.time()
    with _device_stats_lock:
        if now - _device_stats_cache["at"] <= max_age:
            return _device_stats_cache["stats"]
        if _device_stats_cache["probing"] \
                and now - _device_stats_cache["probe_started"] < rearm_s:
            return _device_stats_cache["stats"]
        _device_stats_cache["probing"] = True
        _device_stats_cache["probe_started"] = now

    def probe():
        stats = None
        try:
            import jax

            stats = jax.devices()[0].memory_stats()
        except Exception:
            pass
        with _device_stats_lock:
            _device_stats_cache["stats"] = stats
            _device_stats_cache["at"] = time.time()
            _device_stats_cache["probing"] = False

    t = threading.Thread(target=probe, daemon=True, name="device-stats-probe")
    t.start()
    t.join(timeout)
    with _device_stats_lock:
        return _device_stats_cache["stats"]


def _json_value(v):
    import numpy as np

    if v is None:
        return None
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    if isinstance(v, (np.bool_,)):
        return bool(v)
    if isinstance(v, np.str_):
        return str(v)
    if isinstance(v, np.datetime64):
        return str(v)  # ISO date/timestamp text on the wire
    return v


class CoordinatorServer:
    """Serves an Engine over the statement protocol (one process = coordinator role;
    the worker data plane is the SPMD mesh inside the engine, reference:
    CoordinatorModule vs WorkerModule role split)."""

    def __init__(self, engine, host: str = "127.0.0.1", port: int = 8080,
                 dispatch_threads: int = 4, passwords: Optional[dict] = None,
                 spool_dir: Optional[str] = None,
                 spool_threshold_rows: int = 10_000):
        self.engine = engine
        # user -> password; None = open access (reference: optional password
        # authenticator plugins; file-based password auth)
        self.passwords = passwords
        # spooled client protocol (reference: server/protocol/spooling + the
        # SpoolingManager SPI, spi/spool/SpoolingManager.java): results at or
        # above the threshold write as compressed segments the client fetches
        # by URI instead of inline JSON pages.  None disables spooling.
        self.spool_dir = spool_dir
        self.spool_threshold_rows = spool_threshold_rows
        self.host = host
        self.port = port
        self.queries: dict = {}
        self._pool = ThreadPoolExecutor(max_workers=dispatch_threads,
                                        thread_name_prefix="dispatch")
        # shared/exclusive statement gate (round 12): read statements execute
        # CONCURRENTLY against the engine's executor pool (one dispatch
        # thread per in-flight statement, up to dispatch_threads); DDL/DML
        # still serialize exclusively — see _StatementLock
        self._engine_lock = _StatementLock()
        self._queries_lock = threading.Lock()  # guards the queries registry itself
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None

    # -- lifecycle ----------------------------------------------------------------
    def start(self) -> None:
        server = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):  # quiet
                pass

            def _send(self, code, obj):
                body = json.dumps(obj).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_POST(self):
                if self.path != "/v1/statement":
                    self._send(404, {"error": "not found"})
                    return
                user = self.headers.get("X-Trino-User")
                if not server._authenticate(self.headers, user):
                    self.send_response(401)
                    self.send_header("WWW-Authenticate", "Basic")
                    self.end_headers()
                    return
                if user is None:
                    user = server._principal(self.headers) or "user"
                n = int(self.headers.get("Content-Length", 0))
                sql = self.rfile.read(n).decode()
                session_catalog = self.headers.get("X-Trino-Catalog")
                # protocol-level EXECUTE with parameters: the body is a
                # parameterized statement (? markers), the header a JSON
                # array of values to bind — the plan-template path answers
                # repeats without re-planning (round 13)
                params = None
                raw = self.headers.get("X-Trino-Execute-Parameters")
                if raw:
                    try:
                        params = json.loads(raw)
                        if not isinstance(params, list):
                            raise ValueError("parameters must be a JSON list")
                    except ValueError as e:
                        self._send(400, {"error": f"bad parameters: {e}"})
                        return
                q = server._submit(sql, session_catalog, user, params=params)
                self._send(200, server._queued_response(q))

            def do_GET(self):
                if not server._authenticate(self.headers, None):
                    self.send_response(401)
                    self.send_header("WWW-Authenticate", "Basic")
                    self.end_headers()
                    return
                parts = self.path.strip("/").split("/")
                # /v1/statement/executing/{id}/{token}
                if len(parts) == 5 and parts[:3] == ["v1", "statement", "executing"]:
                    qid, token = parts[3], int(parts[4])
                    q = server.queries.get(qid)
                    if q is None:
                        self._send(404, {"error": f"unknown query {qid}"})
                        return
                    if not server._owns(self.headers, q):
                        self._send(403, {"error": "not your query"})
                        return
                    out = server._results_response(q, token)
                    self._send(200, out)
                    if "nextUri" not in out:
                        server._delivered(q)
                    return
                # /v1/query/{id}/trace — OTLP-shaped span tree of the query
                # (reference: airlift TracingModule's OTLP export, served
                # in-process so one curl profiles a finished statement)
                if len(parts) == 4 and parts[:2] == ["v1", "query"] \
                        and parts[3] == "trace":
                    payload = server._query_trace(parts[2])
                    if payload is None:
                        self._send(404, {"error": "unknown query"})
                        return
                    self._send(200, payload)
                    return
                if len(parts) == 3 and parts[:2] == ["v1", "query"]:
                    q = server.queries.get(parts[2])
                    if q is None:
                        self._send(404, {"error": "unknown query"})
                        return
                    self._send(200, server._query_info(q))
                    return
                if parts == ["v1", "info"]:
                    self._send(200, {"coordinator": True, "running": True,
                                     "nodeVersion": {"version": "trino-tpu-0"}})
                    return
                if parts == ["v1", "status"]:
                    # live in-flight introspection (round 8): running queries
                    # with counters-so-far, the in-flight registry, health
                    # verdict, stall report, memory pools + device stats —
                    # the "what is the engine doing right now" surface the
                    # stall post-mortems need (reference: QueryInfo/
                    # TaskInfo live snapshots behind the web UI)
                    self._send(200, server._status_json())
                    return
                if parts == ["v1", "history"]:
                    # round 15: the plan-actuals history — per-node est-vs-
                    # actual records merged across executions (the JSON twin
                    # of system.runtime.plan_history)
                    ph = getattr(server.engine, "plan_history", None)
                    self._send(200, ph.as_dict() if ph is not None
                               else {"plans": []})
                    return
                if parts == ["v1", "flight"]:
                    # round 16: the flight recorder — recorder state + a
                    # summary line per retained record (the JSON twin of
                    # system.runtime.query_log)
                    self._send(200, server._flight_index())
                    return
                if len(parts) == 3 and parts[:2] == ["v1", "flight"]:
                    # /v1/flight/{id} — one statement's full flight record
                    # (counters, stitched span tree, wall breakdown,
                    # plan-actuals) long after the statement finished
                    rec = server._flight_record(parts[2])
                    if rec is None:
                        self._send(404, {"error": "unknown query"})
                        return
                    self._send(200, rec)
                    return
                if parts == ["v1", "compiles"]:
                    # round 17: the compile observatory — census state plus
                    # the retained per-compilation records (site, op label,
                    # query id, arg signature, duration, exe size), the JSON
                    # twin of system.runtime.compilations
                    self._send(200, server._compiles_json())
                    return
                # /v1/spooled/{qid}/{seg} — spooled result segment payload
                # (reference: the client fetching spooled segments by URI,
                # client/trino-client/.../OkHttpSegmentLoader.java)
                if len(parts) == 4 and parts[:2] == ["v1", "spooled"]:
                    q = server.queries.get(parts[2])
                    if q is not None and not server._owns(self.headers, q):
                        self._send(403, {"error": "not your query"})
                        return
                    data = server._read_segment(parts[2], parts[3])
                    if data is None:
                        self._send(404, {"error": "unknown segment"})
                        return
                    self.send_response(200)
                    self.send_header("Content-Type", "application/octet-stream")
                    self.send_header("Content-Length", str(len(data)))
                    self.end_headers()
                    self.wfile.write(data)
                    return
                if parts == ["v1", "metrics"]:
                    # reference: JmxOpenMetricsModule — a Prometheus text
                    # exposition of engine counters
                    body = server._metrics_text().encode()
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     "text/plain; version=0.0.4")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                if parts == ["ui"] or parts == ["ui", ""]:
                    # reference: core/trino-web-ui's SPA, reduced to ONE
                    # self-contained page (inline JS, no build tooling) that
                    # polls the JSON api below — live overview, per-query
                    # drill-down, and a SQL console speaking the same
                    # /v1/statement protocol as every other client
                    body = _UI_APP.encode()
                    self.send_response(200)
                    self.send_header("Content-Type", "text/html; charset=utf-8")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                if parts == ["ui", "api", "overview"]:
                    self._send(200, server._ui_overview())
                    return
                if len(parts) == 4 and parts[:3] == ["ui", "api", "query"]:
                    detail = server._ui_query_json(parts[3])
                    if detail is None:
                        self._send(404, {"error": "unknown query"})
                        return
                    self._send(200, detail)
                    return
                if len(parts) == 3 and parts[:2] == ["ui", "query"]:
                    # per-query drill-down (reference: the web UI's query
                    # detail page — SQL, state, timings, plan)
                    html_q = server._ui_query_html(parts[2])
                    if html_q is None:
                        self._send(404, {"error": "unknown query"})
                        return
                    body = html_q.encode()
                    self.send_response(200)
                    self.send_header("Content-Type", "text/html; charset=utf-8")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                self._send(404, {"error": "not found"})

            def do_DELETE(self):
                if not server._authenticate(self.headers, None):
                    self.send_response(401)
                    self.send_header("WWW-Authenticate", "Basic")
                    self.end_headers()
                    return
                parts = self.path.strip("/").split("/")
                qid = None
                if len(parts) >= 5 and parts[:3] == ["v1", "statement", "executing"]:
                    qid = parts[3]  # DELETE on a nextUri (StatementClientV1 cancel)
                elif len(parts) == 3 and parts[:2] == ["v1", "statement"]:
                    qid = parts[2]
                if qid is not None:
                    q = server.queries.get(qid)
                    if q is not None and not server._owns(self.headers, q):
                        self._send(403, {"error": "not your query"})
                        return
                    if q is not None:
                        with q.lock:
                            if q.state not in ("FINISHED", "FAILED"):
                                q.state = "CANCELED"
                    self._send(204, {})
                    return
                self._send(404, {"error": "not found"})

        self._httpd = ThreadingHTTPServer((self.host, self.port), Handler)
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(target=self._httpd.serve_forever, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
        self._pool.shutdown(wait=False)

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    # -- auth (reference: password authenticators + InternalAuthenticationManager;
    # a password map gates access when configured, else open) ----------------------
    def _authenticate(self, headers, user) -> bool:
        """Basic credentials against the password map (constant-time compare).
        When an X-Trino-User is given it must match the authenticated
        principal (reference: the authenticated user gates the session user);
        result/cancel/metrics GETs authenticate the principal alone."""
        if self.passwords is None:
            return True
        import base64
        import hmac

        auth = headers.get("Authorization", "")
        if not auth.startswith("Basic "):
            return False
        try:
            decoded = base64.b64decode(auth[6:]).decode()
            auth_user, _, pw = decoded.partition(":")
        except Exception:
            return False
        expected = self.passwords.get(auth_user)
        if expected is None or not hmac.compare_digest(expected, pw):
            return False
        return user is None or auth_user == user

    def _owns(self, headers, q) -> bool:
        """Result reads and cancels belong to the submitting principal: query
        ids are guessable, and per-table access control would otherwise be
        moot for any data another user has already queried.  Open servers
        (no password map) skip the check."""
        if self.passwords is None:
            return True
        return self._principal(headers) == q.user

    def _principal(self, headers):
        import base64

        auth = headers.get("Authorization", "")
        if not auth.startswith("Basic "):
            return None
        try:
            return base64.b64decode(auth[6:]).decode().partition(":")[0]
        except Exception:
            return None

    @staticmethod
    def _escape_label(v: str) -> str:
        """Prometheus text-format label-value escaping (backslash, quote,
        newline) — stricter scrapers reject unescaped values."""
        return (str(v).replace("\\", "\\\\").replace('"', '\\"')
                .replace("\n", "\\n"))

    def _metrics_text(self) -> str:
        """Prometheus text exposition with # HELP / # TYPE metadata (the
        format openmetrics-strict scrapers require; reference:
        JmxOpenMetricsModule) including the device-boundary counters, the
        per-site breakdown, and the dispatch-latency histogram — the wedge
        signature (p99 exploding while the dispatch count stalls) is readable
        from one curl of this endpoint."""
        esc = self._escape_label
        with self._queries_lock:
            qs = list(self.queries.values())
        by_state: dict = {}
        for q in qs:
            by_state[q.state] = by_state.get(q.state, 0) + 1
        lines = [
            "# HELP trino_tpu_queries_total Statements accepted by this "
            "coordinator.",
            "# TYPE trino_tpu_queries_total counter",
            f"trino_tpu_queries_total {len(qs)}",
            "# HELP trino_tpu_queries_by_state Tracked queries per lifecycle "
            "state.",
            "# TYPE trino_tpu_queries_by_state gauge",
        ]
        for state, n in sorted(by_state.items()):
            lines.append(
                f'trino_tpu_queries_by_state{{state="{esc(state)}"}} {n}')
        done = [q for q in qs if q.finished_at is not None]
        if done:
            total = sum(q.finished_at - q.created_at for q in done)
            lines += ["# HELP trino_tpu_query_seconds_total Wall seconds of "
                      "finished queries.",
                      "# TYPE trino_tpu_query_seconds_total counter",
                      f"trino_tpu_query_seconds_total {total:.3f}"]
        # device-boundary totals (execution/tracing.QueryCounters): the
        # dispatch/transfer budget spent across every plan execution this
        # engine accounted — on a cluster coordinator this includes merged
        # worker-side counters (server/cluster.py task-response flow)
        ct = getattr(self.engine, "counters_total", None)
        if ct is not None:
            lines += [
                "# HELP trino_tpu_device_dispatches_total Jitted XLA program "
                "launches (one host->device launch each).",
                "# TYPE trino_tpu_device_dispatches_total counter",
                f"trino_tpu_device_dispatches_total {ct.device_dispatches}",
                "# HELP trino_tpu_host_transfers_total Batched device->host "
                "pulls through the _host chokepoint.",
                "# TYPE trino_tpu_host_transfers_total counter",
                f"trino_tpu_host_transfers_total {ct.host_transfers}",
                "# HELP trino_tpu_host_bytes_pulled_total Device bytes moved "
                "to host.",
                "# TYPE trino_tpu_host_bytes_pulled_total counter",
                f"trino_tpu_host_bytes_pulled_total {ct.host_bytes_pulled}",
                "# HELP trino_tpu_coalesced_splits_total Splits executed "
                "inside coalesced multi-split dispatches.",
                "# TYPE trino_tpu_coalesced_splits_total counter",
                f"trino_tpu_coalesced_splits_total "
                f"{getattr(ct, 'coalesced_splits', 0)}",
                "# HELP trino_tpu_faults_injected_total Chaos fault-injector "
                "firings (execution/faults) accounted to queries.",
                "# TYPE trino_tpu_faults_injected_total counter",
                f"trino_tpu_faults_injected_total "
                f"{getattr(ct, 'faults_injected', 0)}",
                "# HELP trino_tpu_task_retries_total Task retries / "
                "re-dispatches charged to queries (FTE retry loop, "
                "coordinator reassignment).",
                "# TYPE trino_tpu_task_retries_total counter",
                f"trino_tpu_task_retries_total "
                f"{getattr(ct, 'task_retries', 0)}",
                # round 11: the memory-pressure ladder.  Bytes the tiered
                # spill routed out of operator working sets, per tier (hbm =
                # device-resident, host = RAM under the "spill" tag, disk =
                # codec-framed files), and admissions deferred at the queue
                # rung.
                "# HELP trino_tpu_spilled_bytes_total Bytes spilled by "
                "Grace-partitioned operators, by destination tier.",
                "# TYPE trino_tpu_spilled_bytes_total counter",
            ]
            from ..execution.tracing import SPILL_TIERS

            for tier in SPILL_TIERS:
                lines.append(
                    f'trino_tpu_spilled_bytes_total{{tier="{tier}"}} '
                    f'{getattr(ct, f"spill_tier_{tier}", 0)}')
            lines += [
                "# HELP trino_tpu_admission_queued_total Queries deferred "
                "at admission under memory pressure (ladder rung: queue "
                "before kill).",
                "# TYPE trino_tpu_admission_queued_total counter",
                f"trino_tpu_admission_queued_total "
                f"{getattr(ct, 'admission_queued', 0)}",
                # round 13: plan templates — statements answered through an
                # already-compiled parameterized plan (hit = zero parse/
                # analyze/plan work and zero re-compilation; miss = the one
                # template creation a statement shape ever pays)
                "# HELP trino_tpu_plan_template_hits_total Statements served "
                "through a cached plan template (compile once, bind "
                "constants per request).",
                "# TYPE trino_tpu_plan_template_hits_total counter",
                f"trino_tpu_plan_template_hits_total "
                f"{getattr(ct, 'plan_template_hits', 0)}",
                "# HELP trino_tpu_plan_template_misses_total Plan-template "
                "creations (first sight of a parameterized statement "
                "shape).",
                "# TYPE trino_tpu_plan_template_misses_total counter",
                f"trino_tpu_plan_template_misses_total "
                f"{getattr(ct, 'plan_template_misses', 0)}",
            ]
            # the statements' wait states (spans server.queued, batcher.wait,
            # executor.checkout, server.encode, server.deliver) and the
            # buckets of their wall breakdowns, summed
            for field, name, what in _SECONDS_SERIES:
                lines += [f"# HELP trino_tpu_{name}_seconds_total {what}",
                          f"# TYPE trino_tpu_{name}_seconds_total counter",
                          f"trino_tpu_{name}_seconds_total "
                          f"{getattr(ct, field, 0.0):.6f}"]
            lines += ["# HELP trino_tpu_wall_seconds_total Root-span seconds "
                      "of finished statements by wall_breakdown bucket.",
                      "# TYPE trino_tpu_wall_seconds_total counter"]
            for bucket in ("plan", "split_generation", "h2d", "dispatch",
                           "host_pull", "scan_wait", "exchange_wait",
                           "unattributed"):
                lines.append(
                    f'trino_tpu_wall_seconds_total{{bucket="{bucket}"}} '
                    f"{getattr(ct, f'wall_{bucket}_s', 0.0):.6f}")
            lines += ["# HELP trino_tpu_compile_cache_misses_total Programs "
                      "XLA compiled because no compilation cache served them.",
                      "# TYPE trino_tpu_compile_cache_misses_total counter",
                      f"trino_tpu_compile_cache_misses_total "
                      f"{getattr(ct, 'compile_cache_misses', 0)}"]
            for field, what in (
                    ("compactions", "Device-side row compactions "
                     "(live-lane index, then gathers)."),
                    ("compact_lanes_in", "Lanes read by row compactions."),
                    ("compact_lanes_out", "Lanes kept by row compactions "
                     "(their output buckets)."),
                    ("groupby_slots", "Slots of the group-by states that "
                     "were finalized."),
                    ("groupby_state_bytes", "Largest device reservation of "
                     "each group-by, summed."),
                    ("groupby_regrows", "Group-by overflows that cost a "
                     "re-scan of the input."),
                    ("groupby_partitioned_passes", "Grace passes of "
                     "partitioned group-bys."),
                    ("groupby_observed_direct", "Group-bys direct-indexed "
                     "by key bounds read off a blocking child's one page."),
                    ("join_build_rows", "Rows inserted into join build "
                     "tables (0 when a replay reuses its streams)."),
                    ("rows_generated", "Base-table rows the connectors "
                     "generated (resident pages generate none)."),
                    ("join_match_lanes", "Lanes that entered the match step "
                     "of a split join."),
                    ("join_gather_lanes", "Lanes at which split joins then "
                     "gathered their build columns."),
                    ("join_hash_probe_lanes", "Lanes that joins probed "
                     "through the open-addressing loop of a hashed table."),
                    ("join_hash_probe_round_lanes", "Lanes the hashed "
                     "probes of split joins gathered for, rounds times "
                     "width."),
                    ("tail_compiled", "Group-by finalizes and Sorts/TopNs "
                     "that ran as one compiled program."),
                    ("tail_eager", "Group-by finalizes and Sorts/TopNs that "
                     "fell back to the eager/host path."),
                    ("join_direct_probe_lanes", "Lanes that joins probed "
                     "through the one gather of a direct-indexed table."),
                    ("join_hash_table_slots", "Slots of the hashed join "
                     "tables that were built."),
                    ("groupby_insert_lanes", "Lanes that entered the "
                     "group-by's hash insert loop (a regrow's rehash "
                     "included)."),
                    ("groupby_insert_round_lanes", "Lanes the hash-mode "
                     "group-by's inserts probed for: rounds times width, "
                     "summed over the widths a page's rounds ran at (the "
                     "page, then what was still unplaced, packed)."),
                    ("window_kernels", "Window kernels dispatched, one a "
                     "Window node over one materialised page."),
                    ("window_lanes", "Static lanes of the pages the window "
                     "kernels were handed, live rows or not."),
                    ("window_sort_lanes", "Lanes the window kernels sorted: "
                     "lanes times the stable sort passes of their "
                     "(partition, order) clauses."),
                    ("exchange_rows", "Rows the mesh executor's all-to-all "
                     "exchanges delivered (receive cursors and merged "
                     "group counts)."),
                    ("exchange_rows_max_shard", "The fullest worker's share "
                     "of exchange_rows, summed over exchanges."),
                    ("mesh_fragment_hits", "Kept mesh fragments served to a "
                     "replayed plan."),
                    ("mesh_fragment_compiles", "Mesh fragments compiled "
                     "(first sight of a plan node at a ladder rung or a "
                     "learned probe bucket)."),
                    ("probe_exchange_rows", "Rows routed by the probe "
                     "exchanges inside mesh fragments."),
                    ("probe_exchange_lanes", "Lanes the receive tensors of "
                     "those probe exchanges held."),
                    ("exchange_bytes", "Payload bytes of the rows those "
                     "exchanges delivered: rows times the width of their "
                     "routed columns."),
                    ("mesh_scan_batches_resident", "Batches of the mesh's "
                     "sharded scans handed to a step from the page cache's "
                     "entry."),
                    ("mesh_scan_batches_generated", "Batches of the mesh's "
                     "sharded scans generated for a step (a first run, or a "
                     "scan over the entry cap, which streams)."),
                    ("generator_dispatches", "Launches of the connectors' "
                     "page generators from the executor's scan sources "
                     "(not in device_dispatches).")):
                lines += [f"# HELP trino_tpu_{field}_total {what}",
                          f"# TYPE trino_tpu_{field}_total counter",
                          f"trino_tpu_{field}_total {getattr(ct, field, 0)}"]
            # round 21: continuous template batching — fused same-template
            # windows (one device program amortized over N requests), the
            # per-request count, and the fused batch-size distribution
            bt = getattr(self.engine, "template_batcher", None)
            if bt is not None:
                bi = bt.info()
                lines += [
                    "# HELP trino_tpu_template_batches_total Fused "
                    "same-template execution windows (one device program "
                    "serving the whole window).",
                    "# TYPE trino_tpu_template_batches_total counter",
                    f"trino_tpu_template_batches_total "
                    f"{bi['batches_total']}",
                    "# HELP trino_tpu_batched_requests_total Requests "
                    "served through a fused template batch.",
                    "# TYPE trino_tpu_batched_requests_total counter",
                    f"trino_tpu_batched_requests_total "
                    f"{getattr(ct, 'batched_requests', 0)}",
                    "# HELP trino_tpu_template_batch_size Fused batch "
                    "sizes (requests per window).",
                    "# TYPE trino_tpu_template_batch_size histogram",
                ]
                sizes = bi["sizes"]
                ub = 1
                while ub <= max(bi["max_batch"], 1):
                    cum = sum(c for s, c in sizes.items() if s <= ub)
                    lines.append(
                        f'trino_tpu_template_batch_size_bucket{{le="{ub}"}}'
                        f' {cum}')
                    ub *= 2
                lines += [
                    f'trino_tpu_template_batch_size_bucket{{le="+Inf"}} '
                    f"{bi['batches_total']}",
                    f"trino_tpu_template_batch_size_sum "
                    f"{bi['batched_requests_total']}",
                    f"trino_tpu_template_batch_size_count "
                    f"{bi['batches_total']}",
                ]
            # round 15: cardinality-drift signal from the plan-actuals
            # history — the worst est-vs-actual factor currently on record
            # (gauge: it moves as records merge and plans evict) and the
            # lifetime count of node executions past the misestimate
            # threshold
            ph = getattr(self.engine, "plan_history", None)
            if ph is not None:
                lines += [
                    "# HELP trino_tpu_cardinality_misestimate_ratio Worst "
                    "est-vs-actual row factor in the plan-actuals history "
                    "(1.0 = everything on estimate).",
                    "# TYPE trino_tpu_cardinality_misestimate_ratio gauge",
                    f"trino_tpu_cardinality_misestimate_ratio "
                    f"{ph.worst_ratio():.3f}",
                    "# HELP trino_tpu_misestimated_nodes_total Plan-node "
                    "executions recorded past the misestimate threshold "
                    "(2x over/under).",
                    "# TYPE trino_tpu_misestimated_nodes_total counter",
                    f"trino_tpu_misestimated_nodes_total "
                    f"{ph.misestimates_total}",
                ]
            # round 19: the adaptive feedback loop — statements diverted to
            # history-corrected plans, counted holds (material misestimate
            # existed but the win did not cover the recompile price), and
            # demoted corrections (regressed or failed on probation)
            adv = getattr(self.engine, "adaptive_advisor", None)
            if adv is not None:
                ai = adv.info()
                lines += [
                    "# HELP trino_tpu_adaptive_replans_total Statements "
                    "diverted to a history-corrected plan by the adaptive "
                    "advisor.",
                    "# TYPE trino_tpu_adaptive_replans_total counter",
                    f"trino_tpu_adaptive_replans_total "
                    f"{getattr(ct, 'adaptive_replans', 0)}",
                    "# HELP trino_tpu_adaptive_holds_total Material "
                    "misestimates the advisor declined to re-plan "
                    "(win under compile price, or cooling down).",
                    "# TYPE trino_tpu_adaptive_holds_total counter",
                    f"trino_tpu_adaptive_holds_total "
                    f"{getattr(ct, 'adaptive_holds', 0)}",
                    "# HELP trino_tpu_adaptive_demotions_total Corrections "
                    "demoted after regressing or failing on probation.",
                    "# TYPE trino_tpu_adaptive_demotions_total counter",
                    f"trino_tpu_adaptive_demotions_total "
                    f"{ai['demotions_total']}",
                ]
            # round 20: per-shard skew — worst max/mean ratio over the
            # retained window and the latest record's per-worker load
            # vector (rows for mesh exchanges, ms for cluster task walls)
            shard = getattr(ct, "shard_stats", None) or []
            if shard:
                worst = max(float(r.get("ratio") or 1.0) for r in shard)
                lines += [
                    "# HELP trino_tpu_exchange_skew_ratio Worst max/mean "
                    "per-worker load ratio over retained shard records.",
                    "# TYPE trino_tpu_exchange_skew_ratio gauge",
                    f"trino_tpu_exchange_skew_ratio {worst}",
                ]
                last = shard[-1]
                rows = last.get("rows") or []
                if rows:
                    lines += [
                        "# HELP trino_tpu_shard_rows Per-worker load of the "
                        "most recent shard record (rows, or ms for "
                        "kind=task).",
                        "# TYPE trino_tpu_shard_rows gauge"]
                    site = esc(str(last.get("site") or "?"))
                    for wi, v in enumerate(rows):
                        lines.append(
                            f'trino_tpu_shard_rows{{worker="{wi}",'
                            f'site="{site}"}} {int(v)}')
            sites = getattr(ct, "sites", None) or {}
            if sites:
                lines += ["# HELP trino_tpu_site_dispatches_total Device "
                          "dispatches per operator/call-site.",
                          "# TYPE trino_tpu_site_dispatches_total counter"]
                for key in sorted(sites):
                    lines.append(
                        f'trino_tpu_site_dispatches_total{{site="{esc(key)}"}}'
                        f' {sites[key]["dispatches"]}')
                lines += ["# HELP trino_tpu_site_bytes_pulled_total Host "
                          "bytes pulled per operator/call-site.",
                          "# TYPE trino_tpu_site_bytes_pulled_total counter"]
                for key in sorted(sites):
                    lines.append(
                        f'trino_tpu_site_bytes_pulled_total'
                        f'{{site="{esc(key)}"}} {sites[key]["bytes"]}')
                # PR 46: why a scan that missed the page cache is not
                # resident afterwards (record_page_cache's over_cap and
                # store_failed; the mesh's sharded scans record them)
                why = [(key, w, sites[key]["page_cache_" + w])
                       for key in sorted(sites)
                       for w in ("over_cap", "store_failed")
                       if sites[key].get("page_cache_" + w)]
                if why:
                    lines += ["# HELP trino_tpu_site_scans_not_resident_total "
                              "Scans that missed the page cache and stayed "
                              "out of it: over the entry cap (streamed), or "
                              "refused by the pool.",
                              "# TYPE trino_tpu_site_scans_not_resident_total "
                              "counter"]
                    lines += ["trino_tpu_site_scans_not_resident_total"
                              f'{{site="{esc(key)}",why="{w}"}} {n}'
                              for key, w, n in why]
            hist = getattr(ct, "dispatch_latency", None)
            if hist is not None:
                from ..execution.tracing import LATENCY_BUCKETS_S

                h = hist.as_dict()
                lines += ["# HELP trino_tpu_dispatch_latency_seconds Wall "
                          "time of each jitted dispatch (process-wide).",
                          "# TYPE trino_tpu_dispatch_latency_seconds "
                          "histogram"]
                cum = 0
                for ub, c in zip(LATENCY_BUCKETS_S, h["buckets"]):
                    cum += c
                    lines.append(
                        "trino_tpu_dispatch_latency_seconds_bucket"
                        f'{{le="{ub}"}} {cum}')
                lines.append(
                    "trino_tpu_dispatch_latency_seconds_bucket"
                    f'{{le="+Inf"}} {h["count"]}')
                lines.append(
                    f"trino_tpu_dispatch_latency_seconds_sum {h['sum_s']}")
                lines.append(
                    f"trino_tpu_dispatch_latency_seconds_count {h['count']}")
        # round 8: live in-flight / stall gauges — the wedge is visible as a
        # nonzero stalled gauge WHILE it happens, not only as a post-hoc p99
        from ..execution import tracing as _tracing

        wd = getattr(self.engine, "stall_watchdog", None)
        stalled_n = compiling_n = 0
        if wd is not None:
            _, stalled_n, compiling_n = wd.status()
        lines += [
            "# HELP trino_tpu_inflight_entries Device-boundary operations "
            "currently executing (dispatches, pulls, split generation, "
            "exchange segments).",
            "# TYPE trino_tpu_inflight_entries gauge",
            f"trino_tpu_inflight_entries {_tracing.INFLIGHT.depth()}",
            "# HELP trino_tpu_stalled_dispatches In-flight entries older "
            "than the TRINO_TPU_STALL_S threshold, excluding tolerated "
            "compiles (0 when the watchdog is disabled).",
            "# TYPE trino_tpu_stalled_dispatches gauge",
            f"trino_tpu_stalled_dispatches {stalled_n}",
            "# HELP trino_tpu_compiling_dispatches First-seen-signature "
            "dispatches past the stall threshold but under "
            "TRINO_TPU_STALL_COMPILE_S (verdict: compiling, not stalled).",
            "# TYPE trino_tpu_compiling_dispatches gauge",
            f"trino_tpu_compiling_dispatches {compiling_n}",
        ]
        # round 17: the compile observatory — lifetime compile count/seconds
        # (counters), the compile wall-time histogram on its own
        # seconds-to-minutes bucket scale, and recompile-storm detections
        cl = getattr(self.engine, "compile_log", None)
        if cl is not None:
            ci = cl.info()
            lines += [
                "# HELP trino_tpu_compiles_total XLA compilations observed "
                "at the _jit chokepoint (first-seen arg signatures).",
                "# TYPE trino_tpu_compiles_total counter",
                f"trino_tpu_compiles_total {ci['compiles_total']}",
                "# HELP trino_tpu_recompile_storms_total Operator sites "
                "that crossed the distinct-signature storm threshold "
                "(shape churn defeating executable reuse).",
                "# TYPE trino_tpu_recompile_storms_total counter",
                f"trino_tpu_recompile_storms_total {ci['storms_total']}",
            ]
            h = cl.latency.as_dict()
            lines += ["# HELP trino_tpu_compile_seconds Wall time of each "
                      "observed XLA compilation.",
                      "# TYPE trino_tpu_compile_seconds histogram"]
            cum = 0
            for ub, c in zip(cl.latency.buckets, h["buckets"]):
                cum += c
                lines.append(
                    f'trino_tpu_compile_seconds_bucket{{le="{ub}"}} {cum}')
            lines.append(
                f'trino_tpu_compile_seconds_bucket{{le="+Inf"}} {h["count"]}')
            lines.append(f"trino_tpu_compile_seconds_sum {h['sum_s']}")
            lines.append(f"trino_tpu_compile_seconds_count {h['count']}")
        # round 16: flight recorder — the durable per-statement record ring.
        # records/bytes are gauges (rings evict); the lifetime totals,
        # stitched-span counts and guarded-store failures are counters.
        fr = getattr(self.engine, "flight_recorder", None)
        if fr is not None:
            fi = fr.info()
            lines += [
                "# HELP trino_tpu_flight_records Statement/event records "
                "resident in the flight recorder's in-memory ring.",
                "# TYPE trino_tpu_flight_records gauge",
                f"trino_tpu_flight_records {fi['records']}",
                "# HELP trino_tpu_flight_disk_bytes Bytes resident in the "
                "flight recorder's on-disk JSONL ring (0 = disk ring off).",
                "# TYPE trino_tpu_flight_disk_bytes gauge",
                f"trino_tpu_flight_disk_bytes {fi['disk_bytes']}",
                "# HELP trino_tpu_flight_records_total Flight records "
                "appended over this process's lifetime.",
                "# TYPE trino_tpu_flight_records_total counter",
                f"trino_tpu_flight_records_total {fi['records_total']}",
                "# HELP trino_tpu_flight_spans_total Trace spans recorded "
                "into flight records (stitched worker spans included).",
                "# TYPE trino_tpu_flight_spans_total counter",
                f"trino_tpu_flight_spans_total {fi['spans_total']}",
                "# HELP trino_tpu_flight_worker_spans_total Harvested worker "
                "spans stitched into coordinator query traces.",
                "# TYPE trino_tpu_flight_worker_spans_total counter",
                f"trino_tpu_flight_worker_spans_total "
                f"{fi['worker_spans_total']}",
                "# HELP trino_tpu_flight_record_failures_total Flight "
                "records dropped by the recorder's guard (a failure never "
                "fails the query it records).",
                "# TYPE trino_tpu_flight_record_failures_total counter",
                f"trino_tpu_flight_record_failures_total {fi['failures']}",
            ]
        # device buffer pool (round 9): cache effectiveness is a first-class
        # scrape — entries/bytes are gauges (they shrink on eviction and
        # DDL), hit/miss counts are lifetime counters of this node's pool
        bp = getattr(self.engine, "buffer_pool", None)
        if bp is not None:
            bi = bp.info()
            lines += [
                "# HELP trino_tpu_page_cache_bytes Device bytes resident in "
                "the buffer pool (page + build tiers).",
                "# TYPE trino_tpu_page_cache_bytes gauge",
                f"trino_tpu_page_cache_bytes {bi['bytes']}",
                "# HELP trino_tpu_page_cache_entries Entries resident in the "
                "buffer pool.",
                "# TYPE trino_tpu_page_cache_entries gauge",
                f"trino_tpu_page_cache_entries {bi['entries']}",
                "# HELP trino_tpu_page_cache_hits_total Buffer-pool page-"
                "tier hits (whole scans served from device memory).",
                "# TYPE trino_tpu_page_cache_hits_total counter",
                f"trino_tpu_page_cache_hits_total {bi['hits']}",
                "# HELP trino_tpu_page_cache_misses_total Buffer-pool page-"
                "tier misses.",
                "# TYPE trino_tpu_page_cache_misses_total counter",
                f"trino_tpu_page_cache_misses_total {bi['misses']}",
                "# HELP trino_tpu_build_cache_hits_total Buffer-pool build-"
                "tier hits (join builds checked out instead of re-executed).",
                "# TYPE trino_tpu_build_cache_hits_total counter",
                f"trino_tpu_build_cache_hits_total {bi['build_hits']}",
                "# HELP trino_tpu_page_cache_evictions_total LRU evictions "
                "under buffer-pool memory pressure.",
                "# TYPE trino_tpu_page_cache_evictions_total counter",
                f"trino_tpu_page_cache_evictions_total {bi['evictions']}",
                # result tier (round 12): statements answered whole from the
                # cache — hits here are queries that cost ZERO dispatches
                "# HELP trino_tpu_result_cache_bytes Host bytes resident in "
                "the buffer pool's result tier.",
                "# TYPE trino_tpu_result_cache_bytes gauge",
                f"trino_tpu_result_cache_bytes {bi.get('result_bytes', 0)}",
                "# HELP trino_tpu_result_cache_entries Cached statement "
                "results resident in the buffer pool.",
                "# TYPE trino_tpu_result_cache_entries gauge",
                f"trino_tpu_result_cache_entries "
                f"{bi.get('result_entries', 0)}",
                "# HELP trino_tpu_result_cache_hits_total Statements served "
                "whole from the result tier (zero device dispatches).",
                "# TYPE trino_tpu_result_cache_hits_total counter",
                f"trino_tpu_result_cache_hits_total "
                f"{bi.get('result_hits', 0)}",
                "# HELP trino_tpu_result_cache_misses_total Admissible "
                "statements not resident in the result tier.",
                "# TYPE trino_tpu_result_cache_misses_total counter",
                f"trino_tpu_result_cache_misses_total "
                f"{bi.get('result_misses', 0)}",
            ]
        # memory-pool snapshots as labeled gauges (the pool info dict finally
        # reaches the metrics endpoint — round-8 satellite)
        pools = self.engine.memory_info() \
            if hasattr(self.engine, "memory_info") else []
        if pools:
            lines += ["# HELP trino_tpu_memory_reserved_bytes Bytes reserved "
                      "in each executor memory pool.",
                      "# TYPE trino_tpu_memory_reserved_bytes gauge"]
            for d in pools:
                lines.append(f'trino_tpu_memory_reserved_bytes'
                             f'{{pool="{esc(d["pool"])}"}} {d["reserved"]}')
            lines += ["# HELP trino_tpu_memory_max_bytes Capacity of each "
                      "executor memory pool.",
                      "# TYPE trino_tpu_memory_max_bytes gauge"]
            for d in pools:
                lines.append(f'trino_tpu_memory_max_bytes'
                             f'{{pool="{esc(d["pool"])}"}} {d["max_bytes"]}')
        # resource-group queue depths (reference: the resource-group JMX
        # metrics the reference exports per group)
        groups = []
        try:
            groups = self.engine.resource_groups.info()
        except Exception:
            pass
        if groups:
            lines += ["# HELP trino_tpu_resource_group_running Queries "
                      "running per resource group.",
                      "# TYPE trino_tpu_resource_group_running gauge"]
            for g in groups:
                lines.append(f'trino_tpu_resource_group_running'
                             f'{{group="{esc(g["name"])}"}} {g["running"]}')
            lines += ["# HELP trino_tpu_resource_group_queued Queries queued "
                      "per resource group.",
                      "# TYPE trino_tpu_resource_group_queued gauge"]
            for g in groups:
                lines.append(f'trino_tpu_resource_group_queued'
                             f'{{group="{esc(g["name"])}"}} {g["queued"]}')
        return "\n".join(lines) + "\n"

    def _status_json(self) -> dict:
        """GET /v1/status payload: engine health + the live registry.  Reads
        engine state lock-free (poll-grade snapshot; nothing here may block
        on a running query — this endpoint exists precisely for when one is
        wedged)."""
        from ..execution import tracing

        e = self.engine
        health = e.health() if hasattr(e, "health") else {"status": "ok"}
        live = tracing.live_query_counters()
        inflight = tracing.INFLIGHT.snapshot()
        queries = []
        tracker = getattr(e, "query_tracker", None)
        if tracker is not None:
            for q in tracker.all_queries():
                if q.is_done:
                    continue
                i = q.info()
                queries.append({
                    "query_id": i.query_id, "state": i.state, "user": i.user,
                    "elapsed_s": round(i.elapsed_s or 0.0, 3),
                    "sql": i.sql[:500],
                    "counters": live.get(i.query_id),
                    "inflight": [f for f in inflight
                                 if f.get("query_id") == i.query_id]})
        bp = getattr(e, "buffer_pool", None)
        return {"health": health,
                "stall_report": getattr(e, "last_stall_report", None),
                "inflight": inflight,
                "queries": queries,
                "memory": e.memory_info() if hasattr(e, "memory_info") else [],
                # buffer-pool section (round 9): entries/bytes/hit rates plus
                # the per-table breakdown — "what is resident and is it
                # earning its HBM" from one poll
                "buffer_pool": bp.info() if bp is not None else None,
                "device_memory": _device_memory_stats()}

    def _query_row_count(self, q):
        """Result row count for UI surfaces: spooled queries hold their rows
        in segments, not q.rows (which _run empties after spooling)."""
        if q.segments:
            return sum(s["rows"] for s in q.segments)
        return len(q.rows) if q.rows is not None else None

    def _plan_text(self, q):
        """Best-effort EXPLAIN under the engine lock (every other execution
        path holds it; planning against catalogs mid-DDL is a race)."""
        try:
            with self._engine_lock.statement_scope("explain"):
                r = self.engine.execute_sql(f"explain {q.sql}")
            return "\n".join(str(row[0]) for row in r.rows())
        except Exception:
            return None  # DDL/statements EXPLAIN can't cover

    def _ui_overview(self) -> dict:
        """JSON cluster overview the SPA polls (reference: the web UI's
        /ui/api/stats + query list endpoints)."""
        with self._queries_lock:
            qs = sorted(self.queries.values(), key=lambda q: q.created_at,
                        reverse=True)[:100]
        pool = next((ex.memory_pool
                     for ex in getattr(self.engine, "_all_executors", ())
                     if hasattr(ex, "memory_pool")), None)
        mem = pool.info() if pool is not None else {}
        return {
            "catalogs": sorted(self.engine.catalogs),
            "memory": {"reserved": mem.get("reserved", 0),
                       "max_bytes": mem.get("max_bytes", 0)},
            "queries": [{
                "query_id": q.query_id, "state": q.state, "user": q.user,
                "elapsed": round((q.finished_at or time.time())
                                 - q.created_at, 3),
                "rows": self._query_row_count(q),
                "sql": q.sql[:200]} for q in qs],
        }

    def _ui_query_json(self, qid: str):
        q = self.queries.get(qid)
        if q is None:
            return None
        out = {"query_id": q.query_id, "state": q.state, "user": q.user,
               "elapsed": round((q.finished_at or time.time())
                                - q.created_at, 3),
               "sql": q.sql, "error": q.error,
               "columns": list(q.columns or ()),
               "rows": self._query_row_count(q)}
        if not q.error:
            plan = self._plan_text(q)
            if plan is not None:
                out["plan"] = plan
        return out

    def _ui_query_html(self, qid: str):
        """Query drill-down: full SQL, lifecycle timings, output columns, the
        error if any, and a best-effort EXPLAIN of the statement (reference:
        the web UI query page's livePlan tab, reduced to the text plan)."""
        q = self.queries.get(qid)
        if q is None:
            return None
        import html as _html

        elapsed = (q.finished_at or time.time()) - q.created_at
        parts = [_UI_STYLE, f"<h1>query {_html.escape(q.query_id)}</h1>",
                 "<p><a href='/ui'>&larr; all queries</a></p>",
                 "<table>",
                 f"<tr><th>state</th><td>{_html.escape(q.state)}</td></tr>",
                 f"<tr><th>user</th><td>{_html.escape(q.user)}</td></tr>",
                 f"<tr><th>elapsed</th><td>{elapsed:.3f}s</td></tr>"]
        if q.rows is not None:
            parts.append(f"<tr><th>result rows</th><td>{len(q.rows)}</td></tr>")
        if q.columns:
            cols = ", ".join(f"{c['name']} {c['type']}" for c in q.columns)
            parts.append(f"<tr><th>columns</th><td>{_html.escape(cols)}</td>"
                         "</tr>")
        parts.append("</table>")
        parts.append(f"<h2>sql</h2><pre>{_html.escape(q.sql)}</pre>")
        if q.error:
            parts.append(f"<h2>error</h2><pre>{_html.escape(q.error)}</pre>")
        else:
            plan_text = self._plan_text(q)
            if plan_text is not None:
                parts.append(f"<h2>plan</h2><pre>{_html.escape(plan_text)}"
                             "</pre>")
        return "".join(parts)

    # -- dispatch -----------------------------------------------------------------
    def _submit(self, sql: str, catalog: Optional[str],
                user: str = "user", params: Optional[list] = None) -> _Query:
        q = _Query(query_id=f"q{next(_qids)}", sql=sql, user=user,
                   params=params)
        with self._queries_lock:
            self.queries[q.query_id] = q
        self._pool.submit(self._run, q, catalog, user)
        return q

    def _drop_spool(self, query_id: str) -> None:
        import os
        import shutil

        if self.spool_dir is not None:
            shutil.rmtree(os.path.join(self.spool_dir, query_id),
                          ignore_errors=True)

    def _set_state(self, q: _Query, new: str) -> bool:
        """Transition unless a cancel already landed (q.lock guards the race between
        DELETE and the dispatch thread — the reference's StateMachine CAS semantics)."""
        with q.lock:
            if q.state == "CANCELED":
                return False
            q.state = new
            return True

    def _run(self, q: _Query, catalog: Optional[str],
             user: str = "user") -> None:
        from ..execution import tracing

        try:
            # the engine dates the statement's queue phase (server.queued)
            # from the POST, and records it under ITS trace id with ours as
            # an attribute: one timeline per statement
            with tracing.accepted_scope(q.accepted_pc,
                                        server_query_id=q.query_id), \
                    self._engine_lock.statement_scope(q.sql):
                if not self._set_state(q, "PLANNING"):
                    return  # canceled while queued: never execute
                session = self.engine.create_session(catalog)
                session.user = user
                if not self._set_state(q, "RUNNING"):
                    return
                try:
                    if q.params is not None:
                        res = self.engine.execute_sql(q.sql, session,
                                                      parameters=q.params)
                    else:
                        res = self.engine.execute_sql(q.sql, session)
                finally:
                    # the engine publishes the trace on the executing THREAD
                    # (concurrent read statements share last_query_trace, so
                    # the global slot may already be another statement's) —
                    # and FAILED statements keep theirs too (a failed query
                    # is when the trace is most wanted).  No fallback to the
                    # shared slot: a None here (statement failed before
                    # admission) is honest, another statement's trace isn't.
                    acct = getattr(self.engine, "_thread_accounting", None)
                    trace = getattr(acct, "trace", None)
                    if trace:  # our own copy: the server's phases join it
                        q.trace = dict(trace, spans=list(trace["spans"]))
                        q.root = getattr(acct, "root", None)
            encode_pc = time.perf_counter()
            with tracing.annotate("server.encode",
                                  (q.trace or {}).get("query_id")):
                if res is None:  # DDL
                    columns = [{"name": "result", "type": "boolean"}]
                    rows = [[True]]
                else:
                    columns = [{"name": n, "type": t.name}
                               for n, t in zip(res.names, res.types)]
                    rows = [[_json_value(v) for v in row]
                            for row in res.rows()]
                if self.spool_dir is not None \
                        and len(rows) >= self.spool_threshold_rows:
                    segments = self._spool_rows(q.query_id, rows)
                    rows = []  # spooled results live on disk, not inline
                else:
                    segments = None
                with q.lock:
                    canceled = q.state == "CANCELED"
                    if not canceled:
                        q.segments = segments
                        q.columns = columns
                        q.rows = rows
                        # stamped BEFORE the state is published: eviction
                        # orders terminal statements by it
                        q.finished_at = time.time()
                        finished_pc = q.finished_pc = time.perf_counter()
                        q.state = "FINISHED"
            if canceled and segments:
                self._drop_spool(q.query_id)  # orphaned mid-cancel segments
            if not canceled:
                self._phase(q, "server.encode", "encode_s",
                            finished_pc - encode_pc, q.finished_at)
        except Exception as e:  # noqa: BLE001 - protocol surface reports all failures
            with q.lock:
                if q.state != "CANCELED":
                    q.error = f"{type(e).__name__}: {e}"
                    q.finished_at = time.time()
                    q.state = "FAILED"
            traceback.print_exc()
        finally:
            if q.finished_at is None:  # canceled
                q.finished_at = time.time()
            self._evict_finished()

    def _phase(self, q: _Query, name: str, field: str, seconds: float,
               end_s: Optional[float] = None) -> None:
        """One of the server's own phases of a statement, measured after the
        engine closed it: a span under the engine's root span (in the
        engine's tracer and in the tree this server serves for the
        statement, carrying our query id) and seconds on the engine's
        totals, under the lock its own merge uses."""
        tracer = getattr(self.engine, "tracer", None)
        if q.root is not None and tracer is not None:
            from ..execution.tracing import span_dict

            span = tracer.add_completed(name, seconds, parent=q.root,
                                        end_s=end_s,
                                        server_query_id=q.query_id)
            q.trace["spans"].append(span_dict(span))
        totals = getattr(self.engine, "counters_total", None)
        lock = getattr(self.engine, "_init_lock", None)
        if totals is not None and lock is not None:
            with lock:
                setattr(totals, field, getattr(totals, field) + seconds)

    def _delivered(self, q: _Query) -> None:
        """The response that carries the statement's last page was written:
        close ``server.deliver`` (FINISHED published -> here: the client's
        poll lag), once."""
        with q.lock:
            finished_pc, q.finished_pc = q.finished_pc, None
        if finished_pc is not None:
            self._phase(q, "server.deliver", "deliver_wait_s",
                        time.perf_counter() - finished_pc)

    def _evict_finished(self, keep: int = 100) -> None:
        """Bound coordinator memory: retain only the most recent terminal queries'
        results (reference: QueryTracker expiration)."""
        with self._queries_lock:
            done = [q for q in self.queries.values()
                    if q.state in ("FINISHED", "FAILED", "CANCELED")]
            done.sort(key=lambda q: q.finished_at or 0)
            for q in done[:-keep] if len(done) > keep else []:
                self.queries.pop(q.query_id, None)
                self._drop_spool(q.query_id)

    # -- responses ----------------------------------------------------------------
    def _queued_response(self, q: _Query) -> dict:
        return {
            "id": q.query_id,
            "nextUri": f"{self.url}/v1/statement/executing/{q.query_id}/0",
            "stats": {"state": q.state},
        }

    def _results_response(self, q: _Query, token: int) -> dict:
        if q.state == "FAILED":
            return {"id": q.query_id, "stats": {"state": q.state},
                    "error": {"message": q.error}}
        if q.state == "CANCELED":  # terminal: no nextUri, client stops polling
            return {"id": q.query_id, "stats": {"state": q.state},
                    "error": {"message": "query was canceled"}}
        if q.state not in ("FINISHED",):
            # still running: client re-polls the same token (long-poll analog)
            return {"id": q.query_id, "stats": {"state": q.state},
                    "nextUri": f"{self.url}/v1/statement/executing/{q.query_id}/{token}"}
        if q.segments is not None:
            # spooled protocol: one response carries every segment descriptor;
            # the client fetches payloads straight from the spool URIs
            # (reference: server/protocol/spooling/ — segments of
            # json+zstd/json+lz4; the in-tree codec here is json+zlib)
            return {
                "id": q.query_id,
                "columns": q.columns,
                "segments": [
                    {"uri": f"{self.url}/v1/spooled/{q.query_id}/{i}",
                     "encoding": "json+zlib", "rowCount": seg["rows"],
                     "uncompressedSize": seg["raw_bytes"]}
                    for i, seg in enumerate(q.segments)],
                "stats": {"state": q.state,
                          "totalRows": sum(s["rows"] for s in q.segments)},
            }
        lo = token * DATA_ROWS_PER_FETCH
        hi = lo + DATA_ROWS_PER_FETCH
        out = {
            "id": q.query_id,
            "columns": q.columns,
            "data": q.rows[lo:hi],
            "stats": {"state": q.state, "totalRows": len(q.rows)},
        }
        if hi < len(q.rows):
            out["nextUri"] = (
                f"{self.url}/v1/statement/executing/{q.query_id}/{token + 1}")
        return out

    def _spool_rows(self, query_id: str, rows) -> list:
        """Write result rows as compressed JSON segments; returns descriptors.
        Segment size follows the inline page size so the client's memory
        profile matches the paged path."""
        import os
        import zlib

        d = os.path.join(self.spool_dir, query_id)
        os.makedirs(d, exist_ok=True)
        segments = []
        for i in range(0, max(len(rows), 1), DATA_ROWS_PER_FETCH):
            chunk = rows[i:i + DATA_ROWS_PER_FETCH]
            raw = json.dumps(chunk).encode()
            with open(os.path.join(d, f"seg_{len(segments)}"), "wb") as f:
                f.write(zlib.compress(raw, 1))
            segments.append({"rows": len(chunk), "raw_bytes": len(raw)})
        return segments

    def _read_segment(self, query_id: str, seg: str):
        import os

        if self.spool_dir is None or not seg.isdigit() \
                or query_id not in self.queries:
            return None
        path = os.path.join(self.spool_dir, query_id, f"seg_{int(seg)}")
        if not os.path.exists(path):
            return None
        with open(path, "rb") as f:
            return f.read()

    def _flight_index(self) -> dict:
        """GET /v1/flight payload: recorder info + one summary per retained
        record (full records via /v1/flight/{id})."""
        fr = getattr(self.engine, "flight_recorder", None)
        if fr is None:
            return {"info": {"enabled": False}, "records": []}
        out = []
        for rec in fr.snapshot():
            out.append({
                "kind": rec.get("kind"), "query_id": rec.get("query_id"),
                "state": rec.get("state"), "wall_s": rec.get("wall_s"),
                "error": (rec.get("error") or "")[:200] or None,
                "recorded_at": rec.get("recorded_at"),
                "spans": len((rec.get("trace") or {}).get("spans") or ()),
                "sql": (rec.get("sql") or "")[:200] or None})
        return {"info": fr.info(), "records": out}

    def _flight_record(self, qid: str):
        fr = getattr(self.engine, "flight_recorder", None)
        return fr.get(qid) if fr is not None else None

    def _compiles_json(self) -> dict:
        """GET /v1/compiles payload: compile-census state (lifetime totals,
        storm detections) + the retained per-compilation records."""
        cl = getattr(self.engine, "compile_log", None)
        if cl is None:
            return {"info": {"enabled": False}, "records": []}
        return {"info": cl.info(), "records": cl.snapshot()}

    def _query_trace(self, qid: str):
        """OTLP/JSON trace for a server query id or the engine's id of the
        same statement (one tree: the captured trace plus the server's own
        phases), an ENGINE
        or CLUSTER query id served from the FLIGHT RECORDER (round-16
        satellite: a completed statement's trace resolves long after the
        next statement landed — and a distributed query's record carries the
        stitched worker spans the live tracer never sees), or, last, a live
        lookup against the engine tracer (running statements, recorder
        disabled)."""
        from ..execution.tracing import spans_to_otlp

        q = self.queries.get(qid)
        if q is None:  # the engine's id of a statement this server ran
            q = next((x for x in list(self.queries.values())
                      if x.trace and x.trace.get("query_id") == qid), None)
        if q is not None:
            if not q.trace:
                return None
            return spans_to_otlp(q.trace.get("spans", ()))
        fr = getattr(self.engine, "flight_recorder", None)
        if fr is not None:
            rec = fr.get(qid)
            spans = (rec.get("trace") or {}).get("spans") if rec else None
            if spans:
                return spans_to_otlp(spans)
        tracer = getattr(self.engine, "tracer", None)
        if tracer is not None:
            spans = tracer.spans_for(qid)
            if spans:
                return spans_to_otlp(spans)
        return None

    def _query_info(self, q: _Query) -> dict:
        return {
            "queryId": q.query_id,
            "state": q.state,
            "query": q.sql,
            "error": q.error,
            "elapsedMs": round(((q.finished_at or time.time()) - q.created_at) * 1000),
            # the root span's seconds by bucket, and under which container
            # span the unattributed part sits (``unattributed_by``)
            "wallBreakdown": (q.trace or {}).get("wall_breakdown"),
        }
