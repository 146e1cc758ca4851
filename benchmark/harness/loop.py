"""The closed loop: each client sends its next statement when the last one has answered.

One general generator for every traffic file.  A traffic file gives ``clients``, the
``slots`` of its cycle (statement names), ``order`` (``cycle``: the slots as written,
client k starting at slot k * ``stride`` so that classes interleave, and the window
closes with the statement in flight at its end; ``seeded_rounds``: whole rounds of the
cycle, each in an order drawn from the seed as TPC-H's query streams are, and a round is
started only while the last round's seconds still fit into the window, so every run
does the same statements and none measures past ``--seconds``), ``params`` per statement
(``fixed``: the statement's VALIDATION values, a replay of one text; ``fresh``: a draw
from its substitution ranges for every execution), ``poll_interval`` and
``statement_timeout_s`` (what the client gives one statement).  Clients are opened on the
configuration's ``catalog``.  Every client draws from its own ``random.Random`` seeded
from ``--seed`` and the client's index, so the same seed gives the same statements
whatever the threads do.
"""

import contextlib
import random
import threading
import time
import urllib.error

from trino_tpu.server.client import Client


class RecordingClient(Client):
    """The program's own client; only remembers the id of the statement it posted, so
    that its trace can be fetched after the window."""

    last_id = None

    def _request(self, url, method="GET", body=None, extra_headers=None):
        out = super()._request(url, method, body, extra_headers)
        if method == "POST":
            self.last_id = out.get("id")
        return out


def client_rng(seed, client, phase):
    return random.Random(f"{seed}/{phase}/{client}")


def execute(client, statement, name, p, timeout_s, engine=None, annotate=False):
    """One statement from ``Client.execute`` call to the last page of its answer, as a
    record; the client gives it ``timeout_s``.  ``engine`` (single-client cells only: the
    engine's last-statement facts are shared state) adds the statement's own counters."""
    sql, bound = statement.render(p)
    scope = contextlib.nullcontext()
    if annotate:
        import jax

        scope = jax.profiler.TraceAnnotation("inside " + name)
    rec = {"name": name, "params": p, "error": None, "columns": None, "rows": None, "lost": 0}
    with scope:
        rec["t0"] = time.perf_counter()
        for attempt in (0, 1):
            try:
                res = client.execute(sql, timeout=timeout_s, params=bound)
                rec["columns"], rec["rows"] = res.column_names, res.rows
                rec["error"] = None
                break
            except urllib.error.HTTPError as e:
                # the server can evict a statement between finishing it and stamping its
                # finish time (PERF.md, findings of PR 24): the poll then answers 404.
                # A client sends a read-only statement again, once; its seconds count both.
                rec["error"] = f"{type(e).__name__}: {e}"
                if e.code != 404:
                    break
                rec["lost"] += 1
            except Exception as e:  # a failed statement is a result, counted in `failed`
                rec["error"] = f"{type(e).__name__}: {e}"
                break
        rec["t1"] = time.perf_counter()
    rec["seconds"] = rec["t1"] - rec["t0"]
    rec["query_id"] = client.last_id
    if engine is not None:
        c = engine.last_query_counters
        rec["dispatches"], rec["compiles"] = c.device_dispatches, c.compiles
        rec["plan_s"] = ((engine.last_query_trace or {}).get("wall_breakdown") or {}).get("plan")
    return rec


def closed_loop(url, cell, seed, seconds, phase, engine=None, annotate=False, slots=None):
    """Runs the cell's traffic for ``seconds``; a statement in flight at the end is
    finished and counted (``seeded_rounds``: the first round always runs, a later one
    only if it fits).  ``slots`` replaces the traffic's cycle (a warm burst of one class).
    Returns (records in completion order, window start)."""
    traffic = cell.traffic
    clients, slots = traffic["clients"], slots or traffic["slots"]
    stride = traffic.get("stride", 0)
    rounds = traffic.get("order", "cycle") == "seeded_rounds"
    records, lock = [], threading.Lock()
    single = engine if clients == 1 else None

    def run(k, stop_at):
        client = RecordingClient(url, catalog=cell.config["catalog"],
                                 poll_interval=traffic.get("poll_interval", 0.05))
        rng = client_rng(seed, k, phase)
        i, order, round_t0 = k * stride, list(slots), None
        while True:
            now = time.perf_counter()
            if rounds and i % len(slots) == 0:
                if round_t0 is not None and now + (now - round_t0) > stop_at:
                    break
                round_t0 = now
                rng.shuffle(order)
            elif not rounds and now >= stop_at:
                break
            name = order[i % len(slots)]
            i += 1
            statement = cell.statements[name]
            p = statement.VALIDATION if traffic["params"][name] == "fixed" \
                else statement.params(rng, cell.config)
            rec = execute(client, statement, name, p, traffic["statement_timeout_s"],
                          engine=single, annotate=annotate)
            rec["client"] = k
            with lock:
                records.append(rec)

    start = time.perf_counter()
    threads = [threading.Thread(target=run, args=(k, start + seconds), daemon=True)
               for k in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return records, start
