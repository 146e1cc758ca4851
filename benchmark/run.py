"""The benchmark's command: one cell, one seed, one window.

    python -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>
    python -m benchmark.run --workload <name> --seed 7 --seconds 3 --trace 0 --rehearse

ONE process, the only one to touch JAX.  It reads the cell from BENCHMARK.json and
builds the deployment that the cell's configuration file describes, with the program's
defaults (no TRINO_TPU_* switch is set here): the ``connector`` (CONNECTORS) at ``sf``
and ``split_rows``, registered under ``catalog`` in one Engine behind one
CoordinatorServer; with ``chips`` over 1 the engine runs every statement on
``worker_mesh(chips)``.  It warms up the cell's statements (set-up, reported as
``setup_s``), drives the cell's traffic over POST /v1/statement for ``--seconds``, and
only then pulls the reference's columns to the host and compares every checked answer.
Earlier lines of stdout are facts as JSON; the LAST line is the contract's result object.

Without ``--rehearse`` a backend that is not a TPU, or one with fewer chips than the
cell asks for, is a non-zero exit with no result line.  ``--rehearse`` runs the same
control flow on the CPU backend at the config's ``rehearse_sf`` (on as many host devices
as the cell has chips) and never names a TPU.
"""

import argparse
import importlib
import json
import os
import random
import shutil
import sys
import time

_T0 = time.perf_counter()

from benchmark.harness import compare, stats, xplane  # noqa: E402
from benchmark.harness.loader import BenchmarkError, Cell  # noqa: E402

WARM_RUNS_MAX = 4
TRACE_SAMPLE = 40
# a configuration's ``connector`` -> the program's class; each takes (sf=, split_rows=)
CONNECTORS = {"tpch": "trino_tpu.connectors.tpch.TpchConnector",
              "tpcds": "trino_tpu.connectors.tpcds.TpcdsConnector"}


def say(**facts):
    print(json.dumps(facts, default=str), flush=True)


class Context:
    """What a metric's reader may read: see benchmark/README.md."""

    def __init__(self, bench):
        self.cell = bench.cell
        self.config = bench.cell.config
        self.device = bench.device
        self.setup_s = bench.setup_s
        self.records = []       # the window's statements, in completion order
        self.window_s = None    # window start to the last completion
        self.counters = {}      # engine.counters_total, window delta
        self.pool = {}          # engine.buffer_pool.info(), window delta of its counts
        self.trace = None       # xplane.reduce_trace() of the traced window
        self.overhead_s = {}    # statement name -> [client seconds - server root span]
        self._bench = bench

    def row_count(self, table):
        return self._bench.conn.row_count(table)

    def base_rows(self, name):
        """Base-table rows one execution of the statement reads."""
        return sum(self.row_count(t) for t in self.cell.statements[name].TABLES)

    def completed(self, name=None):
        return [r for r in self.records
                if r["error"] is None and (name is None or r["name"] == name)]


def engine_on_mesh(chips):
    """The program's engine with every statement on ``worker_mesh(chips)``.  The mesh is
    an argument of ``execute_sql`` that the served path never passes, and the constructor
    takes none (PERF.md section 7), so the default is set here: a caller that names
    neither ``distributed`` nor ``mesh`` gets both, as a deployment on a mesh would."""
    from trino_tpu import Engine
    from trino_tpu.parallel.mesh import worker_mesh

    class MeshEngine(Engine):
        def execute_sql(self, sql, session=None, distributed=None, mesh=None, **kwargs):
            if distributed is None and mesh is None:
                distributed, mesh = True, self.mesh
            return super().execute_sql(sql, session, bool(distributed), mesh, **kwargs)

    engine = MeshEngine()
    engine.mesh = worker_mesh(chips)
    return engine


def connector_class(config):
    """The program's connector class that the configuration names."""
    if config["connector"] not in CONNECTORS:
        raise BenchmarkError(f"configuration {config['name']!r} names the connector "
                             f"{config['connector']!r}: one of {sorted(CONNECTORS)}")
    module, cls = CONNECTORS[config["connector"]].rsplit(".", 1)
    return getattr(importlib.import_module(module), cls)


class Bench:
    def __init__(self, cell, rehearse):
        import jax

        self.cell, self.rehearse = cell, rehearse
        devices = jax.devices()
        dev = devices[0]
        if (dev.platform == "tpu") == rehearse:
            raise BenchmarkError(
                f"backend is {dev.platform!r}: " + ("--rehearse is for the CPU backend"
                                                   if rehearse else "a TPU is required"))
        if len(devices) < cell.chips:
            raise BenchmarkError(f"cell {cell.name} needs {cell.chips} chips, found {len(devices)}")
        self.jax = jax
        self.device = {"platform": dev.platform, "kind": dev.device_kind, "count": cell.chips}
        if not rehearse:
            cell.peak(dev.device_kind)  # an unknown device kind is an error, not a default

        from trino_tpu import Engine
        from trino_tpu.server.server import CoordinatorServer

        cfg = cell.config
        if rehearse:
            cfg["sf"] = cfg["rehearse_sf"]
        self.conn = connector_class(cfg)(sf=cfg["sf"], split_rows=cfg["split_rows"])
        self.engine = Engine() if cell.chips == 1 else engine_on_mesh(cell.chips)
        self.engine.register_catalog(cfg["catalog"], self.conn)
        self.server = CoordinatorServer(self.engine, port=0)
        self.server.start()
        self.setup_records = []
        self.setup_s = None
        self.trace_dir = os.path.join(cell.root, ".bench_trace", cell.name)
        self._references = {}

    def close(self):
        self.server.stop()

    def _client(self):
        from benchmark.harness import loop

        return loop.RecordingClient(self.server.url, catalog=self.cell.config["catalog"])

    # -- set-up --------------------------------------------------------------------
    def setup(self, seed):
        """Each of the cell's statements with the seed's first parameter draw until a
        run compiles nothing (the adaptive advisor may re-plan once after the cold run),
        then the traffic's warm burst, if it asks for one."""
        from benchmark.harness import loop

        client = self._client()
        rng = loop.client_rng(seed, 0, "setup")
        for name, statement in self.cell.statements.items():
            p = statement.VALIDATION if self.cell.traffic["params"][name] == "fixed" \
                else statement.params(rng, self.cell.config)
            runs, lookups, build_s = [], [], []
            for _ in range(WARM_RUNS_MAX):
                before = self._build_lookups()
                rec = loop.execute(client, statement, name, p,
                                   self.cell.traffic["statement_timeout_s"], engine=self.engine)
                runs.append(rec)
                lookups.append(self._build_lookups() - before)
                build_s.append(self._build_seconds())
                if rec["error"] or not rec["compiles"]:
                    break
            self.setup_records.extend(runs)
            # a join's build side is executed when its stream is compiled, and a replayed
            # text reuses the compiled stream: a run with 0 lookups built nothing
            say(setup=name, params=p, seconds=[round(r["seconds"], 4) for r in runs],
                compiles=[r["compiles"] for r in runs], build_cache_lookups=lookups,
                build_s_when_compiled=build_s, error=runs[-1]["error"])
            if runs[-1]["error"] or runs[-1]["compiles"]:
                raise BenchmarkError(f"set-up of {name} did not reach a run without "
                                     f"compiles in {WARM_RUNS_MAX}: {runs[-1]['error']}")
        for i, burst in enumerate(self.cell.traffic.get("warm_bursts", ())):
            # the batcher's fused programs (one per template and batch rung) compile only
            # under concurrent load: repeat each burst until one compiles nothing
            for attempt in range(WARM_RUNS_MAX):
                before = self.engine.counters_total.compiles
                records, _ = loop.closed_loop(
                    self.server.url, self.cell, seed, burst["seconds"],
                    f"burst{i}.{attempt}", slots=burst.get("slots"))
                self.setup_records.extend(records)
                compiles = self.engine.counters_total.compiles - before
                say(setup="burst", slots=burst.get("slots", "the mix"), attempt=attempt,
                    statements=len(records), compiles=compiles)
                if not compiles:
                    break
        self.setup_s = time.perf_counter() - _T0
        return self.setup_s

    def _build_lookups(self):
        info = self.engine.buffer_pool.info()
        return info["build_hits"] + info["build_misses"]

    def _build_seconds(self):
        """Host seconds of each join build side of the last statement's plan, as the
        engine recorded them when it compiled the streams now in use (first-run seconds
        include tracing and compile-cache reads; a build-cache hit records 0)."""
        nodes = (self.engine.last_plan_actuals or {}).get("nodes") or {}
        return [round(r.get("wall_s", 0.0), 4) for r in nodes.values() if r.get("build")]

    # -- the window ----------------------------------------------------------------
    def window(self, seed, seconds, trace):
        from benchmark.harness import loop

        ctx = Context(self)
        if trace:
            seconds = min(seconds, self.cell.traffic["trace_seconds"])
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            self.jax.profiler.start_trace(self.trace_dir)
        counters0 = self.engine.counters_total.as_dict()
        pool0 = self.engine.buffer_pool.info()
        try:
            ctx.records, start = loop.closed_loop(
                self.server.url, self.cell, seed, seconds, "window",
                engine=self.engine, annotate=bool(trace))
        finally:
            if trace:
                self.jax.profiler.stop_trace()
        counters1 = self.engine.counters_total.as_dict()
        pool1 = self.engine.buffer_pool.info()
        ctx.window_s = max(r["t1"] for r in ctx.records) - start
        ctx.counters = {k: counters1[k] - v for k, v in counters0.items()
                        if isinstance(v, (int, float))}
        ctx.pool = {k: pool1[k] - v for k, v in pool0.items()
                    if isinstance(v, (int, float)) and not isinstance(v, bool)}
        stats_ = self.jax.devices()[0].memory_stats() or {}
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.jax.devices()[:self.cell.chips]]
        self.device["memory_peak_bytes"] = max(peaks, default=0)
        say(memory={"peak_bytes_in_use": self.device["memory_peak_bytes"],
                    "peak_bytes_in_use_by_device": peaks,
                    "bytes_limit": stats_.get("bytes_limit"),
                    "page_cache_budget_bytes": pool1.get("budget_bytes"),
                    "page_cache_bytes": pool1.get("bytes"), "evictions": pool1.get("evictions"),
                    "per_table": pool1.get("per_table")})
        if trace:
            t0 = time.perf_counter()
            path = xplane.find_trace(self.trace_dir)
            ctx.trace = xplane.reduce_trace(path, cpu_stand_in=self.rehearse)
            say(trace_bytes=os.path.getsize(path), reduce_s=time.perf_counter() - t0)
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            self._fetch_overheads(ctx)
        return ctx

    def _fetch_overheads(self, ctx):
        """Client seconds minus the server's root span, for a sample of each class,
        fetched after the window from GET /v1/query/{id}/trace (the server keeps the
        last hundred statements)."""
        client = self._client()
        for name in self.cell.statements:
            for rec in ctx.completed(name)[-TRACE_SAMPLE:]:
                try:
                    payload = client._request(
                        f"{self.server.url}/v1/query/{rec['query_id']}/trace")
                except OSError:
                    continue
                spans = [s for rs in payload.get("resourceSpans", ())
                         for ss in rs.get("scopeSpans", ()) for s in ss.get("spans", ())]
                root = next((s for s in spans
                             if s.get("name") == "query" and not s.get("parentSpanId")), None)
                if root is not None:
                    root_s = (int(root["endTimeUnixNano"]) - int(root["startTimeUnixNano"])) / 1e9
                    ctx.overhead_s.setdefault(name, []).append(rec["seconds"] - root_s)

    # -- the comparison, outside the window ------------------------------------------
    def host_tables(self):
        from benchmark.harness.hosttables import HostTables

        wanted = {}
        for statement in self.cell.statements.values():
            for table, cols in statement.TABLES.items():
                wanted.setdefault(table, []).extend(cols)
        return HostTables(self.conn, wanted)

    def reference(self, tables, name, p, dtype=None):
        key = (name, json.dumps(p, sort_keys=True), dtype)
        if key not in self._references:
            statement = self.cell.statements[name]
            self._references[key] = (statement.reference(tables, p) if dtype is None
                                     else statement.reference(tables, p, dtype=dtype))
        return self._references[key]

    def sample(self, records, seed):
        """The records to compare: all, or the traffic file's seeded sample of them
        (with the slowest in it)."""
        check = self.cell.traffic["check"]
        if check == "all" or not records:
            return list(records)
        n = int(check.split(":")[1])
        done = [r for r in records if r["error"] is None]
        failed = [r for r in records if r["error"] is not None]
        longest = max(done, key=lambda r: r["seconds"], default=None)
        rest = [r for r in done if r is not longest]
        random.Random(f"{seed}/check").shuffle(rest)
        return failed + ([longest] if longest else []) + rest[:max(n - 1, 0)]

    def check(self, tables, records, control_dtype=None):
        """Compares each record's answer with the reference; sets ``ok`` and ``numbers``
        on it.  With ``control_dtype`` the reference computed in that precision stands
        in the program's place (the control: it has to come out as not correct)."""
        import pandas as pd

        failed = 0
        for rec in records:
            statement = self.cell.statements[rec["name"]]
            if rec["error"] is not None:
                rec["ok"], rec["numbers"] = False, None
                failed += 1
                continue
            want = self.reference(tables, rec["name"], rec["params"])
            if control_dtype is None:
                got = pd.DataFrame(rec["rows"], columns=rec["columns"])
            else:
                got = self.reference(tables, rec["name"], rec["params"], dtype=control_dtype)
            rec["numbers"] = compare.compare(got, want, getattr(statement, "AVG_DECIMALS", None))
            rec["ok"] = compare.within_limits(rec["numbers"])
            if control_dtype is None and rec.get("dispatches") == 0:
                rec["ok"] = False  # the answer did not come from the device path
            failed += not rec["ok"]
        return failed


def metrics_of(ctx, entries):
    out = {}
    for m in entries:
        value = m["read"](ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the CPU backend at the config's rehearse_sf (never names a TPU)")
    args = ap.parse_args(argv)
    try:
        cell = Cell(args.workload)
        if args.rehearse and cell.chips > 1 \
                and "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
            # the CPU backend has one device unless asked, and is asked before JAX starts
            os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " --xla_force_host_"
                                       f"platform_device_count={cell.chips}").strip()
        bench = Bench(cell, args.rehearse)
    except BenchmarkError as e:
        print(f"benchmark.run: {e}", file=sys.stderr)
        return 2
    try:
        say(workload=cell.name, config=cell.config["name"], traffic=cell.traffic["name"],
            connector=cell.config["connector"], catalog=cell.config["catalog"],
            sf=cell.config["sf"], seed=args.seed, device=bench.device,
            cache_dir=bench.jax.config.jax_compilation_cache_dir)
        bench.setup(args.seed)
        say(setup_s=bench.setup_s)
        ctx = bench.window(args.seed, args.seconds, args.trace)
        t0 = time.perf_counter()
        tables = bench.host_tables()
        checked = bench.sample(ctx.records, args.seed)
        failed = bench.check(tables, checked)
        setup_failed = bench.check(tables, bench.setup_records)
        result = report(bench, ctx, checked, failed, setup_failed, args.trace)
        say(check_s=time.perf_counter() - t0)
    except BenchmarkError as e:
        print(f"benchmark.run: {e}", file=sys.stderr)
        return 3
    finally:
        bench.close()
    print(json.dumps(result), flush=True)
    return 0


def _seconds_of(records):
    seconds = [r["seconds"] for r in records]
    return {"n": len(seconds), "median_s": stats.median(seconds),
            "mean_s": sum(seconds) / len(seconds), "max_s": max(seconds)}


def report(bench, ctx, checked, failed, setup_failed, trace):
    cell = bench.cell
    window_compiles = ctx.counters.get("compiles", 0)
    numbers = compare.worst([r["numbers"] for r in checked + bench.setup_records
                             if r.get("numbers")])
    say(compared={k: {"value": numbers[k], "limit": compare.LIMITS[k]} for k in numbers},
        statements_in_window=len(ctx.records), statements_compared=len(checked),
        setup_statements_compared=len(bench.setup_records), setup_failed=setup_failed,
        window_s=ctx.window_s, window_compiles=window_compiles,
        by_statement={n: _seconds_of(ctx.completed(n))
                      for n in cell.statements if ctx.completed(n)},
        # where in the window the slowest statements fell: [position, name, seconds]
        slowest=sorted(([i, r["name"], r["seconds"]] for i, r in enumerate(ctx.records)),
                       key=lambda x: -x[2])[:3],
        lost=sum(r["lost"] for r in ctx.records),
        result_cache_hits=ctx.counters.get("result_cache_hits", 0),
        device_dispatches=ctx.counters.get("device_dispatches", 0))
    for rec in checked + bench.setup_records:
        if not rec["ok"]:
            say(wrong=rec["name"], params=rec["params"], error=rec["error"],
                numbers=rec["numbers"], dispatches=rec.get("dispatches"))
    # every answer has to come from the device path: no result-cache tier, and dispatches
    on_device = ctx.counters.get("device_dispatches", 0) > 0 \
        and ctx.counters.get("result_cache_hits", 0) == 0
    entries = cell.per_layer if trace else cell.end_to_end
    device = dict(bench.device)
    result = {"correct": bool(failed == 0 and setup_failed == 0 and on_device and checked),
              "attempted": len(ctx.records),
              "failed": sum(1 for r in ctx.records if r["error"] is not None or r.get("ok") is False),
              "metrics": metrics_of(ctx, entries), "device": device}
    if trace:
        device["busy_s"], device["window_s"] = ctx.trace["busy_s"], ctx.trace["window_s"]
        device["busy_s_by_device"] = ctx.trace["busy_s_by_device"]
        result["breakdown"] = {"device_ops": ctx.trace["device_ops"],
                               "idle_gaps": ctx.trace["idle_gaps"]}
    return result


if __name__ == "__main__":
    sys.exit(main())
