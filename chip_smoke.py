"""Chip smoke: the served TPC-H SF1 path, end to end, on one directly attached TPU.

    python chip_smoke.py              # one chip: Engine + CoordinatorServer, q1/q3/q4/q9/q18
                                      # through POST /v1/statement, each checked against pandas
    python chip_smoke.py --mesh       # four chips: ONLY q1/q3/q18 over worker_mesh() of all
                                      # devices, checked against pandas and the one-chip answer
                                      # (cold, q1+q3 alone took 750 s: allow 1800 s)
    python chip_smoke.py --rehearse   # the same control flow on the CPU backend at SF0.01
                                      # (JAX_PLATFORMS=cpu; never reports a TPU)

ONE process, the only one to touch JAX (a chip belongs to one process at a time).
Without ``--rehearse`` a backend that is not a TPU is a non-zero exit with no result
line.  Any exception or failed check in any phase is a non-zero exit.  Data is
generated on the device from the connector's seed; nothing is read from the repo
but its code, and the compile cache is whatever ``trino_tpu/__init__.py`` placed
(``$JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``).

Earlier lines are facts for CHANGES.md ("one run, not a benchmark"); the LAST line
of stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

import argparse
import json
import os
import sys
import time

SERVED = ("q1", "q3", "q4", "q9", "q18")
MESHED = ("q1", "q3", "q18")
# oracle columns that bench.py's pandas twins leave as scaled-decimal ints (x100)
ORACLE_SCALE = {"q18": {"o_totalprice": 100.0, "l_quantity": 100.0}}
# where the pandas twin's column order is not the statement's SELECT order
ORACLE_ORDER = {"q3": ["l_orderkey", "revenue", "o_orderdate", "o_shippriority"]}
# avg(decimal(p,2)) is a decimal(p,2) in SQL and an exact float mean in pandas:
# those columns agree to half a unit of the last decimal place
ORACLE_DECIMALS = {"q1": {"avg_qty": 2, "avg_pr": 2, "avg_dc": 2}}


class SmokeFailure(AssertionError):
    pass


def _say(**facts) -> None:
    print(json.dumps(facts, default=str), flush=True)


def _column(values, scale: float = 1.0):
    """One result column in comparable form: strings stay strings, everything
    else becomes float64 (dates as days since the epoch)."""
    import numpy as np
    import pandas as pd

    vals = list(values)
    if vals and isinstance(vals[0], str):
        try:  # ISO dates arrive as strings over HTTP
            return (pd.to_datetime(vals, format="%Y-%m-%d").to_numpy()
                    .astype("datetime64[D]").astype(np.int64).astype(np.float64))
        except ValueError:
            return np.array(vals, dtype=object)
    s = pd.Series(vals)
    if pd.api.types.is_datetime64_any_dtype(s):
        return s.to_numpy().astype("datetime64[D]").astype(np.int64).astype(np.float64)
    return s.astype(np.float64).to_numpy() / scale


def check_answer(name: str, got, want, what: str) -> None:
    """Positional, order-sensitive comparison of an engine answer (pandas frame)
    with the pandas oracle's frame — every bench statement has an ORDER BY."""
    import numpy as np

    want = want[ORACLE_ORDER.get(name, list(want.columns))]
    if got.shape != want.shape:
        raise SmokeFailure(f"{name} {what}: shape {got.shape} != oracle {want.shape}")
    for j, wname in enumerate(want.columns):
        g = _column(got.iloc[:, j])
        w = _column(want.iloc[:, j], ORACLE_SCALE.get(name, {}).get(wname, 1.0))
        if g.dtype == object or w.dtype == object:
            same = list(g) == list(w)
        else:
            places = ORACLE_DECIMALS.get(name, {}).get(wname)
            atol = 1e-6 if places is None else 0.5 * 10.0 ** -places + 1e-9
            same = np.allclose(g, w, rtol=1e-9, atol=atol)
        if not same:
            raise SmokeFailure(f"{name} {what}: column {j} ({got.columns[j]} vs "
                               f"oracle {wname}) differs:\n{got.head()}\n{want.head()}")


def _kernel_facts() -> dict:
    from trino_tpu.exec.local_executor import _scan_fused_enabled
    from trino_tpu.ops import pallas_kernels as pk

    on = pk.use_pallas()
    return {"use_pallas": on, "interpret": pk.pallas_interpret(),
            "hash_probe_insert_capacities": [2, pk.PALLAS_TABLE_MAX] if on else None,
            "compact_out_rows_max": pk.COMPACT_OUT_MAX if on else None,
            "scan_fused": _scan_fused_enabled()}


def _cache_files() -> int:
    import jax

    d = jax.config.jax_compilation_cache_dir
    return sum(len(files) for _, _, files in os.walk(d)) if d and os.path.isdir(d) else 0


def _counters(c) -> dict:
    return {"compiles": c.compiles, "compile_s": round(c.compile_s, 3),
            "dispatches": c.device_dispatches, "host_bytes": c.host_bytes_pulled}


def served_phase(engine, oracle, on_tpu: bool) -> None:
    """q1/q3/q4/q9/q18 through Client.execute -> POST /v1/statement, each once
    cold and twice warm, every answer against the pandas oracle; warm runs
    must not compile."""
    import bench
    import jax
    from trino_tpu.server.client import Client
    from trino_tpu.server.server import CoordinatorServer

    srv = CoordinatorServer(engine, port=0)
    srv.start()
    try:
        client = Client(srv.url, catalog="tpch")
        for name in SERVED:
            sql = bench.QUERIES[name]
            t0 = time.perf_counter()
            want = bench.CPU_QUERIES[name](oracle)
            oracle_s = round(time.perf_counter() - t0, 2)

            def run(what):
                t0 = time.perf_counter()
                res = client.execute(sql, timeout=1100.0)
                wall = time.perf_counter() - t0
                c = _counters(engine.last_query_counters)
                check_answer(name, res.to_pandas(), want, what)
                if not c["dispatches"]:
                    raise SmokeFailure(f"{name} {what}: no device dispatch — the "
                                       "answer did not come from the execute path")
                return {"s": round(wall, 4), "rows": len(res.rows), **c}

            cold = run("cold")
            # the adaptive advisor may re-plan ONCE from the cold run's actuals,
            # and the new plan compiles: that run is cold too, not warm
            replan = None
            warm = [run("warm1")]
            if warm[0]["compiles"]:
                replan, warm = warm[0], [run("warm1")]
            warm.append(run("warm2"))
            for w in warm:
                if w["compiles"]:
                    raise SmokeFailure(f"{name} warm run compiled {w['compiles']} "
                                       f"programs ({w['compile_s']}s)")
            _say(query=name, rows=cold["rows"], cold_s=cold["s"],
                 warm_s=[w["s"] for w in warm],
                 cold_compiles=cold["compiles"], cold_compile_s=cold["compile_s"],
                 replan_run=replan, warm_dispatches=warm[-1]["dispatches"],
                 warm_host_bytes=warm[-1]["host_bytes"], oracle="equal",
                 oracle_s=oracle_s)
    finally:
        srv.stop()

    pool = engine.buffer_pool.info()
    resident = pool["per_table"].get("tpch.lineitem", {})
    if not resident.get("entries"):
        raise SmokeFailure(f"buffer pool holds no lineitem pages: {pool['per_table']}")
    stats = jax.devices()[0].memory_stats()
    if on_tpu and not stats:
        raise SmokeFailure("memory_stats() on the device is empty")
    if on_tpu and stats["bytes_in_use"] < resident["bytes"]:
        raise SmokeFailure(f"device holds {stats['bytes_in_use']} bytes, fewer than "
                           f"the {resident['bytes']} of resident lineitem pages")
    _say(lineitem_pages_resident=resident["entries"],
         lineitem_resident_bytes=resident["bytes"],
         page_cache_budget_bytes=pool["budget_bytes"],
         peak_bytes_in_use=(stats or {}).get("peak_bytes_in_use"),
         bytes_limit=(stats or {}).get("bytes_limit"))


def mesh_phase(engine, oracle, on_tpu: bool) -> None:
    """q1/q3/q18 over worker_mesh() of every device (one process, SPMD), each
    against the pandas oracle and the one-chip answer; every device must hold
    bytes after the scans, and the per-worker shard stats are printed."""
    import bench
    import jax
    from trino_tpu.parallel.mesh import worker_mesh

    n = len(jax.devices())
    if n < 4:
        raise SmokeFailure(f"--mesh needs four devices, found {n}")
    mesh = worker_mesh(n)
    session = engine.create_session("tpch")
    for name in MESHED:
        sql = bench.QUERIES[name]
        want = bench.CPU_QUERIES[name](oracle)
        t0 = time.perf_counter()
        local = engine.execute_sql(sql, session).to_pandas()
        local_s = time.perf_counter() - t0
        check_answer(name, local, want, "one-chip")
        walls = []
        for what in ("mesh-cold", "mesh-warm"):
            t0 = time.perf_counter()
            dist = engine.execute_sql(sql, session, distributed=True,
                                      mesh=mesh).to_pandas()
            walls.append(round(time.perf_counter() - t0, 3))
            check_answer(name, dist, want, what)
            check_answer(name, dist, local, what + " vs one-chip")
        c = engine.last_query_counters
        _say(query=name, workers=n, one_chip_cold_s=round(local_s, 3),
             mesh_cold_s=walls[0], mesh_warm_s=walls[1], oracle="equal",
             one_chip="equal", **_counters(c))
        for rec in c.shard_stats:
            _say(query=name, shard_stats=rec)
        if on_tpu:  # per statement: a cut run still shows where the scan lived
            in_use = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in jax.devices()]
            _say(query=name, per_device_peak_bytes_in_use=in_use)
            if not all(in_use):
                raise SmokeFailure(f"{name}: a device never held a byte: {in_use}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mesh", action="store_true",
                    help="four chips: only the one-process mesh phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU backend at SF0.01 (the one way onto the CPU)")
    args = ap.parse_args(argv)
    if args.rehearse:
        # the CPU backend's page cache defaults to OFF; the smoke's residency
        # check needs it, as on the chip where it is a quarter of HBM
        os.environ.setdefault("TRINO_TPU_PAGE_CACHE", str(1 << 30))
        if args.mesh and "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                       + " --xla_force_host_platform_device_count=4").strip()

    import jax

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if on_tpu == args.rehearse:
        print(f"chip_smoke: backend is {dev.platform!r}; "
              + ("--rehearse is for the CPU backend" if on_tpu else
                 "a TPU is required (--rehearse runs the CPU rehearsal)"),
              file=sys.stderr)
        return 2
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}

    import bench
    from trino_tpu import Engine
    from trino_tpu.connectors.tpch import TpchConnector

    sf = 0.01 if args.rehearse else 1
    files_before = _cache_files()
    _say(phase="mesh" if args.mesh else "served", sf=sf, device=device,
         cache_dir=jax.config.jax_compilation_cache_dir,
         cache_files_before=files_before, **_kernel_facts())

    t0 = time.perf_counter()
    conn = TpchConnector(sf=sf, split_rows=1 << 21)
    engine = Engine()
    engine.register_catalog("tpch", conn)
    oracle = bench._HostTables(conn)
    if args.mesh:
        mesh_phase(engine, oracle, on_tpu)
    else:
        served_phase(engine, oracle, on_tpu)
    _say(total_s=round(time.perf_counter() - t0, 1),
         cache_files_written=_cache_files() - files_before,
         lineitem_rows=conn.row_count("lineitem"), orders_rows=conn.row_count("orders"))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
