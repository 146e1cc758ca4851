"""SF100 north-star run on the CPU backend (round-4 verdict item 2).

Engine-only (no pandas baseline: a 600M-row lineitem frame is buildable in
125GB RAM but the point here is exercising the ENGINE's Grace/spill tier at
real size — BASELINE ladder step 3). Runs Q1/Q3/Q18/Q9 at BENCH_SF (default
100) one at a time and rewrites SF100_cpu_r05.json after EVERY query so a
partial run still leaves an artifact with failure analysis.

Run: nice -n 19 python scripts/sf100_run.py  (hours are expected on 1 core).
"""

import json
import os
import pathlib
import time
import traceback

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import sys  # noqa: E402

REPO = str(pathlib.Path(__file__).resolve().parents[1])
sys.path.insert(0, REPO)

from bench import QUERIES  # noqa: E402  (single source of query text)
from trino_tpu import Engine  # noqa: E402
from trino_tpu.connectors.tpch import TpchConnector  # noqa: E402

SF = float(os.environ.get("BENCH_SF", "100"))
# SF100_QUERIES=q18,q9 resumes a partial run without repeating finished ones
ORDER = [q.strip() for q in os.environ.get(
    "SF100_QUERIES", "q1,q3,q18,q9").split(",") if q.strip() in QUERIES]
OUT = os.path.join(REPO, f"SF100_cpu_r05.json")

out = {
    "sf": SF,
    "backend": "cpu-1core",
    "started_at": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
    "queries": {},
}


def _flush():
    with open(OUT, "w") as f:
        json.dump(out, f, indent=1)


_flush()
e = Engine()
e.register_catalog("tpch", TpchConnector(sf=SF))
for q in ORDER:
    rec = {"status": "running", "t0": time.strftime("%H:%M:%S")}
    out["queries"][q] = rec
    _flush()
    t0 = time.time()
    try:
        r = e.execute_sql(QUERIES[q])
        rows = r.rows()
        rec["status"] = "ok"
        rec["n_rows"] = len(rows)
        rec["first_row"] = repr(rows[0]) if rows else None
    except BaseException as exc:  # noqa: BLE001 — artifact must record failures
        rec["status"] = "failed"
        rec["error"] = f"{type(exc).__name__}: {exc}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        if isinstance(exc, KeyboardInterrupt):
            rec["wall_seconds"] = round(time.time() - t0, 1)
            _flush()
            raise
    rec["wall_seconds"] = round(time.time() - t0, 1)
    _flush()
    print(json.dumps({q: rec})[:500], flush=True)
out["finished_at"] = time.strftime("%Y-%m-%dT%H:%M:%S%z")
_flush()
