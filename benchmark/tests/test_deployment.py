"""The deployment is built from the cell's configuration file: another connector under
another catalog, and a mesh of the cell's chips, arrive as new files in a temporary
root with its own BENCHMARK.json, and ``--rehearse`` of them runs on the CPU backend."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import run
from benchmark.harness.loader import ROOT, BenchmarkError, Cell

STORE_SALES = '''"""Net paid by quantity sold, over TPC-DS store_sales."""
import numpy as np
import pandas as pd

TABLES = {"store_sales": ["ss_quantity", "ss_net_paid"]}
VALIDATION = {}
SQL = ("select ss_quantity, count(*) as c, sum(ss_net_paid) as paid from store_sales "
       "group by ss_quantity order by ss_quantity")


def params(rng, config):
    return {}


def render(p):
    return SQL, None


def reference(T, p, dtype=np.float64):
    c = T.columns("store_sales")
    paid = np.bincount(c["ss_quantity"], weights=c["ss_net_paid"].astype(dtype) / dtype(100))
    n = np.bincount(c["ss_quantity"])
    q = np.nonzero(n)[0]
    return pd.DataFrame({"ss_quantity": q, "c": n[q], "paid": paid[q].astype(dtype)})
'''


def add_cell(root, edit, name, config, traffic, statements=()):
    """One configuration, one traffic mix and one cell as new files and entries."""
    b = root / "benchmark"
    for stem, text in statements:
        (b / "statements" / f"{stem}.py").write_text(text)
    (b / "configs" / f"{config['name']}.json").write_text(json.dumps(config))
    (b / "traffic" / f"{name}_mix.json").write_text(json.dumps(traffic))

    def add(bench):
        bench["configs"].append({"name": config["name"], "source": config["source"],
                                 "file": f"benchmark/configs/{config['name']}.json",
                                 "reduced": [], "why": "one more deployment"})
        bench["workloads"].append({"name": name, "config": config["name"],
                                   "traffic": f"{name}_mix", "chips": config["chips"],
                                   "why": "one more cell"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if m["name"] in ("stmt_s.geomean", "dispatches_per_stmt.olap", "plan_ms.olap"):
                m["workloads"].append(name)

    edit(root, add)


def rehearse(root, cell):
    """The command from the temporary root, as the driver runs it from a checkout; the
    program is found on PYTHONPATH, the benchmark in the working directory."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    return subprocess.run([sys.executable, "-m", "benchmark.run", "--workload", cell, "--seed",
                           "3000000031", "--seconds", "2", "--trace", "0", "--rehearse"],
                          cwd=root, env=env, capture_output=True, text=True, timeout=900)


def facts(out):
    return [json.loads(line) for line in out.stdout.splitlines() if line.startswith("{")]


def test_a_tpcds_deployment_arrives_as_new_files_and_rehearses_correct(copy, edit):
    add_cell(copy, edit, "ds_store_sales",
             {"name": "tpcds_sf1_1chip", "source": "TPC-DS Standard Specification, scale factor 1",
              "connector": "tpcds", "catalog": "tpcds", "sf": 1, "rehearse_sf": 0.01,
              "split_rows": 1 << 20, "chips": 1},
             {"loop": "closed", "clients": 1, "slots": ["store_sales_by_quantity"],
              "params": {"store_sales_by_quantity": "fixed"}, "check": "all",
              "trace_seconds": 2, "why": "one statement of a TPC-DS table"},
             statements=[("store_sales_by_quantity", STORE_SALES)])
    out = rehearse(copy, "ds_store_sales")
    assert out.returncode == 0, out.stderr[-2000:]
    lines = facts(out)
    assert (lines[0]["connector"], lines[0]["catalog"], lines[0]["sf"]) == ("tpcds", "tpcds", 0.01)
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"stmt_s.geomean", "setup_s"}
    compared = next(line for line in lines if "compared" in line)
    assert compared["device_dispatches"] > 0 and compared["setup_failed"] == 0
    assert compared["compared"]["exact_mismatches"] == {"value": 0, "limit": 0}


def test_a_four_chip_cell_rehearses_on_a_mesh_of_four_host_devices(copy, edit):
    """Every statement of the cell runs on ``worker_mesh(4)``.  The program's mesh path
    makes a new executor for every execution and so asks for its compiles again on a
    replay: set-up refuses the cell, as it has to (PERF.md section 7).  When the program
    keeps its executor, this cell rehearses to a result line, and the test follows."""
    config = json.loads((copy / "benchmark" / "configs" / "tpch_sf1_1chip.json").read_text())
    config.update(name="tpch_sf1_4chip", chips=4)
    add_cell(copy, edit, "sf1_mesh4_q1", config,
             {"loop": "closed", "clients": 1, "slots": ["q1"], "params": {"q1": "fixed"},
              "check": "all", "trace_seconds": 2, "statement_timeout_s": 900, "why": "q1"})
    out = rehearse(copy, "sf1_mesh4_q1")
    lines = facts(out)
    assert lines[0]["device"] == {"platform": "cpu", "kind": "cpu", "count": 4}
    warm = next(line for line in lines if line.get("setup") == "q1")
    assert warm["error"] is None and len(warm["seconds"]) == run.WARM_RUNS_MAX
    assert all(n > 0 for n in warm["compiles"])
    assert out.returncode == 3 and "correct" not in lines[-1]
    assert "set-up of q1 did not reach a run without compiles in 4" in out.stderr


def test_statements_of_a_mesh_engine_run_on_the_mesh_executor():
    """The four host devices of this process are asked for in a child: the engine that
    the harness builds for ``chips: 4`` answers q1 as ``Engine()`` does, over HTTP, and
    the statement's counters carry the mesh executor's shard records."""
    code = '''
import json
from benchmark.run import engine_on_mesh
from benchmark.statements import q1
from trino_tpu import Engine
from trino_tpu.connectors.tpch import TpchConnector
from trino_tpu.server.client import Client
from trino_tpu.server.server import CoordinatorServer

conn, answers, shards = TpchConnector(sf=0.01, split_rows=1 << 21), [], []
for engine in (Engine(), engine_on_mesh(4)):
    engine.register_catalog("tpch", conn)
    server = CoordinatorServer(engine, port=0)
    server.start()
    try:
        answers.append(Client(server.url, catalog="tpch").execute(q1.render(q1.VALIDATION)[0]).rows)
    finally:
        server.stop()
    shards.append(len(engine.last_query_counters.shard_stats))
print(json.dumps({"equal": answers[0] == answers[1], "rows": len(answers[0]), "shards": shards,
                  "mesh": engine.mesh.devices.size}))
'''
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    seen = json.loads(out.stdout.strip().splitlines()[-1])
    assert seen["equal"] and seen["rows"] == 4 and seen["mesh"] == 4
    assert seen["shards"][0] == 0 and seen["shards"][1] > 0


def test_an_unknown_connector_is_refused_with_the_names_there_are(copy):
    path = copy / "benchmark" / "configs" / "tpch_sf1_1chip.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()), connector="hive")))
    with pytest.raises(BenchmarkError, match=r"'hive': one of \['tpcds', 'tpch'\]"):
        run.connector_class(Cell("sf1_joins", root=str(copy)).config)
    out = rehearse(copy, "sf1_joins")
    assert out.returncode == 2 and out.stdout.strip() == "" and "'hive'" in out.stderr
