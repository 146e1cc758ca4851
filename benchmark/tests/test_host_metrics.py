"""The six per-layer metrics of PR 38, which read what the executor's host does between
its dispatches and pulls: each entry's fields and ``workloads``, each reader on a synthetic
context (value; None on a program without the counter, as the parent is, since the driver
lays these files over its checkout too; None with no completed statement), and a traced
rehearsal of ``sf10_scan`` that lists all it should."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmark.harness.loader import ROOT, Cell, check_name, check_unit

OLAP = ["sf1_joins", "sf10_scan", "sf10_joins", "sf1_mesh4_joins", "ds10_hash_joins"]
SCANS = ["sf10_scan", "sf10_joins", "ds10_hash_joins"]  # the cells whose scans regenerate
SERVE = ["sf1_dashboard"]

# name -> (unit, source, moves, workloads, the counter it reads, expected over 4 statements)
METRICS = {
    "host_unnamed_s_per_stmt.olap": ("s", "program_span", "stmt_s.geomean", OLAP,
                                     "wall_unattributed_s", 0.5),
    "host_unnamed_ms.serve": ("ms", "program_span", "stmts_per_s", SERVE,
                              "wall_unattributed_s", 500.0),
    "scan_wait_s_per_stmt.olap": ("s", "program_span", "stmt_s.geomean", SCANS,
                                  "wall_scan_wait_s", 0.25),
    "generator_dispatches_per_stmt.olap": ("count", "program_counter", "stmt_s.geomean", SCANS,
                                           "generator_dispatches", 51.0),
    "host_cpu_s_per_stmt.olap": ("s", "program_counter", "stmt_s.geomean", OLAP,
                                 "host_cpu_s", 0.125),
    "host_cpu_ms.serve": ("ms", "program_counter", "stmts_per_s", SERVE,
                          "host_cpu_s", 125.0),
}
COUNTERS = {"wall_unattributed_s": 2.0, "wall_scan_wait_s": 1.0, "generator_dispatches": 204,
            "host_cpu_s": 0.5, "device_dispatches": 64}


def ctx_of(counters, statements):
    records = [{"name": "q1", "error": None} for _ in range(statements)]
    return types.SimpleNamespace(counters=counters, completed=lambda name=None: records)


def entries():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return {m["name"]: m for m in json.load(f)["per_layer"]}


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_entry_names_its_layer_its_source_and_its_cells(name):
    unit, source, moves, workloads, _, _ = METRICS[name]
    assert entries()[name] == {"name": name, "unit": unit, "better": "lower", "source": source,
                               "layer": "executor", "moves": moves, "workloads": workloads}
    check_name(name, "metric")
    assert check_unit(unit, name) == unit
    for cell in OLAP + SERVE:
        listed = name in {m["name"] for m in Cell(cell).per_layer}
        assert listed == (cell in workloads), (cell, name)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_reader_value_no_counter_no_statement(name):
    _, _, _, workloads, counter, expected = METRICS[name]
    read = next(m["read"] for m in Cell(workloads[0]).per_layer if m["name"] == name)
    assert read(ctx_of(dict(COUNTERS), 4)) == pytest.approx(expected)
    without = {k: v for k, v in COUNTERS.items() if k != counter}
    assert read(ctx_of(without, 4)) is None    # the parent's program: left out of the line
    assert read(ctx_of(dict(COUNTERS), 0)) is None


def test_the_six_are_appended_and_nothing_before_them_moved():
    names = [m["name"] for m in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["per_layer"]]
    assert names[-6:] == ["host_unnamed_s_per_stmt.olap", "host_unnamed_ms.serve",
                          "scan_wait_s_per_stmt.olap", "generator_dispatches_per_stmt.olap",
                          "host_cpu_s_per_stmt.olap", "host_cpu_ms.serve"]
    assert names[-7] == "hash_probe_round_lanes_per_stmt.olap"  # PR 37's, the last before


def test_a_traced_rehearsal_of_sf10_scan_lists_all_it_should():
    """On the CPU at ``rehearse_sf``: structure, never a device number.  The four buckets
    that the cell reports still sum to the root span's seconds a statement."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    out = subprocess.run([sys.executable, "-m", "benchmark.run", "--workload", "sf10_scan",
                          "--seed", "3000000038", "--seconds", "3", "--trace", "1",
                          "--rehearse"],
                         cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    result = [json.loads(line) for line in out.stdout.splitlines() if line.startswith("{")][-1]
    assert result["correct"] is True and result["failed"] == 0
    metrics = result["metrics"]
    listed = {m["name"] for m in Cell("sf10_scan").per_layer}
    mine = {n for n, spec in METRICS.items() if "sf10_scan" in spec[3]}
    assert mine <= listed and mine <= set(metrics), sorted(mine - set(metrics))
    for name in mine:
        assert metrics[name]["unit"] == METRICS[name][0]
    assert not {n for n in METRICS if n.endswith(".serve")} & set(metrics)
    # every split of the one scan is a launch: the cell's page cache admits nothing of it
    assert metrics["generator_dispatches_per_stmt.olap"]["value"] >= 1
    assert metrics["generator_dispatches_per_stmt.olap"]["value"] \
        == pytest.approx(round(metrics["generator_dispatches_per_stmt.olap"]["value"]))
    assert metrics["host_cpu_s_per_stmt.olap"]["value"] > 0
    assert metrics["scan_wait_s_per_stmt.olap"]["value"] >= 0
    # the remainder is a part of host_other, which holds split generation and staging too
    assert metrics["host_unnamed_s_per_stmt.olap"]["value"] \
        <= metrics["host_other_s_per_stmt.olap"]["value"] + 1e-9
