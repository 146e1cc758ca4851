"""The cell ``sf1_mesh4_joins`` (configuration ``tpch_sf1_4chip``, traffic
``mesh_joins_stream``), added in PR 32 as new files and appended entries: its entries load
and pass the loader's name and unit checks, the float32 control comes out as not correct
at ``rehearse_sf``, and it rehearses on four host devices to a result line with ``correct``
true, no compile in the window and, traced, every per-layer metric the cell lists."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark.harness import compare
from benchmark.harness.hosttables import HostTables
from benchmark.harness.loader import ROOT, Cell, _load_module, check_name, check_unit

CELL = "sf1_mesh4_joins"
NEW_METRICS = {"exchange_wait_s_per_stmt.mesh": "s", "exchange_rows_per_stmt.mesh": "count",
               "shard_imbalance.mesh": "%", "mesh_fragment_hit_share.mesh": "%",
               "rows_per_s_per_chip.mesh": "rows/s", "q1_s.olap": "s"}
LISTED = {"plan_ms.olap", "window_compiles.olap", "compile_misses.olap",
          "dispatches_per_stmt.olap", "page_cache_hit_share.olap",
          "device_busy_s_per_stmt.olap", "q3_s.olap", "host_pull_s_per_stmt.olap",
          "dispatch_s_per_stmt.olap", "host_other_s_per_stmt.olap",
          "generated_rows_per_s.olap"} | set(NEW_METRICS)
# nothing to read on the CPU backend, by design: the page cache is off there (its
# budget is 0, so no lookup is made), and the stand-in trace has no device plane
NONE_ON_CPU = {"page_cache_hit_share.olap", "device_busy_s_per_stmt.olap"}


def test_the_new_entries_load_and_pass_the_name_and_unit_checks():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = Cell(CELL)
    assert cell.chips == 4 and cell.config["name"] == "tpch_sf1_4chip"
    assert (cell.config["sf"], cell.config["rehearse_sf"]) == (1, 0.01)
    assert cell.config["split_rows"] == 1 << 21 and cell.config["chips"] == 4
    assert list(cell.statements) == ["q1", "q3"]
    traffic = dict(cell.traffic)
    assert traffic.pop("why") and traffic.pop("name") == "mesh_joins_stream"
    # poll_interval: at the client's default 0.05 s q1 (0.107 s) is seen at the second or
    # the third poll, and stmt_s.geomean spread 18 % over runs (PERF.md, PR 32)
    assert traffic == {"loop": "closed", "clients": 1, "poll_interval": 0.002,
                       "slots": ["q1", "q3"],
                       "order": "seeded_rounds", "params": {"q1": "fixed", "q3": "fixed"},
                       "check": "all", "statement_timeout_s": 900, "trace_seconds": 5}
    assert {m["name"] for m in cell.end_to_end} == {"stmt_s.geomean", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == LISTED
    config = next(c for c in bench["configs"] if c["name"] == "tpch_sf1_4chip")
    workload = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert bench["configs"][-1] is config and bench["workloads"][-1] is workload
    for text in (config["source"], config["why"], workload["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    for clause in ("4.1.3", "2.4.1", "2.4.3", "5.3"):
        assert clause in config["source"]
    assert config["source"] == cell.config["source"]
    for key in config["reduced"]:
        check_name(key, "reduced")
        assert key in cell.config["reduced"]
    # the guarantees of the one-chip configuration, word for word
    one_chip = Cell("sf1_joins").config
    assert cell.config["guarantees"] == one_chip["guarantees"]
    assert len({c["file"] for c in bench["configs"]}) == len(bench["configs"])
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert four == [CELL]
    new = bench["per_layer"][-len(NEW_METRICS):]
    assert [m["name"] for m in new] == list(NEW_METRICS)
    for entry in new:
        check_name(entry["name"], "metric")
        assert check_unit(entry["unit"], entry["name"]) == NEW_METRICS[entry["name"]]
        assert entry["workloads"] == [CELL] and entry["moves"] == "stmt_s.geomean"
        assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    rows_per_s = next(m for m in bench["end_to_end"] if m["name"] == "rows_per_s")
    assert CELL not in rows_per_s["workloads"]


def test_the_float32_control_is_not_correct_in_the_new_cell():
    """At ``rehearse_sf``, as benchmark/tests/test_compare.py holds the older cells: the
    lower precision has to fail one of the cell's numbers."""
    from trino_tpu.connectors.tpch import TpchConnector

    cell = Cell(CELL)
    wanted = {}
    for st in cell.statements.values():
        for table, cols in st.TABLES.items():
            wanted.setdefault(table, []).extend(cols)
    tables = HostTables(TpchConnector(sf=cell.config["rehearse_sf"],
                                      split_rows=cell.config["split_rows"]), wanted)
    sound, control = [], []
    for st in cell.statements.values():
        want = st.reference(tables, st.VALIDATION)
        sound.append(compare.compare(want, want, getattr(st, "AVG_DECIMALS", None)))
        control.append(compare.compare(st.reference(tables, st.VALIDATION, dtype=np.float32),
                                       want, getattr(st, "AVG_DECIMALS", None)))
    assert compare.within_limits(compare.worst(sound))
    worst = compare.worst(control)
    assert not compare.within_limits(worst), control
    assert worst["max_rel_err"] > 3 * compare.LIMITS["max_rel_err"]
    assert worst["exact_mismatches"] == 0


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_on_four_host_devices(trace):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    out = subprocess.run([sys.executable, "-m", "benchmark.run", "--workload", CELL, "--seed",
                          "3000000037", "--seconds", "3", "--trace", str(trace), "--rehearse"],
                         cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(line) for line in out.stdout.splitlines() if line.startswith("{")]
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2 and result["attempted"] % 2 == 0  # whole rounds
    assert result["device"] == dict(result["device"], platform="cpu", count=4)
    for name in ("q1", "q3"):
        warm = next(line for line in lines if line.get("setup") == name)
        # the second execution of a text asks for no compile: set-up ends there
        assert warm["error"] is None and len(warm["compiles"]) == 2
        assert warm["compiles"][0] > 0 and warm["compiles"][1] == 0
    facts = next(line for line in lines if "compared" in line)
    assert facts["window_compiles"] == 0 and facts["result_cache_hits"] == 0
    assert facts["device_dispatches"] > 0 and facts["setup_failed"] == 0
    assert facts["statements_compared"] == facts["statements_in_window"]  # check: all
    metrics = result["metrics"]
    if not trace:
        assert set(metrics) == {"stmt_s.geomean", "setup_s"}
        return
    assert set(metrics) == LISTED - NONE_ON_CPU
    for name, unit in NEW_METRICS.items():
        assert metrics[name]["unit"] == unit
    assert metrics["mesh_fragment_hit_share.mesh"]["value"] == 100
    assert metrics["window_compiles.olap"]["value"] == 0
    assert metrics["exchange_rows_per_stmt.mesh"]["value"] > 0
    assert metrics["shard_imbalance.mesh"]["value"] >= 0
    assert metrics["exchange_wait_s_per_stmt.mesh"]["value"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_the_new_readers_find_nothing_on_a_program_without_their_counters():
    """The driver lays these files over the parent's checkout too: there the readers of
    the program's new counters return None and the line leaves the metric out."""

    class Ctx:
        counters = {"device_dispatches": 12, "wall_host_pull_s": 1.0}
        window_s = 3.0

        class cell:
            chips = 4

        def completed(self, name=None):
            return [{"name": "q3", "seconds": 1.0}]

    for name in ("exchange_wait_s_per_stmt.mesh", "exchange_rows_per_stmt.mesh",
                 "shard_imbalance.mesh", "mesh_fragment_hit_share.mesh"):
        read = _load_module(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"),
                            name).read
        assert read(Ctx()) is None, name
