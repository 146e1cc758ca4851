"""The cell ``sf10_joins`` (configuration ``tpch_sf10_joins_1chip``, traffic
``joins_sf10_stream``), added in PR 27 as new files and appended entries: its entries
load and pass the loader's name and unit checks, the float32 control comes out as not
correct at ``rehearse_sf``, and a traced rehearsal prints every per-layer metric the
cell lists, the five new ones among them."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark.harness import compare
from benchmark.harness.hosttables import HostTables
from benchmark.harness.loader import ROOT, Cell, check_name, check_unit

CELL = "sf10_joins"
NEW_METRICS = {"groupby_regrows_per_stmt.olap": "count", "groupby_state_mb.olap": "MB",
               "spilled_mb_per_stmt.olap": "MB", "generated_rows_per_s.olap": "rows/s",
               "join_build_rows_per_stmt.olap": "count"}
LISTED = {"plan_ms.olap", "window_compiles.olap", "compile_misses.olap",
          "dispatches_per_stmt.olap", "page_cache_hit_share.olap",
          "build_cache_lookups_per_stmt.olap", "device_busy_s_per_stmt.olap", "q3_s.olap",
          "q18_s.olap", "host_pull_s_per_stmt.olap", "dispatch_s_per_stmt.olap",
          "host_other_s_per_stmt.olap", "join_gather_lane_share.olap"} | set(NEW_METRICS)
# nothing to read on the CPU backend, by design: the page cache is off there (its
# budget is 0, so no lookup is made), and the stand-in trace has no device plane
NONE_ON_CPU = {"page_cache_hit_share.olap", "device_busy_s_per_stmt.olap"}


def _benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_new_entries_load_and_pass_the_name_and_unit_checks():
    bench = _benchmark_json()
    cell = Cell(CELL)
    assert cell.chips == 1 and cell.config["name"] == "tpch_sf10_joins_1chip"
    assert cell.config["sf"] == 10 and cell.config["split_rows"] == 1 << 21
    assert cell.config["rehearse_sf"] == 0.01
    assert list(cell.statements) == ["q3", "q18"]
    traffic = dict(cell.traffic)
    assert traffic.pop("why") and traffic.pop("name") == "joins_sf10_stream"
    assert traffic == {"loop": "closed", "clients": 1, "slots": ["q3", "q18"],
                       "order": "seeded_rounds", "params": {"q3": "fixed", "q18": "fixed"},
                       "check": "all", "statement_timeout_s": 300, "trace_seconds": 5}
    assert {m["name"] for m in cell.end_to_end} == {"stmt_s.geomean", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == LISTED
    config = next(c for c in bench["configs"] if c["name"] == "tpch_sf10_joins_1chip")
    workload = next(w for w in bench["workloads"] if w["name"] == CELL)
    for text in (config["source"], config["why"], workload["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    for clause in ("4.1.3", "2.4.3", "2.4.18", "5.3"):
        assert clause in config["source"]
    assert config["source"] == cell.config["source"]
    for key in config["reduced"]:
        check_name(key, "reduced")
        assert key in cell.config["reduced"]
    assert len({c["file"] for c in bench["configs"]}) == len(bench["configs"])
    for entry in bench["per_layer"]:
        if entry["name"] in NEW_METRICS:
            check_name(entry["name"], "metric")
            assert check_unit(entry["unit"], entry["name"]) == NEW_METRICS[entry["name"]]
            assert entry["workloads"] == [CELL] and entry["moves"] == "stmt_s.geomean"
            assert set(entry) == {"name", "unit", "better", "source", "layer", "moves",
                                  "workloads"}
    # its reference is the benchmark's own: the statements import nothing of the program
    for name in cell.statements:
        with open(os.path.join(cell.bench_dir, "statements", name + ".py")) as f:
            assert "trino_tpu" not in f.read()


def test_the_float32_control_is_not_correct_in_the_new_cell():
    """At ``rehearse_sf``, as benchmark/tests/test_compare.py holds the older cells:
    the lower precision has to fail one of the cell's numbers, not each statement
    (q18's sums of quantities are small enough for float32)."""
    from trino_tpu.connectors.tpch import TpchConnector

    cell = Cell(CELL)
    wanted = {}
    for st in cell.statements.values():
        for table, cols in st.TABLES.items():
            wanted.setdefault(table, []).extend(cols)
    tables = HostTables(TpchConnector(sf=cell.config["rehearse_sf"],
                                      split_rows=cell.config["split_rows"]), wanted)
    sound, control = [], []
    for name, st in cell.statements.items():
        want = st.reference(tables, st.VALIDATION)
        sound.append(compare.compare(want, want))
        control.append(compare.compare(st.reference(tables, st.VALIDATION, dtype=np.float32),
                                       want))
    assert compare.within_limits(compare.worst(sound))
    worst = compare.worst(control)
    assert not compare.within_limits(worst), control
    assert worst["max_rel_err"] > 3 * compare.LIMITS["max_rel_err"]
    assert worst["exact_mismatches"] == 0


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_new_cell(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    out = subprocess.run([sys.executable, "-m", "benchmark.run", "--workload", CELL, "--seed",
                          "3000000029", "--seconds", "3", "--trace", str(trace), "--rehearse"],
                         cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2 and result["attempted"] % 2 == 0  # whole rounds
    assert result["device"]["platform"] == "cpu"
    facts = [json.loads(line) for line in out.stdout.splitlines() if '"compared"' in line][-1]
    assert facts["window_compiles"] == 0 and facts["result_cache_hits"] == 0
    assert facts["device_dispatches"] > 0
    assert facts["statements_compared"] == facts["statements_in_window"]  # check: all
    metrics = result["metrics"]
    if not trace:
        assert set(metrics) == {"stmt_s.geomean", "setup_s"}
        return
    assert set(metrics) == LISTED - NONE_ON_CPU
    for name, unit in NEW_METRICS.items():
        assert metrics[name]["unit"] == unit
    # a replayed text builds nothing, regrows nothing and keeps its states in memory,
    # and lineitem, over the page cache's cap, is generated again by every statement
    assert metrics["join_build_rows_per_stmt.olap"]["value"] == 0
    assert metrics["groupby_regrows_per_stmt.olap"]["value"] == 0
    assert metrics["spilled_mb_per_stmt.olap"]["value"] == 0
    assert metrics["groupby_state_mb.olap"]["value"] > 0
    assert metrics["generated_rows_per_s.olap"]["value"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_the_new_readers_find_nothing_on_a_program_without_their_counters():
    """The driver lays these files over the parent's checkout too: there the readers
    return None and the line leaves the metric out."""
    from benchmark.harness.loader import _load_module

    class Ctx:
        counters = {"device_dispatches": 12, "wall_host_pull_s": 1.0}
        window_s = 3.0

        def completed(self, name=None):
            return [{"name": "q3"}]

    for name in NEW_METRICS:
        if name == "spilled_mb_per_stmt.olap":
            continue  # spilled_bytes is older than this PR
        read = _load_module(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"),
                            name).read
        assert read(Ctx()) is None, name
