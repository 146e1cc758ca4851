"""The cell ``sf10_mesh4_joins`` (configuration ``tpch_sf10_4chip``, traffic
``mesh_sf10_stream``), added in PR 46 as new files and appended entries, after
``test_mesh4_cell.py``: its entries load and pass the loader's name and unit checks, the
float32 control comes out as not correct at ``rehearse_sf``, it rehearses on four host
devices to a result line with ``correct`` true, no compile in the window and, traced,
every per-layer metric the cell lists that the CPU can read, and the three readers the PR
brought return None on a program without their counters (the driver lays these files over
the parent's checkout too)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark.harness import compare
from benchmark.harness.hosttables import HostTables
from benchmark.harness.loader import ROOT, Cell, _load_module, check_name, check_unit

CELL = "sf10_mesh4_joins"
NEW_METRICS = {"resident_batch_share.mesh": "%", "exchange_mb_per_stmt.mesh": "MB",
               "busy_imbalance.mesh": "%"}
# nothing to read in the CPU rehearsal, by design: the page cache is off there (no lookup
# is made), the stand-in trace has no device plane, and at SF0.01 both of q3's joins
# broadcast, so no probe exchange runs
NONE_ON_CPU = {"page_cache_hit_share.olap", "device_busy_s_per_stmt.olap",
               "busy_imbalance.mesh", "probe_recv_fill_share.mesh"}


def test_the_new_entries_load_and_pass_the_name_and_unit_checks():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = Cell(CELL)
    assert cell.chips == 4 and cell.config["name"] == "tpch_sf10_4chip"
    assert (cell.config["sf"], cell.config["rehearse_sf"]) == (10, 0.01)
    assert cell.config["split_rows"] == 1 << 21 and cell.config["chips"] == 4
    assert list(cell.statements) == ["q1", "q3"]
    traffic = dict(cell.traffic)
    assert traffic.pop("why") and traffic.pop("name") == "mesh_sf10_stream"
    assert traffic == {"loop": "closed", "clients": 1, "poll_interval": 0.002,
                       "slots": ["q1", "q3"],
                       "order": "seeded_rounds", "params": {"q1": "fixed", "q3": "fixed"},
                       "check": "all", "statement_timeout_s": 900, "trace_seconds": 15}
    assert {m["name"] for m in cell.end_to_end} == {"stmt_s.geomean", "setup_s"}
    # what the SF1 mesh cell reports, this one reports
    assert {m["name"] for m in cell.per_layer} \
        >= {m["name"] for m in Cell("sf1_mesh4_joins").per_layer} >= set(NEW_METRICS)
    config = next(c for c in bench["configs"] if c["name"] == "tpch_sf10_4chip")
    workload = next(w for w in bench["workloads"] if w["name"] == CELL)
    for text in (config["source"], config["why"], workload["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    for clause in ("4.1.3 scale factor 10", "2.4.1", "2.4.3", "5.3"):
        assert clause in config["source"]
    assert config["source"] == cell.config["source"]
    # two deployments from one public benchmark need sources that differ, and a file each
    assert len({c["source"] for c in bench["configs"]}) == len(bench["configs"])
    assert len({c["file"] for c in bench["configs"]}) == len(bench["configs"])
    for key in config["reduced"]:
        check_name(key, "reduced")
        assert key in cell.config["reduced"]
    assert cell.config["guarantees"] == Cell("sf1_joins").config["guarantees"]
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert CELL in four and 2 * len(four) <= len(bench["workloads"])
    for name, unit in NEW_METRICS.items():
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        check_name(entry["name"], "metric")
        assert check_unit(entry["unit"], entry["name"]) == unit
        assert entry["workloads"][:2] == ["sf1_mesh4_joins", CELL]
        assert entry["moves"] == "stmt_s.geomean"
        assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    rows_per_s = next(m for m in bench["end_to_end"] if m["name"] == "rows_per_s")
    assert CELL not in rows_per_s["workloads"]


def test_the_float32_control_is_not_correct_in_the_new_cell():
    """At ``rehearse_sf``, as ``test_mesh4_cell.py`` holds the SF1 cell: the lower precision
    has to fail one of the cell's numbers, not each."""
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
                          "3000000046", "--seconds", "3", "--trace", str(trace), "--rehearse"],
                         cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(line) for line in out.stdout.splitlines() if line.startswith("{")]
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2 and result["attempted"] % 2 == 0  # whole rounds
    assert result["device"] == dict(result["device"], platform="cpu", count=4)
    for name in ("q1", "q3"):
        warm = next(line for line in lines if line.get("setup") == name)
        assert warm["error"] is None and warm["compiles"][0] > 0 and warm["compiles"][-1] == 0
    facts = next(line for line in lines if "compared" in line)
    assert facts["window_compiles"] == 0 and facts["result_cache_hits"] == 0
    assert facts["device_dispatches"] > 0 and facts["setup_failed"] == 0
    assert facts["statements_compared"] == facts["statements_in_window"]  # check: all
    metrics = result["metrics"]
    if not trace:
        assert set(metrics) == {"stmt_s.geomean", "setup_s"}
        return
    assert set(metrics) == {m["name"] for m in Cell(CELL).per_layer} - NONE_ON_CPU
    for name in set(NEW_METRICS) - NONE_ON_CPU:
        assert metrics[name]["unit"] == NEW_METRICS[name]
    # the CPU's page cache is off: every batch of both scans is generated
    assert metrics["resident_batch_share.mesh"]["value"] == 0
    assert metrics["exchange_mb_per_stmt.mesh"]["value"] > 0
    assert metrics["mesh_fragment_hit_share.mesh"]["value"] == 100
    assert metrics["window_compiles.olap"]["value"] == 0


def test_the_new_readers_find_nothing_on_a_program_without_their_counters():
    class Ctx:
        counters = {"device_dispatches": 12, "exchange_rows": 40, "rows_generated": 7}
        window_s = 3.0
        trace = None
        device = {"platform": "tpu"}

        def completed(self, name=None):
            return [{"name": "q3", "seconds": 1.0}]

    reads = {name: _load_module(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"),
                                name).read for name in NEW_METRICS}
    for name, read in reads.items():
        assert read(Ctx()) is None, name
    # and on a program with them
    ctx = Ctx()
    ctx.counters = {"mesh_scan_batches_resident": 13, "mesh_scan_batches_generated": 13,
                    "exchange_bytes": 5_000_000}
    ctx.trace = {"busy_s_by_device": [1.2, 1.0, 1.0, 0.8]}
    assert reads["resident_batch_share.mesh"](ctx) == 50.0
    assert reads["exchange_mb_per_stmt.mesh"](ctx) == 5.0
    assert abs(reads["busy_imbalance.mesh"](ctx) - 20.0) < 1e-9
    ctx.device = {"platform": "cpu"}  # the rehearsal's stand-in trace is no device's
    assert reads["busy_imbalance.mesh"](ctx) is None
