"""The cell ``ds10_hash_joins`` (configuration ``tpcds_sf10_1chip``, traffic
``ds_hash_stream``), added in PR 36 as new files and appended entries: its entries load
and pass the loader's name and unit checks, it has ONE statement class (a process of this harness ends
only when the statement it has in flight ends, and the tree the cell arrived on needs over
1,130 s for q65's first run: PERF.md section 6, PR 36), the float32 control comes out as not correct at
``rehearse_sf``, a rehearsal ends ``correct`` with every per-layer metric the cell lists,
and the new readers find nothing on a program without their counters."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark.harness import compare
from benchmark.harness.hosttables import HostTables
from benchmark.harness.loader import ROOT, Cell, _load_module, check_name, check_unit

CELL = "ds10_hash_joins"
NEW_METRICS = {"ds_q93_s.olap": "s", "hash_join_lane_share.olap": "%",
               "hash_probe_lanes_per_stmt.olap": "count",
               "groupby_insert_lanes_per_stmt.olap": "count"}
COUNTER_METRICS = set(NEW_METRICS) - {"ds_q93_s.olap"}
LISTED = {"plan_ms.olap", "window_compiles.olap", "compile_misses.olap",
          "dispatches_per_stmt.olap", "page_cache_hit_share.olap",
          "build_cache_lookups_per_stmt.olap", "device_busy_s_per_stmt.olap",
          "host_pull_s_per_stmt.olap", "dispatch_s_per_stmt.olap",
          "host_other_s_per_stmt.olap", "groupby_regrows_per_stmt.olap",
          "groupby_state_mb.olap", "spilled_mb_per_stmt.olap", "generated_rows_per_s.olap",
          "join_build_rows_per_stmt.olap", "join_gather_lane_share.olap"} | set(NEW_METRICS)
# nothing to read on the CPU backend, by design: the page cache is off there (its
# budget is 0, so no lookup is made), and the stand-in trace has no device plane
NONE_ON_CPU = {"page_cache_hit_share.olap", "device_busy_s_per_stmt.olap"}


def _benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_new_entries_load_and_pass_the_name_and_unit_checks():
    bench = _benchmark_json()
    cell = Cell(CELL)
    assert cell.chips == 1 and cell.config["name"] == "tpcds_sf10_1chip"
    assert cell.config["connector"] == "tpcds" and cell.config["catalog"] == "tpcds"
    assert cell.config["sf"] == 10 and cell.config["split_rows"] == 1 << 21
    assert cell.config["rehearse_sf"] == 0.01
    traffic = dict(cell.traffic)
    assert traffic.pop("why") and traffic.pop("name") == "ds_hash_stream"
    assert traffic == {"loop": "closed", "clients": 1, "slots": ["ds_q93"],
                       "order": "seeded_rounds", "params": {"ds_q93": "fixed"}, "check": "all",
                       "statement_timeout_s": 360, "poll_interval": 0.05, "trace_seconds": 5}
    assert {m["name"] for m in cell.end_to_end} == {"stmt_s.geomean", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == LISTED
    config = next(c for c in bench["configs"] if c["name"] == "tpcds_sf10_1chip")
    workload = next(w for w in bench["workloads"] if w["name"] == CELL)
    for text in (config["source"], config["why"], workload["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    for word in ("TPC-DS", "scale 10", "query93.tpl"):
        assert word in config["source"]
    # one statement class, and the texts claim no more than it runs
    for text in (config["source"], config["why"], workload["why"]):
        assert "65" not in text and "group-by" not in text
    assert config["source"] == cell.config["source"]
    assert set(config["reduced"]) == set(cell.config["reduced"])
    for key in config["reduced"]:
        check_name(key, "reduced")
    assert "PALLAS_TABLE_MAX" in cell.config["hash_tables"]
    assert len({c["file"] for c in bench["configs"]}) == len(bench["configs"])
    assert [w["chips"] for w in bench["workloads"]].count(4) == 1 and len(bench["workloads"]) == 6
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


def test_the_cell_has_one_statement_class():
    """A set-up that times out leaves its statement running, and the process ends when
    that statement does: with one class a parent's run is bounded by that class's own
    first run (about 580 s for q93 on the tree the cell arrived on)."""
    cell = Cell(CELL)
    assert list(cell.statements) == ["ds_q93"]
    assert cell.traffic["statement_timeout_s"] == 360
    # the next cell's statement waits beside its tier-1 test, and is no part of this one
    assert not os.path.exists(os.path.join(cell.bench_dir, "statements", "ds_q65.py"))
    assert os.path.isfile(os.path.join(ROOT, "tests", "ds_q65.py"))


def test_the_float32_control_is_not_correct_in_the_new_cell():
    """At ``rehearse_sf``: the lower precision has to fail one of the cell's numbers."""
    from trino_tpu.connectors.tpcds import TpcdsConnector

    cell = Cell(CELL)
    wanted = {}
    for st in cell.statements.values():
        for table, cols in st.TABLES.items():
            wanted.setdefault(table, []).extend(cols)
    tables = HostTables(TpcdsConnector(sf=cell.config["rehearse_sf"],
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
    lines = [json.loads(line) for line in out.stdout.splitlines() if line.startswith("{")]
    assert [l["setup"] for l in lines if "setup" in l] == ["ds_q93"]
    result = lines[-1]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert result["device"]["platform"] == "cpu"
    facts = [l for l in lines if "compared" in l][-1]
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
    # q93's table over store_returns is hashed and its join is split (the boundary packs
    # the tenth that matched); the table over reason is direct and probed at that width
    assert 50 < metrics["hash_join_lane_share.olap"]["value"] < 100
    assert 0 < metrics["join_gather_lane_share.olap"]["value"] <= 25
    assert metrics["hash_probe_lanes_per_stmt.olap"]["value"] > 0
    assert metrics["groupby_insert_lanes_per_stmt.olap"]["value"] > 0
    assert metrics["join_build_rows_per_stmt.olap"]["value"] == 0
    assert metrics["generated_rows_per_s.olap"]["value"] > 0


def test_the_new_readers_find_nothing_on_a_program_without_their_counters():
    """The driver lays these files over the parent's checkout too: there the readers
    return None and the line leaves the metric out."""

    class Ctx:
        counters = {"device_dispatches": 12, "join_match_lanes": 5, "groupby_slots": 7}
        window_s = 3.0

        def completed(self, name=None):
            return [{"name": "q3", "seconds": 1.0}] if name in (None, "q3") else []

    for name in NEW_METRICS:
        read = _load_module(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"),
                            name).read
        assert read(Ctx()) is None, name
    assert COUNTER_METRICS == {n for n in NEW_METRICS if "lane" in n}


@pytest.mark.parametrize("name, where", [("ds_q65", "tests"),
                                         ("ds_q93", os.path.join("benchmark", "statements"))])
def test_the_tpcds_statements_draw_inside_their_templates_ranges(name, where):
    """What ``test_statements.py`` holds the TPC-H statements to; its table of ranges is a
    file that is there and has no entry for ``ds_q93`` (two of its cases are red for it:
    the next ``benchmark`` issue's)."""
    import random

    ranges = {"ds_q65": lambda p: p["dms"] in (1176, 1188, 1200, 1212) and p["factor"] == "0.1",
              "ds_q93": lambda p: p["reason"] in {"reason %d" % i for i in range(1, 36)}}
    st = _load_module(os.path.join(ROOT, where, name + ".py"), name)
    draws = [st.params(random.Random(3_000_000_123), {"sf": 10}) for _ in range(2)]
    assert draws[0] == draws[1]
    rng = random.Random(5)
    seen = [st.params(rng, {"sf": 10}) for _ in range(200)]
    assert all(ranges[name](p) for p in seen) and ranges[name](st.VALIDATION)
    assert len({tuple(sorted(p.items())) for p in seen}) > 1
    sql, bound = st.render(seen[0])
    assert sql.lstrip().lower().startswith("select") and bound is None and "{" not in sql
