"""The cell ``ds10_hash_groupby`` (configuration ``tpcds_sf10_q65_1chip``, traffic
``ds_groupby_stream``), added in PR 40 as new files and appended entries: its entries load
and pass the loader's name and unit checks, it has ONE statement class (``ds_q65``, whose
text departs from query65.tpl in the completed ORDER BY alone), its ``TABLES`` name every
column its reference reads, the float32 control comes out as not correct where the answer
has rows, a rehearsal ends ``correct`` and prints both new metrics, and the new counter's
reader finds nothing on a program without the counter."""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from benchmark.harness import compare
from benchmark.harness.hosttables import HostTables
from benchmark.harness.loader import ROOT, Cell, _load_module, check_name, check_unit

CELL = "ds10_hash_groupby"
NEW_METRICS = {"ds_q65_s.olap": ("s", "host_clock"),
               "groupby_insert_round_lanes_per_stmt.olap": ("count", "program_counter")}
LISTED = {"plan_ms.olap", "window_compiles.olap", "dispatches_per_stmt.olap",
          "page_cache_hit_share.olap", "build_cache_lookups_per_stmt.olap",
          "device_busy_s_per_stmt.olap", "host_pull_s_per_stmt.olap",
          "dispatch_s_per_stmt.olap", "host_other_s_per_stmt.olap",
          "host_unnamed_s_per_stmt.olap", "host_cpu_s_per_stmt.olap", "compile_misses.olap",
          "groupby_regrows_per_stmt.olap", "groupby_state_mb.olap", "spilled_mb_per_stmt.olap",
          "generated_rows_per_s.olap", "generator_dispatches_per_stmt.olap",
          "scan_wait_s_per_stmt.olap", "join_build_rows_per_stmt.olap",
          "join_gather_lane_share.olap", "groupby_insert_lanes_per_stmt.olap"} | set(NEW_METRICS)
# nothing to read on the CPU backend, by design: the page cache is off there (its
# budget is 0, so no lookup is made), and the stand-in trace has no device plane
NONE_ON_CPU = {"page_cache_hit_share.olap", "device_busy_s_per_stmt.olap"}


def _benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _statement():
    return _load_module(os.path.join(ROOT, "benchmark", "statements", "ds_q65.py"), "ds_q65")


def test_the_new_entries_load_and_pass_the_name_and_unit_checks():
    bench = _benchmark_json()
    cell = Cell(CELL)
    sibling = Cell("ds10_hash_joins")
    assert cell.chips == 1 and cell.config["name"] == "tpcds_sf10_q65_1chip"
    for key in ("connector", "catalog", "sf", "rehearse_sf", "split_rows", "chips"):
        assert cell.config[key] == sibling.config[key], key  # the six harness keys
    traffic = dict(cell.traffic)
    assert traffic.pop("why") and traffic.pop("name") == "ds_groupby_stream"
    timeout = traffic.pop("statement_timeout_s")
    assert 360 <= timeout <= 900
    assert traffic == {"loop": "closed", "clients": 1, "slots": ["ds_q65"],
                       "order": "seeded_rounds", "params": {"ds_q65": "fixed"}, "check": "all",
                       "poll_interval": 0.05, "trace_seconds": 5}
    assert list(cell.statements) == ["ds_q65"]  # ONE class: the parent's run has to end
    assert {m["name"] for m in cell.end_to_end} == {"stmt_s.geomean", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == LISTED
    config = next(c for c in bench["configs"] if c["name"] == "tpcds_sf10_q65_1chip")
    workload = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert workload == dict(workload, config="tpcds_sf10_q65_1chip",
                            traffic="ds_groupby_stream", chips=1)
    for text in (config["source"], config["why"], workload["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    for word in ("TPC-DS", "scale 10", "query65.tpl", "DMS 1176"):
        assert word in config["source"]
    assert config["source"] == cell.config["source"]
    assert config["source"] != next(c for c in bench["configs"]
                                    if c["name"] == "tpcds_sf10_1chip")["source"]
    assert set(config["reduced"]) == set(cell.config["reduced"])
    for key in config["reduced"]:
        check_name(key, "reduced")
    assert "order_by" in cell.config["assumed"] and "PALLAS_TABLE_MAX" in cell.config["hash_tables"]
    assert any("group-by" in g and "result cache" in g for g in cell.config["guarantees"])
    assert len({c["file"] for c in bench["configs"]}) == len(bench["configs"])
    assert [w["chips"] for w in bench["workloads"]].count(4) == 1
    for entry in bench["per_layer"]:
        if entry["name"] in NEW_METRICS:
            unit, source = NEW_METRICS[entry["name"]]
            check_name(entry["name"], "metric")
            assert check_unit(entry["unit"], entry["name"]) == unit
            assert entry == {"name": entry["name"], "unit": unit, "better": "lower",
                             "source": source, "layer": "operators and kernels",
                             "moves": "stmt_s.geomean", "workloads": [CELL]}
    # its reference is the benchmark's own: the statement imports nothing of the program
    with open(os.path.join(cell.bench_dir, "statements", "ds_q65.py")) as f:
        assert "import trino_tpu" not in f.read().replace("from trino_tpu", "import trino_tpu")


def test_the_cells_text_is_the_template_with_its_order_by_completed():
    st = _statement()
    tail = ", sc.revenue, i_current_price, i_wholesale_cost, i_brand"
    assert st.SQL.replace(tail + "\nlimit 100", "\nlimit 100") == st.TEMPLATE_SQL
    assert st.render(st.VALIDATION)[0] == st.SQL.format(**st.VALIDATION)
    assert st.render_template(st.VALIDATION)[0] == st.TEMPLATE_SQL.format(**st.VALIDATION)
    assert st.VALIDATION == {"dms": 1176, "factor": "0.1"}
    # the completed order is the SELECT list, in SELECT order: the reference sorts by it
    select = re.search(r"select (.*?)\nfrom", st.SQL, re.S).group(1)
    order = re.search(r"order by (.*?)\nlimit", st.SQL, re.S).group(1)
    assert [c.strip() for c in select.split(",")] == [c.strip() for c in order.split(",")]


def test_the_statements_tables_name_every_column_its_reference_reads():
    st = _statement()
    with open(os.path.join(ROOT, "benchmark", "statements", "ds_q65.py")) as f:
        body = f.read().split("def reference", 1)[1]
    read = set(re.findall(r'"((?:ss|d|s|i)_[a-z_]+)"', body))
    named = {c for cols in st.TABLES.values() for c in cols}
    assert read and read <= named, read - named
    # and every named column is one the text selects, joins or filters on
    assert all(c in st.SQL for c in named), [c for c in named if c not in st.SQL]


def _tables(cell):
    from trino_tpu.connectors.tpcds import TpcdsConnector

    wanted = {}
    for st in cell.statements.values():
        for table, cols in st.TABLES.items():
            wanted.setdefault(table, []).extend(cols)
    return HostTables(TpcdsConnector(sf=cell.config["rehearse_sf"],
                                     split_rows=cell.config["split_rows"]), wanted)


def test_the_float32_control_is_not_correct_where_the_answer_has_rows():
    """At ``rehearse_sf`` no pair is under a tenth of its store's average (thirty sales a
    pair), so the control is read at the average itself; at scale 10 the qualification
    value answers 100 rows (``benchmark.limits`` on the chip: PERF.md section 2)."""
    cell = Cell(CELL)
    tables = _tables(cell)
    st = cell.statements["ds_q65"]
    assert len(st.reference(tables, st.VALIDATION)) == 0
    p = dict(st.VALIDATION, factor="1.0")
    want = st.reference(tables, p)
    assert len(want) > 10
    assert compare.within_limits(compare.compare(want, want))
    control = compare.compare(st.reference(tables, p, dtype=np.float32), want)
    assert not compare.within_limits(control), control
    assert control["max_rel_err"] > 3 * compare.LIMITS["max_rel_err"]
    assert control["exact_mismatches"] == 0


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_new_cell(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    out = subprocess.run([sys.executable, "-m", "benchmark.run", "--workload", CELL, "--seed",
                          "3000040029", "--seconds", "3", "--trace", str(trace), "--rehearse"],
                         cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(line) for line in out.stdout.splitlines() if line.startswith("{")]
    assert [l["setup"] for l in lines if "setup" in l] == ["ds_q65"]
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
    for name, (unit, _) in NEW_METRICS.items():
        assert metrics[name]["unit"] == unit
    # every statement of the window groups the year's (store, item) lanes again, and the
    # loop's rounds run at least once over each
    inserted = metrics["groupby_insert_lanes_per_stmt.olap"]["value"]
    assert inserted > 0
    assert metrics["groupby_insert_round_lanes_per_stmt.olap"]["value"] >= inserted
    assert metrics["ds_q65_s.olap"]["value"] > 0
    assert metrics["join_build_rows_per_stmt.olap"]["value"] == 0
    assert metrics["build_cache_lookups_per_stmt.olap"]["value"] == 0
    assert metrics["groupby_regrows_per_stmt.olap"]["value"] == 0


class Ctx:
    window_s = 45.0

    def __init__(self, counters):
        self.counters = counters

    def completed(self, name=None):
        return [{"name": "ds_q65", "seconds": 30.0}] * 2 if name in (None, "ds_q65") else []


def _read(name):
    return _load_module(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"),
                        name).read


def test_the_new_readers_with_and_without_what_they_read():
    """The driver lays these files over the parent's checkout too: there the counter's
    reader returns None and the line leaves the metric out."""
    rounds = _read("groupby_insert_round_lanes_per_stmt.olap")
    assert rounds(Ctx({"groupby_insert_round_lanes": 400_000_000,
                       "groupby_insert_lanes": 18_800_000})) == 200_000_000
    assert rounds(Ctx({"groupby_insert_lanes": 18_800_000})) is None
    seconds = _read("ds_q65_s.olap")
    assert seconds(Ctx({})) == 30.0

    class Empty(Ctx):
        def completed(self, name=None):
            return []

    assert seconds(Empty({})) is None and rounds(Empty({"groupby_insert_round_lanes": 1})) is None
