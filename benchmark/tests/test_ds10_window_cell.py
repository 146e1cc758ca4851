"""The cell ``ds10_window_outer`` (configuration ``tpcds_sf10_q51_1chip``, traffic
``ds_window_stream``), added in PR 42 as new files and appended entries: its entries load
and pass the loader's name and unit checks, it has ONE statement class (``ds_q51``, whose
text departs from query51.tpl in the two coalesces of its outermost SELECT alone), its
``TABLES`` name every column its reference reads, the statement draws inside its
template's range, the float32 control comes out as not correct, a rehearsal ends
``correct`` and prints the four new metrics, and the counters' readers find nothing on a
program without the counters (the parent's).  What a later PR may append to is held as
"at least", not pinned."""

import json
import os
import random
import re
import subprocess
import sys

import numpy as np
import pytest

from benchmark.harness import compare
from benchmark.harness.hosttables import HostTables
from benchmark.harness.loader import ROOT, Cell, _load_module, check_name, check_unit

CELL = "ds10_window_outer"
NEW_METRICS = {"ds_q51_s.olap": ("s", "host_clock"),
               "window_lanes_per_stmt.olap": ("count", "program_counter"),
               "window_sort_lanes_per_stmt.olap": ("count", "program_counter"),
               "window_kernels_per_stmt.olap": ("count", "program_counter")}
# what ISSUE 42 asks the cell to report: every list that names ds10_hash_groupby but
# ds_q65_s.olap, the two hashed-lookup metrics that read something here, and its own four
LISTED = {"plan_ms.olap", "window_compiles.olap", "dispatches_per_stmt.olap",
          "page_cache_hit_share.olap", "build_cache_lookups_per_stmt.olap",
          "device_busy_s_per_stmt.olap", "host_pull_s_per_stmt.olap",
          "dispatch_s_per_stmt.olap", "host_other_s_per_stmt.olap",
          "host_unnamed_s_per_stmt.olap", "host_cpu_s_per_stmt.olap", "compile_misses.olap",
          "groupby_regrows_per_stmt.olap", "groupby_state_mb.olap", "spilled_mb_per_stmt.olap",
          "generated_rows_per_s.olap", "generator_dispatches_per_stmt.olap",
          "scan_wait_s_per_stmt.olap", "join_build_rows_per_stmt.olap",
          "join_gather_lane_share.olap", "groupby_insert_lanes_per_stmt.olap",
          "groupby_insert_round_lanes_per_stmt.olap", "hash_join_lane_share.olap",
          "hash_probe_lanes_per_stmt.olap"} | set(NEW_METRICS)
# (hash_probe_round_lanes_per_stmt.olap does not list the cell: both joins of the FULL
# OUTER JOIN run fused, and only a split join's boundary pulls its lookup's rounds)
# nothing to read on the CPU backend, by design: the page cache is off there (its
# budget is 0, so no lookup is made), and the stand-in trace has no device plane
NONE_ON_CPU = {"page_cache_hit_share.olap", "device_busy_s_per_stmt.olap"}


def _benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _statement():
    return _load_module(os.path.join(ROOT, "benchmark", "statements", "ds_q51.py"), "ds_q51")


def test_the_new_entries_load_and_pass_the_name_and_unit_checks():
    bench = _benchmark_json()
    cell = Cell(CELL)
    sibling = Cell("ds10_hash_groupby")
    assert cell.chips == 1 and cell.config["name"] == "tpcds_sf10_q51_1chip"
    for key in ("connector", "catalog", "sf", "rehearse_sf", "split_rows", "chips"):
        assert cell.config[key] == sibling.config[key], key  # the six harness keys
    traffic = dict(cell.traffic)
    assert traffic.pop("why") and traffic.pop("name") == "ds_window_stream"
    assert traffic == {"loop": "closed", "clients": 1, "slots": ["ds_q51"],
                       "order": "seeded_rounds", "params": {"ds_q51": "fixed"}, "check": "all",
                       "statement_timeout_s": 900, "poll_interval": 0.05, "trace_seconds": 5}
    assert list(cell.statements) == ["ds_q51"]  # ONE class: the parent's run has to end
    assert {m["name"] for m in cell.end_to_end} == {"stmt_s.geomean", "setup_s"}
    assert {m["name"] for m in cell.per_layer} >= LISTED
    config = next(c for c in bench["configs"] if c["name"] == "tpcds_sf10_q51_1chip")
    workload = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert workload == dict(workload, config="tpcds_sf10_q51_1chip",
                            traffic="ds_window_stream", chips=1)
    for text in (config["source"], config["why"], workload["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    for word in ("TPC-DS", "scale 10", "query51.tpl", "DMS 1200"):
        assert word in config["source"]
    assert config["source"] == cell.config["source"]
    others = [c["source"] for c in bench["configs"] if c["name"] != config["name"]]
    assert config["source"] not in others
    assert config["reduced"] == ["scale", "queries", "substitution_parameters",
                                 "data_maintenance", "streams"]
    assert set(config["reduced"]) == set(cell.config["reduced"])
    for key in config["reduced"]:
        check_name(key, "reduced")
    assert {"data", "dms", "null_sentinel", "seed"} <= set(cell.config["assumed"])
    assert "coalesce(web_sales, -1)" in cell.config["assumed"]["null_sentinel"]
    assert any("all three windows again" in g and "result cache" in g
               for g in cell.config["guarantees"])
    assert len({c["file"] for c in bench["configs"]}) == len(bench["configs"])
    for entry in bench["per_layer"]:
        if entry["name"] in NEW_METRICS:
            unit, source = NEW_METRICS[entry["name"]]
            check_name(entry["name"], "metric")
            assert check_unit(entry["unit"], entry["name"]) == unit
            assert dict(entry, workloads=None) == {
                "name": entry["name"], "unit": unit, "better": "lower", "source": source,
                "layer": "operators and kernels", "moves": "stmt_s.geomean",
                "workloads": None}
            assert CELL in entry["workloads"]
    assert set(NEW_METRICS) <= {e["name"] for e in bench["per_layer"]}
    assert CELL in next(e for e in bench["end_to_end"]
                        if e["name"] == "stmt_s.geomean")["workloads"]
    # its reference is the benchmark's own: the statement imports nothing of the program
    with open(os.path.join(cell.bench_dir, "statements", "ds_q51.py")) as f:
        assert "import trino_tpu" not in f.read().replace("from trino_tpu", "import trino_tpu")


def test_the_cells_text_is_the_template_but_for_the_two_coalesces():
    st = _statement()
    select = ("select item_sk, d_date, coalesce(web_sales, -1) web_sales, "
              "coalesce(store_sales, -1) store_sales, web_cumulative, store_cumulative from (")
    assert st.SQL.count(select) == 1 and st.TEMPLATE_SQL.count("select * from (") == 1
    assert st.SQL.replace(select, "select * from (") == st.TEMPLATE_SQL
    assert st.render(st.VALIDATION)[0] == st.SQL.format(**st.VALIDATION)
    assert st.render_template(st.VALIDATION)[0] == st.TEMPLATE_SQL.format(**st.VALIDATION)
    assert st.VALIDATION == {"dms": 1200} and st.NULL_SENTINEL == -1
    for word in ("full outer join", "rows between unbounded preceding and current row",
                 "sum(sum(ws_sales_price))", "sum(sum(ss_sales_price))",
                 "where web_cumulative > store_cumulative", "order by item_sk, d_date",
                 "limit 100"):
        assert word in st.TEMPLATE_SQL, word
    assert st.COLUMNS == ["item_sk", "d_date", "web_sales", "store_sales",
                          "web_cumulative", "store_cumulative"]


def test_the_statement_draws_inside_its_templates_range():
    st = _statement()
    rng = random.Random("3000000042/window/0")
    draws = [st.params(rng, {})["dms"] for _ in range(400)]
    assert min(draws) >= 1176 and max(draws) <= 1224 and len(set(draws)) > 20
    assert all(isinstance(d, int) for d in draws)
    again = random.Random("3000000042/window/0")
    assert [st.params(again, {})["dms"] for _ in range(400)] == draws
    assert "between 1187 and 1187+11" in st.render({"dms": 1187})[0]


def test_the_statements_tables_name_every_column_its_reference_reads():
    st = _statement()
    with open(os.path.join(ROOT, "benchmark", "statements", "ds_q51.py")) as f:
        body = f.read().split("def _channel", 1)[1]
    read = set(re.findall(r'"(d_[a-z_]+)"', body))
    read |= {prefix + suffix for prefix in ("ws", "ss")
             for suffix in re.findall(r'prefix \+ "(_[a-z_]+)"', body)}
    named = {c for cols in st.TABLES.values() for c in cols}
    assert read == named, read ^ named
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


def test_the_float32_control_is_not_correct_and_nulls_stand_as_the_sentinel():
    cell = Cell(CELL)
    tables = _tables(cell)
    st = cell.statements["ds_q51"]
    want = st.reference(tables, st.VALIDATION)
    assert len(want) == 100 and list(want.columns) == st.COLUMNS
    assert compare.within_limits(compare.compare(want, want))
    control = compare.compare(st.reference(tables, st.VALIDATION, dtype=np.float32), want)
    assert not compare.within_limits(control), control
    assert control["max_rel_err"] > 3 * compare.LIMITS["max_rel_err"]
    # NULL as -1 in the cell's frame, NaN in the template's; never in a cumulative maximum
    raw = st.reference(tables, st.VALIDATION, nulls=None)
    for name in ("web_sales", "store_sales"):
        assert ((want[name] == -1) == raw[name].isna()).all() and raw[name].isna().any()
    assert raw["web_cumulative"].notna().all() and raw["store_cumulative"].notna().all()
    assert (raw["web_cumulative"] > raw["store_cumulative"]).all()
    full = st.reference(tables, st.VALIDATION, limit=None)
    assert len(full) > 100 and not full.duplicated(["item_sk", "d_date"]).any()
    assert full[["item_sk", "d_date"]].equals(
        full[["item_sk", "d_date"]].sort_values(["item_sk", "d_date"]).reset_index(drop=True))


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_new_cell(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    out = subprocess.run([sys.executable, "-m", "benchmark.run", "--workload", CELL, "--seed",
                          "3000042029", "--seconds", "3", "--trace", str(trace), "--rehearse"],
                         cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(line) for line in out.stdout.splitlines() if line.startswith("{")]
    assert [l["setup"] for l in lines if "setup" in l] == ["ds_q51"]
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
    assert set(metrics) >= LISTED - NONE_ON_CPU
    for name, (unit, _) in NEW_METRICS.items():
        assert metrics[name]["unit"] == unit
    # every statement of the window runs each channel's group-by and window and the
    # window over the joined rows again
    assert metrics["window_kernels_per_stmt.olap"]["value"] == 3
    lanes = metrics["window_lanes_per_stmt.olap"]["value"]
    assert lanes > 0 and metrics["window_sort_lanes_per_stmt.olap"]["value"] >= 2 * lanes
    assert metrics["groupby_insert_lanes_per_stmt.olap"]["value"] > 0
    assert metrics["hash_probe_lanes_per_stmt.olap"]["value"] > 0
    assert metrics["ds_q51_s.olap"]["value"] > 0
    assert metrics["join_build_rows_per_stmt.olap"]["value"] == 0
    assert metrics["build_cache_lookups_per_stmt.olap"]["value"] == 0
    assert metrics["groupby_regrows_per_stmt.olap"]["value"] == 0


class Ctx:
    window_s = 45.0

    def __init__(self, counters):
        self.counters = counters

    def completed(self, name=None):
        return [{"name": "ds_q51", "seconds": 12.0}] * 3 if name in (None, "ds_q51") else []


def _read(name):
    return _load_module(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"),
                        name).read


def test_the_new_readers_with_and_without_what_they_read():
    """The driver lays these files over the parent's checkout too: there the counters'
    readers return None and the line leaves the metrics out."""
    counters = {"window_kernels": 9, "window_lanes": 30_000_000, "window_sort_lanes": 150_000_000}
    for name, field in (("window_kernels_per_stmt.olap", "window_kernels"),
                        ("window_lanes_per_stmt.olap", "window_lanes"),
                        ("window_sort_lanes_per_stmt.olap", "window_sort_lanes")):
        read = _read(name)
        assert read(Ctx(counters)) == counters[field] / 3
        assert read(Ctx({"groupby_insert_lanes": 1})) is None  # the parent's program
    seconds = _read("ds_q51_s.olap")
    assert seconds(Ctx({})) == 12.0

    class Empty(Ctx):
        def completed(self, name=None):
            return []

    assert seconds(Empty({})) is None
    assert _read("window_lanes_per_stmt.olap")(Empty(counters)) is None
