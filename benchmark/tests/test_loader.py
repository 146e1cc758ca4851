import json
import os

import pytest

from benchmark.harness.loader import BenchmarkError, Cell, check_name, check_unit


@pytest.mark.parametrize("name", ["a b", "a,b", "a/b", "", "-x", "x" * 65, "qμ"])
def test_bad_names_are_refused(name):
    with pytest.raises(BenchmarkError):
        check_name(name, "metric")


@pytest.mark.parametrize("unit", ["tokens per s", "", "x" * 17, "μs", "a,b"])
def test_bad_units_are_refused(unit):
    with pytest.raises(BenchmarkError):
        check_unit(unit, "m")


def test_good_names_and_units():
    for name in ("stmt_s.geomean", "q9_s.olap", "sf1_joins", "9x", "_y"):
        assert check_name(name, "metric") == name
    for unit in ("rows/s", "%", "s", "stmts/s"):
        assert check_unit(unit, "m") == unit


@pytest.mark.parametrize("cell", ["sf1_joins", "sf10_scan", "sf1_dashboard", "sf10_joins"])
def test_every_cell_of_the_repo_loads(cell):
    c = Cell(cell)
    # the deployment the harness builds is written out in every file of the repo
    assert (c.config["connector"], c.config["catalog"], c.config["chips"]) == ("tpch", "tpch", 1)
    assert c.traffic["statement_timeout_s"] == 300
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer and all(callable(m["read"]) for m in c.per_layer + c.end_to_end)
    assert set(c.traffic["params"]) == set(c.traffic["slots"]) == set(c.statements)
    for st in c.statements.values():
        assert st.TABLES and callable(st.reference) and isinstance(st.VALIDATION, dict)


def test_a_bad_metric_name_or_unit_in_benchmark_json_is_refused(copy, edit):
    edit(copy, lambda b: b["per_layer"].append(
        {"name": "bad name", "unit": "s", "better": "lower", "source": "host_clock",
         "layer": "x", "moves": "setup_s"}))
    with pytest.raises(BenchmarkError, match="bad metric name"):
        Cell("sf1_joins", root=str(copy))
    edit(copy, lambda b: b["per_layer"][-1].update(name="fine", unit="rows per s"))
    with pytest.raises(BenchmarkError, match="bad unit"):
        Cell("sf1_joins", root=str(copy))


def test_a_workload_naming_a_missing_file_is_refused(copy, edit):
    edit(copy, lambda b: b["workloads"].append(
        {"name": "ghost", "config": "tpch_sf1_1chip", "traffic": "no_such_mix", "chips": 1,
         "why": "x"}))
    with pytest.raises(BenchmarkError, match="missing file"):
        Cell("ghost", root=str(copy))
    with pytest.raises(BenchmarkError, match="no workload"):
        Cell("nowhere", root=str(copy))


def test_a_later_pr_adds_one_of_each_as_new_files_and_entries(copy, edit):
    """A statement, a traffic mix, a configuration, a per-layer metric and a cell: new
    files and new BENCHMARK.json entries, no edit to a file that was there."""
    before = {p: os.path.getmtime(os.path.join(dp, p)) for dp, _, fs in os.walk(copy / "benchmark")
              for p in fs}
    b = copy / "benchmark"
    (b / "statements" / "count_orders.py").write_text(
        'import pandas as pd\n'
        'TABLES = {"orders": ["o_orderkey"]}\nVALIDATION = {}\n'
        'def params(rng, config):\n    return {}\n'
        'def render(p):\n    return "select count(*) c from orders", None\n'
        'def reference(T, p, dtype=None):\n'
        '    return pd.DataFrame({"c": [len(T.columns("orders")["o_orderkey"])]})\n')
    (b / "traffic" / "count_stream.json").write_text(json.dumps(
        {"loop": "closed", "clients": 1, "slots": ["count_orders"],
         "params": {"count_orders": "fixed"}, "check": "all", "trace_seconds": 2,
         "why": "one more mix"}))
    config = json.loads((b / "configs" / "tpch_sf1_1chip.json").read_text())
    config["sf"] = 3
    (b / "configs" / "tpch_sf3_1chip.json").write_text(json.dumps(config))
    (b / "layer_metrics" / "host_bytes_per_stmt.olap.py").write_text(
        'def read(ctx):\n'
        '    done = len(ctx.completed())\n'
        '    return ctx.counters.get("host_bytes_pulled", 0) / done if done else None\n')

    def add(bench):
        bench["configs"].append({"name": "tpch_sf3_1chip", "source": "TPC-H, scale factor 3",
                                 "file": "benchmark/configs/tpch_sf3_1chip.json",
                                 "reduced": [], "why": "one more deployment"})
        bench["workloads"].append({"name": "sf3_count", "config": "tpch_sf3_1chip",
                                   "traffic": "count_stream", "chips": 1, "why": "one more cell"})
        for m in bench["end_to_end"]:
            if m["name"] in ("stmt_s.geomean", "rows_per_s"):
                m["workloads"].append("sf3_count")
        bench["per_layer"].append(
            {"name": "host_bytes_per_stmt.olap", "unit": "bytes", "better": "lower",
             "source": "program_counter", "layer": "executor", "moves": "stmt_s.geomean",
             "workloads": ["sf3_count"]})

    edit(copy, add)
    cell = Cell("sf3_count", root=str(copy))
    assert cell.config["sf"] == 3 and list(cell.statements) == ["count_orders"]
    assert [m["name"] for m in cell.per_layer] == ["host_bytes_per_stmt.olap"]
    assert {m["name"] for m in cell.end_to_end} == {"stmt_s.geomean", "rows_per_s", "setup_s"}
    after = {p: os.path.getmtime(os.path.join(dp, p)) for dp, _, fs in os.walk(b) for p in fs}
    assert all(after[p] == t for p, t in before.items())  # nothing that was there was edited
    Cell("sf1_joins", root=str(copy))  # and the old cells still load


def test_an_unknown_device_kind_has_no_peaks():
    cell = Cell("sf10_scan")
    assert cell.peak("TPU v5 lite")["hbm_gb_per_s"] == 819
    with pytest.raises(BenchmarkError, match="no peaks"):
        cell.peak("TPU v9")


def test_a_file_that_leaves_the_deployment_keys_out_describes_todays(copy):
    path = copy / "benchmark" / "configs" / "tpch_sf1_1chip.json"
    config = json.loads(path.read_text())
    for key in ("connector", "catalog", "chips"):
        del config[key]
    path.write_text(json.dumps(config))
    traffic = copy / "benchmark" / "traffic" / "joins_stream.json"
    traffic.write_text(json.dumps({k: v for k, v in json.loads(traffic.read_text()).items()
                                   if k != "statement_timeout_s"}))
    cell = Cell("sf1_joins", root=str(copy))
    assert (cell.config["connector"], cell.config["catalog"], cell.config["chips"]) \
        == ("tpch", "tpch", 1)
    assert cell.traffic["statement_timeout_s"] == 300
    config.update(connector="tpcds")
    path.write_text(json.dumps(config))
    assert Cell("sf1_joins", root=str(copy)).config["catalog"] == "tpcds"


def test_chips_of_the_configuration_and_of_the_cell_have_to_agree(copy, edit):
    edit(copy, lambda b: b["workloads"][0].update(chips=4))
    with pytest.raises(BenchmarkError, match="asks for 4 chips.*describes a deployment on 1"):
        Cell("sf1_joins", root=str(copy))
