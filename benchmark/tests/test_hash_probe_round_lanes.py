"""``hash_probe_round_lanes_per_stmt.olap`` (PR 37), appended for ``ds10_hash_joins``: its
entry's name, unit and ``workloads``, and its reader on a program with and without the
counter (the driver lays this file over the parent's checkout too)."""

import json
import os

from benchmark.harness.loader import ROOT, Cell, _load_module, check_name, check_unit

NAME = "hash_probe_round_lanes_per_stmt.olap"


def _read():
    return _load_module(os.path.join(ROOT, "benchmark", "layer_metrics", NAME + ".py"),
                        NAME).read


class Ctx:
    window_s = 45.0

    def __init__(self, counters):
        self.counters = counters

    def completed(self, name=None):
        return [{"name": "ds_q93", "seconds": 4.0}] * 3


def test_the_entry_is_the_last_one_and_names_the_one_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = json.load(f)["per_layer"][-1]
    assert entry == {"name": NAME, "unit": "count", "better": "lower",
                     "source": "program_counter", "layer": "operators and kernels",
                     "moves": "stmt_s.geomean", "workloads": ["ds10_hash_joins"]}
    check_name(entry["name"], "metric")
    assert check_unit(entry["unit"], entry["name"]) == "count"
    assert NAME in {m["name"] for m in Cell("ds10_hash_joins").per_layer}
    assert NAME not in {m["name"] for m in Cell("sf10_joins").per_layer}


def test_the_reader_divides_the_counter_by_the_statements_completed():
    assert _read()(Ctx({"join_hash_probe_round_lanes": 162_000_000,
                        "join_hash_probe_lanes": 100_663_296})) == 54_000_000


def test_the_reader_finds_nothing_on_a_program_without_the_counter():
    assert _read()(Ctx({"join_hash_probe_lanes": 100_663_296})) is None
