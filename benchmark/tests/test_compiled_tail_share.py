"""``compiled_tail_share.serve`` (PR 39), appended for ``sf1_dashboard``: its entry, and its
reader on a program with and without the counters (the driver lays this file over the
parent's checkout too)."""

import json
import os

from benchmark.harness.loader import ROOT, Cell, _load_module, check_name, check_unit

NAME = "compiled_tail_share.serve"


def _read():
    return _load_module(os.path.join(ROOT, "benchmark", "layer_metrics", NAME + ".py"),
                        NAME).read


class Ctx:
    def __init__(self, counters):
        self.counters = counters


def test_the_entry_names_the_dashboard_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"] if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "executor",
                     "moves": "stmts_per_s", "workloads": ["sf1_dashboard"]}
    check_name(entry["name"], "metric")
    assert check_unit(entry["unit"], entry["name"]) == "%"
    assert NAME in {m["name"] for m in Cell("sf1_dashboard").per_layer}
    assert NAME not in {m["name"] for m in Cell("sf10_scan").per_layer}


def test_the_reader_takes_the_compiled_share_of_all_tails():
    assert _read()(Ctx({"tail_compiled": 150, "tail_eager": 0})) == 100.0
    assert _read()(Ctx({"tail_compiled": 3, "tail_eager": 1})) == 75.0


def test_the_reader_finds_nothing_without_the_counters_or_without_a_tail():
    assert _read()(Ctx({"device_dispatches": 12})) is None
    assert _read()(Ctx({"tail_compiled": 0, "tail_eager": 0})) is None
