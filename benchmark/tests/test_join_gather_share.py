"""``join_gather_lane_share.olap`` (PR 28, a new file and an appended entry): listed in
both join cells, None on a program without the two counters (the driver lays this file
over the parent's checkout too), the share of matched lanes gathered with them."""

import json
import os

import pytest

from benchmark.harness.loader import ROOT, Cell, _load_module

NAME = "join_gather_lane_share.olap"


def _read():
    return _load_module(os.path.join(ROOT, "benchmark", "layer_metrics", NAME + ".py"),
                        NAME).read


class Ctx:
    def __init__(self, counters):
        self.counters = counters


def test_the_entry_lists_the_two_join_cells_and_nothing_else_changed_shape():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = json.load(f)["per_layer"][-1]
    assert entry == {"name": NAME, "unit": "%", "better": "lower",
                     "source": "program_counter", "layer": "operators and kernels",
                     "moves": "stmt_s.geomean", "workloads": ["sf1_joins", "sf10_joins"]}
    for cell in ("sf1_joins", "sf10_joins"):
        assert NAME in {m["name"] for m in Cell(cell).per_layer}
    for cell in ("sf10_scan", "sf1_dashboard"):
        assert NAME not in {m["name"] for m in Cell(cell).per_layer}


@pytest.mark.parametrize("counters,want", [
    ({"device_dispatches": 12, "compactions": 3}, None),            # the parent
    ({"join_match_lanes": 0, "join_gather_lanes": 0}, None),        # no join in the window
    ({"join_match_lanes": 37_748_718, "join_gather_lanes": 12_976_120},
     100.0 * 12_976_120 / 37_748_718),                              # a round of sf1_joins
    ({"join_match_lanes": 1 << 21, "join_gather_lanes": 1 << 21}, 100.0),  # all dense
    ({"join_match_lanes": 1 << 21, "join_gather_lanes": 1 << 15}, 1.5625),  # all n/64
])
def test_the_reader(counters, want):
    assert _read()(Ctx(counters)) == want
