"""The per-layer metrics that read the program's wait-state and wall-bucket seconds
counters: each against a fake context (counter present, absent, no statements)."""

import types

import pytest

from benchmark.harness.loader import Cell

COUNTERS = {"queued_s": 0.8, "encode_s": 0.02, "deliver_wait_s": 0.1, "batch_wait_s": 0.04,
            "executor_wait_s": 0.2, "wall_plan_s": 0.01, "wall_host_pull_s": 12.0,
            "wall_dispatch_s": 0.5, "wall_split_generation_s": 0.25, "wall_h2d_s": 0.05,
            "wall_unattributed_s": 0.7, "compile_cache_misses": 0}

EXPECTED = {  # over 10 statements
    "queue_wait_ms.serve": 80.0, "deliver_wait_ms.serve": 12.0, "batch_wait_ms.serve": 4.0,
    "executor_wait_ms.serve": 20.0, "plan_ms.serve": 1.0,
    "host_pull_s_per_stmt.olap": 1.2, "dispatch_s_per_stmt.olap": 0.05,
    "host_other_s_per_stmt.olap": 0.1, "compile_misses.olap": 0, "compile_misses.serve": 0}


def ctx_of(counters, statements):
    records = [{"name": "point", "error": None} for _ in range(statements)]
    return types.SimpleNamespace(counters=counters, completed=lambda name=None: records)


def readers():
    out = {}
    for cell in ("sf1_joins", "sf10_scan", "sf1_dashboard"):
        for m in Cell(cell).per_layer:
            if m["name"] in EXPECTED:
                out.setdefault(m["name"], []).append((cell, m))
    return out


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_present_absent_and_no_statements(name):
    cells = readers()[name]
    want_cells = ["sf1_dashboard"] if name.endswith(".serve") else ["sf1_joins", "sf10_scan"]
    assert [c for c, _ in cells] == want_cells
    for _, m in cells:
        read = m["read"]
        assert read(ctx_of(dict(COUNTERS), 10)) == pytest.approx(EXPECTED[name])
        # the parent program has no such counter: the metric is left out, nothing raises
        assert read(ctx_of({"compiles": 0, "device_dispatches": 7}, 10)) is None
        if not name.startswith("compile_misses"):
            assert read(ctx_of(dict(COUNTERS), 0)) is None
        assert m["better"] == "lower" and m["source"] in ("program_span", "program_counter")
