"""The yardstick still reads the program: a cell's whole control flow
(``python -m benchmark.run --rehearse``, the CPU at SF0.01) runs to its result
line, every answer right, for the two traffic shapes: one client replaying
(``sf1_joins``) and eight over HTTP (``sf1_dashboard``), and for the mesh
(``sf10_mesh4_joins``, PR 46: ``--rehearse`` asks the CPU backend for the
cell's four host devices).  Nothing of
``benchmark/`` is imported: the command and ``BENCHMARK.json`` are the
contract."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("cell", ["sf1_joins", "sf1_dashboard", "sf10_mesh4_joins"])
def test_cell_rehearses_to_a_correct_result_line(cell):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        declared = json.load(f)
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", cell,
         "--seed", "7", "--seconds", "3", "--trace", "0", "--rehearse"],
        cwd=REPO, env={**env, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is True, last
    assert last["failed"] == 0 and last["attempted"] > 0, last
    assert last["device"]["platform"] == "cpu"  # a rehearsal reports no chip
    cells = {w["name"]: w for w in declared["workloads"]}
    assert last["device"]["count"] == cells[cell]["chips"]
    listed = {m["name"] for m in declared["end_to_end"]
              if cell in m.get("workloads", [cell])}
    assert listed <= set(last["metrics"]), (listed, last["metrics"])
