"""The command itself on the CPU backend: ``--rehearse`` of each cell ends in a
well-formed result line that names the CPU; without it no result is printed; and a run
whose timed path is broken underneath comes out as not correct."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness.loader import ROOT

CELLS = ("sf1_joins", "sf10_scan", "sf1_dashboard")
E2E = {"sf1_joins": {"stmt_s.geomean", "setup_s"},
       "sf10_scan": {"stmt_s.geomean", "rows_per_s", "setup_s"},
       "sf1_dashboard": {"stmt_s.p95", "stmts_per_s", "setup_s"}}


def command(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    return subprocess.run([sys.executable, "-m", "benchmark.run", *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=900)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_ends_in_a_well_formed_result_line(cell, trace):
    out = command("--workload", cell, "--seed", "3000000019", "--seconds", "3",
                  "--trace", str(trace), "--rehearse")
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(result) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu" and result["device"]["count"] == 1
    assert "tpu" not in json.dumps(result["device"]).lower()
    for metric in result["metrics"].values():
        assert isinstance(metric["value"], float) and metric["unit"]
    if trace:
        assert result["device"]["busy_s"] > 0 and result["device"]["window_s"] > 0
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
        assert all(name == "between statements" or name.startswith("inside ")
                   for name, _ in result["breakdown"]["idle_gaps"])
        # the annotations are found on whatever the host thread's line is called
        assert any(name.startswith("inside ") for name, _ in result["breakdown"]["idle_gaps"])
        assert not set(result["metrics"]) & E2E[cell]
    else:
        assert set(result["metrics"]) == E2E[cell]
        assert all(m["value"] > 0 for m in result["metrics"].values())
    compared = [json.loads(line) for line in out.stdout.splitlines() if '"compared"' in line][-1]
    assert all({"value", "limit"} <= set(v) for v in compared["compared"].values())


def test_without_rehearse_a_cpu_backend_prints_no_result():
    out = command("--workload", "sf1_joins", "--seed", "1", "--seconds", "1", "--trace", "0")
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "a TPU is required" in out.stderr


def test_an_unknown_workload_prints_no_result():
    out = command("--workload", "nope", "--seed", "1", "--seconds", "1", "--trace", "0",
                  "--rehearse")
    assert out.returncode != 0 and out.stdout.strip() == ""


@pytest.mark.parametrize("cell", ["sf1_joins", "sf1_dashboard"])
def test_an_answer_altered_where_it_is_produced_is_not_correct(cell, monkeypatch, capsys):
    """Drives a whole run (past the look for a chip: ``--rehearse``) with the served path
    broken underneath: every answer's last numeric value is off by a millionth."""
    from benchmark import run
    from trino_tpu.server.client import Client

    real = Client.execute

    def altered(self, sql, timeout=600.0, params=None):
        res = real(self, sql, timeout=timeout, params=params)
        for row in res.rows[:1]:
            for j in reversed(range(len(row))):
                if isinstance(row[j], float):
                    row[j] = row[j] * (1 + 1e-6) + 1e-6
                    break
        return res

    monkeypatch.setattr(Client, "execute", altered)
    rc = run.main(["--workload", cell, "--seed", "3000000023", "--seconds", "2",
                   "--trace", "0", "--rehearse"])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert result["correct"] is False and result["failed"] > 0
