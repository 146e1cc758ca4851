"""chip_smoke.py's contract, as far as a machine without a chip can show it:
the CPU rehearsal runs end to end and ends in the result line, no TPU without
``--rehearse`` is a non-zero exit with no result line, and the compile cache
is placed where the contract says."""

import json
import os
import subprocess
import sys

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_rehearsal_runs_end_to_end(monkeypatch, capsys):
    # main() would setdefault this for the rehearsal; keep it out of the
    # environment of the tests that follow in this worker
    monkeypatch.setenv("TRINO_TPU_PAGE_CACHE", str(1 << 30))
    assert chip_smoke.main(["--rehearse"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is True
    assert set(last) == {"ok", "device"}
    assert last["device"]["platform"] == "cpu"  # never reports a TPU
    assert set(last["device"]) == {"platform", "kind", "count"}
    facts = [json.loads(x) for x in lines[:-1]]
    served = {f["query"]: f for f in facts if "query" in f}
    assert set(served) == set(chip_smoke.SERVED)
    assert all(f["oracle"] == "equal" for f in served.values())
    assert any(f.get("lineitem_pages_resident") for f in facts)


def test_no_tpu_and_no_rehearsal_is_a_failure(capsys):
    assert chip_smoke.main([]) != 0
    assert capsys.readouterr().out == ""  # no result line


def test_answer_check_catches_a_wrong_answer():
    import pandas as pd

    want = pd.DataFrame({"k": ["a", "b"], "v": [1.0, 2.0]})
    chip_smoke.check_answer("q4", want.copy(), want, "self")
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_answer("q4", want.assign(v=[1.0, 2.5]), want, "wrong value")
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_answer("q4", want.iloc[:1], want, "missing row")


def _cache_dir_of_a_fresh_process(**env):
    base = {k: v for k, v in os.environ.items()
            if k not in ("JAX_COMPILATION_CACHE_DIR", "TRINO_TPU_NO_COMPILE_CACHE")}
    out = subprocess.run(
        [sys.executable, "-c",
         "import trino_tpu, jax; print(jax.config.jax_compilation_cache_dir)"],
        cwd=REPO, env={**base, "JAX_PLATFORMS": "cpu", **env},
        capture_output=True, text=True, timeout=300, check=True)
    return out.stdout.strip().splitlines()[-1]


def test_cache_dir_is_placed_from_outside(tmp_path):
    placed = str(tmp_path / "cc")
    assert _cache_dir_of_a_fresh_process(JAX_COMPILATION_CACHE_DIR=placed) == placed


def test_cache_dir_default_is_fixed_inside_the_checkout():
    d = _cache_dir_of_a_fresh_process()
    assert d == _cache_dir_of_a_fresh_process()  # no pid, no time: same in every process
    root = os.path.join(REPO, ".jax_cache")
    assert d == root or os.path.dirname(d) == root
    # at most a CPU-feature sub-directory: no boot id, host, pid, time, temp name
    assert os.path.basename(d) == ".jax_cache" or os.path.basename(d).startswith("cpu-")
    with open("/proc/sys/kernel/random/boot_id") as f:
        assert f.read().strip()[:8] not in d
