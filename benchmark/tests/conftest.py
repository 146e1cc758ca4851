"""CPU tests of the benchmark's harness (not part of the repo's tier-1 suite):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import json  # noqa: E402
import shutil  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture
def copy(tmp_path):
    """BENCHMARK.json and benchmark/ (without its tests) in a temporary directory."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    return tmp_path


@pytest.fixture
def edit():
    """edit(root, fn): rewrites the root's BENCHMARK.json through ``fn(bench)``."""
    def edit_(root, fn):
        path = os.path.join(root, "BENCHMARK.json")
        with open(path) as f:
            bench = json.load(f)
        fn(bench)
        with open(path, "w") as f:
            json.dump(bench, f)
    return edit_
