import os

import pytest

from benchmark.harness import xplane

SMALL = os.path.join(os.path.dirname(__file__), "small.xplane.pb")


def test_union_merges_nested_and_overlapping_intervals():
    assert xplane.union([(5, 7), (0, 3), (1, 2), (2, 4), (7, 8)]) == [(0, 4), (5, 8)]


def test_reduction_of_the_recorded_v5e_trace():
    """small.xplane.pb: two annotated spans ("inside a", "inside b") of three small jitted
    calls each on one TPU v5e, with sleeps between them (record_small_trace.py)."""
    r = xplane.reduce_trace(SMALL)
    assert r["devices"] == 1
    # six executions of about 11.8 us each, four of them inside the annotated window's clock
    assert 0 < r["busy_s"] < 1e-4
    assert 0.02 < r["window_s"] < 0.03
    assert r["busy_s"] < r["window_s"]
    ops = dict(r["device_ops"])
    assert "convolution_reduce_fusion" in ops
    assert sum(ops.values()) >= r["busy_s"]
    gaps = dict(r["idle_gaps"])
    assert set(gaps) <= {"between statements", "inside a", "inside b"}
    assert gaps["between statements"] > 0.015  # the sleeps
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"], rel=1e-6)
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10


def test_a_trace_without_device_operations_is_an_error():
    # the recorded trace has no CPU stand-in lines named as a device: only the TPU plane counts
    import jax

    data = jax.profiler.ProfileData.from_file(SMALL)
    assert any(p.name.startswith(xplane.DEVICE_PLANE) for p in data.planes)
    with pytest.raises(FileNotFoundError):
        xplane.find_trace(os.path.dirname(__file__))
