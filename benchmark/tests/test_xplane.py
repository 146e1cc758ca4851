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
    # six executions of about 11.8 us each, four of them inside the annotated window's clock:
    # the numbers the reduction gave before it listed each device's own busy seconds
    assert (r["busy_s"], r["window_s"]) == (4.7374e-05, 0.024291369)
    assert r["busy_s_by_device"] == [r["busy_s"]]
    assert r["device_ops"][0] == ["convolution_reduce_fusion", 4.7309e-05]
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


def test_busy_seconds_are_averaged_over_the_devices_and_listed_for_each(monkeypatch):
    """Two device planes, one busy twice as long as the other."""
    from types import SimpleNamespace as NS

    def plane(name, *events):
        return NS(name=name, lines=[NS(name="XLA Ops", events=[
            NS(name="fusion = f32[]", start_ns=a, duration_ns=d) for a, d in events])])

    data = NS(planes=[plane("/device:TPU:0", (0, 400), (600, 400)),
                      plane("/device:TPU:1", (100, 400)), NS(name="/host:CPU", lines=[])])
    import jax

    monkeypatch.setattr(jax.profiler.ProfileData, "from_file", staticmethod(lambda path: data))
    r = xplane.reduce_trace("two.xplane.pb")
    assert r["devices"] == 2 and r["window_s"] == 1000e-9
    assert r["busy_s_by_device"] == [800e-9, 400e-9] and r["busy_s"] == 600e-9
    assert dict(r["idle_gaps"]) == {"between statements": pytest.approx(400e-9)}
