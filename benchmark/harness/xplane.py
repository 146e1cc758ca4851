"""The reduction from the profiler's trace (.xplane.pb) to device busy and idle time.

Busy is the union of the intervals in which an operation ran on a device, taken from
the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane and averaged over the devices.
The window is the span of the benchmark's own annotations on the host's thread lines
(``inside <statement>`` around every ``Client.execute``); the two clocks agree to
about a millisecond, which is enough to name a gap by what the host was doing.  On the
CPU backend (``--rehearse``) there is no device plane: the XLA client's host threads
stand in, so that the same code is exercised, and nothing of it is a device number.
"""

import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINES = ("XLA Ops", "XLA Modules")
ANNOTATION = "inside "


def find_trace(trace_dir):
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"the profiler wrote no .xplane.pb under {trace_dir}")
    return paths[-1]


def union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping or nested intervals."""
    out = []
    for a, z in sorted(intervals):
        if out and a <= out[-1][1]:
            if z > out[-1][1]:
                out[-1] = (out[-1][0], z)
        else:
            out.append((a, z))
    return out


def _device_lines(data, cpu_stand_in):
    """[(device name, [events of its operations])], one entry per device."""
    out = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = {line.name: line for line in plane.lines}
            line = next((lines[n] for n in OPS_LINES if n in lines), None)
            if line is not None:
                out.append((plane.name, list(line.events)))
        elif cpu_stand_in and plane.name == "/host:CPU":
            events = [e for line in plane.lines if line.name.startswith("tf_XLA")
                      for e in line.events if e.duration_ns > 0]
            if events:
                out.append((plane.name, events))
    return out


def _annotations(data):
    for plane in data.planes:
        if plane.name == "/host:CPU":
            # a host line is named after its thread ("python", "python3", "Thread-3"...)
            return sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for line in plane.lines
                          for e in line.events if e.name.startswith(ANNOTATION))
    return []


def _host_activity(spans, a, z):
    """What the host was doing in the gap (a, z): inside a statement or between two."""
    mid = (a + z) / 2.0
    for s0, s1, name in spans:
        if s0 <= mid <= s1:
            return name
    return "between statements"


def reduce_trace(path, cpu_stand_in=False, top=10):
    """-> {"busy_s", "busy_s_by_device", "window_s", "devices", "device_ops": [[name, s]...],
    "idle_gaps": [[name, s]...]}; ``busy_s`` is the mean over devices, ``busy_s_by_device``
    each device's own in the order of the trace's planes."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices = _device_lines(data, cpu_stand_in)
    spans = _annotations(data)
    if not devices:
        raise ValueError(f"no device operations in {path}: the traced window never "
                         "reached the device")
    if spans:
        lo, hi = spans[0][0], max(s[1] for s in spans)
    else:
        lo = min(e.start_ns for _, evs in devices for e in evs)
        hi = max(e.start_ns + e.duration_ns for _, evs in devices for e in evs)
    busy, ops, gaps = [], {}, {}
    for _, events in devices:
        clipped = []
        for e in events:
            a, z = max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi)
            if z > a:
                clipped.append((a, z))
                name = e.name.split(" = ")[0].lstrip("%")
                ops[name] = ops.get(name, 0.0) + (z - a)
        merged = union(clipped)
        busy.append(sum(z - a for a, z in merged))
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for a, z in zip(edges[0::2], edges[1::2]):
            if z > a:
                name = _host_activity(spans, a, z)
                gaps[name] = gaps.get(name, 0.0) + (z - a)
    n = len(devices)

    def ranked(d):
        return [[k, v / n / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": sum(busy) / n / 1e9, "busy_s_by_device": [b / 1e9 for b in busy],
            "window_s": (hi - lo) / 1e9, "devices": n,
            "device_ops": ranked(ops), "idle_gaps": ranked(gaps)}
