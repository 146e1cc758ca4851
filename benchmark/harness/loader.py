"""Finds a cell's files by the names in BENCHMARK.json.

Everything that belongs to one configuration, one traffic mix, one statement or one
per-layer metric is a file of its own under ``benchmark/``; a later PR adds files and
``BENCHMARK.json`` entries and edits nothing that is there.
"""

import importlib.util
import json
import os
import re

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class BenchmarkError(Exception):
    """The benchmark's own files are wrong: a bad name, a bad unit, a missing file."""


def check_name(name, what):
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise BenchmarkError(f"bad {what} name {name!r}: letters, digits, '_', '.', '-' "
                             "(at most 64, not starting with '.' or '-')")
    return name


def check_unit(unit, what):
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise BenchmarkError(f"bad unit {unit!r} of {what}: 1 to 16 of letters, digits, "
                             "'_', '/', '%', '.', '-'")
    return unit


def _read_json(path):
    if not os.path.isfile(path):
        raise BenchmarkError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


def _load_module(path, name):
    if not os.path.isfile(path):
        raise BenchmarkError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location("benchmark_file_" + re.sub(r"\W", "_", name), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell:
    """One entry of ``workloads`` with everything it names, loaded and checked."""

    def __init__(self, workload, root=ROOT):
        bench = _read_json(os.path.join(root, "BENCHMARK.json"))
        self.root = root
        self.bench_dir = os.path.join(root, bench["paths"][0])
        self.run_seconds = bench["run_seconds"]
        entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
        if entry is None:
            raise BenchmarkError(f"no workload {workload!r} in BENCHMARK.json: "
                                 f"{[w['name'] for w in bench['workloads']]}")
        self.name = check_name(entry["name"], "workload")
        self.chips = entry["chips"]
        config = next((c for c in bench["configs"] if c["name"] == entry["config"]), None)
        if config is None:
            raise BenchmarkError(f"workload {workload!r} names no config of BENCHMARK.json: "
                                 f"{entry['config']!r}")
        check_name(config["name"], "config")
        self.config = _read_json(os.path.join(root, config["file"]))
        self.config["name"] = config["name"]
        # what the harness builds the deployment from (run.Bench); a file that leaves a
        # key out describes one TpchConnector under the catalog "tpch" on one chip
        self.config.setdefault("connector", "tpch")
        self.config.setdefault("catalog", self.config["connector"])
        if self.config.setdefault("chips", 1) != self.chips:
            raise BenchmarkError(
                f"workload {workload!r} asks for {self.chips} chips, its configuration "
                f"{config['name']!r} describes a deployment on {self.config['chips']}")
        check_name(entry["traffic"], "traffic")
        self.traffic = _read_json(os.path.join(self.bench_dir, "traffic", entry["traffic"] + ".json"))
        self.traffic["name"] = entry["traffic"]
        self.traffic.setdefault("statement_timeout_s", 300)
        self.statements = {}
        for slot in self.traffic["slots"]:
            name = check_name(slot, "statement")
            if name not in self.statements:
                self.statements[name] = _load_module(
                    os.path.join(self.bench_dir, "statements", name + ".py"), name)
        self.end_to_end = self._metrics(bench["end_to_end"], "end_to_end")
        self.per_layer = self._metrics(bench["per_layer"], "layer_metrics")
        if not any(m["name"] == "setup_s" for m in self.end_to_end):
            raise BenchmarkError(f"workload {workload!r} reports no setup_s")
        self.peaks = _read_json(os.path.join(self.bench_dir, "peaks.json"))

    def _metrics(self, entries, directory):
        """The metrics this cell reports (those with no ``workloads`` key, or that list
        it), each with the ``read(ctx)`` of its own file under ``directory``."""
        out = []
        for m in entries:
            check_name(m["name"], "metric")
            check_unit(m["unit"], m["name"])
            if m["better"] not in ("lower", "higher"):
                raise BenchmarkError(f"metric {m['name']}: better is {m['better']!r}")
            if "workloads" not in m or self.name in m["workloads"]:
                path = os.path.join(self.bench_dir, directory, m["name"] + ".py")
                out.append(dict(m, read=_load_module(path, m["name"]).read))
        return out

    def peak(self, device_kind):
        if device_kind not in self.peaks:
            raise BenchmarkError(f"no peaks for device kind {device_kind!r} in peaks.json "
                                 f"(has {sorted(self.peaks)}): add it with its source")
        return self.peaks[device_kind]
