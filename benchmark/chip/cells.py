"""Where the benchmark's data lives, found by the names in BENCHMARK.json.

    configs/<config>.json         sizes of a model, its optimizer, precision
    traffic/<traffic>.json        one traffic mix: parameters of the generator
    limits/<workload>.json        the limits `correct` holds a cell to
    layer_metrics/<metric>.json   layer, unit, moves, reader and its parameters
    readers/<reader>.py           read(view, params) -> number or None
    programs|flops|reference/<family>.py

A later PR adds files and entries and edits none: nothing in the harness
names a cell, a configuration, a mix or a metric. `root` is the benchmark's
directory; data files are looked up there, code there first and then beside
this file, so that a test can lay a toy cell out in a temporary directory.
"""
from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def load_json(path):
    with open(path) as f:
        return json.load(f)


_MODULES = {}


def load_module(kind, name, root=HERE):
    """The module `<kind>/<name>.py`, from `root` or from beside this file
    (loaded once in a process)."""
    for base in (root, HERE):
        path = os.path.join(base, kind, name + ".py")
        if path in _MODULES:
            return _MODULES[path]
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                f"chipbench_{kind}_{name}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            _MODULES[path] = mod
            return mod
    raise FileNotFoundError(f"no {kind}/{name}.py under {root} or {HERE}")


class Cell:
    """One entry of `workloads`, with everything its name leads to."""

    def __init__(self, workload, bench_json=None, root=HERE):
        self.root = root
        self.bench = load_json(bench_json
                               or os.path.join(REPO, "BENCHMARK.json"))
        entries = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in entries:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                           f"it has {sorted(entries)}")
        self.entry = entries[workload]
        self.name = workload
        self.chips = self.entry["chips"]
        self.config = load_json(os.path.join(
            root, "configs", self.entry["config"] + ".json"))
        self.traffic = load_json(os.path.join(
            root, "traffic", self.entry["traffic"] + ".json"))
        held = load_json(os.path.join(root, "limits", workload + ".json"))
        self.limits = held["limits"]
        self.reference = held.get("reference", {})
        self.family = self.config["family"]
        mesh = self.traffic["mesh"]
        n = 1
        for v in mesh.values():
            n *= v
        if n != self.chips:
            raise ValueError(f"{workload}: mesh {mesh} is {n} devices, the "
                             f"cell asks for {self.chips} chips")

    def module(self, kind):
        return load_module(kind, self.family, self.root)

    def reports(self, metric):
        """Whether this cell is among the metric's cells (all, where the
        metric names none)."""
        cells = metric.get("workloads")
        return cells is None or self.name in cells

    def metrics(self, group):
        """The cell's metrics of `end_to_end` or `per_layer`, as entries."""
        return [m for m in self.bench[group] if self.reports(m)]

    def layer_metric(self, name):
        return load_json(os.path.join(self.root, "layer_metrics",
                                      name + ".json"))

    def items_per_step(self):
        t = self.traffic
        return t["batch"] * (t["seq"] if t["items"] == "tokens" else 1)


def peaks(device_kind, root=HERE):
    """Peaks of one chip by `device_kind`; an unknown device is an error."""
    table = load_json(os.path.join(root, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (it has {sorted(table)})")
    return table[device_kind]
