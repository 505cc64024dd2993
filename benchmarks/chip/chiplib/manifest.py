"""BENCHMARK.json and the files it names. Everything that belongs to one
configuration, one traffic mix, one cell's limits or one per-layer metric
is a file found by its name; nothing here lists them."""
from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _json(path):
    with open(path) as f:
        return json.load(f)


def _module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Files:
    """Where a run finds its files. The default is the repository's own
    BENCHMARK.json and this directory; a test passes its own."""

    def __init__(self, root=ROOT, data=HERE, manifest=None):
        self.root, self.data, self._manifest = root, data, manifest

    def load(self):
        if self._manifest is not None:
            return self._manifest
        path = os.path.join(self.root, "BENCHMARK.json")
        if not os.path.exists(path):
            raise SystemExit(f"no BENCHMARK.json at {self.root}")
        return _json(path)

    def config(self, manifest, name):
        for c in manifest["configs"]:
            if c["name"] == name:
                return _json(os.path.join(self.root, c["file"]))
        raise SystemExit(f"configuration {name!r} is not in BENCHMARK.json")

    def traffic(self, name):
        return _json(os.path.join(self.data, "traffic", name + ".json"))

    def limits(self, workload):
        """The cell's comparison limits: ``limits/<workload>.json``."""
        return _json(os.path.join(self.data, "limits", workload + ".json"))

    def path(self, kind, name):
        """``<kind>/<name>.py``: this data directory's own (a test brings
        its own), else the benchmark's."""
        for base in (self.data, HERE):
            path = os.path.join(base, kind, name + ".py")
            if os.path.exists(path):
                return path
        raise SystemExit(f"no {kind}/{name}.py under {self.data} or {HERE}")

    def arch(self, name):
        """``arch/<name>.py``: what the harness knows of one architecture
        (see ``arch/llama_dense.py`` for what it gives)."""
        return _module(self.path("arch", name),
                       "chip_arch_" + name.replace("-", "_"))

    def reference(self, name):
        """``reference/<name>.py``: a configuration's plain reference."""
        return _module(self.path("reference", name),
                       "chip_reference_" + name.replace("-", "_"))


def cell(manifest, name):
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"workload {name!r} is not in BENCHMARK.json "
                     f"({[w['name'] for w in manifest['workloads']]})")


def metric_reader(name):
    """``metrics/<name>.py`` with ``read(obs) -> number | None``."""
    path = os.path.join(HERE, "metrics", name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"metric {name!r} has no reader at {path}")
    return _module(path, "chip_metric_" + name.replace(".", "_")
                   .replace("-", "_")).read


def metrics_for(manifest, workload, group):
    """Names of the ``group`` ('end_to_end' | 'per_layer') metrics that
    this cell reports."""
    names = []
    e2e_here = {m["name"] for m in manifest["end_to_end"]
                if workload in m.get("workloads", [workload])}
    for m in manifest[group]:
        if "workloads" in m:
            if workload in m["workloads"]:
                names.append(m["name"])
        elif group == "end_to_end" or m["moves"] in e2e_here:
            names.append(m["name"])
    return names


def unit(manifest, name):
    for group in ("end_to_end", "per_layer"):
        for m in manifest[group]:
            if m["name"] == name:
                return m["unit"]
    raise KeyError(name)
