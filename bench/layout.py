"""Find a cell's files by name.

``BENCHMARK.json`` at the checkout's root names every cell, configuration and
metric. Each has files of its own, found from its name alone:

- a configuration: the ``file`` its entry gives
  (``bench/configs/<name>.json``), and the model module its file names
  under ``"reference"`` (``bench/models/<name>.py``; without the key, the
  model part of ``reference.py``);
- a traffic mix: ``bench/traffic/<traffic>.json``;
- a cell's correctness limits: ``bench/limits/<cell>.json``;
- a metric: its reader, ``bench/metrics/<metric>.py``, which defines
  ``read(run) -> float | None``.

Adding a cell, a configuration or a metric adds files and entries; no file
that is already there changes.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Layout:
    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        self.bench_dir = os.path.join(self.root, "bench")
        self.spec = _json(os.path.join(self.root, "BENCHMARK.json"))

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return _json(os.path.join(self.root, c["file"]))
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return _json(os.path.join(self.bench_dir, "traffic", f"{name}.json"))

    def limits(self, cell: str) -> dict:
        return _json(os.path.join(self.bench_dir, "limits", f"{cell}.json"))

    def metrics_for(self, cell: str, kind: str) -> list:
        """The ``end_to_end`` or ``per_layer`` metrics ``cell`` reports."""
        names = {m["name"] for m in self.spec["end_to_end"]
                 if cell in m.get("workloads", [cell])}
        out = []
        for m in self.spec[kind]:
            if "workloads" in m:
                if cell in m["workloads"]:
                    out.append(m)
            elif kind == "end_to_end" or m["moves"] in names:
                out.append(m)
        return out

    def model(self, cfg: dict):
        """The model module of a configuration (``reference.py``'s module
        contract): the file its ``reference`` key names, relative to the
        checkout's root, else ``reference`` itself."""
        if "reference" not in cfg:
            import reference
            return reference
        return _load("bench_model_", os.path.join(self.root,
                                                  cfg["reference"]))

    def reader(self, metric: str):
        return _load("bench_metric_", os.path.join(
            self.bench_dir, "metrics", f"{metric}.py")).read


def _load(prefix: str, path: str):
    """The module of the Python file at ``path``, loaded afresh."""
    name = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(
        prefix + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
