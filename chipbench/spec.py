"""Name lookup: the cells, configurations, traffic mixes, metric readers,
kernel cost functions and correctness limits the harness runs.

Every piece is a file found by the name ``BENCHMARK.json`` gives it:

* configuration ``<c>``: the ``file`` of its ``configs`` entry (JSON), whose
  ``model`` key names the plain reference ``chipbench/models/<model>.py``;
* traffic mix ``<t>``: ``chipbench/traffic/<t>.json``;
* per-layer metric ``<m>``: ``chipbench/metrics/<m>.py`` (``read(run)``);
* kernel ``<k>``: ``chipbench/kernels/<k>.py`` (``cost(arch, call)``);
* the limits of cell ``<w>``: ``chipbench/limits/<w>.json``.

A later change adds a cell, configuration, mix, metric or kernel by adding
files and entries; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(ROOT, "chipbench")


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import the Python file at ``path`` under a name made from it."""
    name = "chipbench_file_" + re.sub(r"\W", "_", os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.isfile(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Benchmark:
    """``BENCHMARK.json`` and the files its names lead to.

    ``root`` is the checkout the relative paths start from; ``doc`` the
    parsed ``BENCHMARK.json``; ``data_dir`` holds ``traffic/`` and
    ``limits/`` (tests pass their own of both)."""

    def __init__(self, root: str = ROOT, doc: dict | None = None,
                 data_dir: str = PKG):
        self.root = root
        self.data_dir = data_dir
        self.doc = doc if doc is not None else read_json(
            os.path.join(root, "BENCHMARK.json"))
        self.cells = {w["name"]: w for w in self.doc["workloads"]}
        self.configs = {c["name"]: c for c in self.doc["configs"]}

    def cell(self, name: str) -> dict:
        if name not in self.cells:
            raise KeyError(f"unknown workload {name!r}; known: "
                           f"{sorted(self.cells)}")
        return self.cells[name]

    def config(self, cell: dict) -> dict:
        entry = self.configs[cell["config"]]
        cfg = read_json(os.path.join(self.root, entry["file"]))
        cfg["name"] = entry["name"]
        return cfg

    def traffic(self, cell: dict) -> dict:
        mix = read_json(os.path.join(self.data_dir, "traffic",
                                     cell["traffic"] + ".json"))
        mix["name"] = cell["traffic"]
        return mix

    def limits(self, cell: dict) -> dict:
        return read_json(os.path.join(self.data_dir, "limits",
                                     cell["name"] + ".json"))

    def metrics(self, cell: dict, kind: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
        return [m for m in self.doc[kind]
                if cell["name"] in m.get("workloads", [cell["name"]])]


def model_module(name: str):
    return load_module(os.path.join(PKG, "models", name + ".py"))


def metric_reader(name: str):
    return load_module(os.path.join(PKG, "metrics", name + ".py"))


def kernel_cost(name: str):
    return load_module(os.path.join(PKG, "kernels", name + ".py"))
