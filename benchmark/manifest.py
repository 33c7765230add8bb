"""``BENCHMARK.json`` and the files it names, found by name.

- a cell's configuration: ``configs[name].file``, whose ``generator`` is
  ``benchmark/generators/<generator>.py``;
- its traffic: ``benchmark/traffic/<traffic>.json``, whose ``loop`` is
  ``benchmark/loops/<loop>.py``;
- each per-layer metric's reader: ``benchmark/metrics/<name>.py``.

A later cell, mix or metric is new files plus new entries in
``BENCHMARK.json``; nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import sys
from dataclasses import dataclass

PACKAGE = "benchmark"


def load_module(root: str, kind: str, name: str):
    """``<root>/benchmark/<kind>/<name>.py`` as a module of this package
    (its relative imports resolve against the package)."""
    path = os.path.join(root, PACKAGE, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r}: {path}")
    mod_name = f"{PACKAGE}.{kind}.{re.sub(r'[^0-9A-Za-z_]', '_', name)}"
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = module
    spec.loader.exec_module(module)
    return module


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    root: str
    name: str
    chips: int
    config: dict  # the configuration file, as run
    traffic: dict  # the traffic file
    end_to_end: list  # manifest entries reported with --trace 0
    per_layer: list  # manifest entries reported with --trace 1

    @property
    def loop(self):
        return load_module(self.root, "loops", self.traffic["loop"])

    @property
    def generator(self):
        return load_module(self.root, "generators", self.config["generator"])

    def reader(self, metric: str):
        return load_module(self.root, "metrics", metric)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(root: str, name: str) -> Cell:
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_json(
        os.path.join(root, PACKAGE, "traffic", w["traffic"] + ".json")
    )
    return Cell(
        root=root,
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=[m for m in manifest["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in manifest["per_layer"] if _applies(m, name)],
    )
