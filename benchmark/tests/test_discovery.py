"""A later cell, traffic mix and per-layer metric are new files plus new
entries in BENCHMARK.json: the harness finds them by name, and no file
that is already there changes."""

import hashlib
import json
import os
import shutil

from benchmark.manifest import find_cell, load_module

from conftest import ROOT


def _digests(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "benchmark")):
        if "__pycache__" in d or ".jax-cache" in d:
            continue
        for f in files:
            p = os.path.join(d, f)
            out[os.path.relpath(p, root)] = hashlib.sha256(
                open(p, "rb").read()).hexdigest()
    p = os.path.join(root, "BENCHMARK.json")
    out["BENCHMARK.json"] = hashlib.sha256(open(p, "rb").read()).hexdigest()
    return out


def test_new_cell_traffic_and_metric_from_new_files(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", ".jax-cache"))
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = _digests(root)

    # new files only
    traffic = json.load(open(os.path.join(
        root, "benchmark", "traffic", "batch_closed.json")))
    traffic["rows_per_call"] = 10
    with open(os.path.join(root, "benchmark", "traffic",
                           "batch_small.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(root, "benchmark", "metrics",
                           "checks_seen.closed.py"), "w") as f:
        f.write("def read(ctx):\n    return float(ctx.checks)\n")
    # new entries in the manifest
    manifest["workloads"].append({
        "name": "rbac1m.batch_small", "config": "rbac1m",
        "traffic": "batch_small", "chips": 1, "why": "small pages"})
    manifest["per_layer"].append({
        "name": "checks_seen.closed", "unit": "checks", "better": "higher",
        "source": "program_counter", "layer": "wire",
        "moves": "checks_per_s", "workloads": ["rbac1m.batch_small"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)

    after = _digests(root)
    changed = {k for k in before if before[k] != after.get(k)}
    assert changed == {"BENCHMARK.json"}

    cell = find_cell(root, "rbac1m.batch_small")
    assert cell.traffic["rows_per_call"] == 10
    assert cell.loop.__name__.endswith("closed_batch")
    assert cell.generator.__name__.endswith("rbac")
    names = [m["name"] for m in cell.per_layer]
    assert names == ["checks_seen.closed"]
    reader = load_module(root, "metrics", "checks_seen.closed")

    class Ctx:
        checks = 7

    assert reader.read(Ctx()) == 7.0


def test_every_manifest_name_resolves():
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in manifest["workloads"]:
        cell = find_cell(ROOT, w["name"])
        assert cell.loop and cell.generator
        for m in cell.per_layer:
            assert callable(cell.reader(m["name"]).read)
