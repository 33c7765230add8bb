"""Shared helpers for the benchmark's own tests: tiny cells on the CPU.

Run with ``python -m pytest benchmark/tests``. The benchmark runs only on
a TPU; these tests drive its pieces (and, through ``run_cell``, whole
runs) on the CPU at sizes a test can hold.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.manifest import Cell, load_json  # noqa: E402

SCALED = ("tuples", "users", "groups", "roles", "resources", "teams",
          "repos")


def cell(name: str) -> Cell:
    """``<config>.<traffic>`` from the files, whether or not
    BENCHMARK.json names it as a cell (its metrics, where it does)."""
    config, traffic = name.split(".", 1)
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    files = {c["name"]: c["file"] for c in manifest["configs"]}

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(
        root=ROOT, name=name, chips=1,
        config=load_json(os.path.join(ROOT, files[config])),
        traffic=load_json(os.path.join(ROOT, "benchmark", "traffic",
                                       traffic + ".json")),
        end_to_end=[m for m in manifest["end_to_end"] if applies(m)],
        per_layer=[m for m in manifest["per_layer"] if applies(m)],
    )


def tiny_cell(name: str, scale: float = 0.002, **traffic):
    """``cell(name)`` with every scale key of its configuration cut to
    ``scale`` (widths and mixes kept), batches of at most 64 rows, and a
    light traffic mix."""
    c = cell(name)
    sizes = c.config["sizes"]
    for k in SCALED:
        if k in sizes:
            sizes[k] = max(int(sizes[k] * scale), 20)
    c.config["engine"]["max_batch"] = 64
    t = c.traffic
    t.update(pool_pairs=4096, rows_per_call=16, requests_per_client=64,
             warmup_requests_per_client=8, warmup_s=0.5)
    t.update(traffic)
    return c
