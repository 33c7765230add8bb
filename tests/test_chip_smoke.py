"""chip_smoke.py rehearsed on the CPU: every phase runs and agrees with
the reference at a tiny size, and the script refuses to pass without a
TPU — a CPU run is never reported as a chip run."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def rbac_small():
    return chip_smoke.make_store(20_000, seed=3)


def test_served_path_matches_reference(rbac_small):
    store, sample = rbac_small
    report = chip_smoke.drive_served_path(
        store, sample, seed=3, batch=256, crosscheck=8,
        engine={"query_mode": "device"},
    )
    assert report["mismatches"] == 0
    assert report["answers"] >= 256 + len(chip_smoke.CAT_VIDEOS)
    assert report["checkengine_crosscheck"] == 10
    assert report["oracle_batches"] == 0 and report["failovers"] == 0
    assert report["device_rows"] > 0 and not report["host_queries"]
    # on the CPU the closure is built by the lax step: the smoke's
    # device-work gate must refuse it
    assert report["build_kernel"] == "lax"
    with pytest.raises(chip_smoke.SmokeFailure, match="'lax'"):
        chip_smoke.require_device_work(report)


def test_sharded_path_spans_every_device(rbac_small):
    import jax

    store, sample = rbac_small
    n = len(jax.devices())
    report = chip_smoke.drive_sharded_path(
        store, sample, seed=3, batch=256, n_devices=n
    )
    assert report["mismatches"] == 0
    assert report["array_device_spans"] == [n] * 8


@pytest.mark.parametrize(
    "argv",
    [["chip_smoke.py"], ["chip_smoke.py", "--chips", "4"],
     ["bench.py", "--smoke"]],
)
def test_refuses_without_tpu(tmp_path, argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, argv[0]), *argv[1:]],
        env=env, cwd=tmp_path, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""  # no result line of any kind
    assert "TPU" in proc.stderr


def test_reference_matches_check_engine_on_random_graphs():
    from keto_tpu.engine import CheckEngine
    from keto_tpu.relationtuple import RelationTuple
    from keto_tpu.store import ColumnarTupleStore

    from conftest import random_store

    for seed in range(3):
        mem = random_store(np.random.default_rng(seed), 20, 10, 200)
        store = ColumnarTupleStore()
        store.write_relation_tuples(*mem.all_tuples())
        ref = chip_smoke.ReachabilityReference(store, 5)
        oracle = CheckEngine(store, max_depth=5)
        reqs = mem.all_tuples()[:40] + [
            RelationTuple.from_string(f"miss:o{i}#r@nobody{i}")
            for i in range(5)
        ]
        for depth in (1, 2, 5):
            assert [ref.check(r, depth) for r in reqs] == [
                oracle.subject_is_allowed(r, depth) for r in reqs
            ], (seed, depth)
