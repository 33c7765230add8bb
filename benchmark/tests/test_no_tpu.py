"""Without a TPU the command exits non-zero and prints no result; so it
does in a directory that holds only BENCHMARK.json and the benchmark."""

import json
import os
import shutil
import subprocess
import sys

from conftest import ROOT


def _run(root, env):
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "rbac1m.batch_closed", "--seed", str(2**33 + 1), "--seconds", "1",
         "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300,
    )


def _no_result(proc):
    for line in proc.stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert not (isinstance(obj, dict) and "correct" in obj), line


def test_cpu_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = _run(ROOT, env)
    assert proc.returncode != 0
    _no_result(proc)
    assert "TPU" in proc.stderr


def test_benchmark_alone_exits_nonzero_without_result(tmp_path):
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(tmp_path, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", ".jax-cache"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = _run(str(tmp_path), env)
    assert proc.returncode != 0
    _no_result(proc)
