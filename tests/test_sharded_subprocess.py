"""Drives the multi-device sharding tests under an 8-device virtual CPU
mesh in a fresh interpreter whose env is set at interpreter start, the way
an operator runs them — independent of the env conftest.py sets in-process."""

import os
import subprocess
import sys

import pytest

from keto_tpu.utils.jaxenv import cpu_env


def _run_on_virtual_mesh(test_file: str) -> None:
    env = cpu_env(8)
    proc = subprocess.run(
        [
            sys.executable,
            "-m",
            "pytest",
            os.path.join(os.path.dirname(__file__), test_file),
            "-q",
            "--no-header",
        ],
        env=env,
        capture_output=True,
        text=True,
        timeout=570,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, (
        f"{test_file} failed\nstdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )
    assert "skipped" not in proc.stdout.lower() or "passed" in proc.stdout


@pytest.mark.timeout(600)
def test_multichip_suite_on_virtual_mesh():
    _run_on_virtual_mesh("test_multichip_sharded.py")


@pytest.mark.timeout(600)
def test_sharded_serving_suite_on_virtual_mesh():
    _run_on_virtual_mesh("test_sharded_serving.py")
