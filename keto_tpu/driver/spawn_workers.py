"""Spawned read-worker pool: SQL-backed scale-out, reference-style.

The fork pool (`replicas.py`) shares multi-GB in-memory residency
copy-on-write — the right shape for process-private stores. SQL-backed
stores are the opposite case: the DATABASE is the shared state (the
reference's scale-out model is exactly "stateless replicas behind a LB
sharing one SQL database", internal/driver/daemon.go:62-85), and forking
is actively wrong there — replicas re-applying deltas over fork-inherited
connections would double-commit, and fork-after-threads is a deadlock
lottery Python now warns about. So SQL stores scale out by SPAWNING fresh
worker processes instead:

- each worker is a clean interpreter (no inherited threads, locks, or
  connections) that builds its own registry from a serialized config and
  opens its own database connection;
- all workers bind the same read ports with SO_REUSEPORT (the kernel
  balances connections), exactly like the fork pool;
- freshness needs no delta stream: the closure engine re-checks
  ``store.version`` per batch and rebuilds via its bounded-staleness
  machinery — the database IS the coordination point, as in the
  reference.

The parent keeps the write plane and serves reads as worker 0.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Optional


class SpawnWorkerPool:
    """Spawns ``n_workers - 1`` fresh worker processes (parent is worker 0)."""

    def __init__(self, registry, n_workers: int):
        self.registry = registry
        self.n_workers = n_workers
        self._procs: list[subprocess.Popen] = []

    def start(self, read_port: int, grpc_port: int, http_port: int) -> None:
        cfg = self.registry.config
        # flag overrides outrank env AND file values in Config.get, so
        # they pin the worker-critical keys no matter how the operator
        # set the rest (env-derived settings like KETO_DSN flow through
        # the worker's own environment untouched):
        # - workers=1: a worker must not recursively spawn its own pool;
        # - query_mode=host (unless opted out): the parent/accelerator
        #   runtime holds the chip exclusively, so a worker initializing
        #   the TPU backend would fail or hang; database-backed datasets
        #   a spawn pool serves build their closures fine on host/CPU.
        #   KETO_WORKER_ALLOW_ACCEL=1 opts out on multi-chip hosts.
        allow_accel = os.environ.get("KETO_WORKER_ALLOW_ACCEL") == "1"
        overrides = dict(cfg._overrides)
        overrides["serve.read.workers"] = 1
        if not allow_accel:
            overrides["engine.query_mode"] = "host"
        spec = {
            "config": cfg._data,
            "overrides": overrides,
            "ports": [read_port, grpc_port, http_port],
        }
        if allow_accel:
            env = dict(os.environ)
        else:
            from ..utils.jaxenv import cpu_env

            env = cpu_env()
        env["KETO_WORKER_SPEC"] = json.dumps(spec)
        for _ in range(1, self.n_workers):
            self._procs.append(
                subprocess.Popen(
                    [sys.executable, "-m", "keto_tpu.driver.worker"],
                    env=env,
                )
            )

    def alive(self) -> int:
        return 1 + sum(1 for p in self._procs if p.poll() is None)

    def wait_ready(self, timeout_s: float = 60.0) -> bool:
        """Best-effort wait until every worker process is up (still
        running after its boot window); readiness is also observable via
        each worker's own health service."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if all(p.poll() is None for p in self._procs):
                return True
            time.sleep(0.1)
        return all(p.poll() is None for p in self._procs)

    def stop(self, timeout_s: float = 10.0) -> None:
        for p in self._procs:
            if p.poll() is None:
                p.terminate()
        deadline = time.monotonic() + timeout_s
        for p in self._procs:
            remaining = max(0.1, deadline - time.monotonic())
            try:
                p.wait(timeout=remaining)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=5)
        self._procs.clear()
