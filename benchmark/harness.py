"""One run of one cell: generate, load, boot, warm up, drive the window,
read the numbers, then compare the window's answers with the reference.

Progress goes to standard error as JSON lines, one per phase; the result
line is the object :func:`run_cell` returns.
"""

from __future__ import annotations

import gc
import json
import os
import pickle
import subprocess
import sys
import tempfile
import time

import numpy as np

from . import load as load_mod
from .manifest import Cell

#: sampled check answers compared with the reference per run
VERIFY_CHECKS = 1024
#: the compile cache, at a fixed path inside the checkout
CACHE_DIR = os.path.join("benchmark", ".jax-cache")


def log(**fields) -> None:
    print(json.dumps(fields, default=str), file=sys.stderr, flush=True)


def process_start() -> float:
    """This process's start as a ``time.monotonic`` instant."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    age = time.clock_gettime(time.CLOCK_BOOTTIME) - ticks / os.sysconf(
        "SC_CLK_TCK")
    return time.monotonic() - age


def streams(seed: int):
    """Independent generators for the graph, the traffic, the warm-up
    and the verification sample, all from ``seed``."""
    return [np.random.default_rng(s)
            for s in np.random.SeedSequence(seed).spawn(3)]


class Clients:
    """Load-client processes, one per spec, off the chip."""

    def __init__(self, root: str, loop: str, specs: list, common: dict):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        self.procs = []
        for spec in specs:
            p = subprocess.Popen(
                [sys.executable, os.path.join(root, "benchmark", "client.py")],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
                cwd=root,
            )
            pickle.dump({**spec, **common, "root": root, "loop": loop},
                        p.stdin, protocol=pickle.HIGHEST_PROTOCOL)
            p.stdin.flush()
            self.procs.append(p)

    def ready(self) -> list:
        return [pickle.load(p.stdout) for p in self.procs]

    def go(self, t0: float) -> None:
        for p in self.procs:
            pickle.dump({"t0": t0}, p.stdin)
            p.stdin.flush()

    def results(self, timeout: float) -> list:
        out = []
        for p in self.procs:
            out.append(pickle.load(p.stdout)["result"])
        for p in self.procs:
            p.wait(timeout=timeout)
        return out

    def close(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.kill()
            p.wait()


def attribution_seconds(registry) -> dict:
    snap = registry.attribution().snapshot()
    return {k: v["seconds"] for k, v in snap.get("stages", {}).items()}


def memory_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


class CompileLog:
    """Names of the programs JAX compiles while it is installed (JAX's
    own compile log, captured instead of printed)."""

    def __init__(self) -> None:
        import logging

        self.names: list = []
        self._handler = logging.Handler()
        self._handler.emit = self._emit
        self._logger = logging.getLogger("jax")

    def _emit(self, record) -> None:
        msg = record.getMessage()
        if msg.startswith(("Compiling ", "Finished XLA compilation")):
            self.names.append(msg.split(" for ")[0][:200])

    def __enter__(self):
        import jax

        self._logger.addHandler(self._handler)
        jax.config.update("jax_log_compiles", True)
        return self

    def __exit__(self, *exc) -> None:
        import jax

        jax.config.update("jax_log_compiles", False)
        self._logger.removeHandler(self._handler)


class Window:
    """The measured part of a run, on a booted server."""

    def __init__(self, cell: Cell, server, plan: dict, seconds: float):
        self.cell = cell
        self.server = server
        self.plan = plan
        self.seconds = seconds

    def drive(self, compiles, trace_dir: str | None):
        loop = self.cell.loop
        t_pre = time.monotonic()
        prewarm = getattr(loop, "prewarm", None)
        self.prewarmed = (prewarm(self.plan, self.server.read_port)
                          if prewarm else 0)
        self.prewarm_s = time.monotonic() - t_pre
        clients = Clients(self.cell.root, self.cell.traffic["loop"],
                          self.plan["specs"], {
                              "read_port": self.server.read_port,
                              "write_port": self.server.write_port,
                              "seconds": self.seconds,
                          })
        try:
            ready = clients.ready()
            self.warm_compiles = compiles.requests
            before = attribution_seconds(self.server.registry)
            if trace_dir is not None:
                import jax

                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
            t0 = time.monotonic() + 0.25
            with CompileLog() as clog:
                clients.go(t0)
                self.t0 = t0
                time.sleep(max(0.0, t0 + self.seconds - time.monotonic()))
                if trace_dir is not None:
                    jax.profiler.stop_trace()
                self.trace_window_s = time.monotonic() - t0
                self.results = clients.results(
                    timeout=load_mod.ANSWER_WAIT_S + 30)
            self.window_compiles = compiles.requests - self.warm_compiles
            self.compiled = clog.names
            after = attribution_seconds(self.server.registry)
        finally:
            clients.close()
        self.attribution = {k: after.get(k, 0.0) - before.get(k, 0.0)
                            for k in after}
        self.ready = ready
        return loop.summarize(self.plan, self.results, self.t0, self.seconds)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float) -> dict:
    """One run; returns the result line's object."""
    import jax

    import keto_tpu  # noqa: F401 - the system under test; absent, stop here

    from .readers import Context
    from .reference import Reference
    from .server import CompileEvents, Server
    from . import kernels
    from . import trace as trace_mod

    devices = jax.devices()[: cell.chips]
    dev = devices[0]
    peak = kernels.peaks(dev.device_kind) if dev.platform == "tpu" else {}
    cache_dir = os.path.join(cell.root, CACHE_DIR)
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    compiles = CompileEvents()
    log(phase="device", platform=dev.platform, kind=dev.device_kind,
        count=len(devices), cache_dir=cache_dir,
        cache_entries=sum(len(f) for _, _, f in os.walk(cache_dir)))

    g_rng, t_rng, v_rng = streams(seed)
    t0 = time.monotonic()
    graph = cell.generator.build(cell.config["sizes"], g_rng)
    log(phase="generate", config=cell.config["name"], seed=seed,
        tuples=len(graph.src), nodes=graph.n_nodes,
        seconds=time.monotonic() - t0)
    t0 = time.monotonic()
    plan = cell.loop.plan(cell, graph, t_rng, seconds)
    log(phase="plan", traffic=cell.traffic["loop"], clients=len(plan["specs"]),
        seconds=time.monotonic() - t0)

    server = Server(cell.config, graph, cache_dir, log)
    eng = server.engine
    build = dict(getattr(eng, "last_build_phases", {}) or {})
    log(phase="boot", seconds=server.boot_s, build_phases=build,
        build_kernel=getattr(eng, "last_full_build_kernel", ""),
        compile_requests=compiles.requests, cache_hits=compiles.hits,
        cache_misses=compiles.misses)

    window = Window(cell, server, plan, seconds)
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
        try:
            outcome = window.drive(compiles, tdir if trace else None)
            setup_s = window.t0 - t_start
            log(phase="warmup", clients=window.ready,
                shape_calls=window.prewarmed, shape_s=window.prewarm_s,
                compile_requests=window.warm_compiles,
                cache_hits=compiles.hits, cache_misses=compiles.misses)
            for line in outcome["lines"]:
                log(**line)
            log(phase="window", seconds=seconds,
                compiles_in_window=window.window_compiles,
                compiled=window.compiled,
                attribution_s=window.attribution)
            mem_peak = memory_peak_bytes(devices)
            placement = {
                "device_rows": getattr(eng, "n_device_rows", None),
                "oracle_rows": getattr(eng, "n_oracle_rows", None),
                "host_queries": bool(eng.host_queries())
                if hasattr(eng, "host_queries") else None,
            }
            log(phase="placement", **placement)
            tr = None
            if trace:
                t1 = time.monotonic()
                tr = trace_mod.load(trace_mod.find_xplane(tdir),
                                    window_s=window.trace_window_s)
                log(phase="trace", read_s=time.monotonic() - t1,
                    window_s=tr.window_s, busy_s=tr.busy_s(),
                    ops=sum(len(v) for v in tr.devices.values()))
        finally:
            server.stop()
    del server, eng
    gc.collect()

    t0 = time.monotonic()
    reference = Reference(graph, int(cell.config["max_depth"]))
    compared = cell.loop.verify(plan, window.results, reference, v_rng,
                                VERIFY_CHECKS)
    compared["unanswered"] = (outcome["unanswered"], 0, outcome["attempted"])
    log(phase="reference", seconds=time.monotonic() - t0)
    correct = all(v <= lim for v, lim, _ in compared.values())

    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    metrics = {}
    result_device = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(devices), "memory_peak_bytes": mem_peak}
    out = {"correct": correct, "attempted": outcome["attempted"],
           "failed": outcome["failed"]}
    if not trace:
        values = {"setup_s": setup_s, **outcome["metrics"]}
        for m in cell.end_to_end:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = values[m["name"]]
    else:
        ctx = Context(
            window_s=seconds,
            checks=outcome["checks"],
            attribution=window.attribution,
            build_phases=build,
            peak=peak,
            trace=tr,
        )
        for m in cell.per_layer:
            v = cell.reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = float(v)
        result_device["busy_s"] = tr.busy_s()
        result_device["window_s"] = tr.window_s
        out["breakdown"] = {"device_ops": [list(x) for x in tr.top_ops(10)],
                            "idle_gaps": tr.idle_gaps(10)}
    out["metrics"] = {k: {"value": float(v), "unit": units[k]}
                      for k, v in metrics.items()}
    out["device"] = result_device
    out["compared"] = {k: {"value": v, "limit": lim, "of": n}
                       for k, (v, lim, n) in compared.items()}
    for k, (v, lim, n) in compared.items():
        print(f"compared {k}: {v} (limit {lim}, of {n})", file=sys.stderr,
              flush=True)
    return out
