"""The system under test: a Registry booted as ``keto serve`` boots it,
serving a store loaded with the generated graph, in this process.

Both planes listen on free loopback ports; the Registry's event loop runs
on a thread of its own so the harness thread stays free.
"""

from __future__ import annotations

import asyncio
import threading
import time

from .graph import Graph


def registry_config(config: dict, cache_dir: str) -> dict:
    """The Registry's config values: the configuration's engine settings
    over the served defaults, on free loopback ports."""
    engine = dict(config["engine"])
    engine["compile_cache_dir"] = cache_dir
    return {
        "dsn": config["dsn"],
        "namespaces": [
            {"id": i + 1, "name": ns}
            for i, ns in enumerate(config["namespaces"])
        ],
        "log": {"level": "error"},
        "serve": {
            "read": {
                "port": 0,
                "host": "127.0.0.1",
                "max-depth": int(config["max_depth"]),
                **config["serve"]["read"],
            },
            "write": {"port": 0, "host": "127.0.0.1"},
        },
        "engine": engine,
    }


class CompileEvents:
    """Counts JAX compile requests and persistent-cache hits and misses."""

    def __init__(self) -> None:
        import jax

        self.requests = self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class Server:
    """Registry over ``graph``: load the store, then ``start_all``."""

    def __init__(self, config: dict, graph: Graph, cache_dir: str,
                 log) -> None:
        from keto_tpu.driver.config import Config
        from keto_tpu.driver.registry import Registry

        self.registry = Registry(
            Config(values=registry_config(config, cache_dir), env={})
        )
        t0 = time.perf_counter()
        used = graph.keys(graph.src), graph.keys(graph.dst)
        t1 = time.perf_counter()
        self.registry.store().bulk_load_edges(*used)
        t2 = time.perf_counter()
        log(phase="load", tuples=len(self.registry.store()),
            keys_s=t1 - t0, bulk_load_s=t2 - t1)
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(
            target=self.loop.run_forever, name="registry-loop", daemon=True
        )
        self.thread.start()
        t0 = time.perf_counter()
        fut = asyncio.run_coroutine_threadsafe(
            self.registry.start_all(), self.loop
        )
        self.read_port, self.write_port = fut.result(timeout=900)
        self.boot_s = time.perf_counter() - t0

    @property
    def engine(self):
        return self.registry.check_engine()

    def stop(self) -> None:
        asyncio.run_coroutine_threadsafe(
            self.registry.stop_all(), self.loop
        ).result(timeout=120)
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(timeout=30)
        self.loop.close()
