"""Device telemetry: per-device HBM, jit compilation events, host<->device
transfer bytes, closure-query dispatches, and the graph panel.

One process-wide collector (``DEVSTATS``) because the tally points live
deep in the engine hot path (device-engine staging buffers, the closure
engine's query operands) where threading a registry handle through every
constructor would couple the engine layer to telemetry wiring. The
driver registry calls ``DEVSTATS.bind(metrics, graph_panel_fn=...)``
when it builds its MetricsRegistry; ``bind`` is re-entrant — tests build
many registries per process and each bind simply repoints the exported
counters/gauges at the newest one. Tallies (transfer bytes, compile
counts) accumulate for the life of the process, which is exactly what a
``_total`` counter wants. Per-stage check-pipeline seconds are host time
and live in ``keto_pipeline_stage_seconds`` (engine/batcher.py).

HBM gauges sample ``jax.local_devices()[i].memory_stats()`` at scrape
time; on CPU backends that returns ``None`` and the gauges read 0 —
degrade, don't crash, because tier-1 runs under JAX_PLATFORMS=cpu.
Compilation events come from ``jax.monitoring`` duration listeners when
that API exists (guarded — listeners cannot be unregistered, so exactly
one is installed per process and it writes through the singleton).
"""

from __future__ import annotations

import threading
import time
from typing import Optional

from .metrics import MetricsRegistry

# memory_stats() keys worth exporting, mapped to gauge name suffixes
_HBM_KEYS = (
    ("bytes_in_use", "keto_device_hbm_bytes_in_use",
     "HBM bytes currently allocated on the device"),
    ("bytes_limit", "keto_device_hbm_bytes_limit",
     "HBM allocation limit on the device"),
    ("peak_bytes_in_use", "keto_device_hbm_peak_bytes",
     "peak HBM bytes allocated on the device since process start"),
)

# graph-panel dict key -> (gauge name, help)
_PANEL_GAUGES = (
    ("tuples", "keto_graph_tuples",
     "relation tuples in the live store"),
    ("csr_nnz", "keto_graph_csr_nnz",
     "non-zeros (edges) in the snapshot CSR"),
    ("vocab_size", "keto_graph_vocab_size",
     "node vocabulary size of the live snapshot"),
    ("closure_age_s", "keto_graph_closure_age_seconds",
     "seconds since the serving closure artifact was built"),
    ("snapshot_version", "keto_graph_snapshot_version",
     "store version of the live graph snapshot"),
)


def _local_devices():
    try:
        import jax

        return jax.local_devices()
    except Exception:
        return []


class DeviceStatsCollector:
    def __init__(self):
        self._lock = threading.Lock()
        self._transfer_bytes = {"h2d": 0.0, "d2h": 0.0}
        self._dispatches = 0
        self._compiles = 0
        self._compile_seconds = 0.0
        self._graph_panel_fn = None
        self._listener_installed = False
        # metric handles from the most recent bind(); None before any
        self._c_transfer = None
        # direction -> the transfer counter's labeled child
        self._transfer_children: dict = {}
        self._c_dispatches = None
        self._c_compiles = None
        self._c_compile_s = None

    # -- wiring ---------------------------------------------------------------

    def bind(self, metrics: MetricsRegistry, graph_panel_fn=None) -> None:
        """Export this collector through ``metrics``. Re-entrant: each
        call repoints the exported series at the given registry."""
        if graph_panel_fn is not None:
            self._graph_panel_fn = graph_panel_fn
        self._c_transfer = metrics.counter(
            "keto_device_transfer_bytes_total",
            "host<->device bytes staged by the check engines",
            labelnames=("direction",),
        )
        self._transfer_children = {}
        self._c_dispatches = metrics.counter(
            "keto_device_query_dispatches_total",
            "closure-query programs launched on the device",
        )
        self._c_compiles = metrics.counter(
            "keto_device_jit_compilations_total",
            "jit compilation events observed via jax.monitoring",
        )
        self._c_compile_s = metrics.counter(
            "keto_device_compile_seconds_total",
            "cumulative wall seconds spent in jit compilation",
        )
        # replay the accumulated tallies into the fresh counters so a
        # rebind mid-process doesn't zero the totals
        with self._lock:
            for direction, nbytes in self._transfer_bytes.items():
                if nbytes:
                    self._c_transfer.labels(direction=direction).inc(nbytes)
            if self._dispatches:
                self._c_dispatches.inc(self._dispatches)
            if self._compiles:
                self._c_compiles.inc(self._compiles)
            if self._compile_seconds:
                self._c_compile_s.inc(self._compile_seconds)
        metrics.gauge(
            "keto_device_count",
            "devices visible to jax.local_devices()",
            fn=lambda: float(len(_local_devices())),
        )
        hbm_gauges = [
            metrics.gauge(name, help, labelnames=("device",))
            for _, name, help in _HBM_KEYS
        ]
        for i, dev in enumerate(_local_devices()):
            label = f"{getattr(dev, 'platform', 'dev')}:{getattr(dev, 'id', i)}"
            for (key, _, _), gauge in zip(_HBM_KEYS, hbm_gauges):
                gauge.labels(device=label).set_fn(
                    self._hbm_sampler(dev, key)
                )
        for key, name, help in _PANEL_GAUGES:
            metrics.gauge(name, help, fn=self._panel_sampler(key))
        self._install_jax_listener()

    @staticmethod
    def _hbm_sampler(dev, key):
        def sample():
            try:
                stats = dev.memory_stats()
            except Exception:
                stats = None
            if not stats:
                return 0.0
            return float(stats.get(key, 0))

        return sample

    def _panel_sampler(self, key):
        def sample():
            fn = self._graph_panel_fn
            if fn is None:
                return 0.0
            try:
                return float((fn() or {}).get(key) or 0)
            except Exception:
                return 0.0

        return sample

    def _install_jax_listener(self) -> None:
        if self._listener_installed:
            return
        try:
            from jax import monitoring
        except Exception:
            return

        def _on_duration(event: str, duration_s: float, **kw) -> None:
            if "compil" in event.lower():
                self.record_compile(duration_s)

        try:
            monitoring.register_event_duration_secs_listener(_on_duration)
            self._listener_installed = True
        except Exception:
            pass

    # -- tally points (called from the engine hot path) -----------------------

    def record_transfer(self, nbytes: int, direction: str = "h2d") -> None:
        with self._lock:
            self._transfer_bytes[direction] = (
                self._transfer_bytes.get(direction, 0.0) + nbytes
            )
        if self._c_transfer is not None:
            self._transfer_child(direction).inc(nbytes)

    def record_round_trip(self, h2d: int, d2h: int) -> None:
        """One query dispatch: its count, operand and answer bytes, under
        one lock."""
        with self._lock:
            self._dispatches += 1
            self._transfer_bytes["h2d"] += h2d
            self._transfer_bytes["d2h"] += d2h
        if self._c_transfer is not None:
            self._c_dispatches.inc()
            self._transfer_child("h2d").inc(h2d)
            self._transfer_child("d2h").inc(d2h)

    def _transfer_child(self, direction: str):
        child = self._transfer_children.get(direction)
        if child is None:
            child = self._c_transfer.labels(direction=direction)
            self._transfer_children[direction] = child
        return child

    def record_compile(self, seconds: float) -> None:
        with self._lock:
            self._compiles += 1
            self._compile_seconds += seconds
        if self._c_compiles is not None:
            self._c_compiles.inc()
        if self._c_compile_s is not None:
            self._c_compile_s.inc(seconds)

    # -- introspection --------------------------------------------------------

    def sample_devices(self) -> list[dict]:
        out = []
        for i, dev in enumerate(_local_devices()):
            try:
                stats = dev.memory_stats()
            except Exception:
                stats = None
            entry = {
                "id": getattr(dev, "id", i),
                "platform": getattr(dev, "platform", "unknown"),
                "device_kind": getattr(dev, "device_kind", "unknown"),
            }
            if stats:
                entry["memory_stats"] = {
                    k: stats[k]
                    for k in (
                        "bytes_in_use", "bytes_limit", "peak_bytes_in_use",
                        "num_allocs", "largest_alloc_size",
                    )
                    if k in stats
                }
            out.append(entry)
        return out

    def panel(self) -> dict:
        """The /debug/graph payload: graph shape + device samples +
        lifetime transfer/dispatch/compile tallies."""
        graph = {}
        fn = self._graph_panel_fn
        if fn is not None:
            try:
                graph = fn() or {}
            except Exception:
                graph = {}
        with self._lock:
            transfer = dict(self._transfer_bytes)
            dispatches = self._dispatches
            compiles = self._compiles
            compile_s = round(self._compile_seconds, 3)
        return {
            "sampled_at": time.time(),
            "graph": graph,
            "devices": self.sample_devices(),
            "transfer_bytes": transfer,
            "dispatches": dispatches,
            "jit_compilations": compiles,
            "jit_compile_seconds": compile_s,
        }


DEVSTATS = DeviceStatsCollector()
