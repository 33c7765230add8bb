"""Deterministic fault injection: the registry the self-healing plane is
tested against.

The reference inherits its resilience from process-per-request goroutines
and a SQL database as the coordination point (SURVEY §2.10) — any one
request can die without taking the server with it. The TPU-native shape
traded that for shared-fate components: one dispatcher thread in
``CheckBatcher``, one delta-stream socketpair per forked replica, one
compiled device engine. Each of those is a single point whose death used to
wedge or silently stale the read plane. The recovery paths that now guard
them (driver/replicas.py supervision + resync, engine/batcher.py watchdog,
engine/fallback.py circuit breaker) are only trustworthy if they can be
*driven*, deterministically, in tier-1 tests — which is what this module
is: named fault sites compiled into the production code paths, armed
per-process via :data:`FAULTS` or the ``KETO_FAULTS`` environment knob.

Armed sites fire a bounded number of times (never probabilistically: a
flaky fault is a flaky test), then disarm themselves. Unarmed sites cost
one dict lookup under a lock — nothing on the hot path fires them per
request; they sit on failure-handling seams (dispatch loop iterations,
delta broadcasts, device batch entry).

Known sites (the fault matrix tests/test_faults.py walks):

========================  ====================================================
site                      effect when armed
========================  ====================================================
``replica.crash``         a forked read replica ``os._exit``\\ s while applying
                          its next delta frame (driver/replicas.py)
``delta.drop``            the parent skips broadcasting one delta frame to
                          one serving replica — a silent version gap the
                          resync handshake must fill (driver/replicas.py)
``batcher.dispatcher_die``  the CheckBatcher dispatcher thread raises and
                          dies at the top of its loop; the watchdog must
                          restart it (engine/batcher.py)
``batcher.encode_die``    a pipeline encode worker raises and dies at the
                          top of its loop; its held batch must fail typed
                          and the stage restart (engine/batcher.py)
``batcher.decode_die``    the pipeline decode thread raises and dies at the
                          top of its loop; its held batch must fail typed
                          and the stage restart (engine/batcher.py)
``device.compile_error``  a launch raises as an XLA compile failure would
                          (parallel/serving.py)
``device.batch_nan``      a launch returns non-boolean garbage for the
                          batch, as a numerically sick chip would
                          (parallel/serving.py)
``device.oom``            a launch raises as an XLA RESOURCE_EXHAUSTED (HBM
                          out-of-memory) would; the breaker's OOM policy
                          must bisect and re-dispatch the batch halves
                          (parallel/serving.py + engine/fallback.py)
``device.compile_fail``   a launch raises as a *shape-specific* XLA
                          compilation failure would; the (bucket, snapshot)
                          quarantine must absorb it without tripping the
                          global breaker (parallel/serving.py)
``device.lost``           a launch raises as a DEVICE_LOST / wedged-driver
                          error would; the device supervisor must tear the
                          engine down and re-init through a backend probe
                          (parallel/serving.py + driver/registry.py)
``backend.probe_hang``    the supervisor's backend re-probe "hangs" the way
                          ``jax.devices()`` did in BENCH_r05; the supervised
                          probe must count it as a failed attempt instead of
                          wedging the loop (driver/registry.py)
``client.unavailable``    test-only site for client retry paths
``wal.torn_write``        a WAL append writes only half its frame to disk
                          before "the process dies" — replay must truncate
                          the unacked torn tail (store/wal.py)
``wal.corrupt_crc``       a WAL append lands framed but with a flipped CRC;
                          replay must refuse the record (store/wal.py)
``wal.crash_after_append``  a WAL append completes durably (written +
                          fsynced) and then the process dies before acking
                          the caller — recovery may legitimately surface
                          the durable-but-unacked write (store/wal.py)
``checkpoint.crash_mid_write``  the checkpoint writer dies with a
                          half-written tmp file before the atomic rename;
                          readers must keep seeing the previous checkpoint
                          (graph/checkpoint.py)
``shard.launch_fail``     a sharded serving-tier launch raises before the
                          mesh dispatch; the breaker must answer the batch
                          from the host oracle and re-probe the mesh path
                          (parallel/serving.py + engine/fallback.py)
``list.gather_fail``      a list-serving reverse-index gather raises before
                          decoding candidates; the list breaker must answer
                          from the live-store oracle with identical results
                          and later re-probe the reverse path
                          (engine/listing.py)
``election.split_heartbeat``  a follower loses one leader-liveness
                          observation and falsely suspects a live leader —
                          the premature candidacy must lose the lease CAS,
                          never mint a second term (cluster/election.py)
``replica.promote_fail``  a winning candidate's ``promote(wal_dir)`` raises
                          mid-failover; the lease must be released and the
                          election re-run instead of wedging the fleet
                          read-only (cluster/election.py)
``scrub.device_bitflip``  one element of the resident closure matrix is
                          poisoned in place — the silent HBM bit flip the
                          row scrubber must detect and repair via
                          ``reset_residency`` (engine/closure.py)
``wal.bitrot``            one byte of a *sealed* WAL segment flips on disk;
                          the scrubber's rolling CRC rescan must flag it and
                          checkpoint past the damage (store/wal.py,
                          fired from engine/scrub.py)
``wal.enospc``            a WAL append raises ENOSPC before any byte lands;
                          the write is never acked and the durable wrapper
                          fail-stops (store/wal.py)
``replica.skip_delta``    a follower applies a delta's version but drops its
                          tuples — silent divergence with zero reported lag;
                          only the anti-entropy digest can see it
                          (replication/follower.py)
========================  ====================================================

Slowness sites (armed with :meth:`FaultRegistry.arm_slow`, consumed with
:meth:`FaultRegistry.maybe_sleep`): the production failure mode death
doesn't model is *latency* — a device dispatch that takes 40x p50, a
wedged worker that never returns. Each seam below delays (``sleep=ms``)
or blocks until disarmed (``stuck``) instead of raising:

========================  ====================================================
site                      seam that honors it when armed
========================  ====================================================
``batcher.dispatch_slow`` the serial dispatcher stalls before dispatching a
                          batch (engine/batcher.py)
``batcher.encode_slow``   a pipeline encode worker stalls before encoding
                          its batch (engine/batcher.py)
``batcher.launch_slow``   the pipeline launch thread stalls before the
                          device dispatch (engine/batcher.py)
``batcher.decode_slow``   the pipeline decode thread stalls before decoding
                          a launched batch (engine/batcher.py)
``batcher.reconfigure_stall``  a live ``reconfigure()`` stalls in its drain
                          window after quiescing the stages — in-flight
                          batches must still flush and queued requests
                          must survive into the rebuilt pipeline
                          (engine/batcher.py)
``device.slow``           a launch stalls inside the dispatch itself
                          (parallel/serving.py)
``delta.slow``            the parent stalls before broadcasting a delta
                          frame (driver/replicas.py)
``replica.slow``          a serving replica stalls before answering a check
                          (driver/replicas.py) — the hedging drill's seam
``shard.launch_slow``     a sharded serving-tier launch stalls before the
                          mesh dispatch — models a straggling shard, the
                          deadline plane's cross-mesh seam
                          (parallel/serving.py)
``election.lease_stall``  a lease acquire/renew stalls before its critical
                          section — a stalled renewal lets a live leader's
                          lease expire (it must detect the fencing and step
                          down); a stalled candidate loses its race
                          (cluster/election.py)
========================  ====================================================

``KETO_FAULTS`` syntax: comma-separated entries, each one of

- ``site`` — fail-stop, fire once
- ``site:count`` — fail-stop, fire ``count`` times
- ``site:sleep=ms`` — slowness, delay ``ms`` milliseconds once
- ``site:sleep=ms:count`` — slowness, delay ``count`` times
- ``site:stuck`` — slowness, block until the site is disarmed/reset

e.g. ``KETO_FAULTS="delta.drop,device.batch_nan:3,device.slow:sleep=250:2"``.
Parsed once at import; tests arm programmatically instead.

Fork semantics: the registry is plain process memory, so forked replicas
inherit the armed state at fork time and decrement their own copies — that
is what makes ``replica.crash`` deterministic per child. The replica
pool ships its *current* registry snapshot with every respawn command
(driver/replicas.py) so a fault disarmed in the parent does not resurrect
in respawned children.
"""

from __future__ import annotations

import os
import threading
from typing import Optional

#: upper bound on a single ``stuck`` block: even an un-reset registry can't
#: wedge a process (watchdogs fire long before this; CI budgets survive it)
STUCK_CAP_S = 120.0


class FaultInjected(RuntimeError):
    """Raised by an armed :meth:`FaultRegistry.fire` site. Deliberately a
    plain RuntimeError subclass: production recovery paths must treat it
    exactly like the organic failure it stands in for."""

    def __init__(self, site: str):
        super().__init__(f"injected fault: {site}")
        self.site = site


class FaultRegistry:
    """Thread-safe map of site -> remaining fire count."""

    def __init__(self, env: Optional[dict] = None):
        self._lock = threading.Lock()
        self._armed: dict[str, int] = {}
        # site -> [times remaining, sleep_s, stuck]; slowness is a separate
        # map so fail-stop consumers (should_fire/fire) never race a slow
        # arming for the same name
        self._slow: dict[str, list] = {}
        self._fired: dict[str, int] = {}
        # epoch event: sleepers wait on the event captured at sleep start;
        # disarm/reset swap in a fresh one and set the old, so every
        # in-flight sleep (and every ``stuck`` block) wakes immediately
        self._wake = threading.Event()
        if env is not None:
            self.load_env(env)

    # -- arming ---------------------------------------------------------------

    def arm(self, site: str, times: int = 1) -> None:
        if times <= 0:
            raise ValueError(f"times must be positive, got {times}")
        with self._lock:
            self._armed[site] = self._armed.get(site, 0) + times

    def arm_slow(
        self,
        site: str,
        sleep_ms: Optional[float] = None,
        stuck: bool = False,
        times: int = 1,
    ) -> None:
        """Arm a slowness site: each of the next ``times`` consultations of
        :meth:`maybe_sleep` delays ``sleep_ms`` milliseconds, or — with
        ``stuck`` — blocks until the site is disarmed/reset (capped at
        :data:`STUCK_CAP_S`)."""
        if times <= 0:
            raise ValueError(f"times must be positive, got {times}")
        if not stuck and sleep_ms is None:
            raise ValueError("arm_slow needs sleep_ms or stuck=True")
        sleep_s = 0.0 if sleep_ms is None else float(sleep_ms) / 1000.0
        with self._lock:
            self._slow[site] = [times, sleep_s, bool(stuck)]

    def disarm(self, site: str) -> None:
        with self._lock:
            self._armed.pop(site, None)
            self._slow.pop(site, None)
            wake, self._wake = self._wake, threading.Event()
        wake.set()

    def reset(self) -> None:
        """Disarm everything and zero fire counts (test teardown); wakes
        every in-flight sleep/stuck block."""
        with self._lock:
            self._armed.clear()
            self._slow.clear()
            self._fired.clear()
            wake, self._wake = self._wake, threading.Event()
        wake.set()

    def load_env(self, env: Optional[dict] = None) -> None:
        """Arm from ``KETO_FAULTS`` (see the module docstring syntax)."""
        raw = (env if env is not None else os.environ).get("KETO_FAULTS", "")
        for entry in raw.split(","):
            entry = entry.strip()
            if not entry:
                continue
            parts = entry.split(":")
            site = parts[0].strip()
            mods = [p.strip() for p in parts[1:]]
            if not mods:
                self.arm(site)
            elif mods[0] == "stuck":
                self.arm_slow(site, stuck=True)
            elif mods[0].startswith("sleep="):
                ms = float(mods[0][len("sleep=") :])
                times = int(mods[1]) if len(mods) > 1 else 1
                self.arm_slow(site, sleep_ms=ms, times=times)
            else:
                self.arm(site, int(mods[0]))

    # -- introspection --------------------------------------------------------

    def armed(self, site: str) -> int:
        with self._lock:
            return self._armed.get(site, 0)

    def slow_armed(self, site: str) -> int:
        with self._lock:
            spec = self._slow.get(site)
            return spec[0] if spec else 0

    def fired(self, site: str) -> int:
        with self._lock:
            return self._fired.get(site, 0)

    def snapshot(self) -> dict:
        """The armed state, for shipping across a process boundary
        (replica respawn commands carry this). Fail-stop sites map to a
        remaining count; slowness sites to a param dict — :meth:`load`
        accepts both shapes."""
        with self._lock:
            snap: dict = dict(self._armed)
            for site, (times, sleep_s, stuck) in self._slow.items():
                snap[site] = {
                    "times": times,
                    "sleep_ms": sleep_s * 1000.0,
                    "stuck": stuck,
                }
            return snap

    def load(self, armed: dict) -> None:
        """Replace the armed state wholesale (the receiving end of
        :meth:`snapshot`)."""
        with self._lock:
            self._armed = {
                k: int(v)
                for k, v in armed.items()
                if not isinstance(v, dict) and int(v) > 0
            }
            self._slow = {
                k: [
                    int(v["times"]),
                    float(v["sleep_ms"]) / 1000.0,
                    bool(v.get("stuck", False)),
                ]
                for k, v in armed.items()
                if isinstance(v, dict) and int(v["times"]) > 0
            }

    # -- firing ---------------------------------------------------------------

    def should_fire(self, site: str) -> bool:
        """Consume one armed count for ``site``; the caller applies the
        fault itself (drop a frame, corrupt a result)."""
        with self._lock:
            remaining = self._armed.get(site, 0)
            if remaining <= 0:
                return False
            if remaining == 1:
                del self._armed[site]
            else:
                self._armed[site] = remaining - 1
            self._fired[site] = self._fired.get(site, 0) + 1
            return True

    def fire(self, site: str) -> None:
        """Raise :class:`FaultInjected` if ``site`` is armed."""
        if self.should_fire(site):
            raise FaultInjected(site)

    def maybe_sleep(self, site: str) -> float:
        """Consume one slowness arming for ``site`` and block accordingly:
        ``sleep_ms`` waits that long, ``stuck`` waits until disarm/reset
        (capped at :data:`STUCK_CAP_S`). Either wait ends early when the
        registry is disarmed/reset. Returns the seconds this call was
        configured to stall (0.0 when unarmed) — the cost of an unarmed
        site is one dict lookup under the lock."""
        with self._lock:
            spec = self._slow.get(site)
            if spec is None:
                return 0.0
            spec[0] -= 1
            if spec[0] <= 0:
                del self._slow[site]
            _, sleep_s, stuck = spec
            self._fired[site] = self._fired.get(site, 0) + 1
            wake = self._wake
        delay = STUCK_CAP_S if stuck else sleep_s
        wake.wait(delay)
        return delay


#: The process-wide registry every production fault site consults.
FAULTS = FaultRegistry(env=os.environ)
