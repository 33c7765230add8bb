"""Device-fault & memory-pressure robustness plane (engine/fallback.py,
engine/hbm.py, driver/registry.py DeviceSupervisor).

- typed XLA error classification (oom / device_lost / compile_fail /
  transient), including injected-fault sites
- OOM batch bisection: parity-exact against the unsplit host oracle under
  fuzzed batch sizes and armed-OOM counts (random split trees), unicode
  vocab, and batches arriving through the batcher's encoded path — with
  ZERO host-fallback escalations
- compile-failure quarantine: the failing (bucket, snapshot) shape routes
  to the oracle without opening the circuit for every other shape
- device-lost -> supervised backend failover -> bounded recovery, end to
  end through the registry
- breaker half-open re-probe jitter: deterministic under an injected rng,
  exponential cooldown growth capped
- HBM admission control: budget calibration from device memory stats,
  chunk clamping, reserve/release accounting, rebuild gating, and the
  no-device-stats degrade to admission-off
"""

import random
import threading
import time

import jax
import pytest

from keto_tpu.engine import CheckEngine
from keto_tpu.engine.fallback import (
    DeviceFallbackEngine,
    classify_device_error,
)
from keto_tpu.engine.hbm import HbmAdmission
from keto_tpu.faults import FAULTS, FaultInjected
from keto_tpu.graph import SnapshotManager
from keto_tpu.parallel import ShardedServingEngine, make_mesh
from keto_tpu.relationtuple import RelationTuple
from keto_tpu.store import InMemoryTupleStore


def t(s: str) -> RelationTuple:
    return RelationTuple.from_string(s)


@pytest.fixture(autouse=True)
def _clean_faults():
    FAULTS.reset()
    yield
    FAULTS.reset()


def make_breaker(store, **kw):
    """The breaker around the sharded serving engine on a two-device mesh:
    the engine whose launch carries the ``device.*`` fault sites."""
    mgr = SnapshotManager(store)
    dev = ShardedServingEngine(
        mgr, mesh=make_mesh(jax.devices()[:2], data=1), max_depth=5
    )
    breaker = DeviceFallbackEngine(
        dev,
        fallback_factory=lambda: CheckEngine(store, max_depth=5),
        failure_threshold=3,
        cooldown_s=0.1,
        **kw,
    )
    return dev, breaker


# unicode vocab: node ids must survive encode -> split -> re-encode even
# when the key strings are multi-byte (a naive byte-offset split corrupts)
_UNI_OBJS = ["документ", "予約-α", "ficha-ñ", "plain"]
_UNI_USERS = ["алиса", "ユーザー1", "böb", "mallory"]


def _seed_unicode_graph(store):
    tuples = []
    for i, obj in enumerate(_UNI_OBJS):
        tuples.append(t(f"n:{obj}#view@(n:группа{i % 2}#member)"))
    tuples.append(t("n:группа0#member@алиса"))
    tuples.append(t("n:группа1#member@ユーザー1"))
    tuples.append(t("n:plain#view@böb"))
    store.write_relation_tuples(*tuples)


def _encode(breaker, reqs):
    """Vocab-encode requests and park them as the engine's encoded batch
    (``encode_ids``, the batcher's encoded path)."""
    snap = breaker.snapshots.snapshot()
    start, target = snap.encode_requests(reqs)
    return breaker.encode_ids(start, target)


def _request_pool(rng, k):
    """(requests, unused) — mixed hits/misses over the unicode graph."""
    reqs = []
    for _ in range(k):
        obj = _UNI_OBJS[rng.randrange(len(_UNI_OBJS))]
        user = _UNI_USERS[rng.randrange(len(_UNI_USERS))]
        reqs.append(t(f"n:{obj}#view@{user}"))
    return reqs


class TestClassification:
    @pytest.mark.parametrize(
        "msg,kind",
        [
            ("RESOURCE_EXHAUSTED: out of memory allocating 2GB", "oom"),
            ("XLA error: failed to allocate buffer", "oom"),
            ("DEVICE_LOST: tpu rebooted underneath us", "device_lost"),
            ("backend reported device lost", "device_lost"),
            ("Mosaic compilation failure: unsupported op", "compile_fail"),
            ("something unrecognized went wrong", "transient"),
        ],
    )
    def test_message_classification(self, msg, kind):
        assert classify_device_error(RuntimeError(msg)) == kind

    def test_injected_fault_sites(self):
        assert classify_device_error(FaultInjected("device.oom")) == "oom"
        assert (
            classify_device_error(FaultInjected("device.lost"))
            == "device_lost"
        )
        assert (
            classify_device_error(FaultInjected("device.compile_fail"))
            == "compile_fail"
        )
        # the PR2-era compile_error site keeps breaker (transient)
        # semantics for back-compat with the existing fault matrix
        assert (
            classify_device_error(FaultInjected("device.compile_error"))
            == "transient"
        )


class TestOomBisection:
    def _roundtrip(self, breaker, reqs):
        enc = _encode(breaker, reqs)
        launched = breaker.launch_encoded(enc)
        return [bool(v) for v in breaker.decode_launched(launched)]

    def test_parity_fuzz_random_splits(self):
        """Fuzz batch sizes and armed-OOM counts: every bisection tree
        (odd splits, nested re-splits) must answer exactly like the
        unsplit host oracle, with the circuit closed and the host
        fallback engine never even constructed."""
        store = InMemoryTupleStore()
        _seed_unicode_graph(store)
        oracle = CheckEngine(store, max_depth=5)
        _, breaker = make_breaker(store)
        rng = random.Random(11)
        for trial, size in enumerate([2, 3, 5, 17, 33, 64, 120]):
            reqs = _request_pool(rng, size)
            want = [oracle.subject_is_allowed(r) for r in reqs]
            # the bisection's first-half path halves per armed fire, so k
            # fires need size >= 2^k — cap k so NO trial can bottom out at
            # an unsplittable single row (which would escalate, correctly,
            # to the host oracle; the zero-escalation variant is the point
            # of THIS test)
            times = max(1, min(1 + trial % 3, size.bit_length() - 1))
            FAULTS.arm("device.oom", times=times)
            got = self._roundtrip(breaker, reqs)
            assert got == want, f"size={size}"
            assert not FAULTS.armed("device.oom")
        assert not breaker.circuit_open()
        # zero host-fallback escalations: the oracle was never built
        assert breaker._fallback is None

    def test_single_row_oom_cannot_split(self):
        """n=1 cannot bisect: the row still gets a CORRECT answer (host
        oracle fallthrough) — never a wrong one."""
        store = InMemoryTupleStore()
        _seed_unicode_graph(store)
        _, breaker = make_breaker(store)
        FAULTS.arm("device.oom")
        got = self._roundtrip(breaker, [t("n:plain#view@böb")])
        assert got == [True]

    def test_persistent_oom_exhausts_depth_then_oracle(self):
        """Every launch OOMs: bisection bottoms out at max_bisect_depth
        and the batch falls through to the oracle — correct answers,
        bounded work."""
        store = InMemoryTupleStore()
        _seed_unicode_graph(store)
        oracle = CheckEngine(store, max_depth=5)
        _, breaker = make_breaker(store)
        rng = random.Random(3)
        reqs = _request_pool(rng, 32)
        want = [oracle.subject_is_allowed(r) for r in reqs]
        FAULTS.arm("device.oom", times=10_000)
        got = self._roundtrip(breaker, reqs)
        assert got == want
        FAULTS.reset()

    def test_cache_hit_interleaving(self):
        """Batches reach the OOM bisection through the batcher's encoded
        path (this engine keeps no encoded-request cache, so a batch of
        repeated and fresh rows launches whole): the bisected halves must
        merge back into a parity-exact full answer."""
        from keto_tpu.engine.batcher import CheckBatcher

        store = InMemoryTupleStore()
        _seed_unicode_graph(store)
        oracle = CheckEngine(store, max_depth=5)
        _, breaker = make_breaker(store)
        batcher = CheckBatcher(
            breaker, max_batch=256, encoded_cache_size=1024
        )
        try:
            rng = random.Random(5)
            warm = _request_pool(rng, 24)
            snap = breaker.snapshots.snapshot()
            batcher.check_batch_encoded(*snap.encode_requests(warm), [5] * 24)
            mixed = warm[:12] + _request_pool(rng, 36)  # repeats + fresh
            want = [oracle.subject_is_allowed(r) for r in mixed]
            FAULTS.arm("device.oom", times=2)
            got = batcher.check_batch_encoded(
                *snap.encode_requests(mixed), [5] * len(mixed)
            )
            assert [bool(v) for v in got] == want
            assert not FAULTS.armed("device.oom")
            assert not breaker.circuit_open()
            assert breaker._fallback is None
        finally:
            batcher.close()


class TestCompileQuarantine:
    def test_quarantine_absorbs_shape_without_tripping(self):
        store = InMemoryTupleStore()
        _seed_unicode_graph(store)
        oracle = CheckEngine(store, max_depth=5)
        _, breaker = make_breaker(store)
        rng = random.Random(9)
        reqs = _request_pool(rng, 20)
        want = [oracle.subject_is_allowed(r) for r in reqs]
        FAULTS.arm("device.compile_fail")
        enc = _encode(breaker, reqs)
        got = breaker.decode_launched(breaker.launch_encoded(enc))
        assert [bool(v) for v in got] == want
        assert not breaker.circuit_open()
        q = breaker.quarantine_snapshot()
        assert len(q) == 1 and q[0]["bucket"] == 32
        # the same shape now routes straight to the oracle (no fault
        # armed, but the quarantine remembers) — still correct
        enc2 = _encode(breaker, reqs)
        got2 = breaker.decode_launched(breaker.launch_encoded(enc2))
        assert [bool(v) for v in got2] == want
        # a DIFFERENT bucket is untouched by the quarantine: the device
        # still serves it
        small = reqs[:4]
        enc3 = _encode(breaker, small)
        got3 = breaker.decode_launched(breaker.launch_encoded(enc3))
        assert [bool(v) for v in got3] == want[:4]
        assert not breaker.circuit_open()


class TestBreakerJitterAndDeviceLost:
    def _ticking(self, breaker_kw=None):
        fake = [0.0]
        store = InMemoryTupleStore()
        store.write_relation_tuples(t("n:o#view@u"))
        _, breaker = make_breaker(
            store, clock=lambda: fake[0], **(breaker_kw or {})
        )
        return fake, breaker

    def test_jitter_deterministic_under_injected_rng(self):
        opens = []
        for _ in range(2):
            fake, breaker = self._ticking(
                {"rng": random.Random(42), "jitter_frac": 0.25}
            )
            breaker.failure_threshold = 1
            breaker._record_failure(RuntimeError("boom"))
            opens.append(breaker._open_until)
        assert opens[0] == opens[1]  # same rng seed -> same jitter
        # jittered open window lands in [cooldown, cooldown * 1.25)
        assert 0.1 <= opens[0] < 0.1 * 1.25

    def test_cooldown_doubles_and_caps(self):
        from keto_tpu.engine.fallback import _COOLDOWN_CAP_S

        fake, breaker = self._ticking(
            {"rng": random.Random(1), "jitter_frac": 0.0}
        )
        breaker.failure_threshold = 1
        breaker._record_failure(RuntimeError("boom"))
        assert breaker._cooldown_s == pytest.approx(0.1)
        for _ in range(16):  # re-failures while open: exponential, capped
            breaker._record_failure(RuntimeError("boom"))
        assert breaker._cooldown_s == _COOLDOWN_CAP_S

    def test_device_lost_forces_open_and_notifies(self):
        lost = []
        store = InMemoryTupleStore()
        store.write_relation_tuples(t("n:o#view@u"))
        _, breaker = make_breaker(store, on_device_lost=lost.append)
        assert breaker.failure_threshold == 3
        breaker._note_failure(RuntimeError("DEVICE_LOST: gone"))
        # one device_lost opens the circuit immediately — no waiting for
        # the consecutive-failure threshold
        assert breaker.circuit_open()
        assert len(lost) == 1

    def test_force_probe_collapses_open_window(self):
        fake, breaker = self._ticking({"rng": random.Random(2)})
        breaker.failure_threshold = 1
        breaker._record_failure(RuntimeError("boom"))
        assert breaker.circuit_open()
        breaker.force_probe()
        # the next batch may now probe: _use_primary() flips to probing
        assert breaker._use_primary()


class TestFailoverEndToEnd:
    def test_device_lost_failover_recovery_drill(self):
        """The acceptance drill: seeded device.lost keeps serving through
        the host oracle, the supervisor re-probes and returns serving to
        device mode within a bounded window, and the whole episode is
        visible in /debug/device and the flight recorder."""
        from keto_tpu.driver.config import Config
        from keto_tpu.driver.registry import Registry
        from keto_tpu.relationtuple.columns import CheckColumns

        cfg = Config(
            values={
                "namespaces": [{"id": 1, "name": "n"}],
                "log": {"level": "error"},
                "engine": {
                    "sharding": {"enabled": True},
                    "max_batch": 128,
                    "cache_size": 0,
                    "encoded_cache_size": 0,
                    "fallback_cooldown_ms": 100,
                    "failover": {
                        "probe_mode": "inproc",
                        "probe_interval_s": 0.05,
                    },
                },
            }
        )
        reg = Registry(cfg)
        store = reg.store()
        objs = [f"ok{i}" for i in range(16)]
        store.write_relation_tuples(
            *[
                RelationTuple.from_string(f"n:{o}#view@alice")
                for o in objs
            ]
        )
        checker = reg.checker()
        supervisor = reg.device_supervisor()
        breaker = reg._engine_breaker
        try:
            rows = objs + ["ghost0", "ghost1"]
            want = [True] * len(objs) + [False, False]
            cols = CheckColumns(
                ["n"] * len(rows),
                rows,
                ["view"] * len(rows),
                subject_ids=["alice"] * len(rows),
            ).validate()
            FAULTS.arm("device.lost")
            # the lost batch itself: answered by the oracle, still exact
            assert [
                bool(v) for v in checker.check_batch_columnar(cols, 5)
            ] == want
            deadline = time.monotonic() + 15.0
            status = None
            while time.monotonic() < deadline:
                status = supervisor.status()
                if status["failovers"] >= 1 and not status["recovering"]:
                    break
                time.sleep(0.02)
            assert status is not None and status["failovers"] >= 1
            assert not status["recovering"], status
            assert status["last_recovery_s"] < 15.0
            events = [e["event"] for e in status["timeline"]]
            assert "device_lost" in events and "recovered" in events
            # post-recovery: the forced half-open probe lets the next
            # batch close the circuit and serve from the device again
            assert [
                bool(v) for v in checker.check_batch_columnar(cols, 5)
            ] == want
            assert not breaker.circuit_open()
            status = reg._device_status()
            assert status["backend"] == "cpu"
            assert status["supervisor"]["failovers"] >= 1
            flight = reg.flight()
            kinds = [r.get("kind") for r in flight.records(100)]
            assert "device_failover" in kinds
        finally:
            checker.close()
            supervisor.stop()

    def test_probe_hang_counts_as_failed_attempt(self):
        """backend.probe_hang: the supervisor's probe child 'hangs' and is
        killed — recovery happens on the NEXT probe, never a wedge."""
        from keto_tpu.driver.registry import DeviceSupervisor

        class _Eng:
            def reset_residency(self):
                pass

            def warmup(self, n):
                pass

        sup = DeviceSupervisor(
            _Eng(),
            probe_mode="inproc",
            probe_interval_s=0.01,
            max_backoff_s=0.05,
        )
        FAULTS.arm("backend.probe_hang")
        sup.notify_device_lost(RuntimeError("DEVICE_LOST"))
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            st = sup.status()
            if not st["recovering"]:
                break
            time.sleep(0.02)
        st = sup.status()
        assert not st["recovering"]
        probe_events = [
            e for e in st["timeline"] if e["event"] == "probe"
        ]
        assert any(not e["ok"] for e in probe_events)  # the hung one
        assert any(e["ok"] for e in probe_events)  # the recovery one
        sup.stop()


class _FakeDevstats:
    def __init__(self, limit=1_000_000, peak=0):
        self.limit = limit
        self.peak = peak

    def sample_devices(self):
        if self.limit is None:
            return [{"platform": "cpu", "memory_stats": None}]
        return [
            {
                "platform": "tpu",
                "memory_stats": {
                    "bytes_in_use": 0,
                    "bytes_limit": self.limit,
                    "peak_bytes_in_use": self.peak,
                },
            }
        ]


class TestHbmAdmission:
    def test_no_device_stats_disables_admission(self):
        hbm = HbmAdmission(devstats=_FakeDevstats(limit=None))
        assert hbm.budget_bytes() is None
        assert hbm.clamp_rows(4096) == 4096
        assert hbm.reserve(128, 1) == 0  # free token
        hbm.release(0)  # no-op
        assert hbm.wait_for_headroom(timeout_s=0.0)

    def test_budget_and_clamp(self):
        hbm = HbmAdmission(
            budget_frac=0.5,
            bytes_per_row=100,
            devstats=_FakeDevstats(limit=1_000_000),
        )
        assert hbm.budget_bytes() == pytest.approx(500_000)
        # 500k budget / 100 B per row = 5000 rows fit: no clamp at 4096
        assert hbm.clamp_rows(4096) == 4096
        # reserve a big shape, then the same ask must be pre-split
        tok = hbm.reserve(4096, 1)
        assert tok != 0
        assert hbm.clamp_rows(4096) < 4096
        hbm.release(tok)
        assert hbm.clamp_rows(4096) == 4096
        assert hbm.snapshot()["inflight_batches"] == 0

    def test_reserve_release_accounting(self):
        hbm = HbmAdmission(
            bytes_per_row=100, devstats=_FakeDevstats(limit=1_000_000)
        )
        t1 = hbm.reserve(128, 1)
        t2 = hbm.reserve(256, 1)
        snap = hbm.snapshot()
        assert snap["inflight_batches"] == 2
        assert snap["inflight_bytes"] == pytest.approx(38_400)
        hbm.release(t1)
        hbm.release(t1)  # double release is a no-op
        hbm.release(t2)
        assert hbm.snapshot()["inflight_bytes"] == 0.0

    def test_rebuild_gate_blocks_until_headroom(self):
        hbm = HbmAdmission(
            budget_frac=1.0,
            bytes_per_row=1000,
            devstats=_FakeDevstats(limit=100_000),
        )
        tok = hbm.reserve(100, 1)  # 100k modeled = the whole budget
        assert not hbm.wait_for_headroom(frac=0.5, timeout_s=0.05)
        done = []

        def _release_later():
            time.sleep(0.05)
            hbm.release(tok)
            done.append(True)

        threading.Thread(target=_release_later, daemon=True).start()
        assert hbm.wait_for_headroom(frac=0.5, timeout_s=5.0)
        assert done

    def test_peak_delta_learns_model(self):
        stats = _FakeDevstats(limit=1_000_000, peak=0)
        hbm = HbmAdmission(bytes_per_row=100, devstats=stats)
        tok = hbm.reserve(128, 1)
        stats.peak = 64_000  # the batch pushed the high-water mark up
        hbm.release(tok)
        # the learned per-shape model replaces the per-row estimate
        assert hbm.modeled_bytes(128, 1) == pytest.approx(64_000)
