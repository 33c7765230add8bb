"""The fault matrix: every self-healing path driven deterministically via
keto_tpu.faults (ISSUE 1 acceptance). Covers, in rough blast-radius order:

- fault registry semantics (arm/fire counts, env knob, fork snapshots)
- dispatcher death -> watchdog restart, in-flight futures failed typed
- queue full -> load shed with 429/RESOURCE_EXHAUSTED semantics
- close() -> queued/in-flight futures fail BatcherClosed, never hang
- device failure (raise AND NaN garbage) -> circuit breaker -> host
  fallback -> health NOT_SERVING -> recovery probe -> SERVING again
- client retry: backoff+jitter schedule, deadline honored end-to-end
- replica SIGKILL -> supervisor respawn via zygote + delta-log resync
- delta-stream drop -> version gap -> resync handshake refills it
- replica.crash fault inherited at fork -> whole-pool crash -> heal
"""

import asyncio
import os
import signal
import threading
import time

import httpx
import pytest

from keto_tpu.engine.batcher import (
    BatcherClosed,
    BatcherOverloaded,
    CheckBatcher,
    DispatcherCrashed,
)
from keto_tpu.engine.fallback import DeviceFallbackEngine
from keto_tpu.faults import FAULTS, FaultInjected, FaultRegistry
from keto_tpu.relationtuple.definitions import RelationTuple, SubjectID
from keto_tpu.telemetry import MetricsRegistry


@pytest.fixture(autouse=True)
def _clean_faults():
    FAULTS.reset()
    yield
    FAULTS.reset()


def _tup(i: int = 0) -> RelationTuple:
    return RelationTuple(
        namespace="n", object=f"o{i}", relation="view",
        subject=SubjectID(id="alice"),
    )


class TestFaultRegistry:
    def test_fire_consumes_armed_count(self):
        r = FaultRegistry()
        r.arm("x.y", times=2)
        with pytest.raises(FaultInjected, match="x.y"):
            r.fire("x.y")
        assert r.armed("x.y") == 1
        with pytest.raises(FaultInjected):
            r.fire("x.y")
        r.fire("x.y")  # disarmed: no-op
        assert r.fired("x.y") == 2

    def test_should_fire_is_the_non_raising_form(self):
        r = FaultRegistry()
        assert not r.should_fire("a")
        r.arm("a")
        assert r.should_fire("a")
        assert not r.should_fire("a")

    def test_env_knob(self):
        r = FaultRegistry(env={"KETO_FAULTS": "a.b, c.d:3 ,,"})
        assert r.armed("a.b") == 1
        assert r.armed("c.d") == 3

    def test_snapshot_load_roundtrip(self):
        r = FaultRegistry()
        r.arm("a", times=2)
        snap = r.snapshot()
        r2 = FaultRegistry()
        r2.arm("stale.fault")
        r2.load(snap)
        assert r2.armed("a") == 2
        assert r2.armed("stale.fault") == 0  # load replaces wholesale

    def test_arm_rejects_nonpositive(self):
        with pytest.raises(ValueError):
            FaultRegistry().arm("a", times=0)


class _OkEngine:
    def batch_check(self, requests, max_depth=0, depths=None):
        return [True] * len(requests)


class _GateEngine:
    """Blocks every batch on an event — makes queue states controllable."""

    def __init__(self):
        self.gate = threading.Event()
        self.calls = 0

    def batch_check(self, requests, max_depth=0, depths=None):
        self.calls += 1
        self.gate.wait(timeout=10)
        return [True] * len(requests)


class TestDispatcherWatchdog:
    def test_injected_death_restarts_dispatcher(self):
        m = MetricsRegistry()
        b = CheckBatcher(_OkEngine(), window_s=0, metrics=m)
        try:
            restarts = b._m_restarts
            FAULTS.arm("batcher.dispatcher_die")
            # the armed fault kills the dispatcher at its next loop top;
            # this check wakes it, gets answered, then the thread dies
            # and the watchdog replaces it
            assert b.check(_tup()) is True
            deadline = time.time() + 5
            while restarts.value < 1 and time.time() < deadline:
                time.sleep(0.01)
            assert restarts.value == 1
            # the replacement thread serves
            assert b.check(_tup(1)) is True
            assert FAULTS.fired("batcher.dispatcher_die") == 1
        finally:
            b.close()

    def test_inflight_futures_fail_typed_on_crash(self):
        class _Bomb(BaseException):  # escapes the per-batch engine guard
            pass

        class _BombEngine:
            def __init__(self):
                self.boom = True

            def batch_check(self, requests, max_depth=0, depths=None):
                if self.boom:
                    self.boom = False
                    raise _Bomb()
                return [True] * len(requests)

        b = CheckBatcher(_BombEngine(), window_s=0)
        try:
            with pytest.raises(DispatcherCrashed) as ei:
                b.check(_tup())
            assert ei.value.grpc_code == "INTERNAL"
            assert b.check(_tup(1)) is True  # watchdog restarted it
        finally:
            b.close()


class _FakeEncoded:
    """Stand-in for an engine's encoded batch: just enough surface for
    the pipelined batcher (version/keys for the encoded cache, release for
    crash cleanup)."""

    version = 0

    def __init__(self, requests):
        self.requests = list(requests)
        self.released = False

    def keys(self):
        return [(r.object, 0, 0) for r in self.requests]

    def compact(self, keep):
        self.requests = [self.requests[i] for i in keep]

    def release(self):
        self.released = True


class _SplitEngine:
    """Minimal split encode/launch/decode engine: deterministic True
    answers, so the pipeline drills isolate STAGE failure handling from
    engine behavior."""

    def pipeline_supported(self):
        return True

    def encode_batch(self, requests, max_depth=0, depths=None):
        return _FakeEncoded(requests)

    def launch_encoded(self, enc):
        return enc

    def decode_launched(self, launched):
        return [True] * len(launched.requests)

    def batch_check(self, requests, max_depth=0, depths=None):
        return [True] * len(requests)


class TestPipelineStageWatchdog:
    """ISSUE-2 drills: a pipeline stage death (encode worker, decode
    thread) fails exactly the in-flight batch typed-retryable and the
    watchdog restarts the stage — queued work and the other stages keep
    serving."""

    def _pipelined(self, metrics=None):
        return CheckBatcher(
            _SplitEngine(),
            window_s=0,
            metrics=metrics,
            pipeline_depth=2,
            encode_workers=2,
        )

    @pytest.mark.parametrize(
        "site", ["batcher.encode_die", "batcher.decode_die"]
    )
    def test_stage_death_fails_inflight_typed_and_restarts(self, site):
        m = MetricsRegistry()
        b = self._pipelined(metrics=m)
        try:
            assert b.pipelined is True
            restarts = b._m_restarts
            FAULTS.arm(site)
            # the armed fault kills the stage while it HOLDS this batch:
            # the caller must get the typed retryable error, not a hang
            with pytest.raises(DispatcherCrashed) as ei:
                b.check(_tup(), timeout=10)
            assert ei.value.grpc_code == "INTERNAL"
            assert FAULTS.fired(site) == 1
            deadline = time.time() + 5
            while restarts.value < 1 and time.time() < deadline:
                time.sleep(0.01)
            assert restarts.value == 1
            # the restarted stage serves the next request
            assert b.check(_tup(1), timeout=10) is True
            # nothing leaked: the failed batch left the pipeline registry
            assert b.pipeline_stats()["batches_in_pipeline"] == 0
        finally:
            b.close()

    def test_stage_crash_releases_encoded_buffers(self):
        class _TrackingSplit(_SplitEngine):
            def encode_batch(self, requests, max_depth=0, depths=None):
                self.last_enc = _FakeEncoded(requests)
                return self.last_enc

        eng = _TrackingSplit()
        b = CheckBatcher(eng, window_s=0, pipeline_depth=2, encode_workers=1)
        try:
            FAULTS.arm("batcher.decode_die")
            with pytest.raises(DispatcherCrashed):
                b.check(_tup(), timeout=10)
            # the crash path returned the staging buffers to the pool
            # (enc.release) instead of leaking them until GC
            assert eng.last_enc.released is True
            assert b.check(_tup(1), timeout=10) is True
        finally:
            b.close()

    def test_pipelined_close_fails_stragglers_typed(self):
        class _StuckSplit(_SplitEngine):
            def __init__(self):
                self.gate = threading.Event()

            def decode_launched(self, launched):
                self.gate.wait(timeout=10)  # wedged device materialization
                return [True] * len(launched.requests)

        eng = _StuckSplit()
        b = CheckBatcher(
            eng, window_s=0, pipeline_depth=2, encode_workers=1
        )
        b.close_join_s = 0.2
        errs = []

        def call():
            try:
                b.check(_tup(), timeout=10)
            except Exception as e:
                errs.append(e)

        t = threading.Thread(target=call, daemon=True)
        t.start()
        deadline = time.time() + 5
        while not b.pipeline_stats()["batches_in_pipeline"] and (
            time.time() < deadline
        ):
            time.sleep(0.005)
        b.close()  # join budget expires; the held batch fails typed
        t.join(timeout=5)
        assert len(errs) == 1 and isinstance(errs[0], BatcherClosed)
        eng.gate.set()


class TestReconfigureDrill:
    """ISSUE-18 satellite: a LIVE ``reconfigure()`` (the autotuner's
    pipeline_depth/encode_workers seam) stalls in its drain window via
    the ``batcher.reconfigure_stall`` fault — concurrent traffic must
    neither error nor vanish: in-flight batches flush through the old
    stages, queued requests survive into the rebuilt pipeline."""

    def test_stalled_reconfigure_keeps_concurrent_traffic(self):
        b = CheckBatcher(
            _SplitEngine(), window_s=0, pipeline_depth=2, encode_workers=1
        )
        try:
            assert b.check(_tup()) is True  # pipeline warm
            FAULTS.arm_slow("batcher.reconfigure_stall", sleep_ms=150)
            results, errs = [], []

            def call(i):
                try:
                    results.append(b.check(_tup(i), timeout=10))
                except Exception as e:  # pragma: no cover - failure path
                    errs.append(e)

            threads = [
                threading.Thread(target=call, args=(i,), daemon=True)
                for i in range(8)
            ]
            for t in threads:
                t.start()
            # the reconfigure races the in-flight checks AND stalls in
            # its drain window while holding the quiesce flag
            assert b.reconfigure(pipeline_depth=3, encode_workers=2)
            for t in threads:
                t.join(timeout=10)
            assert FAULTS.fired("batcher.reconfigure_stall") == 1
            assert errs == []
            assert results == [True] * 8
            assert b.pipeline_depth == 3 and b.encode_workers == 2
            assert b.pipelined is True
            # the rebuilt pipeline serves fresh traffic
            assert b.check(_tup(99), timeout=10) is True
            assert b.pipeline_stats()["batches_in_pipeline"] == 0
        finally:
            b.close()


class TestLoadShedding:
    def test_queue_full_sheds_with_429_semantics(self):
        eng = _GateEngine()
        m = MetricsRegistry()
        b = CheckBatcher(eng, window_s=0, max_queue=1, metrics=m)
        try:
            t1 = threading.Thread(
                target=lambda: b.check(_tup()), daemon=True
            )
            t1.start()
            deadline = time.time() + 5
            while eng.calls < 1 and time.time() < deadline:
                time.sleep(0.005)  # first check is now IN FLIGHT
            t2 = threading.Thread(
                target=lambda: b.check(_tup(1)), daemon=True
            )
            t2.start()
            deadline = time.time() + 5
            while len(b._queue) < 1 and time.time() < deadline:
                time.sleep(0.005)  # second check is QUEUED: queue full
            with pytest.raises(BatcherOverloaded) as ei:
                b.check(_tup(2))
            assert ei.value.status_code == 429
            assert ei.value.grpc_code == "RESOURCE_EXHAUSTED"
            assert ei.value.retry_after_s >= 1
            assert b._m_shed.value == 1
            eng.gate.set()
            t1.join(timeout=5)
            t2.join(timeout=5)
        finally:
            eng.gate.set()
            b.close()


class TestBatcherClose:
    def test_check_after_close_raises_typed(self):
        b = CheckBatcher(_OkEngine(), window_s=0)
        b.close()
        with pytest.raises(BatcherClosed) as ei:
            b.check(_tup())
        assert ei.value.status_code == 503
        with pytest.raises(BatcherClosed):
            b.check_batch([_tup()])

    def test_close_fails_stuck_inflight_instead_of_hanging(self):
        eng = _GateEngine()  # never released: the sick-chip hang mode
        b = CheckBatcher(eng, window_s=0)
        b.close_join_s = 0.2
        errs = []

        def call():
            try:
                b.check(_tup())
            except Exception as e:
                errs.append(e)

        t = threading.Thread(target=call, daemon=True)
        t.start()
        deadline = time.time() + 5
        while eng.calls < 1 and time.time() < deadline:
            time.sleep(0.005)
        b.close()  # join budget 0.2s, then inflight is failed typed
        t.join(timeout=5)
        assert len(errs) == 1 and isinstance(errs[0], BatcherClosed)
        eng.gate.set()

    def test_close_drains_queue_when_engine_healthy(self):
        b = CheckBatcher(_OkEngine(), window_s=0)
        results = [b.check(_tup(i)) for i in range(4)]
        b.close()
        assert results == [True] * 4


class _FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class _FlakyPrimary:
    def __init__(self):
        self.fail = 0
        self.nan = 0
        self.calls = 0

    def batch_check(self, requests, max_depth=0, depths=None):
        self.calls += 1
        if self.fail > 0:
            self.fail -= 1
            raise RuntimeError("XLA compile blew up")
        if self.nan > 0:
            self.nan -= 1
            return [float("nan")] * len(requests)
        return [True] * len(requests)

    def wait_for_version(self, v, timeout_s=30.0):
        return True


class _Oracle:
    def __init__(self):
        self.calls = 0

    def batch_check(self, requests, max_depth=0):
        self.calls += 1
        return [False] * len(requests)

    def subject_is_allowed(self, requested, max_depth=0):
        self.calls += 1
        return False


class TestDeviceCircuitBreaker:
    def _breaker(self, primary, oracle, health=None, threshold=3):
        clock = _FakeClock()
        m = MetricsRegistry()
        eng = DeviceFallbackEngine(
            primary,
            fallback_factory=lambda: oracle,
            failure_threshold=threshold,
            cooldown_s=1.0,
            health=health,
            metrics=m,
            clock=clock,
        )
        return eng, clock, m

    def test_trips_after_threshold_and_serves_fallback(self):
        from keto_tpu.api.services import HealthServicer

        health = HealthServicer()
        health.set_serving(True)
        primary, oracle = _FlakyPrimary(), _Oracle()
        eng, clock, m = self._breaker(primary, oracle, health=health)
        primary.fail = 10
        for _ in range(2):
            assert eng.batch_check([_tup()]) == [False]  # oracle answers
            assert not eng.circuit_open()
            assert health.is_serving()
        assert eng.batch_check([_tup()]) == [False]  # third strike
        assert eng.circuit_open()
        assert not health.is_serving()  # degraded mode is visible
        # while open, the primary is not even consulted
        calls = primary.calls
        assert eng.batch_check([_tup()]) == [False]
        assert primary.calls == calls

    def test_nan_output_counts_as_failure(self):
        primary, oracle = _FlakyPrimary(), _Oracle()
        eng, clock, m = self._breaker(primary, oracle, threshold=1)
        primary.nan = 1
        assert eng.batch_check([_tup()]) == [False]  # validated, rejected
        assert eng.circuit_open()

    def test_halfopen_probe_recovers_and_restores_health(self):
        from keto_tpu.api.services import HealthServicer

        health = HealthServicer()
        health.set_serving(True)
        primary, oracle = _FlakyPrimary(), _Oracle()
        eng, clock, m = self._breaker(primary, oracle, health=health)
        primary.fail = 3
        for _ in range(3):
            eng.batch_check([_tup()])
        assert eng.circuit_open() and not health.is_serving()
        clock.t += 1.5  # past the cooldown: next batch is the probe
        assert eng.batch_check([_tup()]) == [True]  # primary healthy again
        assert not eng.circuit_open()
        assert health.is_serving()

    def test_failed_probe_reopens_with_backoff(self):
        primary, oracle = _FlakyPrimary(), _Oracle()
        eng, clock, m = self._breaker(primary, oracle)
        primary.fail = 4  # 3 to trip + 1 failed probe
        for _ in range(3):
            eng.batch_check([_tup()])
        clock.t += 1.5
        assert eng.batch_check([_tup()]) == [False]  # probe fails -> oracle
        assert eng.circuit_open()
        clock.t += 1.5  # doubled cooldown (2.0s): still open
        assert eng._use_primary() is False
        clock.t += 1.0  # now past it
        assert eng.batch_check([_tup()]) == [True]
        assert not eng.circuit_open()

    def test_injected_device_faults_reach_host_fallback_end_to_end(self):
        """The registry-wired path: device.compile_error and
        device.batch_nan (fault sites of the sharded serving engine's
        launch) degrade to the host oracle; answers stay correct
        throughout."""
        from keto_tpu.driver import Config, Registry

        cfg = Config(
            values={
                "namespaces": [{"id": 1, "name": "n"}],
                "log": {"level": "error"},
                "engine": {
                    "sharding": {"enabled": True},
                    "cache_size": 0,  # a cache hit would mask the faults
                    "encoded_cache_size": 0,  # ditto for the encoded cache
                    "fallback_threshold": 2,
                    "fallback_cooldown_ms": 50,
                },
            }
        )
        reg = Registry(cfg)
        reg.store().transact_relation_tuples([_tup()], [])
        checker = reg.checker()
        breaker = reg._engine_breaker
        assert isinstance(breaker, DeviceFallbackEngine)
        try:
            assert checker.check(_tup()) is True  # device path, healthy
            FAULTS.arm("device.compile_error", times=2)
            assert checker.check(_tup()) is True  # oracle keeps truth
            assert checker.check(_tup()) is True  # second strike: trips
            assert FAULTS.fired("device.compile_error") == 2
            assert breaker.circuit_open()
            assert not reg.health.is_serving()
            # the next device attempt is the half-open probe — make it hit
            # the OTHER failure class (garbage output, not an exception)
            FAULTS.arm("device.batch_nan", times=1)
            time.sleep(0.1)  # past the 50ms cooldown
            assert checker.check(_tup()) is True  # failed probe -> oracle
            assert FAULTS.fired("device.batch_nan") == 1
            assert breaker.circuit_open()  # reopened, cooldown doubled
            time.sleep(0.25)  # past the doubled (100ms) cooldown
            assert checker.check(_tup()) is True  # probe succeeds
            assert not breaker.circuit_open()
            assert reg.health.is_serving()
        finally:
            checker.close()


class TestClientRetry:
    def test_backoff_schedule_with_jitter_floor(self):
        from keto_tpu.client.retry import RetryPolicy, run_with_retry

        sleeps = []
        p = RetryPolicy(
            max_attempts=4, base_delay_s=0.1, multiplier=2.0,
            jitter=0.5, sleep=sleeps.append, rand=lambda: 0.0,
        )
        calls = []

        def attempt(remaining):
            calls.append(remaining)
            if len(calls) < 4:
                raise ConnectionError("down")
            return "ok"

        assert (
            run_with_retry(attempt, p, lambda e: True, timeout=None) == "ok"
        )
        # rand()=0 -> the jitter FLOOR: half the nominal delay each time
        assert sleeps == pytest.approx([0.05, 0.1, 0.2])

    def test_attempts_exhaust(self):
        from keto_tpu.client.retry import RetryPolicy, run_with_retry

        p = RetryPolicy(max_attempts=2, sleep=lambda s: None)
        with pytest.raises(ConnectionError):
            run_with_retry(
                self._always_fail, p, lambda e: True, timeout=None
            )

    @staticmethod
    def _always_fail(remaining):
        raise ConnectionError("down")

    def test_non_retryable_raises_immediately(self):
        from keto_tpu.client.retry import RetryPolicy, run_with_retry

        calls = []

        def attempt(remaining):
            calls.append(1)
            raise ValueError("bad request")

        with pytest.raises(ValueError):
            run_with_retry(
                attempt,
                RetryPolicy(sleep=lambda s: None),
                lambda e: isinstance(e, ConnectionError),
                timeout=None,
            )
        assert len(calls) == 1

    def test_deadline_is_honored_end_to_end(self):
        from keto_tpu.client.retry import RetryPolicy, run_with_retry

        clock = _FakeClock()
        slept = []

        def sleep(s):
            slept.append(s)
            clock.t += s

        p = RetryPolicy(
            max_attempts=10, base_delay_s=0.4, jitter=0.0, sleep=sleep
        )
        remainders = []

        def attempt(remaining):
            remainders.append(remaining)
            clock.t += 0.1  # each attempt costs 100ms
            raise ConnectionError("down")

        with pytest.raises(ConnectionError):
            run_with_retry(
                attempt, p, lambda e: True, timeout=1.0, clock=clock
            )
        # attempts see a SHRINKING budget, and the loop stops as soon as
        # the next backoff would cross the deadline — well before 10 tries
        assert remainders[0] == pytest.approx(1.0)
        assert all(
            a > b for a, b in zip(remainders, remainders[1:])
        )
        assert len(remainders) < 10
        assert clock.t - 100.0 <= 1.0 + 1e-6

    def test_grpc_code_matching_and_call_wiring(self):
        import grpc

        from keto_tpu.client import GrpcClient, RetryPolicy
        from keto_tpu.client.retry import grpc_retryable

        class _Code:
            def __init__(self, name):
                self.name = name

        class _Rpc(grpc.RpcError):
            def __init__(self, name):
                self._name = name

            def code(self):
                return _Code(self._name)

        assert grpc_retryable(_Rpc("UNAVAILABLE"))
        assert grpc_retryable(_Rpc("RESOURCE_EXHAUSTED"))
        assert not grpc_retryable(_Rpc("INVALID_ARGUMENT"))
        assert not grpc_retryable(ValueError("x"))

        client = GrpcClient(
            "127.0.0.1:1",
            retry=RetryPolicy(max_attempts=3, sleep=lambda s: None),
        )
        try:
            outcomes = [_Rpc("UNAVAILABLE"), _Rpc("RESOURCE_EXHAUSTED")]

            def rpc(request, timeout=None):
                if outcomes:
                    raise outcomes.pop(0)
                return "answer"

            assert client._call(rpc, object(), timeout=5.0) == "answer"

            def rpc_fatal(request, timeout=None):
                raise _Rpc("INVALID_ARGUMENT")

            with pytest.raises(grpc.RpcError):
                client._call(rpc_fatal, object(), timeout=5.0)
        finally:
            client.close()

    def test_rest_client_retries_shed_and_unavailable(self):
        from keto_tpu.client import RestClient, RetryPolicy

        codes = iter([429, 503, 200])
        seen = []

        def handler(request):
            code = next(codes)
            seen.append(code)
            if code != 200:
                return httpx.Response(
                    code,
                    json={"error": {"code": code, "message": "busy"}},
                    headers={"Retry-After": "1"},
                )
            return httpx.Response(200, json={"allowed": True})

        client = RestClient(
            "http://test",
            transport=httpx.MockTransport(handler),
            retry=RetryPolicy(max_attempts=4, sleep=lambda s: None),
        )
        try:
            assert client.check(_tup()).allowed is True
            assert seen == [429, 503, 200]
        finally:
            client.close()

    def test_rest_client_does_not_retry_client_errors(self):
        from keto_tpu.client import RestClient, RetryPolicy
        from keto_tpu.utils.errors import ErrMalformedInput

        calls = []

        def handler(request):
            calls.append(1)
            return httpx.Response(
                400, json={"error": {"code": 400, "message": "nope"}}
            )

        client = RestClient(
            "http://test",
            transport=httpx.MockTransport(handler),
            retry=RetryPolicy(max_attempts=4, sleep=lambda s: None),
        )
        try:
            with pytest.raises(ErrMalformedInput):
                client.check(_tup())
            assert len(calls) == 1
        finally:
            client.close()


# -- replica pool fault drills (integration) --------------------------------


def _pool_config():
    from keto_tpu.driver import Config

    return Config(
        values={
            "namespaces": [{"id": 1, "name": "n"}],
            "log": {"level": "error"},
            "serve": {
                "read": {"port": 0, "host": "127.0.0.1", "workers": 3},
                "write": {"port": 0, "host": "127.0.0.1"},
            },
        }
    )


@pytest.fixture()
def pool():
    from keto_tpu.driver import Registry

    reg = Registry(_pool_config())
    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()
    rp, wp = asyncio.run_coroutine_threadsafe(
        reg.start_all(), loop
    ).result(timeout=120)
    yield reg, rp, wp
    asyncio.run_coroutine_threadsafe(reg.stop_all(), loop).result(
        timeout=30
    )
    loop.call_soon_threadsafe(loop.stop)


def _converges(rp, params, want_status, tries=24, timeout=120.0):
    """Fresh connection per probe: SO_REUSEPORT spreads them over the
    replicas, so `tries` consecutive agreements cover the whole pool."""
    deadline = time.time() + timeout
    streak = 0
    while streak < tries and time.time() < deadline:
        try:
            r = httpx.get(
                f"http://127.0.0.1:{rp}/check", params=params, timeout=10
            )
            status = r.status_code
        except httpx.TransportError:
            status = -1  # replica churn mid-probe: keep probing
        if status == want_status:
            streak += 1
        else:
            streak = 0
            time.sleep(0.05)
    return streak >= tries


def _put(wp, tup):
    r = httpx.put(f"http://127.0.0.1:{wp}/relation-tuples", json=tup)
    assert r.status_code == 201


def _wait_children(pool_obj, n, timeout=30.0, dead=()):
    """Until the pool has n live children, none of them in `dead` — the
    latter matters right after a kill, when the supervisor may not have
    pruned the victim yet and the old link set still looks healthy."""
    dead = set(dead)
    deadline = time.time() + timeout
    while time.time() < deadline:
        links = list(pool_obj._children)
        pids = [l.pid for l in links]
        if (
            len(links) == n
            and all(p > 0 for p in pids)
            and not dead.intersection(pids)
        ):
            return links
        time.sleep(0.05)
    raise AssertionError(
        f"pool never reached {n} live children (dead={dead}): "
        f"{[l.pid for l in pool_obj._children]}"
    )


class TestReplicaSelfHealing:
    def test_sigkill_respawn_and_resync(self, pool):
        reg, rp, wp = pool
        pool_obj = reg._replica_pool
        assert pool_obj is not None
        links = _wait_children(pool_obj, 2)
        old_pids = {l.pid for l in links}

        # a write BEFORE the kill: the respawned replica must know it
        before = {
            "namespace": "n", "object": "pre", "relation": "view",
            "subject_id": "alice",
        }
        _put(wp, before)
        assert _converges(rp, before, 200)

        victim = links[0].pid
        os.kill(victim, signal.SIGKILL)
        # supervisor heals the pool: victim pruned, replacement spawned
        links = _wait_children(pool_obj, 2, dead={victim})
        new_pids = {l.pid for l in links}
        assert new_pids != old_pids

        # a write AFTER the respawn: the delta stream + resync handshake
        # must reach the replacement too
        after = {
            "namespace": "n", "object": "post", "relation": "view",
            "subject_id": "alice",
        }
        _put(wp, after)
        assert _converges(rp, after, 200)
        assert _converges(rp, before, 200)
        m = reg.metrics()
        assert m._metrics["keto_replica_respawns_total"].value >= 1

    def test_delta_drop_resync_refills_the_gap(self, pool):
        reg, rp, wp = pool
        pool_obj = reg._replica_pool
        _wait_children(pool_obj, 2)
        # drop exactly one frame to one replica: a silent version gap
        FAULTS.arm("delta.drop")
        dropped = {
            "namespace": "n", "object": "dropped", "relation": "view",
            "subject_id": "alice",
        }
        _put(wp, dropped)
        assert FAULTS.fired("delta.drop") == 1
        # the NEXT write arrives out of order at the gapped replica,
        # triggering its resync request; the parent replays the log
        trailer = {
            "namespace": "n", "object": "trailer", "relation": "view",
            "subject_id": "alice",
        }
        _put(wp, trailer)
        assert _converges(rp, dropped, 200)
        assert _converges(rp, trailer, 200)
        m = reg.metrics()
        assert m._metrics["keto_replica_resyncs_total"].value >= 1

    def test_inherited_replica_crash_fault_heals(self):
        """replica.crash armed BEFORE the fork is inherited by every
        replica (each crashes applying its first delta); disarming in the
        parent means respawns — which carry the parent's current fault
        snapshot — come back clean. The pool heals without intervention."""
        from keto_tpu.driver import Registry

        FAULTS.arm("replica.crash")  # inherited at fork by both children
        reg = Registry(_pool_config())
        loop = asyncio.new_event_loop()
        threading.Thread(target=loop.run_forever, daemon=True).start()
        try:
            rp, wp = asyncio.run_coroutine_threadsafe(
                reg.start_all(), loop
            ).result(timeout=120)
            pool_obj = reg._replica_pool
            old = {l.pid for l in _wait_children(pool_obj, 2)}
            # parent disarms: respawn commands ship a CLEAN snapshot
            FAULTS.disarm("replica.crash")
            tup = {
                "namespace": "n", "object": "doc", "relation": "view",
                "subject_id": "alice",
            }
            _put(wp, tup)  # both replicas crash applying this delta
            links = _wait_children(pool_obj, 2, timeout=60, dead=old)
            assert {l.pid for l in links} != old
            assert _converges(rp, tup, 200)
        finally:
            asyncio.run_coroutine_threadsafe(reg.stop_all(), loop).result(
                timeout=30
            )
            loop.call_soon_threadsafe(loop.stop)


class TestShedAtTheTransports:
    def test_rest_maps_shed_to_429_with_retry_after(self):
        """BatcherOverloaded -> HTTP 429 + Retry-After via the REST error
        middleware mapping."""
        from keto_tpu.api.rest import _json_error

        resp = _json_error(BatcherOverloaded())
        assert resp.status == 429
        assert resp.headers["Retry-After"] == "1"

    def test_rest_maps_closed_to_503_with_retry_after(self):
        from keto_tpu.api.rest import _json_error

        resp = _json_error(BatcherClosed())
        assert resp.status == 503
        assert resp.headers["Retry-After"] == "1"

    def test_grpc_abort_carries_resource_exhausted(self):
        import grpc

        from keto_tpu.api.services import _abort

        class _Ctx:
            def __init__(self):
                self.trailing = None
                self.code = None
                self.details = None

            def set_trailing_metadata(self, md):
                self.trailing = md

            def abort(self, code, details):
                self.code = code
                self.details = details
                raise RuntimeError("aborted")  # grpc aborts by raising

        ctx = _Ctx()
        with pytest.raises(RuntimeError):
            _abort(ctx, BatcherOverloaded())
        assert ctx.code == grpc.StatusCode.RESOURCE_EXHAUSTED
        assert ("retry-after", "1") in tuple(ctx.trailing)
