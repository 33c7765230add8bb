"""Performance-attribution plane e2e (PR 7): cross-process trace
propagation (client-minted W3C traceparent -> server spans, flight
records, exemplars — hedged duplicates included), the wall-clock
accounting ledger behind /debug/attribution and
keto_time_attribution_seconds_total (conservation: stages must sum to the
measured wall time), and the stdlib sampling profiler behind
/debug/pprof + tools/flame.py."""

import importlib.util
import os
import re
import threading
import time

import grpc
import httpx
import pytest

from keto_tpu.driver import Config
from keto_tpu.telemetry.attribution import (
    ATTRIBUTION_STAGES,
    UNATTRIBUTED,
    AttributionLedger,
    TimeLedger,
    current_ledger,
    ledger_mark,
    reset_current_ledger,
    set_current_ledger,
)
from keto_tpu.telemetry.tracing import (
    SpanContext,
    current_traceparent,
    format_traceparent,
    mint_traceparent,
    parse_traceparent,
)
from tests.test_api_server import ServerFixture

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def server():
    cfg = Config(
        values={
            "namespaces": [{"id": 1, "name": "videos"}],
            "serve": {
                "read": {"port": 0, "host": "127.0.0.1"},
                "write": {"port": 0, "host": "127.0.0.1"},
            },
            "log": {"level": "error"},
            # slow_ms 0: EVERY check is flight-recorded, so the tests can
            # join client trace ids against /debug/flight deterministically
            "telemetry": {"flight": {"slow_ms": 0}},
        },
        env={},
    )
    s = ServerFixture(cfg)
    yield s
    s.stop()


def _trace_id_of(traceparent: str) -> str:
    return traceparent.split("-")[1]


def _debug(server, path: str, **params):
    return httpx.get(
        f"http://127.0.0.1:{server.read_port}{path}",
        params=params,
        timeout=30,
    )


def _flight_trace_ids(server) -> dict:
    """trace_id -> list of flight records carrying it."""
    out: dict = {}
    for rec in _debug(server, "/debug/flight", n=500).json()["records"]:
        tid = rec.get("trace_id")
        if tid:
            out.setdefault(tid, []).append(rec)
    return out


def _span_trace_ids(server) -> set:
    return {
        s["trace_id"]
        for s in _debug(server, "/debug/traces", n=500).json()["spans"]
    }


class TestTraceparentHelpers:
    def test_roundtrip(self):
        tp = format_traceparent(0xABC123, 0x42)
        assert tp == f"00-{0xABC123:032x}-{0x42:016x}-01"
        ctx = parse_traceparent(tp)
        assert isinstance(ctx, SpanContext)
        assert ctx.trace_id == 0xABC123 and ctx.span_id == 0x42

    def test_mint_parses(self):
        ctx = parse_traceparent(mint_traceparent())
        assert ctx is not None
        assert ctx.trace_id != 0 and ctx.span_id != 0

    @pytest.mark.parametrize(
        "bad",
        [
            "",
            "garbage",
            "00-zz-11-01",
            "00-" + "0" * 32 + "-" + "1" * 16 + "-01",  # zero trace id
            "00-" + "1" * 32 + "-" + "0" * 16 + "-01",  # zero span id
            "00-" + "1" * 31 + "-" + "1" * 16 + "-01",  # short trace id
        ],
    )
    def test_rejects_malformed(self, bad):
        assert parse_traceparent(bad) is None

    def test_current_traceparent_requires_active_span(self):
        assert current_traceparent() is None


class TestTimeLedger:
    def test_marks_attribute_intervals(self):
        led = TimeLedger(t0=100.0)
        led.mark("admission", now=100.010)
        led.mark("queue", now=100.030)
        led.mark("kernel", now=100.031)
        assert led.stages["admission"] == pytest.approx(0.010)
        assert led.stages["queue"] == pytest.approx(0.020)
        assert led.attributed() == pytest.approx(0.031)

    def test_conservation_is_by_construction(self):
        """Stages + the explicit unattributed residual must equal wall
        time exactly — the property the bench smoke gate asserts at 95%
        end to end."""
        led = TimeLedger(t0=0.0)
        now = 0.0
        for stage, dt in [
            ("admission", 0.001),
            ("queue", 0.004),
            ("encode", 0.002),
            ("launch", 0.0005),
            ("kernel", 0.020),
            ("decode", 0.003),
            ("serialize", 0.001),
            ("reply", 0.0002),
        ]:
            now += dt
            led.mark(stage, now=now)
        wall = now + 0.0013  # some untracked tail
        agg = AttributionLedger()
        agg.record(led, wall_s=wall)
        snap = agg.snapshot()
        total = sum(
            info["seconds"] for info in snap["stages"].values()
        )
        # snapshot rounds seconds to 6dp and coverage to 4dp
        assert total == pytest.approx(wall, abs=1e-5)
        assert snap["stages"][UNATTRIBUTED]["seconds"] == pytest.approx(
            0.0013, abs=1e-6
        )
        assert snap["coverage"] == pytest.approx(
            led.attributed() / wall, abs=1e-3
        )
        assert snap["coverage"] > 0.95

    def test_snapshot_orders_canonical_stages_first(self):
        led = TimeLedger(t0=0.0)
        led.mark("kernel", now=0.5)
        agg = AttributionLedger()
        agg.record(led, wall_s=0.5)
        stages = list(agg.snapshot()["stages"])
        known = [s for s in stages if s in ATTRIBUTION_STAGES]
        assert known == [
            s for s in ATTRIBUTION_STAGES if s in set(known)
        ]

    def test_ambient_ledger_contextvar(self):
        assert current_ledger() is None
        ledger_mark("kernel")  # no ambient ledger: must be a no-op
        led = TimeLedger(t0=0.0)
        token = set_current_ledger(led)
        try:
            assert current_ledger() is led
            ledger_mark("admission")
            assert "admission" in led.stages
        finally:
            reset_current_ledger(token)
        assert current_ledger() is None


class TestRestTracePropagation:
    def test_client_traceparent_reaches_spans_flight_and_exemplars(
        self, server
    ):
        from keto_tpu.client import RestClient

        with RestClient(f"http://127.0.0.1:{server.read_port}") as c:
            res = c.check("videos:/cats#view@nobody")
        assert res.traceparent
        tid = _trace_id_of(res.traceparent)
        assert int(tid, 16) != 0

        # the same trace id must appear in server-side spans ...
        assert tid in _span_trace_ids(server)
        # ... in the flight record for this request ...
        recs = _flight_trace_ids(server)
        assert tid in recs
        assert recs[tid][0]["transport"] == "rest"
        # ... with the per-request ledger riding the record
        ledger_ms = recs[tid][0].get("ledger_ms") or {}
        assert "serialize" in ledger_ms and "reply" in ledger_ms
        # ... and in the duration histogram's OpenMetrics exemplar
        exposition = httpx.get(
            f"http://127.0.0.1:{server.read_port}/metrics",
            headers={"Accept": "application/openmetrics-text"},
        ).text
        assert tid in exposition

    def test_explicit_traceparent_is_honored(self, server):
        from keto_tpu.client import RestClient

        tp = mint_traceparent()
        with RestClient(f"http://127.0.0.1:{server.read_port}") as c:
            res = c.check("videos:/cats#view@nobody", traceparent=tp)
        assert res.traceparent == tp
        assert _trace_id_of(tp) in _flight_trace_ids(server)

    def test_batch_check_carries_trace(self, server):
        from keto_tpu.client import RestClient

        tp = mint_traceparent()
        with RestClient(f"http://127.0.0.1:{server.read_port}") as c:
            c.batch_check(
                ["videos:/cats#view@a", "videos:/cats#view@b"],
                traceparent=tp,
            )
        recs = _flight_trace_ids(server)
        assert _trace_id_of(tp) in recs
        assert recs[_trace_id_of(tp)][0]["transport"] == "rest_batch"


class TestGrpcTracePropagation:
    def test_grpc_check_joins_client_trace(self, server):
        from keto_tpu.client import GrpcClient

        with GrpcClient(f"127.0.0.1:{server.read_port}") as g:
            res = g.check("videos:/cats#view@nobody")
        tid = _trace_id_of(res.traceparent)
        assert tid in _span_trace_ids(server)
        recs = _flight_trace_ids(server)
        assert tid in recs
        assert recs[tid][0]["transport"] == "grpc"

    def test_hedged_duplicate_shares_trace_and_is_tagged(self, server):
        """The hedged-duplicate case: one traceparent, two server-side
        requests, the reissue alone tagged hedge — so the operator can
        tell them apart while correlating both to the one client call."""
        from keto_tpu.client import GrpcClient, HedgePolicy, Hedger
        from keto_tpu.faults import FAULTS

        # the primary rides a one-shot 300ms replica stall; the hedge
        # fires at 30ms, dodges it, and wins
        FAULTS.arm_slow("replica.slow", sleep_ms=300, times=1)
        try:
            with GrpcClient(f"127.0.0.1:{server.read_port}") as g:
                with Hedger(HedgePolicy(delay_s=0.03)) as h:
                    out = g.check_hedged("videos:/cats#view@nobody", h)
        finally:
            FAULTS.disarm("replica.slow")
        assert out.hedged is True
        tid = _trace_id_of(out.result.traceparent)

        # both attempts eventually finish server-side; wait for both
        # flight records (the stalled primary lands ~300ms later)
        deadline = time.monotonic() + 5.0
        recs = []
        while time.monotonic() < deadline:
            recs = _flight_trace_ids(server).get(tid, [])
            if len(recs) >= 2:
                break
            time.sleep(0.05)
        assert len(recs) == 2, f"expected 2 flight records, got {recs}"
        hedge_flags = sorted(bool(r.get("hedge")) for r in recs)
        assert hedge_flags == [False, True]
        assert tid in _span_trace_ids(server)


class TestAttributionEndpoint:
    def test_ledger_conservation_under_slowness(self, server):
        """The acceptance property, end to end: with slowness faults
        armed, /debug/attribution must still decompose batch-check wall
        time into named stages summing to >= 95% of measured wall."""
        from keto_tpu.client import GrpcClient, RestClient
        from keto_tpu.faults import FAULTS

        # both slowness seams, as in the bench tail phase: replica.slow
        # fires on any path; device.slow only in the sharded serving
        # engine's launch, which this server (no engine.sharding) never runs
        FAULTS.arm_slow("device.slow", sleep_ms=20, times=3)
        FAULTS.arm_slow("replica.slow", sleep_ms=20, times=3)
        try:
            with RestClient(
                f"http://127.0.0.1:{server.read_port}"
            ) as rc:
                rc.batch_check(
                    [f"videos:/cats#view@u{i}" for i in range(32)]
                )
            with GrpcClient(f"127.0.0.1:{server.read_port}") as g:
                for i in range(8):
                    g.check(f"videos:/cats#view@w{i}")
        finally:
            FAULTS.disarm("device.slow")
            FAULTS.disarm("replica.slow")

        payload = _debug(server, "/debug/attribution").json()
        snap = payload["attribution"]
        assert snap["requests"] > 0
        assert snap["coverage"] >= 0.95
        # conservation: stages (incl. the explicit residual) sum to wall
        total = sum(
            info["seconds"] for info in snap["stages"].values()
        )
        # stage seconds are rounded to 6dp each in the snapshot
        assert total == pytest.approx(snap["wall_s"], abs=1e-4)
        # the serving stages the transports mark must be present
        for stage in ("serialize", "reply"):
            assert stage in snap["stages"]
        # the engine built at boot reports its phase split alongside
        phases = payload.get("closure_build_phases")
        if phases:
            assert "total" in phases

    def test_attribution_counter_exposed(self, server):
        body = httpx.get(
            f"http://127.0.0.1:{server.read_port}/metrics"
        ).text
        assert "keto_time_attribution_seconds_total" in body
        assert 'stage="serialize"' in body


class TestSamplingProfiler:
    def test_samples_fold_and_overhead_stays_bounded(self):
        from keto_tpu.telemetry.profiler import SamplingProfiler

        stop = threading.Event()

        def busy():
            while not stop.is_set():
                sum(i * i for i in range(2000))

        worker = threading.Thread(target=busy, name="busy-worker")
        worker.start()
        prof = SamplingProfiler(hz=67.0)
        prof.start()
        try:
            time.sleep(0.6)
        finally:
            prof.stop()
            stop.set()
            worker.join(timeout=5)
        snap = prof.snapshot()
        assert snap["samples"] > 5
        assert snap["self_overhead"] < 0.05  # the acceptance budget
        folds = prof.folded()
        assert any(k.startswith("busy-worker;") for k in folds)
        # folded text is the classic `stack count` line format
        for line in prof.folded_text().splitlines():
            assert re.fullmatch(r".+ \d+", line)
        # tree value equals total folded samples
        assert prof.tree()["value"] == sum(folds.values())

    def test_bounded_fold_table_truncates(self):
        """With max_stacks=1, distinct stacks beyond the first land in
        the [truncated] overflow bucket instead of growing the table."""
        from keto_tpu.telemetry.profiler import SamplingProfiler

        stop = threading.Event()

        def loop_a():
            while not stop.is_set():
                time.sleep(0.01)

        def loop_b():
            while not stop.is_set():
                time.sleep(0.01)

        threads = [
            threading.Thread(target=loop_a, name="fold-a"),
            threading.Thread(target=loop_b, name="fold-b"),
        ]
        for t in threads:
            t.start()
        prof = SamplingProfiler(hz=67.0, max_stacks=1)
        try:
            for _ in range(10):
                prof._sample_once()
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=5)
        folds = prof.folded()
        # one real entry at most, everything else overflowed
        assert len(folds) <= 2
        assert folds.get("[truncated]", 0) > 0
        assert prof.snapshot()["truncated_stacks"] > 0

    def test_pprof_endpoint_on_demand_capture(self, server):
        r = _debug(server, "/debug/pprof", seconds=0.3)
        assert r.status_code == 200
        doc = r.json()
        assert doc["profiler"]["samples"] > 0
        # every sample lands in exactly one stack, so the tree root's
        # subtree total equals the sample count
        assert doc["tree"]["value"] == doc["profiler"]["samples"]
        folded = _debug(server, "/debug/pprof", format="folded")
        assert folded.status_code == 200
        assert folded.text.strip()  # server threads always have frames


class TestFlameTool:
    def _flame(self):
        spec = importlib.util.spec_from_file_location(
            "flame", os.path.join(_REPO, "tools", "flame.py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_folded_to_html(self):
        flame = self._flame()
        folds = flame.parse_folded(
            "main;engine:check 42\nmain;api:reply 10\nbad line\n"
        )
        assert folds == {
            ("main", "engine:check"): 42,
            ("main", "api:reply"): 10,
        }
        tree = flame.build_tree(folds)
        assert tree["value"] == 52
        html = flame.render_html(tree)
        assert "<svg" in html and "engine:check" in html
        svg = flame.render_svg(tree)
        assert svg.startswith("<svg") and svg.endswith("</svg>")

    def test_profiler_folded_feeds_flame(self, server):
        flame = self._flame()
        text = _debug(server, "/debug/pprof", format="folded").text
        folds = flame.parse_folded(text)
        assert folds
        html = flame.render_html(flame.build_tree(folds))
        assert "<svg" in html


# -- a served BatchCheck, accounted from gRPC's hand-off to the reply -------

_ENGINE_STAGES_SEEN = ("lookup", "gather", "dispatch", "device_wait",
                       "finish")
_ARRIVAL = ("pool_wait", "deserialize", "parse")
# users in more groups than the closure query's row width (l_max 32) take
# the host oracle
_WIDE_GROUPS = 40


def _closure_store_tuples():
    from keto_tpu.relationtuple import RelationTuple

    rows = [f"groups:g{i}#member@alice" for i in range(_WIDE_GROUPS)]
    rows += [
        f"docs:d0#view@(groups:g{i}#member)" for i in range(_WIDE_GROUPS)
    ]
    rows += [
        "groups:g0#member@bob",
        "docs:d1#view@carol",
        "docs:d2#view@(groups:g0#member)",
    ]
    return [RelationTuple.from_string(r) for r in rows]


@pytest.fixture(scope="module")
def closure_server():
    """A read plane over the closure engine with queries on the device
    (JAX's CPU device here), holding one user too wide for the query."""
    cfg = Config(
        values={
            "namespaces": [
                {"id": 1, "name": "groups"},
                {"id": 2, "name": "docs"},
            ],
            "serve": {
                "read": {"port": 0, "host": "127.0.0.1"},
                "write": {"port": 0, "host": "127.0.0.1"},
            },
            "log": {"level": "error"},
            "engine": {"mode": "closure", "query_mode": "device"},
        },
        env={},
    )
    from keto_tpu.driver import Registry

    registry = Registry(cfg)
    # loaded before boot, so the boot's closure holds the tuples (writes
    # after it would be answered by the write overlay)
    registry.store().write_relation_tuples(*_closure_store_tuples())
    s = ServerFixture(registry)
    yield s
    s.stop()


def _columnar_batch_check(port: int, rows) -> list:
    """One columnar BatchCheck of ``(namespace, object, relation,
    subject_id)`` rows over gRPC."""
    from keto_tpu.api import check_service_pb2
    from keto_tpu.client import GrpcClient

    req = check_service_pb2.BatchCheckRequest(
        namespaces=[r[0] for r in rows],
        objects=[r[1] for r in rows],
        relations=[r[2] for r in rows],
        subject_ids=[r[3] for r in rows],
    )
    with GrpcClient(f"127.0.0.1:{port}") as g:
        return list(g.check_service.BatchCheck(req).allowed)


def _stage_window(server, send) -> tuple[dict, dict]:
    """Stage seconds the ledger gained while ``send()`` ran, and the
    window's snapshot totals (requests, wall, attributed)."""
    att = server.registry.attribution()
    before = att.snapshot()
    send()
    after = att.snapshot()
    stages = {
        k: v["seconds"] - before["stages"].get(k, {}).get("seconds", 0.0)
        for k, v in after["stages"].items()
    }
    totals = {
        k: after[k] - before[k]
        for k in ("requests", "wall_s", "attributed_s")
    }
    return stages, totals


class TestServedBatchCheckStages:
    def test_stages_from_arrival_to_reply(self, closure_server):
        port = closure_server.read_port
        # warm-up: the first call builds the closure and compiles
        assert _columnar_batch_check(
            port, [("docs", "d2", "view", "bob")]
        ) == [True]
        windows = []
        for i in range(7):
            # fresh subjects each call, so the result cache misses; 128
            # rows, one query program shape for every call
            batch = [("docs", "d2", "view", "bob")] + [
                ("docs", f"d{1 + j % 2}", "view", f"nobody{j}-{i}")
                for j in range(127)
            ]
            windows.append(_stage_window(
                closure_server,
                lambda: _columnar_batch_check(port, batch),
            ))
        residue = []
        for stages, totals in windows:
            assert totals["requests"] == 1
            for s in _ARRIVAL + _ENGINE_STAGES_SEEN + ("admission",):
                assert stages.get(s, 0.0) > 0, (s, stages)
            assert all(v >= 0 for v in stages.values())
            assert stages.get("oracle", 0.0) == 0
            engine = sum(stages[s] for s in _ENGINE_STAGES_SEEN)
            residue.append(stages.get("kernel", 0.0) / engine)
        wall = sum(t["wall_s"] for _, t in windows)
        assert sum(t["attributed_s"] for _, t in windows) / wall >= 0.95
        # the batcher's trailing mark holds only the engine call's
        # residue (median call: a preempted call may read more)
        assert sorted(residue)[len(residue) // 2] < 0.01, residue

    def test_overflow_row_shows_oracle(self, closure_server):
        port = closure_server.read_port

        def send():
            assert _columnar_batch_check(
                port,
                [("docs", "d0", "view", "alice"),
                 ("docs", "d2", "view", "bob"),
                 ("docs", "d1", "view", "carol"),
                 ("docs", "d1", "view", "dave")],
            ) == [True, True, True, False]

        eng = closure_server.registry.check_engine()
        oracle_rows = getattr(eng, "primary", eng).n_oracle_rows
        stages, _ = _stage_window(closure_server, send)
        assert stages.get("oracle", 0.0) > 0, stages
        assert getattr(eng, "primary", eng).n_oracle_rows == oracle_rows + 1
        for s in _ENGINE_STAGES_SEEN:
            assert stages.get(s, 0.0) > 0, (s, stages)

    def test_device_query_counts_its_transfers(self, closure_server):
        from keto_tpu.telemetry.devstats import DEVSTATS

        def exported():
            text = closure_server.registry.metrics().expose()
            return {
                d: float(v)
                for d, v in re.findall(
                    r'keto_device_transfer_bytes_total\{direction="(\w+)"\}'
                    r" (\S+)",
                    text,
                )
            }

        port = closure_server.read_port
        batch = [("docs", "d1", "view", f"counted{j}") for j in range(128)]
        before, before_x = DEVSTATS.panel()["transfer_bytes"], exported()
        assert _columnar_batch_check(port, batch) == [False] * 128
        after, after_x = DEVSTATS.panel()["transfer_bytes"], exported()
        # operands: two int32 index blocks, extra and depth (int32) and
        # direct (bool) of 128 rows; answers: one per padded row
        assert after["h2d"] - before["h2d"] >= 128 * (4 + 4 + 4 + 4 + 1)
        assert after["d2h"] - before["d2h"] >= 128
        for d in ("h2d", "d2h"):
            assert after_x[d] - before_x.get(d, 0.0) == pytest.approx(
                after[d] - before[d]
            )

    def test_device_query_counts_its_dispatches(self, closure_server):
        from keto_tpu.telemetry.devstats import DEVSTATS

        def exported():
            text = closure_server.registry.metrics().expose()
            m = re.search(r"^keto_device_query_dispatches_total (\S+)$",
                          text, re.M)
            return float(m.group(1)) if m else 0.0

        port = closure_server.read_port
        batch = [("docs", "d1", "view", f"dispatched{j}") for j in range(64)]
        before, before_x = DEVSTATS.panel()["dispatches"], exported()
        assert _columnar_batch_check(port, batch) == [False] * 64
        after, after_x = DEVSTATS.panel()["dispatches"], exported()
        # 64 narrow rows: one fan-out class, one closure_query dispatch
        assert after - before == 1
        assert after_x - before_x == after - before


class TestArrivalLedger:
    def test_record_adopts_the_arrival_ledger_once(self):
        from keto_tpu.telemetry.attribution import (
            close_arrival,
            open_arrival,
            take_arrival_ledger,
        )
        from keto_tpu.telemetry.flight import CheckTelemetry

        agg = AttributionLedger()
        tel = CheckTelemetry(attribution=agg)
        t_call = time.perf_counter() - 1e-3
        arrival = TimeLedger(t_call)
        arrival.mark("pool_wait", t_call + 1e-4)
        arrival.mark("deserialize", t_call + 2e-4)
        token = open_arrival(arrival)
        try:
            with tel.record_check("grpc_batch", batch_size=3) as rec:
                assert rec.ledger is arrival
                assert current_ledger() is arrival
                # admission starts where the record opened, as before
                assert arrival.last == rec._t0
                assert arrival.stages["parse"] == pytest.approx(
                    rec._t0 - (t_call + 2e-4)
                )
                ledger_mark("admission")
            # adopted once: a second record on the call opens its own
            assert take_arrival_ledger() is None
        finally:
            close_arrival(token)
        snap = agg.snapshot()
        # the accounted wall runs from the arrival
        assert snap["wall_s"] >= 2e-4
        assert list(snap["stages"])[:4] == [
            "pool_wait", "deserialize", "parse", "admission",
        ]

    def test_record_without_arrival_keeps_its_own_ledger(self):
        from keto_tpu.telemetry.flight import CheckTelemetry

        tel = CheckTelemetry(attribution=AttributionLedger())
        with tel.record_check("rest") as rec:
            assert rec.ledger.t0 == rec._t0
            assert not rec.ledger.stages


class TestEngineStageSpans:
    def test_trace_names_and_ledger_stages_agree(self):
        from keto_tpu.engine.stages import TRACE_NAMES, engine_stage
        from keto_tpu.telemetry.attribution import ENGINE_STAGES

        assert set(ENGINE_STAGES) <= set(ATTRIBUTION_STAGES)
        assert [s for s in ATTRIBUTION_STAGES if s in ENGINE_STAGES] == list(
            ENGINE_STAGES
        )
        assert TRACE_NAMES == {s: f"keto.{s}" for s in ENGINE_STAGES}
        led = TimeLedger()
        token = set_current_ledger(led)
        try:
            for s in ENGINE_STAGES:
                with engine_stage(s):
                    pass
        finally:
            reset_current_ledger(token)
        assert set(led.stages) <= set(ENGINE_STAGES)
        with pytest.raises(KeyError):
            engine_stage("not_a_stage")

    def test_profiler_trace_holds_the_engine_stages(self, tmp_path):
        """With a profiler session on, each engine stage the ledger marks
        is a ``keto.<stage>`` host event in the trace."""
        import glob

        import jax
        from jax.profiler import ProfileData

        from keto_tpu.engine.closure import ClosureCheckEngine
        from keto_tpu.graph import SnapshotManager
        from keto_tpu.relationtuple.columns import CheckColumns
        from keto_tpu.store import InMemoryTupleStore
        from keto_tpu.telemetry.attribution import ENGINE_STAGES

        store = InMemoryTupleStore()
        store.write_relation_tuples(*_closure_store_tuples())
        eng = ClosureCheckEngine(
            SnapshotManager(store), max_depth=5, query_mode="device"
        )
        cols = CheckColumns(
            ["docs"] * 3, ["d0", "d0", "d1"], ["view"] * 3,
            ["alice", "bob", "nobody"],
        ).validate()
        assert eng.batch_check_columns(cols) == [True, True, False]  # warm
        led = TimeLedger()
        token = set_current_ledger(led)
        jax.profiler.start_trace(str(tmp_path))
        try:
            assert eng.batch_check_columns(cols) == [True, True, False]
        finally:
            jax.profiler.stop_trace()
            reset_current_ledger(token)
        (path,) = glob.glob(
            str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")
        )
        names = {
            e.name
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines
            for e in line.events
            if e.name.startswith("keto.")
        }
        assert set(led.stages) == set(ENGINE_STAGES)
        assert names == {f"keto.{s}" for s in ENGINE_STAGES}
