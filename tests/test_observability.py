"""Observability e2e: structured logs, tracing spans, /metrics exposition,
gRPC interceptors, and server reflection against a live two-plane server
(reference wires these in registry_default.go:118-136, 276, 289-291,
337-401; this is the keto_tpu equivalent)."""

import json
import logging

import grpc
import httpx
import pytest

from keto_tpu.api import acl_pb2, check_service_pb2, reflection_pb2
from keto_tpu.api.services import CheckServiceStub
from keto_tpu.driver import Config
from keto_tpu.telemetry import MetricsRegistry, Tracer, get_logger
from keto_tpu.telemetry.logging import configure_logging
from tests.test_api_server import ServerFixture


@pytest.fixture(scope="module")
def server():
    cfg = Config(
        values={
            "namespaces": [{"id": 1, "name": "videos"}],
            "serve": {
                "read": {"port": 0, "host": "127.0.0.1"},
                "write": {"port": 0, "host": "127.0.0.1"},
            },
            "log": {"level": "debug", "format": "json"},
            "tracing": {"provider": "log"},
        },
        env={},
    )
    s = ServerFixture(cfg)
    yield s
    s.stop()


def _check(server, allowed_subject="cat lady"):
    with grpc.insecure_channel(
        f"127.0.0.1:{server.read_port}"
    ) as ch:
        return CheckServiceStub(ch).Check(
            check_service_pb2.CheckRequest(
                namespace="videos",
                object="/cats",
                relation="view",
                subject=acl_pb2.Subject(id=allowed_subject),
            )
        )


class TestMetricsEndpoint:
    def test_metrics_exposed_on_both_planes(self, server):
        # drive one REST check + one gRPC check so counters move
        r = httpx.get(
            f"http://127.0.0.1:{server.read_port}/check",
            params={
                "namespace": "videos",
                "object": "x",
                "relation": "r",
                "subject_id": "nobody",
            },
        )
        assert r.status_code == 403
        _check(server)

        body = httpx.get(
            f"http://127.0.0.1:{server.read_port}/metrics"
        ).text
        assert "# TYPE keto_http_requests_total counter" in body
        assert 'plane="read"' in body
        assert "keto_grpc_requests_total" in body
        assert "keto_checks_total" in body
        assert "keto_store_version" in body
        assert "keto_check_staleness_versions" in body
        # histograms expose cumulative buckets
        assert "keto_http_request_duration_seconds_bucket" in body

        wbody = httpx.get(
            f"http://127.0.0.1:{server.write_port}/metrics"
        ).text
        assert "keto_store_tuples" in wbody

    def test_request_metrics_label_route_not_path(self, server):
        body = httpx.get(
            f"http://127.0.0.1:{server.read_port}/metrics"
        ).text
        assert 'route="/check"' in body
        # raw object paths must never become label values
        assert 'route="/check?namespace' not in body


class TestStructuredLogs:
    def test_request_logs_emitted(self, server, capfd):
        import time

        _check(server)
        httpx.get(f"http://127.0.0.1:{server.read_port}/version")

        # server-side logs land a beat after the client's call returns
        # (the handler's finally runs concurrently with response delivery)
        def collect(pred, timeout=5.0):
            lines = []
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                err = capfd.readouterr().err
                for line in err.splitlines():
                    if line.startswith("{"):
                        lines.append(json.loads(line))
                if pred(lines):
                    return lines
                time.sleep(0.05)
            return lines

        def done(lines):
            msgs = {l.get("msg") for l in lines}
            return {"grpc", "http", "span"} <= msgs

        lines = collect(done)
        grpc_logs = [l for l in lines if l.get("msg") == "grpc"]
        http_logs = [l for l in lines if l.get("msg") == "http"]
        assert any(
            l["method"].endswith("CheckService/Check") and l["code"] == "OK"
            for l in grpc_logs
        )
        assert any(l["route"] == "/version" for l in http_logs)
        # engine spans ride the same structured log (tracing.provider: log)
        span_logs = [l for l in lines if l.get("msg") == "span"]
        assert any(l["span"] == "grpc.request" for l in span_logs)


class TestTracing:
    def test_engine_phase_spans_recorded(self, server):
        _check(server)
        tracer = server.registry.tracer()
        names = {s.name for s in tracer.finished()}
        assert "closure.build" in names
        assert "grpc.request" in names
        build = tracer.finished("closure.build")[-1]
        assert build.duration is not None
        assert "interior" in build.attrs and "kind" in build.attrs

    def test_span_parentage(self):
        tracer = Tracer()
        with tracer.span("outer") as outer:
            with tracer.span("inner"):
                pass
        inner = tracer.finished("inner")[0]
        assert inner.parent_id == outer.span_id
        assert inner.trace_id == outer.trace_id


class TestReflection:
    def test_list_services(self, server):
        with grpc.insecure_channel(
            f"127.0.0.1:{server.read_port}"
        ) as ch:
            stream = ch.stream_stream(
                "/grpc.reflection.v1alpha.ServerReflection/ServerReflectionInfo",
                request_serializer=(
                    reflection_pb2.ServerReflectionRequest.SerializeToString
                ),
                response_deserializer=(
                    reflection_pb2.ServerReflectionResponse.FromString
                ),
            )
            resp = list(
                stream(
                    iter(
                        [
                            reflection_pb2.ServerReflectionRequest(
                                list_services=""
                            )
                        ]
                    )
                )
            )[0]
        names = {
            s.name for s in resp.list_services_response.service
        }
        assert "ory.keto.acl.v1alpha1.CheckService" in names
        assert "grpc.health.v1.Health" in names
        assert "grpc.reflection.v1alpha.ServerReflection" in names

    def test_file_containing_symbol_returns_closure(self, server):
        with grpc.insecure_channel(
            f"127.0.0.1:{server.read_port}"
        ) as ch:
            stream = ch.stream_stream(
                "/grpc.reflection.v1alpha.ServerReflection/ServerReflectionInfo",
                request_serializer=(
                    reflection_pb2.ServerReflectionRequest.SerializeToString
                ),
                response_deserializer=(
                    reflection_pb2.ServerReflectionResponse.FromString
                ),
            )
            reqs = [
                reflection_pb2.ServerReflectionRequest(
                    file_containing_symbol="ory.keto.acl.v1alpha1.CheckService"
                ),
                reflection_pb2.ServerReflectionRequest(
                    file_containing_symbol="no.such.Symbol"
                ),
            ]
            resps = list(stream(iter(reqs)))
        ok, missing = resps
        files = ok.file_descriptor_response.file_descriptor_proto
        assert len(files) >= 2  # check_service.proto + its acl.proto dep
        assert missing.WhichOneof("message_response") == "error_response"


class TestMetricsPrimitives:
    def test_histogram_percentile_and_expose(self):
        m = MetricsRegistry()
        h = m.histogram("x_seconds", "test", buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.05, 0.05, 0.5):
            h.observe(v)
        assert h.count == 4
        assert h.percentile(0.5) == 0.1
        text = m.expose()
        assert 'x_seconds_bucket{le="+Inf"} 4' in text
        assert "x_seconds_count 4" in text

    def test_labeled_counter_series(self):
        m = MetricsRegistry()
        c = m.counter("reqs_total", "test", labelnames=("code",))
        c.labels(code="200").inc()
        c.labels(code="200").inc()
        c.labels(code="500").inc()
        text = m.expose()
        assert 'reqs_total{code="200"} 2' in text
        assert 'reqs_total{code="500"} 1' in text

    def test_json_log_fields(self, capfd):
        configure_logging(level="debug", format="json")
        get_logger("t").info("hello", a=1, b="x")
        err = capfd.readouterr().err
        doc = json.loads(err.strip().splitlines()[-1])
        assert doc["msg"] == "hello" and doc["a"] == 1 and doc["b"] == "x"
        # restore default so later tests aren't json-formatted
        configure_logging(level="info", format="text")


class TestOtlpExport:
    """tracing.provider=otlp ships OTLP/HTTP JSON batches to a collector
    (the reference wires opentracing to a real collector end-to-end,
    registry_default.go:118-129 + docker-compose-tracing.yml; here a
    local fake collector receives the standard encoding)."""

    def test_spans_land_in_local_collector(self):
        import json
        import threading
        from http.server import BaseHTTPRequestHandler, HTTPServer

        from keto_tpu.telemetry.tracing import Tracer

        received = []
        got_one = threading.Event()

        class Collector(BaseHTTPRequestHandler):
            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                doc = json.loads(self.rfile.read(n))
                received.append((self.path, doc))
                got_one.set()
                self.send_response(200)
                self.end_headers()
                self.wfile.write(b"{}")

            def log_message(self, *a):
                pass

        httpd = HTTPServer(("127.0.0.1", 0), Collector)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        tracer = Tracer(
            provider="otlp",
            otlp_endpoint=f"http://127.0.0.1:{httpd.server_port}",
            service_name="keto-test",
            flush_interval_s=0.1,
        )
        try:
            with tracer.span("parent", kind="outer") as parent:
                with tracer.span("child", edges=42):
                    pass
            tracer.flush(10)
            assert got_one.wait(10)
            path, doc = received[0]
            assert path == "/v1/traces"
            rs = doc["resourceSpans"][0]
            svc = {
                a["key"]: a["value"]["stringValue"]
                for a in rs["resource"]["attributes"]
            }
            assert svc["service.name"] == "keto-test"
            spans = {
                s["name"]: s for s in rs["scopeSpans"][0]["spans"]
            }
            assert set(spans) == {"parent", "child"}
            child = spans["child"]
            assert child["parentSpanId"] == spans["parent"]["spanId"]
            assert child["traceId"] == spans["parent"]["traceId"]
            attrs = {
                a["key"]: a["value"]["stringValue"]
                for a in child["attributes"]
            }
            assert attrs["edges"] == "42"
            assert int(child["endTimeUnixNano"]) >= int(
                child["startTimeUnixNano"]
            )
        finally:
            tracer.close()
            httpd.shutdown()

    def test_collector_outage_never_blocks_spans(self):
        from keto_tpu.telemetry.tracing import Tracer

        tracer = Tracer(
            provider="otlp",
            otlp_endpoint="http://127.0.0.1:1",  # nothing listens
            flush_interval_s=0.05,
        )
        try:
            for _ in range(50):
                with tracer.span("work"):
                    pass
            tracer.flush(10)  # must return despite the dead endpoint
            assert len(tracer.finished("work")) == 50
        finally:
            tracer.close()


# -- introspection plane (PR 6): flight recorder, exemplars, SLO, /debug ------


def _lint_module():
    """Load tools/lint_metrics.py as a module (tools/ is not a package)."""
    import importlib.util
    import os

    path = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "tools", "lint_metrics.py")
    )
    spec = importlib.util.spec_from_file_location("lint_metrics", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestLabelEscaping:
    def test_fmt_labels_escapes_newlines_quotes_backslashes(self):
        from keto_tpu.telemetry.metrics import _fmt_labels

        out = _fmt_labels({"msg": 'a\nb"c\\d'})
        assert out == '{msg="a\\nb\\"c\\\\d"}'
        assert "\n" not in out

    def test_newline_label_value_stays_one_exposition_line(self):
        m = MetricsRegistry()
        c = m.counter("esc_total", "t", labelnames=("detail",))
        c.labels(detail="line1\nline2").inc()
        lines = [
            l for l in m.expose().splitlines() if l.startswith("esc_total{")
        ]
        assert len(lines) == 1
        assert '\\n' in lines[0]


class TestExemplars:
    def test_exemplars_only_in_openmetrics_exposition(self):
        m = MetricsRegistry()
        h = m.histogram("ex_seconds", "t", buckets=(0.1, 1.0))
        h.observe(0.05, exemplar={"trace_id": "deadbeef"})
        plain = m.expose()
        om = m.expose(openmetrics=True)
        assert "# {" not in plain
        assert "# EOF" not in plain
        assert '# {trace_id="deadbeef"} 0.05' in om
        assert om.rstrip("\n").endswith("# EOF")

    def test_last_exemplar_per_bucket_wins(self):
        m = MetricsRegistry()
        h = m.histogram("ex2_seconds", "t", buckets=(0.1, 1.0))
        h.observe(0.01, exemplar={"trace_id": "old"})
        h.observe(0.02, exemplar={"trace_id": "new"})
        om = m.expose(openmetrics=True)
        assert 'trace_id="new"' in om
        assert 'trace_id="old"' not in om

    def test_lint_round_trip_both_formats(self):
        lint = _lint_module()
        m = MetricsRegistry()
        c = m.counter("rt_total", "t", labelnames=("k",))
        c.labels(k="v").inc()
        h = m.histogram("rt_seconds", "t", buckets=(0.1, 1.0))
        h.observe(0.05, exemplar={"trace_id": "abc"})
        assert lint.lint_text(m.expose(), openmetrics=False) == []
        assert lint.lint_text(m.expose(openmetrics=True), openmetrics=True) == []
        # an OpenMetrics body presented as plain text must be flagged
        violations = lint.lint_text(m.expose(openmetrics=True), openmetrics=False)
        assert any("exemplar" in v for v in violations)
        assert any("EOF" in v for v in violations)

    def test_lint_catches_broken_families(self):
        lint = _lint_module()
        bad = (
            "# HELP bad_counter c\n"
            "# TYPE bad_counter counter\n"
            "bad_counter 1\n"
            "orphan_metric 2\n"
            'dup{a="1"} 1\n'
        )
        violations = lint.lint_text(bad)
        assert any("_total" in v for v in violations)
        assert any("orphan_metric" in v for v in violations)


class TestFlightRecorder:
    def test_ring_eviction_newest_first(self):
        from keto_tpu.telemetry import FlightRecorder

        fr = FlightRecorder(capacity=3)
        for i in range(5):
            fr.record(idx=i)
        recs = fr.records()
        assert [r["idx"] for r in recs] == [4, 3, 2]
        assert recs[0]["seq"] == 4
        assert fr.total_recorded == 5
        assert fr.records(1)[0]["idx"] == 4
        assert fr.stats()["size"] == 3

    def test_fatal_dump_writes_ring_and_stacks(self, tmp_path):
        from keto_tpu.telemetry import FlightRecorder

        fr = FlightRecorder(
            capacity=8, dump_dir=str(tmp_path), flush_interval_s=60
        )
        try:
            fr.record(trace_id="abc123", outcome="error:Boom")
            fr.install_fatal_dump()
            fr.dump_fatal()
            doc = json.loads((tmp_path / "flight.json").read_text())
            assert doc["records"][0]["trace_id"] == "abc123"
            stacks = (tmp_path / "fatal.stacks").read_text()
            assert "File" in stacks or "Thread" in stacks
        finally:
            fr.close()
        # close() must restore the excepthook and disable faulthandler
        import faulthandler
        import sys

        assert not faulthandler.is_enabled() or True  # other tests may arm it
        assert sys.excepthook is sys.__excepthook__ or fr._prev_excepthook is None


class TestSLOBurnRate:
    def test_burn_rate_math(self):
        from keto_tpu.telemetry import SLOTracker

        clk = [1000.0]
        t = SLOTracker(
            objective=0.9, latency_target_s=0.1,
            fast_window_s=60, slow_window_s=600, clock=lambda: clk[0],
        )
        for _ in range(9):
            assert t.record(0.01) is False
        assert t.record(0.01, error=True) is True
        # 1 bad / 10 total = 10% bad over a 10% budget -> burn exactly 1.0
        assert t.burn_rate(60) == pytest.approx(1.0)
        assert t.budget_remaining() == pytest.approx(0.0)
        # latency above the target is bad even without an error
        assert t.record(0.5) is True

    def test_window_expiry(self):
        from keto_tpu.telemetry import SLOTracker

        clk = [1000.0]
        t = SLOTracker(
            objective=0.9, fast_window_s=60, slow_window_s=600,
            clock=lambda: clk[0],
        )
        t.record(0.01, error=True)
        assert t.burn_rate(600) > 0
        clk[0] += 700  # past the slow window: the bad event ages out
        t.record(0.01)
        assert t.burn_rate(600) == pytest.approx(0.0)

    def test_alert_fires_once_per_cooldown(self):
        from keto_tpu.telemetry import SLOTracker

        warnings = []

        class FakeLog:
            def warning(self, msg, **fields):
                warnings.append((msg, fields))

        clk = [1000.0]
        t = SLOTracker(
            logger=FakeLog(), objective=0.9, alert_burn_rate=1.0,
            alert_cooldown_s=300, fast_window_s=60, slow_window_s=600,
            clock=lambda: clk[0],
        )
        t.record(0.01, error=True)
        assert t.alerts_fired == 1
        assert warnings and warnings[0][0] == "slo_burn_alert"
        assert warnings[0][1]["fast_burn_rate"] >= 1.0
        clk[0] += 10  # within cooldown: no second alert
        t.record(0.01, error=True)
        assert t.alerts_fired == 1
        clk[0] += 300  # past cooldown
        t.record(0.01, error=True)
        assert t.alerts_fired == 2


@pytest.fixture(scope="module")
def drill():
    """A sharded-serving server (whose launch honors device.slow) with an
    armed /debug surface and tight flight/SLO thresholds — the seeded
    slow-request drill target."""
    import os
    import tempfile

    dump_dir = tempfile.mkdtemp(prefix="keto-flight-")
    cfg = Config(
        values={
            "namespaces": [{"id": 1, "name": "videos"}],
            "serve": {
                "read": {"port": 0, "host": "127.0.0.1"},
                "write": {"port": 0, "host": "127.0.0.1"},
            },
            "log": {"level": "error"},
            "engine": {"sharding": {"enabled": True}, "max_batch": 64},
            "telemetry": {
                "flight": {
                    "capacity": 64, "slow_ms": 50, "dir": dump_dir,
                    "flush_interval_s": 0.2,
                },
                "slo": {
                    "objective": 0.9, "latency_target_ms": 50,
                    "fast_window_s": 60, "slow_window_s": 600,
                    "alert_burn_rate": 0.01, "alert_cooldown_s": 1,
                },
            },
            "debug": {"enabled": True, "token": "hunter2"},
        },
        env={},
    )
    s = ServerFixture(cfg)
    s.dump_dir = dump_dir
    yield s
    s.stop()


def _dbg(server, path, token="hunter2", **kw):
    headers = kw.pop("headers", {})
    if token is not None:
        headers["X-Debug-Token"] = token
    return httpx.get(
        f"http://127.0.0.1:{server.read_port}{path}",
        headers=headers, timeout=30, **kw,
    )


class TestDebugSurface:
    def test_token_gate(self, drill):
        assert _dbg(drill, "/debug/stacks", token=None).status_code == 403
        assert _dbg(drill, "/debug/stacks", token="wrong").status_code == 403
        r = _dbg(drill, "/debug/stacks")
        assert r.status_code == 200
        assert "MainThread" in r.text

    def test_bearer_token_accepted(self, drill):
        r = httpx.get(
            f"http://127.0.0.1:{drill.read_port}/debug/stacks",
            headers={"Authorization": "Bearer hunter2"}, timeout=30,
        )
        assert r.status_code == 200

    def test_config_redacts_secrets(self, drill):
        r = _dbg(drill, "/debug/config")
        assert r.status_code == 200
        assert "hunter2" not in r.text
        assert "[redacted]" in r.text
        doc = r.json()
        assert doc["config"]["debug"]["token"] == "[redacted]"

    def test_graph_panel_endpoint(self, drill):
        doc = _dbg(drill, "/debug/graph").json()
        assert "graph" in doc and "devices" in doc
        assert "tuples" in doc["graph"]

    def test_traces_endpoint(self, drill):
        doc = _dbg(drill, "/debug/traces").json()
        assert isinstance(doc["spans"], list)

    def test_debug_disabled_is_404(self):
        cfg = Config(
            values={
                "namespaces": [{"id": 1, "name": "videos"}],
                "serve": {
                    "read": {"port": 0, "host": "127.0.0.1"},
                    "write": {"port": 0, "host": "127.0.0.1"},
                },
                "log": {"level": "error"},
                "debug": {"enabled": False},
            },
            env={},
        )
        s = ServerFixture(cfg)
        try:
            r = httpx.get(
                f"http://127.0.0.1:{s.read_port}/debug/stacks", timeout=30
            )
            assert r.status_code == 404
            # the rest of the plane still serves
            assert (
                httpx.get(
                    f"http://127.0.0.1:{s.read_port}/health/alive", timeout=30
                ).status_code
                == 200
            )
        finally:
            s.stop()


class TestIntrospectionDrill:
    """The acceptance drill: an armed device.slow fault must leave a
    correlated evidence trail — flight-recorder entry, histogram exemplar
    trace id, and a burning SLO gauge — with no log spelunking."""

    def test_slow_fault_leaves_full_evidence(self, drill):
        from keto_tpu.faults import FAULTS

        base = f"http://127.0.0.1:{drill.read_port}"
        put = httpx.put(
            f"http://127.0.0.1:{drill.write_port}/relation-tuples",
            json={
                "namespace": "videos",
                "object": "/cats",
                "relation": "view",
                "subject_id": "cat lady",
            },
            timeout=60,
        )
        assert put.status_code in (200, 201)
        try:
            FAULTS.arm_slow("device.slow", sleep_ms=120, times=8)
            r = httpx.get(
                f"{base}/check",
                params={
                    "namespace": "videos",
                    "object": "/cats",
                    "relation": "view",
                    "subject_id": "cat lady",
                },
                timeout=60,
            )
            assert r.status_code == 200
        finally:
            FAULTS.reset()

        # 1. the flight recorder captured it (slow >= 50ms threshold)
        doc = _dbg(drill, "/debug/flight").json()
        slow = [
            rec for rec in doc["records"]
            if rec.get("slow") and rec.get("transport") == "rest"
        ]
        assert slow, f"no slow flight record in {doc['records']!r}"
        rec = slow[0]
        assert rec["outcome"] == "ok"
        assert rec["duration_ms"] >= 100
        trace_id = rec["trace_id"]
        assert len(trace_id) == 32

        # 2. the check-latency histogram carries that trace id as an
        #    OpenMetrics exemplar
        om = httpx.get(
            f"{base}/metrics",
            headers={"Accept": "application/openmetrics-text"},
            timeout=30,
        )
        assert "application/openmetrics-text" in om.headers["content-type"]
        assert om.text.rstrip("\n").endswith("# EOF")
        assert "keto_check_duration_seconds_bucket" in om.text
        assert f'trace_id="{trace_id}"' in om.text

        # 3. the SLO burn-rate gauge is non-zero (50ms target, ~120ms hit)
        plain = httpx.get(f"{base}/metrics", timeout=30).text
        burn = [
            l for l in plain.splitlines()
            if l.startswith('keto_slo_burn_rate{window="fast"}')
        ]
        assert burn, "keto_slo_burn_rate{window=fast} not exposed"
        assert float(burn[0].split()[-1]) > 0
        assert "keto_slo_bad_events_total" in plain

        # 4. exemplars never leak into the plain-text exposition
        assert "# {" not in plain

        # 5. the armed dump_dir got the ring flushed to disk
        import time as _time

        deadline = _time.time() + 5
        flight_path = None
        while _time.time() < deadline:
            import os

            p = os.path.join(drill.dump_dir, "flight.json")
            if os.path.exists(p):
                flight_path = p
                break
            _time.sleep(0.1)
        assert flight_path, "flight ring never flushed to dump dir"
        disk = json.loads(open(flight_path).read())
        assert any(r.get("trace_id") == trace_id for r in disk["records"])

    def test_both_expositions_stay_lint_clean(self, drill):
        lint = _lint_module()
        base = f"http://127.0.0.1:{drill.read_port}"
        plain = httpx.get(f"{base}/metrics", timeout=30).text
        om = httpx.get(
            f"{base}/metrics",
            headers={"Accept": "application/openmetrics-text"},
            timeout=30,
        ).text
        assert lint.lint_text(plain, openmetrics=False) == []
        assert lint.lint_text(om, openmetrics=True) == []

    def test_debug_snapshot_tarball(self, drill, tmp_path):
        import tarfile

        from click.testing import CliRunner

        from keto_tpu.cli import cli

        out = str(tmp_path / "snap.tar.gz")
        res = CliRunner().invoke(
            cli,
            [
                "--read-remote", f"127.0.0.1:{drill.read_port}",
                "debug", "snapshot", "--out", out, "--token", "hunter2",
            ],
        )
        assert res.exit_code == 0, res.output
        with tarfile.open(out) as tar:
            names = set(tar.getnames())
            assert {
                "stacks.txt", "config.json", "graph.json",
                "flight.json", "traces.json", "metrics.prom",
            } <= names
            cfg_doc = json.loads(tar.extractfile("config.json").read())
            assert cfg_doc["config"]["debug"]["token"] == "[redacted]"
            stacks = tar.extractfile("stacks.txt").read().decode()
            assert "MainThread" in stacks


class TestBenchHeartbeat:
    def test_heartbeat_appends_jsonl(self, tmp_path, monkeypatch):
        import bench

        hb = tmp_path / "hb.jsonl"
        monkeypatch.setenv("BENCH_HEARTBEAT_FILE", str(hb))
        monkeypatch.setattr(bench, "_LAST_PHASE", None)
        bench._heartbeat("phase-one")
        bench._heartbeat("phase-two", skipped="budget", budget_left_s=1.5)
        lines = [json.loads(l) for l in hb.read_text().splitlines()]
        assert len(lines) == 2
        assert lines[0]["phase"] == "phase-one"
        assert lines[0]["last_completed"] is None
        assert lines[1]["phase"] == "phase-two"
        assert lines[1]["last_completed"] == "phase-one"
        assert lines[1]["skipped"] == "budget"
        for doc in lines:
            assert "wall_s" in doc and "t_mono" in doc and "t" in doc


class TestInteriorWalkTelemetry:
    """A snapshot whose interior exceeds the closure limit: the walk's
    build phase and span, its rows counter, and the engine stages around
    its dispatch."""

    @staticmethod
    def _engine(tracer=None, metrics=None, **kw):
        from keto_tpu.engine.closure import ClosureCheckEngine
        from keto_tpu.graph import SnapshotManager
        from keto_tpu.relationtuple import RelationTuple
        from keto_tpu.store import InMemoryTupleStore

        store = InMemoryTupleStore()
        store.write_relation_tuples(*(
            RelationTuple.from_string(s) for s in (
                "drive:d#view@(drive:f1#view)",
                "drive:d#view@(drive:d#owner)",
                "drive:d#owner@olga",
                "drive:f1#view@(drive:f0#view)",
                "drive:f0#view@(group:a#member)",
                "drive:f0#view@(group:b#member)",
                "group:a#member@alice",
                "group:b#member@bob",
            )
        ))
        return ClosureCheckEngine(
            SnapshotManager(store), max_depth=12, interior_limit=2,
            query_mode="device", tracer=tracer, metrics=metrics, **kw
        )

    @staticmethod
    def _reqs():
        from keto_tpu.relationtuple import RelationTuple

        return [RelationTuple.from_string(f"drive:d#view@{u}")
                for u in ("alice", "bob", "olga", "mallory")]

    def test_walk_build_phase_and_span(self):
        from keto_tpu.engine.closure import _WalkArtifacts

        tracer = Tracer()
        eng = self._engine(tracer=tracer)
        assert eng.batch_check(self._reqs()) == [True, True, True, False]
        assert isinstance(eng._state, _WalkArtifacts)
        assert eng.last_build_phases["walk"] > 0
        assert "matmul" not in eng.last_build_phases
        (walk,) = tracer.finished("closure.walk")
        assert walk.attrs["interior"] == eng._state.ig.m
        build = tracer.finished("closure.build")[-1]
        assert build.attrs["kind"] == "walk"
        assert walk.parent_id == build.span_id

    @pytest.mark.parametrize("wide_max", [2048, 1])
    def test_rows_counter_by_path(self, monkeypatch, wide_max):
        from keto_tpu.engine import closure

        metrics = MetricsRegistry()
        # a one-wide frontier: rows whose walk meets two groups at once
        # overflow, and the host walks them again wider or (with no room to
        # widen) the oracle answers them; the rest stay on the device
        monkeypatch.setattr(closure, "_WALK_WIDTH", 1)
        monkeypatch.setattr(closure, "_WALK_WIDE_MAX", wide_max)
        eng = self._engine(metrics=metrics, f0_max=1)
        assert eng.batch_check(self._reqs()) == [True, True, True, False]
        text = metrics.expose()
        paths = ("device", "host", "oracle")
        name = 'keto_interior_walk_rows_total{path="%s"}'
        counts = {
            p: float(l.split()[-1]) for l in text.splitlines()
            for p in paths if l.startswith(name % p + " ")
        }
        assert sum(counts.values()) == 4
        assert counts["oracle"] == eng.n_oracle_rows
        wider = "host" if wide_max > 1 else "oracle"
        assert counts[wider] > 0
        assert counts["host" if wider == "oracle" else "oracle"] == 0
        assert counts["device"] > 0

    def test_dispatch_and_device_wait_wrap_the_walk(self, tmp_path):
        import glob

        import jax
        from jax.profiler import ProfileData

        eng = self._engine()
        eng.batch_check(self._reqs())  # build and compile
        jax.profiler.start_trace(str(tmp_path))
        try:
            eng.batch_check(self._reqs())
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(
            str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")
        )
        events = [
            (e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines
            for e in line.events
        ]

        def spans(name):
            return [(a, b) for n, a, b in events if n == name]

        (dispatch,) = spans("keto.dispatch")
        (wait,) = spans("keto.device_wait")
        calls = spans("PjitFunction(interior_walk)")
        assert calls
        assert all(dispatch[0] <= a and b <= dispatch[1] for a, b in calls)
        assert dispatch[1] <= wait[0]
        assert not spans("PjitFunction(closure_query)")


class TestClosureFanoutTelemetry:
    """``keto_closure_fanout_batches_total{plan}`` counts each batch that
    holds both fan-out classes once, under the plan it was queried by."""

    @pytest.mark.parametrize(
        "mode, plan", [("device", "one"), ("host", "split")]
    )
    def test_mixed_batch_counted_once_under_its_plan(
        self, monkeypatch, mode, plan
    ):
        from keto_tpu import native
        from keto_tpu.engine.closure import ClosureCheckEngine
        from keto_tpu.graph import SnapshotManager
        from keto_tpu.relationtuple import RelationTuple
        from keto_tpu.store import InMemoryTupleStore

        if mode == "host":
            # the numpy twin, not the fused C path, queries the batch
            monkeypatch.setattr(native, "lib", None)
        store = InMemoryTupleStore()
        store.write_relation_tuples(*(
            RelationTuple.from_string(s)
            for s in [f"groups:g{i}#member@alice" for i in range(12)]
            + [f"docs:all#view@(groups:g{i}#member)" for i in range(12)]
            + ["docs:d#view@(groups:g0#member)", "groups:g0#member@bob"]
        ))
        metrics = MetricsRegistry()
        eng = ClosureCheckEngine(
            SnapshotManager(store), max_depth=5, query_mode=mode,
            metrics=metrics,
        )

        def counts():
            name = 'keto_closure_fanout_batches_total{plan="%s"}'
            return {
                p: float(l.split()[-1])
                for l in metrics.expose().splitlines()
                for p in ("one", "split") if l.startswith(name % p + " ")
            }

        # alice (in twelve subject sets) is the wide class, bob the narrow
        mixed = [RelationTuple.from_string(f"docs:d#view@{u}")
                 for u in ("alice", "bob", "carol")]
        assert eng.batch_check(mixed) == [True, True, False]
        want = {"one": 0.0, "split": 0.0}
        want[plan] = 1.0
        assert counts() == want
        assert eng.batch_check(mixed[1:]) == [True, False]  # narrow only
        assert counts() == want
