"""gRPC server interceptor chain: request logging + metrics + error parity.

The reference builds an interceptor chain per server — herodot error
unwrap, logrus request logging, opentracing, telemetry (reference
internal/driver/registry_default.go:337-367). Python's grpc server takes
interceptors at construction; this module provides the equivalent chain:

- every finished RPC emits a structured log line (method, code, ms) and a
  ``keto_grpc_requests_total{plane,method,code}`` count + duration
  histogram observation;
- a tracing span wraps the handler, parenting any engine-phase spans the
  call produces;
- each unary call's accounting ledger opens where gRPC hands the call to
  this program (``intercept_service`` runs on gRPC's poller thread,
  before the hand-off to the handler pool): the wait for a pool thread
  and the message receipt land in ``pool_wait``, the request message
  parse in ``deserialize``, and the check record adopts the ledger;
- uncaught KetoError escaping a handler maps to its canonical status code
  (the servicers already map errors at the call site; the interceptor is
  the backstop that guarantees parity for any future handler).
"""

from __future__ import annotations

import time

import grpc

from ..telemetry.attribution import TimeLedger, close_arrival, open_arrival
from ..telemetry.tracing import TRACEPARENT_HEADER, parse_traceparent
from ..utils.errors import KetoError


class TelemetryInterceptor(grpc.ServerInterceptor):
    def __init__(self, plane: str, logger=None, metrics=None, tracer=None):
        self.plane = plane
        self.logger = logger
        self.tracer = tracer
        if metrics is not None:
            self._requests = metrics.counter(
                "keto_grpc_requests_total",
                "gRPC requests by plane/method/code",
                labelnames=("plane", "method", "code"),
            )
            self._duration = metrics.histogram(
                "keto_grpc_request_duration_seconds",
                "gRPC request duration",
                labelnames=("plane",),
            )
        else:
            self._requests = None
            self._duration = None

    def _observe(self, method: str, code: str, elapsed: float) -> None:
        if self._requests is not None:
            self._requests.labels(
                plane=self.plane, method=method, code=code
            ).inc()
            self._duration.labels(plane=self.plane).observe(elapsed)
        if self.logger is not None:
            self.logger.info(
                "grpc",
                plane=self.plane,
                method=method,
                code=code,
                ms=round(1000 * elapsed, 2),
            )

    def intercept_service(self, continuation, handler_call_details):
        t_call = time.perf_counter()
        handler = continuation(handler_call_details)
        if handler is None or not handler.unary_unary:
            # streaming handlers (health Watch, reflection) pass through
            # un-instrumented: their lifetime is the stream, not a request
            return handler
        method = handler_call_details.method
        inner = handler.unary_unary
        parse_request = handler.request_deserializer
        # start and end of the request's deserialization, which gRPC runs
        # on the pool thread just before the behavior
        read: list = []

        def deserialize(raw):
            read.append(time.perf_counter())
            try:
                return raw if parse_request is None else parse_request(raw)
            finally:
                read.append(time.perf_counter())
        # W3C trace propagation: a client-minted traceparent on the
        # invocation metadata becomes the remote parent of the grpc.request
        # span, so the whole server-side span tree joins the caller's trace
        remote = None
        for key, value in handler_call_details.invocation_metadata or ():
            if key == TRACEPARENT_HEADER:
                remote = parse_traceparent(value)
                break

        def wrapped(request, context):
            t0 = time.perf_counter()
            ledger = TimeLedger(t_call)
            if read:
                ledger.mark("pool_wait", read[0])
                ledger.mark("deserialize", read[1])
            else:
                ledger.mark("pool_wait", t0)
            arrival = open_arrival(ledger)
            code = "OK"
            span = (
                self.tracer.span("grpc.request", method=method, parent=remote)
                if self.tracer is not None
                else None
            )
            try:
                if span is not None:
                    with span:
                        return inner(request, context)
                return inner(request, context)
            except KetoError as e:
                # error parity backstop: KetoError -> canonical status
                code = e.grpc_code
                context.abort(
                    getattr(
                        grpc.StatusCode, e.grpc_code, grpc.StatusCode.INTERNAL
                    ),
                    e.message,
                )
            except Exception:
                # context.abort raises to unwind the stack — the servicers'
                # own abort calls land here; report the set code when the
                # grpc version exposes it
                code = "INTERNAL"
                try:
                    set_code = context.code()
                    if set_code is not None:
                        code = set_code.name
                except Exception:
                    pass
                raise
            finally:
                close_arrival(arrival)
                self._observe(method, code, time.perf_counter() - t0)

        return grpc.unary_unary_rpc_method_handler(
            wrapped,
            request_deserializer=deserialize,
            response_serializer=handler.response_serializer,
        )
