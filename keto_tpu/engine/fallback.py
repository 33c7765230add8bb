"""Device-engine circuit breaker: degrade to the host oracle, never crash.

The compiled device engine is shared-fate for every check in the process: an
XLA compile error, a driver wedge, or a numerically sick chip (batches full
of NaN — hardware-accelerated retrieval stacks like LogosKG document the
same failure class) used to surface as an exception on every caller, or
worse, as silently wrong answers. The read plane needs an explicit degraded
mode instead of an implicit crash mode:

- every batch answered by the primary engine is *validated* (right length,
  strictly boolean — a NaN or garbage element is a failure, not an answer);
- ``failure_threshold`` consecutive failures trip the breaker: checks are
  served by the exact host oracle (``CheckEngine`` over the live store) for
  ``cooldown_s``, the health service drops to NOT_SERVING so balancers
  deprioritize this process (it still answers, slower), and a telemetry
  counter records every fallback-served batch;
- after the cooldown one probe batch rides the primary (half-open); success
  closes the breaker and restores SERVING, failure re-opens it with
  doubled cooldown (capped) plus jitter — a fixed re-probe interval can
  resonate with a flapping device, so every open window is stretched by a
  random fraction of itself.

Not every device error deserves the breaker. :func:`classify_device_error`
types each failure and routes it to a recovery policy:

- **oom** (RESOURCE_EXHAUSTED / allocation failure): the batch was too big
  for current HBM headroom, not a sick device — bisect the encoded batch,
  re-dispatch the halves against the *same* snapshot, merge in order
  (parity-exact: the kernels answer rows independently). Bounded recursion
  depth; a single-row OOM degrades to the host oracle.
- **compile_fail** (shape-specific XLA compilation failure): quarantine
  that (bucket, snapshot-version) shape — route it to the host oracle
  without tripping the global breaker, because every *other* shape still
  compiles and serves fine.
- **device_lost** (DEVICE_LOST / dead driver): force the breaker open
  immediately (no threshold — the device is gone for every future batch)
  and notify the device supervisor (``on_device_lost``), which tears the
  engine down and re-probes the backend while the oracle covers the gap.
- **transient** (everything else): the original consecutive-failure
  threshold semantics.

The wrapper is transparent: everything the batcher/registry reach through
(``wait_for_version``, ``answering_version``, ``warmup``, ...) delegates to
the primary engine.
"""

from __future__ import annotations

import random
import threading
import time
from typing import Optional, Sequence

from ..faults import FaultInjected
from ..relationtuple.definitions import RelationTuple
from .stages import engine_stage

_COOLDOWN_CAP_S = 60.0
#: every open window is stretched by up to this fraction of itself
_JITTER_FRAC = 0.25
#: bisection recursion bound: 2^6 = 64 sub-batches from one OOM at worst
_MAX_BISECT_DEPTH = 6
#: quarantined (bucket, snapshot-version) shapes kept; oldest pruned first
_QUARANTINE_CAP = 64

#: injected-fault sites mapped straight to their error class — the drills
#: arm these instead of fabricating XLA status strings
_FAULT_SITE_KINDS = {
    "device.oom": "oom",
    "device.lost": "device_lost",
    "device.compile_fail": "compile_fail",
}


def classify_device_error(err: BaseException) -> str:
    """Type a raised device/XLA error: ``oom`` | ``device_lost`` |
    ``compile_fail`` | ``transient``. Matching is on exception type name
    plus XLA status-message substrings — no hard jaxlib import, because
    the host-only test mesh must classify the same way the TPU does."""
    if isinstance(err, FaultInjected):
        return _FAULT_SITE_KINDS.get(err.site, "transient")
    msg = str(err).lower()
    if (
        "resource_exhausted" in msg
        or "out of memory" in msg
        or "failed to allocate" in msg
        or "allocation failure" in msg
    ):
        return "oom"
    if (
        "device_lost" in msg
        or "device lost" in msg
        or "device or resource busy" in msg
        or "failed_precondition: device" in msg
    ):
        return "device_lost"
    name = type(err).__name__
    if "compilation failure" in msg or "xla compilation" in msg:
        return "compile_fail"
    if name in ("XlaRuntimeError", "JaxRuntimeError", "JaxStackTraceBeforeTransformation") and (
        "compil" in msg or "mosaic" in msg or "unsupported" in msg
    ):
        return "compile_fail"
    return "transient"


class _FallbackAnswered:
    """launch_encoded's return when the batch was answered by the host
    oracle instead of dispatched: decode_launched just unwraps it."""

    __slots__ = ("results",)

    def __init__(self, results: list):
        self.results = results


def _valid_batch(results, n: int) -> bool:
    """The engine contract is list[bool] of the batch length. Anything else
    (short batch, NaN, floats, None) is a sick-device symptom: treat it as
    a failure rather than bool()-coercing garbage into an answer."""
    try:
        if len(results) != n:
            return False
        for v in results:
            # bool and numpy.bool_ are fine; exact 0/1 integers are fine
            # (int is not bool, so check values); everything else — float
            # NaN included — is garbage
            if isinstance(v, bool):
                continue
            if type(v).__name__ == "bool_":  # numpy scalar, no hard dep
                continue
            if isinstance(v, int) and v in (0, 1):
                continue
            return False
    except TypeError:
        return False
    return True


class DeviceFallbackEngine:
    """Circuit breaker around a device-backed check engine with a host
    (exact oracle) fallback.

    ``fallback_factory`` is called at most once, on first need — the host
    oracle over the live store is cheap to build but there is no reason to
    pay it on healthy boots.
    """

    def __init__(
        self,
        primary,
        fallback_factory,
        failure_threshold: int = 3,
        cooldown_s: float = 1.0,
        health=None,  # HealthServicer; breaker drives SERVING/NOT_SERVING
        metrics=None,
        logger=None,
        clock=time.monotonic,
        on_device_lost=None,  # DeviceSupervisor.notify_device_lost
        max_bisect_depth: int = _MAX_BISECT_DEPTH,
        jitter_frac: float = _JITTER_FRAC,
        rng=None,  # injectable random.Random for deterministic jitter tests
    ):
        self.primary = primary
        self._fallback_factory = fallback_factory
        self._fallback = None
        self.failure_threshold = max(1, failure_threshold)
        self.base_cooldown_s = cooldown_s
        self.health = health
        self._logger = logger
        self._clock = clock
        self._on_device_lost = on_device_lost
        self.max_bisect_depth = max(0, int(max_bisect_depth))
        self.jitter_frac = max(0.0, float(jitter_frac))
        self._rng = rng if rng is not None else random.Random()
        self._lock = threading.Lock()
        self._consecutive_failures = 0
        self._open_until: Optional[float] = None  # None = closed
        self._cooldown_s = cooldown_s
        self._probing = False  # half-open: one probe at a time
        self._degraded_health = False  # only restore what WE took down
        # (bucket, snapshot-version) -> quarantined-at (breaker clock):
        # shapes whose compile failed, served by the oracle without opening
        # the circuit; insertion-ordered so the cap prunes oldest first
        self._quarantine: dict[tuple[int, int], float] = {}
        # batches the host oracle answered (any cause); read by
        # chip_smoke.py, which fails the run unless it stays 0
        self.n_fallback_batches = 0
        self._m_failures = None
        self._m_fallback_batches = None
        self._m_open = None
        self._m_deadline_skips = None
        self._m_bisections = None
        self._m_quarantine = None
        if metrics is not None:
            self._m_failures = metrics.counter(
                "keto_device_engine_failures_total",
                "device engine batches that raised or returned invalid output",
            )
            self._m_fallback_batches = metrics.counter(
                "keto_device_fallback_batches_total",
                "check batches answered by the host oracle while the "
                "device circuit is open",
            )
            self._m_open = metrics.gauge(
                "keto_device_circuit_open",
                "1 while checks are served by the host fallback",
            )
            self._m_deadline_skips = metrics.counter(
                "keto_fallback_deadline_skips_total",
                "rows the host-oracle fallback did not re-answer because "
                "their caller deadline had already passed",
            )
            self._m_bisections = metrics.counter(
                "keto_device_oom_bisections_total",
                "encoded batches split in half and re-dispatched after a "
                "device out-of-memory",
            )
            self._m_quarantine = metrics.gauge(
                "keto_compile_quarantine_size",
                "(bucket, snapshot-version) shapes quarantined to the host "
                "oracle after a shape-specific compile failure",
            )

    # -- breaker bookkeeping ---------------------------------------------------

    def circuit_open(self) -> bool:
        with self._lock:
            return self._open_until is not None

    def force_probe(self) -> None:
        """Collapse the open window: the next batch becomes the half-open
        probe NOW. The device supervisor calls this after a successful
        teardown/re-init — waiting out a (possibly doubled) cooldown after
        the device is already back just burns oracle latency."""
        with self._lock:
            if self._open_until is not None:
                self._open_until = self._clock()

    def _fallback_engine(self):
        if self._fallback is None:
            self._fallback = self._fallback_factory()
        return self._fallback

    def _use_primary(self) -> bool:
        """Route decision per batch; flips to half-open probe after the
        cooldown (exactly one concurrent probe — the rest keep falling
        back until the probe verdict lands)."""
        with self._lock:
            if self._open_until is None:
                return True
            if self._probing or self._clock() < self._open_until:
                return False
            self._probing = True
            return True

    def _record_failure(
        self, err: Optional[BaseException], force_open: bool = False
    ) -> None:
        """``force_open`` opens the circuit regardless of the consecutive
        threshold — a lost device fails every future batch, so waiting out
        the threshold just burns caller latency."""
        if self._m_failures is not None:
            self._m_failures.inc()
        with self._lock:
            self._probing = False
            self._consecutive_failures += 1
            was_open = self._open_until is not None
            if was_open:
                # failed probe: re-open, back off harder
                self._cooldown_s = min(self._cooldown_s * 2, _COOLDOWN_CAP_S)
                tripped = False
            else:
                tripped = (
                    force_open
                    or self._consecutive_failures >= self.failure_threshold
                )
            if tripped or was_open:
                # jittered open window: a flapping device must not phase-
                # lock with the half-open probe cadence
                jitter = (
                    self._cooldown_s * self.jitter_frac * self._rng.random()
                )
                self._open_until = self._clock() + self._cooldown_s + jitter
            take_health_down = (tripped or was_open) and not self._degraded_health
            if take_health_down:
                self._degraded_health = True
        if tripped or was_open:
            if self._m_open is not None:
                self._m_open.set(1)
            if self._logger is not None:
                self._logger.warn(
                    "device engine circuit OPEN; serving checks from the "
                    "host oracle",
                    error=str(err) if err is not None else "invalid output",
                    cooldown_s=self._cooldown_s,
                )
        if take_health_down and self.health is not None:
            self.health.set_serving(False)

    def _record_success(self) -> None:
        with self._lock:
            self._consecutive_failures = 0
            self._probing = False
            recovered = self._open_until is not None
            self._open_until = None
            self._cooldown_s = self.base_cooldown_s
            restore = recovered and self._degraded_health
            if restore:
                self._degraded_health = False
        if recovered:
            if self._m_open is not None:
                self._m_open.set(0)
            if self._logger is not None:
                self._logger.info(
                    "device engine circuit CLOSED; primary engine healthy"
                )
        if restore and self.health is not None:
            self.health.set_serving(True)

    def _note_failure(self, err: Optional[BaseException]) -> None:
        """Typed failure bookkeeping for the non-launch seams: device-lost
        forces the circuit open and wakes the supervisor; everything else
        keeps the consecutive-threshold semantics."""
        if err is not None and classify_device_error(err) == "device_lost":
            self._record_failure(err, force_open=True)
            self._notify_device_lost(err)
        else:
            self._record_failure(err)

    def _notify_device_lost(self, err: BaseException) -> None:
        cb = self._on_device_lost
        if cb is None:
            return
        try:
            cb(err)
        except Exception:
            pass  # the supervisor is best-effort; serving must not care

    # -- compile quarantine ----------------------------------------------------

    def _quarantined(self, key: tuple[int, int]) -> bool:
        with self._lock:
            return key in self._quarantine

    def _add_quarantine(self, key: tuple[int, int]) -> None:
        with self._lock:
            self._quarantine[key] = self._clock()
            while len(self._quarantine) > _QUARANTINE_CAP:
                self._quarantine.pop(next(iter(self._quarantine)))
            size = len(self._quarantine)
        if self._m_quarantine is not None:
            self._m_quarantine.set(size)

    def quarantine_snapshot(self) -> list[dict]:
        """The quarantined shapes, for /debug/device."""
        with self._lock:
            return [
                {"bucket": b, "snapshot_version": v, "since": t}
                for (b, v), t in self._quarantine.items()
            ]

    def breaker_snapshot(self) -> dict:
        """Breaker internals, for /debug/device."""
        with self._lock:
            return {
                "open": self._open_until is not None,
                "consecutive_failures": self._consecutive_failures,
                "cooldown_s": self._cooldown_s,
                "probing": self._probing,
                "quarantine_size": len(self._quarantine),
            }

    # -- check surface ---------------------------------------------------------

    def batch_check(
        self,
        requests: Sequence[RelationTuple],
        max_depth: int = 0,
        depths: Optional[Sequence[int]] = None,
    ) -> list[bool]:
        if not requests:
            return []
        if self._use_primary():
            try:
                results = self.primary.batch_check(
                    requests, max_depth, depths=depths
                )
            except Exception as e:
                self._note_failure(e)
                return self._fallback_check(requests, max_depth, depths)
            with engine_stage("finish"):
                valid = _valid_batch(results, len(requests))
                if valid:
                    self._record_success()
                    results = [bool(v) for v in results]
            if not valid:
                self._record_failure(None)
                return self._fallback_check(requests, max_depth, depths)
            return results
        return self._fallback_check(requests, max_depth, depths)

    # -- pipelined surface (encode/launch/decode split) ------------------------
    #
    # The batcher's pipeline reaches the engine through these instead of
    # batch_check. Encode is host-side (vocab probes — a raise there is a
    # caller bug, not a sick chip) and passes straight through; launch and
    # decode are the device seams, so they carry the breaker. The contract
    # the pipeline needs: NO in-flight batch is ever lost — a batch whose
    # launch or decode fails is re-answered exactly (host oracle), and once
    # the circuit trips every later launch routes to the oracle immediately,
    # so every future already in the pipe still resolves.

    def pipeline_supported(self) -> bool:
        sup = getattr(self.primary, "pipeline_supported", None)
        if callable(sup):
            return sup()
        return callable(getattr(self.primary, "encode_batch", None))

    def encode_batch(self, requests, max_depth=0, depths=None):
        return self.primary.encode_batch(requests, max_depth, depths=depths)

    @staticmethod
    def _shape_key(enc) -> tuple:
        # tolerant of minimal engine stand-ins in tests: an unknown shape
        # (None, None) can be quarantined like any other
        return (getattr(enc, "b", None), getattr(enc, "version", None))

    def launch_encoded(self, enc):
        if self._quarantined(self._shape_key(enc)):
            # this (bucket, snapshot) shape failed to compile: route it to
            # the oracle without consulting (or charging) the breaker
            return self._answer_from_oracle(enc)
        if self._use_primary():
            try:
                return self.primary.launch_encoded(enc)
            except Exception as e:
                handled = self._handle_launch_error(enc, e)
                if handled is not None:
                    return handled
        # circuit open (or the launch itself died): answer this batch from
        # the host oracle NOW — the batch is released and decode becomes a
        # no-op unwrap
        return self._answer_from_oracle(enc)

    def _answer_from_oracle(self, enc) -> "_FallbackAnswered":
        requests, depths = enc.requests, enc.depths
        deadlines = getattr(enc, "deadlines", None)
        enc.release()
        return _FallbackAnswered(
            self._fallback_check(requests, 0, depths, deadlines)
        )

    def _handle_launch_error(self, enc, err):
        """Typed recovery for a failed launch. Returns a ``_FallbackAnswered``
        when a policy absorbed the error (bisection answered exactly, or the
        shape went to quarantine); ``None`` sends the caller down the
        breaker's host-oracle path."""
        kind = classify_device_error(err)
        if kind == "oom":
            results = self._bisect_oom(enc)
            if results is not None:
                # the batch was too big for current HBM headroom, not a
                # sick device: the halves answered, the breaker stays closed
                self._record_success()
                return _FallbackAnswered(results)
            self._record_failure(err)
            return None
        if kind == "compile_fail":
            key = self._shape_key(enc)
            self._add_quarantine(key)
            if self._logger is not None:
                self._logger.warn(
                    "compile failure: quarantining batch shape to the "
                    "host oracle",
                    bucket=key[0],
                    snapshot_version=key[1],
                    error=str(err),
                )
            return self._answer_from_oracle(enc)
        if kind == "device_lost":
            self._record_failure(err, force_open=True)
            self._notify_device_lost(err)
            return None
        self._record_failure(err)
        return None

    # -- OOM bisection ---------------------------------------------------------

    def _bisect_oom(self, enc) -> Optional[list[bool]]:
        """Split-and-retry for an OOM'd launch: snapshot the encoded ids,
        re-encode the halves against the parent batch's snapshot, dispatch
        each, merge in order. Returns the merged bool list (parity-exact
        with the unsplit answer — the kernels answer rows independently),
        or None when bisection can't help (single row, unsupported engine,
        a half failed for a non-OOM reason, depth exhausted)."""
        n = getattr(enc, "n", 0)
        if self.max_bisect_depth <= 0 or n <= 1:
            return None
        encode_at = getattr(self.primary, "encode_ids_at", None)
        if encode_at is None:
            return None
        try:
            start = enc.start[: n].copy()
            target = enc.target[: n].copy()
            depths = list(enc.depths) if enc.depths is not None else [0] * n
            results = self._bisect_ids(enc.snap, start, target, depths, 1)
        except Exception:
            return None
        if results is None or not _valid_batch(results, n):
            return None
        enc.release()
        if self._logger is not None:
            self._logger.info(
                "device OOM absorbed by batch bisection", rows=n
            )
        return [bool(v) for v in results]

    def _bisect_ids(self, snap, start, target, depths, depth):
        if self._m_bisections is not None:
            self._m_bisections.inc()
        mid = len(start) // 2
        merged: list = []
        for lo, hi in ((0, mid), (mid, len(start))):
            sub = self._dispatch_ids(
                snap, start[lo:hi], target[lo:hi], depths[lo:hi], depth
            )
            if sub is None:
                return None
            merged.extend(sub)
        return merged

    def _dispatch_ids(self, snap, start, target, depths, depth):
        enc = self.primary.encode_ids_at(snap, start, target, depths)
        try:
            launched = self.primary.launch_encoded(enc)
        except Exception as e:
            # a raised launch never reaches decode: release the half here
            enc.release()
            if (
                classify_device_error(e) == "oom"
                and len(start) > 1
                and depth < self.max_bisect_depth
            ):
                return self._bisect_ids(snap, start, target, depths, depth + 1)
            return None
        try:
            results = self.primary.decode_launched(launched)
        except Exception:
            return None  # primary's decode releases in its finally
        if not _valid_batch(results, len(start)):
            return None
        return list(results)

    def decode_launched(self, launched) -> list[bool]:
        if isinstance(launched, _FallbackAnswered):
            return launched.results
        enc = launched.enc
        n = enc.n
        depths = enc.depths
        # the oracle's tuples are built (enc.requests, lazily) ONLY inside
        # the failure branches below — a healthy decode never touches
        # per-item objects
        deadlines = getattr(enc, "deadlines", None)
        try:
            results = self.primary.decode_launched(launched)
        except Exception as e:
            self._note_failure(e)
            return self._fallback_check(enc.requests, 0, depths, deadlines)
        if not _valid_batch(results, n):
            self._record_failure(None)
            return self._fallback_check(enc.requests, 0, depths, deadlines)
        self._record_success()
        return [bool(v) for v in results]

    def batch_check_columns(
        self, cols, max_depth: int = 0, depths=None
    ) -> list[bool]:
        """Columnar twin of batch_check: the primary answers straight from
        the columns; ``RelationTuple`` objects are built lazily ONLY when
        the breaker is open or the primary's answer is invalid and the
        host oracle must re-answer the batch."""
        n = len(cols)
        if not n:
            return []
        run = getattr(self.primary, "batch_check_columns", None)
        if run is None:
            return self.batch_check(cols.materialize(), max_depth, depths)
        if self._use_primary():
            try:
                results = run(cols, max_depth, depths)
            except Exception as e:
                self._note_failure(e)
                return self._fallback_check(
                    cols.materialize(), max_depth, depths
                )
            with engine_stage("finish"):
                valid = _valid_batch(results, n)
                if valid:
                    self._record_success()
                    results = [bool(v) for v in results]
            if not valid:
                self._record_failure(None)
                return self._fallback_check(
                    cols.materialize(), max_depth, depths
                )
            return results
        return self._fallback_check(cols.materialize(), max_depth, depths)

    def _fallback_check(
        self, requests, max_depth, depths, deadlines=None
    ) -> list:
        with self._lock:
            self.n_fallback_batches += 1
        if self._m_fallback_batches is not None:
            self._m_fallback_batches.inc()
        if deadlines is not None:
            # rows whose caller deadline already passed are not re-answered
            # — their slot comes back as None (the batcher's decode stage
            # failed those futures typed; a None is never cached). The
            # comparison clock is the batcher's (time.monotonic), not the
            # breaker's injectable one.
            now = time.monotonic()
            live = [
                i
                for i, dl in enumerate(deadlines)
                if dl is None or now < dl
            ]
            if len(live) < len(requests):
                if self._m_deadline_skips is not None:
                    self._m_deadline_skips.inc(len(requests) - len(live))
                sub = self._fallback_answer(
                    [requests[i] for i in live],
                    max_depth,
                    None if depths is None else [depths[i] for i in live],
                )
                out = [None] * len(requests)
                for i, v in zip(live, sub):
                    out[i] = bool(v)
                return out
        return self._fallback_answer(requests, max_depth, depths)

    def _fallback_answer(self, requests, max_depth, depths) -> list[bool]:
        if not requests:
            return []
        engine = self._fallback_engine()
        if depths is not None:
            # the host oracle has no per-request-depth batch entry point;
            # per-request evaluation is its native shape anyway
            return [
                bool(engine.subject_is_allowed(r, d))
                for r, d in zip(requests, depths)
            ]
        return [
            bool(v) for v in engine.batch_check(requests, max_depth)
        ] if hasattr(engine, "batch_check") else [
            bool(engine.subject_is_allowed(r, max_depth)) for r in requests
        ]

    def subject_is_allowed(
        self, requested: RelationTuple, max_depth: int = 0
    ) -> bool:
        return self.batch_check([requested], max_depth)[0]

    # -- transparency ----------------------------------------------------------

    def __getattr__(self, name):
        # wait_for_version / answering_version / served_version / warmup /
        # host_queries / snapshots ... — everything else is the primary's
        return getattr(self.primary, name)
