"""Check-request batching: concurrent RPCs -> device-wide lockstep batches.

The reference runs one goroutine per request, each walking the graph alone
(SURVEY.md §2.10). On TPU the economics invert: one batched frontier
expansion amortizes kernel launch and HBM traffic over every in-flight
request. The batcher is that seam: callers block on ``check()``, and the
dispatch machinery drains the queue into device engine batches —
taking whatever has accumulated while the previous batch was on device (the
natural batching window), plus a tiny fixed window when the queue is empty.

Two dispatch shapes share this class:

- **serial** (``pipeline_depth=0``, or an engine without the split
  encode/launch/decode API): one dispatcher thread runs vocab-encode ->
  upload -> execute -> decode strictly in order, one batch in flight.
- **pipelined** (``pipeline_depth>=1`` and a capable engine): a bounded
  multi-stage pipeline. Encode workers drain the queue and vocab-encode on
  host threads; a launch thread enqueues kernels back-to-back (JAX async
  dispatch returns at enqueue, so up to ``pipeline_depth`` batches are in
  flight on device); a decode thread materializes results and resolves
  caller futures off the critical path. An optional snapshot-versioned
  encoded-request cache sits in front of the device stage: rows whose
  (start, target, depth) triple was answered at this snapshot version skip
  the kernel entirely.

Caller-assembled columnar batches (``check_batch_columnar``, the BatchCheck
transports) skip the queue and run on their own handler threads, and
concurrent ones group-commit: a call that needs the engine either leads an
engine pass, with every call pending at that moment merged into one
``batch_check_columns``, or waits for a leader to answer it. At most
``_COLUMNS_PASSES`` passes run at once; a finishing leader hands its slot
to the oldest waiting call. ``keto_batcher_columns_pass_calls`` counts the
calls each pass served.

Because callers funnel through shared-fate stage threads, every stage is
supervised the same way the PR-1 dispatcher was:

- **watchdog**: a stage thread death (a bug, an injected
  ``batcher.dispatcher_die``/``batcher.encode_die``/``batcher.decode_die``
  fault) fails exactly the batch that stage held with
  :class:`DispatcherCrashed` (typed, retryable) and restarts the stage;
  queued requests and batches held by other stages survive.
- **bounded queue**: past ``max_queue`` waiting requests the batcher sheds
  load with :class:`BatcherOverloaded` (HTTP 429 / gRPC RESOURCE_EXHAUSTED
  at the transports) instead of growing the queue — and the latency of
  everything behind it — without bound.
- **typed shutdown**: after ``close()`` no caller can hang past the join
  budget; anything still queued or in flight fails with
  :class:`BatcherClosed`.

Observability: per-stage latency histograms
(``keto_pipeline_stage_seconds{stage=enqueue|encode|launch|device|decode}``)
plus launch/decode queue-depth gauges — see telemetry/metrics.py
(PIPELINE_STAGES) and docs/guides/performance.md for how to read them.
"""

from __future__ import annotations

import queue as _queue_mod
import threading
import time
from concurrent.futures import Future
from concurrent.futures import TimeoutError as _FutTimeout
from typing import Optional, Sequence

import numpy as np

from ..faults import FAULTS
from ..relationtuple.columns import CheckColumns
from ..relationtuple.definitions import RelationTuple
from ..telemetry.attribution import current_ledger, ledger_mark
from ..telemetry.metrics import (
    deadline_expired_counter,
    pipeline_stage_histogram,
)
from ..telemetry.tracing import _current_span
from ..utils.errors import (
    DeadlineExceeded,
    ErrInternal,
    ErrResourceExhausted,
    ErrUnavailable,
)


class BatcherClosed(ErrUnavailable):
    """The batcher was shut down: rebuilds stopped, so cached answers could
    no longer be invalidated and must not be served either."""

    def default_message(self) -> str:
        return "The check batcher is closed (server shutting down)."


class BatcherOverloaded(ErrResourceExhausted):
    """The dispatch queue is full; this request was shed."""

    def default_message(self) -> str:
        return "The check queue is full; retry with backoff."


class DispatcherCrashed(ErrInternal):
    """A dispatch stage thread died while this request was in flight; the
    watchdog restarted it. The request was NOT answered — retryable."""

    def default_message(self) -> str:
        return "The check dispatcher crashed mid-batch and was restarted."


# close()/clean-shutdown marker passed down the stage queues
_SENTINEL = object()

#: columnar engine passes in flight at once. One: on a v5e a second
#: concurrent pass halved the calls each pass served and raised the p95 of
#: the rbac1m and github6m cells; only the interior-walk cell, whose device
#: time a call is ~8x larger, gained from it (PERF.md, section 6)
_COLUMNS_PASSES = 1


class _ColumnsCall:
    """A columnar call in the group commit: its rows and depth; once its
    pass ends, its answers or the pass's exception. ``wake`` is held until
    a leader releases it, with the answers or with the lead."""

    __slots__ = ("cols", "depth", "wake", "lead", "result", "error")

    def __init__(self, cols, depth: int):
        self.cols = cols
        self.depth = depth
        self.wake = threading.Lock()
        self.wake.acquire()
        self.lead = False
        self.result = None
        self.error = None


class _PBatch:
    """One batch moving through the pipeline: queue items plus per-stage
    artifacts and timestamps."""

    __slots__ = ("items", "enc", "launched", "keys", "t_encoded", "hbm_token")

    def __init__(self, items):
        # [(request, depth, Future, t_enqueued, deadline, ledger,
        #   span_ctx), ...]
        self.items = items
        self.enc = None  # the engine's encoded batch after encode
        self.launched = None  # its launched batch after the launch stage
        self.keys = None  # encoded-cache keys (when the cache is on)
        self.t_encoded = 0.0
        self.hbm_token = 0  # HBM admission reservation; 0 = none held


class _Holder:
    """The batch a stage loop currently owns — what its watchdog fails on
    a crash. Ownership passes to the next queue the moment the loop clears
    the holder, so exactly one owner exists at any time."""

    __slots__ = ("batch",)

    def __init__(self):
        self.batch = None


class CheckBatcher:
    def __init__(
        self,
        engine,  # anything with batch_check(requests, depths=...) -> list[bool]
        max_batch: int = 4096,
        window_s: float = 0.0002,
        metrics=None,
        cache=None,  # CheckResultCache; None disables
        version_fn=None,  # ANSWERING-version supplier for cache stamping
        # (engine.answering_version — not served_version, which lags writes)
        max_queue: int = 0,  # 0 -> 8 * max_batch
        logger=None,
        pipeline_depth: int = 0,  # 0 -> serial dispatch (one batch in flight)
        encode_workers: int = 2,
        encoded_cache_size: int = 0,  # 0 disables the encoded-request cache
        # snaptoken catch-up cap: float, or a zero-arg callable for a
        # hot-reloadable knob (serve.read.max_freshness_wait_s)
        max_freshness_wait_s=30.0,
        tracer=None,  # stage spans join the caller's trace when set
        qos=None,  # NamespaceQos: per-tenant token-bucket admission
        hbm=None,  # HbmAdmission: device-memory budget; None disables
        overload=None,  # OverloadController: adaptive admission + brownout
    ):
        self.engine = engine
        self.tracer = tracer
        self.qos = qos
        self.hbm = hbm
        self.overload = overload
        self.max_batch = max_batch
        self.window_s = window_s
        self.cache = cache
        self.version_fn = version_fn
        self.max_queue = max_queue if max_queue > 0 else 8 * max_batch
        self._max_freshness_wait_s = max_freshness_wait_s
        self._logger = logger
        self.pipeline_depth = pipeline_depth
        self.encode_workers = max(1, encode_workers)
        # pipelining needs the engine's split encode/launch/decode API;
        # engines without it (host oracle, closure) keep the serial loop
        sup = getattr(engine, "pipeline_supported", None)
        capable = (
            sup()
            if callable(sup)
            else callable(getattr(engine, "encode_batch", None))
        )
        self.pipelined = pipeline_depth >= 1 and capable
        self.encoded_cache = None
        # the encoded-request cache serves BOTH the pipelined single-check
        # path and the columnar batch transport, so it only needs a capable
        # engine — not the pipeline threads
        if capable and encoded_cache_size > 0:
            from .cache import CheckResultCache

            self.encoded_cache = CheckResultCache(
                encoded_cache_size, metrics, name="encoded"
            )
        self._m_batch_size = None
        self._m_shed = None
        self._m_restarts = None
        self._m_stage = None
        self._m_columnar = None
        self._m_deadline = None
        self._m_cancelled = None
        self._m_pass_calls = None
        # per-stage cull tallies mirrored outside the metrics registry so
        # pipeline_stats() (the /pipeline endpoint) can surface them even
        # on metric-less builds
        self._cull_expired_counts: dict[str, int] = {}
        self._cull_cancelled_counts: dict[str, int] = {}
        if metrics is not None:
            self._m_batch_size = metrics.histogram(
                "keto_batcher_batch_size",
                "requests coalesced per dispatched batch",
                buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096),
            )
            self._m_shed = metrics.counter(
                "keto_batcher_shed_total",
                "check requests rejected because the dispatch queue was full",
            )
            self._m_restarts = metrics.counter(
                "keto_batcher_dispatcher_restarts_total",
                "dispatch stage thread deaths recovered by the watchdog",
            )
            self._m_columnar = metrics.counter(
                "keto_batcher_columnar_batches_total",
                "caller-assembled batches served through the columnar "
                "zero-object path",
            )
            self._m_pass_calls = metrics.histogram(
                "keto_batcher_columns_pass_calls",
                "columnar calls served by one engine pass (group commit)",
                buckets=(1, 2, 3, 4, 6, 8, 12, 16, 24, 32),
            )
            self._m_deadline = deadline_expired_counter(metrics)
            self._m_cancelled = metrics.counter(
                "keto_check_cancelled_total",
                "check requests dropped because the caller disconnected "
                "before an answer, labeled by the stage that freed the slot",
                labelnames=("stage",),
            )
            metrics.gauge(
                "keto_batcher_queue_depth",
                "check requests waiting for dispatch",
                fn=lambda: len(self._queue),
            )
            if self.pipelined:
                self._m_stage = pipeline_stage_histogram(metrics)
        # integrity-scrub tap (engine/scrub.py ScrubDaemon.observe_batch):
        # called with (requests, results) after each direct dispatch so
        # the scrubber can reservoir-sample live traffic for oracle replay
        self.scrub_observer = None
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        # (request, depth, Future, t_enqueued, deadline, ledger, span_ctx)
        self._queue: list[tuple] = []
        # serial mode: the batch the dispatcher popped but has not answered
        # yet — the watchdog fails exactly these on a dispatcher death, and
        # close() fails them after the join budget
        self._inflight: list[tuple] = []
        # columnar group commit: engine passes running and the calls
        # waiting for one, oldest first (_run_columns)
        self._pass_lock = threading.Lock()
        self._passes = 0
        self._pending: list[_ColumnsCall] = []
        # pipelined mode: every batch admitted to the pipeline and not yet
        # resolved, whichever stage or queue currently owns it — close()
        # fails the stragglers after the join budget
        self._pipe_batches: dict[int, _PBatch] = {}
        self._closed = False
        # reconfigure(): quiesce asks the stage threads to drain in-flight
        # batches and exit WITHOUT failing the queue — queued entries stay
        # put and the rebuilt pipeline picks them up
        self._quiesce = False
        self._reconfig_lock = threading.Lock()
        self._metrics = metrics
        # close() lets the dispatcher drain for this long before failing
        # the leftovers typed; only a wedged engine ever exhausts it
        self.close_join_s = 5.0
        if self.pipelined:
            # launch_q admits roughly one encoded batch per encode worker;
            # decode_q is the in-flight bound: the launch thread blocks
            # putting batch N+pipeline_depth until batch N is materialized
            self._launch_q: _queue_mod.Queue = _queue_mod.Queue(
                maxsize=max(2, self.encode_workers)
            )
            self._decode_q: _queue_mod.Queue = _queue_mod.Queue(
                maxsize=max(1, pipeline_depth)
            )
            self._encoders_live = self.encode_workers
            self._register_pipeline_metrics()
            self._threads = self._spawn_pipeline()
            self._thread = self._threads[0]  # close()/tests compatibility
        else:
            self._thread = self._spawn_dispatcher()
            self._threads = [self._thread]

    def _register_pipeline_metrics(self) -> None:
        """Queue-depth gauges + stage histogram for the pipelined shape.
        The gauges sample through lambdas (not bound queue methods) so a
        reconfigure() that swaps the queue objects keeps them live;
        re-registration after a serial->pipelined transition dedups to the
        same metric and rebinds its sampler."""
        metrics = self._metrics
        if metrics is None:
            return
        metrics.gauge(
            "keto_pipeline_launch_queue_depth",
            "encoded batches waiting for kernel dispatch",
        ).set_fn(lambda: self._launch_q.qsize())
        metrics.gauge(
            "keto_pipeline_decode_queue_depth",
            "launched batches in flight awaiting decode",
        ).set_fn(lambda: self._decode_q.qsize())
        self._m_stage = pipeline_stage_histogram(metrics)

    def _spawn_dispatcher(self) -> threading.Thread:
        t = threading.Thread(
            target=self._run_guard, name="check-batcher", daemon=True
        )
        t.start()
        return t

    def _spawn_pipeline(self) -> list[threading.Thread]:
        threads = []
        for i in range(self.encode_workers):
            threads.append(
                threading.Thread(
                    target=self._encode_guard,
                    name=f"check-encode-{i}",
                    daemon=True,
                )
            )
        threads.append(
            threading.Thread(
                target=self._stage_guard,
                args=(self._launch_loop, "launch"),
                name="check-launch",
                daemon=True,
            )
        )
        threads.append(
            threading.Thread(
                target=self._stage_guard,
                args=(self._decode_loop, "decode"),
                name="check-decode",
                daemon=True,
            )
        )
        for t in threads:
            t.start()
        return threads

    def max_freshness_wait_s(self) -> float:
        """Current freshness-wait cap; resolves the hot-reload callable."""
        cap = self._max_freshness_wait_s
        return float(cap() if callable(cap) else cap)

    def check(
        self,
        request: RelationTuple,
        max_depth: int = 0,
        timeout: Optional[float] = None,
        min_version: int = 0,
        deadline: Optional[float] = None,  # absolute time.monotonic() secs
        entry_hook=None,  # called with the entry Future after enqueue —
        # transports hold it to cancel on client disconnect
        criticality: str = "default",  # critical | default | sheddable
    ) -> bool:
        if self._closed:
            raise BatcherClosed()
        if self.qos is not None:
            # per-tenant admission precedes everything: a throttled
            # tenant must not consume queue slots, cache probes, or a
            # freshness wait
            self.qos.admit(request.namespace)
        if deadline is not None:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                # already dead on arrival: reject before the queue, the
                # cache, or any engine work is touched
                self._note_expired("admission", 1)
                raise DeadlineExceeded()
            timeout = remaining if timeout is None else min(timeout, remaining)
        if min_version > 0:
            # at-least-as-fresh consistency (CheckRequest.snaptoken): make
            # the serving snapshot catch up before answering. The cache is
            # still safe afterward — its stamp is the answering version.
            # Brownout rung 2+ relaxes this to bounded-stale: answer at
            # the current snapshot instead of spending queue time waiting
            # for one — the freshness wait is the cheapest latency to
            # refuse under pressure, after hedges
            ov = self.overload
            if ov is not None and ov.stale_ok():
                ov.note_stale_served()
            else:
                wait = getattr(self.engine, "wait_for_version", None)
                if wait is not None:
                    wait(
                        min_version,
                        timeout_s=(
                            timeout
                            if timeout is not None
                            else self.max_freshness_wait_s()
                        ),
                    )
            if deadline is not None and time.monotonic() >= deadline:
                # the freshness wait consumed the whole budget
                self._note_expired("admission", 1)
                raise DeadlineExceeded()
        if self.cache is not None:
            version = self.version_fn()
            key = (request, max_depth)
            cached = self.cache.get(version, key)
            if cached is not None:
                return cached
        f: Future = Future()
        # the per-request accounting ledger and span context ride the
        # queue entry: the pipeline stage threads mark wait/encode/
        # launch/kernel/decode on the ledger and parent their stage
        # spans to the caller's trace. Everything up to the enqueue is
        # "admission" (transport handling, freshness wait, cache probe).
        led = current_ledger()
        if led is not None:
            led.mark("admission")
        span_ctx = _current_span.get()
        with self._cv:
            if self._closed:
                raise BatcherClosed()
            # the adaptive overload plane is the primary shed signal:
            # latency-driven brownout by criticality class, plus the SRE
            # accepts/requests throttle once the ladder is shedding
            if self.overload is not None:
                reason = self.overload.admit(len(self._queue), criticality)
                if reason is not None:
                    if self._m_shed is not None:
                        self._m_shed.inc()
                    raise BatcherOverloaded(
                        f"The server is overloaded ({reason}, "
                        f"criticality={criticality}); retry with backoff."
                    )
            if len(self._queue) >= self.max_queue:
                # hard backstop behind the adaptive limiter: a full queue
                # means the engine is already saturated max_queue/max_batch
                # dispatches deep — queueing further only converts overload
                # into latency for every caller. This bound sheds even
                # `critical` traffic; the brownout ladder never does
                if self._m_shed is not None:
                    self._m_shed.inc()
                raise BatcherOverloaded()
            self._queue.append(
                (
                    request, max_depth, f, time.perf_counter(), deadline,
                    led, span_ctx, criticality,
                )
            )
            self._cv.notify()
        if entry_hook is not None:
            entry_hook(f)
        try:
            result = f.result(timeout=timeout)
        except _FutTimeout:
            if deadline is not None and time.monotonic() >= deadline:
                # the caller's budget ran out while the entry was still in
                # the pipe: cancel it so the next stage boundary frees the
                # slot instead of paying device time for a dead request
                f.cancel()
                raise DeadlineExceeded() from None
            raise
        if self.cache is not None:
            self.cache.put(version, key, result)
        return result

    def check_batch(
        self,
        requests: Sequence[RelationTuple],
        max_depth: int = 0,
        min_version: int = 0,
        timeout: Optional[float] = None,
        deadline: Optional[float] = None,
        criticality: str = "default",
    ) -> list[bool]:
        """A caller-assembled batch: already amortized, so it skips the
        queue and dispatches directly (the batch-check transport path).
        `min_version` applies the at-least-as-fresh contract to the whole
        batch before dispatch, bounded by `timeout` (the RPC deadline).
        The result cache is consulted in bulk with the same stamp the
        single path uses — a hot repeated payload costs dict probes, not
        an engine dispatch."""
        if self._closed:
            raise BatcherClosed()
        if self.qos is not None:
            counts: dict[str, int] = {}
            for r in requests:
                counts[r.namespace] = counts.get(r.namespace, 0) + 1
            self.qos.admit_counts(counts)
        if self.overload is not None:
            # one admission decision covers the whole caller-assembled
            # batch — it rides the direct path, but it still competes with
            # the queue for engine time, so it sheds by the same ladder
            reason = self.overload.admit(len(self._queue), criticality)
            if reason is not None:
                if self._m_shed is not None:
                    self._m_shed.inc()
                raise BatcherOverloaded(
                    f"The server is overloaded ({reason}, "
                    f"criticality={criticality}); retry with backoff."
                )
        if deadline is not None:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                self._note_expired("admission", 1)
                raise DeadlineExceeded()
            timeout = remaining if timeout is None else min(timeout, remaining)
        if min_version > 0:
            ov = self.overload
            if ov is not None and ov.stale_ok():
                ov.note_stale_served()
            else:
                wait = getattr(self.engine, "wait_for_version", None)
                if wait is not None:
                    wait(
                        min_version,
                        timeout_s=(
                            timeout
                            if timeout is not None
                            else self.max_freshness_wait_s()
                        ),
                    )
            if deadline is not None and time.monotonic() >= deadline:
                self._note_expired("admission", 1)
                raise DeadlineExceeded()
        if self.cache is None:
            ledger_mark("admission")
            res = self._dispatch_direct(requests, max_depth)
            ledger_mark("kernel")
            return res
        version = self.version_fn()
        keys = [(r, max_depth) for r in requests]
        cached = self.cache.get_many(version, keys)
        miss_idx = [i for i, v in enumerate(cached) if v is None]
        # admission covers transport handling, the freshness wait, and
        # the bulk result-cache probe; the engine has not run yet
        ledger_mark("admission")
        if not miss_idx:
            return [bool(v) for v in cached]
        res = self._dispatch_direct(
            [requests[i] for i in miss_idx], max_depth
        )
        ledger_mark("kernel")
        self.cache.put_many(version, [keys[i] for i in miss_idx], res)
        out = [None if v is None else bool(v) for v in cached]
        for i, v in zip(miss_idx, res):
            out[i] = bool(v)
        ledger_mark("decode")
        return out

    def _admit_rows(self) -> int:
        """Chunk size the HBM admission controller will currently accept:
        ``max_batch`` clamped to the budget headroom left by in-flight
        batches. Re-asked per chunk — headroom moves as batches decode."""
        if self.hbm is None:
            return self.max_batch
        return max(1, self.hbm.clamp_rows(self.max_batch))

    def _dispatch_direct(self, requests, max_depth: int) -> list[bool]:
        """Monolithic engine dispatch for a caller-assembled batch, under
        a stage span that joins the caller's trace via the ambient
        contextvar (direct paths run on the transport handler thread)."""
        if self.tracer is not None:
            with self.tracer.span(
                "batcher.dispatch", batch_size=len(requests)
            ):
                res = dispatch_batched(
                    self.engine, requests, max_depth, self._admit_rows()
                )
        else:
            res = dispatch_batched(
                self.engine, requests, max_depth, self._admit_rows()
            )
        obs = self.scrub_observer
        if obs is not None:
            try:
                obs(requests, res)
            except Exception:
                pass  # a broken scrub tap must never fail live checks
        return res

    def check_batch_columnar(
        self,
        cols,
        max_depth: int = 0,
        min_version: int = 0,
        timeout: Optional[float] = None,
    ) -> list[bool]:
        """Columnar twin of ``check_batch``: the caller-assembled batch
        arrives as a ``CheckColumns`` and stays columnar through vocab
        encode and the kernel. Engines with the columnar split API probe
        the encoded-request cache in bulk on the encoded
        (snapshot_version, start, target, depth) id triples; no
        ``RelationTuple``/``Subject`` objects are built unless the
        circuit-breaker fallback fires (lazy materialization inside the
        engine's encoded batch)."""
        if self._closed:
            raise BatcherClosed()
        n = len(cols)
        if n == 0:
            return []
        if self.qos is not None:
            counts: dict[str, int] = {}
            for ns in cols.namespaces:
                counts[ns] = counts.get(ns, 0) + 1
            self.qos.admit_counts(counts)
        if min_version > 0:
            wait = getattr(self.engine, "wait_for_version", None)
            if wait is not None:
                wait(
                    min_version,
                    timeout_s=(
                        timeout
                        if timeout is not None
                        else self.max_freshness_wait_s()
                    ),
                )
        if self._m_columnar is not None:
            self._m_columnar.inc()
        # transport handling + freshness wait up to this point
        ledger_mark("admission")
        if getattr(self.engine, "encode_columns", None) is None:
            return self._columns_via_engine(cols, max_depth)
        out: list[bool] = []
        i = 0
        while i < n:
            step = self._admit_rows()
            chunk = (
                cols
                if i == 0 and n <= step
                else cols.select(range(i, min(i + step, n)))
            )
            out.extend(self._dispatch_columns(chunk, max_depth))
            i += step
        return out

    def _dispatch_columns(self, cols, max_depth: int) -> list[bool]:
        if self.tracer is not None:
            with self.tracer.span(
                "batcher.dispatch", batch_size=len(cols), columnar=1
            ):
                return self._dispatch_columns_inner(cols, max_depth)
        return self._dispatch_columns_inner(cols, max_depth)

    def _dispatch_columns_inner(self, cols, max_depth: int) -> list[bool]:
        """One encoded columnar dispatch: encode into staging, resolve
        cache hits, launch only the misses. Runs on the transport
        handler thread, so ``ledger_mark`` charges each phase to the
        ambient request ledger (the engine itself marks 'kernel' inside
        ``decode_launched``)."""
        enc = self.engine.encode_columns(cols, max_depth)
        cache = self.encoded_cache
        if cache is None:
            ledger_mark("encode")
            launched = self.engine.launch_encoded(enc)
            ledger_mark("launch")
            out = [
                bool(v) for v in self.engine.decode_launched(launched)
            ]
            ledger_mark("decode")
            return out
        keys = enc.keys()
        cached = cache.get_many(enc.version, keys)
        miss = [i for i, v in enumerate(cached) if v is None]
        ledger_mark("encode")
        if not miss:
            enc.release()
            return [bool(v) for v in cached]
        if len(miss) < len(keys):
            enc.compact(miss)
        launched = self.engine.launch_encoded(enc)
        ledger_mark("launch")
        res = self.engine.decode_launched(launched)
        cache.put_many(
            enc.version, [keys[i] for i in miss], [bool(v) for v in res]
        )
        out = [None if v is None else bool(v) for v in cached]
        for i, v in zip(miss, res):
            out[i] = bool(v)
        ledger_mark("decode")
        return out

    def _columns_via_engine(self, cols, max_depth: int) -> list[bool]:
        """Engines without the columnar split API (closure, host oracle):
        dispatch via their ``batch_check_columns`` when present (closure's
        array path), else materialized tuples — with the result cache
        probed in bulk on flat string row keys, not request objects."""
        if self.cache is None:
            res = self._run_columns(cols, max_depth)
            ledger_mark("kernel")
            return res
        version = self.version_fn()
        keys = cols.row_keys(max_depth)
        cached = self.cache.get_many(version, keys)
        miss = [i for i, v in enumerate(cached) if v is None]
        ledger_mark("encode")
        if not miss:
            return [bool(v) for v in cached]
        sub = cols.select(miss) if len(miss) < len(cols) else cols
        res = self._run_columns(sub, max_depth)
        ledger_mark("kernel")
        self.cache.put_many(version, [keys[i] for i in miss], res)
        out = [None if v is None else bool(v) for v in cached]
        for i, v in zip(miss, res):
            out[i] = bool(v)
        ledger_mark("decode")
        return out

    def _run_columns(self, cols, max_depth: int) -> list[bool]:
        """Group commit: if fewer than ``_COLUMNS_PASSES`` engine passes
        are running the call leads a pass at once, else it joins the
        pending list and sleeps until a finishing leader either hands it
        the lead or wakes it with its answers. A leader serves every call
        pending when it starts, up to ``_admit_rows()`` rows, in one
        engine call. Nothing waits for company: a lone call leads a pass
        of one."""
        call = _ColumnsCall(cols, max_depth)
        with self._pass_lock:
            if self._passes < _COLUMNS_PASSES:
                # nothing is pending while a slot is free: a finishing
                # leader hands its slot to the oldest waiter
                self._passes += 1
                call.lead = True
            else:
                self._pending.append(call)
        if not call.lead:
            call.wake.acquire()
            ledger_mark("queue")
            if not call.lead:
                if call.error is not None:
                    raise call.error
                return call.result
        return self._lead_pass(call)

    def _lead_pass(self, call: "_ColumnsCall") -> list[bool]:
        group = [call]
        try:
            room = self._admit_rows() - len(call.cols)
            with self._pass_lock:
                pending = self._pending
                while pending and len(pending[0].cols) <= room:
                    room -= len(pending[0].cols)
                    group.append(pending.pop(0))
            if self._m_pass_calls is not None:
                self._m_pass_calls.observe(len(group))
            if len(group) == 1:
                answers = self._engine_columns(call.cols, call.depth)
            else:
                merged = CheckColumns.concat([c.cols for c in group])
                if all(c.depth == call.depth for c in group):
                    answers = self._engine_columns(merged, call.depth)
                else:
                    answers = self._engine_columns(
                        merged,
                        0,
                        np.repeat(
                            [c.depth for c in group],
                            [len(c.cols) for c in group],
                        ),
                    )
        except BaseException as e:
            self._hand_off()
            for c in group[1:]:
                c.error = e
                c.wake.release()
            raise
        self._hand_off()
        if len(group) == 1:
            return answers
        at = len(call.cols)
        for c in group[1:]:
            c.result = answers[at : at + len(c.cols)]
            at += len(c.cols)
            c.wake.release()
        return answers[: len(call.cols)]

    def _hand_off(self) -> None:
        """End a pass: its slot goes to the oldest pending call, which
        wakes as the next leader, or is freed when none is pending."""
        with self._pass_lock:
            if not self._pending:
                self._passes -= 1
                return
            succ = self._pending.pop(0)
        succ.lead = True
        succ.wake.release()

    def _engine_columns(self, cols, max_depth: int, depths=None) -> list[bool]:
        run = getattr(self.engine, "batch_check_columns", None)
        out: list[bool] = []
        n = len(cols)
        i = 0
        while i < n:
            step = self._admit_rows()
            chunk = (
                cols
                if i == 0 and n <= step
                else cols.select(range(i, min(i + step, n)))
            )
            # per-row depths only where a pass merged calls of two depths
            kw = {} if depths is None else {"depths": depths[i : i + step]}
            if run is not None:
                # the engines' columnar answers are already a list of bools
                out.extend(run(chunk, max_depth, **kw))
            else:
                out.extend(
                    bool(v)
                    for v in self.engine.batch_check(
                        chunk.materialize(), max_depth, **kw
                    )
                )
            i += step
        return out

    def check_batch_encoded(
        self,
        start_ids,
        target_ids,
        depths=None,
        min_version: int = 0,
        timeout: Optional[float] = None,
        ns_counts: Optional[dict] = None,
    ) -> list[bool]:
        """Pre-encoded id batches (array-native clients, the id-native
        wire tier, bench): probe the encoded cache on (start, target,
        depth) triples and dispatch only the misses through the engine's
        array path — zero per-item Python objects end to end.

        ``ns_counts`` is the per-namespace row count the wire front
        derived from the request's namespace-id column (id -> name via
        the vocab-synced NamespaceTable, so only unique tenant names are
        materialized, never per-row strings); when present it is charged
        against the same QoS buckets the string paths use."""
        if self._closed:
            raise BatcherClosed()
        n = len(start_ids)
        if n == 0:
            return []
        if ns_counts and self.qos is not None:
            self.qos.admit_counts(ns_counts)
        if min_version > 0:
            wait = getattr(self.engine, "wait_for_version", None)
            if wait is not None:
                wait(
                    min_version,
                    timeout_s=(
                        timeout
                        if timeout is not None
                        else self.max_freshness_wait_s()
                    ),
                )
        s = np.asarray(start_ids, dtype=np.int64)
        t = np.asarray(target_ids, dtype=np.int64)
        gmax = int(getattr(self.engine, "global_max_depth", 0) or 0)
        if depths is not None:
            want = np.asarray(depths, dtype=np.int32)
        else:
            want = np.zeros(n, dtype=np.int32)
        if gmax > 0:
            d = np.where((want <= 0) | (want > gmax), gmax, want)
        else:
            d = want
        ledger_mark("admission")
        out: list[bool] = []
        i = 0
        while i < n:
            step = self._admit_rows()
            out.extend(
                self._dispatch_encoded(
                    s[i : i + step],
                    t[i : i + step],
                    d[i : i + step],
                )
            )
            i += step
        return out

    def _dispatch_encoded(self, s, t, d) -> list[bool]:
        cache = self.encoded_cache
        keys = None
        if cache is not None and self.version_fn is not None:
            version = self.version_fn()
            keys = list(zip(s.tolist(), t.tolist(), d.tolist()))
            cached = cache.get_many(version, keys)
            miss = [i for i, v in enumerate(cached) if v is None]
            if not miss:
                return [bool(v) for v in cached]
            if len(miss) < len(keys):
                s, t, d = s[miss], t[miss], d[miss]
        res = self._run_encoded(s, t, d)
        if keys is not None:
            cache.put_many(
                version,
                [keys[i] for i in miss],
                [bool(v) for v in res],
            )
            out = [None if v is None else bool(v) for v in cached]
            for i, v in zip(miss, res):
                out[i] = bool(v)
            ledger_mark("decode")
            return out
        ledger_mark("decode")
        return [bool(v) for v in res]

    def _run_encoded(self, s, t, d) -> list[bool]:
        # prefer the split encode/launch/decode path: the circuit-breaker
        # wrapper overrides launch/decode, so a breaker-open or failed
        # batch is re-answered by the host oracle from tuples the
        # encoded batch materializes lazily out of the id arrays
        encode_ids = getattr(self.engine, "encode_ids", None)
        if encode_ids is not None:
            enc = encode_ids(s, t, d)
            ledger_mark("encode")
            launched = self.engine.launch_encoded(enc)
            ledger_mark("launch")
            return [
                bool(v) for v in self.engine.decode_launched(launched)
            ]
        check_ids = getattr(self.engine, "check_ids", None)
        if check_ids is None:
            raise ErrInternal(
                "engine has no array-native check path "
                "(check_batch_encoded needs check_ids or encode_ids)"
            )
        # the closure engine's array path wants per-row subject kinds;
        # derive them from the vocab (ids out of range read as sets —
        # they clamp to the inert dummy downstream anyway)
        is_id = np.zeros(len(t), dtype=bool)
        snaps = getattr(self.engine, "snapshots", None)
        if snaps is not None:
            is_set = snaps.snapshot().vocab.is_set_array()
            safe = (t >= 0) & (t < len(is_set))
            is_id[safe] = ~is_set[t[safe]]
        return [bool(v) for v in check_ids(s, t, is_id, d)]

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        # the stages drain the queue before exiting; the join budget only
        # runs out when the engine itself is wedged (the sick-chip
        # hang-not-raise mode) — then every waiter is failed typed instead
        # of hanging past shutdown
        deadline = time.monotonic() + self.close_join_s
        for t in self._threads:
            t.join(timeout=max(0.0, deadline - time.monotonic()))
        with self._cv:
            leftovers = self._queue + self._inflight
            self._queue = []
            self._inflight = []
            for b in self._pipe_batches.values():
                leftovers.extend(b.items)
            self._pipe_batches = {}
        for item in leftovers:
            f = item[2]
            if not f.done():
                f.set_exception(BatcherClosed())

    def pipeline_stats(self) -> dict:
        """Queue/stage occupancy snapshot — surfaced by the read plane's
        stats endpoints so pipeline health is observable without scraping."""
        with self._lock:
            expired = dict(self._cull_expired_counts)
            cancelled = dict(self._cull_cancelled_counts)
        out = {
            "pipelined": self.pipelined,
            "queue_depth": len(self._queue),
            "max_queue": self.max_queue,
            "max_batch": self.max_batch,
            "deadline_expired": expired,
            "cancelled": cancelled,
        }
        if self.overload is not None:
            out["overload"] = self.overload.snapshot()
        if self.pipelined:
            with self._lock:
                inflight = len(self._pipe_batches)
            out.update(
                {
                    "pipeline_depth": self.pipeline_depth,
                    "encode_workers": self.encode_workers,
                    "launch_queue_depth": self._launch_q.qsize(),
                    "decode_queue_depth": self._decode_q.qsize(),
                    "batches_in_pipeline": inflight,
                    "encoded_cache_entries": (
                        len(self.encoded_cache)
                        if self.encoded_cache is not None
                        else 0
                    ),
                }
            )
        return out

    def reconfigure(
        self,
        pipeline_depth: Optional[int] = None,
        encode_workers: Optional[int] = None,
    ) -> bool:
        """Resize the dispatch pipeline on a live batcher — the autotuner's
        seam for ``engine.pipeline_depth`` / ``engine.encode_workers``.

        Correctness contract: in-flight batches drain FIRST. The quiesce
        flag makes every stage loop exit through :meth:`_await_work`
        without draining the admission queue; the encode-worker sentinel
        cascade then flushes everything already past encode through
        launch/decode in FIFO order, so no caller future is dropped or
        failed by a clean resize. Queued requests simply wait out the
        swap (callers block on their futures as usual) and the rebuilt
        stage threads pick them up. Serial <-> pipelined transitions are
        handled: the new shape is re-derived from the engine's
        capabilities exactly as in ``__init__``.

        Only a wedged engine can exhaust the join budget; the batches a
        wedged stage still holds are then failed typed (retryable), the
        same contract a stage death gives.

        Returns True when the pipeline was rebuilt, False for a no-op.
        Fault site ``batcher.reconfigure_stall`` stalls the drain window
        (tests/test_faults.py drills traffic through it)."""
        with self._reconfig_lock:
            new_depth = (
                self.pipeline_depth
                if pipeline_depth is None
                else max(0, int(pipeline_depth))
            )
            new_workers = (
                self.encode_workers
                if encode_workers is None
                else max(1, int(encode_workers))
            )
            if (
                new_depth == self.pipeline_depth
                and new_workers == self.encode_workers
            ):
                return False
            with self._cv:
                if self._closed:
                    raise BatcherClosed()
                self._quiesce = True
                self._cv.notify_all()
            # the drain window: in-flight batches flush through the
            # sentinel cascade while new arrivals pool in the queue
            FAULTS.maybe_sleep("batcher.reconfigure_stall")
            deadline = time.monotonic() + self.close_join_s
            for t in self._threads:
                t.join(timeout=max(0.0, deadline - time.monotonic()))
            stragglers: list[tuple] = []
            with self._cv:
                # a wedged stage (engine hang) keeps its batch past the
                # join budget: fail exactly those typed, like a stage
                # death would — queued entries are NOT touched
                stragglers.extend(self._inflight)
                self._inflight = []
                for b in self._pipe_batches.values():
                    stragglers.extend(b.items)
                self._pipe_batches = {}
                self._quiesce = False
                self.pipeline_depth = new_depth
                self.encode_workers = new_workers
                sup = getattr(self.engine, "pipeline_supported", None)
                capable = (
                    sup()
                    if callable(sup)
                    else callable(getattr(self.engine, "encode_batch", None))
                )
                self.pipelined = new_depth >= 1 and capable
            for item in stragglers:
                f = item[2]
                if not f.done():
                    f.set_exception(DispatcherCrashed())
            if self.pipelined:
                self._launch_q = _queue_mod.Queue(
                    maxsize=max(2, self.encode_workers)
                )
                self._decode_q = _queue_mod.Queue(
                    maxsize=max(1, new_depth)
                )
                self._encoders_live = self.encode_workers
                self._register_pipeline_metrics()
                self._threads = self._spawn_pipeline()
                self._thread = self._threads[0]
            else:
                self._thread = self._spawn_dispatcher()
                self._threads = [self._thread]
            if self._logger is not None:
                self._logger.info(
                    "check batcher reconfigured",
                    pipeline_depth=new_depth,
                    encode_workers=new_workers,
                    pipelined=self.pipelined,
                    failed_stragglers=len(stragglers),
                )
            return True

    # -- shared plumbing -----------------------------------------------------

    def _drain(self) -> list[tuple]:
        ov = self.overload
        if ov is not None and self._queue:
            cutoff = ov.cull_age_s()
            if cutoff is not None:
                # CoDel cull under sustained pressure: entries that have
                # already queued past the delay target would blow their
                # budget anyway — fail them typed now, free the slots
                now = time.perf_counter()
                kept: list[tuple] = []
                culled = 0
                for it in self._queue:
                    # critical-class entries are exempt: the plane's
                    # promise is that only the max_queue backstop ever
                    # drops critical work (adaptive LIFO may still serve
                    # it late, but it is never failed by the cull)
                    if (
                        now - it[3] > cutoff
                        and not (len(it) > 7 and it[7] == "critical")
                    ):
                        f = it[2]
                        if not f.done():
                            f.set_exception(
                                BatcherOverloaded(
                                    "The check queued past the standing-"
                                    "queue delay target and was culled; "
                                    "retry with backoff."
                                )
                            )
                        culled += 1
                    else:
                        kept.append(it)
                if culled:
                    self._queue[:] = kept
                    ov.note_culled(culled)
            if ov.lifo() and self._queue:
                # adaptive-LIFO while overloaded: the newest entries are
                # the ones most likely to still meet their deadlines
                batch = self._queue[-self._admit_rows():]
                del self._queue[-len(batch):]
                return batch
        batch = self._queue[: self._admit_rows()]
        del self._queue[: len(batch)]
        return batch

    def _await_work(self) -> Optional[list[tuple]]:
        """Block for queued requests; returns None on clean shutdown with
        an empty queue — or immediately on a reconfigure quiesce, BEFORE
        draining, so queued entries stay intact for the rebuilt pipeline —
        else the drained batch (after the accumulation window when only
        one request is waiting)."""
        with self._cv:
            while not self._queue and not self._closed and not self._quiesce:
                self._cv.wait()
            if self._quiesce and not self._closed:
                return None
            if self._closed and not self._queue:
                return None
            first_only = len(self._queue) == 1
        if first_only and self.window_s > 0:
            # brief accumulation window; under load the device round-trip
            # itself provides the window and this never triggers
            time.sleep(self.window_s)
        with self._cv:
            return self._drain()

    def _observe(self, stage: str, seconds: float) -> None:
        if self._m_stage is not None:
            self._m_stage.labels(stage=stage).observe(seconds)

    @staticmethod
    def _batch_parent(items):
        """Parent context for a stage span: the first queue entry that
        carries one — a batch span joins one representative caller
        trace (the batch serves many traces; OTLP has no multi-parent)."""
        for it in items:
            if len(it) > 6 and it[6] is not None:
                return it[6]
        return None

    @staticmethod
    def _mark_items(items, stage: str, now: Optional[float] = None) -> None:
        """Charge ``stage`` on every entry's ledger. Safe cross-thread:
        each entry's marks are sequential (stage handoffs through the
        bounded queues give the happens-before), and marks always run
        BEFORE the entry's future resolves so they never race the
        caller's serialize/reply marks."""
        for it in items:
            led = it[5] if len(it) > 5 else None
            if led is not None:
                led.mark(stage, now)

    # -- deadline / cancellation culling ---------------------------------------

    def _note_expired(self, stage: str, n: int) -> None:
        if self._m_deadline is not None:
            self._m_deadline.labels(stage=stage).inc(n)
        with self._lock:
            self._cull_expired_counts[stage] = (
                self._cull_expired_counts.get(stage, 0) + n
            )

    def _note_cancelled(self, stage: str, n: int) -> None:
        if self._m_cancelled is not None:
            self._m_cancelled.labels(stage=stage).inc(n)
        with self._lock:
            self._cull_cancelled_counts[stage] = (
                self._cull_cancelled_counts.get(stage, 0) + n
            )

    def _cull(self, items: list, stage: str) -> tuple[list, list[int]]:
        """Drop entries whose caller gave up — deadline passed (their
        future fails typed with :class:`DeadlineExceeded`) or future
        cancelled on client disconnect — so the stage ahead never pays
        for them. Returns (kept entries, their indices in ``items``);
        the index list lets the launch stage compact staged device
        buffers in the same motion."""
        now = time.monotonic()
        kept: list = []
        keep_idx: list[int] = []
        expired = cancelled = 0
        for i, it in enumerate(items):
            f = it[2]
            if f.cancelled():
                cancelled += 1
                continue
            dl = it[4]
            if dl is not None and now >= dl:
                if not f.done():
                    f.set_exception(DeadlineExceeded())
                expired += 1
                continue
            kept.append(it)
            keep_idx.append(i)
        if expired:
            self._note_expired(stage, expired)
        if cancelled:
            self._note_cancelled(stage, cancelled)
        return kept, keep_idx

    # -- serial dispatcher ---------------------------------------------------

    def _run_guard(self) -> None:
        """Watchdog shell around the dispatch loop: a dispatcher death must
        not strand callers (their futures would never resolve) or kill
        batching for the process lifetime. In-flight futures fail typed;
        queued ones survive for the replacement thread."""
        while True:
            try:
                self._run()
                return  # clean close
            except BaseException:
                with self._cv:
                    inflight = self._inflight
                    self._inflight = []
                    closed = self._closed
                for item in inflight:
                    f = item[2]
                    if not f.done():
                        f.set_exception(DispatcherCrashed())
                if self._m_restarts is not None:
                    self._m_restarts.inc()
                if self._logger is not None:
                    self._logger.warn(
                        "check dispatcher died; restarting",
                        failed_inflight=len(inflight),
                    )
                if closed:
                    return

    def _run(self) -> None:
        while True:
            FAULTS.fire("batcher.dispatcher_die")
            batch = self._await_work()
            if batch is None:
                return
            batch, _ = self._cull(batch, "dispatch")
            if not batch:
                continue
            FAULTS.maybe_sleep("batcher.dispatch_slow")
            with self._cv:
                self._inflight = batch
            if self._m_batch_size is not None:
                self._m_batch_size.observe(len(batch))
            t_dispatch = time.perf_counter()
            self._mark_items(batch, "queue", t_dispatch)
            requests = [b[0] for b in batch]
            depths = [b[1] for b in batch]
            span = None
            if self.tracer is not None:
                span = self.tracer.span(
                    "batcher.dispatch",
                    parent=self._batch_parent(batch),
                    batch_size=len(batch),
                )
            try:
                if span is not None:
                    with span:
                        results = self.engine.batch_check(
                            requests, depths=depths
                        )
                else:
                    results = self.engine.batch_check(
                        requests, depths=depths
                    )
            except Exception as e:  # propagate to every caller in the batch
                for item in batch:
                    f = item[2]
                    if not f.done():
                        f.set_exception(e)
                with self._cv:
                    self._inflight = []
                continue
            if self.overload is not None:
                # queue delay (oldest entry's wait) + engine service time
                # feed the adaptive limiter's AIMD/CoDel signal
                self.overload.observe(
                    t_dispatch - min(it[3] for it in batch),
                    time.perf_counter() - t_dispatch,
                )
            # the serial engine call is monolithic (encode+kernel+decode
            # in one); charge it all to 'kernel', marked before the
            # futures resolve so callers' marks can't race
            self._mark_items(batch, "kernel")
            for item, allowed in zip(batch, results):
                f = item[2]
                if not f.done():
                    f.set_result(bool(allowed))
            obs = self.scrub_observer
            if obs is not None:
                try:
                    obs(requests, results)
                except Exception:
                    pass  # a broken scrub tap must never fail live checks
            with self._cv:
                self._inflight = []

    # -- pipelined stages ----------------------------------------------------

    def _register(self, batch: _PBatch) -> None:
        with self._lock:
            self._pipe_batches[id(batch)] = batch

    def _complete(self, batch: _PBatch) -> None:
        with self._lock:
            self._pipe_batches.pop(id(batch), None)
        if self.hbm is not None and batch.hbm_token:
            self.hbm.release(batch.hbm_token)
            batch.hbm_token = 0

    def _fail_batch(self, batch: _PBatch, exc: BaseException) -> None:
        self._complete(batch)
        if batch.enc is not None:
            batch.enc.release()
        for item in batch.items:
            f = item[2]
            if not f.done():
                f.set_exception(exc)

    def _stage_guard(self, loop_fn, stage: str) -> None:
        """Watchdog shell shared by the launch/decode stages (encode adds
        worker accounting on top): a stage death fails exactly the batch
        that stage held, typed and retryable, then restarts the stage.
        Batches owned by the queues or by other stages are untouched."""
        while True:
            holder = _Holder()
            try:
                loop_fn(holder)
                return  # clean close
            except BaseException:
                batch, holder.batch = holder.batch, None
                if batch is not None:
                    self._fail_batch(batch, DispatcherCrashed())
                if self._m_restarts is not None:
                    self._m_restarts.inc()
                if self._logger is not None:
                    self._logger.warn(
                        "check pipeline stage died; restarting",
                        stage=stage,
                        failed_inflight=0 if batch is None else len(batch.items),
                    )
                if self._closed:
                    return

    def _encode_guard(self) -> None:
        self._stage_guard(self._encode_loop, "encode")
        # clean exit: the LAST encode worker out sends the shutdown
        # sentinel downstream so launch/decode drain and exit in order
        with self._lock:
            self._encoders_live -= 1
            last = self._encoders_live == 0
        if last:
            self._launch_q.put(_SENTINEL)

    def _encode_loop(self, holder: _Holder) -> None:
        while True:
            items = self._await_work()
            if items is None:
                return
            items, _ = self._cull(items, "encode")
            if not items:
                continue
            if self.tracer is not None:
                with self.tracer.span(
                    "batcher.encode",
                    parent=self._batch_parent(items),
                    batch_size=len(items),
                ):
                    self._encode_step(items, holder)
            else:
                self._encode_step(items, holder)

    def _encode_step(self, items: list, holder: _Holder) -> None:
        batch = _PBatch(items)
        holder.batch = batch
        self._register(batch)
        FAULTS.fire("batcher.encode_die")
        FAULTS.maybe_sleep("batcher.encode_slow")
        t0 = time.perf_counter()
        self._observe("enqueue", t0 - min(it[3] for it in items))
        if self.overload is not None:
            # pipelined shape: the queue delay is the limiter signal; the
            # per-stage service time is already attributed downstream
            self.overload.observe(t0 - min(it[3] for it in items))
        self._mark_items(items, "queue", t0)
        if self._m_batch_size is not None:
            self._m_batch_size.observe(len(items))
        requests = [it[0] for it in items]
        depths = [it[1] for it in items]
        try:
            enc = self.engine.encode_batch(requests, depths=depths)
        except Exception as e:
            self._fail_batch(batch, e)
            holder.batch = None
            return
        batch.enc = enc
        if self.encoded_cache is not None:
            # encoded-request cache: rows answered at this snapshot
            # version resolve here; only the misses ride the kernel
            keys = enc.keys()
            cached = self.encoded_cache.get_many(enc.version, keys)
            miss = [i for i, v in enumerate(cached) if v is None]
            if len(miss) < len(items):
                now = time.perf_counter()
                for i, v in enumerate(cached):
                    if v is not None:
                        it = items[i]
                        led = it[5] if len(it) > 5 else None
                        if led is not None:
                            led.mark("encode", now)
                        f = it[2]
                        if not f.done():
                            f.set_result(bool(v))
                if not miss:
                    enc.release()
                    self._complete(batch)
                    holder.batch = None
                    self._observe("encode", time.perf_counter() - t0)
                    return
                enc.compact(miss)
                batch.items = [items[i] for i in miss]
                batch.keys = [keys[i] for i in miss]
            else:
                batch.keys = keys
        self._observe("encode", time.perf_counter() - t0)
        batch.t_encoded = time.perf_counter()
        self._mark_items(batch.items, "encode", batch.t_encoded)
        # ownership passes to the launch queue; bounded put is the
        # encode stage's backpressure
        self._set_deadlines(batch.enc, batch.items)
        holder.batch = None
        self._launch_q.put(batch)

    @staticmethod
    def _set_deadlines(enc, items) -> None:
        """Stamp per-row caller deadlines onto the encoded batch so the
        circuit-breaker fallback can skip re-answering rows whose caller
        already gave up. Best-effort: engines whose encoded type can't
        carry the attribute just lose the optimization."""
        try:
            enc.deadlines = [it[4] for it in items]
        except (AttributeError, TypeError):
            pass

    def _launch_loop(self, holder: _Holder) -> None:
        while True:
            batch = self._launch_q.get()
            if batch is _SENTINEL:
                self._decode_q.put(_SENTINEL)
                return
            if self.tracer is not None:
                with self.tracer.span(
                    "batcher.launch",
                    parent=self._batch_parent(batch.items),
                    batch_size=len(batch.items),
                ):
                    self._launch_step(batch, holder)
            else:
                self._launch_step(batch, holder)

    def _launch_step(self, batch: _PBatch, holder: _Holder) -> None:
        holder.batch = batch
        # the device stage inherits the PR-1 dispatcher fault site:
        # "the dispatcher" is now the thread that talks to the device
        FAULTS.fire("batcher.dispatcher_die")
        FAULTS.maybe_sleep("batcher.launch_slow")
        # cull rows that died waiting in the launch queue BEFORE the
        # kernel dispatch: compacting the staged buffers here is the
        # last chance to not pay device time for them
        kept, keep_idx = self._cull(batch.items, "launch")
        if not kept:
            batch.enc.release()
            self._complete(batch)
            holder.batch = None
            return
        if len(kept) < len(batch.items):
            batch.enc.compact(keep_idx)
            batch.items = kept
            if batch.keys is not None:
                batch.keys = [batch.keys[i] for i in keep_idx]
            self._set_deadlines(batch.enc, batch.items)
        if self.hbm is not None:
            # charge the batch's modeled HBM footprint before dispatch;
            # released in _complete/_fail_batch once it leaves the device
            batch.hbm_token = self.hbm.reserve(
                getattr(batch.enc, "b", 0) or 0,
                getattr(batch.enc, "version", 0) or 0,
            )
        try:
            batch.launched = self.engine.launch_encoded(batch.enc)
        except Exception as e:
            self._fail_batch(batch, e)
            holder.batch = None
            return
        # launch = queue wait + kernel enqueue (async dispatch: this
        # does NOT include device execution, which overlaps the next
        # batch's encode/launch)
        self._observe("launch", time.perf_counter() - batch.t_encoded)
        self._mark_items(batch.items, "launch")
        holder.batch = None
        # bounded put: blocks once pipeline_depth batches await decode,
        # which is what caps batches in flight on device
        self._decode_q.put(batch)

    def _decode_loop(self, holder: _Holder) -> None:
        while True:
            batch = self._decode_q.get()
            if batch is _SENTINEL:
                return
            if self.tracer is not None:
                with self.tracer.span(
                    "batcher.decode",
                    parent=self._batch_parent(batch.items),
                    batch_size=len(batch.items),
                ):
                    self._decode_step(batch, holder)
            else:
                self._decode_step(batch, holder)

    def _decode_step(self, batch: _PBatch, holder: _Holder) -> None:
        holder.batch = batch
        FAULTS.fire("batcher.decode_die")
        FAULTS.maybe_sleep("batcher.decode_slow")
        # rows that died on device still decode (the kernel already
        # ran; materializing frees the staging buffers) but their
        # callers are failed typed NOW instead of after the blocking
        # materialization — items stay in place so results align
        now = time.monotonic()
        n_expired = 0
        for item in batch.items:
            f = item[2]
            dl = item[4]
            if dl is not None and now >= dl and not f.done():
                f.set_exception(DeadlineExceeded())
                n_expired += 1
        if n_expired:
            self._note_expired("decode", n_expired)
        t0 = time.perf_counter()
        try:
            results = self.engine.decode_launched(batch.launched)
        except Exception as e:
            self._fail_batch(batch, e)
            holder.batch = None
            return
        # device = block-until-materialized; with the pipeline full
        # this approaches pure device execution time per batch
        t1 = time.perf_counter()
        self._observe("device", t1 - t0)
        for item, allowed in zip(batch.items, results):
            f = item[2]
            led = item[5] if len(item) > 5 else None
            if led is not None:
                # kernel = launch-mark -> materialized; decode = the
                # residual up to this row's future resolution. Marked
                # BEFORE set_result so the woken caller's serialize/
                # reply marks cannot race the ledger.
                led.mark("kernel", t1)
                led.mark("decode")
            if allowed is not None and not f.done():
                f.set_result(bool(allowed))
        obs = self.scrub_observer
        if obs is not None:
            # rows the fallback skipped as already-dead carry None; only
            # real answers are replay candidates
            pairs = [
                (item[0], v)
                for item, v in zip(batch.items, results)
                if v is not None
            ]
            if pairs:
                try:
                    obs([p[0] for p in pairs], [p[1] for p in pairs])
                except Exception:
                    pass  # a broken scrub tap must never fail live checks
        if self.encoded_cache is not None and batch.keys is not None:
            # a None result marks a row the fallback skipped as
            # already-dead: nothing to cache for it
            live = [
                (k, bool(v))
                for k, v in zip(batch.keys, results)
                if v is not None
            ]
            if live:
                self.encoded_cache.put_many(
                    batch.enc.version,
                    [k for k, _ in live],
                    [v for _, v in live],
                )
        self._complete(batch)
        self._observe("decode", time.perf_counter() - t1)
        holder.batch = None


def dispatch_batched(
    engine, requests: Sequence[RelationTuple], max_depth: int, max_batch: int
) -> list[bool]:
    """Dispatch a caller-assembled batch in max_batch slices so one giant
    request cannot balloon the engine's working set past what every other
    path is capped at. Shared by every batch-transport checker."""
    out: list[bool] = []
    for i in range(0, len(requests), max_batch):
        out.extend(
            bool(v)
            for v in engine.batch_check(
                requests[i : i + max_batch], max_depth
            )
        )
    return out
