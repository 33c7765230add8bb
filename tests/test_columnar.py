"""Columnar check path parity suite (PR 3): the zero-copy wire->vocab->
kernel batch path must be answer-identical to the per-tuple convert path
and to the host oracle — through every layer it touches.

- CheckColumns decode/validate semantics (malformed rows must 400, not
  crash or mis-answer; unicode namespaces round-trip)
- fuzzed parity: random graphs + random batches through batch_check
  (per-tuple), batch_check_columns, CheckBatcher.check_batch_columnar,
  and CheckBatcher.check_batch_encoded, all against CheckEngine (oracle)
- breaker-open fallback: an encoded/columnar batch re-answered by the
  host oracle from lazily materialized tuples, answers unchanged
- result-cache correctness across writes (snapshot-version stamps)
- live-server REST + gRPC columnar transports: parity with the per-tuple
  transport, malformed bodies rejected with 400/INVALID_ARGUMENT
"""

import asyncio
import json
import threading
import time

import grpc
import httpx
import jax
import numpy as np
import pytest

from keto_tpu.api import acl_pb2, check_service_pb2
from keto_tpu.api.services import CheckServiceStub
from keto_tpu.driver import Config, Registry
from keto_tpu.engine.batcher import CheckBatcher
from keto_tpu.engine.check import CheckEngine
from keto_tpu.engine.cache import CheckResultCache
from keto_tpu.engine.closure import ClosureCheckEngine
from keto_tpu.engine.fallback import DeviceFallbackEngine
from keto_tpu.faults import FAULTS
from keto_tpu.graph import SnapshotManager
from keto_tpu.parallel import ShardedServingEngine, make_mesh
from keto_tpu.relationtuple import (
    CheckColumns,
    RelationTuple,
    SubjectID,
    SubjectSet,
)
from keto_tpu.store import InMemoryTupleStore
from keto_tpu.utils.errors import ErrMalformedInput

# unicode namespaces ride every fuzz round: the columnar path must carry
# them byte-identically through proto/json/vocab
_NAMESPACES = ("n", "ns-日本語", "grüße")
_RELATIONS = ("view", "edit", "member")


def _t(s: str) -> RelationTuple:
    return RelationTuple.from_string(s)


def _random_store(rng, n_tuples=150):
    store = InMemoryTupleStore()
    tuples = []
    seen = set()
    while len(tuples) < n_tuples:
        ns = _NAMESPACES[rng.integers(len(_NAMESPACES))]
        obj = f"o{rng.integers(12)}"
        rel = _RELATIONS[rng.integers(len(_RELATIONS))]
        if rng.random() < 0.35:
            subject = SubjectSet(
                namespace=_NAMESPACES[rng.integers(len(_NAMESPACES))],
                object=f"o{rng.integers(12)}",
                relation=_RELATIONS[rng.integers(len(_RELATIONS))],
            )
        else:
            subject = SubjectID(id=f"u{rng.integers(20)}")
        tup = RelationTuple(
            namespace=ns, object=obj, relation=rel, subject=subject
        )
        if str(tup) not in seen:
            seen.add(str(tup))
            tuples.append(tup)
    store.write_relation_tuples(*tuples)
    return store


def _random_requests(rng, k):
    """Random check batch: existing-ish keys plus guaranteed vocab misses
    (unknown namespaces/objects/users)."""
    reqs = []
    for _ in range(k):
        miss = rng.random() < 0.2
        ns = "missing-ns" if miss else _NAMESPACES[rng.integers(3)]
        obj = f"o{rng.integers(14)}"
        rel = _RELATIONS[rng.integers(3)]
        if rng.random() < 0.3:
            subject = SubjectSet(
                namespace=_NAMESPACES[rng.integers(3)],
                object=f"o{rng.integers(14)}",
                relation=_RELATIONS[rng.integers(3)],
            )
        else:
            subject = SubjectID(id=f"u{rng.integers(24)}")
        reqs.append(
            RelationTuple(
                namespace=ns, object=obj, relation=rel, subject=subject
            )
        )
    return reqs


class TestCheckColumns:
    def test_from_tuples_materialize_roundtrip(self):
        reqs = [
            _t("n:doc0#view@alice"),
            _t("ns-日本語:doc1#edit@(grüße:team0#member)"),
        ]
        cols = CheckColumns.from_tuples(reqs)
        assert len(cols) == 2
        assert cols.materialize() == reqs
        assert cols.is_id_rows() == [True, False]
        assert cols.start_keys()[1] == ("ns-日本語", "doc1", "edit")
        assert cols.target_keys() == [
            ("alice",),
            ("grüße", "team0", "member"),
        ]

    def test_validate_normalizes_omitted_subject_columns(self):
        cols = CheckColumns(
            ["n", "n"], ["o1", "o2"], ["view", "view"],
            subject_ids=["alice", "bob"],
        ).validate()
        assert cols.subject_set_namespaces == ["", ""]
        assert cols.materialize()[0].subject == SubjectID(id="alice")

    def test_row_without_subject_rejected(self):
        with pytest.raises(ErrMalformedInput, match="without subject"):
            CheckColumns(
                ["n"], ["o"], ["view"], subject_ids=[""]
            ).validate()

    def test_row_with_both_subject_forms_rejected(self):
        with pytest.raises(ErrMalformedInput, match="both subject_id"):
            CheckColumns(
                ["n"], ["o"], ["view"],
                subject_ids=["alice"],
                subject_set_namespaces=["n"],
                subject_set_objects=["g"],
                subject_set_relations=["member"],
            ).validate()

    def test_length_mismatch_rejected(self):
        with pytest.raises(ErrMalformedInput, match="length mismatch"):
            CheckColumns(["n", "n"], ["o"], ["view", "view"]).validate()
        with pytest.raises(ErrMalformedInput, match="length mismatch"):
            CheckColumns(
                ["n", "n"], ["o1", "o2"], ["view", "view"],
                subject_ids=["alice"],
            ).validate()

    def test_rest_body_type_guard(self):
        with pytest.raises(ErrMalformedInput, match="array of strings"):
            CheckColumns.from_rest_body(
                {"namespaces": "n", "objects": ["o"], "relations": ["v"]}
            )
        with pytest.raises(ErrMalformedInput, match="array of strings"):
            CheckColumns.from_rest_body(
                {
                    "namespaces": ["n"],
                    "objects": [1],
                    "relations": ["v"],
                    "subject_ids": ["a"],
                }
            )

    def test_select_keeps_parallel_rows(self):
        reqs = [_t(f"n:o{i}#view@u{i}") for i in range(5)]
        cols = CheckColumns.from_tuples(reqs)
        sub = cols.select([0, 3])
        assert sub.materialize() == [reqs[0], reqs[3]]

    def test_concat_keeps_rows_in_order(self):
        a = CheckColumns.from_tuples([_t("n:o0#view@u0")])
        b = CheckColumns(["n", "n"], ["o1", "o2"], ["view", "edit"],
                         subject_ids=["u1", "u2"])  # set columns omitted
        c = CheckColumns.from_tuples([_t("n:o3#view@(n:g#member)")])
        got = CheckColumns.concat([a, b, CheckColumns([], [], []), c])
        assert len(got) == 4
        assert got.materialize() == [
            _t("n:o0#view@u0"), _t("n:o1#view@u1"), _t("n:o2#edit@u2"),
            _t("n:o3#view@(n:g#member)"),
        ]
        assert got.validate() is got


class TestEngineParityFuzz:
    @pytest.mark.parametrize("seed", [3, 11, 29])
    def test_columnar_paths_match_oracle(self, seed):
        rng = np.random.default_rng(seed)
        store = _random_store(rng)
        snaps = SnapshotManager(store)
        oracle = CheckEngine(store, max_depth=5)
        device = ClosureCheckEngine(snaps, max_depth=5, query_mode="device")
        closure = ClosureCheckEngine(snaps, max_depth=5, query_mode="host")
        batcher = CheckBatcher(device, window_s=0)
        try:
            for _round in range(3):
                reqs = _random_requests(rng, 48)
                want = [
                    bool(oracle.subject_is_allowed(r, 5)) for r in reqs
                ]
                cols = CheckColumns.from_tuples(reqs)
                # per-tuple convert path (the pre-existing contract)
                assert [
                    bool(v) for v in device.batch_check(reqs)
                ] == want
                # engine-level columnar
                for eng in (device, closure):
                    got = eng.batch_check_columns(cols)
                    assert [bool(v) for v in got] == want, type(eng)
                # batcher columnar (twice: second round rides the caches)
                for _ in range(2):
                    got = batcher.check_batch_columnar(cols)
                    assert [bool(v) for v in got] == want
                # pre-encoded id path
                snap = snaps.snapshot()
                s_ids, t_ids = snap.encode_requests_columnar(cols)
                for _ in range(2):
                    got = batcher.check_batch_encoded(s_ids, t_ids)
                    assert [bool(v) for v in got] == want
        finally:
            batcher.close()

    def test_closure_batcher_parity(self):
        """The serial engine path (row_keys cache, no encode/launch
        split) must agree with the oracle too."""
        rng = np.random.default_rng(5)
        store = _random_store(rng)
        snaps = SnapshotManager(store)
        oracle = CheckEngine(store, max_depth=5)
        store_ref = store
        batcher = CheckBatcher(
            ClosureCheckEngine(snaps, max_depth=5), window_s=0,
            cache=CheckResultCache(256),
            version_fn=lambda: store_ref.version,
        )
        try:
            reqs = _random_requests(rng, 40)
            want = [bool(oracle.subject_is_allowed(r, 5)) for r in reqs]
            cols = CheckColumns.from_tuples(reqs)
            for _ in range(2):
                got = batcher.check_batch_columnar(cols)
                assert [bool(v) for v in got] == want
        finally:
            batcher.close()


class TestBreakerFallbackParity:
    """PR-1 failure semantics preserved: with the circuit open, columnar
    and encoded batches are re-answered by the host oracle from lazily
    materialized tuples — identical answers, no per-tuple objects on the
    healthy path."""

    @pytest.fixture(autouse=True)
    def _clean_faults(self):
        FAULTS.reset()
        yield
        FAULTS.reset()

    def _fixture(self):
        rng = np.random.default_rng(17)
        store = _random_store(rng)
        snaps = SnapshotManager(store)
        fb = DeviceFallbackEngine(
            ShardedServingEngine(
                snaps, mesh=make_mesh(jax.devices()[:2], data=1),
                max_depth=5,
            ),
            lambda: CheckEngine(store, max_depth=5),
            failure_threshold=1,
            cooldown_s=60.0,
        )
        oracle = CheckEngine(store, max_depth=5)
        reqs = _random_requests(rng, 32)
        want = [bool(oracle.subject_is_allowed(r, 5)) for r in reqs]
        return snaps, fb, reqs, want

    def test_columnar_fallback_on_raise(self):
        snaps, fb, reqs, want = self._fixture()
        cols = CheckColumns.from_tuples(reqs)
        assert fb.batch_check_columns(cols) == want  # healthy
        FAULTS.arm("device.compile_error", times=1)
        assert fb.batch_check_columns(cols) == want  # trip + re-answer
        assert fb.circuit_open()
        assert fb.batch_check_columns(cols) == want  # open: oracle serves

    def test_batcher_columnar_and_encoded_fallback(self):
        snaps, fb, reqs, want = self._fixture()
        cols = CheckColumns.from_tuples(reqs)
        b = CheckBatcher(fb, window_s=0, encoded_cache_size=0)
        try:
            assert [bool(v) for v in b.check_batch_columnar(cols)] == want
            FAULTS.arm("device.compile_error", times=1)
            got = b.check_batch_columnar(cols)
            assert [bool(v) for v in got] == want
            assert fb.circuit_open()
            # pure-id encoded batches while open: tuples decoded from the
            # snapshot vocab before the oracle re-answers
            snap = snaps.snapshot()
            s_ids, t_ids = snap.encode_requests_columnar(cols)
            got = b.check_batch_encoded(s_ids, t_ids)
            assert [bool(v) for v in got] == want
        finally:
            b.close()

    def test_encoded_garbage_batch_reanswered(self):
        snaps, fb, reqs, want = self._fixture()
        cols = CheckColumns.from_tuples(reqs)
        b = CheckBatcher(fb, window_s=0, encoded_cache_size=0)
        try:
            snap = snaps.snapshot()
            s_ids, t_ids = snap.encode_requests_columnar(cols)
            assert [bool(v) for v in b.check_batch_encoded(s_ids, t_ids)] == want
            FAULTS.arm("device.batch_nan", times=1)
            got = b.check_batch_encoded(s_ids, t_ids)
            assert [bool(v) for v in got] == want
        finally:
            b.close()


class TestResultCacheFreshness:
    def test_cache_does_not_serve_stale_answers_across_writes(self):
        """The result cache the served columnar path probes is stamped
        with the store version: a write makes every cached row a miss."""
        store = InMemoryTupleStore()
        store.write_relation_tuples(
            _t("n:doc0#view@(n:team0#member)"),
            _t("n:team0#member@alice"),
        )
        snaps = SnapshotManager(store)
        engine = ClosureCheckEngine(snaps, max_depth=5, query_mode="device")
        b = CheckBatcher(
            engine, window_s=0, cache=CheckResultCache(512),
            version_fn=lambda: store.version,
        )
        try:
            cols = CheckColumns.from_tuples(
                [_t("n:doc0#view@alice"), _t("n:doc0#view@bob")]
            )
            assert [bool(v) for v in b.check_batch_columnar(cols)] == [
                True, False,
            ]
            store.write_relation_tuples(_t("n:team0#member@bob"))
            got = b.check_batch_columnar(
                cols, min_version=store.version
            )
            assert [bool(v) for v in got] == [True, True]
            store.delete_relation_tuples(_t("n:team0#member@alice"))
            got = b.check_batch_columnar(
                cols, min_version=store.version
            )
            assert [bool(v) for v in got] == [False, True]
        finally:
            b.close()


def _chain_store():
    """g0 -> g1 -> ... -> g6 -> alice as nested member sets, so how deep
    a check may walk decides its answer."""
    store = InMemoryTupleStore()
    store.write_relation_tuples(
        *[_t(f"n:g{i}#member@(n:g{i + 1}#member)") for i in range(6)],
        _t("n:g6#member@alice"),
        _t("n:doc#view@(n:g0#member)"),
        _t("n:g3#member@bob"),
    )
    return store


def _chain_call(k: int) -> CheckColumns:
    rows = [f"n:g{i}#member@alice" for i in range(7)] + [
        "n:doc#view@alice", "n:doc#view@bob", "n:g0#member@carol",
        "ghost:x#view@alice",
    ]
    # each call a different rotation and length of the rows
    rows = (rows[k % len(rows):] + rows[: k % len(rows)])[: 4 + k % 8]
    return CheckColumns.from_tuples([_t(r) for r in rows])


class _GatedEngine:
    """Delegates to ``inner``. Pass k (in order of entry) releases
    ``entered``, then blocks on ``gates[k]`` while k < len(gates); the
    pass numbered ``fail_pass`` raises instead of answering."""

    def __init__(self, inner, n_gates: int, fail_pass=None):
        self.inner = inner
        self.gates = [threading.Event() for _ in range(n_gates)]
        self.entered = threading.Semaphore(0)
        self.rows: list[int] = []  # rows of each pass, in order of entry
        self.fail_pass = fail_pass
        self._lock = threading.Lock()

    def batch_check_columns(self, cols, max_depth=0, depths=None):
        with self._lock:
            k = len(self.rows)
            self.rows.append(len(cols))
        self.entered.release()
        if k < len(self.gates):
            assert self.gates[k].wait(30)
        if k == self.fail_pass:
            raise RuntimeError("injected engine pass failure")
        return self.inner.batch_check_columns(cols, max_depth, depths)


def _pass_calls(metrics) -> tuple[float, float]:
    """(passes, calls served) of ``keto_batcher_columns_pass_calls``."""
    got = {}
    for line in metrics.expose().splitlines():
        for k in ("count", "sum"):
            if line.startswith(f"keto_batcher_columns_pass_calls_{k} "):
                got[k] = float(line.split()[-1])
    return got["count"], got["sum"]


def _run_queued(batcher, stub, calls):
    """Hold every pass slot in ``stub``, queue ``calls`` ((cols, depth)
    pairs) behind them, then let the passes go one at a time. Returns each
    call's answers, or the exception it raised."""
    out: list = [None] * len(calls)

    def send(i, cols, depth):
        try:
            out[i] = batcher.check_batch_columnar(cols, depth)
        except Exception as e:  # noqa: BLE001 - the test inspects it
            out[i] = e

    one = CheckColumns.from_tuples([_t("n:doc#view@alice")])
    holders = [
        threading.Thread(target=batcher.check_batch_columnar, args=(one, 0))
        for _ in stub.gates
    ]
    for t in holders:
        t.start()
        assert stub.entered.acquire(timeout=30)
    threads = [
        threading.Thread(target=send, args=(i, cols, depth))
        for i, (cols, depth) in enumerate(calls)
    ]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 30
    while len(batcher._pending) < len(calls):
        assert time.monotonic() < deadline, "calls never queued"
        time.sleep(0.001)
    # the first held pass ends: its slot goes to the oldest queued call,
    # which serves every queued call in one pass before the second ends
    stub.gates[0].set()
    assert stub.entered.acquire(timeout=30)
    for g in stub.gates[1:]:
        g.set()
    for t in holders + threads:
        t.join(30)
    return out


class TestColumnsGroupCommit:
    """Concurrent columnar calls share engine passes: a call queued behind
    the running passes is answered by the next one, with its own depth,
    and gets exactly the answers it gets alone."""

    _DEPTHS = {"mixed": [1, 2, 3, 0, 5, 6, 8, 4, 2, 7], "same": [0] * 10}

    @pytest.mark.parametrize("depths", sorted(_DEPTHS))
    def test_queued_calls_share_one_pass(self, depths):
        from keto_tpu.engine.batcher import _COLUMNS_PASSES
        from keto_tpu.telemetry.metrics import MetricsRegistry

        store = _chain_store()
        oracle = CheckEngine(store, max_depth=8)
        engine = ClosureCheckEngine(
            SnapshotManager(store), max_depth=8, query_mode="device"
        )
        calls = [(_chain_call(k), d)
                 for k, d in enumerate(self._DEPTHS[depths])]
        alone = CheckBatcher(engine, window_s=0)
        try:
            want = [alone.check_batch_columnar(c, d) for c, d in calls]
        finally:
            alone.close()
        for (cols, d), w in zip(calls, want):
            assert w == [bool(oracle.subject_is_allowed(r, d))
                         for r in cols.materialize()]
        # the answers turn on the depth: g0 reaches alice through 6 sets
        alice = _t("n:g0#member@alice")
        assert not oracle.subject_is_allowed(alice, 3)
        assert oracle.subject_is_allowed(alice, 0)
        metrics = MetricsRegistry()
        stub = _GatedEngine(engine, _COLUMNS_PASSES)
        b = CheckBatcher(stub, window_s=0, metrics=metrics)
        try:
            got = _run_queued(b, stub, calls)
        finally:
            b.close()
        assert got == want
        held = [1] * _COLUMNS_PASSES
        assert stub.rows == held + [sum(len(c) for c, _ in calls)]
        assert _pass_calls(metrics) == (
            _COLUMNS_PASSES + 1, _COLUMNS_PASSES + len(calls))

    def test_lone_call_is_a_pass_of_one(self):
        from keto_tpu.telemetry.metrics import MetricsRegistry

        store = _chain_store()
        metrics = MetricsRegistry()
        b = CheckBatcher(
            ClosureCheckEngine(SnapshotManager(store), max_depth=8),
            window_s=0, metrics=metrics,
        )
        try:
            for k in range(5):
                b.check_batch_columnar(_chain_call(k), k)
        finally:
            b.close()
        assert _pass_calls(metrics) == (5, 5)
        assert b._passes == 0 and not b._pending

    def test_concurrent_calls_match_oracle(self):
        """More client threads than cores, a short switch interval: every
        call is served by exactly one pass, with its own answers."""
        import sys

        from keto_tpu.telemetry.metrics import MetricsRegistry

        store = _chain_store()
        oracle = CheckEngine(store, max_depth=8)
        metrics = MetricsRegistry()
        b = CheckBatcher(
            ClosureCheckEngine(
                SnapshotManager(store), max_depth=8, query_mode="device"
            ),
            window_s=0, metrics=metrics,
        )
        n_threads, n_calls = 16, 12
        wrong: list = []

        def client(seed):
            rng = np.random.default_rng(seed)
            for _ in range(n_calls):
                k, d = int(rng.integers(40)), int(rng.integers(9))
                cols = _chain_call(k)
                want = [bool(oracle.subject_is_allowed(r, d))
                        for r in cols.materialize()]
                got = b.check_batch_columnar(cols, d)
                if got != want:
                    wrong.append((k, d, got, want))

        threads = [threading.Thread(target=client, args=(s,))
                   for s in range(n_threads)]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
        finally:
            sys.setswitchinterval(interval)
            b.close()
        assert not any(t.is_alive() for t in threads)
        assert not wrong
        passes, served = _pass_calls(metrics)
        assert served == n_threads * n_calls and passes <= served
        assert b._passes == 0 and not b._pending

    @pytest.mark.parametrize("breaker", [True, False])
    def test_failed_pass_reaches_every_member(self, breaker):
        """A merged pass that raises is re-answered by the breaker's host
        oracle for every member, as one failure; without a breaker every
        member gets the exception."""
        from keto_tpu.engine.batcher import _COLUMNS_PASSES
        from keto_tpu.telemetry.metrics import MetricsRegistry

        store = _chain_store()
        oracle = CheckEngine(store, max_depth=8)
        stub = _GatedEngine(
            ClosureCheckEngine(SnapshotManager(store), max_depth=8),
            _COLUMNS_PASSES, fail_pass=_COLUMNS_PASSES,
        )
        metrics = MetricsRegistry()
        engine = stub
        if breaker:
            engine = DeviceFallbackEngine(
                stub, lambda: CheckEngine(store, max_depth=8),
                failure_threshold=3, metrics=metrics,
            )
        calls = [(_chain_call(k), d) for k, d in enumerate([0, 2, 5, 3])]
        b = CheckBatcher(engine, window_s=0, metrics=metrics)
        try:
            got = _run_queued(b, stub, calls)
        finally:
            b.close()
        assert len(stub.rows) == _COLUMNS_PASSES + 1
        if breaker:
            assert got == [
                [bool(oracle.subject_is_allowed(r, d))
                 for r in cols.materialize()]
                for cols, d in calls
            ]
            assert engine.n_fallback_batches == 1
            assert "keto_device_engine_failures_total 1.0" in \
                metrics.expose()
        else:
            assert all(isinstance(e, RuntimeError) for e in got)
        assert b._passes == 0 and not b._pending


# ---------------------------------------------------------------------------
# live-server transports
# ---------------------------------------------------------------------------


class _ServerFixture:
    def __init__(self, config: Config):
        self.registry = Registry(config)
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(
            target=self.loop.run_forever, daemon=True
        )
        self.thread.start()
        fut = asyncio.run_coroutine_threadsafe(
            self.registry.start_all(), self.loop
        )
        self.read_port, self.write_port = fut.result(timeout=180)

    def stop(self):
        asyncio.run_coroutine_threadsafe(
            self.registry.stop_all(), self.loop
        ).result(timeout=10)
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(timeout=5)


@pytest.fixture(scope="module")
def server():
    cfg = Config(
        values={
            "namespaces": [
                {"id": 1, "name": "n"},
                {"id": 2, "name": "ns-日本語"},
            ],
            "log": {"level": "error"},
            "serve": {
                "read": {"port": 0, "host": "127.0.0.1"},
                "write": {"port": 0, "host": "127.0.0.1"},
            },
        }
    )
    s = _ServerFixture(cfg)
    store = s.registry.store()
    store.write_relation_tuples(
        _t("n:doc0#view@(n:team0#member)"),
        _t("n:team0#member@alice"),
        _t("n:doc1#view@bob"),
        _t("ns-日本語:ページ#view@ユーザー"),
    )
    yield s
    s.stop()


def _columnar_body(reqs):
    cols = CheckColumns.from_tuples(reqs)
    return {
        "namespaces": cols.namespaces,
        "objects": cols.objects,
        "relations": cols.relations,
        "subject_ids": cols.subject_ids,
        "subject_set_namespaces": cols.subject_set_namespaces,
        "subject_set_objects": cols.subject_set_objects,
        "subject_set_relations": cols.subject_set_relations,
    }


_SERVER_REQS = [
    "n:doc0#view@alice",
    "n:doc0#view@bob",
    "n:doc1#view@bob",
    "n:doc0#view@(n:team0#member)",
    "ns-日本語:ページ#view@ユーザー",
    "ns-日本語:ページ#view@alice",
]


class TestRestColumnar:
    def test_columnar_body_matches_per_tuple(self, server):
        reqs = [_t(s) for s in _SERVER_REQS]
        with httpx.Client(
            base_url=f"http://127.0.0.1:{server.read_port}", timeout=60
        ) as c:
            per_tuple = c.post(
                "/check/batch",
                json={"tuples": [t.to_dict() for t in reqs]},
            )
            assert per_tuple.status_code == 200
            want = per_tuple.json()["allowed"]
            assert want == [True, False, True, True, True, False]
            columnar = c.post("/check/batch", json=_columnar_body(reqs))
            assert columnar.status_code == 200
            body = columnar.json()
            assert body["allowed"] == want
            assert body["snaptoken"]

    def test_columnar_body_without_set_columns(self, server):
        with httpx.Client(
            base_url=f"http://127.0.0.1:{server.read_port}", timeout=60
        ) as c:
            r = c.post(
                "/check/batch",
                json={
                    "namespaces": ["n", "n"],
                    "objects": ["doc0", "doc1"],
                    "relations": ["view", "view"],
                    "subject_ids": ["alice", "bob"],
                },
            )
            assert r.status_code == 200
            assert r.json()["allowed"] == [True, True]

    def test_malformed_columnar_bodies_400(self, server):
        cases = [
            # row without any subject
            {
                "namespaces": ["n"], "objects": ["doc0"],
                "relations": ["view"], "subject_ids": [""],
            },
            # both subject forms on one row
            {
                "namespaces": ["n"], "objects": ["doc0"],
                "relations": ["view"], "subject_ids": ["alice"],
                "subject_set_namespaces": ["n"],
                "subject_set_objects": ["team0"],
                "subject_set_relations": ["member"],
            },
            # column length mismatch
            {
                "namespaces": ["n", "n"], "objects": ["doc0"],
                "relations": ["view", "view"],
                "subject_ids": ["alice", "bob"],
            },
            # wrong element type
            {
                "namespaces": ["n"], "objects": [7],
                "relations": ["view"], "subject_ids": ["alice"],
            },
        ]
        with httpx.Client(
            base_url=f"http://127.0.0.1:{server.read_port}", timeout=60
        ) as c:
            for body in cases:
                r = c.post("/check/batch", json=body)
                assert r.status_code == 400, body
                assert "error" in r.json()


class TestGrpcColumnar:
    def _stub(self, server):
        ch = grpc.insecure_channel(f"127.0.0.1:{server.read_port}")
        return ch, CheckServiceStub(ch)

    def test_columnar_request_matches_per_tuple(self, server):
        reqs = [_t(s) for s in _SERVER_REQS]
        per_tuple = check_service_pb2.BatchCheckRequest(
            tuples=[
                check_service_pb2.CheckRequestTuple(
                    namespace=t.namespace,
                    object=t.object,
                    relation=t.relation,
                    subject=acl_pb2.Subject(id=t.subject.id)
                    if isinstance(t.subject, SubjectID)
                    else acl_pb2.Subject(
                        set=acl_pb2.SubjectSet(
                            namespace=t.subject.namespace,
                            object=t.subject.object,
                            relation=t.subject.relation,
                        )
                    ),
                )
                for t in reqs
            ]
        )
        cols = CheckColumns.from_tuples(reqs)
        columnar = check_service_pb2.BatchCheckRequest(
            namespaces=cols.namespaces,
            objects=cols.objects,
            relations=cols.relations,
            subject_ids=cols.subject_ids,
            subject_set_namespaces=cols.subject_set_namespaces,
            subject_set_objects=cols.subject_set_objects,
            subject_set_relations=cols.subject_set_relations,
        )
        ch, stub = self._stub(server)
        try:
            want = list(stub.BatchCheck(per_tuple).allowed)
            assert want == [True, False, True, True, True, False]
            resp = stub.BatchCheck(columnar)
            assert list(resp.allowed) == want
            assert resp.snaptoken
        finally:
            ch.close()

    def test_malformed_columnar_request_invalid_argument(self, server):
        ch, stub = self._stub(server)
        try:
            req = check_service_pb2.BatchCheckRequest(
                namespaces=["n"],
                objects=["doc0"],
                relations=["view"],
                subject_ids=[""],
            )
            with pytest.raises(grpc.RpcError) as exc:
                stub.BatchCheck(req)
            assert exc.value.code() == grpc.StatusCode.INVALID_ARGUMENT
            assert "without subject" in exc.value.details()
        finally:
            ch.close()
