"""Check-result cache: hot single checks skip the engine; any advance of
the ANSWERING version empties the cache (the reference lists caching as
planned/unimplemented — docs/docs/implemented-planned-features.mdx:30-34)."""

from keto_tpu.driver.factory import new_test_registry
from keto_tpu.engine.cache import CheckResultCache
from keto_tpu.relationtuple import RelationTuple


def t(s: str) -> RelationTuple:
    return RelationTuple.from_string(s)


class _CountingEngine:
    """Spy wrapping an engine, counting batch_check invocations."""

    def __init__(self, inner):
        self.inner = inner
        self.calls = 0

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def batch_check(self, *a, **kw):
        self.calls += 1
        return self.inner.batch_check(*a, **kw)


class TestCacheUnit:
    def test_lru_eviction_and_version_clear(self):
        c = CheckResultCache(capacity=2)
        c.put(1, "a", True)  # version mismatch before any get: dropped
        assert c.get(1, "a") is None  # also pins version 1
        c.put(1, "a", True)
        c.put(1, "b", False)
        assert c.get(1, "a") is True
        c.put(1, "c", True)  # evicts LRU ("b": "a" was touched)
        assert c.get(1, "b") is None
        assert c.get(1, "a") is True
        # version advance clears everything
        assert c.get(2, "a") is None
        assert len(c) == 0


class TestCacheServing:
    def test_hot_check_skips_engine_until_write(self):
        reg = new_test_registry(namespaces=("videos",))
        store = reg.store()
        store.write_relation_tuples(t("videos:o#r@alice"))
        checker = reg.checker()
        spy = _CountingEngine(reg.check_engine())
        checker.engine = spy

        assert checker.check(t("videos:o#r@alice"), 0) is True
        calls_after_first = spy.calls
        for _ in range(5):
            assert checker.check(t("videos:o#r@alice"), 0) is True
        assert spy.calls == calls_after_first  # all cache hits

        # different depth is a different key
        checker.check(t("videos:o#r@alice"), 3)
        assert spy.calls == calls_after_first + 1

        # a write advances the version: cache must empty, fresh answer
        store.write_relation_tuples(t("videos:o#r@bob"))
        assert checker.check(t("videos:o#r@bob"), 0) is True
        assert spy.calls > calls_after_first + 1
        reg._batcher.close()

    def test_delete_invalidates_cached_allow(self):
        reg = new_test_registry(namespaces=("videos",))
        store = reg.store()
        store.write_relation_tuples(t("videos:o#r@alice"))
        checker = reg.checker()
        assert checker.check(t("videos:o#r@alice"), 0) is True
        store.delete_relation_tuples(t("videos:o#r@alice"))
        assert checker.check(t("videos:o#r@alice"), 0) is False
        reg._batcher.close()

    def test_cache_metrics_exposed(self):
        reg = new_test_registry(namespaces=("videos",))
        reg.store().write_relation_tuples(t("videos:o#r@alice"))
        checker = reg.checker()
        checker.check(t("videos:o#r@alice"), 0)
        checker.check(t("videos:o#r@alice"), 0)
        text = reg.metrics().expose()
        assert "keto_check_cache_hits_total 1" in text
        reg._batcher.close()

    def test_bounded_freshness_cache_hits_do_not_starve_rebuild(self):
        """Under bounded freshness a cached allow must still converge
        after a revoking write even if every request hits the cache —
        answering_version kicks the background rebuild on staleness."""
        import time

        reg = new_test_registry(
            namespaces=("videos",),
            values={
                "engine": {"freshness": "bounded", "rebuild_debounce_ms": 0}
            },
        )
        store = reg.store()
        store.write_relation_tuples(t("videos:o#r@alice"))
        checker = reg.checker()
        assert checker.check(t("videos:o#r@alice"), 0) is True
        store.delete_relation_tuples(t("videos:o#r@alice"))
        deadline = time.monotonic() + 15
        got = True
        while time.monotonic() < deadline:
            got = checker.check(t("videos:o#r@alice"), 0)
            if got is False:
                break
            time.sleep(0.02)
        assert got is False
        reg._batcher.close()

    def test_cache_disabled_by_config(self):
        reg = new_test_registry(
            namespaces=("videos",), values={"engine": {"cache_size": 0}}
        )
        reg.store().write_relation_tuples(t("videos:o#r@alice"))
        checker = reg.checker()
        assert checker.cache is None
        assert checker.check(t("videos:o#r@alice"), 0) is True
        reg._batcher.close()


def test_toobig_fallback_answers_stamp_live_version():
    """A max depth past the closure's uint8 distances routes checks to
    the live-store fallback; cached answers must invalidate on EVERY
    write (stamp = store version), even under bounded freshness."""
    reg = new_test_registry(
        namespaces=("videos",),
        values={
            "engine": {
                "freshness": "bounded",
                "rebuild_debounce_ms": 0,
            },
            "serve": {"read": {"max-depth": 256}},
        },
    )
    store = reg.store()
    # the closure falls back for the whole snapshot
    store.write_relation_tuples(
        t("videos:a#r@(videos:b#r)"),
        t("videos:b#r@(videos:c#r)"),
        t("videos:c#r@(videos:d#r)"),
        t("videos:d#r@alice"),
    )
    checker = reg.checker()
    assert checker.check(t("videos:d#r@alice"), 0) is True
    store.delete_relation_tuples(t("videos:d#r@alice"))
    # fallback reads the live store: the revocation must be visible on
    # the very next check, not after a rebuild window
    assert checker.check(t("videos:d#r@alice"), 0) is False
    reg._batcher.close()


def test_closed_batcher_refuses_even_cached_keys():
    import pytest

    from keto_tpu.engine.batcher import BatcherClosed

    reg = new_test_registry(namespaces=("videos",))
    reg.store().write_relation_tuples(t("videos:o#r@alice"))
    checker = reg.checker()
    assert checker.check(t("videos:o#r@alice"), 0) is True
    reg._batcher.close()
    with pytest.raises(BatcherClosed):
        checker.check(t("videos:o#r@alice"), 0)


def test_snaptoken_consistency_waits_for_rebuild():
    """gRPC CheckRequest.snaptoken (at-least-as-fresh) is real: under
    bounded freshness, a check carrying the write's snaptoken must
    reflect that write, while a plain check may serve the older
    snapshot (reference documents the field as not implemented,
    check_service.proto:43-80)."""
    reg = new_test_registry(
        namespaces=("videos",),
        values={
            "engine": {"freshness": "bounded", "rebuild_debounce_ms": 0}
        },
    )
    store = reg.store()
    store.write_relation_tuples(t("videos:o#r@alice"))
    checker = reg.checker()
    assert checker.check(t("videos:o#r@alice"), 0) is True

    store.write_relation_tuples(t("videos:o#r@bob"))
    token = store.version
    # consistency-pinned check: must see bob immediately
    assert checker.check(t("videos:o#r@bob"), 0, min_version=token) is True
    reg._batcher.close()


def test_grpc_snaptoken_and_latest_fields():
    import grpc

    from keto_tpu.api import acl_pb2, check_service_pb2
    from keto_tpu.api.services import CheckServiceStub
    from tests.test_api_server import ServerFixture

    reg = new_test_registry(
        namespaces=("videos",),
        values={
            "engine": {"freshness": "bounded", "rebuild_debounce_ms": 0}
        },
    )
    s = ServerFixture(reg)
    try:
        store = reg.store()
        store.write_relation_tuples(t("videos:o#r@alice"))
        with grpc.insecure_channel(f"127.0.0.1:{s.read_port}") as ch:
            stub = CheckServiceStub(ch)

            def check(sub, **kw):
                return stub.Check(
                    check_service_pb2.CheckRequest(
                        namespace="videos", object="o", relation="r",
                        subject=acl_pb2.Subject(id=sub), **kw,
                    )
                )

            # server may have warmed on the empty store: pin the first
            # check to the write's version (the contract under test)
            assert check("alice", snaptoken=str(store.version)).allowed
            store.write_relation_tuples(t("videos:o#r@bob"))
            token = str(store.version)
            resp = check("bob", snaptoken=token)
            assert resp.allowed and int(resp.snaptoken) >= int(token)
            store.write_relation_tuples(t("videos:o#r@carol"))
            assert check("carol", latest=True).allowed
            # malformed snaptoken -> INVALID_ARGUMENT
            import pytest

            with pytest.raises(grpc.RpcError) as e:
                check("alice", snaptoken="not-a-number")
            assert e.value.code() == grpc.StatusCode.INVALID_ARGUMENT
    finally:
        s.stop()


def test_rest_snaptoken_and_latest_params():
    """REST /check honors snaptoken/latest the same way gRPC does (a
    keto_tpu extension — the reference REST surface has neither)."""
    import httpx

    from tests.test_api_server import ServerFixture

    reg = new_test_registry(
        namespaces=("videos",),
        values={"engine": {"freshness": "bounded", "rebuild_debounce_ms": 0}},
    )
    s = ServerFixture(reg)
    try:
        store = reg.store()
        store.write_relation_tuples(t("videos:o#r@alice"))
        base = f"http://127.0.0.1:{s.read_port}/check"

        def check(sub, **extra):
            return httpx.get(
                base,
                params={
                    "namespace": "videos", "object": "o", "relation": "r",
                    "subject_id": sub, **extra,
                },
            )

        # server warmed up on the empty store: a PLAIN check may serve
        # that older snapshot under bounded freshness — pinning with the
        # write's snaptoken is exactly what forces the catch-up
        assert check("alice", snaptoken=str(store.version)).status_code == 200
        store.write_relation_tuples(t("videos:o#r@bob"))
        token = str(store.version)
        assert check("bob", snaptoken=token).status_code == 200
        store.write_relation_tuples(t("videos:o#r@carol"))
        assert check("carol", latest="true").status_code == 200
        assert check("alice", snaptoken="junk!").status_code == 400
    finally:
        s.stop()


def test_batch_consistency_both_transports():
    """Batch checks honor snaptoken/latest on both transports via the
    shipped clients (proto BatchCheckRequest fields + REST query params)."""
    from keto_tpu.client import GrpcClient, RestClient
    from tests.test_api_server import ServerFixture

    reg = new_test_registry(
        namespaces=("videos",),
        values={"engine": {"freshness": "bounded", "rebuild_debounce_ms": 0}},
    )
    s = ServerFixture(reg)
    try:
        store = reg.store()
        store.write_relation_tuples(t("videos:o#r@alice"))
        token = str(store.version)
        with RestClient(f"http://127.0.0.1:{s.read_port}") as rc:
            assert rc.batch_check(
                ["videos:o#r@alice", "videos:o#r@nobody"], snaptoken=token
            ) == [True, False]
        store.write_relation_tuples(t("videos:o#r@bob"))
        with GrpcClient(f"127.0.0.1:{s.read_port}") as gc:
            assert gc.batch_check(
                ["videos:o#r@bob", "videos:o#r@nobody"], latest=True
            ) == [True, False]
    finally:
        s.stop()
