"""Client SDK e2e: the RestClient/GrpcClient package must drive a live
server the way the reference's generated swagger SDK + gRPC clients drive
theirs (reference internal/e2e/sdk_client_test.go / grpc_client_test.go),
and the registry factories must stand up working registries (reference
registry_factory.go:56-95)."""

import pytest

from keto_tpu.client import GrpcClient, RestClient
from keto_tpu.driver.factory import (
    new_sqlite_test_registry,
    new_test_registry,
)
from keto_tpu.relationtuple import (
    RelationQuery,
    RelationTuple,
    SubjectSet,
)
from keto_tpu.utils.errors import ErrMalformedInput, ErrNotFound
from tests.test_api_server import ServerFixture


@pytest.fixture(scope="module")
def server():
    s = ServerFixture(new_test_registry(namespaces=("videos",)))
    yield s
    s.stop()


@pytest.fixture
def rest(server):
    with RestClient(
        f"http://127.0.0.1:{server.read_port}",
        f"http://127.0.0.1:{server.write_port}",
    ) as c:
        yield c
        # leave a clean store for the next test
        c.delete_relation_tuples(RelationQuery(namespace="videos"))


class TestRestClient:
    def test_crud_check_expand_flow(self, rest):
        rest.create_relation_tuple("videos:/cats#owner@cat lady")
        rest.create_relation_tuple(
            "videos:/cats/1.mp4#view@(videos:/cats#owner)"
        )
        assert rest.check("videos:/cats/1.mp4#view@cat lady").allowed
        assert not rest.check("videos:/cats/1.mp4#view@dog guy").allowed
        assert rest.batch_check(
            [
                "videos:/cats/1.mp4#view@cat lady",
                "videos:/cats/1.mp4#view@dog guy",
            ]
        ) == [True, False]

        tree = rest.expand(
            SubjectSet(namespace="videos", object="/cats/1.mp4", relation="view")
        )
        assert tree is not None and "cat lady" in str(tree)

        page = rest.get_relation_tuples(RelationQuery(namespace="videos"))
        assert len(page.relation_tuples) == 2
        assert page.next_page_token == ""

    def test_pagination_iterator(self, rest):
        for i in range(7):
            rest.create_relation_tuple(f"videos:v{i}#view@u{i}")
        seen = list(
            rest.iter_relation_tuples(
                RelationQuery(namespace="videos"), page_size=3
            )
        )
        assert len(seen) == 7

    def test_patch_transaction(self, rest):
        t1 = RelationTuple.from_string("videos:a#r@u1")
        t2 = RelationTuple.from_string("videos:b#r@u2")
        rest.patch_relation_tuples(insert=[t1, t2])
        rest.patch_relation_tuples(insert=[], delete=[t1])
        page = rest.get_relation_tuples(RelationQuery(namespace="videos"))
        assert [t.object for t in page.relation_tuples] == ["b"]

    def test_error_classes(self, rest):
        with pytest.raises(ErrNotFound):
            rest.create_relation_tuple("nope:x#r@u")  # unknown namespace
        with pytest.raises(ErrMalformedInput):
            rest.get_relation_tuples(
                RelationQuery(namespace="videos"), page_token="garbage!!"
            )

    def test_health_version_metrics(self, rest):
        assert rest.alive() and rest.ready()
        assert rest.version()
        assert "keto_checks_total" in rest.metrics()


class TestGrpcClient:
    def test_check_and_expand(self, server, rest):
        rest.create_relation_tuple("videos:/d#view@eve")
        with GrpcClient(
            f"127.0.0.1:{server.read_port}",
            f"127.0.0.1:{server.write_port}",
        ) as g:
            res = g.check("videos:/d#view@eve")
            assert res.allowed and res.snaptoken
            assert not g.check("videos:/d#view@mallory").allowed
            tree = g.expand(
                SubjectSet(namespace="videos", object="/d", relation="view")
            )
            assert tree is not None

    def test_grpc_batch_check(self, server, rest):
        rest.create_relation_tuple("videos:/b#view@eve")
        with GrpcClient(f"127.0.0.1:{server.read_port}") as g:
            assert g.batch_check(
                [
                    "videos:/b#view@eve",
                    "videos:/b#view@nobody",
                    "videos:/b#view@eve",
                ]
            ) == [True, False, True]


class TestRegistryFactories:
    def test_sqlite_test_registry_automigrates(self, tmp_path):
        reg = new_sqlite_test_registry(str(tmp_path / "t.db"))
        store = reg.store()
        store.write_relation_tuples(
            RelationTuple.from_string("videos:o#r@alice")
        )
        assert len(store.get_relation_tuples(RelationQuery())[0]) == 1

    def test_test_registry_engine_default(self):
        reg = new_test_registry()
        from keto_tpu.engine.closure import ClosureCheckEngine

        assert isinstance(reg.check_engine(), ClosureCheckEngine)
