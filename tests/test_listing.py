"""List-serving parity: the reverse-index answers (engine/listing.py) must
be byte-identical to a brute-force forward-scan oracle — one fallback check
per candidate — on every graph (cycles, unicode vocab, subject-set
indirections), through both the host and device closure engines, across
page boundaries with writes landing between pages, and through the REST
and gRPC surfaces. Plus the breaker drill: injected gather failures must
fall back to the oracle with identical results and open the breaker."""

import time

import grpc
import numpy as np
import pytest

from keto_tpu.client import GrpcClient, RestClient
from keto_tpu.engine import CheckEngine
from keto_tpu.engine.closure import ClosureCheckEngine
from keto_tpu.engine.listing import ListEngine
from keto_tpu.engine.paging import encode_page_token
from keto_tpu.faults import FAULTS
from keto_tpu.graph import SnapshotManager
from keto_tpu.relationtuple import RelationTuple, SubjectID, SubjectSet
from keto_tpu.store import InMemoryTupleStore
from keto_tpu.utils.errors import ErrMalformedPageToken, ErrStalePageToken
from tests.test_api_server import ServerFixture
from conftest import random_store


def t(s: str) -> RelationTuple:
    return RelationTuple.from_string(s)


DEPTH = 5


@pytest.fixture(params=["host", "device"])
def query_mode(request):
    return request.param


def make_list_engine(store, query_mode, **kw):
    eng = ClosureCheckEngine(
        SnapshotManager(store),
        max_depth=DEPTH,
        freshness="strong",
        rebuild_debounce_s=0.0,
        query_mode=query_mode,
    )
    return eng, ListEngine(eng, **kw)


def store_vocab(store):
    """Every (namespace, object) and subject id the store mentions —
    the candidate universe the brute-force oracle scans."""
    from keto_tpu.relationtuple import RelationQuery
    from keto_tpu.utils.pagination import PaginationOptions

    objects, rels, sids = set(), set(), set()
    token = ""
    while True:
        batch, token = store.get_relation_tuples(
            RelationQuery(), PaginationOptions(token=token)
        )
        for tp in batch:
            objects.add((tp.namespace, tp.object))
            rels.add(tp.relation)
            if isinstance(tp.subject, SubjectSet):
                objects.add((tp.subject.namespace, tp.subject.object))
                rels.add(tp.subject.relation)
            else:
                sids.add(tp.subject.id)
        if not token:
            break
    return sorted(objects), sorted(rels), sorted(sids)


def oracle_objects(store, subject, relation, namespace):
    """Forward-scan oracle: one independent host-BFS check per candidate
    object (deliberately NOT listing.py's internal oracle)."""
    chk = CheckEngine(store, max_depth=DEPTH)
    objects, _, _ = store_vocab(store)
    return sorted(
        o
        for ns, o in objects
        if ns == namespace
        and chk.subject_is_allowed(
            RelationTuple(namespace, o, relation, subject), DEPTH
        )
    )


def oracle_subjects(store, namespace, object, relation):
    chk = CheckEngine(store, max_depth=DEPTH)
    _, _, sids = store_vocab(store)
    return sorted(
        s
        for s in sids
        if chk.subject_is_allowed(
            RelationTuple(namespace, object, relation, SubjectID(s)), DEPTH
        )
    )


def all_items(le, kind, *args, page_size=0):
    """Drain every page; returns (items, sources)."""
    items, sources, token = [], [], ""
    fn = le.list_objects if kind == "objects" else le.list_subjects
    while True:
        page = fn(*args, max_depth=DEPTH, page_size=page_size,
                  page_token=token)
        items.extend(page.items)
        sources.append(page.source)
        token = page.next_page_token
        if not token:
            break
    return items, sources


class TestReverseParityRandom:
    """Random graphs (cycles + ~45% subject-set indirections) — the same
    generator the device-engine parity suite trusts."""

    @pytest.mark.parametrize("seed", range(4))
    def test_list_objects_matches_oracle(self, query_mode, seed):
        rng = np.random.default_rng(seed)
        store = random_store(rng, n_objects=15, n_users=10, n_edges=120)
        _, le = make_list_engine(store, query_mode)
        for rel in ("r0", "r1", "r2"):
            for sub in (
                SubjectID("u3"),
                SubjectID("u7"),
                SubjectSet("n", "o2", "r1"),
                SubjectID("nobody"),
            ):
                got = le.list_objects(sub, rel, "n", max_depth=DEPTH)
                want = oracle_objects(store, sub, rel, "n")
                assert got.items == want, (
                    f"seed={seed} mode={query_mode} rel={rel} sub={sub}"
                )
        assert le.n_oracle == 0, "reverse path declined on a resident closure"

    @pytest.mark.parametrize("seed", range(4))
    def test_list_subjects_matches_oracle(self, query_mode, seed):
        rng = np.random.default_rng(seed + 50)
        store = random_store(rng, n_objects=12, n_users=8, n_edges=100)
        _, le = make_list_engine(store, query_mode)
        for o in range(0, 12, 3):
            for rel in ("r0", "r2"):
                got = le.list_subjects("n", f"o{o}", rel, max_depth=DEPTH)
                want = oracle_subjects(store, "n", f"o{o}", rel)
                assert got.items == want, (
                    f"seed={seed} mode={query_mode} o=o{o} rel={rel}"
                )
        assert le.n_oracle == 0


class TestReverseParityShapes:
    def test_cycle(self, query_mode):
        store = InMemoryTupleStore()
        store.write_relation_tuples(
            t("n:a#r@(n:b#r)"),
            t("n:b#r@(n:a#r)"),
            t("n:b#r@alice"),
            t("n:c#r@(n:a#r)"),
        )
        _, le = make_list_engine(store, query_mode)
        got = le.list_objects(SubjectID("alice"), "r", "n", max_depth=DEPTH)
        assert got.items == ["a", "b", "c"]
        assert got.items == oracle_objects(store, SubjectID("alice"), "r", "n")
        assert le.list_subjects("n", "c", "r", max_depth=DEPTH).items == [
            "alice"
        ]

    def test_unicode_vocab(self, query_mode):
        store = InMemoryTupleStore()
        store.write_relation_tuples(
            RelationTuple("n", "café", "läsa", SubjectID("żółć")),
            RelationTuple(
                "n", "naïve/文档", "läsa", SubjectSet("n", "café", "läsa")
            ),
            RelationTuple("n", "café", "läsa", SubjectID("ピカチュウ")),
        )
        _, le = make_list_engine(store, query_mode)
        for sid in ("żółć", "ピカチュウ"):
            got = le.list_objects(SubjectID(sid), "läsa", "n", max_depth=DEPTH)
            assert got.items == ["café", "naïve/文档"]
            assert got.items == oracle_objects(
                store, SubjectID(sid), "läsa", "n"
            )
        assert le.list_subjects("n", "naïve/文档", "läsa",
                                max_depth=DEPTH).items == [
            "żółć", "ピカチュウ"
        ]

    def test_namespaces_do_not_leak(self, query_mode):
        store = InMemoryTupleStore()
        store.write_relation_tuples(
            t("n:doc#view@alice"), t("m:doc2#view@alice")
        )
        _, le = make_list_engine(store, query_mode)
        assert le.list_objects(
            SubjectID("alice"), "view", "n", max_depth=DEPTH
        ).items == ["doc"]
        assert le.list_objects(
            SubjectID("alice"), "view", "m", max_depth=DEPTH
        ).items == ["doc2"]


class TestPaging:
    def seeded(self, query_mode):
        store = InMemoryTupleStore()
        store.write_relation_tuples(
            *(t(f"n:doc{i:02d}#view@alice") for i in range(9)),
            t("n:hub#view@(n:doc03#view)"),
        )
        return store, make_list_engine(store, query_mode)

    def test_paged_equals_unpaged(self, query_mode):
        store, (_, le) = self.seeded(query_mode)
        full = le.list_objects(
            SubjectID("alice"), "view", "n", max_depth=DEPTH
        ).items
        assert full == oracle_objects(store, SubjectID("alice"), "view", "n")
        for size in (1, 3, 4):
            items, _ = all_items(
                le, "objects", SubjectID("alice"), "view", "n",
                page_size=size,
            )
            assert items == full, f"page_size={size}"

    def test_write_between_pages_is_stale(self, query_mode):
        store, (_, le) = self.seeded(query_mode)
        page = le.list_objects(
            SubjectID("alice"), "view", "n", max_depth=DEPTH, page_size=4
        )
        assert page.next_page_token
        store.write_relation_tuples(t("n:zzz#view@alice"))
        with pytest.raises(ErrStalePageToken) as ei:
            le.list_objects(
                SubjectID("alice"), "view", "n",
                max_depth=DEPTH, page_token=page.next_page_token,
            )
        assert ei.value.status_code == 409
        # a fresh (token-free) query serves the new version, new item seen
        fresh = le.list_objects(
            SubjectID("alice"), "view", "n", max_depth=DEPTH
        )
        assert "zzz" in fresh.items

    def test_cross_engine_token_rejected(self, query_mode):
        _, (_, le) = self.seeded(query_mode)
        page = le.list_objects(
            SubjectID("alice"), "view", "n", max_depth=DEPTH
        )
        alien = encode_page_token("expand", page.version, {"o": 0})
        with pytest.raises(ErrMalformedPageToken) as ei:
            le.list_objects(
                SubjectID("alice"), "view", "n",
                max_depth=DEPTH, page_token=alien,
            )
        assert ei.value.status_code == 400
        with pytest.raises(ErrMalformedPageToken):
            le.list_objects(
                SubjectID("alice"), "view", "n",
                max_depth=DEPTH, page_token="!!garbage!!",
            )


class TestBreakerDrill:
    def test_gather_failures_fall_back_then_open(self):
        store = InMemoryTupleStore()
        store.write_relation_tuples(
            t("n:doc1#view@alice"),
            t("n:doc2#view@(n:doc1#view)"),
        )
        eng, le = make_list_engine(
            store, "host", breaker_threshold=3, breaker_cooldown_s=0.2
        )
        want = le.list_objects(
            SubjectID("alice"), "view", "n", max_depth=DEPTH
        )
        assert want.source == "reverse"
        try:
            FAULTS.arm("list.gather_fail", times=3)
            for i in range(3):
                page = le.list_objects(
                    SubjectID("alice"), "view", "n", max_depth=DEPTH
                )
                # the oracle answer is byte-identical to the reverse one
                assert page.source == "oracle", f"call {i}"
                assert page.items == want.items
            assert le.breaker_open()
            assert le.n_reverse_failures == 3
            # breaker open: served by the oracle without touching reverse
            page = le.list_objects(
                SubjectID("alice"), "view", "n", max_depth=DEPTH
            )
            assert page.source == "oracle"
            assert page.items == want.items
        finally:
            FAULTS.reset()
        time.sleep(0.25)
        healed = le.list_objects(
            SubjectID("alice"), "view", "n", max_depth=DEPTH
        )
        assert healed.source == "reverse"
        assert healed.items == want.items


@pytest.fixture(scope="module")
def server():
    from keto_tpu.driver import Config

    cfg = Config(
        values={
            "namespaces": [{"id": 1, "name": "n"}],
            "log": {"level": "error"},
            "serve": {
                "read": {"port": 0, "host": "127.0.0.1"},
                "write": {"port": 0, "host": "127.0.0.1"},
            },
        }
    )
    s = ServerFixture(cfg)
    with RestClient(
        f"http://127.0.0.1:{s.read_port}",
        f"http://127.0.0.1:{s.write_port}",
    ) as c:
        c.create_relation_tuple("n:doc1#viewer@alice")
        c.create_relation_tuple("n:doc2#viewer@alice")
        c.create_relation_tuple("n:doc1#viewer@bob")
        c.create_relation_tuple("n:doc3#viewer@(n:doc1#viewer)")
    yield s
    s.stop()


class TestRestSurface:
    def test_list_objects_and_subjects(self, server):
        with RestClient(f"http://127.0.0.1:{server.read_port}") as c:
            res = c.list_objects("alice", "viewer", "n")
            assert res.items == ["doc1", "doc2", "doc3"]
            assert res.snaptoken
            assert c.list_subjects("n", "doc1", "viewer").items == [
                "alice", "bob"
            ]
            # doc3 grants ride the doc1#viewer indirection
            assert c.list_subjects("n", "doc3", "viewer").items == [
                "alice", "bob"
            ]

    def test_paging_round_trip(self, server):
        with RestClient(f"http://127.0.0.1:{server.read_port}") as c:
            first = c.list_objects("alice", "viewer", "n", page_size=2)
            assert len(first.items) == 2 and first.next_page_token
            rest_ = c.list_objects(
                "alice", "viewer", "n",
                page_size=2, page_token=first.next_page_token,
            )
            assert first.items + rest_.items == ["doc1", "doc2", "doc3"]

    def test_missing_params_400(self, server):
        import httpx

        with httpx.Client(
            base_url=f"http://127.0.0.1:{server.read_port}", timeout=30
        ) as c:
            r = c.get(
                "/relation-tuples/list-objects",
                params={"namespace": "n", "relation": "viewer"},
            )
            assert r.status_code == 400  # no subject
            r = c.get(
                "/relation-tuples/list-subjects",
                params={"namespace": "n", "relation": "viewer"},
            )
            assert r.status_code == 400  # no object

    def test_stale_token_409(self, server):
        with RestClient(
            f"http://127.0.0.1:{server.read_port}",
            f"http://127.0.0.1:{server.write_port}",
        ) as c:
            first = c.list_objects("alice", "viewer", "n", page_size=2)
            assert first.next_page_token
            c.create_relation_tuple("n:stale-probe#viewer@alice")
            with pytest.raises(ErrStalePageToken):
                c.list_objects(
                    "alice", "viewer", "n",
                    page_size=2, page_token=first.next_page_token,
                )


class TestGrpcSurface:
    def test_list_round_trip(self, server):
        with GrpcClient(f"127.0.0.1:{server.read_port}") as c:
            res = c.list_objects("alice", "viewer", "n")
            assert "doc1" in res.items and "doc2" in res.items
            subs = c.list_subjects("n", "doc1", "viewer")
            assert subs.items == ["alice", "bob"]
            assert res.snaptoken

    def test_stale_token_failed_precondition(self, server):
        with GrpcClient(f"127.0.0.1:{server.read_port}") as c:
            first = c.list_objects("alice", "viewer", "n", page_size=1)
            token = first.next_page_token
            assert token
        with RestClient(
            f"http://127.0.0.1:{server.read_port}",
            f"http://127.0.0.1:{server.write_port}",
        ) as w:
            w.create_relation_tuple("n:grpc-stale#viewer@alice")
        with GrpcClient(f"127.0.0.1:{server.read_port}") as c:
            with pytest.raises(grpc.RpcError) as ei:
                c.list_objects(
                    "alice", "viewer", "n", page_size=1, page_token=token
                )
            assert ei.value.code() == grpc.StatusCode.FAILED_PRECONDITION
