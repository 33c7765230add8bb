"""Native C kernels vs their numpy twins: bit-for-bit parity.

The native tier (keto_tpu/native) is pure performance — prefetch-pipelined
versions of the closure query and vocab probe. Any divergence from the numpy
paths is a correctness bug, so every kernel is tested against both its numpy
twin and the host oracle on random graphs, including rows whose fan-out
exceeds the numpy path's f0_max/l_max caps (where numpy falls back to the
oracle but C walks the true degrees)."""

import numpy as np
import pytest

from keto_tpu import native
from keto_tpu.engine import CheckEngine
from keto_tpu.engine.closure import ClosureCheckEngine
from keto_tpu.graph import SnapshotManager
from keto_tpu.relationtuple import RelationTuple
from keto_tpu.store import InMemoryTupleStore

from conftest import random_store

pytestmark = pytest.mark.skipif(
    native.lib is None, reason="native kernels unavailable (no C compiler)"
)


def t(s: str) -> RelationTuple:
    return RelationTuple.from_string(s)


def _requests(rng, n_objects, n_users, k):
    reqs = []
    for _ in range(k):
        obj = f"o{rng.integers(n_objects)}"
        rel = f"r{rng.integers(3)}"
        if rng.random() < 0.3:
            sub = f"n:o{rng.integers(n_objects)}#r{rng.integers(3)}"
        else:
            sub = f"u{rng.integers(n_users)}"
        reqs.append(t(f"n:{obj}#{rel}@({sub})"))
    return reqs


class TestObjectHashes:
    def test_matches_python_hash(self):
        keys = [("ns", f"o{i}", "rel") for i in range(100)] + [
            (f"u{i}",) for i in range(100)
        ]
        h = native.object_hashes(keys)
        assert h.tolist() == [hash(k) for k in keys]

    def test_unhashable_raises(self):
        with pytest.raises(TypeError):
            native.object_hashes([["list", "unhashable"]])


class TestProbeParity:
    @pytest.mark.parametrize("seed", range(3))
    def test_lookup_bulk_native_vs_numpy(self, seed, monkeypatch):
        from keto_tpu.graph.vocab import NodeVocab

        rng = np.random.default_rng(seed)
        vocab = NodeVocab()
        keys = [("n", f"o{i}", f"r{i % 3}") for i in range(2000)] + [
            (f"u{i}",) for i in range(2000)
        ]
        vocab.intern_bulk(keys)
        probe = [keys[i] for i in rng.integers(len(keys), size=500)]
        probe += [("n", "missing", "x"), ("nouser",)] * 10
        got_native = vocab.lookup_bulk(probe)
        monkeypatch.setattr(native, "lib", None)
        got_numpy = vocab.lookup_bulk(probe)
        np.testing.assert_array_equal(got_native, got_numpy)
        # and both agree with the exact dict
        exact = [
            v if (v := vocab.lookup(k)) is not None else -1 for k in probe
        ]
        assert got_native.tolist() == exact


class TestRequestHashes:
    def test_tuple_hash_parity(self):
        assert native.tuple_hash_ok
        for tup in [("a", "b", "c"), ("x",), ("", "", ""), ("u" * 99,)]:
            assert native.lib.tuple_hash_check(tup) == hash(tup)

    def test_hashes_and_flags(self):
        from keto_tpu.relationtuple import SubjectID, SubjectSet

        reqs = [
            t("n:o1#r@alice"),
            t("n:o2#r@(m:g#member)"),
            t(":#@()"),  # empty-string fields are legal key material
        ]
        hs, ht, is_id = native.request_hashes(reqs, SubjectID)
        for i, r in enumerate(reqs):
            assert hs[i] == hash((r.namespace, r.object, r.relation))
            s = r.subject
            want = (
                hash((s.id,))
                if isinstance(s, SubjectID)
                else hash((s.namespace, s.object, s.relation))
            )
            assert ht[i] == want
            assert is_id[i] == isinstance(s, SubjectID)

    def test_lookup_hashes_matches_lookup_bulk(self):
        from keto_tpu.graph.vocab import NodeVocab

        vocab = NodeVocab()
        keys = [("n", f"o{i}", "r") for i in range(500)] + [
            (f"u{i}",) for i in range(500)
        ]
        vocab.intern_bulk(keys)
        probe = keys[::3] + [("n", "nope", "r"), ("ghost",)]
        h = np.fromiter((hash(k) for k in probe), np.int64, count=len(probe))
        got = vocab.lookup_hashes(h, lambda i: probe[i])
        want = vocab.lookup_bulk(probe)
        np.testing.assert_array_equal(got, want)

    def test_lookup_hashes_collision_fallback(self):
        """Keys routed to the exact dict when their hash collides inside
        the vocab must still resolve through key_fn."""
        from keto_tpu.graph.vocab import NodeVocab

        vocab = NodeVocab()
        keys = [("n", f"o{i}", "r") for i in range(64)]
        vocab.intern_bulk(keys)
        vocab._extend_hash_index()
        # force a recorded collision for one stored hash
        mask, slots, slot_ids, collisions, upto = vocab._h_table
        victim = keys[7]
        collisions.add(hash(victim))
        vocab._h_table = (mask, slots, slot_ids, collisions, upto)
        h = np.array([hash(victim)], np.int64)
        got = vocab.lookup_hashes(h, lambda i: victim)
        assert got[0] == vocab.lookup(victim)
        # a DIFFERENT key with that same hash value resolves to unknown
        got_missing = vocab.lookup_hashes(h, lambda i: ("not", "a", "key"))
        assert got_missing[0] == -1


class TestClosureCheckParity:
    @pytest.mark.parametrize("seed", range(4))
    def test_native_vs_numpy_vs_oracle(self, seed, monkeypatch):
        rng = np.random.default_rng(seed)
        store = random_store(rng, n_objects=15, n_users=10, n_edges=150)
        reqs = _requests(rng, 15, 10, 128)
        for depth in (1, 2, 3, 5):
            oracle = CheckEngine(store, max_depth=depth)
            eng = ClosureCheckEngine(
                SnapshotManager(store), max_depth=depth
            )
            got_native = eng.batch_check(reqs)
            monkeypatch.setattr(native, "lib", None)
            eng2 = ClosureCheckEngine(
                SnapshotManager(store), max_depth=depth
            )
            got_numpy = eng2.batch_check(reqs)
            monkeypatch.undo()
            expect = oracle.batch_check(reqs)
            assert got_native == expect
            assert got_numpy == expect

    def test_wide_fanout_exceeding_numpy_caps(self):
        """Rows wider than f0_max/l_max: numpy falls back to the oracle,
        C walks true degrees — both must match the oracle."""
        store = InMemoryTupleStore()
        tuples = []
        # start with 70 set successors (> f0_max=32)
        for i in range(70):
            tuples.append(t(f"n:doc#view@(n:g{i}#m)"))
            tuples.append(t(f"n:g{i}#m@(n:h{i}#m)"))
        # target with 50 interior in-neighbors (> l_max=32)
        for i in range(50):
            tuples.append(t(f"n:h{i}#m@alice"))
        store.write_relation_tuples(*tuples)
        oracle = CheckEngine(store, max_depth=5)
        eng = ClosureCheckEngine(SnapshotManager(store), max_depth=5)
        reqs = [
            t("n:doc#view@alice"),
            t("n:doc#view@bob"),
            t("n:doc#view@(n:g3#m)"),
            t("n:doc#view@(n:h9#m)"),
        ]
        assert eng.batch_check(reqs) == oracle.batch_check(reqs)
        # per-request depths through the same path
        assert eng.batch_check(reqs, depths=[1, 2, 3, 4]) == oracle.batch_check(
            reqs, depths=[1, 2, 3, 4]
        )

    def test_mixed_depths_and_direct_edges(self):
        store = InMemoryTupleStore()
        store.write_relation_tuples(
            t("n:a#r@alice"),
            t("n:a#r@(n:b#r)"),
            t("n:b#r@(n:c#r)"),
            t("n:c#r@bob"),
        )
        oracle = CheckEngine(store, max_depth=8)
        eng = ClosureCheckEngine(SnapshotManager(store), max_depth=8)
        reqs = [
            t("n:a#r@alice"),  # direct, depth 1
            t("n:a#r@bob"),  # 3 hops
            t("n:a#r@(n:c#r)"),  # set target, 2 hops
            t("n:a#r@(n:a#r)"),  # self
            t("n:zzz#r@alice"),  # unknown start
        ]
        for depths in (None, [1, 1, 1, 1, 1], [1, 3, 2, 1, 5], [2, 2, 2, 2, 2]):
            assert eng.batch_check(reqs, depths=depths) == oracle.batch_check(
                reqs, depths=depths
            )


class TestGatherMin:
    def test_matches_numpy(self):
        rng = np.random.default_rng(0)
        d = rng.integers(0, 256, size=(64, 64), dtype=np.uint8)
        rows = rng.integers(0, 64, size=(40, 5)).astype(np.int32)
        cols = rng.integers(0, 64, size=(40, 3)).astype(np.int32)
        got = native.gather_min_u8(d, rows, cols)
        want = d[rows[:, :, None], cols[:, None, :]].min(axis=(1, 2))
        np.testing.assert_array_equal(got, want)
