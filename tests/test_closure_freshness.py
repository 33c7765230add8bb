"""Write-path freshness of the closure engine: incremental closure updates,
bounded-staleness serving with background rebuilds, and snaptoken honesty.

The reference stubs snapshot tokens ("not yet implemented",
/root/reference/internal/check/handler.go:182); here bounded freshness is the
real Zanzibar zookie contract: a check may be answered at a slightly older
store version, and the response names that version.
"""

import time

import numpy as np
import pytest

from keto_tpu.engine import CheckEngine
from keto_tpu.engine.closure import ClosureCheckEngine
from keto_tpu.graph import SnapshotManager
from keto_tpu.relationtuple import RelationTuple
from keto_tpu.store import InMemoryTupleStore

from conftest import random_store


def t(s: str) -> RelationTuple:
    return RelationTuple.from_string(s)


def _wait_until(pred, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.01)
    return False


class TestIncrementalClosure:
    def test_appended_interior_edge_updates_in_place(self):
        store = InMemoryTupleStore()
        store.write_relation_tuples(
            t("n:a#r@(n:b#r)"),
            t("n:b#r@(n:c#r)"),
            t("n:c#r@u1"),
        )
        mgr = SnapshotManager(store)
        eng = ClosureCheckEngine(mgr, max_depth=8)
        assert eng.subject_is_allowed(t("n:a#r@u1"))
        full0 = eng.n_full_builds
        assert full0 >= 1 and eng.n_incremental_builds == 0

        # c#r -> b#r: both endpoints already interior. Since round 4 the
        # write OVERLAY absorbs this with an in-place O(M^2) D patch — no
        # rebuild of any kind (engine/overlay.py)
        store.write_relation_tuples(t("n:c#r@(n:b#r)"))
        assert eng.subject_is_allowed(t("n:c#r@u1"))
        assert eng.n_incremental_builds == 0
        assert eng.n_full_builds == full0

        # the cycle b -> c -> b must now resolve both ways
        assert eng.subject_is_allowed(t("n:b#r@(n:b#r)"))
        assert eng.subject_is_allowed(t("n:c#r@(n:c#r)"))

    def test_new_interior_node_grows_without_rebuild(self):
        store = InMemoryTupleStore()
        store.write_relation_tuples(
            t("n:a#r@(n:b#r)"), t("n:b#r@u1")
        )
        mgr = SnapshotManager(store)
        eng = ClosureCheckEngine(mgr, max_depth=8)
        eng.subject_is_allowed(t("n:a#r@u1"))
        full0 = eng.n_full_builds

        # a#r gains an incoming edge -> becomes interior. Since round 4
        # the overlay grows it into D's reserved padding in place
        # (engine/overlay.py _grow_interior) — no rebuild
        store.write_relation_tuples(t("n:x#q@(n:a#r)"))
        assert eng.subject_is_allowed(t("n:x#q@u1"))
        assert eng.n_full_builds == full0
        assert eng.served_version() == store.version

    @pytest.mark.parametrize("seed", range(3))
    def test_incremental_stream_matches_oracle(self, seed):
        """A stream of appended set->set edges between existing interior
        nodes must keep the closure bit-for-bit exact vs host BFS."""
        rng = np.random.default_rng(seed + 300)
        store = random_store(rng, n_objects=12, n_users=8, n_edges=120)
        mgr = SnapshotManager(store)
        eng = ClosureCheckEngine(mgr, max_depth=6)
        host = CheckEngine(store, max_depth=6)
        snap = mgr.snapshot()
        from keto_tpu.graph.interior import build_interior

        ig = build_interior(snap)
        if ig.m < 3:
            pytest.skip("graph too small to have interior pairs")
        keys = [snap.vocab.key(int(i)) for i in ig.interior_ids]
        eng.subject_is_allowed(t("n:o0#r0@u0"))  # prime the closure
        for _ in range(5):
            a = keys[rng.integers(len(keys))]
            b = keys[rng.integers(len(keys))]
            store.write_relation_tuples(
                RelationTuple.from_string(
                    f"{a[0]}:{a[1]}#{a[2]}@({b[0]}:{b[1]}#{b[2]})"
                )
            )
            reqs = []
            for _ in range(32):
                obj = f"o{rng.integers(12)}"
                rel = f"r{rng.integers(3)}"
                sub = f"u{rng.integers(8)}"
                reqs.append(t(f"n:{obj}#{rel}@{sub}"))
            expect = [host.subject_is_allowed(r) for r in reqs]
            assert eng.batch_check(reqs) == expect
        # round 4: the overlay absorbs the whole stream without rebuilds
        assert eng.n_incremental_builds == 0
        assert eng.n_full_builds == 1


class TestBoundedFreshness:
    def test_serves_stale_then_converges(self):
        store = InMemoryTupleStore()
        store.write_relation_tuples(t("n:obj#r@alice"))
        mgr = SnapshotManager(store)
        eng = ClosureCheckEngine(
            mgr, max_depth=5, freshness="bounded", rebuild_debounce_s=0.0
        )
        assert eng.subject_is_allowed(t("n:obj#r@alice"))
        v0 = eng.served_version()

        store.write_relation_tuples(t("n:obj#r@bob"))
        # the first check after the write must NOT stall on a rebuild: it
        # answers from the stale snapshot (served_version says which)
        eng.subject_is_allowed(t("n:obj#r@bob"))
        # ...and the background rebuild converges to the new version
        assert _wait_until(
            lambda: eng.served_version() == store.version
            and eng.subject_is_allowed(t("n:obj#r@bob"))
        )
        assert eng.served_version() > v0

    def test_no_stall_under_write_storm(self):
        """Checks stay fast while writes stream in: no check should ever
        pay a synchronous rebuild under bounded freshness."""
        store = InMemoryTupleStore()
        for i in range(50):
            store.write_relation_tuples(t(f"n:o{i}#r@(n:g{i % 7}#m)"))
        for i in range(7):
            store.write_relation_tuples(t(f"n:g{i}#m@alice"))
        mgr = SnapshotManager(store)
        eng = ClosureCheckEngine(
            mgr, max_depth=5, freshness="bounded", rebuild_debounce_s=0.0
        )
        eng.warmup()
        req = t("n:o1#r@alice")
        lat = []
        for i in range(60):
            store.write_relation_tuples(t(f"n:extra{i}#r@bob"))
            t0 = time.perf_counter()
            assert eng.subject_is_allowed(req)
            lat.append(time.perf_counter() - t0)
        # p95 bounded: stale serving means no check waits on a rebuild.
        # (generous bound — CI boxes are noisy; the failure mode being
        # guarded against is a multi-second synchronous closure rebuild)
        assert sorted(lat)[int(len(lat) * 0.95)] < 0.5
        assert _wait_until(lambda: eng.served_version() == store.version)

    def test_strong_freshness_is_read_your_writes(self):
        store = InMemoryTupleStore()
        mgr = SnapshotManager(store)
        eng = ClosureCheckEngine(mgr, max_depth=5, freshness="strong")
        assert not eng.subject_is_allowed(t("n:obj#r@alice"))
        store.write_relation_tuples(t("n:obj#r@alice"))
        assert eng.subject_is_allowed(t("n:obj#r@alice"))
        assert eng.served_version() == store.version

    def test_auto_is_strong_at_small_scale(self):
        store = InMemoryTupleStore()
        mgr = SnapshotManager(store)
        eng = ClosureCheckEngine(mgr, max_depth=5)  # freshness="auto"
        store.write_relation_tuples(t("n:obj#r@alice"))
        # tiny graph -> strong: immediately visible
        assert eng.subject_is_allowed(t("n:obj#r@alice"))
