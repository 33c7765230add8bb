"""Write-overlay freshness: exact serving-time deltas over the resident
closure (engine/overlay.py; VERDICT r3 #3 — bounded staleness under deletes
without the full-rebuild cliff).

The property under test everywhere: with bounded freshness, after ANY
sequence of leaf writes/deletes the engine answers exactly like a fresh
host oracle at the live store version, WITHOUT having rebuilt the closure;
interior-edge inserts absorb into D in place; interior-edge deletes absorb
via the bounded exact re-close of affected D rows (r5: VERDICT r4 weak #3);
only cap/budget overflow falls back to the rebuild path — and remains
correct there.
"""

import numpy as np
import pytest

from keto_tpu.engine import CheckEngine
from keto_tpu.engine.closure import ClosureCheckEngine, _ClosureArtifacts
from keto_tpu.graph import SnapshotManager
from keto_tpu.relationtuple import RelationTuple
from keto_tpu.store import InMemoryTupleStore

from conftest import random_store


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


def make_engine(store, **kw):
    kw.setdefault("max_depth", 5)
    kw.setdefault("freshness", "bounded")
    kw.setdefault("rebuild_debounce_s", 0.0)
    eng = ClosureCheckEngine(SnapshotManager(store), **kw)
    return eng


def assert_live_parity(eng, store, reqs, depths=(0,)):
    oracle = CheckEngine(store, max_depth=eng.global_max_depth)
    for d in depths:
        got = eng.batch_check(reqs, max_depth=d)
        want = oracle.batch_check(reqs, max_depth=d)
        assert got == want


class TestLeafWrites:
    @pytest.mark.parametrize("seed", range(4))
    def test_random_leaf_mutations_stay_exact_without_rebuild(self, seed):
        rng = np.random.default_rng(seed)
        store = random_store(rng, n_objects=12, n_users=9, n_edges=110)
        eng = make_engine(store)
        reqs = _requests(rng, 12, 9, 96)
        eng.batch_check(reqs)  # build the base closure
        builds0 = eng.n_full_builds + eng.n_incremental_builds
        # interleave leaf writes and deletes with checks
        all_tuples = store.all_tuples()
        for step in range(6):
            victims = [
                all_tuples[i]
                for i in rng.integers(len(all_tuples), size=3)
            ]
            # only delete leaf edges (subject-id dst, or src not interior):
            # pick id-subject tuples — always leaf
            victims = [
                v for v in victims if not hasattr(v.subject, "relation")
            ]
            if victims:
                store.delete_relation_tuples(*victims)
            store.write_relation_tuples(
                t(f"n:o{rng.integers(12)}#r{rng.integers(3)}"
                  f"@u{rng.integers(9)}"),
                t(f"n:o{rng.integers(12)}#r{rng.integers(3)}"
                  f"@newuser{step}"),
            )
            assert_live_parity(eng, store, reqs, depths=(0, 2))
            # served at the LIVE version, via overlay — not a rebuild
            assert eng.served_version() == store.version
        assert eng.n_full_builds + eng.n_incremental_builds == builds0

    def test_delete_then_reinsert_roundtrip(self):
        store = InMemoryTupleStore()
        store.write_relation_tuples(
            t("n:doc#view@(n:g#m)"), t("n:g#m@alice")
        )
        eng = make_engine(store)
        q = [t("n:doc#view@alice"), t("n:g#m@alice")]
        assert eng.batch_check(q) == [True, True]
        store.delete_relation_tuples(t("n:g#m@alice"))
        assert eng.batch_check(q) == [False, False]
        assert eng.served_version() == store.version
        store.write_relation_tuples(t("n:g#m@alice"))
        assert eng.batch_check(q) == [True, True]
        assert eng.n_full_builds == 1  # the initial build only

    def test_new_user_and_new_object_after_snapshot(self):
        """Nodes interned after the base snapshot (beyond padded width)
        must resolve through the overlay, not clamp to dummy-deny."""
        store = InMemoryTupleStore()
        store.write_relation_tuples(t("n:doc#view@(n:g#m)"))
        eng = make_engine(store)
        assert eng.subject_is_allowed(t("n:doc#view@zoe")) is False
        store.write_relation_tuples(t("n:g#m@zoe"))
        assert eng.subject_is_allowed(t("n:doc#view@zoe")) is True
        # brand-new object too
        store.write_relation_tuples(t("n:newdoc#view@zoe"))
        assert eng.subject_is_allowed(t("n:newdoc#view@zoe")) is True
        assert eng.n_full_builds == 1

    def test_direct_edge_delete_with_surviving_indirect_path(self):
        store = InMemoryTupleStore()
        store.write_relation_tuples(
            t("n:doc#view@alice"),  # direct
            t("n:doc#view@(n:g#m)"),
            t("n:g#m@alice"),  # indirect, depth 3... actually 2
        )
        eng = make_engine(store)
        assert eng.subject_is_allowed(t("n:doc#view@alice")) is True
        store.delete_relation_tuples(t("n:doc#view@alice"))
        # the direct edge is gone but the group path survives
        assert eng.subject_is_allowed(t("n:doc#view@alice")) is True
        # at depth 1 only the (deleted) direct edge would have counted
        assert eng.subject_is_allowed(t("n:doc#view@alice"), 1) is False
        store.delete_relation_tuples(t("n:g#m@alice"))
        assert eng.subject_is_allowed(t("n:doc#view@alice")) is False
        assert eng.n_full_builds == 1


class TestInteriorWrites:
    def test_interior_edge_insert_patches_closure_in_place(self):
        store = InMemoryTupleStore()
        store.write_relation_tuples(
            t("n:doc#view@(n:g1#m)"),
            t("n:g2#m@alice"),
            t("n:g2#m@(n:g3#m)"),  # make g2, g3 interior
            t("n:g3#m@bob"),
        )
        eng = make_engine(store)
        assert eng.subject_is_allowed(t("n:doc#view@alice")) is False
        # new interior edge g1 -> g2 (both ends interior-capable)
        store.write_relation_tuples(t("n:g1#m@(n:g2#m)"))
        assert eng.subject_is_allowed(t("n:doc#view@alice")) is True
        # path doc -> g1 -> g2 -> g3 -> bob needs depth 4
        assert eng.subject_is_allowed(t("n:doc#view@bob"), 4) is True
        assert eng.subject_is_allowed(t("n:doc#view@bob"), 3) is False
        assert eng.n_full_builds == 1

    def test_new_interior_node_grows_into_padding(self):
        store = InMemoryTupleStore()
        store.write_relation_tuples(t("n:g0#m@(n:g1#m)"), t("n:g1#m@u0"))
        eng = make_engine(store)
        eng.batch_check([t("n:g0#m@u0")])
        # a chain of brand-new set nodes: each becomes interior via overlay
        store.write_relation_tuples(t("n:g1#m@(n:h1#x)"))
        store.write_relation_tuples(t("n:h1#x@(n:h2#x)"))
        store.write_relation_tuples(t("n:h2#x@carol"))
        oracle = CheckEngine(store, max_depth=5)
        reqs = [
            t("n:g0#m@carol"),
            t("n:g0#m@(n:h2#x)"),
            t("n:h1#x@carol"),
        ]
        assert eng.batch_check(reqs) == oracle.batch_check(reqs)
        assert eng.served_version() == store.version
        assert eng.n_full_builds == 1

    def test_interior_delete_absorbed_without_rebuild(self):
        store = InMemoryTupleStore()
        store.write_relation_tuples(
            t("n:doc#view@(n:g1#m)"),
            t("n:g1#m@(n:g2#m)"),
            t("n:g2#m@alice"),
        )
        eng = make_engine(store)
        assert eng.subject_is_allowed(t("n:doc#view@alice")) is True
        builds0 = eng.n_full_builds + eng.n_incremental_builds
        # deleting the interior g1->g2 edge: bounded exact re-close of
        # the affected D rows — NO rebuild (r5; used to be the one
        # full-rebuild cliff left)
        store.delete_relation_tuples(t("n:g1#m@(n:g2#m)"))
        assert eng.subject_is_allowed(t("n:doc#view@alice")) is False
        assert eng.n_full_builds + eng.n_incremental_builds == builds0
        assert eng.served_version() == store.version

    def test_interior_delete_keeps_surviving_longer_path(self):
        """Deleting one interior edge must re-lengthen, not sever: a
        surviving longer path through another group must still answer
        True (with the correct new depth requirement)."""
        store = InMemoryTupleStore()
        store.write_relation_tuples(
            t("n:doc#view@(n:g1#m)"),
            t("n:g1#m@(n:g3#m)"),      # short path: doc -> g1 -> g3
            t("n:g1#m@(n:g2#m)"),      # long path: doc -> g1 -> g2 -> g3
            t("n:g2#m@(n:g3#m)"),
            t("n:g3#m@alice"),
        )
        eng = make_engine(store)
        assert eng.subject_is_allowed(t("n:doc#view@alice"), 3) is True
        builds0 = eng.n_full_builds + eng.n_incremental_builds
        store.delete_relation_tuples(t("n:g1#m@(n:g3#m)"))
        # still reachable via g2, one hop longer
        assert eng.subject_is_allowed(t("n:doc#view@alice"), 4) is True
        assert eng.subject_is_allowed(t("n:doc#view@alice"), 3) is False
        assert eng.n_full_builds + eng.n_incremental_builds == builds0
        assert_live_parity(eng, store, [t("n:doc#view@alice")], depths=(0, 3, 4))

    def test_interior_delete_of_overlay_inserted_edge(self):
        """Insert an interior edge through the overlay, then delete it
        again: the re-close must consult the CURRENT adjacency (base +
        overlay-inserted - deleted), not the base CSR alone."""
        store = InMemoryTupleStore()
        store.write_relation_tuples(
            t("n:doc#view@(n:g1#m)"),
            t("n:g1#m@x"),
            t("n:g2#m@alice"),
            t("n:top#m@(n:g2#m)"),  # make g2 interior in the base
        )
        eng = make_engine(store)
        eng.batch_check([t("n:doc#view@alice")])
        store.write_relation_tuples(t("n:g1#m@(n:g2#m)"))  # overlay insert
        assert eng.subject_is_allowed(t("n:doc#view@alice")) is True
        store.delete_relation_tuples(t("n:g1#m@(n:g2#m)"))  # overlay delete
        assert eng.subject_is_allowed(t("n:doc#view@alice")) is False
        # and re-insert brings it back
        store.write_relation_tuples(t("n:g1#m@(n:g2#m)"))
        assert eng.subject_is_allowed(t("n:doc#view@alice")) is True
        assert eng.served_version() == store.version

    def test_interior_delete_budget_breaks_to_rebuild(self):
        """A delete whose candidate row set exceeds max_delete_rows must
        break the overlay (rebuild path) and still answer correctly."""
        from keto_tpu.engine.overlay import WriteOverlay

        store = InMemoryTupleStore()
        store.write_relation_tuples(
            t("n:doc#view@(n:g1#m)"),
            t("n:g1#m@(n:g2#m)"),
            t("n:g2#m@alice"),
        )
        eng = make_engine(store)
        eng.batch_check([t("n:doc#view@alice")])
        ov = eng._overlay
        assert isinstance(ov, WriteOverlay)
        ov.max_delete_rows = 0  # force the budget break
        store.delete_relation_tuples(t("n:g1#m@(n:g2#m)"))
        eng.batch_check([t("n:doc#view@alice")])  # drains -> breaks
        assert ov.broken and "interior delete" in ov.broken_reason
        # bounded freshness: stale until the rebuild lands, then exact
        eng.wait_for_version(store.version, timeout_s=30)
        assert eng.subject_is_allowed(t("n:doc#view@alice")) is False


class TestPromotionReclassification:
    def test_chain_built_tuple_by_tuple_from_empty_store(self):
        """The cat-videos regression: an engine whose base snapshot is
        EMPTY sees every edge via overlay; nodes promoted to interior must
        reclassify their earlier OVERLAY out-edges (id successors into L,
        set successors into D), not only base edges."""
        store = InMemoryTupleStore()
        eng = make_engine(store)
        eng.batch_check([t("videos:/cats#owner@nobody")])  # empty base
        for s in [
            "videos:/cats#owner@(cat lady)",
            "videos:/cats/1.mp4#owner@(videos:/cats#owner)",
            "videos:/cats/1.mp4#view@(videos:/cats/1.mp4#owner)",
        ]:
            store.write_relation_tuples(t(s))
        oracle = CheckEngine(store, max_depth=5)
        reqs = [
            t("videos:/cats#owner@(cat lady)"),
            t("videos:/cats/1.mp4#owner@(cat lady)"),
            t("videos:/cats/1.mp4#view@(cat lady)"),  # two indirections
            t("videos:/cats/1.mp4#view@(dog guy)"),
        ]
        assert eng.batch_check(reqs) == oracle.batch_check(reqs) == [
            True, True, True, False,
        ]
        assert eng.n_full_builds == 1

    def test_transact_insert_and_delete_same_set_tuple(self):
        """A transact inserting AND deleting the same set-subject tuple
        nets to absent; the overlay must apply inserts first (store order)
        so the delete sees the promotion's index — a delete-first pass
        left a phantom F0 entry granting a permission that doesn't exist."""
        store = InMemoryTupleStore()
        store.write_relation_tuples(t("n:g#m@alice"))  # g#m exterior
        eng = make_engine(store)
        eng.batch_check([t("n:g#m@alice")])
        store.transact_relation_tuples(
            insert=[t("n:doc#view@(n:g#m)")],
            delete=[t("n:doc#view@(n:g#m)")],
        )
        oracle = CheckEngine(store, max_depth=5)
        reqs = [t("n:doc#view@alice"), t("n:g#m@alice")]
        assert eng.batch_check(reqs) == oracle.batch_check(reqs) == [
            False, True,
        ]

    def test_promotion_skips_overlay_deleted_base_edges(self):
        """A base out-edge deleted via overlay must NOT be resurrected
        when its source node is later promoted to interior."""
        store = InMemoryTupleStore()
        store.write_relation_tuples(t("n:g#m@alice"))  # base: g -> alice
        eng = make_engine(store)
        eng.batch_check([t("n:g#m@alice")])
        store.delete_relation_tuples(t("n:g#m@alice"))
        # now promote g by giving it an in-edge
        store.write_relation_tuples(t("n:doc#view@(n:g#m)"))
        oracle = CheckEngine(store, max_depth=5)
        reqs = [t("n:doc#view@alice"), t("n:g#m@alice")]
        assert eng.batch_check(reqs) == oracle.batch_check(reqs) == [
            False, False,
        ]
        assert eng.n_full_builds == 1


class TestOverlayLifecycle:
    def test_wait_for_version_satisfied_by_overlay(self):
        store = InMemoryTupleStore()
        store.write_relation_tuples(t("n:doc#view@(n:g#m)"))
        eng = make_engine(store)
        eng.batch_check([t("n:doc#view@alice")])
        store.write_relation_tuples(t("n:g#m@alice"))
        # the overlay covers the write: no 503, no rebuild wait
        eng.wait_for_version(store.version, timeout_s=0.5)
        assert eng.subject_is_allowed(t("n:doc#view@alice")) is True

    def test_cap_overflow_breaks_overlay_then_rebuild_recovers(self):
        rng = np.random.default_rng(0)
        store = random_store(rng, n_objects=8, n_users=6, n_edges=60)
        eng = make_engine(store)
        reqs = _requests(rng, 8, 6, 64)
        eng.batch_check(reqs)
        eng._overlay.max_events = 4  # force the cap
        for i in range(6):
            store.write_relation_tuples(t(f"n:o1#r0@x{i}"))
        # overlay broke; bounded freshness serves stale then catches up
        assert_live_parity_eventually(eng, store, reqs)

    def test_mixed_random_mutations_vs_oracle(self):
        """The big one: arbitrary interleaved writes/deletes (incl.
        interior) with parity asserted against a fresh oracle after every
        step, across freshness policies."""
        for policy in ("bounded", "strong"):
            rng = np.random.default_rng(42)
            store = random_store(rng, n_objects=10, n_users=8, n_edges=90)
            eng = make_engine(store, freshness=policy)
            reqs = _requests(rng, 10, 8, 80)
            eng.batch_check(reqs)
            for step in range(8):
                roll = rng.random()
                if roll < 0.4:
                    all_t = store.all_tuples()
                    victims = [
                        all_t[i]
                        for i in rng.integers(len(all_t), size=2)
                    ]
                    store.delete_relation_tuples(*victims)
                elif roll < 0.8:
                    store.write_relation_tuples(
                        *_requests(rng, 10, 8, 3)
                    )
                else:
                    store.write_relation_tuples(
                        t(f"n:o{rng.integers(10)}#r0"
                          f"@(n:o{rng.integers(10)}#r1)")
                    )
                assert_live_parity_eventually(eng, store, reqs)


def assert_live_parity_eventually(eng, store, reqs, timeout_s=10.0):
    """Parity at the live version, allowing the bounded-freshness rebuild
    to land first when the overlay could not absorb the writes."""
    import time

    oracle = CheckEngine(store, max_depth=eng.global_max_depth)
    want = oracle.batch_check(reqs)
    deadline = time.monotonic() + timeout_s
    while True:
        try:
            eng.wait_for_version(store.version, timeout_s=2.0)
        except Exception:
            pass
        got = eng.batch_check(reqs)
        if got == want and eng.served_version() == store.version:
            return
        if time.monotonic() > deadline:
            assert got == want, "answers never converged to the oracle"
            assert eng.served_version() == store.version
            return
        time.sleep(0.05)


class TestInteriorChurn:
    """Randomized interior-edge churn: interleaved inserts AND deletes of
    group->group edges must stay exact vs the live-store oracle with ZERO
    closure rebuilds — the full absorption property (r5: re-close +
    relaxation + promotion all composing)."""

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_random_interior_churn_stays_exact_without_rebuild(self, seed):
        rng = np.random.default_rng(seed)
        n_groups = 12
        store = InMemoryTupleStore()
        # base: a layer of docs granting to groups, groups holding users,
        # and some initial nesting so the interior is non-trivial
        base = []
        for g in range(n_groups):
            base.append(t(f"n:g{g}#m@u{g % 5}"))
            base.append(t(f"n:doc{g % 4}#view@(n:g{g}#m)"))
        for _ in range(8):
            a, b = rng.integers(n_groups, size=2)
            base.append(t(f"n:g{a}#m@(n:g{b}#m)"))
        store.write_relation_tuples(*base)
        eng = make_engine(store)
        reqs = [
            t(f"n:doc{d}#view@u{u}") for d in range(4) for u in range(5)
        ] + [
            t(f"n:g{a}#m@u{u}")
            for a in range(0, n_groups, 3)
            for u in range(5)
        ]
        assert_live_parity(eng, store, reqs, depths=(0, 2, 3))
        builds0 = eng.n_full_builds + eng.n_incremental_builds

        for step in range(60):
            a, b = (int(x) for x in rng.integers(n_groups, size=2))
            edge = t(f"n:g{a}#m@(n:g{b}#m)")
            if rng.random() < 0.5:
                store.write_relation_tuples(edge)
            else:
                store.delete_relation_tuples(edge)
            if step % 5 == 0:
                assert_live_parity(eng, store, reqs, depths=(0, 3))
        assert_live_parity(eng, store, reqs, depths=(0, 2, 3, 5))
        assert eng.n_full_builds + eng.n_incremental_builds == builds0, (
            "interior churn must absorb without rebuilds"
        )
        assert eng.served_version() == store.version
