"""ClosureCheckEngine vs the host oracle: the gather-only closure path must
agree bit-for-bit with host BFS on every graph — the scenario matrix of
reference internal/check/engine_test.go:45-581 at each query placement
(host queries, device queries, and the interior walk), plus closure-specific
edges: overflow fallback, interior-limit fallback, and write-driven closure
rebuilds."""

import numpy as np
import pytest

from keto_tpu.engine import CheckEngine
from keto_tpu.engine.closure import ClosureCheckEngine, _WalkArtifacts
from keto_tpu.graph import SnapshotManager
from keto_tpu.graph.interior import build_interior
from keto_tpu.relationtuple import RelationTuple, SubjectSet
from keto_tpu.store import InMemoryTupleStore

from conftest import random_store


def t(s: str) -> RelationTuple:
    return RelationTuple.from_string(s)


@pytest.fixture
def store():
    return InMemoryTupleStore()


def make_engines(store, max_depth=5, **kw):
    mgr = SnapshotManager(store)
    return (
        CheckEngine(store, max_depth=max_depth),
        ClosureCheckEngine(mgr, max_depth=max_depth, **kw),
    )


# where a batch is answered: the closure's D queried by the host kernels or
# on the device, or the interior's CSR walked on the device (a limit below
# every interior size, the empty one included, forces the walk)
PLACEMENTS = {
    "host": dict(query_mode="host"),
    "device": dict(query_mode="device"),
    "walk": dict(query_mode="device", interior_limit=-1),
}


@pytest.fixture(params=list(PLACEMENTS))
def placed(request):
    """``placed(store, max_depth=5)`` -> (oracle, engine) at this test's
    placement; a walk case fails unless every engine it built walks."""
    built = []

    def make(store, max_depth=5, **kw):
        oracle, eng = make_engines(
            store, max_depth, **PLACEMENTS[request.param], **kw
        )
        built.append(eng)
        return oracle, eng

    yield make
    if request.param == "walk":
        for eng in built:
            assert isinstance(eng._state, _WalkArtifacts)


class TestClosureScenarios:
    def test_direct_inclusion(self, store, placed):
        store.write_relation_tuples(t("n:obj#access@alice"))
        _, eng = placed(store)
        assert eng.subject_is_allowed(t("n:obj#access@alice"))
        assert not eng.subject_is_allowed(t("n:obj#access@bob"))

    def test_indirect_inclusion_two_levels(self, store, placed):
        store.write_relation_tuples(
            t("n:obj#access@(n:org#member)"),
            t("n:org#member@(n:team#member)"),
            t("n:team#member@alice"),
        )
        _, eng = placed(store)
        assert eng.subject_is_allowed(t("n:obj#access@alice"))
        assert eng.subject_is_allowed(t("n:obj#access@(n:team#member)"))
        assert not eng.subject_is_allowed(t("n:obj#access@mallory"))

    def test_wrong_object_or_relation(self, store, placed):
        store.write_relation_tuples(t("n:obj#access@alice"))
        _, eng = placed(store)
        assert not eng.subject_is_allowed(t("n:other#access@alice"))
        assert not eng.subject_is_allowed(t("n:obj#write@alice"))
        assert not eng.subject_is_allowed(t("other:obj#access@alice"))

    def test_circular_tuples_terminate(self, store, placed):
        store.write_relation_tuples(t("n:a#r@(n:b#r)"), t("n:b#r@(n:a#r)"))
        _, eng = placed(store)
        assert not eng.subject_is_allowed(t("n:a#r@alice"))
        # the sets themselves are mutually reachable (cycle of length 2)
        assert eng.subject_is_allowed(t("n:a#r@(n:a#r)"))
        assert eng.subject_is_allowed(t("n:a#r@(n:b#r)"))

    def test_depth_budget(self, store, placed):
        store.write_relation_tuples(
            t("n:obj#r@(n:s1#m)"),
            t("n:s1#m@(n:s2#m)"),
            t("n:s2#m@(n:s3#m)"),
            t("n:s3#m@alice"),
        )
        _, eng = placed(store, max_depth=10)
        req = t("n:obj#r@alice")
        assert not eng.subject_is_allowed(req, max_depth=3)
        assert eng.subject_is_allowed(req, max_depth=4)
        assert eng.subject_is_allowed(req, max_depth=0)  # clamps to global
        assert eng.subject_is_allowed(req, max_depth=99)

    def test_global_max_depth_precedence(self, store, placed):
        store.write_relation_tuples(
            t("n:obj#r@(n:s1#m)"),
            t("n:s1#m@(n:s2#m)"),
            t("n:s2#m@alice"),
        )
        _, eng = placed(store, max_depth=2)
        assert not eng.subject_is_allowed(t("n:obj#r@alice"), max_depth=50)

    def test_subject_set_exact_match_semantics(self, store, placed):
        store.write_relation_tuples(t("n:obj#r@alice"))
        _, eng = placed(store)
        assert not eng.subject_is_allowed(t("n:obj#r@(n:obj#r)"))

    def test_set_target_depth_one(self, store, placed):
        # direct set-to-set edge must be allowed at depth 1 exactly
        store.write_relation_tuples(t("n:obj#r@(n:grp#m)"), t("n:grp#m@u"))
        _, eng = placed(store)
        assert eng.subject_is_allowed(t("n:obj#r@(n:grp#m)"), max_depth=1)

    def test_unknown_everything(self, store, placed):
        _, eng = placed(store)
        assert not eng.subject_is_allowed(t("no:thing#here@nobody"))

    def test_write_visibility_rebuilds_closure(self, store, placed):
        _, eng = placed(store)
        req = t("n:obj#r@alice")
        assert not eng.subject_is_allowed(req)
        store.write_relation_tuples(req)
        assert eng.subject_is_allowed(req)
        store.delete_relation_tuples(req)
        assert not eng.subject_is_allowed(req)
        # indirect path appears after incremental writes
        store.write_relation_tuples(t("n:obj#r@(n:g#m)"))
        store.write_relation_tuples(t("n:g#m@alice"))
        assert eng.subject_is_allowed(req)

    def test_batch_mixed_depths(self, store, placed):
        store.write_relation_tuples(
            t("n:obj#r@(n:s1#m)"),
            t("n:s1#m@alice"),
            t("n:obj#r@bob"),
        )
        _, eng = placed(store)
        reqs = [t("n:obj#r@alice"), t("n:obj#r@bob"), t("n:obj#r@eve")]
        assert eng.batch_check(reqs, depths=[1, 1, 5]) == [False, True, False]
        assert eng.batch_check(reqs, depths=[2, 1, 5]) == [True, True, False]

    def test_exact_depth_boundary(self, store, placed):
        """A path of length d is allowed at depth d and denied at d-1,
        with the request's depth equal to the global maximum."""
        store.write_relation_tuples(t("n:a#r@(n:b#r)"), t("n:b#r@u"))
        _, eng = placed(store, max_depth=2)
        assert eng.subject_is_allowed(t("n:a#r@u"), max_depth=2)
        assert not eng.subject_is_allowed(t("n:a#r@u"), max_depth=1)

    def test_start_equals_target_needs_real_path(self, store, placed):
        """set@same-set is allowed only through an actual cycle: the start
        itself, at distance 0, does not satisfy the check."""
        store.write_relation_tuples(
            t("n:obj#r@alice"), t("n:obj#r@(n:grp#m)"), t("n:grp#m@bob")
        )
        _, eng = placed(store)
        assert not eng.subject_is_allowed(t("n:obj#r@(n:obj#r)"))
        assert not eng.subject_is_allowed(t("n:grp#m@(n:grp#m)"))
        store.write_relation_tuples(t("n:grp#m@(n:obj#r)"))
        assert eng.subject_is_allowed(t("n:grp#m@(n:grp#m)"))


def _random_requests(rng, n_objects, n_users, k=64):
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


class TestClosureMatchesOracle:
    @pytest.mark.parametrize("seed", range(6))
    def test_random_graphs(self, seed, placed):
        rng = np.random.default_rng(seed)
        store = random_store(rng, n_objects=15, n_users=10, n_edges=120)
        for depth in (1, 2, 3, 5, 8):
            host, eng = placed(store, max_depth=depth)
            reqs = _random_requests(rng, 15, 10)
            expect = [host.subject_is_allowed(r) for r in reqs]
            got = eng.batch_check(reqs)
            assert got == expect, f"seed={seed} depth={depth}"

    @pytest.mark.parametrize("seed", range(3))
    def test_random_graphs_per_request_depths(self, seed, placed):
        rng = np.random.default_rng(seed + 50)
        store = random_store(rng, n_objects=12, n_users=8, n_edges=90)
        host, eng = placed(store, max_depth=8)
        reqs = _random_requests(rng, 12, 8)
        depths = [int(rng.integers(1, 9)) for _ in reqs]
        expect = [
            host.subject_is_allowed(r, max_depth=d)
            for r, d in zip(reqs, depths)
        ]
        assert eng.batch_check(reqs, depths=depths) == expect

    @pytest.mark.parametrize("seed", range(2))
    def test_overflow_rows_fall_back_exactly(self, seed):
        """Tiny F0/L widths force the overflow path; answers stay exact."""
        rng = np.random.default_rng(seed + 200)
        store = random_store(rng, n_objects=10, n_users=6, n_edges=100)
        host, eng = make_engines(store, max_depth=5, f0_max=1, l_max=1)
        reqs = _random_requests(rng, 10, 6)
        expect = [host.subject_is_allowed(r) for r in reqs]
        assert eng.batch_check(reqs) == expect

    def test_interior_limit_falls_back_whole_batch(self):
        rng = np.random.default_rng(7)
        store = random_store(rng, n_objects=10, n_users=6, n_edges=80)
        host, eng = make_engines(store, max_depth=5, interior_limit=2)
        reqs = _random_requests(rng, 10, 6)
        expect = [host.subject_is_allowed(r) for r in reqs]
        assert eng.batch_check(reqs) == expect


class TestCheckIds:
    @pytest.mark.parametrize("interior_limit", [16384, 2])
    def test_array_api_matches_object_api(self, interior_limit):
        rng = np.random.default_rng(11)
        store = random_store(rng, n_objects=12, n_users=8, n_edges=100)
        host, eng = make_engines(
            store, max_depth=5, interior_limit=interior_limit
        )
        reqs = _random_requests(rng, 12, 8)
        snap = eng.snapshots.snapshot()
        start = np.array(
            [
                snap.node_for_set(r.namespace, r.object, r.relation)
                for r in reqs
            ],
            dtype=np.int64,
        )
        target = np.array(
            [snap.node_for_subject(r.subject) for r in reqs], dtype=np.int64
        )
        from keto_tpu.relationtuple import SubjectID
        is_id = np.array(
            [isinstance(r.subject, SubjectID) for r in reqs]
        )
        expect = [host.subject_is_allowed(r) for r in reqs]
        got = eng.check_ids(start, target, is_id)
        assert got.tolist() == expect

    def test_empty_batch(self):
        rng = np.random.default_rng(13)
        store = random_store(rng, n_objects=6, n_users=4, n_edges=30)
        _, eng = make_engines(store, max_depth=5)
        assert eng.batch_check([]) == []
        got = eng.check_ids(
            np.empty(0, np.int64), np.empty(0, np.int64), np.empty(0, bool)
        )
        assert got.tolist() == []

    def test_huge_max_depth_stays_exact(self):
        """max_depth beyond the uint8 distance range must not produce
        spurious allows (the INF sentinel would collide at depth >= 256)."""
        store = InMemoryTupleStore()
        store.write_relation_tuples(
            RelationTuple.from_string("n:a#r@(n:b#r)"),
            RelationTuple.from_string("n:x#r@(n:y#r)"),
        )
        host, eng = make_engines(store, max_depth=256)
        req = RelationTuple.from_string("n:a#r@(n:y#r)")
        assert host.subject_is_allowed(req) is False
        assert eng.subject_is_allowed(req) is False
        # a depth-255 engine still uses the closure and stays exact
        host2, eng2 = make_engines(store, max_depth=255)
        assert eng2.subject_is_allowed(req) is False
        assert eng2.subject_is_allowed(
            RelationTuple.from_string("n:a#r@(n:b#r)")
        )

    def test_unknown_ids_denied(self):
        rng = np.random.default_rng(12)
        store = random_store(rng, n_objects=6, n_users=4, n_edges=30)
        _, eng = make_engines(store, max_depth=5, interior_limit=2)
        snap = eng.snapshots.snapshot()
        dummy = snap.dummy_node
        got = eng.check_ids(
            np.array([dummy]), np.array([dummy]), np.array([True])
        )
        assert got.tolist() == [False]


class TestInteriorGraph:
    def test_decomposition_shape(self, store):
        store.write_relation_tuples(
            t("n:doc#view@(n:doc#own)"),   # doc#own interior
            t("n:doc#own@(n:team#m)"),     # team#m interior
            t("n:team#m@alice"),           # id sink
            t("n:lonely#r@bob"),           # lonely#r has no in-edges
        )
        snap = SnapshotManager(store).snapshot()
        ig = build_interior(snap)
        assert ig.m == 2  # doc#own, team#m
        interior_nodes = {
            snap.vocab.key(int(i)) for i in ig.interior_ids
        }
        assert interior_nodes == {("n", "doc", "own"), ("n", "team", "m")}
        # direct edge test
        s = snap.node_for_set("n", "team", "m")
        a = snap.vocab.lookup(("alice",))
        assert ig.direct_edge(
            np.array([s], dtype=np.int64), np.array([a], dtype=np.int64)
        ).tolist() == [True]

    def test_wildcard_subject_is_plain_id(self, store):
        # the cat-videos '*' convention: a literal id, nothing special
        store.write_relation_tuples(t("v:/cats/1#view@*"))
        _, eng = make_engines(store)
        assert eng.subject_is_allowed(t("v:/cats/1#view@*"))
        assert not eng.subject_is_allowed(t("v:/cats/2#view@*"))


class TestDeviceView:
    """device_view() serves the same resident closure with
    query_mode=device — answers must match the host path bit-for-bit
    (the bench's device leg rests on this parity)."""

    def test_parity_on_random_graphs(self):
        rng = np.random.default_rng(42)
        for seed in range(3):
            store = random_store(np.random.default_rng(seed), 20, 10, 200)
            oracle, eng = make_engines(store, query_mode="host")
            dview = eng.device_view()
            reqs = store.all_tuples()[:64]
            # mix hits with misses
            reqs += [t(f"miss:obj{i}#rel@nobody{i}") for i in range(16)]
            rng.shuffle(reqs)
            want = eng.batch_check(reqs)
            got = dview.batch_check(reqs)
            assert got == want, f"seed {seed}"
            assert want == oracle.batch_check(reqs), f"seed {seed} vs oracle"

    def test_device_view_requires_resident_closure(self):
        store = InMemoryTupleStore()
        # a real interior node (subject-set indirection) with
        # interior_limit=0 forces the _TooBig fallback state
        store.write_relation_tuples(
            t("n:o#r@(n:g#m)"), t("n:g#m@alice")
        )
        _, eng = make_engines(store, interior_limit=0)
        eng.subject_is_allowed(t("n:o#r@alice"))  # forces _TooBig state
        with pytest.raises(RuntimeError):
            eng.device_view()


class TestPlacementFollowsPlatform:
    """query_mode=auto decides from the platform alone: device queries on
    an accelerator, host queries when the backend is the CPU. No probe."""

    @pytest.mark.parametrize(
        "platform, host", [("cpu", True), ("tpu", False), ("gpu", False)]
    )
    def test_auto_mode(self, store, monkeypatch, platform, host):
        import jax

        monkeypatch.setattr(jax, "default_backend", lambda: platform)
        _, eng = make_engines(store, query_mode="auto")
        assert eng.host_queries() is host

    @pytest.mark.parametrize("mode, host", [("host", True), ("device", False)])
    def test_explicit_mode_ignores_platform(self, store, monkeypatch, mode, host):
        import jax

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        _, eng = make_engines(store, query_mode=mode)
        assert eng.host_queries() is host

    @pytest.mark.parametrize(
        "mode, kernel", [("host", "host-bitset"), ("device", "lax")]
    )
    def test_full_build_records_kernel_and_rows(self, store, mode, kernel):
        store.write_relation_tuples(
            t("n:o#r@(n:g#m)"), t("n:g#m@(n:h#m)"), t("n:h#m@alice")
        )
        oracle, eng = make_engines(store, query_mode=mode)
        reqs = [t("n:o#r@alice"), t("n:o#r@bob")]
        assert eng.batch_check(reqs) == oracle.batch_check(reqs) == [
            True, False,
        ]
        assert eng.last_full_build_kernel == kernel
        assert eng.n_oracle_rows == 0
        assert eng.n_device_rows == (2 if mode == "device" else 0)


class TestPallasStepNeverDemotes:
    """On a TPU the semiring builder runs the Pallas step; a Pallas failure
    raises instead of silently switching to the lax step."""

    def test_step_follows_platform(self, monkeypatch):
        import jax

        from keto_tpu.engine import pallas_spmv

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert pallas_spmv.semiring_step() == "pallas"
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        assert pallas_spmv.semiring_step() == "lax"

    def test_pallas_failure_raises(self, monkeypatch):
        from keto_tpu.engine import pallas_spmv

        calls = []

        def broken(*a, use_pallas, **kw):
            calls.append(use_pallas)
            raise RuntimeError("mosaic lowering failed")

        monkeypatch.setattr(pallas_spmv, "semiring_step", lambda: "pallas")
        monkeypatch.setattr(pallas_spmv, "_build_closure_semiring", broken)
        packed = np.zeros((256, 32), np.uint8)
        with pytest.raises(RuntimeError, match="mosaic"):
            pallas_spmv.build_closure_semiring(packed, 3, m_pad=256, k_max=4)
        with pytest.raises(RuntimeError, match="mosaic"):
            pallas_spmv.build_closure_semiring(packed, 3, m_pad=256, k_max=4)
        assert calls == [True, True]  # never retried on the lax step

    def test_misaligned_width_raises_on_tpu(self, monkeypatch):
        from keto_tpu.engine import pallas_spmv

        monkeypatch.setattr(pallas_spmv, "semiring_step", lambda: "pallas")
        with pytest.raises(ValueError, match="Pallas row tile"):
            pallas_spmv.build_closure_semiring(
                np.zeros((72, 9), np.uint8), 3, m_pad=72, k_max=4
            )


class TestDeviceQueryHandOff:
    """Device queries hand the host operands straight to the jitted
    ``closure_query``: no Python-level copy to the device per dispatch."""

    # alice is a member of more groups than the narrow gather width, so
    # her rows take the wide fan-out class; the rest stay narrow. Twenty
    # groups gather at width 32, so a large batch of narrow rows with one
    # of hers saves far more by splitting than a launch is worth
    _WIDE = 20

    def _store(self):
        store = InMemoryTupleStore()
        rows = [f"groups:g{i}#member@alice" for i in range(self._WIDE)]
        rows += [
            f"docs:d0#view@(groups:g{i}#member)" for i in range(self._WIDE)
        ]
        rows += [
            "groups:g0#member@bob",
            "groups:g1#member@(groups:g2#member)",
            "docs:d1#view@carol",
            "docs:d2#view@(groups:g1#member)",
        ]
        store.write_relation_tuples(*(t(r) for r in rows))
        return store

    _MIXED = (
        "docs:d0#view@alice",  # wide
        "docs:d0#view@bob",  # wide: d0 names twenty groups
        "docs:d1#view@carol",
        "docs:d2#view@alice",  # wide: via g1, one of twenty
        "docs:d2#view@bob",
        "docs:d2#view@(groups:g2#member)",
        "docs:d1#view@nobody",
        "docs:d0#view@(groups:g0#member)",  # wide
    )
    _NARROW = (
        "docs:d1#view@carol",
        "docs:d2#view@bob",
        "docs:d2#view@(groups:g2#member)",
        "docs:d1#view@nobody",
    )

    @pytest.mark.parametrize(
        "case, parts",
        [
            # eight rows: one launch costs more than the narrow rows save
            ("device", 1),
            # the numpy twin launches nothing: split wherever it saves
            ("host", 2),
            # 4,095 narrow rows and one hot one: the split saves ~4M
            # gathered elements of D
            ("device_4096", 2),
        ],
    )
    def test_mixed_fan_out_batch_without_python_copies(
        self, monkeypatch, case, parts
    ):
        import jax
        import jax.numpy as jnp

        from keto_tpu import native
        from keto_tpu.relationtuple.columns import CheckColumns
        from keto_tpu.telemetry.devstats import DEVSTATS

        store = self._store()
        device = case != "host"
        if not device:
            # keep the fused C path off the host query: the numpy twin
            # takes the batch
            monkeypatch.setattr(native, "lib", None)
        oracle, eng = make_engines(
            store, query_mode="device" if device else "host"
        )
        if case == "device_4096":
            narrow = [t(r) for r in self._NARROW]
            reqs = [narrow[i % len(narrow)] for i in range(4095)]
            reqs.append(t("docs:d0#view@alice"))
        else:
            reqs = [t(r) for r in self._MIXED]
        cols = CheckColumns.from_tuples(reqs).validate()
        want = oracle.batch_check(reqs)
        assert any(want) and not all(want)
        # warm: every part's shape compiles here
        assert eng.batch_check_columns(cols) == want

        def no_copy(*a, **kw):
            raise AssertionError("operand copied through Python")

        monkeypatch.setattr(jnp, "asarray", no_copy)
        monkeypatch.setattr(jax, "device_put", no_copy)
        queried = []
        query_rows = eng._query_rows

        def spy(art, ig, start, *rest):
            queried.append(len(start))
            return query_rows(art, ig, start, *rest)

        monkeypatch.setattr(eng, "_query_rows", spy)
        before = DEVSTATS.panel()["dispatches"]
        assert eng.batch_check_columns(cols) == want
        assert len(queried) == parts and sum(queried) == len(reqs)
        # one dispatch per part on the device, none on the host twin
        assert DEVSTATS.panel()["dispatches"] - before == (
            parts if device else 0
        )
        assert eng.n_oracle_rows == 0

    def test_query_program_keeps_its_index_parameters(self):
        """The served shape's compiled program still declares ``f0`` and
        ``l`` as their own ``s32[B, W]`` parameters, which the roofline
        reader finds by name in the device trace's op text."""
        import re

        from keto_tpu.ops.closure import closure_query

        b, w = 128, 8
        text = closure_query.lower(
            np.zeros((256, 256), np.uint8),
            np.zeros((b, w), np.int32),
            np.zeros((b, w), np.int32),
            np.zeros(b, np.int32),
            np.ones(b, np.int32),
            np.zeros(b, bool),
        ).compile().as_text()
        for name, pos in (("f0", 1), ("l", 2)):
            assert re.search(
                rf"%{name}\.\d+ = s32\[{b},{w}\]\{{[^}}]*\}} "
                rf"parameter\({pos}\)",
                text,
            ), name


class TestFanoutParts:
    """A batch holding both fan-out classes is queried as one part unless
    splitting it saves more gathered elements of D than a launch is
    worth; either plan answers alike."""

    # sixteen rows: twelve narrow (F0 and L of 8), four wide (F0 of 32, L
    # of 2). Whole: 16 x 32 x 8 = 4,096 gathered elements; split, padded
    # to the device buckets: 16 x 8 x 8 + 8 x 32 x 2 = 1,536; unpadded, as
    # on the host twin: 12 x 64 + 4 x 64 = 1,024
    @pytest.mark.parametrize("padded, saved", [(True, 2560), (False, 3072)])
    def test_one_part_up_to_the_launch_worth(self, padded, saved):
        from keto_tpu.engine.closure import _NARROW_WIDTH, _fanout_parts

        f0_deg = np.array([8] * 12 + [32] * 4)
        l_deg = np.array([8] * 12 + [2] * 4)
        narrow = (f0_deg <= _NARROW_WIDTH) & (l_deg <= _NARROW_WIDTH)

        def plan(launch_elems):
            return _fanout_parts(
                narrow, f0_deg, l_deg, launch_elems, padded, 32, 32
            )

        (whole,) = plan(saved)
        assert whole.tolist() == list(range(16))
        parts = plan(saved - 1)
        assert [p.tolist() for p in parts] == [
            list(range(12)), list(range(12, 16))
        ]

    @staticmethod
    def _hub_store(rng):
        """A random graph plus users in 9 to 40 groups and sets naming 9 to
        40 subject sets: rows of both classes, and some past the 32-wide
        gather (the oracle answers those)."""
        store = random_store(rng, n_objects=50, n_users=20, n_edges=150)
        rows = set()
        for h, k in enumerate((9, 12, 20, 40)):
            for j in rng.choice(50, size=k, replace=False):
                rows.add(f"n:o{j}#r1@hub{h}")
                rows.add(f"n:o{h}#r0@(n:o{j}#r1)")
        store.write_relation_tuples(*(t(r) for r in rows))
        return store

    @pytest.mark.parametrize("seed", range(2))
    def test_one_part_and_split_answer_alike(self, monkeypatch, seed):
        from keto_tpu.engine import closure
        from keto_tpu.telemetry import MetricsRegistry

        rng = np.random.default_rng(seed + 300)
        store = self._hub_store(rng)
        reqs = _random_requests(rng, 50, 20, k=100)
        reqs += [
            t(f"n:o{rng.integers(4)}#r0@hub{rng.integers(4)}")
            for _ in range(28)
        ]
        oracle = CheckEngine(store, max_depth=5)
        want = [oracle.subject_is_allowed(r) for r in reqs]
        assert any(want) and not all(want)
        answers, oracle_rows = {}, {}
        for plan, launch_elems in (("one", 1 << 40), ("split", -(1 << 40))):
            monkeypatch.setattr(closure, "_LAUNCH_ELEMS", launch_elems)
            metrics = MetricsRegistry()
            eng = ClosureCheckEngine(
                SnapshotManager(store), max_depth=5, query_mode="device",
                metrics=metrics,
            )
            answers[plan] = eng.batch_check(reqs)
            oracle_rows[plan] = eng.n_oracle_rows
            assert (
                f'keto_closure_fanout_batches_total{{plan="{plan}"}} 1.0'
                in metrics.expose()
            )
        assert answers["one"] == answers["split"] == want
        assert oracle_rows["one"] == oracle_rows["split"] > 0
