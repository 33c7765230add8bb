"""The interior walk: a snapshot whose interior exceeds
``engine.interior_limit`` holds the interior's CSR instead of a dense
closure, and every batch walks it (ops/closure.py interior_walk on the
device, interior_walk_host for host queries). Every answer must equal the
host oracle (CheckEngine) and the benchmark's plain reference
(benchmark/reference.py) on Drive-shaped graphs: documents in nested
folders, view inherited from the parent folder, shares to users and to
nested groups."""

import numpy as np
import pytest

from benchmark.generators import drive
from benchmark.harness import streams
from benchmark.load import pair_pool
from benchmark.reference import Reference
from keto_tpu.engine import CheckEngine
from keto_tpu.engine import closure as closure_mod
from keto_tpu.engine.closure import (
    ClosureCheckEngine,
    _ClosureArtifacts,
    _WalkArtifacts,
)
from keto_tpu.graph import SnapshotManager
from keto_tpu.ops.closure import interior_walk, interior_walk_host
from keto_tpu.relationtuple import RelationTuple, SubjectID, SubjectSet
from keto_tpu.store import ColumnarTupleStore, InMemoryTupleStore

MODES = ["device", "host"]

SIZES = dict(
    namespace="drive", group_namespace="group", tuples=3000, users=150,
    docs=600, folders=160, folder_levels=8, groups=30, group_levels=3,
    group_members=450, group_nesting=20, share_folder=0.5,
    share_to_group=0.3,
)


def t(s: str) -> RelationTuple:
    return RelationTuple.from_string(s)


def drive_store(seed):
    g = drive.build(SIZES, streams(seed)[0])
    store = ColumnarTupleStore()
    store.bulk_load_edges(g.keys(g.src), g.keys(g.dst))
    return g, store


def engine(store, mode, max_depth=12, **kw):
    kw.setdefault("interior_limit", 16)
    return ClosureCheckEngine(
        SnapshotManager(store), max_depth=max_depth, query_mode=mode, **kw
    )


def requests(g, objs, subs):
    out = []
    for (ns, obj, rel), sub in zip(g.keys(objs), g.keys(subs)):
        subject = (
            SubjectID(id=sub[0]) if len(sub) == 1 else SubjectSet(*sub)
        )
        out.append(RelationTuple(ns, obj, rel, subject))
    return out


class TestWalkMatchesOracleAndReference:
    @pytest.mark.parametrize("mode", MODES)
    @pytest.mark.parametrize("seed", range(3))
    def test_drive_rows(self, mode, seed):
        g, store = drive_store(seed)
        rng = np.random.default_rng(seed)
        s, u = pair_pool(g, rng, 256, 0.5, 12)
        # set targets: a document's view against folders and groups
        sets = np.concatenate([
            g.kind("folder_view").start
            + rng.integers(g.kind("folder_view").count, size=32),
            g.kind("group").start + rng.integers(g.kind("group").count,
                                                 size=32),
        ])
        s = np.concatenate([s, g.kind("doc_view").start
                            + rng.integers(g.kind("doc_view").count,
                                           size=64)])
        u = np.concatenate([u, sets])
        reqs = requests(g, s, u)
        ref = Reference(g, 12)
        want = [ref.check(int(a), int(b)) for a, b in zip(s, u)]
        assert CheckEngine(store, max_depth=12).batch_check(reqs) == want
        eng = engine(store, mode)
        assert eng.batch_check(reqs) == want
        assert isinstance(eng._state, _WalkArtifacts)
        assert eng._state.ig.m > eng.interior_limit
        assert eng.n_oracle_rows == 0  # nothing overflowed
        assert 0 < sum(want) < len(want)
        assert any(want[-64:])  # some set target allowed

    @pytest.mark.parametrize("mode", MODES)
    def test_per_request_depths(self, mode):
        g, store = drive_store(3)
        rng = np.random.default_rng(3)
        s, u = pair_pool(g, rng, 128, 1.0, 12)
        reqs = requests(g, s, u)
        depths = [int(d) for d in rng.integers(0, 14, size=len(reqs))]
        oracle = CheckEngine(store, max_depth=12)
        want = oracle.batch_check(reqs, depths=depths)
        assert engine(store, mode).batch_check(reqs, depths=depths) == want
        assert 0 < sum(want) < len(want)


def chain_store(folders=8, groups=3, extra_groups=0):
    """doc d in the deepest of a ``folders``-long folder chain, a share
    on the root to a group nested ``groups`` deep, alice in the
    innermost: a path of 1 + (folders - 1) + 1 + (groups - 1) + 1
    tuples."""
    store = InMemoryTupleStore()
    tup = [t(f"drive:d#view@(drive:f{folders - 1}#view)")]
    for i in range(folders - 1, 0, -1):
        tup.append(t(f"drive:f{i}#view@(drive:f{i - 1}#view)"))
    tup.append(t("drive:f0#view@(group:g0#member)"))
    for i in range(groups - 1):
        tup.append(t(f"group:g{i}#member@(group:g{i + 1}#member)"))
    tup.append(t(f"group:g{groups - 1}#member@alice"))
    for i in range(extra_groups):
        tup.append(t(f"group:x{i}#member@(group:x{i + 1}#member)"))
    store.write_relation_tuples(*tup)
    return store


class TestDepthBoundary:
    @pytest.mark.parametrize("mode", MODES)
    def test_path_of_exactly_max_depth(self, mode):
        store = chain_store()  # 12 tuples
        host = CheckEngine(store, max_depth=12)
        eng = engine(store, mode, interior_limit=2)
        req = t("drive:d#view@alice")
        assert host.subject_is_allowed(req) is True
        assert eng.subject_is_allowed(req) is True
        assert isinstance(eng._state, _WalkArtifacts)
        # a request's own depth below the path denies
        assert eng.subject_is_allowed(req, max_depth=11) is False
        assert eng.subject_is_allowed(req, max_depth=12) is True

    @pytest.mark.parametrize("mode", MODES)
    def test_path_of_max_depth_plus_one(self, mode):
        store = chain_store(folders=9)  # 13 tuples
        host = CheckEngine(store, max_depth=12)
        eng = engine(store, mode, interior_limit=2)
        req = t("drive:d#view@alice")
        assert host.subject_is_allowed(req) is False
        assert eng.subject_is_allowed(req) is False
        # the set one tuple short of alice is at depth 12
        inner = t("drive:d#view@(group:g2#member)")
        assert host.subject_is_allowed(inner) is True
        assert eng.subject_is_allowed(inner) is True
        assert eng.n_oracle_rows == 0


class TestCyclesTargetsUnknowns:
    def cyclic(self):
        store = InMemoryTupleStore()
        store.write_relation_tuples(
            t("drive:d#view@(drive:f#view)"),
            t("drive:f#view@(group:a#member)"),
            t("group:a#member@(group:b#member)"),
            t("group:b#member@(group:c#member)"),
            t("group:c#member@(group:a#member)"),
            t("group:c#member@carol"),
            t("drive:e#view@(group:b#member)"),
            t("drive:e#view@bob"),
        )
        return store

    @pytest.mark.parametrize("mode", MODES)
    def test_group_cycles_terminate_and_stay_exact(self, mode):
        store = self.cyclic()
        host = CheckEngine(store, max_depth=12)
        eng = engine(store, mode, interior_limit=2)
        reqs = [
            t("drive:d#view@carol"),
            t("drive:d#view@mallory"),
            t("drive:e#view@carol"),
            t("drive:e#view@bob"),
            t("drive:d#view@(group:c#member)"),
            t("drive:e#view@(group:a#member)"),
            t("group:a#member@(group:a#member)"),
            t("group:b#member@(drive:f#view)"),
        ]
        want = host.batch_check(reqs)
        assert want == [True, False, True, True, True, True, True, False]
        assert eng.batch_check(reqs) == want
        assert isinstance(eng._state, _WalkArtifacts)
        assert eng.batch_check(reqs, max_depth=3) == host.batch_check(
            reqs, max_depth=3
        )

    @pytest.mark.parametrize("mode", MODES)
    def test_unknown_ids_denied(self, mode):
        store = self.cyclic()
        host = CheckEngine(store, max_depth=12)
        eng = engine(store, mode, interior_limit=2)
        reqs = [
            t("drive:nowhere#view@carol"),
            t("drive:d#view@nobody"),
            t("nons:d#view@carol"),
            t("drive:d#view@(group:none#member)"),
        ]
        assert host.batch_check(reqs) == [False] * 4
        assert eng.batch_check(reqs) == [False] * 4
        snap = eng.snapshots.snapshot()
        dummy = snap.dummy_node
        got = eng.check_ids(
            np.array([dummy, -1]), np.array([dummy, -1]),
            np.array([True, True]),
        )
        assert got.tolist() == [False, False]


class TestOverflowGoesToTheOracle:
    @pytest.mark.parametrize("mode", MODES)
    def test_narrow_walk_rows_answered_by_oracle(self, mode, monkeypatch):
        monkeypatch.setattr(closure_mod, "_WALK_WIDTH", 1)
        monkeypatch.setattr(closure_mod, "_WALK_WIDE_MAX", 1)  # no rewalk
        g, store = drive_store(4)
        s, u = pair_pool(g, np.random.default_rng(4), 256, 0.5, 12)
        reqs = requests(g, s, u)
        ref = Reference(g, 12)
        want = [ref.check(int(a), int(b)) for a, b in zip(s, u)]
        eng = engine(store, mode, f0_max=1)
        assert eng.batch_check(reqs) == want
        # some rows overflowed and the oracle answered them, not all
        assert 0 < eng.n_oracle_rows < len(reqs)

    @pytest.mark.parametrize("mode", MODES)
    def test_narrow_walk_rows_walked_again_wider_on_the_host(
        self, mode, monkeypatch
    ):
        """Rows that overflow a one-wide walk are walked again on the host
        at their whole F0 and L rows and a wider frontier: exact, and the
        oracle (a store scan per visited set) answers none of them."""
        monkeypatch.setattr(closure_mod, "_WALK_WIDTH", 1)
        g, store = drive_store(4)
        s, u = pair_pool(g, np.random.default_rng(4), 256, 0.5, 12)
        reqs = requests(g, s, u)
        ref = Reference(g, 12)
        want = [ref.check(int(a), int(b)) for a, b in zip(s, u)]
        eng = engine(store, mode, f0_max=1)
        wide = []
        real = closure_mod.interior_walk_host

        def spy(*a, **kw):
            wide.append(kw["width"])
            return real(*a, **kw)

        monkeypatch.setattr(closure_mod, "interior_walk_host", spy)
        assert eng.batch_check(reqs) == want
        assert eng.n_oracle_rows == 0
        assert max(wide) >= 8  # the rewalk ran, eight times wider

    @pytest.mark.parametrize("mode", MODES)
    def test_frontier_past_its_width_goes_to_the_oracle(
        self, mode, monkeypatch
    ):
        """F0 is one folder, whose view is shared with six groups: the
        hop-1 frontier outgrows a width of 2 and the row must go to the
        oracle, never be denied from the truncated frontier."""
        store = InMemoryTupleStore()
        store.write_relation_tuples(
            t("drive:d#view@(drive:f#view)"),
            *(t(f"drive:f#view@(group:g{i}#member)") for i in range(6)),
            *(t(f"group:g{i}#member@u{i}") for i in range(6)),
        )
        host = CheckEngine(store, max_depth=12)
        monkeypatch.setattr(closure_mod, "_WALK_WIDTH", 2)
        reqs = [t(f"drive:d#view@u{i}") for i in range(6)]
        reqs.append(t("drive:d#view@nobody"))
        assert host.batch_check(reqs) == [True] * 6 + [False]
        # the host's wider walk answers the rows exactly
        eng = engine(store, mode, interior_limit=2, f0_max=1)
        assert eng.batch_check(reqs) == [True] * 6 + [False]
        assert eng.n_oracle_rows == 0
        # and with no room to widen, the oracle does
        monkeypatch.setattr(closure_mod, "_WALK_WIDE_MAX", 2)
        eng = engine(store, mode, interior_limit=2, f0_max=1)
        assert eng.batch_check(reqs) == [True] * 6 + [False]
        assert eng.n_oracle_rows > 0

    def test_kernel_and_host_twin_agree_on_overflow(self):
        rng = np.random.default_rng(0)
        m, rows = 40, 48
        src = rng.integers(m, size=160)
        dst = rng.integers(m, size=160)
        order = np.argsort(src, kind="stable")
        csr = np.zeros(rows + 1 + len(src), np.int32)
        np.cumsum(np.bincount(src, minlength=rows), out=csr[1 : rows + 1])
        csr[rows + 1 :] = dst[order]
        pad = rows - 1
        b = 64
        f0 = np.where(rng.random((b, 4)) < 0.5,
                      rng.integers(m, size=(b, 4)), pad).astype(np.int32)
        l = np.where(rng.random((b, 4)) < 0.5,
                     rng.integers(m, size=(b, 4)), pad).astype(np.int32)
        extra = rng.integers(2, size=b).astype(np.int32)
        depth = rng.integers(1, 8, size=b).astype(np.int32)
        direct = rng.random(b) < 0.05
        for width, fanout in ((4, 8), (16, 64)):
            kw = dict(rows=rows, hops=6, width=width, fanout=fanout)
            dev = np.asarray(
                interior_walk(csr, f0, l, extra, depth, direct, **kw)
            )
            host = interior_walk_host(csr, f0, l, extra, depth, direct,
                                      **kw)
            assert np.array_equal(dev, host)
            assert not np.any(dev[0] & dev[1])
            if width == 4:
                assert dev[1].any()
        assert dev[0].any() and (~dev[0]).any()


class TestReadYourWrite:
    @pytest.mark.parametrize("mode", MODES)
    @pytest.mark.parametrize("freshness", ["strong", "bounded"])
    def test_write_then_check_with_its_snaptoken(self, mode, freshness):
        store = chain_store(extra_groups=20)
        eng = engine(store, mode, interior_limit=2, freshness=freshness,
                     rebuild_debounce_s=0)
        req = t("drive:d#view@dave")
        assert eng.subject_is_allowed(req) is False
        store.write_relation_tuples(t("group:g1#member@dave"))
        # the write's snaptoken: the store version after it
        eng.wait_for_version(store.version, timeout_s=30)
        assert eng.subject_is_allowed(req) is True
        assert eng.served_version() >= store.version
        assert isinstance(eng._state, _WalkArtifacts)
        store.delete_relation_tuples(t("group:g1#member@dave"))
        eng.wait_for_version(store.version, timeout_s=30)
        assert eng.subject_is_allowed(req) is False

    @pytest.mark.parametrize("mode", MODES)
    def test_strong_check_after_a_write_waits_on_no_rebuild(
        self, mode, monkeypatch
    ):
        """A walk state has no write overlay: under strong freshness the
        checks after a write read the live store through the oracle while
        the walk rebuilds in the background, then the walk answers again."""
        store = chain_store(extra_groups=20)
        eng = engine(store, mode, interior_limit=2, freshness="strong")
        req = t("drive:d#view@dave")
        assert eng.subject_is_allowed(req) is False
        walk = eng._state
        kicks = []
        monkeypatch.setattr(eng, "_kick_rebuild", lambda: kicks.append(1))
        store.write_relation_tuples(t("group:g1#member@dave"))
        oracle = eng.n_oracle_rows
        assert eng.subject_is_allowed(req) is True
        assert eng._state is walk and kicks  # rebuild kicked, not awaited
        assert eng.n_oracle_rows == oracle + 1
        assert eng.served_version() == store.version
        assert eng.answering_version() == store.version
        eng._build_sync()  # what the background rebuild runs
        assert isinstance(eng._state, _WalkArtifacts) and eng._state is not walk
        assert eng.subject_is_allowed(req) is True
        assert eng.n_oracle_rows == oracle + 1


class TestUnderTheLimitKeepsTheClosure:
    def test_small_interior_builds_d_and_queries_it(self, monkeypatch):
        calls = {"query": 0}
        real = closure_mod.closure_query

        def spy(*a, **kw):
            calls["query"] += 1
            return real(*a, **kw)

        def refuse(*a, **kw):
            raise AssertionError("walk under the closure limit")

        monkeypatch.setattr(closure_mod, "closure_query", spy)
        monkeypatch.setattr(closure_mod, "interior_walk", refuse)
        monkeypatch.setattr(closure_mod, "interior_walk_host", refuse)
        store = chain_store()
        host = CheckEngine(store, max_depth=12)
        eng = engine(store, "device", interior_limit=16384)
        req = t("drive:d#view@alice")
        assert eng.subject_is_allowed(req) is host.subject_is_allowed(req)
        assert isinstance(eng._state, _ClosureArtifacts)
        assert eng._state.d is not None
        assert calls["query"] == 1
        assert "walk" not in eng.last_build_phases


class TestWalkShapes:
    def test_shapes_depend_on_the_batch_bucket_only(self):
        """Rows of any fan-out gather at the fixed widths: one program
        per batch bucket, and one dispatch per batch."""
        g, store = drive_store(5)
        rng = np.random.default_rng(5)
        eng = engine(store, "device")
        seen = []
        real = closure_mod.interior_walk

        def spy(csr, f0, l, *a, **kw):
            seen.append((f0.shape, l.shape))
            return real(csr, f0, l, *a, **kw)

        closure_mod.interior_walk = spy
        try:
            for k in (3, 8, 20, 100):
                s, u = pair_pool(g, rng, k, 0.5, 12)
                eng.batch_check(requests(g, s, u))
        finally:
            closure_mod.interior_walk = real
        assert [sh for sh, _ in seen] == [
            (8, eng.f0_max), (8, eng.f0_max), (32, eng.f0_max),
            (128, eng.f0_max),
        ]
        assert {sh for _, sh in seen} == {(8, eng.l_max), (32, eng.l_max),
                                          (128, eng.l_max)}
