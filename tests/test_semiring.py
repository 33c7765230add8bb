"""Semiring closure builder parity + incremental correctness.

The contract (keto_tpu/engine/semiring.py): the bitset masked-SpMV builder
and the incremental dirty-row updater produce byte-identical uint8 closure
matrices to the legacy dense-matmul builder (ops.closure.build_closure_packed)
on every graph — cycles, unicode vocab, padding, arbitrary insert/delete
deltas, and snapshot-overlay rebuilds mid-serve included.
"""

import numpy as np
import pytest

from keto_tpu.engine.closure import ClosureCheckEngine
from keto_tpu.engine.semiring import (
    build_closure_bitset,
    interior_edge_delta,
    update_closure_bitset,
)
from keto_tpu.graph import SnapshotManager
from keto_tpu.graph.interior import build_interior, interior_blocks
from keto_tpu.ops.closure import build_closure_packed, pack_adjacency
from keto_tpu.relationtuple import RelationTuple, SubjectSet
from keto_tpu.store import InMemoryTupleStore

from test_closure_engine import _random_requests
from conftest import random_store


def t(s: str) -> RelationTuple:
    return RelationTuple.from_string(s)


def _m_pad(m):
    return ((m + 255) // 256) * 256


def _rand_edges(rng, m, n_edges):
    src = rng.integers(0, m, n_edges, dtype=np.int32)
    dst = rng.integers(0, m, n_edges, dtype=np.int32)
    return src, dst


def _oracle(src, dst, m, m_pad, k_max):
    packed = pack_adjacency(src, dst, m_pad)
    return np.asarray(build_closure_packed(packed, m, m_pad=m_pad, k_max=k_max))


class TestBitsetParity:
    def test_matches_matmul_on_random_graphs(self):
        rng = np.random.default_rng(3)
        for _ in range(25):
            m = int(rng.integers(0, 60))
            m_pad = _m_pad(m)
            n_edges = int(rng.integers(0, 4 * max(m, 1)))
            src, dst = _rand_edges(rng, max(m, 1), n_edges)
            if m == 0:
                src = src[:0]
                dst = dst[:0]
            k_max = int(rng.integers(1, 7))
            got = build_closure_bitset(src, dst, m, m_pad, k_max)
            want = _oracle(src, dst, m, m_pad, k_max)
            np.testing.assert_array_equal(got, want)

    def test_cycles_and_self_loops(self):
        # 0 -> 1 -> 2 -> 0 cycle plus a self loop: distances clamp at
        # k_max, diagonal stays 0 (a cycle never shrinks it)
        src = np.array([0, 1, 2, 3], dtype=np.int32)
        dst = np.array([1, 2, 0, 3], dtype=np.int32)
        for k_max in (1, 2, 3, 6):
            got = build_closure_bitset(src, dst, 4, 256, k_max)
            want = _oracle(src, dst, 4, 256, k_max)
            np.testing.assert_array_equal(got, want)

    def test_block_scheduled_and_threaded(self):
        rng = np.random.default_rng(5)
        for _ in range(8):
            m = int(rng.integers(10, 80))
            m_pad = _m_pad(m)
            src, dst = _rand_edges(rng, m, 3 * m)

            class _IG:
                pass

            ig = _IG()
            ig.m = m
            ig.ii_src = src
            ig.ii_dst = dst
            blocks = interior_blocks(ig)
            got = build_closure_bitset(
                src, dst, m, m_pad, 4, workers=4, blocks=blocks
            )
            want = _oracle(src, dst, m, m_pad, 4)
            np.testing.assert_array_equal(got, want)

    def test_padding_rows_stay_inf(self):
        src = np.array([0], dtype=np.int32)
        dst = np.array([1], dtype=np.int32)
        d = build_closure_bitset(src, dst, 2, 256, 4)
        assert (d[2:] == 255).all()
        assert d[0, 0] == 0 and d[1, 1] == 0
        assert d[0, 1] == 1


class TestIncremental:
    def test_insert_and_delete_deltas(self):
        rng = np.random.default_rng(9)
        for trial in range(20):
            m = int(rng.integers(8, 64))
            m_pad = _m_pad(m)
            src, dst = _rand_edges(rng, m, 3 * m)
            k_max = int(rng.integers(2, 6))
            d_prev = build_closure_bitset(src, dst, m, m_pad, k_max)
            # arbitrary delta: drop a slice, add fresh edges
            keep = rng.random(len(src)) > 0.2
            add_src, add_dst = _rand_edges(rng, m, int(rng.integers(1, 10)))
            new_src = np.concatenate([src[keep], add_src])
            new_dst = np.concatenate([dst[keep], add_dst])
            d_new, n_dirty = update_closure_bitset(
                d_prev, src, dst, new_src, new_dst, m, m_pad, k_max
            )
            want = build_closure_bitset(new_src, new_dst, m, m_pad, k_max)
            np.testing.assert_array_equal(d_new, want, err_msg=f"trial {trial}")
            assert n_dirty <= m

    def test_deletion_only_with_block_refinement(self):
        rng = np.random.default_rng(13)
        for _ in range(10):
            m = int(rng.integers(8, 64))
            m_pad = _m_pad(m)
            src, dst = _rand_edges(rng, m, 3 * m)

            class _IG:
                pass

            ig = _IG()
            ig.m = m
            ig.ii_src = src
            ig.ii_dst = dst
            blocks = interior_blocks(ig)
            d_prev = build_closure_bitset(src, dst, m, m_pad, 4)
            keep = rng.random(len(src)) > 0.3
            d_new, _ = update_closure_bitset(
                d_prev,
                src,
                dst,
                src[keep],
                dst[keep],
                m,
                m_pad,
                4,
                blocks=blocks,
            )
            want = build_closure_bitset(src[keep], dst[keep], m, m_pad, 4)
            np.testing.assert_array_equal(d_new, want)

    def test_empty_delta_reuses_matrix(self):
        src = np.array([0, 1], dtype=np.int32)
        dst = np.array([1, 2], dtype=np.int32)
        d = build_closure_bitset(src, dst, 3, 256, 4)
        # same edges, different order/duplicates: no dirty rows at all
        src2 = np.array([1, 0, 0], dtype=np.int32)
        dst2 = np.array([2, 1, 1], dtype=np.int32)
        d_new, n_dirty = update_closure_bitset(
            d, src, dst, src2, dst2, 3, 256, 4
        )
        assert n_dirty == 0
        assert d_new is d

    def test_edge_delta_keys(self):
        ins, dele = interior_edge_delta(
            np.array([0, 1]),
            np.array([1, 2]),
            np.array([1, 5]),
            np.array([2, 6]),
            256,
        )
        assert list(ins) == [5 * 256 + 6]
        assert list(dele) == [0 * 256 + 1]


class TestEngineParity:
    """ClosureCheckEngine(builder=semiring) vs builder=matmul vs the
    host-recursion oracle, over random stores with unicode vocab and
    overlay deltas applied mid-serve."""

    def _engines(self, store, **kw):
        from keto_tpu.engine import CheckEngine

        # strong freshness + no debounce: every write's rebuild happens
        # synchronously inside the next batch_check, so the build-path
        # counters below observe it deterministically
        kw.setdefault("freshness", "strong")
        kw.setdefault("rebuild_debounce_s", 0.0)
        oracle = CheckEngine(store, max_depth=5)
        semi = ClosureCheckEngine(
            SnapshotManager(store), max_depth=5, builder="semiring", **kw
        )
        mat = ClosureCheckEngine(
            SnapshotManager(store), max_depth=5, builder="matmul", **kw
        )
        return oracle, semi, mat

    def test_random_graph_parity(self):
        rng = np.random.default_rng(21)
        store = random_store(rng, n_objects=40, n_users=30, n_edges=300)
        oracle, semi, mat = self._engines(store)
        reqs = _random_requests(rng, 40, 30, k=128)
        want = oracle.batch_check(reqs)
        assert semi.batch_check(reqs) == want
        assert mat.batch_check(reqs) == want
        assert semi.last_build_phases.get("kernel") is not None
        assert semi.last_build_phases.get("blocks") is not None

    def test_unicode_vocab(self):
        store = InMemoryTupleStore()
        store.write_relation_tuples(
            t("n:café#члены@(n:日本語#члены)"),
            t("n:日本語#члены@(ユーザー☃)"),
            t("n:café#viewer@(n:café#члены)"),
        )
        oracle, semi, mat = self._engines(store)
        reqs = [
            t("n:café#viewer@(ユーザー☃)"),
            t("n:café#члены@(ユーザー☃)"),
            t("n:café#viewer@(nobody)"),
        ]
        want = oracle.batch_check(reqs)
        assert want == [True, True, False]
        assert semi.batch_check(reqs) == want
        assert mat.batch_check(reqs) == want

    def test_overlay_delta_mid_serve_goes_incremental(self):
        """A write burst past the 8-edge patch window, landing entirely on
        already-interior nodes, takes the semiring dirty-row rebuild (no
        full-rebuild cliff) and stays exact."""
        store = InMemoryTupleStore()
        base = [t(f"n:root#r@(n:g{i}#m)") for i in range(12)]
        base += [t(f"n:g{i}#m@(u{i})") for i in range(12)]
        base.append(t("n:top#r@(n:root#r)"))
        store.write_relation_tuples(*base)
        oracle, semi, mat = self._engines(store)
        reqs = [t(f"n:root#r@(u{i})") for i in range(12)]
        reqs += [t(f"n:g0#m@(u{i})") for i in range(12)]
        assert semi.batch_check(reqs) == oracle.batch_check(reqs)
        assert semi.n_incremental_builds == 0
        # 12 fresh set->set edges between EXISTING interior nodes: blows
        # the per-edge patch window, keeps the interior node set stable —
        # the write overlay serves it exactly, and the COMPACTION rebuild
        # (folding the overlay back into D) must take the semiring
        # dirty-row path, not the full O(m^3) build the old engine re-ran
        burst = [t(f"n:g{i}#m@(n:g{(i + 1) % 12}#m)") for i in range(12)]
        store.write_relation_tuples(*burst)
        want = oracle.batch_check(reqs)
        assert semi.batch_check(reqs) == want
        assert mat.batch_check(reqs) == want
        full0 = semi.n_full_builds
        semi._build_sync()  # the overlay-compaction rebuild, on demand
        assert semi.n_incremental_builds >= 1
        assert semi.n_full_builds == full0
        assert semi.last_build_phases.get("incremental") is not None
        # the compacted closure must still answer exactly
        assert semi.batch_check(reqs) == want

    def test_deletion_goes_incremental(self):
        """Deletions force a snapshot re-encode; on a store with a stable
        append-only vocab (columnar) and an unchanged interior node set,
        the engine still updates D incrementally instead of rebuilding."""
        from keto_tpu.store.columnar import ColumnarTupleStore

        store = ColumnarTupleStore()
        base = [t(f"n:top#r@(n:p{i}#r)") for i in range(2)]
        base += [t(f"n:p{i}#r@(n:s#m)") for i in range(2)]
        base += [t("n:s#m@(u1)"), t("n:keep#r@(n:s#m)")]
        store.write_relation_tuples(*base)
        oracle, semi, _ = self._engines(store)
        reqs = [
            t("n:top#r@(u1)"),
            t("n:p0#r@(u1)"),
            t("n:p1#r@(u1)"),
            t("n:s#m@(u1)"),
            t("n:top#r@(u2)"),
        ]
        assert semi.batch_check(reqs) == oracle.batch_check(reqs)
        # delete an interior-interior edge; s#m keeps other incoming
        # edges so the interior node set is unchanged
        store.delete_relation_tuples(t("n:p1#r@(n:s#m)"))
        want = oracle.batch_check(reqs)
        assert want == [True, True, False, True, False]
        assert semi.batch_check(reqs) == want
        full0 = semi.n_full_builds
        semi._build_sync()  # fold the deletion into D: incremental path
        assert semi.n_incremental_builds >= 1
        assert semi.n_full_builds == full0
        assert semi.batch_check(reqs) == want

    def test_builder_knob_validation(self):
        store = InMemoryTupleStore()
        with pytest.raises(ValueError):
            ClosureCheckEngine(SnapshotManager(store), builder="nope")


class TestPallasStepInterpret:
    """The fused Pallas masked-SpMV step (the TPU builder) run in Pallas
    interpret mode on the CPU: byte-identical to the lax step and to the
    dense oracle. Catches block-index mistakes no TPU compile can see."""

    @pytest.fixture
    def interpret(self, monkeypatch):
        import functools

        from jax.experimental import pallas as pl

        monkeypatch.setattr(
            pl, "pallas_call", functools.partial(pl.pallas_call, interpret=True)
        )

    @pytest.mark.parametrize("seed", range(2))
    def test_build_matches_lax_and_oracle(self, interpret, seed):
        import jax.numpy as jnp

        from keto_tpu.engine.pallas_spmv import _build_closure_semiring

        rng = np.random.default_rng(seed)
        m, m_pad, k_max = 200, 256, 4
        src, dst = _rand_edges(rng, m, 600)
        packed = jnp.asarray(pack_adjacency(src, dst, m_pad))
        got = {
            use: np.asarray(
                _build_closure_semiring(
                    packed, jnp.int32(m), m_pad=m_pad, k_max=k_max,
                    group=256, use_pallas=use,
                )
            )
            for use in (True, False)
        }
        np.testing.assert_array_equal(got[True], got[False])
        np.testing.assert_array_equal(
            got[True], _oracle(src, dst, m, m_pad, k_max)
        )
