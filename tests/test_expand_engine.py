"""Expand-engine tests — ports of reference internal/expand/engine_test.go:
leaf for subject ids, one/two-level expansion, max-depth degradation to leaf,
pagination, subject-set leaves, circular tuples."""

import numpy as np
import pytest

from keto_tpu.engine.expand import ExpandEngine, SnapshotExpandEngine
from keto_tpu.engine.tree import NodeType, Tree
from keto_tpu.graph import SnapshotManager
from keto_tpu.namespace import MemoryNamespaceManager
from keto_tpu.relationtuple import RelationTuple, SubjectID, SubjectSet
from keto_tpu.store import InMemoryTupleStore

from conftest import random_store


def make_env(*namespaces):
    nsmgr = MemoryNamespaceManager()
    for n in namespaces:
        nsmgr.add(n)
    store = InMemoryTupleStore(namespace_manager=nsmgr)
    return store, ExpandEngine(store)


def T(ns, obj, rel, subject):
    return RelationTuple(ns, obj, rel, subject)


def subjects_of(tree):
    return {str(c.subject) for c in tree.children}


class TestExpandEngine:
    def test_subject_id_is_leaf(self):
        _, e = make_env("n")
        tree = e.build_tree(SubjectID("user"), 100)
        assert tree == Tree(type=NodeType.LEAF, subject=SubjectID("user"))

    def test_expands_one_level(self):
        store, e = make_env("n")
        root = SubjectSet("n", "obj", "access")
        store.write_relation_tuples(
            T("n", "obj", "access", SubjectID("u1")),
            T("n", "obj", "access", SubjectID("u2")),
        )
        tree = e.build_tree(root, 100)
        assert tree.type == NodeType.UNION
        assert tree.subject == root
        assert subjects_of(tree) == {"u1", "u2"}
        assert all(c.type == NodeType.LEAF for c in tree.children)

    def test_expands_two_levels(self):
        store, e = make_env("n")
        root = SubjectSet("n", "z", "access")
        store.write_relation_tuples(
            T("n", "z", "access", SubjectSet("n", "x", "member")),
            T("n", "x", "member", SubjectID("u1")),
            T("n", "x", "member", SubjectID("u2")),
        )
        tree = e.build_tree(root, 100)
        assert tree.type == NodeType.UNION
        (child,) = tree.children
        assert child.type == NodeType.UNION
        assert child.subject == SubjectSet("n", "x", "member")
        assert subjects_of(child) == {"u1", "u2"}

    def test_respects_max_depth_degrades_to_leaf(self):
        # reference expand engine_test.go:179-236: at rest depth 1 a subject
        # set with tuples becomes a leaf instead of expanding
        store, e = make_env("n")
        root = SubjectSet("n", "z", "access")
        store.write_relation_tuples(
            T("n", "z", "access", SubjectSet("n", "x", "member")),
            T("n", "x", "member", SubjectID("u1")),
        )
        tree = e.build_tree(root, 1)
        assert tree == Tree(type=NodeType.LEAF, subject=root)

        tree = e.build_tree(root, 2)
        (child,) = tree.children
        assert child == Tree(type=NodeType.LEAF, subject=SubjectSet("n", "x", "member"))

    def test_paginates_across_pages(self):
        store, e = make_env("n")
        root = SubjectSet("n", "obj", "access")
        users = [f"u{i:03d}" for i in range(250)]  # > 2 default pages
        store.write_relation_tuples(
            *[T("n", "obj", "access", SubjectID(u)) for u in users]
        )
        tree = e.build_tree(root, 100)
        assert subjects_of(tree) == set(users)

    def test_subject_set_without_tuples_becomes_leaf_child(self):
        store, e = make_env("n")
        root = SubjectSet("n", "obj", "access")
        store.write_relation_tuples(
            T("n", "obj", "access", SubjectSet("n", "empty", "member")),
        )
        tree = e.build_tree(root, 100)
        # reference returns nil for an empty subject set (engine.go:67-69) but
        # the parent substitutes a Leaf for the nil child (engine.go:80-86)
        assert tree.type == NodeType.UNION
        (child,) = tree.children
        assert child == Tree(
            type=NodeType.LEAF, subject=SubjectSet("n", "empty", "member")
        )

    def test_circular_tuples_terminate(self):
        store, e = make_env("m")
        a, b = "A", "B"
        store.write_relation_tuples(
            T("m", a, "connected", SubjectSet("m", b, "connected")),
            T("m", b, "connected", SubjectSet("m", a, "connected")),
        )
        tree = e.build_tree(SubjectSet("m", a, "connected"), 100)
        # A expands to B; B's re-expansion of A is suppressed by the visited
        # set, degrading to a Leaf child (engine.go:80-86) — never dropped
        assert tree.type == NodeType.UNION
        (child,) = tree.children
        assert child.subject == SubjectSet("m", b, "connected")
        assert child.type == NodeType.UNION
        (grandchild,) = child.children
        assert grandchild == Tree(
            type=NodeType.LEAF, subject=SubjectSet("m", a, "connected")
        )

    def test_unknown_namespace_returns_none(self):
        _, e = make_env("known")
        assert e.build_tree(SubjectSet("unknown", "o", "r"), 5) is None

    def test_tree_json_roundtrip(self):
        store, e = make_env("n")
        root = SubjectSet("n", "z", "access")
        store.write_relation_tuples(
            T("n", "z", "access", SubjectSet("n", "x", "member")),
            T("n", "x", "member", SubjectID("u1")),
        )
        tree = e.build_tree(root, 100)
        assert Tree.from_dict(tree.to_dict()) == tree

    def test_tree_pretty_print(self):
        store, e = make_env("n")
        root = SubjectSet("n", "obj", "access")
        store.write_relation_tuples(T("n", "obj", "access", SubjectID("u1")))
        s = str(e.build_tree(root, 100))
        assert "∪ n:obj#access" in s
        assert "u1" in s


class TestSnapshotExpandMatchesStore:
    """The snapshot engine's CSR walk builds the store engine's tree node
    for node on random graphs, at every depth."""

    @pytest.mark.parametrize("seed", range(3))
    def test_random_graphs_expand(self, seed):
        rng = np.random.default_rng(seed + 100)
        store = random_store(rng, n_objects=10, n_users=8, n_edges=60)
        host = ExpandEngine(store, max_depth=7)
        dev = SnapshotExpandEngine(SnapshotManager(store), max_depth=7)
        for depth in (1, 2, 4, 7):
            for o in range(10):
                for r in range(3):
                    subject = SubjectSet(
                        namespace="n", object=f"o{o}", relation=f"r{r}"
                    )
                    ht = host.build_tree(subject, max_depth=depth)
                    dt = dev.build_tree(subject, max_depth=depth)
                    hd = None if ht is None else ht.to_dict()
                    dd = None if dt is None else dt.to_dict()
                    assert hd == dd, f"seed={seed} depth={depth} {subject}"
