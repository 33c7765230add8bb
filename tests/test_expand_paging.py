"""Paged Expand: worklist traversal, page stitching, and token hygiene.

The contract (keto_tpu/engine/expand.py, both engines): the explicit
work-stack traversal never hits Python's recursion limit, paged expansion
stitched with ``apply_expand_patches`` is byte-identical to the unpaged
tree for every page size, and continuation tokens fail closed — garbage,
cross-engine, or stale-version tokens all raise ``ErrMalformedPageToken``.
"""

import sys

import numpy as np
import pytest

from keto_tpu.engine.expand import (
    ExpandEngine,
    SnapshotExpandEngine,
    decode_expand_page_token,
    encode_expand_page_token,
)
from keto_tpu.engine.tree import NodeType, Tree, apply_expand_patches
from keto_tpu.graph import SnapshotManager
from keto_tpu.namespace import MemoryNamespaceManager
from keto_tpu.relationtuple import RelationTuple, SubjectID, SubjectSet
from keto_tpu.store import InMemoryTupleStore
from keto_tpu.utils.errors import ErrMalformedInput, ErrMalformedPageToken

from conftest import random_store


def t(s: str) -> RelationTuple:
    return RelationTuple.from_string(s)


def make_env(*namespaces):
    nsmgr = MemoryNamespaceManager()
    for n in namespaces:
        nsmgr.add(n)
    store = InMemoryTupleStore(namespace_manager=nsmgr)
    return store, ExpandEngine(store)


def _engines(store, max_depth=None):
    """(name, engine) pairs — the host store-walking engine and the
    snapshot CSR engine share the paging contract."""
    kw = {} if max_depth is None else {"max_depth": max_depth}
    return [
        ("host", ExpandEngine(store, **kw)),
        ("snap", SnapshotExpandEngine(SnapshotManager(store), **kw)),
    ]


def _drain(engine, subject, max_depth=0, page_size=3, max_pages=10_000):
    """Walk every page, stitch, and return (tree, n_pages)."""
    page = engine.build_tree_page(
        subject, max_depth=max_depth, page_size=page_size
    )
    tree = page.tree
    pages = 1
    while page.next_page_token:
        assert pages < max_pages, "paged expand did not terminate"
        page = engine.build_tree_page(
            subject,
            max_depth=max_depth,
            page_size=page_size,
            page_token=page.next_page_token,
        )
        tree = apply_expand_patches(tree, page.patches)
        pages += 1
    return tree, pages


class TestWorklist:
    def test_self_referential_set_terminates(self):
        # a set that contains itself: visited-set suppression degrades the
        # recursive occurrence to a Leaf, no infinite loop / recursion
        store, e = make_env("n")
        store.write_relation_tuples(
            t("n:a#r@(n:a#r)"), t("n:a#r@(u1)")
        )
        for name, eng in _engines(store):
            tree = eng.build_tree(SubjectSet("n", "a", "r"), 100)
            assert tree.type == NodeType.UNION, name
            subjects = {str(c.subject) for c in tree.children}
            assert subjects == {"n:a#r", "u1"}, name
            assert all(c.type == NodeType.LEAF for c in tree.children), name

    def test_chain_beyond_recursion_limit(self):
        # a subject-set chain much deeper than sys.getrecursionlimit():
        # the old recursive engine died with RecursionError here
        depth = sys.getrecursionlimit() + 500
        store, _ = make_env("n")
        store.write_relation_tuples(
            *[t(f"n:c{i}#r@(n:c{i + 1}#r)") for i in range(depth)],
            t(f"n:c{depth}#r@(bottom)"),
        )
        for name, eng in _engines(store, max_depth=depth + 5):
            tree = eng.build_tree(SubjectSet("n", "c0", "r"), depth + 5)
            node, levels = tree, 0
            while node.type == NodeType.UNION:
                (node,) = node.children
                levels += 1
            assert node.subject == SubjectID("bottom"), name
            assert levels == depth + 1, name

    def test_deep_chain_pages_and_stitches(self):
        depth = sys.getrecursionlimit() + 200
        store, _ = make_env("n")
        store.write_relation_tuples(
            *[t(f"n:c{i}#r@(n:c{i + 1}#r)") for i in range(depth)],
            t(f"n:c{depth}#r@(bottom)"),
        )
        for name, eng in _engines(store, max_depth=depth + 5):
            want = eng.build_tree(SubjectSet("n", "c0", "r"), depth + 5)
            got, pages = _drain(
                eng,
                SubjectSet("n", "c0", "r"),
                max_depth=depth + 5,
                page_size=64,
            )
            # Tree.__eq__ recurses — compare the unary chain iteratively
            a, b = got, want
            while True:
                assert (a.type, a.subject) == (b.type, b.subject), name
                assert len(a.children) == len(b.children), name
                if not a.children:
                    break
                (a,), (b,) = a.children, b.children
            assert pages > 1, name


class TestPagingParity:
    @pytest.mark.parametrize("seed", range(8))
    @pytest.mark.parametrize("page_size", [1, 2, 3, 7, 1000])
    def test_stitched_equals_unpaged(self, seed, page_size):
        rng = np.random.default_rng(seed)
        store = random_store(rng, n_objects=12, n_users=8, n_edges=90)
        for name, eng in _engines(store):
            for depth in (2, 4, 7):
                for obj in ("o0", "o3", "o7"):
                    root = SubjectSet("n", obj, "r0")
                    want = eng.build_tree(root, depth)
                    got, _ = _drain(
                        eng, root, max_depth=depth, page_size=page_size
                    )
                    if want is None:
                        assert got is None, (name, obj, depth)
                    else:
                        assert got == want, (name, obj, depth)

    def test_first_page_has_placeholders_then_patched(self):
        store, _ = make_env("n")
        store.write_relation_tuples(
            t("n:root#r@(n:a#m)"),
            t("n:root#r@(n:b#m)"),
            t("n:a#m@(u1)"),
            t("n:a#m@(u2)"),
            t("n:b#m@(u3)"),
        )
        for name, eng in _engines(store):
            root = SubjectSet("n", "root", "r")
            page = eng.build_tree_page(root, max_depth=5, page_size=1)
            # budget of 1: root entered, both set children deferred as
            # placeholder Leaves
            assert page.next_page_token, name
            assert page.tree.type == NodeType.UNION, name
            assert all(
                c.type == NodeType.LEAF for c in page.tree.children
            ), name
            # later pages arrive as path-addressed subtree patches
            tree = page.tree
            while page.next_page_token:
                page = eng.build_tree_page(
                    root,
                    max_depth=5,
                    page_size=1,
                    page_token=page.next_page_token,
                )
                assert page.tree is None, name
                tree = apply_expand_patches(tree, page.patches)
            assert tree == eng.build_tree(root, 5), name

    def test_subject_id_is_single_page(self):
        store, _ = make_env("n")
        for name, eng in _engines(store):
            page = eng.build_tree_page(SubjectID("u1"), page_size=1)
            assert page.next_page_token == "", name
            assert page.tree == Tree(
                type=NodeType.LEAF, subject=SubjectID("u1")
            ), name

    def test_page_dict_shape(self):
        store, _ = make_env("n")
        store.write_relation_tuples(
            t("n:root#r@(n:a#m)"), t("n:a#m@(u1)")
        )
        _, eng = _engines(store)[0]
        root = SubjectSet("n", "root", "r")
        p1 = eng.build_tree_page(root, max_depth=5, page_size=1)
        d1 = p1.to_dict()
        assert "tree" in d1 and "patches" not in d1
        assert d1["next_page_token"] == p1.next_page_token
        p2 = eng.build_tree_page(
            root, max_depth=5, page_size=100, page_token=p1.next_page_token
        )
        d2 = p2.to_dict()
        assert "patches" in d2 and "tree" not in d2
        assert "next_page_token" not in d2
        # patches round-trip through their wire form
        stitched = apply_expand_patches(
            Tree.from_dict(d1["tree"]),
            [(p["path"], p["tree"]) for p in d2["patches"]],
        )
        assert stitched == eng.build_tree(root, 5)


class TestTokens:
    def _token_env(self):
        store, _ = make_env("n")
        store.write_relation_tuples(
            t("n:root#r@(n:a#m)"),
            t("n:a#m@(u1)"),
            t("n:root#r@(n:b#m)"),
            t("n:b#m@(u2)"),
        )
        return store

    def test_garbage_token_rejected(self):
        store = self._token_env()
        for name, eng in _engines(store):
            for bad in ("garbage", "aGVsbG8=", "", "!!!!"):
                with pytest.raises(ErrMalformedPageToken):
                    eng.build_tree_page(
                        SubjectSet("n", "root", "r"),
                        max_depth=5,
                        page_size=1,
                        page_token=bad or "x",
                    )

    def test_cross_engine_token_rejected(self):
        store = self._token_env()
        root = SubjectSet("n", "root", "r")
        host = ExpandEngine(store)
        snap = SnapshotExpandEngine(SnapshotManager(store))
        host_tok = host.build_tree_page(
            root, max_depth=5, page_size=1
        ).next_page_token
        snap_tok = snap.build_tree_page(
            root, max_depth=5, page_size=1
        ).next_page_token
        assert host_tok and snap_tok
        with pytest.raises(ErrMalformedPageToken):
            snap.build_tree_page(
                root, max_depth=5, page_size=1, page_token=host_tok
            )
        with pytest.raises(ErrMalformedPageToken):
            host.build_tree_page(
                root, max_depth=5, page_size=1, page_token=snap_tok
            )

    def test_stale_version_token_rejected(self):
        # the cursor pins the data version it was cut at; a write in
        # between supersedes it — fail closed, the client restarts
        store = self._token_env()
        for name, eng in _engines(store):
            tok = eng.build_tree_page(
                SubjectSet("n", "root", "r"), max_depth=5, page_size=1
            ).next_page_token
            assert tok, name
            store.write_relation_tuples(
                t(f"n:root#r@(fresh-{name})")
            )
            with pytest.raises(ErrMalformedPageToken):
                eng.build_tree_page(
                    SubjectSet("n", "root", "r"),
                    max_depth=5,
                    page_size=1,
                    page_token=tok,
                )

    def test_token_roundtrip(self):
        pending = [([0, 2], ["n", "obj", "rel"], 4), ([1], ["n", "x", "y"], 2)]
        visited = ["n:a#b", "n:c#d"]
        tok = encode_expand_page_token("host", 7, pending, visited)
        got_pending, got_visited = decode_expand_page_token(tok, "host", 7)
        assert got_pending == [tuple(p) for p in pending] or got_pending == [
            (list(path), ref, rest) for path, ref, rest in pending
        ]
        assert got_visited == visited
        with pytest.raises(ErrMalformedPageToken):
            decode_expand_page_token(tok, "snap", 7)
        with pytest.raises(ErrMalformedPageToken):
            decode_expand_page_token(tok, "host", 8)


class TestPatchErrors:
    def _tree(self):
        return Tree(
            type=NodeType.UNION,
            subject=SubjectSet("n", "o", "r"),
            children=[Tree(type=NodeType.LEAF, subject=SubjectID("u1"))],
        )

    def test_empty_path_rejected(self):
        with pytest.raises(ErrMalformedInput):
            apply_expand_patches(self._tree(), [([], self._tree())])

    def test_unresolvable_path_rejected(self):
        for path in ([5], [0, 0], [-1]):
            with pytest.raises(ErrMalformedInput):
                apply_expand_patches(self._tree(), [(path, self._tree())])
