"""Expand engine: build the full subject tree for a subject set.

Mirrors reference internal/expand/engine.go:33-102:

- SubjectID (or depth exhausted) -> Leaf.
- SubjectSet -> Union node whose children are the expansions of each tuple's
  subject; depth <= 1 degrades the node to a Leaf (engine.go:72-75).
- A subject set already visited on the current search, or one with no tuples,
  yields no node (``None``) (engine.go:42-45, 67-69).
- Tuple pages are followed do-while style (engine.go:55-65).

Unlike the reference (and this engine's first cut), the traversal is an
explicit work stack, not host recursion: a subject-set chain deeper than
Python's recursion limit — or an adversarial ``max_depth`` — walks fine, and
the same machinery yields **paged Expand**: ``build_tree_page`` expands
until ~``page_size`` tree nodes have materialized, returns the partial tree
(deferred subject sets rendered as placeholder Leaves) plus a continuation
token, and later pages return path-addressed subtree patches
(``engine/tree.py apply_expand_patches`` stitches them). Deferred work
resumes in exact DFS-preorder: once the budget is exhausted no further set
is entered, so the visited-set mutation order across stitched pages is
identical to the unpaged walk and the stitched tree is byte-identical.

The continuation token pins the data version it was cut at; a token
presented after the store moved raises ``ErrMalformedPageToken`` (the
cursor names nodes that may no longer exist).

``SnapshotExpandEngine`` builds the same tree from the resident CSR of a
graph snapshot instead of issuing per-node paginated store queries: the
traversal stays on the host (a tree is a host-shaped output) but touches
no store pages.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from ..graph.snapshot import GraphSnapshot, SnapshotManager
from ..relationtuple.definitions import (
    Manager,
    RelationQuery,
    Subject,
    SubjectID,
    SubjectSet,
)
from ..utils.errors import ErrMalformedPageToken, ErrNotFound
from .paging import decode_page_token, encode_page_token
from ..utils.pagination import PaginationOptions
from .check import DEFAULT_MAX_DEPTH, clamp_depth
from .tree import NodeType, Tree

# page budget when a client asks for paging without naming a size and the
# serving registry configured no default (engine.expand_page_size)
FALLBACK_PAGE_SIZE = 1024


@dataclass
class ExpandPage:
    """One page of a paged Expand.

    The first page carries ``tree`` (the partial tree, deferred sets as
    placeholder Leaves); continuation pages carry ``patches`` — (path,
    subtree) pairs addressing placeholder Leaves of the stitched-so-far
    tree. ``next_page_token`` is empty when the expansion is complete.
    """

    tree: Optional[Tree] = None
    patches: list = field(default_factory=list)
    next_page_token: str = ""

    def to_dict(self) -> dict:
        out: dict = {}
        if self.patches:
            out["patches"] = [
                {"path": list(path), "tree": t.to_dict()}
                for path, t in self.patches
            ]
        else:
            out["tree"] = None if self.tree is None else self.tree.to_dict()
        if self.next_page_token:
            out["next_page_token"] = self.next_page_token
        return out


def encode_expand_page_token(kind: str, version, pending, visited) -> str:
    """Continuation cursor: the deferred work items (in DFS-preorder resume
    order), the visited set, and the data version the page was cut at —
    minted through the shared engine/paging.py format."""
    return encode_page_token(
        kind,
        version,
        {
            "p": [[list(path), ref, rest] for path, ref, rest in pending],
            "vis": visited,
        },
    )


def decode_expand_page_token(token: str, kind: str, version):
    """-> (pending, visited). Raises ErrMalformedPageToken on garbage or a
    cursor from the other engine flavor, ErrStalePageToken (a 409 subclass
    of it) on a version mismatch (the snapshot the cursor walked has been
    superseded)."""
    payload = decode_page_token(token, kind, version, what="expand page")
    try:
        pending = [
            (list(path), ref, int(rest))
            for path, ref, rest in payload["p"]
        ]
        visited = payload["vis"]
    except Exception as e:
        raise ErrMalformedPageToken("malformed expand page token") from e
    return pending, visited


class _Frame:
    """One open Union node on the explicit traversal stack."""

    __slots__ = ("subject", "children", "subjects", "i", "rest", "path")

    def __init__(self, subject, subjects, rest, path):
        self.subject = subject
        self.children: list[Tree] = []
        self.subjects = subjects  # child subjects, store insertion order
        self.i = 0
        self.rest = rest
        self.path = path


class ExpandEngine:
    def __init__(
        self,
        manager: Manager,
        max_depth: int = DEFAULT_MAX_DEPTH,
        default_page_size: int = 0,
    ):
        self.manager = manager
        self.global_max_depth = max_depth
        self.default_page_size = default_page_size

    def build_tree(self, subject: Subject, max_depth: int = 0) -> Optional[Tree]:
        depth = clamp_depth(max_depth, self.global_max_depth)
        if not isinstance(subject, SubjectSet):
            return Tree(type=NodeType.LEAF, subject=subject)
        # unbounded budget: nothing defers, the walk completes in one call
        return self._expand_one(
            subject, depth, [], set(), [float("inf")], []
        )

    def build_tree_page(
        self,
        subject: Subject,
        max_depth: int = 0,
        page_size: int = 0,
        page_token: str = "",
    ) -> ExpandPage:
        """Frontier-bounded Expand: materialize ~page_size tree nodes (the
        last entered node may overshoot by its fan-out), defer the rest."""
        depth = clamp_depth(max_depth, self.global_max_depth)
        if page_size <= 0:
            page_size = self.default_page_size or FALLBACK_PAGE_SIZE
        if not isinstance(subject, SubjectSet):
            return ExpandPage(tree=Tree(type=NodeType.LEAF, subject=subject))
        version = getattr(self.manager, "version", 0)
        if page_token:
            pending, vis = decode_expand_page_token(
                page_token, "host", version
            )
            visited = set(vis)
            work = [
                (path, SubjectSet(ref[0], ref[1], ref[2]), rest)
                for path, ref, rest in pending
            ]
            first = False
        else:
            visited = set()
            work = [([], subject, depth)]
            first = True
        budget = [page_size]
        tree: Optional[Tree] = None
        patches = []
        while work and budget[0] > 0:
            path, subj, rest = work.pop(0)
            deferred: list = []
            t = self._expand_one(subj, rest, path, visited, budget, deferred)
            # deferred descendants must resume BEFORE later pending items:
            # that is their DFS-preorder position in the unpaged walk
            work = deferred + work
            if first:
                tree = t
                first = False
            elif t is not None:
                patches.append((path, t))
        token = ""
        if work:
            token = encode_expand_page_token(
                "host",
                version,
                [
                    (path, [s.namespace, s.object, s.relation], rest)
                    for path, s, rest in work
                ],
                sorted(visited),
            )
        return ExpandPage(tree=tree, patches=patches, next_page_token=token)

    # -- traversal core --------------------------------------------------------

    def _subjects_of(self, subject: SubjectSet) -> Optional[list[Subject]]:
        """All tuple subjects of the set, following store pages do-while
        style (engine.go:55-65); None mirrors the reference's nil returns
        (unknown namespace / no tuples)."""
        query = RelationQuery(
            namespace=subject.namespace,
            object=subject.object,
            relation=subject.relation,
        )
        rels, token = [], ""
        while True:
            try:
                page, token = self.manager.get_relation_tuples(
                    query, PaginationOptions(token=token)
                )
            except ErrNotFound:
                return None
            rels.extend(page)
            if not token:
                break
        if not rels:
            return None
        return [r.subject for r in rels]

    def _enter(self, subject, rest, path, visited, budget):
        """The visited/fetch/depth gate of one subject set — the prefix of
        the reference's recursive call. Returns a terminal Optional[Tree]
        or an open _Frame for the union node."""
        key = str(subject)
        if key in visited:
            return None
        visited.add(key)
        subjects = self._subjects_of(subject)
        if subjects is None:
            return None
        budget[0] -= 1
        if rest <= 1:
            return Tree(type=NodeType.LEAF, subject=subject)
        return _Frame(subject, subjects, rest, path)

    def _expand_one(
        self, subject, rest, path, visited, budget, deferred
    ) -> Optional[Tree]:
        """Expand one work item with an explicit stack. Once `budget` is
        exhausted, every not-yet-entered subject set renders as a
        placeholder Leaf and is appended to `deferred` (in DFS-preorder —
        the resume order)."""
        res = self._enter(subject, rest, path, visited, budget)
        if not isinstance(res, _Frame):
            return res
        stack = [res]
        while True:
            fr = stack[-1]
            if fr.i >= len(fr.subjects):
                stack.pop()
                tree = Tree(
                    type=NodeType.UNION,
                    subject=fr.subject,
                    children=fr.children,
                )
                if not stack:
                    return tree
                stack[-1].children.append(tree)
                continue
            idx = fr.i
            fr.i += 1
            child_subject = fr.subjects[idx]
            if not isinstance(child_subject, SubjectSet):
                budget[0] -= 1
                fr.children.append(
                    Tree(type=NodeType.LEAF, subject=child_subject)
                )
                continue
            child_path = fr.path + [idx]
            if budget[0] <= 0:
                # page budget spent: placeholder Leaf now, real expansion
                # on a later page (unless a later item visits it first —
                # the resumed _enter re-checks, exactly like the unpaged
                # walk would have at this point in the preorder)
                fr.children.append(
                    Tree(type=NodeType.LEAF, subject=child_subject)
                )
                deferred.append((child_path, child_subject, fr.rest - 1))
                continue
            res = self._enter(
                child_subject, fr.rest - 1, child_path, visited, budget
            )
            if isinstance(res, _Frame):
                stack.append(res)
            else:
                # nil child (visited cycle / set with no tuples) degrades
                # to a Leaf for that subject, never dropped (engine.go:80-86)
                fr.children.append(
                    res
                    if res is not None
                    else Tree(type=NodeType.LEAF, subject=child_subject)
                )


class _SnapFrame:
    """One open Union node on the snapshot engine's explicit traversal
    stack (CSR twin of engine.expand._Frame)."""

    __slots__ = ("subject", "children", "successors", "i", "rest", "path")

    def __init__(self, subject, successors, rest, path):
        self.subject = subject
        self.children: list[Tree] = []
        self.successors = successors  # child node ids, CSR insertion order
        self.i = 0
        self.rest = rest
        self.path = path


class SnapshotExpandEngine:
    """Expand-tree construction over the resident CSR (no store round-trips).

    Matches the host ExpandEngine (reference internal/expand/engine.go:33-102)
    node for node: SubjectID -> Leaf; a subject set already visited or with no
    tuples -> no node; remaining depth <= 1 -> Leaf; otherwise Union over the
    expansions of each tuple's subject, in store insertion order (the CSR's
    stable sort preserves it).

    Traversal is DFS-preorder like the reference — the visited set's
    mutation order is observable in which occurrence of a repeated set gets
    expanded — but runs on an explicit stack (no recursion limit) with the
    per-node Python work collapsed: child node ids come straight from the
    CSR (no per-node vocab dict probes), the visited set is a bool array,
    and the bottom level of the tree (where every child renders as a Leaf
    regardless of its own edges) is built in one bulk pass per node instead
    of one stack frame per child. At 100M-tuple scale a wide depth-3 expand
    is dominated by exactly that bottom level — millions of Leaf
    constructions — so the interior walk stays Python while the fan-out
    pays only object construction. The same stack drives frontier-bounded
    paged Expand (``build_tree_page``), stitched back by
    ``engine.tree.apply_expand_patches``.
    """

    def __init__(
        self,
        snapshots: SnapshotManager,
        max_depth: int = DEFAULT_MAX_DEPTH,
        default_page_size: int = 0,
    ):
        self.snapshots = snapshots
        self.global_max_depth = max_depth
        self.default_page_size = default_page_size

    def build_tree(
        self, subject: Subject, max_depth: int = 0
    ) -> Optional[Tree]:
        depth = clamp_depth(max_depth, self.global_max_depth)
        snap = self.snapshots.snapshot()
        if not isinstance(subject, SubjectSet):
            return Tree(type=NodeType.LEAF, subject=subject)
        nid = snap.vocab.lookup_subject(subject)
        if nid is None or nid >= snap.padded_nodes:
            # set never appears as an object#relation (or was interned
            # after this snapshot): no tuples
            return None
        visited = np.zeros(snap.padded_nodes, dtype=bool)
        return self._expand_one(
            snap, subject, nid, depth, [], visited, [float("inf")], []
        )

    def build_tree_page(
        self,
        subject: Subject,
        max_depth: int = 0,
        page_size: int = 0,
        page_token: str = "",
    ) -> ExpandPage:
        """Frontier-bounded paged Expand over the resident CSR. Same
        work-queue machinery as the host ExpandEngine; the continuation
        token carries node ids and pins the snapshot version, so a token
        outlives its snapshot only as an ErrMalformedPageToken."""
        depth = clamp_depth(max_depth, self.global_max_depth)
        snap = self.snapshots.snapshot()
        if page_size <= 0:
            page_size = self.default_page_size or FALLBACK_PAGE_SIZE
        if not isinstance(subject, SubjectSet):
            return ExpandPage(tree=Tree(type=NodeType.LEAF, subject=subject))
        visited = np.zeros(snap.padded_nodes, dtype=bool)
        key_of = snap.vocab._key_of
        if page_token:
            pending, vis = decode_expand_page_token(
                page_token, "snap", snap.version
            )
            visited[np.asarray(vis, dtype=np.int64)] = True
            work = [(path, int(nid), rest) for path, nid, rest in pending]
            first = False
        else:
            nid = snap.vocab.lookup_subject(subject)
            if nid is None or nid >= snap.padded_nodes:
                return ExpandPage(tree=None)
            work = [([], nid, depth)]
            first = True
        budget = [page_size]
        tree: Optional[Tree] = None
        patches = []
        while work and budget[0] > 0:
            path, nid, rest = work.pop(0)
            k = key_of[nid]
            subj = SubjectSet(namespace=k[0], object=k[1], relation=k[2])
            deferred: list = []
            t = self._expand_one(
                snap, subj, nid, rest, path, visited, budget, deferred
            )
            # deferred descendants resume BEFORE later pending items —
            # their DFS-preorder position in the unpaged walk
            work = deferred + work
            if first:
                tree = t
                first = False
            elif t is not None:
                patches.append((path, t))
        token = ""
        if work:
            token = encode_expand_page_token(
                "snap",
                snap.version,
                work,
                np.nonzero(visited)[0].tolist(),
            )
        return ExpandPage(tree=tree, patches=patches, next_page_token=token)

    def _enter(self, snap, subject, nid, rest, path, visited, budget):
        """visited/successors/depth gate of one subject set: a terminal
        Optional[Tree], an open _SnapFrame, or the bulk bottom level."""
        if visited[nid]:
            return None  # cycle suppression (engine.go:42-45)
        visited[nid] = True
        successors = snap.out_neighbors(nid)
        if successors.size == 0:
            return None  # no tuples (engine.go:67-69)
        budget[0] -= 1
        if rest <= 1:
            return Tree(type=NodeType.LEAF, subject=subject)
        if rest == 2:
            # whole bottom level in one bulk pass; budget charged for every
            # materialized Leaf so page overshoot stays one node's fan-out
            budget[0] -= int(successors.size)
            return self._union_of_leaves(snap, subject, successors, visited)
        return _SnapFrame(subject, successors.tolist(), rest, path)

    def _expand_one(
        self, snap, subject, nid, rest, path, visited, budget, deferred
    ) -> Optional[Tree]:
        """Iterative DFS-preorder expansion of one work item (explicit
        stack: subject-set chains outlast Python's recursion limit). Once
        `budget` is spent, not-yet-entered subject sets render as
        placeholder Leaves and queue on `deferred` in preorder."""
        res = self._enter(snap, subject, nid, rest, path, visited, budget)
        if not isinstance(res, _SnapFrame):
            return res
        key_of = snap.vocab._key_of
        stack = [res]
        while True:
            fr = stack[-1]
            if fr.i >= len(fr.successors):
                stack.pop()
                tree = Tree(
                    type=NodeType.UNION,
                    subject=fr.subject,
                    children=fr.children,
                )
                if not stack:
                    return tree
                stack[-1].children.append(tree)
                continue
            idx = fr.i
            fr.i += 1
            child_nid = fr.successors[idx]
            k = key_of[child_nid]
            if len(k) == 1:
                budget[0] -= 1
                fr.children.append(
                    Tree(type=NodeType.LEAF, subject=SubjectID(id=k[0]))
                )
                continue
            child_subject = SubjectSet(
                namespace=k[0], object=k[1], relation=k[2]
            )
            if budget[0] <= 0:
                # page budget spent: placeholder Leaf now, expansion on a
                # later page; the resumed _enter re-checks visited, exactly
                # like the unpaged walk would at this preorder position
                fr.children.append(
                    Tree(type=NodeType.LEAF, subject=child_subject)
                )
                deferred.append(
                    (fr.path + [idx], child_nid, fr.rest - 1)
                )
                continue
            res = self._enter(
                snap,
                child_subject,
                child_nid,
                fr.rest - 1,
                fr.path + [idx],
                visited,
                budget,
            )
            if isinstance(res, _SnapFrame):
                stack.append(res)
            else:
                # nil child (visited cycle / set with no tuples) degrades to a
                # Leaf for that subject, never dropped (engine.go:80-86)
                fr.children.append(
                    res
                    if res is not None
                    else Tree(type=NodeType.LEAF, subject=child_subject)
                )

    @staticmethod
    def _union_of_leaves(
        snap: GraphSnapshot,
        subject: SubjectSet,
        successors: np.ndarray,
        visited: np.ndarray,
    ) -> Tree:
        """The tree's bottom level: with one depth step left every child
        renders as a Leaf whatever its own edges are, so the whole child
        loop collapses into bulk Leaf construction. The only recursion side
        effect to preserve is visited bookkeeping: each not-yet-visited SET
        child would have been marked before its depth check."""
        is_set = snap.vocab.is_set_array()
        flags = is_set[successors]
        set_ids = successors[flags]
        if set_ids.size:
            visited[set_ids] = True
        leaf = NodeType.LEAF
        key_of = snap.vocab._key_of
        children = [
            Tree(type=leaf, subject=SubjectID(id=k[0]))
            if len(k) == 1
            else Tree(
                type=leaf,
                subject=SubjectSet(namespace=k[0], object=k[1], relation=k[2]),
            )
            for k in map(key_of.__getitem__, successors.tolist())
        ]
        return Tree(type=NodeType.UNION, subject=subject, children=children)
