"""The generated relation-tuple graph, in the benchmark's own node ids.

A generator (``generators/<name>.py``) lays nodes out as contiguous ranges
of kinds (users, groups, ...) and draws edges as int arrays: edge ``s -> t``
is the tuple ``key(s)@key(t)``. Keys are rendered only for the nodes that
are used, so a 6M-tuple graph never builds Python objects for the millions
of repository permissions that hold no tuple. The reference, the traffic
and the store load all start from these arrays.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True)
class Kind:
    """A contiguous range of nodes. ``key`` is the key template: a
    1-tuple ``("u{}",)`` for subject ids, or ``(namespace, object,
    relation)`` with ``{}`` in the object for subject sets."""

    name: str
    start: int
    count: int
    key: tuple

    @property
    def stop(self) -> int:
        return self.start + self.count

    @property
    def is_subject_id(self) -> bool:
        return len(self.key) == 1


@dataclass
class Graph:
    kinds: list[Kind]
    src: np.ndarray  # int64 edge sources, in load order
    dst: np.ndarray  # int64 edge targets
    #: kind names: random check objects, check subjects, and the kinds
    #: whose out-edges are grants (where reachable check pairs start)
    objects: str
    subjects: str
    grants: tuple

    @property
    def n_nodes(self) -> int:
        return self.kinds[-1].stop

    def kind(self, name: str) -> Kind:
        for k in self.kinds:
            if k.name == name:
                return k
        raise KeyError(name)

    def is_subject_id(self, nodes: np.ndarray) -> np.ndarray:
        out = np.zeros(len(nodes), dtype=bool)
        for k in self.kinds:
            if k.is_subject_id:
                out |= (nodes >= k.start) & (nodes < k.stop)
        return out

    def keys(self, nodes: np.ndarray) -> np.ndarray:
        """Object array of store keys for ``nodes`` (any order, repeats
        allowed): ``(id,)`` or ``(namespace, object, relation)``."""
        nodes = np.asarray(nodes, dtype=np.int64)
        uniq, inv = np.unique(nodes, return_inverse=True)
        out = np.empty(len(uniq), dtype=object)
        for k in self.kinds:
            lo, hi = np.searchsorted(uniq, [k.start, k.stop])
            if lo == hi:
                continue
            ids = (uniq[lo:hi] - k.start).tolist()
            if k.is_subject_id:
                fmt = k.key[0]
                out[lo:hi] = [(fmt.format(i),) for i in ids]
            else:
                ns, obj, rel = k.key
                out[lo:hi] = [(ns, obj.format(i), rel) for i in ids]
        if len(uniq) and out[-1] is None:
            raise ValueError("node id outside every kind")
        return out[inv]

    def degree_csr(self) -> tuple[np.ndarray, np.ndarray]:
        """(indptr, targets): out-edges by source, stable in load order."""
        order = np.argsort(self.src, kind="stable")
        indptr = np.zeros(self.n_nodes + 1, dtype=np.int64)
        np.cumsum(np.bincount(self.src, minlength=self.n_nodes),
                  out=indptr[1:])
        return indptr, self.dst[order]


def dedupe(src: np.ndarray, dst: np.ndarray, n_nodes: int,
           existing: np.ndarray | None = None):
    """Drop repeated edges, keeping each first occurrence in order (what
    the store's bulk load keeps), and edges already in ``existing`` (a
    sorted array of ``src * n_nodes + dst`` keys)."""
    keys = src * n_nodes + dst
    _, first = np.unique(keys, return_index=True)
    first.sort()
    if existing is not None and len(existing):
        pos = np.searchsorted(existing, keys[first])
        pos = np.minimum(pos, len(existing) - 1)
        first = first[existing[pos] != keys[first]]
    return src[first], dst[first]


def fill(stages, top_up, n_tuples: int, n_nodes: int, rng):
    """Edge arrays for ``stages`` (a list of ``draw(rng) -> (src, dst)``),
    deduplicated, then ``top_up(rng, k)`` edges until exactly
    ``n_tuples`` distinct edges exist (collisions are drawn again)."""
    src = np.concatenate([s for s, _ in stages])
    dst = np.concatenate([d for _, d in stages])
    src, dst = dedupe(src, dst, n_nodes)
    while len(src) < n_tuples:
        s, d = top_up(rng, n_tuples - len(src))
        existing = np.sort(src * n_nodes + dst)
        s, d = dedupe(s, d, n_nodes, existing)
        src = np.concatenate([src, s])
        dst = np.concatenate([dst, d])
    return src[:n_tuples], dst[:n_tuples]


def pick(kind: Kind, rng, k: int) -> np.ndarray:
    """k nodes of ``kind``, uniform."""
    return kind.start + rng.integers(kind.count, size=k)


def reachable_pairs(graph: Graph, rng, k: int, max_hops: int):
    """k (object, subject-id) pairs joined by a path of at most
    ``max_hops`` edges: a random grant (an edge out of a ``grants`` kind),
    then random out-edges until a subject id is reached. Walks that stop
    short are dropped and drawn again."""
    indptr, targets = graph.degree_csr()
    deg = indptr[1:] - indptr[:-1]
    sources = np.repeat(np.arange(graph.n_nodes), deg)
    grants = np.concatenate([
        np.arange(indptr[graph.kind(n).start], indptr[graph.kind(n).stop])
        for n in graph.grants
    ])
    out_s: list[np.ndarray] = []
    out_t: list[np.ndarray] = []
    got = 0
    while got < k:
        e = grants[rng.integers(len(grants), size=2 * (k - got) + 64)]
        start = sources[e]
        cur = targets[e]
        done = graph.is_subject_id(cur)
        alive = ~done
        for _ in range(max_hops - 1):
            idx = np.nonzero(alive)[0]
            d = deg[cur[idx]]
            alive[idx[d == 0]] = False
            idx = idx[d > 0]
            if not len(idx):
                break
            c = cur[idx]
            cur[idx] = targets[
                indptr[c] + (rng.random(len(idx)) * deg[c]).astype(np.int64)
            ]
            hit = idx[graph.is_subject_id(cur[idx])]
            done[hit] = True
            alive[hit] = False
        out_s.append(start[done])
        out_t.append(cur[done])
        got += int(done.sum())
    return np.concatenate(out_s)[:k], np.concatenate(out_t)[:k]


def random_pairs(graph: Graph, rng, k: int):
    """k (object, subject-id) pairs drawn uniformly and independently."""
    return (pick(graph.kind(graph.objects), rng, k),
            pick(graph.kind(graph.subjects), rng, k))
