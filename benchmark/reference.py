"""Plain reference for Check over the generated graph.

Independent of the program: it reads only the generator's edge arrays,
in the benchmark's own node ids.

Check (Keto's semantics, as the program's ``CheckEngine`` walks them):
``object#relation@subject`` is allowed iff the subject is reached from the
object's subject set along at most ``depth`` tuples, where ``depth`` is
the request's depth clamped to the global max-depth. Answered by a
bidirectional search: nodes reached in 1..a steps forward, nodes that
reach the subject in 0..b steps backward, a + b = depth; a path of length
L <= depth has its node at position min(L, a) in both sets.
"""

from __future__ import annotations

import numpy as np

from .graph import Graph


def clamp_depth(requested: int, global_max: int) -> int:
    """The global max-depth wins when it is smaller, or when the request
    asks for 0 or less (Keto's engine)."""
    if requested <= 0 or global_max < requested:
        return global_max
    return requested


def _csr(keys_from: np.ndarray, keys_to: np.ndarray, n: int):
    order = np.argsort(keys_from, kind="stable")
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(keys_from, minlength=n), out=indptr[1:])
    return indptr, keys_to[order]


def _step(nodes: np.ndarray, indptr: np.ndarray, nbr: np.ndarray):
    """Sorted distinct neighbours of ``nodes``."""
    lo = indptr[nodes]
    lens = indptr[nodes + 1] - lo
    starts = np.repeat(lo - (np.cumsum(lens) - lens), lens)
    return np.unique(nbr[starts + np.arange(int(lens.sum()))])


class Reference:
    def __init__(self, graph: Graph, max_depth: int) -> None:
        self.n = graph.n_nodes
        self.max_depth = max_depth
        self.out_ptr, self.out_nbr = _csr(graph.src, graph.dst, self.n)
        self.in_ptr, self.in_nbr = _csr(graph.dst, graph.src, self.n)

    def check(self, s: int, t: int, depth: int = 0) -> bool:
        d = clamp_depth(depth, self.max_depth)
        if d < 1:
            return False
        a = (d + 1) // 2
        fwd = _step(np.array([s], dtype=np.int64), self.out_ptr,
                    self.out_nbr)
        frontier = fwd
        for _ in range(a - 1):
            if not len(frontier) or t in frontier:
                break
            nxt = _step(frontier, self.out_ptr, self.out_nbr)
            frontier = np.setdiff1d(nxt, fwd, assume_unique=True)
            fwd = np.union1d(fwd, frontier)
        if np.isin(t, fwd):
            return True
        back = np.array([t], dtype=np.int64)
        frontier = back
        for _ in range(d - a):
            if not len(frontier):
                break
            prv = _step(frontier, self.in_ptr, self.in_nbr)
            frontier = np.setdiff1d(prv, back, assume_unique=True)
            back = np.union1d(back, frontier)
            if np.intersect1d(frontier, fwd, assume_unique=True).size:
                return True
        return False
