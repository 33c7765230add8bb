"""Pieces the loops share: check-pair pools, latency statistics and the
shape warm-up.
"""

from __future__ import annotations

import math

import numpy as np

from .graph import Graph, random_pairs, reachable_pairs

#: how long past the window's close the harness waits for an answer
#: before it counts the request as never answered
ANSWER_WAIT_S = 60.0

# outcome of one request, as a client records it
OK, ERROR, NO_ANSWER = 0, 1, 2


def pair_pool(graph: Graph, rng, k: int, reachable_share: float,
              max_hops: int):
    """k (object, subject) node pairs, shuffled: ``reachable_share`` of
    them on a path of at most ``max_hops`` tuples (a grant, then members
    below it), the rest drawn at random."""
    n_reach = int(round(k * reachable_share))
    rs, rt = reachable_pairs(graph, rng, n_reach, max_hops)
    us, ut = random_pairs(graph, rng, k - n_reach)
    s = np.concatenate([rs, us])
    t = np.concatenate([rt, ut])
    order = rng.permutation(k)
    return s[order], t[order]


def p95(latencies: np.ndarray) -> float:
    """95th percentile (linear between order statistics) of seconds;
    failed requests enter as +inf and count as missing the limit."""
    if not len(latencies):
        return math.nan
    return float(np.percentile(latencies, 95))


def latency_line(name: str, lat: np.ndarray) -> dict:
    finite = lat[np.isfinite(lat)]
    return {
        "phase": "latency",
        "of": name,
        "n": int(len(lat)),
        "failed": int(len(lat) - len(finite)),
        "p50_ms": float(np.percentile(finite, 50)) * 1e3 if len(finite) else None,
        "p95_ms": p95(lat) * 1e3,
        "max_ms": float(finite.max()) * 1e3 if len(finite) else None,
    }


def latencies(rec: dict, start: np.ndarray) -> np.ndarray:
    """Seconds from ``start`` to the answer; +inf where none came."""
    lat = rec["done"] - start
    lat[rec["status"] != OK] = np.inf
    return lat


def _width(deg: np.ndarray) -> np.ndarray:
    """Power-of-two width a batch pads a fan-out of ``deg`` to (1 for 0
    or 1), as the closure query's row gathers do."""
    deg = np.maximum(deg, 1)
    return (1 << np.ceil(np.log2(deg)).astype(np.int64)).astype(np.int64)


def shape_warmup(graph: Graph, rng, b_max: int) -> list:
    """BatchCheck row sets that compile every query shape the traffic can
    meet: for each pair of fan-out widths present (object: tuples to
    subject sets; subject: tuples from subject sets that are themselves
    subjects, the interior), one batch of each power-of-two size from 8 to
    ``b_max`` whose rows all have those widths. Returns [(objects,
    subjects)] node arrays."""
    n = graph.n_nodes
    is_id = graph.is_subject_id(np.arange(n))
    to_set = ~is_id[graph.dst]
    interior = np.zeros(n, dtype=bool)
    interior[graph.dst[to_set]] = True
    f0 = np.bincount(graph.src[to_set], minlength=n)
    from_interior = interior[graph.src] & is_id[graph.dst]
    l_in = np.bincount(graph.dst[from_interior], minlength=n)
    objs = np.concatenate([np.arange(graph.kind(k).start, graph.kind(k).stop)
                           for k in graph.grants])
    users = graph.kind(graph.subjects)
    subs = np.arange(users.start, users.stop)
    wf, wl = _width(f0[objs]), _width(l_in[subs])
    groups = []
    for fb in np.unique(wf):
        cand_o = objs[wf == fb]
        for lb in np.unique(wl):
            cand_u = subs[wl == lb]
            b = 8
            while b <= b_max:
                groups.append((cand_o[rng.integers(len(cand_o), size=b)],
                               cand_u[rng.integers(len(cand_u), size=b)]))
                b *= 2
    return groups
