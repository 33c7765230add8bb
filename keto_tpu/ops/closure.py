"""Bounded all-pairs-distance closure over the interior graph — MXU path.

The interior subgraph (keto_tpu.graph.interior) is small enough to hold as a
dense adjacency, so depth-bounded all-pairs distances are computed once per
snapshot with iterated bf16 matmuls on the systolic array:

    reach_{<=k} = reach_{<=k-1}  OR  (reach_{<=k-1} @ A)
    D[i, j]     = first k at which j becomes reachable from i   (uint8)

After the closure is resident, a whole Check batch costs only gathers
(ops equivalent of the reference's recursive SQL walk,
internal/check/engine.go:82-114, collapsed into a table lookup):

    allowed(b) = direct(b)  OR  min_{s in F0(b), s' in L(b)} D[s, s']
                 + 1 + extra(b)  <=  depth(b)

with F0 = interior successors of the start node, L = interior in-neighbors
of the target (or the target itself when it is a set), extra = 1 for
subject-id targets (the final s' -> target hop), 0 for set targets.

Transfer discipline: host->device copies cross PCIe, so the adjacency ships
BITPACKED (1 bit/edge-slot, 8x smaller than uint8) and is expanded on
device. The query-side gather exists in two forms: `closure_query` (jit,
on an accelerator) and the engine's host-side twin for a CPU backend and
forked read replicas (keto_tpu/engine/closure.py picks by platform).

Shapes are static per (m_pad, k_max) — the closure build compiles once per
snapshot width bucket. D's padding rows/columns stay at INF (255) so a
padded index can never produce a spurious allow.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

INF_DIST = 255  # uint8 sentinel: not reachable within the depth bound


def pack_adjacency(ii_src, ii_dst, m_pad: int):
    """Host-side: COO interior edges -> bitpacked rows uint8[m_pad, m_pad/8].

    m_pad must be a multiple of 8 (the engine buckets to 256).
    """
    import numpy as np

    adj = np.zeros((m_pad, m_pad), dtype=np.uint8)
    if len(ii_src):
        adj[ii_src, ii_dst] = 1
    return np.packbits(adj, axis=1)


@partial(jax.jit, static_argnames=("m_pad", "k_max"))
def build_closure_packed(packed, m, *, m_pad, k_max):
    """D: uint8[m_pad, m_pad] bounded shortest-path matrix.

    packed: uint8[m_pad, m_pad/8] bitpacked adjacency rows (pack_adjacency);
    m: live interior count (dynamic — avoids a recompile per write);
    k_max: longest path length to resolve (global max-depth - 1).
    """
    shifts = jnp.arange(7, -1, -1, dtype=jnp.uint8)  # np.packbits bit order
    adj_bits = (packed[:, :, None] >> shifts[None, None, :]) & jnp.uint8(1)
    adj = adj_bits.reshape(m_pad, m_pad).astype(jnp.bfloat16)
    return _closure_from_dense(adj, m, m_pad, k_max)


@partial(jax.jit, static_argnames=("m_pad", "k_max"))
def build_closure(adj, m, *, m_pad, k_max):
    """As build_closure_packed but from a dense bf16 adjacency (tests)."""
    return _closure_from_dense(adj, m, m_pad, k_max)


def _closure_from_dense(adj, m, m_pad, k_max):
    inf = jnp.uint8(INF_DIST)
    reach = adj > 0.5
    d = jnp.where(reach, jnp.uint8(1), inf)

    def body(k, state):
        reach, d = state
        nxt = (
            jnp.dot(
                reach.astype(jnp.bfloat16),
                adj,
                preferred_element_type=jnp.float32,
            )
            > 0.5
        )
        newly = jnp.logical_and(nxt, ~reach)
        d = jnp.where(newly, k.astype(jnp.uint8), d)
        return jnp.logical_or(reach, nxt), d

    if k_max >= 2:
        _, d = lax.fori_loop(2, k_max + 1, body, (reach, d))

    # diagonal = 0 (s == s' costs no interior steps) — but only for live
    # rows; padding diag stays INF so the PAD index is inert in queries
    idx = jnp.arange(m_pad, dtype=jnp.int32)
    live = idx < m
    eye = idx[:, None] == idx[None, :]
    diag_vals = jnp.where(live, jnp.uint8(0), inf)
    return jnp.where(eye, diag_vals[:, None], d)


@jax.jit
def closure_insert_edge(d, u, v, k_max):
    """Exact incremental update of a bounded closure for one inserted
    interior edge u -> v (device path).

    For a single nonnegative-weight edge insertion the all-pairs update
    D'[i,j] = min(D[i,j], D[i,u] + 1 + D[v,j]) is exact (a shortest path
    uses the new edge at most once). Distances beyond k_max are clamped to
    INF_DIST, preserving the bounded-closure invariant. O(M^2) instead of
    the O(M^3) full rebuild — the write-path fix for closure thrash.
    """
    col = d[:, u].astype(jnp.int32)
    row = d[v, :].astype(jnp.int32)
    cand = col[:, None] + 1 + row[None, :]
    cand = jnp.where(cand > k_max, jnp.int32(INF_DIST), cand)
    return jnp.minimum(d, cand.astype(jnp.uint8))


def closure_insert_edge_host(d, u: int, v: int, k_max: int):
    """Numpy twin of closure_insert_edge (host query mode), in place.

    Restricted to the rows that reach u and the columns reachable from v:
    everything else gets cand > k_max and cannot improve. At the 100M
    rung (22k interior) the full M^2 relax allocated a ~2 GB int32
    temp per interior write; the restricted form touches |reach(u)| x
    |reach(v)| — typically thousands of entries, not half a billion —
    cutting interior-insert staleness from seconds to milliseconds.
    Writes stay per-entry monotone (uint8 stores), so concurrent readers
    see between-versions answers exactly as before."""
    import numpy as np

    du = d[:, u].astype(np.int16)
    dv = d[v, :].astype(np.int16)
    # du + 1 + dv <= k_max requires both legs <= k_max - 1
    rows = np.nonzero(du <= k_max - 1)[0]
    if rows.size == 0:
        return d
    cols = np.nonzero(dv <= k_max - 1)[0]
    if cols.size == 0:
        return d
    cand = du[rows][:, None] + np.int16(1) + dv[cols][None, :]
    cand = np.where(cand > k_max, np.int16(INF_DIST), cand).astype(np.uint8)
    ix = np.ix_(rows, cols)
    d[ix] = np.minimum(d[ix], cand)
    return d


@jax.jit
def closure_query(d, f0, l, extra, depth, direct):
    """allowed: bool[B] — device-side query (cheap-dispatch deployments).

    d: uint8[m_pad, m_pad] closure; f0: int32[B, F0] interior successor rows
    (PAD-filled); l: int32[B, L] interior in-neighbor rows (PAD-filled);
    extra: int32[B] (1 for id targets); depth: int32[B]; direct: bool[B].
    """
    sub = d[f0[:, :, None], l[:, None, :]]  # uint8[B, F0, L] gather
    best = jnp.min(sub, axis=(1, 2)).astype(jnp.int32)
    # INF must never satisfy any depth budget (valid distances are <= 254,
    # so 255 is unambiguously "unreachable")
    best = jnp.where(best >= INF_DIST, jnp.int32(1 << 30), best)
    total = 1 + best + extra
    return jnp.logical_or(
        jnp.logical_and(direct, depth >= 1), total <= depth
    )


# -- interior walk: interiors too large for a dense closure -------------------
#
# Where D would not fit (an interior of millions of subject sets: documents in
# nested folders), each row walks forward from its F0 set over the interior's
# own CSR, all rows in lockstep, one hop per step, and tests each hop's
# frontier against its L set. A frontier is a fixed-width column of distinct
# interior indices; the CSR's last row is empty and its index is the PAD, the
# largest value any frontier holds. The helpers take the array module (numpy
# or jax.numpy) so the host twin runs the same math.
#
# Layout, for the chip: rows run along the minor axis ([W, B], [L, B]) and
# the CSR is one array, offsets then successors (graph/interior.py
# interior_csr). A hop is then about 26 device ops at B = 128 where rows
# along the major axis and two arrays made it 57, mostly layout copies and
# slices: each op is an event in a profiler trace, and a dispatch runs up
# to max-depth - 1 hops.


def _walk_sort(xp, x):
    if xp is jnp:
        return lax.sort(x, dimension=0, is_stable=False)
    return xp.sort(x, axis=0)


def _walk_meets(xp, f, l, pad):
    """bool[B]: does column b of frontier f share an index with l[:, b]?"""
    both = (f[:, None, :] == l[None, :, :]) & (f != pad)[:, None, :]
    return xp.any(both, axis=(0, 1))


def _walk_step(xp, csr, rows, f, pad, width, fanout):
    """The distinct successors of each column of frontier f[W, B]: (next
    frontier [width, B], overflow bool[B]). A row overflows when its
    frontier's out-edges exceed ``fanout`` or their distinct targets exceed
    ``width``: the frontier it gets is then truncated.

    Gathers are what a hop costs on the chip (several ns an element), so
    it makes three: the CSR offsets at f and at f + 1, and one successor
    id per candidate slot. Which entry's row fills slot j is a masked sum
    over the W entries, not a gather."""
    lo = csr[f]
    deg = csr[f + 1] - lo
    ends = xp.cumsum(deg, axis=0)
    starts = ends - deg
    total = ends[-1]
    # slot j belongs to entry i when starts[i] <= j < ends[i]; its edge is
    # the successor at lo[i] + j - starts[i]
    slot = xp.arange(fanout, dtype=f.dtype)[:, None, None]
    mine = (starts[None] <= slot) & (slot < ends[None])
    at = xp.sum(xp.where(mine, (lo - starts)[None], 0), axis=1) + slot[:, 0]
    valid = slot[:, 0] < total[None]
    cand = xp.where(valid, csr[xp.where(valid, at + rows + 1, 0)], pad)
    # distinct, PAD last (it is the largest index), then the first `width`
    s = _walk_sort(xp, cand)
    new = xp.concatenate(
        [s[:1] != pad, (s[1:] != s[:-1]) & (s[1:] != pad)], axis=0
    )
    nxt = _walk_sort(xp, xp.where(new, s, pad))[:width]
    return nxt, (total > fanout) | (xp.sum(new, axis=0) > width)


def _walk_start(xp, f0, l, extra, depth, direct, pad, width):
    """Hop 0: the frontier is F0 itself ([B, F0] in, [width, B] out), and
    a row is allowed by its direct edge or by F0 meeting L."""
    f = f0.T
    if f.shape[0] < width:
        f = xp.concatenate(
            [f, xp.full((width - f.shape[0], f.shape[1]), pad, f.dtype)],
            axis=0,
        )
    l = l.T
    budget = depth - 1 - extra  # interior hops a row may take
    allowed = (direct & (depth >= 1)) | (
        _walk_meets(xp, f, l, pad) & (budget >= 0)
    )
    return f, l, budget, allowed


def _walk_live(xp, h, f, budget, allowed, over, pad):
    """The frontier of the rows that hop h + 1 could still allow; the
    others' is all PAD (it has no successors)."""
    live = ~allowed & ~over & (h < budget) & xp.any(f != pad, axis=0)
    return xp.where(live[None], f, pad)


@partial(jax.jit, static_argnames=("rows", "hops", "width", "fanout"))
def interior_walk(csr, f0, l, extra, depth, direct, *, rows, hops, width,
                  fanout):
    """bool[2, B]: row 0 allowed, row 1 overflow (not allowed, and the
    walk could not tell: the host oracle answers those rows).

    csr: int32[rows + 1 + E] — the interior's forward CSR, offsets then
    successors (graph/interior.py interior_csr), whose last row rows - 1
    is empty and is the PAD index; f0, l, extra, depth, direct as
    ``closure_query``, F0 of width <= ``width``. A hit at hop h allows the
    row when 1 + h + extra <= depth, the same accounting as
    ``closure_query``; ``hops`` (the global max depth - 1) bounds the
    loop. Shapes are static per (B, F0, L) with width, fanout and hops
    fixed per engine.
    """
    pad = rows - 1
    f, l, budget, allowed = _walk_start(
        jnp, f0, l, extra, depth, direct, pad, width
    )
    over = jnp.zeros_like(allowed)
    h = jnp.int32(0)
    f = _walk_live(jnp, h, f, budget, allowed, over, pad)

    def cond(state):
        h, f, _, _ = state
        return (h < hops) & jnp.any(f != pad)

    def body(state):
        h, f, allowed, over = state
        f, full = _walk_step(jnp, csr, rows, f, pad, width, fanout)
        allowed = allowed | _walk_meets(jnp, f, l, pad)
        over = over | full
        f = _walk_live(jnp, h + 1, f, budget, allowed, over, pad)
        return h + 1, f, allowed, over

    _, _, allowed, over = lax.while_loop(
        cond, body, (h, f, allowed, over)
    )
    return jnp.stack([allowed, over & ~allowed])


def interior_walk_host(csr, f0, l, extra, depth, direct, *, rows, hops,
                       width, fanout):
    """Numpy twin of ``interior_walk`` (host query mode): same math, no
    device. Rows drop out of the arrays once their frontier is PAD."""
    import numpy as np

    pad = rows - 1
    f, l, budget, allowed = _walk_start(
        np, f0, l, extra, depth, direct, pad, width
    )
    over = np.zeros_like(allowed)
    cols = np.arange(len(allowed))
    h = 0
    while h < hops:
        f = _walk_live(np, h, f, budget[cols], allowed[cols], over[cols],
                       pad)
        keep = np.any(f != pad, axis=0)
        if not keep.any():
            break
        cols, f = cols[keep], f[:, keep]
        f, full = _walk_step(np, csr, rows, f, pad, width, fanout)
        allowed[cols] |= _walk_meets(np, f, l[:, cols], pad)
        over[cols] |= full
        h += 1
    return np.stack([allowed, over & ~allowed])
