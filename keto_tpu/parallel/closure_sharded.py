"""Sharded closure check: the 1B-tuple rung of the BASELINE ladder.

The single-chip closure engine holds three memory classes:

1. the interior distance matrix D — O(M^2) where M is the interior
   (group/role nesting) count. In RBAC and org shapes M does NOT grow
   with users or objects (SURVEY bench note: 22k interior at 100M
   tuples), so D stays ~0.5 GB even at 1B tuples → **replicated** on
   every device. Drive-shaped deployments are the exception: there every
   document's owner set and every folder is interior, M grows with
   objects, and no D fits (the single-chip engine walks the interior's
   CSR instead, ops/closure.py interior_walk).
2. the boundary CSRs (F0 = set successors by node, L = interior
   in-neighbors by node) and the direct-edge table — O(E), the actual
   scale axis. At 1B edges these exceed one device's HBM →
   **node-striped** over the mesh's ``edge`` axis: device k owns the CSR
   rows of nodes with ``node % n_shards == k``.
3. the vocab — host-side (the data-parallel front end encodes).

A batched check then needs exactly two collectives (scaling-book recipe:
shard, compute locally, reduce over the mesh):

  phase 1  owner(start) gathers its F0 row and folds D rows:
           dvec[q, :] = min over a in F0(start_q) of D[a, :]
           -> lax.pmin over 'edge' (non-owners contribute INF)
  phase 2  owner(target) gathers its L row (or the target's interior
           index for set targets) and reduces best_q = min_b dvec[q, b];
           the direct edge is a vectorized binary search of the owner's
           full-out CSR row (dst-sorted within row — int32 throughout, no
           64-bit packed keys: jax without x64 silently downcasts int64
           device arrays, and s*N+t overflows int32 at 1B nodes anyway)
           -> pmin/pmax over 'edge'
  allowed  = (direct & depth>=1) | (1 + best + extra <= depth)

Rows whose true fan-out exceeds the static gather widths report an
overflow flag; they run a second device pass at escalated widths, and
only rows overflowing those too are re-answered host-side by the exact
oracle.

This module is the kernel, the base residency layer and the query path:
``check_ids`` is encode_ids -> launch_encoded -> decode_launched, the
split batch API ``CheckBatcher`` and the circuit breaker drive. Live
check traffic reaches it through :class:`.serving.ShardedServingEngine`,
the serving wrapper the registry wires up under
``engine.sharding.enabled``: it adds the launch's fault sites,
incremental re-sharding across snapshot rebuilds, and per-shard
residency accounting. :class:`ShardedClosureEngine` used directly (bench
`sharded_closure_oracle` configs, parity tests) rebuilds its residency
per snapshot, the mesh-correctness oracle against that serving path.

Design sketch per VERDICT r3 next-#6; BASELINE.md v5e-16 configuration.
"""

from __future__ import annotations

import threading
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from ..engine.check import DEFAULT_MAX_DEPTH, CheckEngine
from ..graph.interior import build_interior
from ..graph.snapshot import GraphSnapshot, SnapshotManager
from ..ops.closure import INF_DIST, build_closure_packed, pack_adjacency
from ..relationtuple.definitions import RelationTuple, SubjectID, SubjectSet
from .mesh import make_mesh


def _stripe_csr(
    indptr: np.ndarray, vals: np.ndarray, pn: int, n_shards: int
) -> tuple[np.ndarray, np.ndarray, int]:
    """Node-stripe a CSR: rows of node n go to shard n % n_shards at local
    row n // n_shards. Returns (indptr [n_shards, local_rows+1],
    vals [n_shards, max_nnz] PAD-padded, local_rows)."""
    local_rows = -(-pn // n_shards)
    out_indptr = np.zeros((n_shards, local_rows + 1), dtype=np.int32)
    shard_vals = []
    for k in range(n_shards):
        nodes = np.arange(k, pn, n_shards, dtype=np.int64)
        counts = np.zeros(local_rows, dtype=np.int64)
        row_counts = (indptr[nodes + 1] - indptr[nodes]).astype(np.int64)
        counts[: len(nodes)] = row_counts
        out_indptr[k, 1:] = np.cumsum(counts).astype(np.int32)
        # ragged gather of the rows' values in stripe order, vectorized
        # (a per-node Python loop would be millions of iterations)
        total = int(row_counts.sum())
        if total:
            starts_rep = np.repeat(
                indptr[nodes].astype(np.int64), row_counts
            )
            within = np.arange(total, dtype=np.int64) - np.repeat(
                np.cumsum(row_counts) - row_counts, row_counts
            )
            shard_vals.append(vals[starts_rep + within])
        else:
            shard_vals.append(np.empty(0, vals.dtype))
    max_nnz = max(1, max(len(v) for v in shard_vals))
    out_vals = np.full((n_shards, max_nnz), 0, dtype=np.int32)
    for k, v in enumerate(shard_vals):
        out_vals[k, : len(v)] = v
    return out_indptr, out_vals, local_rows


def _stripe_vector(
    vec: np.ndarray, pn: int, n_shards: int, fill
) -> np.ndarray:
    """[pn] -> [n_shards, local_rows]: entry of node n at
    [n % n_shards, n // n_shards]."""
    local_rows = -(-pn // n_shards)
    out = np.full((n_shards, local_rows), fill, dtype=vec.dtype)
    for k in range(n_shards):
        nodes = np.arange(k, pn, n_shards, dtype=np.int64)
        out[k, : len(nodes)] = vec[nodes]
    return out


@partial(
    jax.jit,
    static_argnames=(
        "mesh", "n_shards", "m_pad", "f0_max", "l_max", "pn"
    ),
)
def _sharded_closure_check(
    d,
    f0_indptr, f0_vals,
    l_indptr, l_vals,
    int_idx,
    out_indptr, out_vals,
    start, target, is_id, depth,
    *, mesh, n_shards, m_pad, f0_max, l_max, pn,
):
    """allowed, overflow: bool[B]. D replicated; CSRs node-striped over
    'edge'; batch sharded over 'data'."""

    def kernel(
        d, f0_indptr, f0_vals, l_indptr, l_vals, int_idx,
        out_indptr, out_vals,
        start, target, is_id, depth,
    ):
        # shard_map hands each operand with its sharded axes removed of
        # the OTHER shards: leading dim 1 for the edge-sharded arrays
        f0_indptr = f0_indptr[0]
        f0_vals = f0_vals[0]
        l_indptr = l_indptr[0]
        l_vals = l_vals[0]
        int_idx = int_idx[0]
        out_indptr = out_indptr[0]
        out_vals = out_vals[0]
        me = lax.axis_index("edge")
        b = start.shape[0]
        rows = jnp.arange(b, dtype=jnp.int32)
        pad = jnp.int32(m_pad - 1)
        inf16 = jnp.int16(INF_DIST)

        def padded_rows(indptr, vals, nodes, own, width):
            """[b, width] local CSR row gather (PAD where absent) +
            per-row overflow flag."""
            local = (nodes // n_shards).astype(jnp.int32)
            local = jnp.where(own, local, 0)
            off = indptr[local]
            deg = indptr[local + 1] - off
            deg = jnp.where(own, deg, 0)
            j = jnp.arange(width, dtype=jnp.int32)[None, :]
            idx = off[:, None] + j
            valid = j < jnp.minimum(deg, width)[:, None]
            idx = jnp.clip(idx, 0, vals.shape[0] - 1)
            out = jnp.where(valid, vals[idx], pad)
            return out, deg > width

        own_s = (start % n_shards) == me
        f0, f0_over = padded_rows(f0_indptr, f0_vals, start, own_s, f0_max)

        # phase 1: dvec[q, :] = min over F0 row of D rows (scan keeps the
        # [b, f0_max, m_pad] intermediate out of memory)
        def fold(dv, f0_col):
            return jnp.minimum(dv, d[f0_col, :].astype(jnp.int16)), None

        dvec0 = jnp.full((b, m_pad), inf16, dtype=jnp.int16)
        dvec, _ = lax.scan(fold, dvec0, f0.T)
        dvec = lax.pmin(dvec, "edge")

        # phase 2: owner(target) reduces over L
        own_t = (target % n_shards) == me
        l_id, l_over = padded_rows(l_indptr, l_vals, target, own_t, l_max)
        t_local = jnp.where(own_t, (target // n_shards).astype(jnp.int32), 0)
        t_int = int_idx[t_local]
        l_set = jnp.where(
            (t_int >= 0) & own_t, t_int, pad
        )[:, None]
        l_set = jnp.concatenate(
            [l_set, jnp.full((b, l_max - 1), pad, jnp.int32)], axis=1
        )
        l = jnp.where(is_id[:, None], l_id, l_set)
        l_over = l_over & is_id  # set targets never overflow
        picked = dvec[rows[:, None], l]  # [b, l_max]
        best_local = jnp.min(picked, axis=1)
        best_local = jnp.where(own_t | is_id, best_local, inf16)
        best = lax.pmin(best_local, "edge")

        # direct edge: owner(start) binary-searches its full-out CSR row
        # (dst-sorted within row), int32 throughout — vectorized
        # lower_bound over log2(max_degree) fori steps
        s_local = jnp.where(own_s, (start // n_shards).astype(jnp.int32), 0)
        lo0 = out_indptr[s_local]
        hi0 = out_indptr[s_local + 1]
        size = out_vals.shape[0]
        n_steps = max(1, int(np.ceil(np.log2(max(size, 2)))) + 1)

        def bs(_, lohi):
            lo, hi = lohi
            active = lo < hi
            mid = (lo + hi) // 2
            v = out_vals[jnp.clip(mid, 0, size - 1)]
            less = v < target
            lo = jnp.where(active & less, mid + 1, lo)
            hi = jnp.where(active & ~less, mid, hi)
            return lo, hi

        lo, _ = lax.fori_loop(0, n_steps, bs, (lo0, hi0))
        found = (lo < hi0) & (
            out_vals[jnp.clip(lo, 0, size - 1)] == target
        )
        hit_local = own_s & found
        direct = lax.pmax(hit_local.astype(jnp.int8), "edge") > 0

        best32 = best.astype(jnp.int32)
        best32 = jnp.where(best32 >= INF_DIST, jnp.int32(1 << 30), best32)
        extra = is_id.astype(jnp.int32)
        allowed = (direct & (depth >= 1)) | (1 + best32 + extra <= depth)
        overflow = lax.pmax(
            (f0_over | l_over).astype(jnp.int8), "edge"
        ) > 0
        return allowed, overflow

    return shard_map(
        kernel,
        mesh=mesh,
        in_specs=(
            P(),  # D replicated on every device
            P("edge"), P("edge"),  # F0 CSR stripes (leading shard dim)
            P("edge"), P("edge"),  # L CSR stripes
            P("edge"),  # interior-index stripe
            P("edge"), P("edge"),  # full-out CSR stripes (direct probe)
            P("data"), P("data"), P("data"), P("data"),
        ),
        out_specs=(P("data"), P("data")),
        check_vma=False,
    )(
        d, f0_indptr, f0_vals, l_indptr, l_vals, int_idx,
        out_indptr, out_vals,
        start, target, is_id, depth,
    )


def _decode_ids(snap, start, target) -> list:
    """Vocab-decode id pairs back to RelationTuples (the host-oracle
    re-answer of overflow rows and failed batches; the hot path never
    runs this)."""
    vocab = snap.vocab
    n_live = len(vocab)
    out = []
    for s, t in zip(start, target):
        if int(s) < n_live:
            ns, obj, rel = vocab.key(int(s))
        else:  # dummy/unknown start: resolves to no tuples downstream
            ns = obj = rel = ""
        subject = (
            vocab.subject_of(int(t)) if int(t) < n_live else SubjectID(id="")
        )
        out.append(
            RelationTuple(
                namespace=ns, object=obj, relation=rel, subject=subject
            )
        )
    return out


class _ShardedEncodedBatch:
    """A pure-id batch parked between encode and launch on the sharded
    path. Plain numpy arrays (no staging pool — the mesh upload sharding
    re-lays the buffers anyway); carries exactly the attributes the
    circuit breaker's fallback/bisection contract reads (``n``, ``b``,
    ``snap``, ``start``, ``target``, ``depths``, lazy ``requests``)."""

    __slots__ = (
        "_requests", "depths", "n", "b", "snap", "start", "target",
        "depth", "flag",
    )

    def __init__(self, depths, n, b, snap, start, target, flag, depth):
        self._requests = None
        self.depths = depths
        self.n = n
        self.b = b
        self.snap = snap
        self.start = start
        self.target = target
        self.depth = depth
        self.flag = flag

    @property
    def requests(self):
        """Per-item RelationTuples, decoded through the snapshot vocab on
        first access — only the breaker's host-oracle fallback reads this."""
        if self._requests is None:
            self._requests = _decode_ids(
                self.snap, self.start[: self.n], self.target[: self.n]
            )
        return self._requests

    @property
    def version(self) -> int:
        return self.snap.version

    def release(self) -> None:
        """No staging pool on this path — idempotent no-op kept for the
        breaker/batcher release contract."""


class _ShardedLaunched:
    """A dispatched sharded batch: un-materialized device results. JAX
    async dispatch returns as soon as the kernel is enqueued; blocking
    (and overflow escalation) happens in :meth:`ShardedServingEngine.
    decode_launched`. ``garbage`` stands for a numerically sick chip
    (the ``device.batch_nan`` fault site): decode returns non-booleans."""

    __slots__ = ("enc", "allowed", "overflow", "garbage")

    def __init__(self, enc, allowed=None, overflow=None, garbage=False):
        self.enc = enc
        self.allowed = allowed
        self.overflow = overflow
        self.garbage = garbage


class ShardedClosureEngine:
    """ClosureCheckEngine's multi-chip sibling: D replicated, boundary
    CSRs node-striped over the mesh's 'edge' axis, batch data-parallel
    over 'data'. The engine for graphs whose CSRs exceed one device's HBM
    (BASELINE's 1B-tuple v5e-16 rung)."""

    def __init__(
        self,
        snapshots: SnapshotManager,
        mesh: Optional[Mesh] = None,
        max_depth: int = DEFAULT_MAX_DEPTH,
        f0_max: int = 32,
        l_max: int = 32,
        f0_max_escalated: int = 512,
        l_max_escalated: int = 512,
        fallback=None,
    ):
        self.snapshots = snapshots
        self.mesh = mesh if mesh is not None else make_mesh()
        self.global_max_depth = max_depth
        self.f0_max = f0_max
        self.l_max = l_max
        # second-pass gather widths for the wide-fanout tail (a user in
        # hundreds of groups): wide enough that host fallback is a
        # measurable-rarity, narrow enough that the escalated kernel's
        # scan stays cheap for the small overflow sub-batches
        self.f0_max_escalated = f0_max_escalated
        self.l_max_escalated = l_max_escalated
        self.n_data = self.mesh.shape["data"]
        self.n_edge = self.mesh.shape["edge"]
        self._lock = threading.Lock()
        self._resident = None  # (snap, device arrays..., shard_bytes)
        self._fallback = fallback
        # overflow accounting: rows seen / escalated to the wide pass /
        # beyond even that (host oracle) — the bench and dryrun log these
        self.overflow_stats = {
            "rows": 0, "escalated": 0, "host_fallback": 0,
        }

    def fallback_engine(self):
        if self._fallback is None:
            self._fallback = CheckEngine(
                self.snapshots.store, max_depth=self.global_max_depth
            )
        return self._fallback

    # -- residency -------------------------------------------------------------

    def _build_resident(self, snap: GraphSnapshot):
        ig = build_interior(snap)
        n = self.n_edge
        pn = snap.padded_nodes
        m_pad = -(-(ig.m + 1) // 256) * 256
        packed = pack_adjacency(ig.ii_src, ig.ii_dst, m_pad)
        d = build_closure_packed(
            jnp.asarray(packed), jnp.int32(ig.m),
            m_pad=m_pad, k_max=self.global_max_depth - 1,
        )
        f0_ip, f0_v, _ = _stripe_csr(
            ig.set_out_indptr, ig.set_out_vals, pn, n
        )
        l_ip, l_v, _ = _stripe_csr(ig.id_in_indptr, ig.id_in_vals, pn, n)
        int_idx = _stripe_vector(ig.interior_index, pn, n, -1)
        # direct-edge probe structure: full-out CSR (all successors by
        # src) with dsts SORTED within each row — int32 binary search,
        # no 64-bit packed keys (they overflow int32 at 1B nodes and jax
        # without x64 silently downcasts int64 device arrays)
        e = snap.num_edges
        src = snap.src[:e]
        dst = snap.dst[:e]
        order = np.lexsort((dst, src))
        counts = np.bincount(src, minlength=pn)
        full_indptr = np.zeros(pn + 1, dtype=np.int64)
        np.cumsum(counts, out=full_indptr[1:])
        out_ip, out_v, _ = _stripe_csr(
            full_indptr.astype(np.int64), dst[order], pn, n
        )

        mesh = self.mesh
        edge_sh = NamedSharding(mesh, P("edge"))
        repl = NamedSharding(mesh, P())
        shard_bytes = {
            "d_replicated": int(m_pad) * int(m_pad),
            "f0_indptr": f0_ip.nbytes // n,
            "f0_vals": f0_v.nbytes // n,
            "l_indptr": l_ip.nbytes // n,
            "l_vals": l_v.nbytes // n,
            "interior_index": int_idx.nbytes // n,
            "out_indptr": out_ip.nbytes // n,
            "out_vals": out_v.nbytes // n,
        }
        shard_bytes["total_per_shard"] = sum(shard_bytes.values())
        resident = (
            snap,
            ig,
            m_pad,
            jax.device_put(d, repl),
            jax.device_put(f0_ip, edge_sh),
            jax.device_put(f0_v, edge_sh),
            jax.device_put(l_ip, edge_sh),
            jax.device_put(l_v, edge_sh),
            jax.device_put(int_idx, edge_sh),
            jax.device_put(out_ip, edge_sh),
            jax.device_put(out_v, edge_sh),
            shard_bytes,
        )
        return resident

    def _residency(self, snap: GraphSnapshot):
        with self._lock:
            r = self._resident
            if r is not None and r[0] is snap:
                return r
            r = self._build_resident(snap)
            self._resident = r
            return r

    def shard_bytes(self) -> dict:
        """Per-shard residency byte accounting (bench/dryrun logging)."""
        r = self._residency(self.snapshots.snapshot())
        return dict(r[-1])

    # -- query -----------------------------------------------------------------

    def _bucket_batch(self, k: int) -> int:
        per_device = -(-max(k, 8) // self.n_data)
        per_device = 1 << (per_device - 1).bit_length()
        return per_device * self.n_data

    def check_ids(
        self,
        start: np.ndarray,
        target: np.ndarray,
        is_id: Optional[np.ndarray] = None,
        depths: Optional[np.ndarray] = None,
    ) -> np.ndarray:
        """allowed: bool[n] for id pairs, through encode/launch/decode.
        Target kinds come from the snapshot vocab (``is_id`` is accepted
        for signature parity: a known node's kind is its vocab key's, and
        an unknown one clamps to the dummy, denied either way)."""
        del is_id
        if len(start) == 0:
            return np.zeros(0, dtype=bool)
        enc = self.encode_ids(start, target, depths)
        return np.asarray(self.decode_launched(self.launch_encoded(enc)))

    # -- split encode/launch/decode --------------------------------------------

    def encode_ids(self, start, target, depths=None):
        return self.encode_ids_at(
            self.snapshots.snapshot(), start, target, depths
        )

    def encode_ids_at(self, snap, start, target, depths=None):
        start = np.asarray(start, dtype=np.int64)
        target = np.asarray(target, dtype=np.int64)
        n = len(start)
        b = self._bucket_batch(max(n, 1))
        pn = snap.padded_nodes
        dummy = snap.dummy_node
        gmax = self.global_max_depth
        s = np.full(b, dummy, dtype=np.int32)
        t = np.full(b, dummy, dtype=np.int32)
        dp = np.ones(b, dtype=np.int32)
        flag = np.zeros(b, dtype=bool)
        s[:n] = np.where((start < 0) | (start >= pn), dummy, start)
        t[:n] = np.where((target < 0) | (target >= pn), dummy, target)
        if depths is None:
            dp[:n] = gmax
        else:
            want = np.asarray(depths, dtype=np.int32)
            dp[:n] = np.where((want <= 0) | (want > gmax), gmax, want)
        is_set = snap.vocab.is_set_array()
        if len(is_set):
            safe = np.clip(t[:n], 0, len(is_set) - 1)
            flag[:n] = ~is_set[safe]
        else:
            # empty vocab (boot warmup before any write): every target
            # is an unknown id — clamped to dummy and denied anyway
            flag[:n] = True
        return _ShardedEncodedBatch(
            dp[:n].tolist(), n, b, snap, s, t, flag, dp
        )

    def launch_encoded(self, enc: _ShardedEncodedBatch) -> _ShardedLaunched:
        r = self._residency(enc.snap)
        allowed, overflow = self._device_pass(
            r, enc.start, enc.target, enc.flag, enc.depth,
            self.f0_max, self.l_max,
        )
        return _ShardedLaunched(enc, allowed, overflow)

    def decode_launched(self, launched: _ShardedLaunched) -> list[bool]:
        enc = launched.enc
        n = enc.n
        allowed = np.asarray(launched.allowed)[:n].copy()
        overflow = np.asarray(launched.overflow)[:n]
        self.overflow_stats["rows"] += n
        r = self._residency(enc.snap)
        allowed = self._resolve_overflow(
            r, enc.snap, allowed, overflow,
            enc.start, enc.target, enc.flag, enc.depth, n,
        )
        return allowed.tolist()

    def _device_pass(self, r, sv, tv, fv, dv, f0_w, l_w):
        """Dispatch the sharded kernel; returns un-materialized device
        arrays (async — materialization blocks in the caller)."""
        (
            snap, _ig, m_pad, d,
            f0_ip, f0_v, l_ip, l_v, int_idx, out_ip, out_v, _bytes,
        ) = r
        data_sh = NamedSharding(self.mesh, P("data"))
        return _sharded_closure_check(
            d, f0_ip, f0_v, l_ip, l_v, int_idx, out_ip, out_v,
            jax.device_put(sv, data_sh),
            jax.device_put(tv, data_sh),
            jax.device_put(fv, data_sh),
            jax.device_put(dv, data_sh),
            mesh=self.mesh,
            n_shards=self.n_edge,
            m_pad=m_pad,
            f0_max=f0_w,
            l_max=l_w,
            pn=snap.padded_nodes,
        )

    def _resolve_overflow(
        self, r, snap, allowed, overflow, s, t, flag, depth, n
    ) -> np.ndarray:
        """Rows whose fan-out overflowed the narrow pass: a SECOND device
        pass at escalated gather widths (a user in hundreds of groups is
        ordinary in team-heavy graphs), then the exact host oracle for
        rows overflowing those too (dummy/unknown endpoints decode to
        inert empties the oracle denies, matching the clamp semantics).
        ``overflow_stats`` counts both tiers."""
        if overflow.any():
            idxs = np.nonzero(overflow)[0]
            self.overflow_stats["escalated"] += len(idxs)
            k = len(idxs)
            dummy = snap.dummy_node
            b2 = self._bucket_batch(k)
            s2 = np.full(b2, dummy, dtype=np.int32)
            t2 = np.full(b2, dummy, dtype=np.int32)
            flag2 = np.zeros(b2, dtype=bool)
            depth2 = np.ones(b2, dtype=np.int32)
            s2[:k], t2[:k] = s[idxs], t[idxs]
            flag2[:k], depth2[:k] = flag[idxs], depth[idxs]
            allowed2, overflow2 = self._device_pass(
                r, s2, t2, flag2, depth2,
                self.f0_max_escalated, self.l_max_escalated,
            )
            allowed[idxs] = np.asarray(allowed2)[:k]
            overflow = np.zeros(n, dtype=bool)
            overflow[idxs[np.asarray(overflow2)[:k]]] = True
        if overflow.any():
            fb = self.fallback_engine()
            idxs = np.nonzero(overflow)[0]
            self.overflow_stats["host_fallback"] += len(idxs)
            reqs = _decode_ids(snap, s[idxs], t[idxs])
            res = fb.batch_check(
                reqs, depths=[int(depth[i]) for i in idxs]
            )
            allowed[idxs] = res
        return allowed

    def batch_check(
        self,
        requests: Sequence[RelationTuple],
        max_depth: int = 0,
        depths: Optional[Sequence[int]] = None,
    ) -> list[bool]:
        if not requests:
            return []
        snap = self.snapshots.snapshot()
        pn = snap.padded_nodes
        dummy = snap.dummy_node
        skeys = [(r.namespace, r.object, r.relation) for r in requests]
        tkeys = [
            (s.id,) if not isinstance(s, SubjectSet)
            else (s.namespace, s.object, s.relation)
            for s in (r.subject for r in requests)
        ]
        s_ids = snap.vocab.lookup_bulk(skeys)
        t_ids = snap.vocab.lookup_bulk(tkeys)
        start = np.where((s_ids < 0) | (s_ids >= pn), dummy, s_ids)
        target = np.where((t_ids < 0) | (t_ids >= pn), dummy, t_ids)
        if depths is not None:
            want = np.asarray(depths, dtype=np.int32)
        else:
            want = np.full(len(requests), max_depth, dtype=np.int32)
        return self.check_ids(start, target, depths=want).tolist()

    def subject_is_allowed(
        self, requested: RelationTuple, max_depth: int = 0
    ) -> bool:
        return self.batch_check([requested], max_depth)[0]