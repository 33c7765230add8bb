"""Edge-partitioned batched check over a device mesh.

Sharding layout (how-to-scale-your-model recipe: pick a mesh, annotate
shardings, let XLA insert collectives):

- mesh axes ``("data", "edge")``: requests are data-parallel over ``data``;
  the COO edge arrays are partitioned over ``edge`` (each device holds
  E/n_edge edges — the CSR-in-HBM scale axis, BASELINE.md's 1B-tuple
  configuration).
- The frontier ``F[B_local, N]`` is replicated along ``edge``. One expansion
  step: every device propagates its local edges (gather/scatter on its
  shard), then a ``jax.lax.pmax`` over the ``edge`` axis ORs the partial
  successor sets — the collective rides ICI, nothing touches the host.
- The early-exit while_loop runs inside shard_map, so an entire depth-5
  check batch is one XLA program with 5 pmax collectives, fused.

Tenant (network-id) isolation stays what it is on one chip: separate
snapshots per store; a tenant's arrays never mix with another's.
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

from ..engine.check import DEFAULT_MAX_DEPTH
from ..graph.snapshot import GraphSnapshot, SnapshotManager
from ..relationtuple.definitions import RelationTuple, SubjectSet


def make_mesh(
    devices=None, data: int = 1, edge: Optional[int] = None
) -> Mesh:
    """(data, edge) mesh over the given devices (default: all)."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if edge is None:
        edge = n // data
    if data * edge != n:
        raise ValueError(f"mesh {data}x{edge} != {n} devices")
    arr = np.array(devices).reshape(data, edge)
    return Mesh(arr, axis_names=("data", "edge"))


def _local_propagate(f, src, dst, padded_nodes: int, edge_chunk: int):
    """Successor set of `f` along this device's edge shard."""
    n_chunks = src.shape[0] // edge_chunk

    if n_chunks <= 1:
        vals = jnp.take(f, src, axis=1)
        p = jnp.zeros_like(f).at[:, dst].max(vals)
    else:
        def step(p, k):
            s = lax.dynamic_slice(src, (k * edge_chunk,), (edge_chunk,))
            d = lax.dynamic_slice(dst, (k * edge_chunk,), (edge_chunk,))
            vals = jnp.take(f, s, axis=1)
            return p.at[:, d].max(vals), None

        p, _ = lax.scan(
            step, jnp.zeros_like(f), jnp.arange(n_chunks, dtype=jnp.int32)
        )
    return p.at[:, padded_nodes - 1].set(False)


@partial(
    jax.jit,
    static_argnames=("mesh", "padded_nodes", "edge_chunk", "max_steps"),
)
def sharded_check(
    src, dst, start, target, depth, *, mesh, padded_nodes, edge_chunk, max_steps
):
    """allowed: bool[B] — edges sharded over mesh axis 'edge', batch sharded
    over 'data', frontier exchange via pmax per step."""

    def kernel(src, dst, start, target, depth):
        batch = start.shape[0]
        f = (
            jnp.arange(padded_nodes, dtype=jnp.int32)[None, :]
            == start[:, None]
        )
        rows = jnp.arange(batch, dtype=jnp.int32)

        def cond(state):
            i, f, hit, done = state
            return jnp.logical_and(i < max_steps, ~jnp.all(done))

        def body(state):
            i, f, hit, done = state
            local = _local_propagate(f, src, dst, padded_nodes, edge_chunk)
            # OR partial successor sets across edge shards (ICI collective)
            p = lax.pmax(local.astype(jnp.int8), "edge").astype(bool)
            newly = jnp.logical_and(p, ~f)
            changed = jnp.any(newly, axis=1)
            reached = p[rows, target]
            hit = jnp.logical_or(hit, jnp.logical_and(reached, i < depth))
            f = jnp.logical_or(f, p)
            done = jnp.logical_or(done, hit)
            done = jnp.logical_or(done, ~changed)
            done = jnp.logical_or(done, (i + 1) >= depth)
            return i + 1, f, hit, done

        hit0 = jnp.zeros((batch,), dtype=bool)
        done0 = jnp.zeros((batch,), dtype=bool)
        _, _, hit, _ = lax.while_loop(
            cond, body, (jnp.int32(0), f, hit0, done0)
        )
        return hit

    return shard_map(
        kernel,
        mesh=mesh,
        in_specs=(P("edge"), P("edge"), P("data"), P("data"), P("data")),
        out_specs=P("data"),
        check_vma=False,
    )(src, dst, start, target, depth)


class ShardedCheckEngine:
    """DeviceCheckEngine's multi-chip sibling: same contract, edges spread
    over the mesh. Use when the graph exceeds one device's HBM or check
    volume exceeds one chip's throughput."""

    def __init__(
        self,
        snapshots: SnapshotManager,
        mesh: Optional[Mesh] = None,
        max_depth: int = DEFAULT_MAX_DEPTH,
    ):
        self.snapshots = snapshots
        self.mesh = mesh if mesh is not None else make_mesh()
        self.global_max_depth = max_depth
        self._lock = threading.Lock()
        self._cached = None  # (host_src_id, host_dst_id, dev_src, dev_dst)
        self.n_data = self.mesh.shape["data"]
        self.n_edge = self.mesh.shape["edge"]

    def _device_arrays(self, snap: GraphSnapshot):
        with self._lock:
            cached = self._cached
            if (
                cached is not None
                and cached[0] is snap.src
                and cached[1] is snap.dst
            ):
                return cached[2], cached[3]
            edge_sharding = NamedSharding(self.mesh, P("edge"))
            dev_src = jax.device_put(snap.src, edge_sharding)
            dev_dst = jax.device_put(snap.dst, edge_sharding)
            self._cached = (snap.src, snap.dst, dev_src, dev_dst)
            return dev_src, dev_dst

    def _bucket_batch(self, n: int) -> int:
        # batch must divide evenly across the data axis: bucket the
        # per-device slice to a power of two, then multiply back out (works
        # for any n_data, including non-powers of two)
        per_device = -(-max(n, 8) // self.n_data)
        per_device = 1 << (per_device - 1).bit_length()
        return per_device * self.n_data

    def batch_check(
        self,
        requests: Sequence[RelationTuple],
        max_depth: int = 0,
        depths: Optional[Sequence[int]] = None,
    ) -> list[bool]:
        if not requests:
            return []
        snap = self.snapshots.snapshot()
        n = len(requests)
        # encode via the vocab's vectorized hash index (same path as the
        # closure engine) — no per-request Python in the hot loop
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
            want = np.full(n, max_depth, dtype=np.int32)
        return self.check_ids(start, target, depths=want).tolist()

    def check_ids(
        self,
        start: np.ndarray,
        target: np.ndarray,
        is_id: Optional[np.ndarray] = None,
        depths: Optional[np.ndarray] = None,
    ) -> np.ndarray:
        """Array-native sharded check: vocab-encoded node ids in, bool[n]
        out — the same contract as ClosureCheckEngine.check_ids (is_id is
        accepted for interface parity; the lockstep BFS treats id and set
        targets uniformly). Unknown/overflow ids clamp to the inert dummy
        node, which can neither reach nor be reached."""
        del is_id
        start = np.asarray(start, dtype=np.int64)
        if len(start) == 0:
            return np.zeros(0, dtype=bool)
        target = np.asarray(target, dtype=np.int64)
        snap = self.snapshots.snapshot()
        dev_src, dev_dst = self._device_arrays(snap)
        n = len(start)
        b = self._bucket_batch(n)
        dummy = snap.dummy_node
        gmax = self.global_max_depth
        s = np.full(b, dummy, dtype=np.int32)
        t = np.full(b, dummy, dtype=np.int32)
        depth = np.ones(b, dtype=np.int32)
        s[:n] = np.where(start >= snap.padded_nodes, dummy, start)
        t[:n] = np.where(target >= snap.padded_nodes, dummy, target)
        if depths is None:
            depth[:n] = gmax
        else:
            want = np.asarray(depths, dtype=np.int32)
            depth[:n] = np.where((want <= 0) | (want > gmax), gmax, want)
        data_sharding = NamedSharding(self.mesh, P("data"))
        local_edges = snap.padded_edges // self.n_edge
        chunk = local_edges
        while chunk > 1024 and (b // self.n_data) * chunk > (1 << 23):
            chunk //= 2
        hit = sharded_check(
            dev_src,
            dev_dst,
            jax.device_put(s, data_sharding),
            jax.device_put(t, data_sharding),
            jax.device_put(depth, data_sharding),
            mesh=self.mesh,
            padded_nodes=snap.padded_nodes,
            edge_chunk=chunk,
            max_steps=self.global_max_depth,
        )
        return np.asarray(hit)[:n].copy()

    def subject_is_allowed(
        self, requested: RelationTuple, max_depth: int = 0
    ) -> bool:
        return self.batch_check([requested], max_depth)[0]

    def warmup(self, batch: int = 1) -> None:
        """Compile the sharded kernel at production batch buckets."""
        dummy = RelationTuple(
            namespace="", object="", relation="",
            subject=SubjectSet(namespace="", object="", relation=""),
        )
        batch = max(1, batch)
        self.batch_check([dummy] * batch)
        if self._bucket_batch(batch) != self._bucket_batch(1):
            self.batch_check([dummy])
