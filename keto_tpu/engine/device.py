"""Device-backed check/expand engines.

``DeviceCheckEngine`` answers the same contract as the host ``CheckEngine``
(reference Engine.SubjectIsAllowed, internal/check/engine.go:116-123) but
evaluates whole batches on the accelerator: requests are vocab-encoded to
(start, target, depth) int32 triples, padded to a batch bucket, and handed to
the jitted frontier kernels (keto_tpu.ops.frontier). Depth clamping matches
the reference (global serve.read.max-depth wins when smaller or when the
request depth is <= 0).

``SnapshotExpandEngine`` builds the same union/leaf subject tree as the host
expand engine (reference internal/expand/engine.go:33-102) but walks the
resident CSR arrays instead of issuing per-node paginated store queries —
the traversal itself is host-side (tree materialization is inherently a
host-shaped output), yet touches no store pages.

Freshness: engines read through a SnapshotManager, so every answer is at
least as fresh as the store version at call time — the version is the
snaptoken the reference never implemented (its Check returns
`snaptoken: "not yet implemented"`, internal/check/handler.go:168-184).
"""

from __future__ import annotations

import threading
from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np

from ..faults import FAULTS
from ..graph.snapshot import GraphSnapshot, SnapshotManager, _bucket
from ..telemetry.attribution import ledger_mark
from ..telemetry.devstats import DEVSTATS
from ..ops.frontier import (
    batched_check_dense,
    batched_check_scatter,
    batched_distances_dense,
    batched_distances_scatter,
    build_dense_adjacency,
    pick_edge_chunk,
)
from ..relationtuple.definitions import (
    RelationTuple,
    Subject,
    SubjectID,
    SubjectSet,
)
from .check import DEFAULT_MAX_DEPTH, clamp_depth
from .expand import (
    FALLBACK_PAGE_SIZE,
    ExpandPage,
    decode_expand_page_token,
    encode_expand_page_token,
)
from .tree import Tree, NodeType

_MIN_BATCH = 8
_DENSE_THRESHOLD_DEFAULT = 8192  # adj = bf16 N*N: 8192^2 = 128 MiB in HBM
_PACKED_MIN_BATCH = 4096  # bitpacked kernel: W = B/32 int32 lanes, W % 128 == 0


def _bucket_batch(b: int) -> int:
    return _bucket(b, _MIN_BATCH)


def _decode_ids(snap, start, target) -> list:
    """Vocab-decode id pairs back to RelationTuples (fallback paths of
    pre-encoded batches only; the hot path never runs this)."""
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


class EncodedBatch:
    """A vocab-encoded batch parked between pipeline stages: staging
    buffers filled, kernel not yet dispatched. Holds the original requests
    (or, on the columnar path, the raw columns) so a downstream failure
    (circuit breaker) can re-answer exactly this batch through the host
    oracle — columnar batches materialize their ``RelationTuple`` objects
    lazily, ONLY if that fallback actually fires."""

    __slots__ = (
        "_requests", "_cols", "depths", "deadlines", "n", "b", "snap", "dg",
        "start", "target", "depth",
    )

    def __init__(
        self, requests, depths, n, b, snap, dg, start, target, depth,
        cols=None,
    ):
        self._requests = requests
        self._cols = cols
        self.depths = depths
        # per-row absolute caller deadlines (monotonic secs), stamped by
        # the batcher after encode; the breaker fallback skips re-answering
        # rows whose entry here has passed
        self.deadlines = None
        self.n = n
        self.b = b
        self.snap = snap
        self.dg = dg
        self.start = start
        self.target = target
        self.depth = depth

    @property
    def requests(self):
        """Per-item RelationTuples. Columnar batches build them here on
        first access — the hot path (launch/decode) never reads this.
        Pure-id batches (check_ids) decode through the snapshot vocab."""
        if self._requests is None:
            if self._cols is not None:
                self._requests = self._cols.materialize()
            else:
                self._requests = _decode_ids(
                    self.snap, self.start[: self.n], self.target[: self.n]
                )
        return self._requests

    @property
    def version(self) -> int:
        return self.snap.version

    def keys(self) -> list[tuple[int, int, int]]:
        """Per-request (start, target, depth) id triples — the
        snapshot-versioned encoded-request cache key."""
        n = self.n
        return list(
            zip(
                self.start[:n].tolist(),
                self.target[:n].tolist(),
                self.depth[:n].tolist(),
            )
        )

    def compact(self, keep: Sequence[int]) -> None:
        """Shrink to the `keep` rows (increasing indices) in place —
        encoded-cache hits drop out before the kernel ever sees them.
        Freed tail rows are reset to the inert padding state."""
        m = len(keep)
        if m == self.n:
            return
        idx = np.asarray(keep, dtype=np.int64)
        self.start[:m] = self.start[idx]
        self.target[:m] = self.target[idx]
        self.depth[:m] = self.depth[idx]
        dummy = self.dg.dummy
        self.start[m : self.n] = dummy
        self.target[m : self.n] = dummy
        self.depth[m : self.n] = 0 if self.dg.mode == "packed" else 1
        if self._requests is not None:
            self._requests = [self._requests[i] for i in keep]
        if self._cols is not None:
            self._cols = self._cols.select(keep)
        if self.depths is not None:
            self.depths = [self.depths[i] for i in keep]
        if self.deadlines is not None:
            self.deadlines = [self.deadlines[i] for i in keep]
        self.n = m

    def release(self) -> None:
        """Return the staging buffers to the per-bucket free-list (idempotent)."""
        if self.start is not None:
            self.dg.return_staging((self.start, self.target, self.depth))
            self.start = self.target = self.depth = None


class LaunchedBatch:
    """A dispatched batch: the un-materialized device result. JAX async
    dispatch means constructing this returns as soon as the kernel is
    enqueued; blocking happens in decode (np.asarray)."""

    __slots__ = ("enc", "hit", "garbage")

    def __init__(self, enc: EncodedBatch, hit=None, garbage: bool = False):
        self.enc = enc
        self.hit = hit
        self.garbage = garbage


class _DeviceGraph:
    """Per-snapshot device residency: uploaded COO arrays, dense adjacency,
    or dst-sorted edges for the bitpacked DMA kernel (``packed`` mode).

    Also owns the per-bucket staging buffers for the pipelined dispatch
    path: the (start, target, depth) int32 arrays a batch is encoded into
    are allocated once per (bucket, snapshot) and recycled through a bounded
    free-list instead of np.full-allocated per batch. The dummy fill value
    is snapshot-dependent (padded_nodes - 1), which is why the buffers live
    here and not on the engine: a snapshot swap naturally retires them."""

    # free-list depth per bucket: bounds idle memory at (pipeline depth + a
    # couple of concurrent caller-assembled batches) — beyond that a fresh
    # allocation is cheaper than holding the arrays forever
    _STAGING_KEEP = 8

    def __init__(self, snap: GraphSnapshot, mode: str):
        self.host_src = snap.src  # identity keys for the residency cache:
        self.host_dst = snap.dst  # equal arrays => equal device contents
        self.padded_nodes = snap.padded_nodes
        self.padded_edges = snap.padded_edges
        self.dummy = snap.dummy_node
        self.mode = mode
        self.adj = self.src = self.dst = None
        self.src_by_dst = self.dst_by_dst = None
        self._staging_lock = threading.Lock()
        self._staging: dict[int, list] = {}
        if mode == "dense":
            self.adj = build_dense_adjacency(
                jnp.asarray(snap.src), jnp.asarray(snap.dst), snap.padded_nodes
            )
        elif mode == "packed":
            # the DMA kernel streams edges in in-CSR (dst-sorted) order so
            # destination windows flush once; padding edges (dummy->dummy)
            # sort to the tail, which is harmless — the dummy row is inert
            e = snap.num_edges
            order = np.argsort(snap.dst[:e], kind="stable")
            self.src_by_dst = jnp.asarray(snap.src[:e][order])
            self.dst_by_dst = jnp.asarray(snap.dst[:e][order])
        else:
            self.src = jnp.asarray(snap.src)
            self.dst = jnp.asarray(snap.dst)

    @property
    def dense(self) -> bool:
        return self.mode == "dense"

    def checkout_staging(
        self, b: int
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(start, target, depth) int32[b] buffers, reset to the inert
        state (start/target = dummy, depth = 1) so stale rows from the
        previous batch can never leak past the new batch's length."""
        with self._staging_lock:
            pool = self._staging.get(b)
            bufs = pool.pop() if pool else None
        if bufs is None:
            return (
                np.full(b, self.dummy, dtype=np.int32),
                np.full(b, self.dummy, dtype=np.int32),
                np.ones(b, dtype=np.int32),
            )
        start, target, depth = bufs
        start.fill(self.dummy)
        target.fill(self.dummy)
        depth.fill(1)
        return start, target, depth

    def return_staging(self, bufs) -> None:
        b = len(bufs[0])
        with self._staging_lock:
            pool = self._staging.setdefault(b, [])
            if len(pool) < self._STAGING_KEEP:
                pool.append(bufs)


class DeviceCheckEngine:
    def __init__(
        self,
        snapshots: SnapshotManager,
        max_depth: int = DEFAULT_MAX_DEPTH,
        mode: str = "auto",  # auto | dense | scatter | packed
        dense_threshold: int = _DENSE_THRESHOLD_DEFAULT,
        interpret: Optional[bool] = None,
    ):
        self.snapshots = snapshots
        self.global_max_depth = max_depth
        self.mode = mode
        self.dense_threshold = dense_threshold
        self._lock = threading.Lock()
        self._cached: Optional[_DeviceGraph] = None
        self._scatter_companion: Optional[_DeviceGraph] = None
        if interpret is None:
            # the packed kernel is Mosaic/TPU; elsewhere (CPU test meshes)
            # it runs in pallas interpret mode
            import jax

            interpret = jax.default_backend() != "tpu"
        self.interpret = interpret

    # -- device residency ----------------------------------------------------

    def _device_graph(self, snap: GraphSnapshot) -> _DeviceGraph:
        with self._lock:
            cached = self._cached
            # keyed on edge-array identity, not snapshot identity: version-only
            # snapshots (duplicate writes) share arrays and must not trigger a
            # re-upload or dense-adjacency rebuild
            if (
                cached is not None
                and cached.host_src is snap.src
                and cached.host_dst is snap.dst
            ):
                return cached
            if self.mode in ("dense", "scatter", "packed"):
                mode = self.mode
            else:
                mode = (
                    "dense"
                    if snap.padded_nodes <= self.dense_threshold
                    else "scatter"
                )
            dg = _DeviceGraph(snap, mode)
            self._cached = dg
            return dg

    def reset_residency(self) -> None:
        """Drop every device-resident artifact: the uploaded edge arrays /
        dense adjacency, the staging free-lists hanging off them, and the
        packed mode's scatter companion. The next dispatch re-uploads from
        the live snapshot. This is the device-lost recovery seam — after a
        backend teardown/re-init the old buffers belong to a dead client
        and must never be touched again."""
        with self._lock:
            self._cached = None
            self._scatter_companion = None

    def warmup(self, batch: int = 1) -> None:
        """Compile the kernel for the current snapshot shape at production
        batch buckets (first XLA compile is tens of seconds; serve paths
        call this at boot so live traffic never pays it). Warms the `batch`
        bucket — the configured maximum — and the smallest bucket, which
        light traffic hits."""
        dummy = RelationTuple(
            namespace="", object="", relation="",
            subject=SubjectSet(namespace="", object="", relation=""),
        )
        batch = max(1, batch)
        self.batch_check([dummy] * batch)
        if _bucket_batch(batch) != _bucket_batch(1):
            self.batch_check([dummy])

    def subject_is_allowed(
        self, requested: RelationTuple, max_depth: int = 0
    ) -> bool:
        return self.batch_check([requested], max_depth)[0]

    def batch_check(
        self,
        requests: Sequence[RelationTuple],
        max_depth: int = 0,
        depths: Optional[Sequence[int]] = None,
    ) -> list[bool]:
        """Evaluate a batch; `depths` (per-request) overrides `max_depth`.
        Serial composition of the pipeline stages — one batch in flight."""
        if not requests:
            return []
        return self.decode_launched(
            self.launch_encoded(self.encode_batch(requests, max_depth, depths))
        )

    # -- pipelined dispatch: encode -> launch -> decode ----------------------
    #
    # The three stages batch_check used to run serially, split so a
    # pipelined caller (engine/batcher.py) can overlap them: encode batch
    # N+1 on host threads while batch N's kernel runs (JAX async dispatch
    # returns at enqueue), and materialize batch N-1's result (the only
    # blocking step) off the critical path.

    def encode_batch(
        self,
        requests: Sequence[RelationTuple],
        max_depth: int = 0,
        depths: Optional[Sequence[int]] = None,
    ) -> EncodedBatch:
        """Stage 1 (host, parallelizable): vocab-encode into persistent
        per-(bucket, snapshot) staging buffers."""
        snap = self.snapshots.snapshot()
        dg = self._device_graph(snap)
        n = len(requests)
        b = (
            _PACKED_MIN_BATCH * ((n + _PACKED_MIN_BATCH - 1) // _PACKED_MIN_BATCH)
            if dg.mode == "packed"  # W = B/32 lanes must fill 128-lane tiles
            else _bucket_batch(n)
        )
        dummy = snap.dummy_node
        start, target, depth = dg.checkout_staging(b)
        snap.encode_requests(requests, out_start=start, out_target=target)
        gmax = self.global_max_depth
        if depths is not None:
            want = np.asarray(depths, dtype=np.int32)
        else:
            want = np.full(n, max_depth, dtype=np.int32)
        depth[:n] = np.where((want <= 0) | (want > gmax), gmax, want)
        # clamped per-request depths, captured before the packed-mode dummy
        # override: the breaker's host-oracle re-answer needs the real ones
        fb_depths = depth[:n].tolist()
        if dg.mode == "packed":
            # unknown-node contract: a dummy start must not "reach" the
            # dummy target through the shared dummy row — force depth 0
            depth[:n] = np.where(
                (start[:n] == dummy) | (target[:n] == dummy), 0, depth[:n]
            )
            depth[n:] = 0
        return EncodedBatch(
            list(requests), fb_depths, n, b, snap, dg, start, target, depth,
        )

    def encode_columns(
        self,
        cols,
        max_depth: int = 0,
        depths: Optional[Sequence[int]] = None,
    ) -> EncodedBatch:
        """Columnar stage 1: a ``CheckColumns`` batch vocab-encodes straight
        from its parallel string lists into the staging buffers — no
        ``RelationTuple``/``Subject`` objects on the hot path (they
        materialize lazily only if the breaker fallback needs them)."""
        snap = self.snapshots.snapshot()
        dg = self._device_graph(snap)
        n = len(cols)
        b = (
            _PACKED_MIN_BATCH * ((n + _PACKED_MIN_BATCH - 1) // _PACKED_MIN_BATCH)
            if dg.mode == "packed"
            else _bucket_batch(n)
        )
        dummy = snap.dummy_node
        start, target, depth = dg.checkout_staging(b)
        snap.encode_requests_columnar(cols, out_start=start, out_target=target)
        gmax = self.global_max_depth
        if depths is not None:
            want = np.asarray(depths, dtype=np.int32)
        else:
            want = np.full(n, max_depth, dtype=np.int32)
        depth[:n] = np.where((want <= 0) | (want > gmax), gmax, want)
        fb_depths = depth[:n].tolist()
        if dg.mode == "packed":
            depth[:n] = np.where(
                (start[:n] == dummy) | (target[:n] == dummy), 0, depth[:n]
            )
            depth[n:] = 0
        return EncodedBatch(
            None, fb_depths, n, b, snap, dg, start, target, depth, cols=cols,
        )

    def batch_check_columns(
        self,
        cols,
        max_depth: int = 0,
        depths: Optional[Sequence[int]] = None,
    ) -> list[bool]:
        """Serial columnar dispatch — the zero-object twin of batch_check."""
        if not len(cols):
            return []
        return self.decode_launched(
            self.launch_encoded(self.encode_columns(cols, max_depth, depths))
        )

    def check_ids(
        self,
        start,
        target,
        is_id=None,
        depths: Optional[Sequence[int]] = None,
    ) -> np.ndarray:
        """Array-native check over pre-encoded vocab ids: bool[n] out,
        zero per-request Python (the frontier kernels don't distinguish
        subject-id from subject-set targets, so ``is_id`` is accepted for
        signature parity with the closure engine and ignored). Unknown or
        beyond-snapshot ids are clamped to the inert dummy node."""
        n = len(start)
        if n == 0:
            return np.zeros(0, dtype=bool)
        enc = self.encode_ids(start, target, depths)
        return np.asarray(
            self.decode_launched(self.launch_encoded(enc)), dtype=bool
        )

    def encode_ids(
        self,
        start,
        target,
        depths: Optional[Sequence[int]] = None,
    ) -> EncodedBatch:
        """Stage 1 for pre-encoded id batches (check_batch_encoded): the
        ids go straight into staging — no vocab probe at all."""
        return self.encode_ids_at(
            self.snapshots.snapshot(), start, target, depths
        )

    def encode_ids_at(
        self,
        snap: GraphSnapshot,
        start,
        target,
        depths: Optional[Sequence[int]] = None,
    ) -> EncodedBatch:
        """encode_ids pinned to an explicit snapshot. Node ids are only
        meaningful against the vocab that produced them (the dummy id in
        particular is ``padded_nodes - 1``, which moves as the graph
        grows), so the OOM-bisection retry in engine/fallback.py re-encodes
        its halves against the *parent batch's* snapshot — never a fresh
        one."""
        dg = self._device_graph(snap)
        n = len(start)
        b = (
            _PACKED_MIN_BATCH * ((n + _PACKED_MIN_BATCH - 1) // _PACKED_MIN_BATCH)
            if dg.mode == "packed"
            else _bucket_batch(n)
        )
        dummy = snap.dummy_node
        pn = snap.padded_nodes
        st, tg, dp = dg.checkout_staging(b)
        s = np.asarray(start, dtype=np.int64)
        t = np.asarray(target, dtype=np.int64)
        st[:n] = np.where((s < 0) | (s >= pn), dummy, s)
        tg[:n] = np.where((t < 0) | (t >= pn), dummy, t)
        gmax = self.global_max_depth
        if depths is not None:
            want = np.asarray(depths, dtype=np.int32)
        else:
            want = np.full(n, 0, dtype=np.int32)
        dp[:n] = np.where((want <= 0) | (want > gmax), gmax, want)
        fb_depths = dp[:n].tolist()
        if dg.mode == "packed":
            dp[:n] = np.where(
                (st[:n] == dummy) | (tg[:n] == dummy), 0, dp[:n]
            )
            dp[n:] = 0
        return EncodedBatch(
            None, fb_depths, n, b, snap, dg, st, tg, dp,
        )

    def launch_encoded(self, enc: EncodedBatch) -> LaunchedBatch:
        """Stage 2 (the device stage): enqueue the kernel. Returns as soon
        as dispatch is accepted — the result array is still on device."""
        # fault sites: stand-ins for an XLA compile failure, an HBM
        # out-of-memory, a lost device, a shape-specific compile failure,
        # a numerically sick chip returning garbage, and a slow/wedged
        # dispatch — the typed recovery policies in engine/fallback.py,
        # the device supervisor in driver/registry.py, and the deadline
        # culls in engine/batcher.py are tested against exactly these
        FAULTS.fire("device.compile_error")
        FAULTS.fire("device.oom")
        FAULTS.fire("device.compile_fail")
        FAULTS.fire("device.lost")
        FAULTS.maybe_sleep("device.slow")
        if FAULTS.should_fire("device.batch_nan"):
            return LaunchedBatch(enc, garbage=True)
        DEVSTATS.record_transfer(
            enc.start.nbytes + enc.target.nbytes + enc.depth.nbytes, "h2d"
        )
        dg = enc.dg
        if dg.mode == "packed":
            from ..ops.packed import packed_batched_check

            hit = packed_batched_check(
                dg.src_by_dst,
                dg.dst_by_dst,
                jnp.asarray(enc.start),
                jnp.asarray(enc.target),
                jnp.asarray(enc.depth),
                padded_nodes=dg.padded_nodes,
                max_steps=self.global_max_depth,
                interpret=self.interpret,
            )
        elif dg.dense:
            hit = batched_check_dense(
                dg.adj,
                jnp.asarray(enc.start),
                jnp.asarray(enc.target),
                jnp.asarray(enc.depth),
                max_steps=self.global_max_depth,
            )
        else:
            chunk = pick_edge_chunk(dg.padded_edges, enc.b)
            hit = batched_check_scatter(
                dg.src,
                dg.dst,
                jnp.asarray(enc.start),
                jnp.asarray(enc.target),
                jnp.asarray(enc.depth),
                padded_nodes=dg.padded_nodes,
                edge_chunk=chunk,
                max_steps=self.global_max_depth,
            )
        return LaunchedBatch(enc, hit)

    def decode_launched(self, launched: LaunchedBatch) -> list[bool]:
        """Stage 3: materialize the device result (the only blocking step)
        and recycle the staging buffers."""
        enc = launched.enc
        try:
            if launched.garbage:
                return [float("nan")] * enc.n
            hit = np.asarray(launched.hit)
            # the np.asarray above blocked until the kernel materialized:
            # on the direct (caller-thread) batch paths this charges the
            # device wait to 'kernel' on the ambient request ledger, so
            # the host-side list conversion below lands in 'decode'
            ledger_mark("kernel")
            DEVSTATS.record_transfer(hit.nbytes, "d2h")
            return hit[: enc.n].tolist()
        finally:
            enc.release()

    def distances(
        self, subject_sets: Sequence[SubjectSet], max_depth: int = 0
    ) -> np.ndarray:
        """BFS levels int32[B, padded_nodes] from each subject set (UNREACHED
        sentinel where unreachable) — device-side bulk expand support."""
        snap = self.snapshots.snapshot()
        dg = self._device_graph(snap)
        n = len(subject_sets)
        b = _bucket_batch(n)
        dummy = snap.dummy_node
        start = np.full(b, dummy, dtype=np.int32)
        for i, s in enumerate(subject_sets):
            start[i] = snap.node_for_set(s.namespace, s.object, s.relation)
        d = clamp_depth(max_depth, self.global_max_depth)
        depth = np.full(b, d, dtype=np.int32)
        if dg.mode == "packed":
            # distances are an expand-support query, not the packed check's
            # hot path: reuse the COO scatter kernel — cached per snapshot
            # (a fresh upload per expand would re-ship the whole edge list)
            with self._lock:
                companion = self._scatter_companion
                if not (
                    companion is not None
                    and companion.host_src is snap.src
                    and companion.host_dst is snap.dst
                ):
                    companion = _DeviceGraph(snap, "scatter")
                    self._scatter_companion = companion
            dg = companion
        if dg.dense:
            dist = batched_distances_dense(
                dg.adj,
                jnp.asarray(start),
                jnp.asarray(depth),
                max_steps=self.global_max_depth,
            )
        else:
            chunk = pick_edge_chunk(dg.padded_edges, b)
            dist = batched_distances_scatter(
                dg.src,
                dg.dst,
                jnp.asarray(start),
                jnp.asarray(depth),
                padded_nodes=dg.padded_nodes,
                edge_chunk=chunk,
                max_steps=self.global_max_depth,
            )
        return np.asarray(dist)[:n]


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
