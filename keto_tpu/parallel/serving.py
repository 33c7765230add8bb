"""Sharded serving tier: the edge-partitioned mesh closure engine as a
first-class serving path (not a bench parity oracle).

:class:`ShardedServingEngine` wraps :class:`.closure_sharded.
ShardedClosureEngine` with everything ``CheckBatcher`` and the circuit
breaker need to route live check traffic into the mesh:

- the fault sites of the split ``encode_ids``/``launch_encoded``/
  ``decode_launched`` API (inherited; every check goes through it, so
  the batcher's encoded path, the breaker's host-oracle fallback, the
  OOM bisection and the compile quarantine all guard it): the
  ``device.*`` and ``shard.*`` sites fire in its launch, and a garbage
  launch decodes to NaNs the breaker refuses;
- residency that survives snapshot rebuilds: the replicated interior
  distance matrix D is kept as a host uint8 bitset and updated with the
  semiring dirty-row machinery (``update_closure_bitset``) on
  append-only deltas, and only the node stripes whose shards actually
  own a touched node are re-gathered — a write no longer re-shards the
  world. Device buffers for untouched components are reused verbatim
  (object-identity keyed), so a delta that only appends direct edges
  re-uploads the full-out stripes and nothing else;
- per-shard residency accounting pushed into the HBM admission model
  (``HbmAdmission.set_shard_residency``), so batch admission respects
  the headroom of the *fullest* shard, and exported as
  ``keto_shard_residency_bytes{shard}`` for the federation plane's
  shard-skew view, with ``keto_shard_escalations_total{path}`` counting
  the wide-pass/host-oracle tail.

The single-chip engines stay the right choice below the HBM cliff; the
registry only routes here when ``engine.sharding.enabled`` is set AND
the mesh has more than one device (see driver/registry.py).
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..engine.check import DEFAULT_MAX_DEPTH
from ..engine.semiring import build_closure_bitset, update_closure_bitset
from ..faults import FAULTS
from ..graph.interior import build_interior
from ..graph.snapshot import GraphSnapshot, SnapshotManager
from .closure_sharded import (
    ShardedClosureEngine,
    _ShardedEncodedBatch,
    _ShardedLaunched,
    _stripe_csr,
    _stripe_vector,
)


class ShardedServingEngine(ShardedClosureEngine):
    """The serving wrapper around the edge-partitioned mesh closure
    kernel. See the module docstring for the contract; the query path is
    entirely inherited — this class owns residency lifetime, the launch's
    fault sites, escalation accounting, and the admission/metrics seams."""

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
        edge_chunk: int = 0,
        escalation_budget: float = 0.05,
        hbm=None,
        metrics=None,
        logger=None,
    ):
        super().__init__(
            snapshots,
            mesh=mesh,
            max_depth=max_depth,
            f0_max=f0_max,
            l_max=l_max,
            f0_max_escalated=f0_max_escalated,
            l_max_escalated=l_max_escalated,
            fallback=fallback,
        )
        # bound on the ragged-gather temporaries of one re-stripe pass
        # (values gathered per chunk); 0 = unchunked
        self.edge_chunk = int(edge_chunk)
        # tolerated host-oracle fraction per batch before the breach is
        # logged and counted — the rebalance signal, not a hard limit
        self.escalation_budget = float(escalation_budget)
        self.hbm = hbm
        self.logger = logger
        # host-side artifacts the incremental re-shard carries across
        # snapshots: {"snap", "ig", "m_pad", "d", "f0", "l", "int",
        # "out", "n_dirty", "shards"} — stripe pairs are (indptr, vals)
        # stacked [n_shards, ...] numpy arrays
        self._host: Optional[dict] = None
        self.n_full_reshards = 0
        self.n_incremental_reshards = 0
        self.last_reshard: dict = {}
        self.n_budget_breaches = 0
        self._m_residency = self._m_escalations = self._m_reshards = None
        if metrics is not None:
            self._m_residency = metrics.gauge(
                "keto_shard_residency_bytes",
                "bytes resident on each mesh shard for the sharded "
                "serving tier (replicated D + this shard's CSR stripes; "
                "logical nnz, excluding stripe padding)",
                labelnames=("shard",),
            )
            self._m_escalations = metrics.counter(
                "keto_shard_escalations_total",
                "sharded-serving rows escalated past the narrow device "
                "pass, by path (wide_pass = second device pass at "
                "escalated gather widths; host_oracle = exact host "
                "fallback beyond even those)",
                labelnames=("path",),
            )
            self._m_reshards = metrics.counter(
                "keto_shard_reshards_total",
                "mesh residency rebuilds by kind (full = re-shard the "
                "world; incremental = dirty-row D update + affected-"
                "shard re-stripe only)",
                labelnames=("kind",),
            )

    # -- residency -------------------------------------------------------------

    def _workers(self) -> int:
        import os

        return min(8, max(1, (os.cpu_count() or 1) // 2))

    def _stripe_one(
        self, indptr: np.ndarray, vals: np.ndarray, pn: int, k: int
    ) -> tuple[np.ndarray, np.ndarray]:
        """One shard's node-striped CSR rows: the single-shard body of
        ``_stripe_csr`` with the ragged gather chunked to ``edge_chunk``
        values so a hot shard's re-stripe has bounded temporaries."""
        n = self.n_edge
        local_rows = -(-pn // n)
        nodes = np.arange(k, pn, n, dtype=np.int64)
        row_counts = (indptr[nodes + 1] - indptr[nodes]).astype(np.int64)
        out_ip = np.zeros(local_rows + 1, dtype=np.int32)
        counts = np.zeros(local_rows, dtype=np.int64)
        counts[: len(nodes)] = row_counts
        out_ip[1:] = np.cumsum(counts).astype(np.int32)
        total = int(row_counts.sum())
        out_v = np.empty(total, dtype=np.int32)
        if total == 0:
            return out_ip, out_v
        cum = np.cumsum(row_counts)
        chunk = self.edge_chunk
        i = pos = 0
        while i < len(nodes):
            if chunk <= 0:
                j = len(nodes)
            else:
                base = cum[i - 1] if i else 0
                j = int(np.searchsorted(cum, base + chunk, side="left")) + 1
                j = min(max(j, i + 1), len(nodes))
            rc = row_counts[i:j]
            tot = int(rc.sum())
            if tot:
                starts_rep = np.repeat(indptr[nodes[i:j]].astype(np.int64), rc)
                within = np.arange(tot, dtype=np.int64) - np.repeat(
                    np.cumsum(rc) - rc, rc
                )
                out_v[pos : pos + tot] = vals[starts_rep + within]
                pos += tot
            i = j
        return out_ip, out_v

    def _restripe(
        self,
        prev: tuple[np.ndarray, np.ndarray],
        indptr: np.ndarray,
        vals: np.ndarray,
        pn: int,
        shards: np.ndarray,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Re-gather only ``shards``' rows from a fresh full CSR, reusing
        the previous stripe rows for every other shard. Returns the prev
        pair untouched (identity) when no shard is affected — the upload
        step keys device-buffer reuse on that identity."""
        if len(shards) == 0:
            return prev
        prev_ip, prev_v = prev
        n = self.n_edge
        rows = {}
        width = prev_v.shape[1]
        need = width
        for k in shards:
            row_ip, row_v = self._stripe_one(indptr, vals, pn, int(k))
            rows[int(k)] = (row_ip, row_v)
            need = max(need, len(row_v), 1)
        if need > width:
            new_v = np.zeros((n, need), dtype=np.int32)
            new_v[:, :width] = prev_v
        else:
            new_v = prev_v.copy()
        new_ip = prev_ip.copy()
        for k, (row_ip, row_v) in rows.items():
            new_ip[k] = row_ip
            new_v[k, : len(row_v)] = row_v
            new_v[k, len(row_v) :] = 0
        return new_ip, new_v

    def _full_out_shard(
        self, snap: GraphSnapshot, k: int
    ) -> tuple[np.ndarray, np.ndarray]:
        """One shard's direct-edge probe rows (dst-sorted within row),
        rebuilt from only that shard's edges — O(E_k log E_k) instead of
        the global lexsort."""
        n = self.n_edge
        pn = snap.padded_nodes
        local_rows = -(-pn // n)
        e = snap.num_edges
        src = snap.src[:e]
        dst = snap.dst[:e]
        mask = (src % n) == k
        s_k = src[mask]
        d_k = dst[mask]
        order = np.lexsort((d_k, s_k))
        s_k = s_k[order]
        local = (s_k // n).astype(np.int64)
        counts = np.bincount(local, minlength=local_rows)
        row_ip = np.zeros(local_rows + 1, dtype=np.int32)
        np.cumsum(counts, out=row_ip[1:])
        return row_ip, d_k[order].astype(np.int32)

    def _restripe_out(
        self,
        prev: tuple[np.ndarray, np.ndarray],
        snap: GraphSnapshot,
        shards: np.ndarray,
    ) -> tuple[np.ndarray, np.ndarray]:
        if len(shards) == 0:
            return prev
        prev_ip, prev_v = prev
        n = self.n_edge
        rows = {int(k): self._full_out_shard(snap, int(k)) for k in shards}
        width = prev_v.shape[1]
        need = max([width] + [len(v) for _, v in rows.values()] + [1])
        if need > width:
            new_v = np.zeros((n, need), dtype=np.int32)
            new_v[:, :width] = prev_v
        else:
            new_v = prev_v.copy()
        new_ip = prev_ip.copy()
        for k, (row_ip, row_v) in rows.items():
            new_ip[k] = row_ip
            new_v[k, : len(row_v)] = row_v
            new_v[k, len(row_v) :] = 0
        return new_ip, new_v

    def _reshard_full(self, snap: GraphSnapshot) -> dict:
        ig = build_interior(snap)
        n = self.n_edge
        pn = snap.padded_nodes
        m_pad = -(-(ig.m + 1) // 256) * 256
        # D built and KEPT host-side (uint8 bitset BFS, parity-exact with
        # the device builder) so writes can dirty-row update it instead
        # of recomputing the O(M^2) matrix on device per snapshot
        d_host = build_closure_bitset(
            ig.ii_src, ig.ii_dst, ig.m, m_pad,
            self.global_max_depth - 1, workers=self._workers(),
        )
        f0 = _stripe_csr(ig.set_out_indptr, ig.set_out_vals, pn, n)[:2]
        l = _stripe_csr(ig.id_in_indptr, ig.id_in_vals, pn, n)[:2]
        int_idx = _stripe_vector(ig.interior_index, pn, n, -1)
        e = snap.num_edges
        src = snap.src[:e]
        dst = snap.dst[:e]
        order = np.lexsort((dst, src))
        counts = np.bincount(src, minlength=pn)
        full_indptr = np.zeros(pn + 1, dtype=np.int64)
        np.cumsum(counts, out=full_indptr[1:])
        out = _stripe_csr(full_indptr, dst[order], pn, n)[:2]
        return {
            "snap": snap, "ig": ig, "m_pad": m_pad, "d": d_host,
            "f0": f0, "l": l, "int": int_idx, "out": out,
            "n_dirty": ig.m, "shards": list(range(n)),
        }

    def _reshard_incremental(
        self, host: dict, snap: GraphSnapshot
    ) -> Optional[dict]:
        """Append-only delta over the resident snapshot with a stable
        interior set: dirty-row update D, re-stripe only the shards
        owning a touched node. None = conditions not met, full re-shard
        required (vocab swap, compaction, interior membership change)."""
        old = host["snap"]
        pe = old.num_edges
        if (
            snap.vocab is not old.vocab
            or snap.padded_nodes != old.padded_nodes
            or snap.num_edges < pe
            or not np.array_equal(snap.src[:pe], old.src[:pe])
            or not np.array_equal(snap.dst[:pe], old.dst[:pe])
        ):
            return None
        ig = build_interior(snap)
        prev_ig = host["ig"]
        if not np.array_equal(ig.interior_ids, prev_ig.interior_ids):
            return None
        n = self.n_edge
        pn = snap.padded_nodes
        m_pad = host["m_pad"]
        d_new, n_dirty = update_closure_bitset(
            host["d"], prev_ig.ii_src, prev_ig.ii_dst,
            ig.ii_src, ig.ii_dst, ig.m, m_pad,
            self.global_max_depth - 1, workers=self._workers(),
        )
        new_src = snap.src[pe : snap.num_edges]
        new_dst = snap.dst[pe : snap.num_edges]
        # shard ownership of the touched CSR rows: F0 and the direct-edge
        # probe are source CSRs, L is a destination CSR
        src_shards = np.unique(new_src % n)
        dst_shards = np.unique(new_dst % n)
        return {
            "snap": snap, "ig": ig, "m_pad": m_pad, "d": d_new,
            "f0": self._restripe(
                host["f0"], ig.set_out_indptr, ig.set_out_vals, pn,
                src_shards,
            ),
            "l": self._restripe(
                host["l"], ig.id_in_indptr, ig.id_in_vals, pn, dst_shards
            ),
            # same interior set + padded width => identical index stripe
            "int": host["int"],
            "out": self._restripe_out(host["out"], snap, src_shards),
            "n_dirty": n_dirty,
            "shards": sorted(
                set(src_shards.tolist()) | set(dst_shards.tolist())
            ),
        }

    def _upload(self, host: dict, prev_host: Optional[dict], prev_r):
        """Host artifacts -> resident device tuple (the parent's layout,
        so every inherited query path works). Components whose host array
        is the SAME OBJECT as the previous re-shard's keep their device
        buffer — no transfer for untouched stripes."""
        mesh = self.mesh
        edge_sh = NamedSharding(mesh, P("edge"))
        repl = NamedSharding(mesh, P())

        def put(arr, spec, prev_arr, prev_dev):
            if prev_host is not None and arr is prev_arr:
                return prev_dev
            return jax.device_put(arr, spec)

        ph = prev_host or {}
        pr = prev_r or (None,) * 12
        n = self.n_edge
        m_pad = host["m_pad"]
        f0_ip, f0_v = host["f0"]
        l_ip, l_v = host["l"]
        out_ip, out_v = host["out"]
        int_idx = host["int"]
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
        # logical (nnz, unpadded) per-shard residency: the skew signal —
        # padded stripe widths are identical across shards by construction
        fixed = (
            shard_bytes["d_replicated"]
            + shard_bytes["f0_indptr"]
            + shard_bytes["l_indptr"]
            + shard_bytes["out_indptr"]
            + shard_bytes["interior_index"]
        )
        shard_bytes["per_shard_logical"] = [
            fixed
            + 4 * (int(f0_ip[k, -1]) + int(l_ip[k, -1]) + int(out_ip[k, -1]))
            for k in range(n)
        ]
        pf0 = ph.get("f0", (None, None))
        pl = ph.get("l", (None, None))
        pout = ph.get("out", (None, None))
        return (
            host["snap"],
            host["ig"],
            m_pad,
            put(host["d"], repl, ph.get("d"), pr[3]),
            put(f0_ip, edge_sh, pf0[0], pr[4]),
            put(f0_v, edge_sh, pf0[1], pr[5]),
            put(l_ip, edge_sh, pl[0], pr[6]),
            put(l_v, edge_sh, pl[1], pr[7]),
            put(int_idx, edge_sh, ph.get("int"), pr[8]),
            put(out_ip, edge_sh, pout[0], pr[9]),
            put(out_v, edge_sh, pout[1], pr[10]),
            shard_bytes,
        )

    def _residency(self, snap: GraphSnapshot):
        with self._lock:
            r = self._resident
            if r is not None and r[0] is snap:
                return r
            prev_host = self._host
            new_host = None
            if prev_host is not None:
                new_host = self._reshard_incremental(prev_host, snap)
            if new_host is None:
                kind = "full"
                new_host = self._reshard_full(snap)
                self.n_full_reshards += 1
            else:
                kind = "incremental"
                self.n_incremental_reshards += 1
            r = self._upload(new_host, prev_host, self._resident)
            self._host = new_host
            self._resident = r
            self.last_reshard = {
                "kind": kind,
                "dirty_rows": int(new_host["n_dirty"]),
                "shards": list(new_host["shards"]),
            }
            self._after_reshard(kind, r[-1])
            return r

    def _after_reshard(self, kind: str, shard_bytes: dict) -> None:
        per_shard = shard_bytes.get("per_shard_logical", [])
        if self._m_reshards is not None:
            self._m_reshards.labels(kind=kind).inc()
        if self._m_residency is not None:
            for k, b in enumerate(per_shard):
                self._m_residency.labels(shard=str(k)).set(float(b))
        if self.hbm is not None:
            push = getattr(self.hbm, "set_shard_residency", None)
            if push is not None:
                push({k: float(b) for k, b in enumerate(per_shard)})

    def reset_residency(self) -> None:
        """Drop every resident buffer (device supervisor re-init hook);
        the next batch rebuilds from scratch on the current backend."""
        with self._lock:
            self._resident = None
            self._host = None

    # -- versions / lifecycle --------------------------------------------------

    def served_version(self) -> int:
        return self.snapshots.store.version

    def answering_version(self) -> int:
        return self.snapshots.store.version

    def wait_for_version(self, min_version: int, timeout_s: float = 5.0):
        """Serving snapshots fresh per batch, so answers are always at
        the live store version — a local client token can never run
        ahead of it. Nothing to wait on (same clamp semantics as the
        closure engine's freshness gate)."""
        return None

    def pipeline_supported(self) -> bool:
        # no string-path encode_batch: the encoded/columnar entry points
        # run caller-thread through encode_ids/launch/decode
        return False

    def warmup(self, batch: int = 8) -> None:
        """Build residency for the current snapshot and compile the
        narrow-pass kernel for one small bucket (boot/failover priming)."""
        n = max(1, min(int(batch) or 1, 64))
        self.check_ids(
            np.zeros(n, dtype=np.int64), np.zeros(n, dtype=np.int64)
        )

    # -- escalation accounting -------------------------------------------------

    def _note_escalations(self, before: dict, n_rows: int) -> None:
        esc = self.overflow_stats["escalated"] - before["escalated"]
        host = self.overflow_stats["host_fallback"] - before["host_fallback"]
        if self._m_escalations is not None:
            if esc:
                self._m_escalations.labels(path="wide_pass").inc(esc)
            if host:
                self._m_escalations.labels(path="host_oracle").inc(host)
        if n_rows and host / n_rows > self.escalation_budget:
            self.n_budget_breaches += 1
            if self.logger is not None:
                self.logger.warning(
                    "sharded escalation budget breached",
                    host_oracle_rows=host,
                    batch_rows=n_rows,
                    budget=self.escalation_budget,
                )

    # -- launch/decode (the CheckBatcher + breaker seam) ------------------------

    def launch_encoded(self, enc: _ShardedEncodedBatch) -> _ShardedLaunched:
        # fault sites: stand-ins for an XLA compile failure, an HBM
        # out-of-memory, a shape-specific compile failure, a lost device,
        # a slow dispatch and a numerically sick chip returning garbage —
        # the typed recovery policies in engine/fallback.py and the
        # device supervisor in driver/registry.py are tested against
        # exactly these
        FAULTS.fire("device.compile_error")
        FAULTS.fire("device.oom")
        FAULTS.fire("device.compile_fail")
        FAULTS.fire("device.lost")
        FAULTS.maybe_sleep("device.slow")
        FAULTS.fire("shard.launch_fail")
        FAULTS.maybe_sleep("shard.launch_slow")
        if FAULTS.should_fire("device.batch_nan"):
            return _ShardedLaunched(enc, garbage=True)
        return super().launch_encoded(enc)

    def decode_launched(self, launched: _ShardedLaunched) -> list[bool]:
        if launched.garbage:
            return [float("nan")] * launched.enc.n
        before = dict(self.overflow_stats)
        out = super().decode_launched(launched)
        self._note_escalations(before, launched.enc.n)
        return out
