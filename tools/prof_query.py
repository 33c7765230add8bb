"""Profile the closure-engine query hot path at scale WITHOUT a device.

The O(M^3) closure build needs the MXU; the query path only needs a D
matrix with the right shape and a realistic hit rate — its values steer
branch outcomes, not the access pattern. This harness generates a bench
graph, builds the real interior decomposition, fills D synthetically, and
times the object path (batch_check: encode + query) and the array path
(check_ids) with per-stage breakdowns.

Usage: python tools/prof_query.py [n_tuples] [batch] [iters]
"""

import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench
from keto_tpu.engine.closure import ClosureCheckEngine, _ClosureArtifacts
from keto_tpu.graph import SnapshotManager
from keto_tpu.graph.interior import build_interior
from keto_tpu.relationtuple import RelationTuple, SubjectID, SubjectSet


def synthetic_closure(ig, m_pad: int, rng) -> np.ndarray:
    """uint8[m_pad, m_pad] with plausible bounded distances: mostly INF,
    small distances on a minority, matching the bench's ~12% allow rate."""
    d = np.full((m_pad, m_pad), 255, dtype=np.uint8)
    m = ig.m
    # ~8% of interior pairs reachable, distances 1..4
    n_fill = int(m * m * 0.08)
    rows = rng.integers(m, size=n_fill)
    cols = rng.integers(m, size=n_fill)
    vals = rng.integers(1, 5, size=n_fill).astype(np.uint8)
    d[rows, cols] = vals
    idx = np.arange(m)
    d[idx, idx] = 0
    return d


def main():
    n_tuples = int(sys.argv[1]) if len(sys.argv) > 1 else 10_000_000
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else 4096
    iters = int(sys.argv[3]) if len(sys.argv) > 3 else 30

    rng = np.random.default_rng(7)
    t0 = time.time()
    store, sample, _roots = bench.gen_rbac(n_tuples, rng)
    print(f"gen: {time.time()-t0:.1f}s  tuples={len(store)}", flush=True)

    t0 = time.time()
    snapshots = SnapshotManager(store)
    snap = snapshots.snapshot()
    print(f"snapshot: {time.time()-t0:.1f}s nodes={snap.num_nodes}", flush=True)

    t0 = time.time()
    ig = build_interior(snap)
    print(f"interior: {time.time()-t0:.1f}s m={ig.m}", flush=True)

    engine = ClosureCheckEngine(
        snapshots, max_depth=5, interior_limit=40960, query_mode="host"
    )
    # hand-build the artifacts with a synthetic D (no device build)
    art = _ClosureArtifacts.__new__(_ClosureArtifacts)
    art.snap = snap
    art.ig = ig
    art.k_max = 4
    from keto_tpu.engine.closure import _bucket_mult

    art.m_pad = _bucket_mult(ig.m + 1, 256)
    art.pad = art.m_pad - 1
    art.d = None
    t0 = time.time()
    art.d_host = synthetic_closure(ig, art.m_pad, rng)
    print(f"synthetic D: {time.time()-t0:.1f}s  {art.m_pad}^2 "
          f"= {art.m_pad*art.m_pad/1e6:.0f} MB", flush=True)
    engine._state = art

    def to_requests(skeys, dkeys):
        return [
            RelationTuple(
                namespace=s[0], object=s[1], relation=s[2],
                subject=SubjectID(d[0]) if len(d) == 1
                else SubjectSet(namespace=d[0], object=d[1], relation=d[2]),
            )
            for s, d in zip(skeys, dkeys)
        ]

    import gc

    # ---- array path (check_ids)
    lookup = snap.vocab.lookup
    dummy = snap.dummy_node
    enc_batches = []
    for _ in range(iters):
        skeys, dkeys = sample(rng, batch)
        s_ids = np.array(
            [v if (v := lookup(k)) is not None else dummy for k in skeys],
            np.int64)
        d_ids = np.array(
            [v if (v := lookup(k)) is not None else dummy for k in dkeys],
            np.int64)
        is_id = np.fromiter((len(k) == 1 for k in dkeys), bool, count=batch)
        enc_batches.append((s_ids, d_ids, is_id))
    res = engine.check_ids(*enc_batches[0])
    print(f"allowed_frac={res.mean():.3f}", flush=True)
    gc.collect(); gc.disable()
    best = 0.0
    for _pass in range(2):
        lats = []
        t_all = time.time()
        for s_ids, d_ids, is_id in enc_batches:
            t0 = time.perf_counter()
            engine.check_ids(s_ids, d_ids, is_id)
            lats.append(time.perf_counter() - t0)
        rps = batch * iters / (time.time() - t_all)
        if rps > best:
            best, keep = rps, lats
    gc.enable()
    print(f"check_ids: {best:,.0f} rps  p50={np.percentile(keep,50)*1e3:.2f}ms "
          f"p95={np.percentile(keep,95)*1e3:.2f}ms", flush=True)

    # ---- object path (batch_check)
    batches = [to_requests(*sample(rng, batch)) for _ in range(iters)]
    engine.batch_check(batches[0])
    gc.collect(); gc.disable()
    best_o = 0.0
    for _pass in range(2):
        lats = []
        t_all = time.time()
        for reqs in batches:
            t0 = time.perf_counter()
            engine.batch_check(reqs)
            lats.append(time.perf_counter() - t0)
        rps = batch * iters / (time.time() - t_all)
        if rps > best_o:
            best_o, keep_o = rps, lats
    gc.enable()
    print(f"batch_check: {best_o:,.0f} rps  "
          f"p50={np.percentile(keep_o,50)*1e3:.2f}ms "
          f"p95={np.percentile(keep_o,95)*1e3:.2f}ms", flush=True)

    # ---- stage breakdown of the object path (one batch, repeated)
    reqs = batches[0]
    n = len(reqs)

    def t_stage(fn, reps=10):
        fn()
        t0 = time.perf_counter()
        for _ in range(reps):
            fn()
        return (time.perf_counter() - t0) / reps * 1e3

    skeys = [(r.namespace, r.object, r.relation) for r in reqs]
    tkeys = [
        (s.id,) if type(s) is SubjectID
        else (s.namespace, s.object, s.relation)
        for s in (r.subject for r in reqs)
    ]
    ms_keys = t_stage(lambda: (
        [(r.namespace, r.object, r.relation) for r in reqs],
        [(s.id,) if type(s) is SubjectID
         else (s.namespace, s.object, s.relation)
         for s in (r.subject for r in reqs)],
    ))
    ms_lookup = t_stage(lambda: (
        snap.vocab.lookup_bulk(skeys), snap.vocab.lookup_bulk(tkeys)))
    s_ids, d_ids, is_id = enc_batches[0]
    ms_arrays = t_stage(lambda: engine._check_arrays(
        snap, art, s_ids.copy(), d_ids.copy(), is_id,
        np.full(n, 5, np.int32)))
    print(f"stages (ms/batch of {n}): keys={ms_keys:.2f} "
          f"lookup_bulk={ms_lookup:.2f} check_arrays={ms_arrays:.2f}",
          flush=True)

    # ---- expand p50/p95 over the bench's root sample (graph frozen out
    # of the cyclic GC, as the serving registry does at boot)
    import gc as _gc

    _gc.freeze()
    from keto_tpu.engine.expand import SnapshotExpandEngine

    expander = SnapshotExpandEngine(snapshots, max_depth=5)
    exp_lat = []
    n_nodes = []

    def count(tree):
        return 1 + sum(count(c) for c in tree.children)

    for key in _roots:
        subject = SubjectSet(namespace=key[0], object=key[1], relation=key[2])
        t0 = time.perf_counter()
        tree = expander.build_tree(subject, max_depth=3)
        exp_lat.append(time.perf_counter() - t0)
        n_nodes.append(0 if tree is None else count(tree))
    print(f"expand: p50={np.percentile(exp_lat,50)*1e3:.2f}ms "
          f"p95={np.percentile(exp_lat,95)*1e3:.2f}ms "
          f"max={max(exp_lat)*1e3:.1f}ms "
          f"nodes_p50={int(np.percentile(n_nodes,50))} "
          f"nodes_p95={int(np.percentile(n_nodes,95))} "
          f"nodes_max={max(n_nodes)}", flush=True)

    from keto_tpu import native
    print(f"native={native.available()}", flush=True)


if __name__ == "__main__":
    main()
