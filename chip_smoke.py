"""Chip bring-up smoke: the served Check/Expand path on a TPU at 1M tuples.

One process owns the chip and drives the path `keto serve` takes:

1. find the TPU (any other backend: print why, exit non-zero, no result);
2. turn on the persistent compile cache before any jit
   (keto_tpu/utils/jaxenv.py enable_compile_cache);
3. build the ``rbac1m`` deployment from ``--seed`` (bench.gen_rbac: 1M
   tuples, ~356k nodes, ~11k interior subject sets) into a
   ColumnarTupleStore;
4. boot both planes with ``Registry.start_all()`` on free ports
   (engine.mode closure, one read worker, no CPU failover);
5. drive requests over the wire — the cat-videos scenario, REST and gRPC
   checks, one 4096-check ``POST /check/batch``, ``GET /expand``, and a
   write followed by a read-your-write check carrying its snaptoken;
6. compare every answer with a host reference over the same store, and
   fail unless the chip did the work: the closure built by the Pallas
   semiring step, device-side queries, no batch answered by the host
   oracle, no failover.

``--chips 4`` runs only the sharded serving path (engine.sharding.enabled:
a mesh over the 4 real devices) and its comparison with the reference.

The last stdout line is ``{"ok": true, "device": {...}}``; any failure
exits non-zero without it.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

N_TUPLES = 1_000_000  # the rbac1m deployment (BASELINE.json)
BATCH = 4096  # one POST /check/batch, the engine's max_batch
#: batch rows also answered by CheckEngine itself (~0.5 s each at 1M)
CROSSCHECK = 16

#: the cat-videos example and its expected answers
#: (.claude/skills/verify/SKILL.md "Canonical drive scenario")
CAT_VIDEOS = [
    ("videos:/cats#owner@cat lady", True),
    ("videos:/cats/1.mp4#owner@cat lady", True),
    ("videos:/cats/1.mp4#view@cat lady", True),
    ("videos:/cats/1.mp4#view@*", True),
    ("videos:/cats/2.mp4#view@*", False),
]


class SmokeFailure(Exception):
    """A phase produced a wrong answer or the chip did not do the work."""


def log(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


# ---------------------------------------------------------------------------
# host reference
# ---------------------------------------------------------------------------


class ReachabilityReference:
    """Plain host reference for Check over a store's live edges: allowed
    iff the subject is reached within ``depth`` tuple hops, breadth first
    (the semantics of engine/check.py CheckEngine). Independent of the
    snapshot, interior and closure code under test; vectorized so a
    4096-check batch over 1M tuples takes seconds, where CheckEngine's
    per-hop store scans take about half a second per check."""

    def __init__(self, store, max_depth: int):
        src, dst, vocab, self.version = store.snapshot_ids()
        n = len(vocab)
        order = np.argsort(src, kind="stable")
        self.nbr = dst[order].astype(np.int64)
        self.indptr = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(np.bincount(src, minlength=n), out=self.indptr[1:])
        self.vocab = vocab
        self.n = n
        self.max_depth = max_depth

    def _node(self, key):
        i = self.vocab.lookup(key)
        return None if i is None or i >= self.n else int(i)

    def check(self, tup, depth: int = 0) -> bool:
        from keto_tpu.engine.check import clamp_depth
        from keto_tpu.relationtuple import SubjectID

        s = self._node((tup.namespace, tup.object, tup.relation))
        subj = tup.subject
        t = self._node(
            (subj.id,)
            if isinstance(subj, SubjectID)
            else (subj.namespace, subj.object, subj.relation)
        )
        if s is None or t is None:
            return False
        seen = np.zeros(self.n, dtype=bool)
        seen[s] = True
        frontier = np.array([s], dtype=np.int64)
        for _ in range(clamp_depth(depth, self.max_depth)):
            lo = self.indptr[frontier]
            lens = self.indptr[frontier + 1] - lo
            total = int(lens.sum())
            if total == 0:
                return False
            starts = np.repeat(lo - (np.cumsum(lens) - lens), lens)
            nb = self.nbr[starts + np.arange(total)]
            if (nb == t).any():
                return True
            nb = np.unique(nb[~seen[nb]])
            seen[nb] = True
            frontier = nb
        return False


def canonical_tree(d):
    """Expand tree JSON with children in a fixed order."""
    if d is None:
        return None
    out = {k: v for k, v in d.items() if k != "children"}
    if d.get("children"):
        out["children"] = sorted(
            (canonical_tree(c) for c in d["children"]),
            key=lambda c: json.dumps(c, sort_keys=True),
        )
    return out


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def find_devices(n_chips: int):
    """The TPU devices, or exit non-zero: no probe child, no CPU path."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: no TPU: JAX found {devices[0].platform!r} "
            f"({devices[0].device_kind}); nothing measured",
            file=sys.stderr,
            flush=True,
        )
        sys.exit(2)
    if len(devices) < n_chips:
        raise SmokeFailure(f"need {n_chips} chips, JAX sees {len(devices)}")
    return devices


class CacheEvents:
    """Counts JAX persistent-compile-cache hits and misses."""

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def make_store(n_tuples: int, seed: int):
    """The rbac deployment, generated anew from ``seed`` (bench.gen_rbac;
    its pool cache and heartbeat go to a throwaway directory)."""
    sys.path.insert(0, REPO)
    import bench

    keys = ("BENCH_POOL_CACHE_DIR", "BENCH_HEARTBEAT_FILE")
    saved = {k: os.environ.get(k) for k in keys}
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as tmp:
        os.environ["BENCH_POOL_CACHE_DIR"] = tmp
        os.environ["BENCH_HEARTBEAT_FILE"] = os.path.join(tmp, "heartbeat")
        try:
            store, sample, _roots = bench.gen_rbac(
                n_tuples, np.random.default_rng(seed)
            )
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
    return store, sample


def _config(**engine):
    from keto_tpu.driver.config import Config

    return Config(
        values={
            "namespaces": [
                {"id": 1, "name": "rbac"},
                {"id": 2, "name": "videos"},
            ],
            "log": {"level": "error"},
            "serve": {
                "read": {"port": 0, "host": "127.0.0.1", "workers": 1},
                "write": {"port": 0, "host": "127.0.0.1"},
            },
            "engine": {
                "mode": "closure",
                "failover": {"allow_cpu": False},
                **engine,
            },
        },
        env={},
    )


class Server:
    """A Registry serving ``store`` on free ports from a loop thread."""

    def __init__(self, config, store):
        from keto_tpu.driver.registry import Registry

        self.registry = Registry(config)
        self.registry._store = store
        self.loop = asyncio.new_event_loop()
        self.thread = threading.Thread(
            target=self.loop.run_forever, name="smoke-loop", daemon=True
        )
        self.thread.start()
        t0 = time.perf_counter()
        fut = asyncio.run_coroutine_threadsafe(
            self.registry.start_all(), self.loop
        )
        self.read_port, self.write_port = fut.result(timeout=900)
        self.boot_s = time.perf_counter() - t0

    def stop(self) -> None:
        asyncio.run_coroutine_threadsafe(
            self.registry.stop_all(), self.loop
        ).result(timeout=60)
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.thread.join(timeout=10)


class Driver:
    """Wire clients for one server, and the running tally of answers."""

    def __init__(self, server: Server):
        import grpc
        import httpx

        self.read = f"http://127.0.0.1:{server.read_port}"
        self.write = f"http://127.0.0.1:{server.write_port}"
        self.http = httpx.Client(timeout=600)
        self.read_ch = grpc.insecure_channel(f"127.0.0.1:{server.read_port}")
        self.write_ch = grpc.insecure_channel(
            f"127.0.0.1:{server.write_port}"
        )
        self.answers = 0
        self.mismatches: list[str] = []

    def close(self) -> None:
        self.http.close()
        self.read_ch.close()
        self.write_ch.close()

    def expect(self, what: str, got, want) -> None:
        self.answers += 1
        if got != want:
            self.mismatches.append(f"{what}: got {got!r}, want {want!r}")

    def put_tuple(self, tup) -> None:
        r = self.http.put(
            f"{self.write}/relation-tuples", json=tup.to_dict()
        )
        if r.status_code != 201:
            raise SmokeFailure(f"PUT /relation-tuples -> {r.status_code}")

    def rest_check(self, tup, snaptoken: str = "") -> bool:
        from keto_tpu.relationtuple import SubjectID

        q = {
            "namespace": tup.namespace,
            "object": tup.object,
            "relation": tup.relation,
        }
        s = tup.subject
        if isinstance(s, SubjectID):
            q["subject_id"] = s.id
        else:
            q["subject_set.namespace"] = s.namespace
            q["subject_set.object"] = s.object
            q["subject_set.relation"] = s.relation
        if snaptoken:
            q["snaptoken"] = snaptoken
        r = self.http.get(f"{self.read}/check", params=q)
        if r.status_code not in (200, 403):
            raise SmokeFailure(f"GET /check -> {r.status_code}: {r.text}")
        allowed = bool(r.json()["allowed"])
        if allowed != (r.status_code == 200):
            raise SmokeFailure(f"GET /check status {r.status_code} vs body")
        return allowed

    def grpc_check(self, tup) -> bool:
        from keto_tpu.api import acl_pb2, check_service_pb2
        from keto_tpu.api.services import CheckServiceStub
        from keto_tpu.relationtuple import SubjectID

        s = tup.subject
        subject = (
            acl_pb2.Subject(id=s.id)
            if isinstance(s, SubjectID)
            else acl_pb2.Subject(
                set=acl_pb2.SubjectSet(
                    namespace=s.namespace, object=s.object, relation=s.relation
                )
            )
        )
        resp = CheckServiceStub(self.read_ch).Check(
            check_service_pb2.CheckRequest(
                namespace=tup.namespace,
                object=tup.object,
                relation=tup.relation,
                subject=subject,
            ),
            timeout=600,
        )
        return bool(resp.allowed)

    def batch_check(self, tuples) -> list[bool]:
        r = self.http.post(
            f"{self.read}/check/batch",
            json={"tuples": [t.to_dict() for t in tuples]},
        )
        if r.status_code != 200:
            raise SmokeFailure(f"POST /check/batch -> {r.status_code}")
        return [bool(v) for v in r.json()["allowed"]]

    def expand(self, namespace, obj, relation, depth):
        r = self.http.get(
            f"{self.read}/expand",
            params={
                "namespace": namespace,
                "object": obj,
                "relation": relation,
                "max-depth": depth,
            },
        )
        if r.status_code != 200:
            raise SmokeFailure(f"GET /expand -> {r.status_code}")
        return r.json()

    def grpc_write(self, tup) -> str:
        from keto_tpu.api import write_service_pb2
        from keto_tpu.api.convert import tuple_to_proto
        from keto_tpu.api.services import WriteServiceStub

        resp = WriteServiceStub(self.write_ch).TransactRelationTuples(
            write_service_pb2.TransactRelationTuplesRequest(
                relation_tuple_deltas=[
                    write_service_pb2.RelationTupleDelta(
                        action=write_service_pb2.RelationTupleDelta.INSERT,
                        relation_tuple=tuple_to_proto(tup),
                    )
                ]
            ),
            timeout=600,
        )
        return resp.snaptokens[0]


def _sample_tuples(sample, rng, k):
    from keto_tpu.relationtuple import RelationTuple, SubjectID

    sk, dk = sample(rng, k)
    return [
        RelationTuple(
            namespace=s[0], object=s[1], relation=s[2],
            subject=SubjectID(id=d[0]),
        )
        for s, d in zip(sk, dk)
    ]


def _oracle_crosscheck(store, ref, tuples, max_depth, report) -> None:
    """CheckEngine (engine/check.py) over the same store agrees with the
    vectorized reference on ``tuples``."""
    from keto_tpu.engine.check import CheckEngine

    oracle = CheckEngine(store, max_depth=max_depth)
    t0 = time.perf_counter()
    for t in tuples:
        if oracle.subject_is_allowed(t) != ref.check(t):
            raise SmokeFailure(f"reference disagrees with CheckEngine: {t}")
    report["checkengine_crosscheck"] = len(tuples)
    report["checkengine_crosscheck_s"] = time.perf_counter() - t0


def drive_served_path(store, sample, *, seed, batch, crosscheck, engine=None):
    """Boot the served path on ``store``, drive every request kind over the
    wire and compare each answer with the host reference. Returns the
    report; raises SmokeFailure on any wrong answer."""
    from keto_tpu.relationtuple import RelationTuple

    report: dict = {"tuples": len(store)}
    rng = np.random.default_rng(seed + 1)
    server = Server(_config(**(engine or {})), store)
    reg = server.registry
    report["boot_s"] = server.boot_s
    eng = reg.check_engine()
    report["build_phases_s"] = dict(eng.last_build_phases)
    report["build_kernel"] = eng.last_full_build_kernel
    report["host_queries"] = bool(eng.host_queries())
    max_depth = reg.config.read_api_max_depth()
    drv = Driver(server)
    try:
        snap = reg.snapshots().snapshot()
        report["nodes"] = snap.num_nodes
        report["edges"] = snap.num_edges
        state = eng._state
        report["interior"] = getattr(getattr(state, "ig", None), "m", None)

        # cat-videos over the write plane, then its expected answers
        cat_dir = os.path.join(REPO, "contrib", "cat-videos-example",
                               "relation-tuples")
        for name in sorted(os.listdir(cat_dir)):
            with open(os.path.join(cat_dir, name)) as f:
                d = json.load(f)
            d.pop("$schema", None)
            drv.put_tuple(RelationTuple.from_dict(d))
        for text, want in CAT_VIDEOS:
            drv.expect(f"cat-videos {text}",
                       drv.rest_check(RelationTuple.from_string(text)), want)

        ref = ReachabilityReference(store, max_depth)
        # one allowed and one denied pair for the single-check transports
        pool = _sample_tuples(sample, rng, 512)
        verdicts = [ref.check(t) for t in pool]
        if True not in verdicts or False not in verdicts:
            raise SmokeFailure("sample has no allowed/denied pair")
        allowed_t = pool[verdicts.index(True)]
        denied_t = pool[verdicts.index(False)]
        drv.expect("REST check allowed", drv.rest_check(allowed_t), True)
        drv.expect("REST check denied", drv.rest_check(denied_t), False)
        drv.expect("gRPC check allowed", drv.grpc_check(allowed_t), True)
        drv.expect("gRPC check denied", drv.grpc_check(denied_t), False)

        # the batch: first call pays the batch-shape compile
        tuples = _sample_tuples(sample, rng, batch)
        t0 = time.perf_counter()
        got = drv.batch_check(tuples)
        report["first_batch_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        want = [ref.check(t) for t in tuples]
        report["reference_batch_s"] = time.perf_counter() - t0
        report["batch"] = len(tuples)
        report["batch_allowed"] = int(sum(want))
        for i, (g, w) in enumerate(zip(got, want)):
            drv.expect(f"batch[{i}] {tuples[i]}", g, w)
        t0 = time.perf_counter()
        drv.batch_check(tuples)
        report["second_batch_s"] = time.perf_counter() - t0

        # expand against the host expand engine over the same store
        from keto_tpu.engine.expand import ExpandEngine
        from keto_tpu.relationtuple import SubjectSet

        root = allowed_t
        tree = drv.expand(root.namespace, root.object, root.relation, 3)
        host_tree = ExpandEngine(store, max_depth=max_depth).build_tree(
            SubjectSet(namespace=root.namespace, object=root.object,
                       relation=root.relation),
            3,
        )
        drv.expect(
            f"expand {root.namespace}:{root.object}#{root.relation}",
            canonical_tree(tree),
            canonical_tree(None if host_tree is None else host_tree.to_dict()),
        )
        report["expand_leaves"] = json.dumps(tree).count('"leaf"')

        # write, then read-your-write with the returned snaptoken
        new_user = f"smoke-user-{seed}"
        grant = RelationTuple.from_string(
            f"{denied_t.namespace}:{denied_t.object}#{denied_t.relation}"
            f"@{new_user}"
        )
        token = drv.grpc_write(grant)
        drv.expect("read-your-write", drv.rest_check(grant, token), True)
        report["snaptoken"] = token

        if crosscheck:
            _oracle_crosscheck(
                store, ref,
                [allowed_t, denied_t, *tuples[:crosscheck]],
                max_depth, report,
            )
    finally:
        drv.close()
        server.stop()
    breaker = reg._engine_breaker
    report["answers"] = drv.answers
    report["mismatches"] = len(drv.mismatches)
    report["oracle_batches"] = breaker.n_fallback_batches if breaker else 0
    sup = reg.device_supervisor()
    report["failovers"] = sup.status()["failovers"] if sup else 0
    report["device_rows"] = eng.n_device_rows
    report["oracle_rows"] = eng.n_oracle_rows
    if drv.mismatches:
        raise SmokeFailure(
            f"{len(drv.mismatches)} answers differ from the reference: "
            + "; ".join(drv.mismatches[:5])
        )
    return report


def require_device_work(report) -> None:
    """Fail unless the chip built the closure and answered the checks."""
    problems = []
    if report["build_kernel"] != "pallas":
        problems.append(f"closure built by {report['build_kernel']!r}")
    if report["host_queries"]:
        problems.append("queries placed on the host")
    if report["oracle_batches"]:
        problems.append(f"{report['oracle_batches']} oracle batches")
    if report["failovers"]:
        problems.append(f"{report['failovers']} failovers")
    if report["device_rows"] <= 0:
        problems.append("no check answered on the device")
    if problems:
        raise SmokeFailure("the chip did not do the work: "
                           + ", ".join(problems))


def drive_sharded_path(store, sample, *, seed, batch, n_devices):
    """The sharded serving tier over a mesh of all visible devices: one
    batch over the wire, compared with the reference; every resident
    array must span all ``n_devices`` devices."""
    report: dict = {"tuples": len(store)}
    rng = np.random.default_rng(seed + 2)
    server = Server(_config(sharding={"enabled": True}), store)
    reg = server.registry
    report["boot_s"] = server.boot_s
    eng = reg.check_engine()
    report["engine"] = type(eng).__name__
    if report["engine"] != "ShardedServingEngine":
        server.stop()
        raise SmokeFailure(f"sharded path served by {report['engine']}")
    drv = Driver(server)
    try:
        ref = ReachabilityReference(store, reg.config.read_api_max_depth())
        tuples = _sample_tuples(sample, rng, batch)
        t0 = time.perf_counter()
        got = drv.batch_check(tuples)
        report["first_batch_s"] = time.perf_counter() - t0
        want = [ref.check(t) for t in tuples]
        report["batch"] = len(tuples)
        report["batch_allowed"] = int(sum(want))
        for i, (g, w) in enumerate(zip(got, want)):
            drv.expect(f"sharded batch[{i}] {tuples[i]}", g, w)
        resident = eng._residency(reg.snapshots().snapshot())
        spans = [
            len({s.device for s in arr.addressable_shards})
            for arr in resident[3:11]
        ]
        report["mesh_shape"] = dict(eng.mesh.shape)
        report["array_device_spans"] = spans
        if any(n != n_devices for n in spans):
            raise SmokeFailure(
                f"sharded arrays span {spans} devices, want {n_devices}"
            )
    finally:
        drv.close()
        server.stop()
    breaker = reg._engine_breaker
    report["answers"] = drv.answers
    report["mismatches"] = len(drv.mismatches)
    report["oracle_batches"] = breaker.n_fallback_batches if breaker else 0
    if drv.mismatches:
        raise SmokeFailure(
            f"{len(drv.mismatches)} sharded answers differ from the "
            "reference: " + "; ".join(drv.mismatches[:5])
        )
    if report["oracle_batches"]:
        raise SmokeFailure(f"{report['oracle_batches']} oracle batches")
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    devices = find_devices(args.chips)
    sys.path.insert(0, REPO)
    from keto_tpu.utils.jaxenv import enable_compile_cache

    cache_dir = enable_compile_cache()
    cache = CacheEvents()
    n_cached = sum(len(f) for _, _, f in os.walk(cache_dir))
    dev = devices[0]
    log(phase="device", platform=dev.platform, kind=dev.device_kind,
        count=len(devices), compile_cache_dir=cache_dir,
        cache_entries_before=n_cached)

    t0 = time.perf_counter()
    store, sample = make_store(N_TUPLES, args.seed)
    log(phase="data", deployment="rbac", seed=args.seed, tuples=len(store),
        gen_s=time.perf_counter() - t0)

    if args.chips == 4:
        report = drive_sharded_path(
            store, sample, seed=args.seed, batch=BATCH,
            n_devices=len(devices),
        )
    else:
        report = drive_served_path(
            store, sample, seed=args.seed, batch=BATCH,
            crosscheck=CROSSCHECK,
        )
        require_device_work(report)
    log(phase="served", **report)
    log(phase="compile_cache", dir=cache_dir, hits=cache.hits,
        misses=cache.misses,
        entries_after=sum(len(f) for _, _, f in os.walk(cache_dir)),
        total_s=time.perf_counter() - t_start)
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(devices),
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
