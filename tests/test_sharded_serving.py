"""Sharded SERVING tier correctness on a multi-device mesh.

The serving wrapper (parallel/serving.ShardedServingEngine) is the path
live check traffic takes when engine.sharding.enabled: CheckBatcher ->
breaker -> encode/launch/decode over the edge-partitioned mesh. These
tests pin parity with the host oracle, the overflow/escalation contract,
incremental re-shard across snapshot rebuilds, mesh-shape validation,
and the breaker interaction under injected launch faults.

Runs only when >= 8 devices are visible: the 8-device virtual CPU mesh
conftest.py sets up, and test_sharded_subprocess.py re-runs them in a
fresh interpreter with that env set at start.
The HBM clamp tests at the bottom need no mesh and always run.
"""

import jax
import numpy as np
import pytest

from keto_tpu.engine import CheckEngine
from keto_tpu.engine.batcher import CheckBatcher
from keto_tpu.engine.fallback import DeviceFallbackEngine
from keto_tpu.engine.hbm import HbmAdmission
from keto_tpu.faults import FAULTS
from keto_tpu.graph import SnapshotManager
from keto_tpu.parallel import make_mesh
from keto_tpu.parallel.serving import ShardedServingEngine
from keto_tpu.relationtuple import RelationTuple
from keto_tpu.store import InMemoryTupleStore

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 devices (virtual CPU mesh)"
)


@pytest.fixture(autouse=True)
def _clean_faults():
    FAULTS.reset()
    yield
    FAULTS.reset()


def t(s: str) -> RelationTuple:
    return RelationTuple.from_string(s)


# unicode vocab: the serving tier encodes/decodes ids against the
# snapshot vocab; multi-byte keys must survive the round trip
_UNI_OBJS = ["документ", "予約-α", "ficha-ñ", "plain"]
_UNI_USERS = ["алиса", "ユーザー1", "böb", "mallory"]


def fuzz_store(rng, n_edges=300):
    store = InMemoryTupleStore()
    tuples = set()
    for _ in range(n_edges):
        obj = f"o{rng.integers(20)}"
        rel = f"r{rng.integers(3)}"
        if rng.random() < 0.45:
            sub = f"n:o{rng.integers(20)}#r{rng.integers(3)}"
        else:
            sub = f"u{rng.integers(12)}"
        tuples.add(f"n:{obj}#{rel}@({sub})")
    # unicode spine, including a cycle through the multi-byte nodes
    for i, (o, u) in enumerate(zip(_UNI_OBJS, _UNI_USERS)):
        tuples.add(f"n:{o}#view@({u})")
        tuples.add(f"n:o{i}#r0@(n:{o}#view)")
    tuples.add(f"n:{_UNI_OBJS[0]}#view@(n:{_UNI_OBJS[1]}#view)")
    tuples.add(f"n:{_UNI_OBJS[1]}#view@(n:{_UNI_OBJS[0]}#view)")
    store.write_relation_tuples(*(t(s) for s in tuples))
    return store


def fuzz_requests(rng, n=96):
    reqs = []
    for _ in range(n):
        roll = rng.random()
        if roll < 0.15:
            obj = _UNI_OBJS[rng.integers(len(_UNI_OBJS))]
            rel = "view"
        else:
            obj = f"o{rng.integers(20)}"
            rel = f"r{rng.integers(3)}"
        if roll < 0.15:
            sub = _UNI_USERS[rng.integers(len(_UNI_USERS))]
        elif roll < 0.4:
            sub = f"(n:o{rng.integers(20)}#r{rng.integers(3)})"
        else:
            sub = f"u{rng.integers(12)}"
        reqs.append(t(f"n:{obj}#{rel}@{sub}"))
    return reqs


def make_batcher(engine, store, **kw):
    breaker = DeviceFallbackEngine(
        engine,
        fallback_factory=lambda: CheckEngine(store, max_depth=5),
        failure_threshold=3,
        cooldown_s=0.1,
    )
    return CheckBatcher(breaker, max_batch=256, window_s=0.0, **kw)


def encode(snap, reqs):
    start = np.array(
        [snap.node_for_set(r.namespace, r.object, r.relation) for r in reqs],
        dtype=np.int64,
    )
    target = np.array(
        [snap.node_for_subject(r.subject) for r in reqs], dtype=np.int64
    )
    return start, target


@needs_mesh
@pytest.mark.parametrize("mesh_shape", [(1, 8), (2, 4), (4, 2)])
def test_serving_parity_fuzz(mesh_shape):
    """batch_check parity with the host oracle over a fuzzed store with
    unicode vocab and cycles, across mesh shapes and depth vectors."""
    rng = np.random.default_rng(11)
    store = fuzz_store(rng)
    mgr = SnapshotManager(store)
    data, edge = mesh_shape
    eng = ShardedServingEngine(
        mgr, mesh=make_mesh(data=data, edge=edge), max_depth=5
    )
    host = CheckEngine(store, max_depth=5)
    reqs = fuzz_requests(rng)
    for depths in (None, [1 + (i % 5) for i in range(len(reqs))]):
        got = eng.batch_check(reqs, depths=depths)
        want = host.batch_check(reqs, depths=depths)
        assert got == want, mesh_shape


@needs_mesh
def test_serving_through_check_batcher_encoded():
    """The production route: CheckBatcher.check_batch_encoded over the
    breaker-wrapped serving engine, byte-identical to the host oracle."""
    rng = np.random.default_rng(12)
    store = fuzz_store(rng)
    mgr = SnapshotManager(store)
    eng = ShardedServingEngine(mgr, mesh=make_mesh(data=2, edge=4), max_depth=5)
    host = CheckEngine(store, max_depth=5)
    batcher = make_batcher(eng, store)
    try:
        reqs = fuzz_requests(rng, n=64)
        start, target = encode(mgr.snapshot(), reqs)
        got = batcher.check_batch_encoded(start, target)
        assert got == host.batch_check(reqs)
        # string path too (same batcher seam the gRPC front uses)
        assert batcher.check_batch(reqs) == host.batch_check(reqs)
    finally:
        batcher.close()


@needs_mesh
def test_serving_overflow_escalates_to_host_oracle():
    """Rows wider than even the escalated gather widths reach the host
    oracle and stay exact; the escalation counters move accordingly."""
    store = InMemoryTupleStore()
    tuples = [t("n:doc#view@(n:g0#m)")]
    for i in range(120):  # alice in 120 groups: L row way past widths
        tuples.append(t(f"n:g{i}#m@alice"))
        tuples.append(t(f"n:top#r@(n:g{i}#m)"))  # make every g interior
    store.write_relation_tuples(*tuples)
    mgr = SnapshotManager(store)
    reqs = [
        t("n:doc#view@alice"),
        t("n:top#r@alice"),
        t("n:doc#view@mallory"),
    ]
    # wide escalated widths: stays on device
    eng = ShardedServingEngine(
        mgr, mesh=make_mesh(data=1, edge=8), max_depth=5
    )
    assert eng.batch_check(reqs) == [True, True, False]
    assert eng.overflow_stats["escalated"] > 0
    assert eng.overflow_stats["host_fallback"] == 0
    # narrow escalated widths: host oracle answers, exactly, and the
    # budget-breach accounting sees the rate
    eng2 = ShardedServingEngine(
        mgr,
        mesh=make_mesh(data=1, edge=8),
        max_depth=5,
        f0_max_escalated=64,
        l_max_escalated=64,
        escalation_budget=0.01,
    )
    assert eng2.batch_check(reqs) == [True, True, False]
    assert eng2.overflow_stats["host_fallback"] > 0
    assert eng2.n_budget_breaches > 0


@needs_mesh
def test_serving_snapshot_rebuild_reuses_residency():
    """An append-only write must re-shard incrementally (dirty rows +
    affected stripes only), not rebuild the closure from scratch — and
    stay exact afterwards."""
    rng = np.random.default_rng(13)
    store = fuzz_store(rng)
    mgr = SnapshotManager(store)
    eng = ShardedServingEngine(
        mgr, mesh=make_mesh(data=2, edge=4), max_depth=5
    )
    reqs = fuzz_requests(rng, n=48)
    eng.batch_check(reqs)
    assert eng.n_full_reshards == 1
    assert eng.n_incremental_reshards == 0
    # append-only delta touching interior rows (set -> set edge)
    store.write_relation_tuples(
        t("n:o1#r0@(n:o2#r1)"), t("n:o2#r1@(n:o3#r2)"), t("n:o3#r2@zoe")
    )
    host = CheckEngine(store, max_depth=5)
    got = eng.batch_check(reqs + [t("n:o1#r0@zoe")])
    assert got == host.batch_check(reqs + [t("n:o1#r0@zoe")])
    assert eng.n_full_reshards == 1
    assert eng.n_incremental_reshards == 1
    assert eng.last_reshard["kind"] == "incremental"
    assert eng.last_reshard["dirty_rows"] >= 1


@needs_mesh
def test_mesh_shape_validation_errors():
    with pytest.raises(ValueError):
        make_mesh(jax.devices()[:8], data=3, edge=3)  # 9 != 8
    with pytest.raises(ValueError):
        make_mesh(jax.devices()[:8], data=16, edge=1)


@needs_mesh
def test_breaker_answers_via_oracle_on_launch_fault():
    """KETO_FAULTS site shard.launch_fail: the breaker catches the
    injected launch failure and the host oracle answers — exactly —
    then the device path resumes once the fault disarms."""
    rng = np.random.default_rng(14)
    store = fuzz_store(rng)
    mgr = SnapshotManager(store)
    eng = ShardedServingEngine(
        mgr, mesh=make_mesh(data=1, edge=8), max_depth=5
    )
    host = CheckEngine(store, max_depth=5)
    batcher = make_batcher(eng, store)
    try:
        reqs = fuzz_requests(rng, n=32)
        start, target = encode(mgr.snapshot(), reqs)
        want = host.batch_check(reqs)
        FAULTS.arm("shard.launch_fail", times=1)
        assert batcher.check_batch_encoded(start, target) == want
        assert FAULTS.fired("shard.launch_fail") == 1
        # fault disarmed: the device path serves again and still agrees
        assert batcher.check_batch_encoded(start, target) == want
    finally:
        batcher.close()


class _FakeDevstats:
    def __init__(self, limit, peak=0, n=2):
        self.limit = limit
        self.peak = peak
        self.n = n

    def sample_devices(self):
        return [
            {
                "memory_stats": {
                    "bytes_in_use": 0,
                    "bytes_limit": self.limit,
                    "peak_bytes_in_use": self.peak,
                }
            }
            for _ in range(self.n)
        ]


class TestPerShardHbmClamp:
    """No mesh needed: the admission math over pinned shard residency."""

    def test_clamp_respects_fullest_shard(self):
        hbm = HbmAdmission(
            budget_frac=1.0,
            bytes_per_row=100,
            devstats=_FakeDevstats(limit=1_000_000),
        )
        assert hbm.clamp_rows(8192) == 8192
        # pin 920k on the fullest shard: 80k headroom / 100 B = 800 rows
        hbm.set_shard_residency({0: 500_000.0, 1: 920_000.0})
        assert hbm.clamp_rows(8192) == 800
        assert hbm.snapshot()["resident_floor_bytes"] == 920_000.0
        # rebalance: residency drops, clamp relaxes
        hbm.set_shard_residency({0: 500_000.0, 1: 500_000.0})
        assert hbm.clamp_rows(8192) == 5000

    def test_clamp_floor_under_full_residency(self):
        hbm = HbmAdmission(
            budget_frac=1.0,
            bytes_per_row=100,
            devstats=_FakeDevstats(limit=1_000_000),
        )
        hbm.set_shard_residency({0: 2_000_000.0})  # over budget
        # never clamps below the minimum viable batch
        assert hbm.clamp_rows(8192) >= 1

    def test_shard_peak_model_learns(self):
        stats = _FakeDevstats(limit=1_000_000, peak=0, n=2)
        hbm = HbmAdmission(bytes_per_row=100, devstats=stats)
        tok = hbm.reserve(128, 1)
        stats.peak = 48_000
        hbm.release(tok)
        assert hbm.modeled_shard_bytes(128, 1, 0) == pytest.approx(48_000)
        assert hbm.modeled_shard_bytes(128, 1, 1) == pytest.approx(48_000)
        assert hbm.snapshot()["modeled_shard_shapes"] >= 1
