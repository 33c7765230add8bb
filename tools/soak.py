#!/usr/bin/env python
"""Seeded chaos soak: deterministic fault schedule under concurrent load.

Drives the full serving stack (registry -> breaker-wrapped device engine ->
batcher) with a mixed read-write workload while a seeded schedule arms
crash / slowness / garbage-output faults at fixed OPERATION COUNTS (not
wall-clock), so the same seed always injects the same faults at the same
points in the workload. Thread interleavings still vary run to run; every
invariant below is interleaving-independent:

- **Answer parity.** Tuples inserted before the soak and never touched
  must always check True; tuples never inserted must always check False;
  a tuple the writer has durably committed (insert-only set) must never
  check False when read at-least-as-fresh (min_version pinned to its
  commit). Transient TYPED errors (shed, crashed dispatcher, deadline)
  are tolerated during fault windows — wrong ANSWERS never are.
- **Snaptoken monotonicity.** The read-plane snaptoken never regresses.
- **No lost or double-resolved futures.** Every check resolves (answer or
  typed error) inside its per-op timeout — a lost future would surface as
  a timeout, a double-resolution as a decode-stage crash. Both count
  against the run. The pipeline must also drain to zero at the end.
- **Bounded tail latency.** p99 across the run (fault windows included)
  stays under a generous budget — a wedged stage or an unculled stuck
  batch blows it immediately.

A final parity sweep (faults cleared) compares every asserted tuple
against a fresh host oracle over the final store.

The optional pool phase (``--pool``) forks a 3-worker SO_REUSEPORT
replica pool and mixes the distribution faults the single process cannot
express — ``delta.drop`` (silent version gap -> resync handshake),
``delta.slow`` (stalled propagation), ``replica.crash`` (supervisor
respawn) — asserting every committed write converges to 200 on fresh
connections afterward.

Usage:
    python tools/soak.py --smoke --seed 4        # the tools/check.sh tier
    python tools/soak.py --seed 7 --ops 20000    # longer soak
    python tools/soak.py --smoke --pool          # include the fork phase

Exit 0 and a one-line summary JSON on stdout when every invariant holds;
exit 1 with the violation list otherwise.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import threading
import time
from concurrent.futures import TimeoutError as _FutTimeout

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# two virtual CPU devices: the engine and device-chaos phases serve from
# the sharded serving engine, whose launch carries the device.* fault sites
if "xla_force_host_platform_device_count" not in os.environ.get(
    "XLA_FLAGS", ""
):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=2"
    ).strip()
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def _restart_child_main(spec_raw: str) -> int:
    """Inner process of the kill-and-restart drill (--restart): recover
    the durable store, run a deterministic single-writer op stream
    printing an INTENT line before each mutator and an ACK line after it
    returns, and — when the spec arms a crash-fault site — SIGKILL
    ourselves the moment it fires. The parent (run_restart_drill) replays
    the ack protocol against its own read-only recovery of the directory.

    Imports stay store-local on purpose: a dozen child processes that each
    paid the jax import tax would blow the soak's CI budget.
    """
    import signal

    spec = json.loads(spec_raw)
    from keto_tpu.faults import FAULTS, FaultInjected
    from keto_tpu.relationtuple.definitions import (
        RelationTuple,
        SubjectID,
    )
    from keto_tpu.store import (
        ColumnarTupleStore,
        DurableTupleStore,
        InMemoryTupleStore,
    )
    from keto_tpu.store.wal import encode_tuple

    def emit(obj) -> None:
        print(json.dumps(obj), flush=True)

    FAULTS.reset()
    inner = (
        InMemoryTupleStore()
        if spec["kind"] == "memory"
        else ColumnarTupleStore()
    )
    store = DurableTupleStore(
        inner,
        spec["dir"],
        sync="always",
        # the drill drives checkpoints explicitly; background triggers
        # would make the replay accounting nondeterministic
        checkpoint_interval_versions=10**9,
        checkpoint_interval_s=0.0,
    )
    rep = store.recovery
    emit(
        {
            "recovered": True,
            "version": rep.final_version,
            "replayed": rep.replayed_deltas,
            "gap": rep.gap,
            "checkpoint_version": rep.checkpoint_version,
        }
    )
    site = spec.get("site")
    fault_at = spec.get("fault_at")
    ops = int(spec["ops"])
    rng = random.Random(int(spec["seed"]) * 7919 + int(spec["cycle"]))
    candidates = list(inner.all_tuples())
    try:
        for i in range(ops):
            if i == fault_at and site == "checkpoint.crash_mid_write":
                FAULTS.arm(site)
                emit({"ckpt_at": i})
                store.checkpoint_now()  # raises FaultInjected
            if i == fault_at and site in (
                "wal.torn_write", "wal.corrupt_crc", "wal.crash_after_append"
            ):
                FAULTS.arm(site)
            if candidates and rng.random() < 0.18:
                t = candidates[rng.randrange(len(candidates))]
                emit({"op": i, "k": "d", "t": encode_tuple(t)})
                store.delete_relation_tuples(t)
                candidates.remove(t)
            else:
                t = RelationTuple(
                    namespace="n",
                    object=f"o{rng.randrange(max(8, ops * 3))}",
                    relation="view",
                    subject=SubjectID(id=f"u{rng.randrange(7)}"),
                )
                emit({"op": i, "k": "w", "t": encode_tuple(t)})
                store.write_relation_tuples(t)
                if t not in candidates:
                    candidates.append(t)
            emit({"ack": i, "version": store.version})
            if site is None and i == ops // 2:
                store.checkpoint_now()
                emit({"ckpt": i, "version": store.version})
    except FaultInjected as e:
        # a real crash, not an orderly unwind: nothing may flush or close
        emit({"crashed": True, "site": e.site})
        os.kill(os.getpid(), signal.SIGKILL)
    if site is None:
        store.close_durable()  # exercises the shutdown checkpoint
    emit({"done": True, "version": store.version})
    return 0


def _promotion_child_main(spec_raw: str) -> int:
    """Leader process of the SIGKILL-promotion drill (--restart): a
    durable store plus the real replication routes (ReplicationSource on
    a bare aiohttp app — no engine stack, same import-tax discipline as
    the restart child). Protocol: write a prefix, cut a checkpoint (the
    follower must seed from a real checkpoint), print ``ready`` with the
    port, wait for ``go`` on stdin (the parent's follower has
    bootstrapped), then stream single-writer ops with INTENT/ACK lines —
    and SIGKILL ourselves right after the ``kill_at`` write lands
    durably but BEFORE its ack, the durable-but-unacked edge promotion
    must surface."""
    import asyncio
    import signal

    from aiohttp import web

    spec = json.loads(spec_raw)
    from keto_tpu.relationtuple.definitions import (
        RelationTuple,
        SubjectID,
    )
    from keto_tpu.replication.leader import ReplicationSource
    from keto_tpu.store import DurableTupleStore, InMemoryTupleStore
    from keto_tpu.store.wal import encode_tuple

    def emit(obj) -> None:
        print(json.dumps(obj), flush=True)

    store = DurableTupleStore(
        InMemoryTupleStore(),
        spec["dir"],
        sync="always",  # WAL-before-ack: the invariant under test
        checkpoint_interval_versions=10**9,
        checkpoint_interval_s=0.0,
    )
    rng = random.Random(int(spec["seed"]) * 104729)
    ops = int(spec["ops"])
    kill_at = int(spec["kill_at"])

    def write_op(i: int) -> None:
        t = RelationTuple(
            namespace="n", object=f"promo{i}", relation="view",
            subject=SubjectID(id=f"u{rng.randrange(5)}"),
        )
        emit({"op": i, "t": encode_tuple(t)})
        store.write_relation_tuples(t)

    prefix = max(1, ops // 3)
    for i in range(prefix):
        write_op(i)
        emit(
            {
                "ack": i,
                "version": store.version,
                "token": str(store.current_token()),
            }
        )
    store.checkpoint_now()

    src = ReplicationSource(store, poll_interval_s=0.01)
    app = web.Application()
    src.register(app)
    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()

    async def _serve() -> int:
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        return site._server.sockets[0].getsockname()[1]

    port = asyncio.run_coroutine_threadsafe(_serve(), loop).result(
        timeout=60
    )
    emit({"ready": True, "port": port, "version": store.version})
    sys.stdin.readline()  # parent's follower has seeded: start streaming

    for i in range(prefix, ops):
        write_op(i)
        if i == kill_at:
            # the frame is on disk (sync=always) but the ack never
            # leaves: recovery surfacing exactly this op is correct
            os.kill(os.getpid(), signal.SIGKILL)
        emit(
            {
                "ack": i,
                "version": store.version,
                "token": str(store.current_token()),
            }
        )
        time.sleep(0.01)  # let the follower tail live traffic
    emit({"done": True})
    return 0


def _election_child_main(spec_raw: str) -> int:
    """Elected-leader process of the game-day drill (``--election``): a
    durable store, the real replication routes, AND a real
    :class:`ElectionManager` holding the fencing-token lease over the
    shared WAL directory. Every streamed write is gated on
    ``is_writable()`` — the per-mutation fence the write plane uses —
    and acked only after its WAL frame is durable. SIGKILLs itself right
    after the ``kill_at`` write lands durably but BEFORE its ack, with
    the lease deliberately un-released: the survivors must wait out the
    TTL, exactly like a real power-cord failover."""
    import asyncio
    import signal

    from aiohttp import web

    spec = json.loads(spec_raw)
    from keto_tpu.cluster.election import ElectionManager, LeaseStore
    from keto_tpu.relationtuple.definitions import (
        RelationTuple,
        SubjectID,
    )
    from keto_tpu.replication.leader import ReplicationSource
    from keto_tpu.store import DurableTupleStore, InMemoryTupleStore
    from keto_tpu.store.wal import encode_tuple

    def emit(obj) -> None:
        print(json.dumps(obj), flush=True)

    store = DurableTupleStore(
        InMemoryTupleStore(),
        spec["dir"],
        sync="always",  # WAL-before-ack: the zero-loss invariant
        checkpoint_interval_versions=10**9,
        checkpoint_interval_s=0.0,
    )
    rng = random.Random(int(spec["seed"]) * 7919)
    ops = int(spec["ops"])
    kill_at = int(spec["kill_at"])

    def write_op(i: int) -> None:
        t = RelationTuple(
            namespace="n", object=f"gameday{i}", relation="view",
            subject=SubjectID(id=f"u{rng.randrange(5)}"),
        )
        emit({"op": i, "t": encode_tuple(t)})
        store.write_relation_tuples(t)

    prefix = max(1, ops // 3)
    for i in range(prefix):
        write_op(i)
        emit({"ack": i, "version": store.version})
    store.checkpoint_now()

    src = ReplicationSource(store, poll_interval_s=0.01)
    app = web.Application()
    src.register(app)
    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()

    async def _serve() -> int:
        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        return site._server.sockets[0].getsockname()[1]

    port = asyncio.run_coroutine_threadsafe(_serve(), loop).result(
        timeout=60
    )
    em = ElectionManager(
        LeaseStore(spec["dir"]),
        instance_id="gameday-leader",
        lease_ttl_s=float(spec["ttl"]),
        heartbeat_interval_s=float(spec["hb"]),
        write_url=f"http://127.0.0.1:{port}",
    )
    if not em.ensure_leadership():
        emit({"error": "leader could not take the bootstrap lease"})
        return 1
    em.start()  # renews every hb; SIGKILL leaves the lease to expire
    emit({"ready": True, "port": port, "version": store.version,
          "term": em.term})
    sys.stdin.readline()  # followers seeded: start live traffic

    for i in range(prefix, ops):
        if not em.is_writable():
            emit({"fenced": i})
            break
        write_op(i)
        if i == kill_at:
            # durable but unacked, lease un-released: the real crash
            os.kill(os.getpid(), signal.SIGKILL)
        emit({"ack": i, "version": store.version})
        time.sleep(0.01)
    emit({"done": True})
    return 0


if "--restart-child" in sys.argv:
    # handled BEFORE the keto_tpu.driver import below: the child only
    # needs the store layer, not the engine stack
    sys.exit(
        _restart_child_main(sys.argv[sys.argv.index("--restart-child") + 1])
    )

if "--promotion-child" in sys.argv:
    sys.exit(
        _promotion_child_main(
            sys.argv[sys.argv.index("--promotion-child") + 1]
        )
    )

if "--election-child" in sys.argv:
    sys.exit(
        _election_child_main(
            sys.argv[sys.argv.index("--election-child") + 1]
        )
    )

from keto_tpu.driver import Config, Registry  # noqa: E402
from keto_tpu.faults import FAULTS  # noqa: E402
from keto_tpu.relationtuple.definitions import (  # noqa: E402
    RelationTuple,
    SubjectID,
)
from keto_tpu.utils.errors import KetoError  # noqa: E402

PER_OP_TIMEOUT_S = 10.0  # lost-future detector: no answer in this long
P99_BUDGET_S = 3.0  # generous; catches wedged stages, not CI jitter

#: the schedule draws from these (kind, site, arm kwargs). Slow sleeps are
#: kept far below PER_OP_TIMEOUT_S so a slept batch still resolves.
FAULT_MENU = (
    ("crash", "batcher.dispatcher_die", {}),
    ("crash", "device.compile_error", {"times": 2}),
    ("nan", "device.batch_nan", {}),
    ("slow", "device.slow", {"sleep_ms": 40, "times": 3}),
    ("slow", "batcher.dispatch_slow", {"sleep_ms": 25, "times": 3}),
)


def _tup(obj: str) -> RelationTuple:
    return RelationTuple(
        namespace="n", object=obj, relation="view",
        subject=SubjectID(id="alice"),
    )


class _Violations:
    def __init__(self):
        self.items: list[str] = []
        self._lock = threading.Lock()

    def add(self, msg: str) -> None:
        with self._lock:
            if len(self.items) < 50:  # bounded: one bad invariant can spam
                self.items.append(msg)


def _percentile(sorted_vals: list[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    return sorted_vals[min(len(sorted_vals) - 1, int(q * len(sorted_vals)))]


def run_engine_soak(
    seed: int,
    n_ops: int = 1200,
    n_readers: int = 4,
    n_writes: int = 120,
    n_faults: int = 6,
) -> dict:
    """The in-process phase: registry + breaker + batcher under load.
    Returns the summary dict; violations are listed under 'violations'."""
    rng = random.Random(seed)
    FAULTS.reset()
    cfg = Config(
        values={
            "namespaces": [{"id": 1, "name": "n"}],
            "log": {"level": "error"},
            "engine": {
                "sharding": {"enabled": True},
                "max_batch": 256,
                "cache_size": 0,  # a cache hit would mask engine faults
                "encoded_cache_size": 0,
                "fallback_threshold": 3,
                "fallback_cooldown_ms": 100,
            },
        }
    )
    reg = Registry(cfg)
    store = reg.store()

    static_true = [f"static{i}" for i in range(32)]
    static_false = [f"ghost{i}" for i in range(32)]
    store.transact_relation_tuples([_tup(o) for o in static_true], [])
    checker = reg.checker()

    # committed insert-only tuples: (object, min_version at/after commit)
    committed: list[tuple[str, int]] = []
    committed_lock = threading.Lock()
    violations = _Violations()
    ops_done = [0] * n_readers
    latencies: list[list[tuple[float, bool]]] = [[] for _ in range(n_readers)]
    tolerated: dict[str, int] = {}
    tol_lock = threading.Lock()
    timeouts = [0]
    stop = threading.Event()
    fault_window = threading.Event()  # any injected fault still pending

    # -- deterministic schedule: (trigger at total-op count, menu entry) ----
    schedule = sorted(
        (rng.randrange(n_ops // 8, n_ops), rng.choice(FAULT_MENU))
        for _ in range(n_faults)
    )
    injected: list[dict] = []

    def injector():
        pending = list(schedule)
        armed_sites: list[str] = []
        while not stop.is_set():
            total = sum(ops_done)
            while pending and pending[0][0] <= total:
                trigger, (kind, site, kw) = pending.pop(0)
                if kind == "slow":
                    FAULTS.arm_slow(site, **kw)
                else:
                    FAULTS.arm(site, **kw)
                armed_sites.append(site)
                injected.append(
                    {"at_op": trigger, "kind": kind, "site": site}
                )
                fault_window.set()
            if fault_window.is_set() and not any(
                FAULTS.armed(s) or FAULTS.slow_armed(s)
                for s in armed_sites
            ):
                fault_window.clear()  # everything injected was consumed
            if not pending and not fault_window.is_set():
                return
            stop.wait(0.002)

    def writer():
        wrote = 0
        while wrote < n_writes and not stop.is_set():
            obj = f"dyn{wrote}"
            churn = f"churn{wrote % 8}"
            before = store.version
            # churn tuples cycle insert/delete for version traffic; their
            # answers are never asserted. dyn tuples are insert-only, so
            # "committed => never False" holds at any later version.
            if wrote % 3 == 2:
                store.transact_relation_tuples([], [_tup(churn)])
            else:
                store.transact_relation_tuples(
                    [_tup(obj), _tup(churn)], []
                )
                with committed_lock:
                    committed.append((obj, store.version))
            if store.version <= before:
                violations.add(
                    f"store version did not advance: {before} -> "
                    f"{store.version}"
                )
            wrote += 1
            time.sleep(0.001)

    def classify(e: BaseException) -> None:
        name = type(e).__name__
        with tol_lock:
            tolerated[name] = tolerated.get(name, 0) + 1

    def reader(idx: int):
        r = random.Random(seed * 1000 + idx)
        my_ops = n_ops // n_readers
        for _ in range(my_ops):
            if stop.is_set():
                return
            roll = r.random()
            min_version = 0
            if roll < 0.4:
                obj, want = r.choice(static_true), True
            elif roll < 0.7:
                obj, want = r.choice(static_false), False
            else:
                with committed_lock:
                    if committed:
                        obj, min_version = r.choice(committed)
                        want = True
                    else:
                        obj, want = r.choice(static_true), True
            in_window = fault_window.is_set()
            t0 = time.perf_counter()
            try:
                got = checker.check(
                    _tup(obj),
                    timeout=PER_OP_TIMEOUT_S,
                    min_version=min_version,
                )
            except _FutTimeout:
                timeouts[0] += 1  # a lost future surfaces exactly here
            except KetoError as e:
                classify(e)  # typed + transient: tolerated, not correct-
                # ness — wrong answers below are the real violations
            except Exception as e:  # noqa: BLE001
                violations.add(f"untyped error from check: {e!r}")
            else:
                if got is not want:
                    violations.add(
                        f"wrong answer for {obj}: got {got}, want {want}"
                        f" (min_version={min_version})"
                    )
            latencies[idx].append((time.perf_counter() - t0, in_window))
            ops_done[idx] += 1

    def snaptoken_monitor():
        last = -1
        while not stop.is_set():
            v = int(reg.read_snaptoken())
            if v < last:
                violations.add(f"snaptoken regressed: {last} -> {v}")
            last = v
            stop.wait(0.005)

    threads = [
        threading.Thread(target=reader, args=(i,), daemon=True)
        for i in range(n_readers)
    ]
    threads += [
        threading.Thread(target=writer, daemon=True),
        threading.Thread(target=snaptoken_monitor, daemon=True),
    ]
    inj = threading.Thread(target=injector, daemon=True)
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    inj.start()
    for t in threads[:n_readers]:
        t.join(timeout=300)
        if t.is_alive():
            violations.add("reader wedged past the soak budget")
    stop.set()
    inj.join(timeout=10)
    for t in threads[n_readers:]:
        t.join(timeout=10)
    wall_s = time.perf_counter() - t_start

    # -- drain + final parity sweep against the host oracle -----------------
    FAULTS.reset()  # disarm leftovers (e.g. an unconsumed slow arming)
    deadline = time.time() + 30
    # a serial batcher (no split-API engine) keeps no pipeline registry
    stats = checker.pipeline_stats()
    while stats.get("batches_in_pipeline") and time.time() < deadline:
        time.sleep(0.05)
        stats = checker.pipeline_stats()
    if stats.get("batches_in_pipeline"):
        violations.add(
            f"pipeline did not drain: {stats['batches_in_pipeline']} "
            "batches still registered"
        )
    from keto_tpu.engine.check import CheckEngine

    oracle = CheckEngine(store, max_depth=5)
    with committed_lock:
        sweep = (
            [(o, True) for o in static_true]
            + [(o, False) for o in static_false]
            + [(o, True) for o, _v in committed]
        )
    parity_mismatches = 0
    for obj, want in sweep:
        o = oracle.subject_is_allowed(_tup(obj))
        try:
            c = checker.check(_tup(obj), timeout=PER_OP_TIMEOUT_S)
        except KetoError:
            c = None  # breaker may still be cooling down; oracle is truth
        if o is not want or (c is not None and c is not o):
            parity_mismatches += 1
            violations.add(
                f"parity sweep: {obj} oracle={o} served={c} want={want}"
            )

    all_lat = sorted(l for per in latencies for (l, _w) in per)
    window_lat = sorted(l for per in latencies for (l, w) in per if w)
    p99 = _percentile(all_lat, 0.99)
    if p99 > P99_BUDGET_S:
        violations.add(f"p99 {p99 * 1e3:.0f}ms over {P99_BUDGET_S}s budget")
    if timeouts[0]:
        violations.add(f"{timeouts[0]} checks timed out (lost futures?)")

    checker.close()
    summary = {
        "phase": "engine",
        "seed": seed,
        "ops": sum(ops_done),
        "wall_s": round(wall_s, 2),
        "faults_injected": injected,
        "tolerated_errors": tolerated,
        "timeouts": timeouts[0],
        "p50_ms": round(_percentile(all_lat, 0.50) * 1e3, 2),
        "p99_ms": round(p99 * 1e3, 2),
        "p99_fault_window_ms": round(
            _percentile(window_lat, 0.99) * 1e3, 2
        ),
        "deadline_culls": stats.get("deadline_expired", {}),
        "parity_mismatches": parity_mismatches,
        "violations": violations.items,
    }
    return summary


def run_overload_drill(seed: int, smoke: bool = True) -> dict:
    """The 10x open-loop overload drill (--overload): boot the serving
    stack with the overload-control plane on, measure this process's
    closed-loop check capacity, then offer ~10x that rate open-loop with
    a 20/60/20 critical/default/sheddable mix, every shed retried
    through a shared client RetryBudget. Invariants:

    - goodput (served accepted checks/s) during the burst >= 0.8x the
      measured capacity — admission control keeps the engine busy on
      work it finishes instead of queueing everything;
    - zero critical-class sheds; the first default-class shed never
      precedes the first sheddable-class shed;
    - accepted checks keep a bounded p99 (the CoDel cull + LIFO flip
      serve admitted work fresh);
    - client retry amplification (attempts/requests) <= 1.1x;
    - the brownout ladder is visibly engaged during the burst (state >=
      shed_sheddable, flight kind=overload transitions recorded) and
      steps back to normal within the hysteresis windows after the
      offered load drops to 1x.
    """
    from concurrent.futures import ThreadPoolExecutor

    from keto_tpu.client.retry import RetryBudget
    from keto_tpu.engine.overload import CRITICAL, DEFAULT, SHEDDABLE
    from keto_tpu.utils.errors import ErrResourceExhausted

    FAULTS.reset()
    hysteresis_s = 0.4
    cfg = Config(
        values={
            "namespaces": [{"id": 1, "name": "n"}],
            "log": {"level": "error"},
            "engine": {
                "mode": "closure",
                "query_mode": "device",
                # a deliberately window-bound engine (~max_batch/window
                # checks/s) so a thread-pool client can genuinely offer
                # 10x its capacity — the pool of blocking workers tops
                # out near 4k submits/s on a small box, so the engine
                # must serve well under that for real pressure to build;
                # max_queue is sized out of reach so every shed in this
                # drill is the LADDER's decision, not the static
                # backstop's
                "max_batch": 8,
                "batch_window_us": 8000,
                "max_queue": 100000,
                "cache_size": 0,  # cache hits would fake infinite capacity
                "encoded_cache_size": 0,
            },
            "overload": {
                "enabled": True,
                "target_delay_ms": 50.0,
                "interval_ms": 50.0,
                "hysteresis_ms": hysteresis_s * 1e3,
                "dwell_ms": 25.0,
                "throttle_window_s": 5.0,
            },
        }
    )
    reg = Registry(cfg)
    store = reg.store()
    objs = [f"o{i}" for i in range(64)]
    store.transact_relation_tuples([_tup(o) for o in objs], [])
    checker = reg.checker()
    controller = reg.overload()
    violations = _Violations()
    rng = random.Random(seed)

    def crit_for(i: int) -> str:
        # 8/62/30 critical/default/sheddable: the critical slice of a
        # 10x burst (0.8x capacity) must fit under capacity alone, or no
        # admission policy could serve it all without shedding critical
        r = i % 50
        return CRITICAL if r < 4 else (DEFAULT if r < 35 else SHEDDABLE)

    budget = RetryBudget(ratio=0.1)
    attempts = [0]
    stats_lock = threading.Lock()
    # per-class: [accepted, shed, culled]; plus first-shed timestamps
    # (admission sheds only) for the ordering invariant
    by_class = {c: [0, 0, 0] for c in (CRITICAL, DEFAULT, SHEDDABLE)}
    first_shed: dict = {}
    accepted_lat: list[float] = []
    last_accept = [0.0]

    def one_check(i: int, crit: str, retry: bool) -> None:
        """One client request: check, and on a shed spend the shared
        retry budget for exactly one immediate retry."""
        budget.on_request()
        for attempt in (0, 1):
            t0 = time.perf_counter()
            with stats_lock:
                attempts[0] += 1
            try:
                checker.check(
                    _tup(objs[i % len(objs)]),
                    timeout=PER_OP_TIMEOUT_S,
                    criticality=crit,
                )
            except ErrResourceExhausted as e:
                # the CoDel cull (queued work dropped for aging past the
                # delay target) is latency protection, not an admission
                # decision — keep it out of the shed-ordering accounting
                is_cull = "culled" in str(e)
                with stats_lock:
                    if is_cull:
                        by_class[crit][2] += 1
                    else:
                        by_class[crit][1] += 1
                        first_shed.setdefault(crit, time.perf_counter())
                if retry and attempt == 0 and budget.spend():
                    continue
                return
            except KetoError:
                return  # typed transient: not this drill's concern
            except Exception as e:  # noqa: BLE001
                violations.add(f"untyped error from check: {e!r}")
                return
            with stats_lock:
                by_class[crit][0] += 1
                accepted_lat.append(time.perf_counter() - t0)
                last_accept[0] = time.perf_counter()
            return

    # -- phase 1: closed-loop capacity measurement ---------------------------
    # capacity is a supremum: a scheduler stall can only DEPRESS a
    # closed-loop window, never inflate it, so the max over two windows
    # is the robust estimate — an under-measured capacity would make
    # the "10x" burst not actually exceed the engine and the ladder
    # (correctly) never engage
    warm_s = 1.0 if smoke else 2.0
    n_workers = 16
    counted = [0]
    t_end = [0.0]

    def closed_loop(idx: int) -> None:
        i = idx
        while time.perf_counter() < t_end[0]:
            one_check(i, DEFAULT, retry=False)
            i += n_workers
            with stats_lock:
                counted[0] += 1

    capacity = 0.0
    for _ in range(2):
        with stats_lock:
            counted[0] = 0
        t_end[0] = time.perf_counter() + warm_s
        with ThreadPoolExecutor(max_workers=n_workers) as pool:
            list(pool.map(closed_loop, range(n_workers)))
        capacity = max(capacity, counted[0] / warm_s)
    if capacity <= 0:
        violations.add("capacity measurement served nothing")
        capacity = 1.0
    # reset the per-class accounting: only the burst is asserted on
    with stats_lock:
        for c in by_class:
            by_class[c] = [0, 0, 0]
        first_shed.clear()
        accepted_lat.clear()
        attempts[0] = 0

    # -- phase 2: 10x open-loop burst ----------------------------------------
    # offered rate is fixed at 10x capacity regardless of completions
    # (open loop); total submissions bounded so CI wall time stays sane
    burst_s = min(3.0 if smoke else 6.0, 30000.0 / (10.0 * capacity))
    offered_rate = 10.0 * capacity
    n_offered = int(offered_rate * burst_s)
    requests_made = [0]
    t_burst0 = time.perf_counter()
    pool = ThreadPoolExecutor(max_workers=128)
    try:
        tick_s = 0.005
        i = 0
        # the ladder can step back down before the last submission
        # lands (hysteresis is short by design here), so the "did the
        # shed rungs engage" check needs the max state seen across the
        # burst, not a point sample at the end
        burst_state = 0
        while i < n_offered:
            tick_deadline = time.perf_counter() + tick_s
            target = min(
                n_offered,
                int((time.perf_counter() - t_burst0) * offered_rate)
                + int(offered_rate * tick_s),
            )
            while i < target:
                pool.submit(one_check, rng.randrange(1 << 20),
                            crit_for(i), True)
                requests_made[0] += 1
                i += 1
            burst_state = max(burst_state, controller.state())
            now = time.perf_counter()
            if now < tick_deadline:
                time.sleep(tick_deadline - now)
        burst_state = max(burst_state, controller.state())
        pool.shutdown(wait=True)
    finally:
        pool.shutdown(wait=True)
    burst_wall = time.perf_counter() - t_burst0
    # goodput is measured to the LAST acceptance, not the full drain:
    # once the submitter stops, what remains in the pool is mostly the
    # shed/retry path (fast rejections against a dry budget) — wall
    # time spent draining it says nothing about how fast admitted work
    # was served
    served_wall = (
        last_accept[0] - t_burst0
        if last_accept[0] > t_burst0
        else burst_wall
    )
    goodput = sum(v[0] for v in by_class.values()) / max(served_wall, 1e-9)
    goodput_frac = goodput / capacity
    amplification = attempts[0] / max(1, requests_made[0])

    # -- phase 3: load drops to ~0 — ladder must step back down --------------
    recover_deadline = time.perf_counter() + (4 * hysteresis_s + 2.0)
    recovered_in_s = None
    t_rec0 = time.perf_counter()
    while time.perf_counter() < recover_deadline:
        one_check(rng.randrange(1 << 20), DEFAULT, retry=False)
        if controller.state() == 0:
            recovered_in_s = time.perf_counter() - t_rec0
            break
        time.sleep(0.02)

    # -- invariants ----------------------------------------------------------
    if goodput_frac < 0.8:
        violations.add(
            f"goodput at 10x was {goodput_frac:.2f}x of capacity "
            f"({goodput:.0f}/s vs {capacity:.0f}/s), below the 0.8x floor"
        )
    if by_class[CRITICAL][1]:
        violations.add(
            f"{by_class[CRITICAL][1]} critical-class sheds — the ladder "
            "must never shed critical"
        )
    if by_class[CRITICAL][2]:
        violations.add(
            f"{by_class[CRITICAL][2]} critical-class culls — the CoDel "
            "cull must exempt critical entries"
        )
    if not by_class[SHEDDABLE][1]:
        violations.add("10x burst shed nothing sheddable — admission dead")
    if DEFAULT in first_shed and SHEDDABLE in first_shed:
        if first_shed[DEFAULT] < first_shed[SHEDDABLE]:
            violations.add(
                "a default-class request was shed before any "
                "sheddable-class request — brownout ordering violated"
            )
    if burst_state < 3:
        violations.add(
            f"the burst never engaged the shed rungs (state={burst_state})"
        )
    if amplification > 1.1:
        violations.add(
            f"retry amplification {amplification:.3f}x over the 1.1x "
            "budget ceiling"
        )
    lat = sorted(accepted_lat)
    accepted_p99 = _percentile(lat, 0.99)
    if accepted_p99 > P99_BUDGET_S:
        violations.add(
            f"accepted p99 {accepted_p99 * 1e3:.0f}ms over the "
            f"{P99_BUDGET_S}s budget — admitted work is not being "
            "served fresh"
        )
    if recovered_in_s is None:
        violations.add(
            f"ladder did not return to normal within "
            f"{4 * hysteresis_s + 2.0:.1f}s of the burst ending "
            f"(state={controller.state()})"
        )
    flight_overload = [
        r for r in reg.flight().records()
        if r.get("kind") == "overload"
    ]
    if not flight_overload:
        violations.add("no kind=overload flight records from the burst")

    checker.close()
    snap = controller.snapshot()
    return {
        "phase": "overload",
        "seed": seed,
        "capacity_per_s": round(capacity, 1),
        "offered_rate_per_s": round(offered_rate, 1),
        # the realized rate can trail the 10x attempt when the client
        # pool itself saturates; still well past capacity, which is what
        # the invariants need
        "offered_realized_per_s": round(
            requests_made[0] / max(burst_wall, 1e-9), 1
        ),
        "burst_s": round(burst_wall, 2),
        "served_wall_s": round(served_wall, 2),
        "goodput_per_s": round(goodput, 1),
        "goodput_frac": round(goodput_frac, 3),
        "burst_state": burst_state,
        "accepted_by_class": {c: v[0] for c, v in by_class.items()},
        "shed_by_class": {c: v[1] for c, v in by_class.items()},
        "culled_by_class": {c: v[2] for c, v in by_class.items()},
        "retry_amplification": round(amplification, 3),
        "accepted_p99_ms": round(accepted_p99 * 1e3, 2),
        "recovered_in_s": (
            round(recovered_in_s, 2) if recovered_in_s is not None else None
        ),
        "flight_transitions": len(flight_overload),
        "controller": snap,
        "violations": violations.items,
    }


def run_device_chaos(seed: int) -> dict:
    """--device-chaos: the device-fault & memory-pressure drills.

    Three seeded scenarios against one registry, asserting ZERO wrong
    answers and bounded recovery throughout:

    1. OOM bisection — ``device.oom`` armed 3x against one 120-row
       encoded batch: every answer must match the host oracle, the
       breaker must stay closed (no host-fallback escalation), and
       ``keto_device_oom_bisections_total`` must reach >= 3.
    2. Compile-failure quarantine — ``device.compile_fail`` armed: the
       failing shape is absorbed into the quarantine (host oracle answers
       it) WITHOUT opening the circuit for every other shape.
    3. Device loss — ``device.lost`` armed: the lost batch is answered by
       the host oracle, the supervisor runs its failover/re-probe loop,
       and serving must return to device mode inside a bounded window,
       visible in the supervisor timeline and the flight recorder.
    """
    from keto_tpu.relationtuple.columns import CheckColumns

    recovery_bound_s = 15.0
    FAULTS.reset()
    rng = random.Random(seed)
    violations = _Violations()
    cfg = Config(
        values={
            "namespaces": [{"id": 1, "name": "n"}],
            "log": {"level": "error"},
            "engine": {
                "sharding": {"enabled": True},
                "max_batch": 256,
                "cache_size": 0,  # a cache hit would mask device faults
                "encoded_cache_size": 0,
                "fallback_threshold": 3,
                "fallback_cooldown_ms": 100,
                # inproc probe: the drill runs on the CPU test mesh where
                # a child re-probe proves nothing and costs a process spawn
                "failover": {
                    "probe_mode": "inproc",
                    "probe_interval_s": 0.05,
                },
            },
        }
    )
    reg = Registry(cfg)
    store = reg.store()
    true_objs = [f"ok{i}" for i in range(48)]
    store.transact_relation_tuples([_tup(o) for o in true_objs], [])
    checker = reg.checker()
    breaker = reg._engine_breaker
    supervisor = reg.device_supervisor()

    def counter(name: str) -> float:
        m = reg.metrics()._metrics.get(name)
        return float(m.value) if m is not None else 0.0

    def batch(n_rows: int):
        """(validated CheckColumns, expected answers): half present
        objects, half ghosts — wrong answers are detectable both ways."""
        objs, want = [], []
        for _ in range(n_rows):
            if rng.random() < 0.5:
                objs.append(true_objs[rng.randrange(len(true_objs))])
                want.append(True)
            else:
                objs.append(f"ghost{rng.randrange(64)}")
                want.append(False)
        cols = CheckColumns(
            ["n"] * n_rows, objs, ["view"] * n_rows,
            subject_ids=["alice"] * n_rows,
        )
        return cols.validate(), want

    def wrong_count(cols_want, label: str) -> int:
        # the encoded path: the breaker's launch seam (OOM bisection,
        # compile quarantine) guards exactly these batches
        cols, want = cols_want
        s_ids, t_ids = reg.snapshots().snapshot().encode_requests_columnar(
            cols
        )
        got = checker.check_batch_encoded(s_ids, t_ids, [5] * len(want))
        wrong = sum(1 for g, w in zip(got, want) if bool(g) is not w)
        if wrong:
            violations.add(f"{label}: {wrong}/{len(want)} wrong answers")
        return wrong

    # -- drill 1: OOM bisection ---------------------------------------------
    fb_before = counter("keto_device_fallback_batches_total")
    FAULTS.arm("device.oom", times=3)
    wrong_count(batch(120), "oom drill")
    bisections = counter("keto_device_oom_bisections_total")
    if bisections < 3:
        violations.add(
            f"oom drill: expected >= 3 bisections, saw {bisections}"
        )
    if counter("keto_device_fallback_batches_total") > fb_before:
        violations.add("oom drill: escalated to host fallback")
    if breaker.circuit_open():
        violations.add("oom drill: tripped the breaker")

    # -- drill 2: compile-failure quarantine --------------------------------
    FAULTS.arm("device.compile_fail")
    wrong_count(batch(96), "compile-fail drill")  # oracle absorbs the shape
    if not breaker.quarantine_snapshot():
        violations.add("compile-fail drill: shape was not quarantined")
    if breaker.circuit_open():
        violations.add("compile-fail drill: quarantine opened the circuit")
    quarantine_size = counter("keto_compile_quarantine_size")

    # -- drill 3: device loss -> failover -> bounded recovery ---------------
    failovers_before = counter("keto_backend_failovers_total")
    FAULTS.arm("device.lost")
    t_lost = time.monotonic()
    wrong_count(batch(64), "device-lost drill (during loss)")
    status = None
    deadline = t_lost + recovery_bound_s
    while time.monotonic() < deadline:
        status = supervisor.status() if supervisor is not None else None
        if (
            status is not None
            and status["failovers"] >= 1
            and not status["recovering"]
        ):
            break
        time.sleep(0.05)
    else:
        violations.add(
            f"device-lost drill: no recovery inside {recovery_bound_s}s "
            f"(status={status})"
        )
    if counter("keto_backend_failovers_total") <= failovers_before:
        violations.add("device-lost drill: failover counter did not move")
    # recovery ends with a forced half-open probe: the next batch must be
    # served by the device again with the circuit closing behind it
    wrong_count(batch(64), "device-lost drill (after recovery)")
    if breaker.circuit_open():
        violations.add("device-lost drill: circuit still open post-recovery")
    flight = reg.flight()
    failover_records = [
        r
        for r in (flight.records(200) if flight is not None else [])
        if r.get("kind") == "device_failover"
    ]
    if not failover_records:
        violations.add(
            "device-lost drill: no device_failover flight records"
        )

    FAULTS.reset()
    checker.close()
    if supervisor is not None:
        supervisor.stop()
    return {
        "phase": "device_chaos",
        "seed": seed,
        "oom_bisections": bisections,
        "compile_quarantine_size": quarantine_size,
        "failovers": counter("keto_backend_failovers_total"),
        "last_recovery_s": (
            status.get("last_recovery_s") if status is not None else None
        ),
        "failover_flight_records": len(failover_records),
        "violations": violations.items,
    }


def run_pool_soak(seed: int, n_rounds: int = 3, per_round: int = 4) -> dict:
    """The fork phase: 3-worker replica pool under delta.drop/delta.slow/
    replica.crash; every committed write must converge to 200 on fresh
    connections (the resync/respawn machinery is what's under test)."""
    import asyncio

    import httpx

    rng = random.Random(seed + 1)
    FAULTS.reset()
    # armed BEFORE the fork so every replica inherits it: each child
    # crashes applying its first delta, and the supervisor must respawn
    # the whole pool from the zygote (the existing drill in
    # tests/test_faults.py::test_inherited_replica_crash_fault_heals)
    FAULTS.arm("replica.crash")
    cfg = Config(
        values={
            "namespaces": [{"id": 1, "name": "n"}],
            "log": {"level": "error"},
            "serve": {
                "read": {"port": 0, "host": "127.0.0.1", "workers": 3},
                "write": {"port": 0, "host": "127.0.0.1"},
            },
        }
    )
    reg = Registry(cfg)
    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()
    violations = _Violations()
    injected: list[dict] = []
    t_start = time.perf_counter()
    try:
        rp, wp = asyncio.run_coroutine_threadsafe(
            reg.start_all(), loop
        ).result(timeout=120)
        # parent disarms NOW: respawn commands carry the parent's current
        # snapshot, so replacements come back clean and the pool heals
        FAULTS.disarm("replica.crash")
        injected.append({"round": -1, "site": "replica.crash"})

        def put(obj: str) -> None:
            body = {
                "namespace": "n", "object": obj, "relation": "view",
                "subject_id": "alice",
            }
            r = httpx.put(
                f"http://127.0.0.1:{wp}/relation-tuples",
                json=body, timeout=30,
            )
            if r.status_code != 201:
                violations.add(f"write {obj} failed: {r.status_code}")

        def converges(obj: str, timeout: float = 60.0) -> bool:
            params = {
                "namespace": "n", "object": obj, "relation": "view",
                "subject_id": "alice",
            }
            deadline = time.time() + timeout
            streak = 0
            while streak < 12 and time.time() < deadline:
                try:  # fresh connection per probe: covers every replica
                    r = httpx.get(
                        f"http://127.0.0.1:{rp}/check",
                        params=params, timeout=10,
                    )
                    streak = streak + 1 if r.status_code == 200 else 0
                except httpx.HTTPError:
                    streak = 0
                time.sleep(0.01)
            return streak >= 12

        wrote: list[str] = []
        for rnd in range(n_rounds):
            site = ("delta.drop", "delta.slow")[rng.randrange(2)]
            if site == "delta.slow":
                FAULTS.arm_slow(site, sleep_ms=200)
            else:
                FAULTS.arm(site)
            injected.append({"round": rnd, "site": site})
            for i in range(per_round):
                obj = f"pool{rnd}_{i}"
                put(obj)
                wrote.append(obj)
            FAULTS.reset()  # respawn snapshots must come back clean
            for obj in wrote[-per_round:]:
                if not converges(obj):
                    violations.add(
                        f"{obj} never converged after {site} round"
                    )
        # everything ever written still answers everywhere
        for obj in (wrote[0], wrote[-1]):
            if not converges(obj):
                violations.add(f"{obj} lost after the full soak")
        m = reg.metrics()._metrics
        respawn_count = (
            m["keto_replica_respawns_total"].value
            if "keto_replica_respawns_total" in m
            else 0
        )
        if respawn_count < 1:
            violations.add(
                "inherited replica.crash produced no respawns — the "
                "supervisor/zygote heal path never ran"
            )
        summary = {
            "phase": "pool",
            "seed": seed,
            "writes": len(wrote),
            "wall_s": round(time.perf_counter() - t_start, 2),
            "faults_injected": injected,
            "respawns": respawn_count,
            "resyncs": m["keto_replica_resyncs_total"].value
            if "keto_replica_resyncs_total" in m
            else 0,
            "violations": violations.items,
        }
        return summary
    finally:
        FAULTS.reset()
        try:
            asyncio.run_coroutine_threadsafe(reg.stop_all(), loop).result(
                timeout=30
            )
        except Exception:
            pass
        loop.call_soon_threadsafe(loop.stop)


def _tree_sig(tree):
    """Order-independent canonical form of an expand tree for parity
    comparison (children arrive in store insertion order, which differs
    between a recovered store and a freshly rebuilt oracle)."""
    if tree is None:
        return None
    d = tree.to_dict()

    def canon(node):
        kids = node.get("children")
        if kids:
            node["children"] = sorted(
                (canon(k) for k in kids), key=lambda n: json.dumps(n, sort_keys=True)
            )
        return node

    return json.dumps(canon(d), sort_keys=True)


def run_restart_drill(seed: int, ops_per_cycle: int = 40) -> dict:
    """Kill-and-restart drill: SIGKILL the writer at every seeded crash
    fault under ``wal.sync=always`` and assert zero acked-write loss.

    For each store kind (memory, columnar) the drill runs a child process
    per cycle (clean warm-up with mid-run + shutdown checkpoints, then one
    cycle per crash site, then a clean verify). The child prints an INTENT
    line before each mutator and an ACK line after it returns; a fired
    fault SIGKILLs the child mid-protocol. After every child exits, the
    parent recovers the directory READ-ONLY and asserts:

    - no WAL gap, and a checkpoint is in play after the first cycle
      (replay alone must not carry the whole history);
    - the recovered tuple set is exactly the acked oracle — plus, only
      for ``wal.crash_after_append``, the one durable-but-unacked op
      (written + fsynced before the kill: recovering it is correct);
    - the recovered snaptoken matches the same rule and never regresses;
    - Check AND Expand parity between the recovered store and a fresh
      in-memory shadow oracle holding the same tuples.
    """
    import shutil
    import subprocess
    import tempfile

    from keto_tpu.engine.check import CheckEngine
    from keto_tpu.engine.expand import ExpandEngine
    from keto_tpu.relationtuple.definitions import SubjectSet
    from keto_tpu.store import (
        ColumnarTupleStore,
        InMemoryTupleStore,
        recover_store,
    )
    from keto_tpu.store.wal import decode_tuple, encode_tuple

    t0 = time.monotonic()
    viol = _Violations()
    cycles_run = 0
    crash_sites = (
        "wal.crash_after_append",
        "wal.torn_write",
        "wal.corrupt_crc",
        "checkpoint.crash_mid_write",
    )
    for kind, store_cls in (
        ("memory", InMemoryTupleStore),
        ("columnar", ColumnarTupleStore),
    ):
        root = tempfile.mkdtemp(prefix=f"keto-restart-{kind}-")
        wal_dir = os.path.join(root, "wal")
        ckpt_dir = os.path.join(wal_dir, "checkpoints")
        oracle: set = set()  # acked tuple state (encoded, hashable)
        last_ack_version = 0
        prev_recovered_version = 0
        try:
            schedule = [None] + list(crash_sites) + [None]
            for cycle, site in enumerate(schedule):
                tag = f"{kind}/cycle{cycle}/{site or 'clean'}"
                spec = {
                    "dir": wal_dir,
                    "kind": kind,
                    "site": site,
                    # past the mid-cycle point so crashes land on a
                    # non-empty uncheckpointed suffix
                    "fault_at": (ops_per_cycle * 2) // 3 if site else None,
                    "ops": ops_per_cycle,
                    "seed": seed,
                    "cycle": cycle,
                }
                proc = subprocess.run(
                    [sys.executable, os.path.abspath(__file__),
                     "--restart-child", json.dumps(spec)],
                    capture_output=True, text=True, timeout=180,
                )
                lines = []
                for raw in proc.stdout.splitlines():
                    try:
                        lines.append(json.loads(raw))
                    except json.JSONDecodeError:
                        viol.add(f"{tag}: undecodable child line {raw!r}")
                crashed = any("crashed" in l for l in lines)
                done = any("done" in l for l in lines)
                if site and not crashed:
                    viol.add(f"{tag}: armed fault never fired "
                             f"(rc={proc.returncode})")
                    continue
                if not site and not done:
                    viol.add(
                        f"{tag}: clean cycle did not complete "
                        f"(rc={proc.returncode}, stderr tail: "
                        f"{proc.stderr[-400:]!r})"
                    )
                    continue
                cycles_run += 1

                # -- child's own recovery report for this cycle ---------------
                rec = next((l for l in lines if l.get("recovered")), None)
                if rec is None:
                    viol.add(f"{tag}: child printed no recovery line")
                    continue
                if rec["gap"]:
                    viol.add(f"{tag}: child recovery reported a WAL gap")
                if cycle >= 1 and rec["checkpoint_version"] == 0:
                    viol.add(f"{tag}: recovery ran without a checkpoint "
                             "(full-history replay)")
                if rec["version"] < prev_recovered_version:
                    viol.add(
                        f"{tag}: snaptoken regressed across restart: "
                        f"{prev_recovered_version} -> {rec['version']}"
                    )

                # -- fold this cycle's acked ops into the oracle --------------
                acked = {l["ack"] for l in lines if "ack" in l}
                intents = [l for l in lines if "op" in l]
                uncertain = None
                for intent in intents:
                    key = tuple(intent["t"])
                    if intent["op"] in acked:
                        if intent["k"] == "w":
                            oracle.add(key)
                        else:
                            oracle.discard(key)
                    elif uncertain is None:
                        uncertain = intent
                    else:
                        viol.add(f"{tag}: more than one unacked intent")
                versions = [l["version"] for l in lines if "ack" in l]
                if versions and versions != sorted(versions):
                    viol.add(f"{tag}: ack versions not monotonic")
                if versions:
                    last_ack_version = versions[-1]

                # -- parent-side read-only recovery + invariants --------------
                recovered = store_cls()
                rep = recover_store(recovered, wal_dir, ckpt_dir)
                if rep.gap:
                    viol.add(f"{tag}: parent recovery reported a WAL gap: "
                             f"{rep.notes}")
                got = {tuple(encode_tuple(t)) for t in recovered.all_tuples()}
                expect = set(oracle)
                expect_version = last_ack_version
                if site == "wal.crash_after_append" and uncertain is not None:
                    # the killed op's record was durable (written + fsynced)
                    # before the kill: recovery MUST surface it
                    key = tuple(uncertain["t"])
                    if uncertain["k"] == "w":
                        expect.add(key)
                    else:
                        expect.discard(key)
                    expect_version = last_ack_version + 1
                if got != expect:
                    viol.add(
                        f"{tag}: acked-write divergence: "
                        f"{len(expect - got)} lost, "
                        f"{len(got - expect)} phantom"
                    )
                else:
                    # adopt: the durable-but-unacked op (if any) is now
                    # part of the baseline the next cycle builds on
                    oracle = expect
                if rep.final_version != expect_version:
                    viol.add(
                        f"{tag}: recovered snaptoken {rep.final_version} "
                        f"!= expected {expect_version}"
                    )
                last_ack_version = max(last_ack_version, rep.final_version)
                prev_recovered_version = rep.final_version

                # -- Check/Expand parity vs a fresh shadow oracle -------------
                tuples = recovered.all_tuples()
                shadow = InMemoryTupleStore()
                if tuples:
                    shadow.write_relation_tuples(*tuples)
                ce_r = CheckEngine(recovered)
                ce_s = CheckEngine(shadow)
                for t in tuples[:25]:
                    if not ce_r.subject_is_allowed(t):
                        viol.add(f"{tag}: recovered store denies {t}")
                    if not ce_s.subject_is_allowed(t):
                        viol.add(f"{tag}: shadow oracle denies {t}")
                for j in range(8):
                    probe = decode_tuple(
                        ["n", f"absent{j}", "view", 0, "nobody"]
                    )
                    if ce_r.subject_is_allowed(probe) or ce_s.subject_is_allowed(
                        probe
                    ):
                        viol.add(f"{tag}: phantom allow for {probe}")
                ee_r = ExpandEngine(recovered)
                ee_s = ExpandEngine(shadow)
                for obj in sorted({t.object for t in tuples})[:5]:
                    ss = SubjectSet(namespace="n", object=obj, relation="view")
                    if _tree_sig(ee_r.build_tree(ss)) != _tree_sig(
                        ee_s.build_tree(ss)
                    ):
                        viol.add(f"{tag}: expand divergence on {obj}")
        finally:
            shutil.rmtree(root, ignore_errors=True)
    return {
        "phase": "restart",
        "cycles": cycles_run,
        "elapsed_s": round(time.monotonic() - t0, 2),
        "violations": viol.items,
    }


def run_promotion_drill(seed: int, ops: int = 60) -> dict:
    """SIGKILL-the-leader drill: a follower seeded from the leader's
    checkpoint and tailing its WAL gets promoted off the dead leader's
    log (shared-disk failover) and must hold EVERY acked write.

    The leader child acks each write only after its WAL frame is durable
    (sync=always) and kills itself mid-stream right after one durable-
    but-unacked write — so the drill asserts the full WAL-before-ack
    contract: zero acked writes lost, ack tokens monotonic, at most the
    one unacked op surfacing as a recovered extra, and the promoted node
    serving at-least-latest reads with no residual lag."""
    import shutil
    import signal
    import subprocess
    import tempfile

    from keto_tpu.replication.follower import FollowerReplicator
    from keto_tpu.replication.token import parse_snaptoken
    from keto_tpu.store import InMemoryTupleStore
    from keto_tpu.store.wal import encode_tuple

    t0 = time.monotonic()
    viol = _Violations()
    root = tempfile.mkdtemp(prefix="keto-promotion-")
    wal_dir = os.path.join(root, "wal")
    scratch = os.path.join(root, "follower")
    rng = random.Random(seed + 31)
    kill_at = rng.randrange((ops * 2) // 3, ops - 2)
    spec = {"dir": wal_dir, "ops": ops, "seed": seed, "kill_at": kill_at}
    follower = None
    proc = None
    summary = {"phase": "promotion", "seed": seed, "kill_at": kill_at}
    try:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--promotion-child", json.dumps(spec)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True,
        )
        lines: list[dict] = []

        def _take(raw: str):
            try:
                doc = json.loads(raw)
            except json.JSONDecodeError:
                viol.add(f"promotion: undecodable child line {raw!r}")
                return None
            lines.append(doc)
            return doc

        port = None
        for raw in proc.stdout:
            doc = _take(raw)
            if doc and doc.get("ready"):
                port = doc["port"]
                break
        if port is None:
            err = proc.stderr.read()[-400:] if proc.stderr else ""
            viol.add(f"promotion: leader child never became ready ({err!r})")
            return {**summary, "violations": viol.items}

        follower = FollowerReplicator(
            InMemoryTupleStore(),
            f"http://127.0.0.1:{port}",
            scratch_dir=scratch,
            poll_interval_s=0.01,
            wait_ms=200.0,
        )
        follower.start()
        if follower.store.version <= 0:
            viol.add(
                "promotion: follower did not seed from the leader's "
                "checkpoint"
            )
        proc.stdin.write("go\n")
        proc.stdin.flush()
        for raw in proc.stdout:  # drains until SIGKILL closes the pipe
            _take(raw)
        rc = proc.wait(timeout=60)
        if any("done" in l for l in lines):
            viol.add(
                f"promotion: leader was never killed (kill_at={kill_at}, "
                f"rc={rc})"
            )

        acked = {l["ack"] for l in lines if "ack" in l}
        intents = {l["op"]: tuple(l["t"]) for l in lines if "op" in l}
        oracle = {intents[i] for i in acked}  # insert-only stream
        unacked = {intents[i] for i in intents if i not in acked}
        versions = [l["version"] for l in lines if "ack" in l]
        token_versions = [
            parse_snaptoken(l["token"]).version
            for l in lines
            if "token" in l
        ]
        if token_versions != sorted(token_versions):
            viol.add("promotion: write-ack snaptokens not monotonic")
        if versions and token_versions and versions != token_versions:
            viol.add("promotion: ack token version != store version")
        tailed_live = follower.applied_total

        rep = follower.promote(wal_dir)
        if rep["gap"]:
            viol.add("promotion: replayed leader log had gaps")
        last_ack = versions[-1] if versions else 0
        if rep["final_version"] < last_ack:
            viol.add(
                f"promotion: final version {rep['final_version']} < last "
                f"acked {last_ack} — acked writes lost"
            )
        got = {
            tuple(encode_tuple(t)) for t in follower.store.all_tuples()
        }
        lost = oracle - got
        if lost:
            viol.add(
                f"promotion: {len(lost)} acked writes missing after "
                "promotion"
            )
        phantom = got - oracle - unacked
        if phantom:
            viol.add(
                f"promotion: {len(phantom)} phantom tuples after promotion"
            )
        try:
            # a promoted node is the authority: zero-window at-least-
            # latest reads must pass with no residual lag
            follower.wait_for_version(rep["final_version"], timeout_s=0.0)
        except KetoError as e:
            viol.add(f"promotion: promoted node still lagging: {e!r}")
        if follower.role != "leader":
            viol.add(f"promotion: role is {follower.role!r} after promote")
        summary.update(
            {
                "acked_ops": len(acked),
                "tailed_live": tailed_live,
                "promote_applied": rep["applied"],
                "final_version": rep["final_version"],
                "elapsed_s": round(time.monotonic() - t0, 2),
                "violations": viol.items,
            }
        )
        return summary
    finally:
        if follower is not None:
            follower.stop()
        if proc is not None and proc.poll() is None:
            proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=10)
        shutil.rmtree(root, ignore_errors=True)


class _GamedayFollower:
    """One follower of the game-day fleet: a real FollowerReplicator
    tailing the leader, a real ElectionManager over the shared lease,
    and an aiohttp surface whose ``/replication/*`` routes come alive
    the moment this node is promoted (the deferred-route pattern the
    write plane uses)."""

    def __init__(self, name: str, wal_dir: str, upstream: str,
                 scratch: str, loop, *, ttl: float, hb: float):
        from aiohttp import web

        from keto_tpu.cluster.election import (
            ElectionManager,
            LeaseStore,
            PromotedReplicationSource,
        )
        from keto_tpu.replication.follower import FollowerReplicator
        from keto_tpu.store import InMemoryTupleStore

        self.name = name
        self.wal_dir = wal_dir
        self.store = InMemoryTupleStore()
        self.rep = FollowerReplicator(
            self.store, upstream, scratch_dir=scratch,
            poll_interval_s=0.01,
        )
        self.promoted_src = None
        self._src_cls = PromotedReplicationSource

        async def h_status(request):
            src = self.promoted_src
            if src is not None:
                return await src.handle_status(request)
            return web.json_response(self.rep.lag())

        async def h_blocked(request):
            src = self.promoted_src
            if src is not None:
                if request.path.endswith("/checkpoint"):
                    return await src.handle_checkpoint(request)
                return await src.handle_wal(request)
            return web.json_response(
                {"error": "not the replication leader"}, status=503
            )

        app = web.Application()
        app.router.add_get("/replication/status", h_status)
        app.router.add_get("/replication/checkpoint", h_blocked)
        app.router.add_get("/replication/wal", h_blocked)

        async def _serve() -> int:
            runner = web.AppRunner(app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            return site._server.sockets[0].getsockname()[1]

        import asyncio

        self.port = asyncio.run_coroutine_threadsafe(
            _serve(), loop
        ).result(timeout=60)
        self.write_url = f"http://127.0.0.1:{self.port}"
        self.em = ElectionManager(
            LeaseStore(wal_dir),
            instance_id=name,
            lease_ttl_s=ttl,
            heartbeat_interval_s=hb,
            write_url=self.write_url,
            promote_fn=self._promote,
            retarget_fn=lambda lease: self.rep.retarget(
                str(lease.get("write_url") or "")
            ),
            position_fn=lambda: self.store.version,
        )

    def _promote(self) -> dict:
        report = self.rep.promote(self.wal_dir)
        src = self._src_cls(self.store, self.wal_dir)
        src.open()
        self.promoted_src = src
        return report

    def start(self) -> None:
        self.rep.start()
        self.em.start()

    def stop(self) -> None:
        self.em.stop()
        if self.promoted_src is not None:
            self.promoted_src.close()
        self.rep.stop()


def run_election_drill(seed: int, ops: int = 60) -> dict:
    """Game day: SIGKILL the elected leader mid-traffic and watch the
    fleet drive itself. One leader child (durable store + replication
    routes + the lease), two in-process followers tailing it, each
    running a real ElectionManager over the shared WAL directory.

    Asserted, in the order the ISSUE states them:

    - a new leader holds the lease within the failover budget (the dead
      leader's lease had at most one TTL to run, plus campaign time);
    - ZERO acked writes are lost — the shadow oracle built from the
      child's INTENT/ACK stream is a subset of the promoted store; at
      most the one durable-but-unacked op surfaces as an extra;
    - reads never stop: a reader hammers both followers' stores through
      the whole window (kill included) with bounded p99 and no errors;
    - the fencing-token lineage on disk is exactly one strictly
      increasing chain, ending at the new leader's term;
    - the loser retargets its tail at the winner and converges on
      post-failover writes without a re-bootstrap.
    """
    import asyncio
    import shutil
    import signal
    import subprocess
    import tempfile

    from keto_tpu.cluster.election import LeaseStore
    from keto_tpu.relationtuple.definitions import RelationTuple, SubjectID
    from keto_tpu.store.wal import encode_tuple

    t0 = time.monotonic()
    viol = _Violations()
    root = tempfile.mkdtemp(prefix="keto-gameday-")
    wal_dir = os.path.join(root, "wal")
    ttl, hb = 2.0, 0.25
    rng = random.Random(seed + 47)
    kill_at = rng.randrange((ops * 2) // 3, ops - 2)
    spec = {
        "dir": wal_dir, "ops": ops, "seed": seed, "kill_at": kill_at,
        "ttl": ttl, "hb": hb,
    }
    summary = {"phase": "election", "seed": seed, "kill_at": kill_at,
               "lease_ttl_s": ttl}
    followers: list[_GamedayFollower] = []
    proc = None
    loop = asyncio.new_event_loop()
    threading.Thread(target=loop.run_forever, daemon=True).start()
    read_errors: list[str] = []
    read_lat: list[float] = []
    stop_reads = threading.Event()
    try:
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--election-child", json.dumps(spec)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True,
        )
        lines: list[dict] = []

        def _take(raw: str):
            try:
                doc = json.loads(raw)
            except json.JSONDecodeError:
                viol.add(f"election: undecodable child line {raw!r}")
                return None
            lines.append(doc)
            return doc

        port = None
        for raw in proc.stdout:
            doc = _take(raw)
            if doc and doc.get("ready"):
                port = doc["port"]
                if doc.get("term") != 1:
                    viol.add(
                        f"election: bootstrap term {doc.get('term')} != 1"
                    )
                break
        if port is None:
            err = proc.stderr.read()[-400:] if proc.stderr else ""
            viol.add(f"election: leader child never became ready ({err!r})")
            return {**summary, "violations": viol.items}

        upstream = f"http://127.0.0.1:{port}"
        for i in range(2):
            f = _GamedayFollower(
                f"gameday-f{i}", wal_dir, upstream,
                os.path.join(root, f"f{i}"), loop, ttl=ttl, hb=hb,
            )
            followers.append(f)
        # seed each follower's peer cache so candidacy ranks are a total
        # order (equal priority/position ties break on instance id)
        members = [
            {"instance_id": f.name, "alive": True,
             "version": f.store.version, "election": {"priority": 0}}
            for f in followers
        ]
        for f in followers:
            f.em.observe_peers({"members": members})
            f.start()
        deadline = time.monotonic() + 30.0
        while any(f.store.version <= 0 for f in followers):
            if time.monotonic() > deadline:
                viol.add("election: followers never seeded from leader")
                return {**summary, "violations": viol.items}
            time.sleep(0.05)

        def reader() -> None:
            i = 0
            while not stop_reads.is_set():
                f = followers[i % len(followers)]
                i += 1
                t_r = time.monotonic()
                try:
                    _ = f.store.version
                    f.store.all_tuples()
                except Exception as e:  # noqa: BLE001
                    read_errors.append(repr(e))
                read_lat.append(time.monotonic() - t_r)
                time.sleep(0.005)

        reads = threading.Thread(target=reader, daemon=True)
        reads.start()

        proc.stdin.write("go\n")
        proc.stdin.flush()
        for raw in proc.stdout:  # drains until SIGKILL closes the pipe
            _take(raw)
        proc.wait(timeout=60)
        t_kill = time.monotonic()
        if any("done" in l for l in lines):
            viol.add(f"election: leader was never killed (kill_at={kill_at})")
        if any("fenced" in l for l in lines):
            viol.add("election: live leader saw its own fence fail")

        # -- a new leader within the failover budget ------------------------
        # the lease had at most one TTL to run at the kill; allow one
        # more TTL for detection + stagger + promotion (CI-safe, still
        # an order of magnitude under "page an operator")
        budget = 2.0 * ttl
        winner = None
        while winner is None and time.monotonic() - t_kill < budget + 5.0:
            winner = next(
                (f for f in followers if f.em.role == "leader"), None
            )
            if winner is None:
                time.sleep(0.02)
        failover_s = time.monotonic() - t_kill
        if winner is None:
            viol.add(f"election: no new leader within {budget + 5.0:.0f}s")
            return {**summary, "violations": viol.items}
        if failover_s > budget:
            viol.add(
                f"election: failover took {failover_s:.2f}s "
                f"(budget {budget:.2f}s = 2x lease TTL)"
            )
        loser = next(f for f in followers if f is not winner)

        # -- zero acked-write loss (shadow-oracle parity) -------------------
        acked = {l["ack"] for l in lines if "ack" in l}
        intents = {l["op"]: tuple(l["t"]) for l in lines if "op" in l}
        oracle = {intents[i] for i in acked}
        unacked = {intents[i] for i in intents if i not in acked}
        got = {
            tuple(encode_tuple(t)) for t in winner.store.all_tuples()
        }
        lost = oracle - got
        if lost:
            viol.add(
                f"election: {len(lost)} acked writes missing on the "
                "promoted leader"
            )
        phantom = got - oracle - unacked
        if phantom:
            viol.add(
                f"election: {len(phantom)} phantom tuples on the "
                "promoted leader"
            )

        # -- exactly one fencing-token lineage ------------------------------
        lineage = LeaseStore(wal_dir).lineage()
        terms = [r["term"] for r in lineage]
        if terms != sorted(set(terms)) or any(
            b - a != 1 for a, b in zip(terms, terms[1:])
        ):
            viol.add(f"election: fencing lineage not one chain: {terms}")
        if not lineage or lineage[-1]["leader_id"] != winner.em.instance_id:
            viol.add(
                f"election: lineage tip {lineage[-1:]} is not the "
                f"winner {winner.em.instance_id}"
            )
        if sum(1 for f in followers if f.em.role == "leader") != 1:
            viol.add("election: more than one in-process leader")
        if not winner.em.is_writable():
            viol.add("election: winner fails its own fence check")
        if loser.em.is_writable():
            viol.add("election: LOSER passes the write fence")

        # -- the loser retargets and converges without re-bootstrap ---------
        post = []
        for i in range(5):
            t = RelationTuple(
                namespace="n", object=f"post{i}", relation="view",
                subject=SubjectID(id="u0"),
            )
            if not winner.em.is_writable():
                viol.add("election: winner lost writability mid-write")
                break
            winner.store.write_relation_tuples(t)
            post.append(tuple(encode_tuple(t)))
        deadline = time.monotonic() + 15.0
        while (
            loser.store.version < winner.store.version
            and time.monotonic() < deadline
        ):
            time.sleep(0.05)
        if loser.rep.upstream.rstrip("/") != winner.write_url:
            viol.add(
                f"election: loser still tails {loser.rep.upstream!r}, "
                f"not the winner {winner.write_url!r}"
            )
        loser_got = {
            tuple(encode_tuple(t)) for t in loser.store.all_tuples()
        }
        missing_post = [p for p in post if p not in loser_got]
        if missing_post:
            viol.add(
                f"election: {len(missing_post)} post-failover writes "
                "never reached the retargeted loser"
            )

        # -- reads never stopped --------------------------------------------
        stop_reads.set()
        reads.join(timeout=5)
        if read_errors:
            viol.add(
                f"election: {len(read_errors)} read errors during "
                f"failover (first: {read_errors[0]})"
            )
        lat_sorted = sorted(read_lat)
        p99 = _percentile(lat_sorted, 0.99) if lat_sorted else 0.0
        if p99 > 1.0:
            viol.add(f"election: read p99 {p99:.3f}s over the 1s budget")
        if len(read_lat) < 50:
            viol.add(
                f"election: only {len(read_lat)} reads served — reads "
                "effectively stopped"
            )

        summary.update(
            {
                "acked_ops": len(acked),
                "failover_s": round(failover_s, 3),
                "winner": winner.em.instance_id,
                "winner_term": winner.em.term,
                "lineage_terms": terms,
                "reads_served": len(read_lat),
                "read_p99_s": round(p99, 4),
                "elapsed_s": round(time.monotonic() - t0, 2),
                "violations": viol.items,
            }
        )
        return summary
    finally:
        stop_reads.set()
        for f in followers:
            try:
                f.stop()
            except Exception:  # noqa: BLE001
                pass
        if proc is not None and proc.poll() is None:
            proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=10)
        loop.call_soon_threadsafe(loop.stop)
        shutil.rmtree(root, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=4)
    ap.add_argument(
        "--smoke", action="store_true",
        help="small deterministic tier for tools/check.sh",
    )
    ap.add_argument("--ops", type=int, default=0, help="reader ops total")
    ap.add_argument("--writes", type=int, default=0)
    ap.add_argument("--faults", type=int, default=0)
    ap.add_argument(
        "--pool", action="store_true",
        help="also run the forked replica-pool phase",
    )
    ap.add_argument(
        "--restart", action="store_true",
        help="also run the durable-store kill-and-restart drill",
    )
    ap.add_argument(
        "--device-chaos", action="store_true",
        help="also run the device-fault drills (OOM bisection, compile "
        "quarantine, device-loss failover)",
    )
    ap.add_argument(
        "--overload", action="store_true",
        help="also run the 10x open-loop overload drill (goodput floor, "
        "strict criticality shed ordering, retry-budget amplification "
        "cap, brownout ladder recovery)",
    )
    ap.add_argument(
        "--election", action="store_true",
        help="also run the game-day failover drill (SIGKILL the elected "
        "leader mid-traffic; assert failover within the lease budget, "
        "zero acked-write loss, bounded reads, one fencing lineage)",
    )
    args = ap.parse_args(argv)

    if args.smoke:
        ops, writes, faults = 800, 80, 5
    else:
        ops, writes, faults = 8000, 600, 24
    if args.ops:
        ops = args.ops
    if args.writes:
        writes = args.writes
    if args.faults:
        faults = args.faults

    phases = [run_engine_soak(args.seed, n_ops=ops, n_writes=writes,
                              n_faults=faults)]
    if args.device_chaos:
        phases.append(run_device_chaos(args.seed))
    if args.pool:
        phases.append(run_pool_soak(args.seed))
    if args.restart:
        phases.append(
            run_restart_drill(
                args.seed, ops_per_cycle=40 if args.smoke else 120
            )
        )
        phases.append(
            run_promotion_drill(args.seed, ops=60 if args.smoke else 150)
        )
    if args.election:
        phases.append(
            run_election_drill(args.seed, ops=60 if args.smoke else 150)
        )
    if args.overload:
        phases.append(run_overload_drill(args.seed, smoke=args.smoke))
    bad = [v for p in phases for v in p["violations"]]
    print(json.dumps({"phases": phases, "ok": not bad}, indent=2))
    if bad:
        print(f"SOAK FAILED: {len(bad)} violation(s)", file=sys.stderr)
        for v in bad:
            print(f"  - {v}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
