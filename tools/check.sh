#!/usr/bin/env bash
# Repo check: the gates a change must pass before review.
#
#   1. import hygiene — every keto_tpu module imports (catches moved
#      upstream APIs like the jax shard_map relocation at CI time)
#   2. sharded serving parity — tests/test_sharded_serving.py on an
#      8-way virtual CPU mesh: the edge-partitioned serving tier's
#      allowed bitsets byte-identical to the single-chip engine and the
#      host oracle, breaker fault absorption, incremental re-shard
#   3. bench smoke — NOT run here: bench.py needs a TPU and exits
#      non-zero on any other backend. Run it on the chip
#      (`python bench.py --smoke` through the chip tool) next to
#      `python chip_smoke.py`. End-to-end (tiny config, short
#      server leg): the serving path must boot, answer, and emit its
#      summary JSON with exit 0. Includes the attribution-leak gate:
#      the wall-clock accounting ledger (/debug/attribution) must cover
#      >= 95% of measured check wall time, else bench.py exits 3 — a
#      refactor that drops a stage's ledger marks fails here, not in
#      production. Also the encoded-wire parity gate: the id-native
#      BatchCheckEncoded leg must answer identically to the per-tuple
#      string path on both transports (encoded_parity == ok) or bench
#      exits 3
#   4. chaos soak smoke — tools/soak.py: seeded deterministic fault
#      schedule (crash/slow/nan + pool-phase drop/crash) under concurrent
#      mixed load; answer parity, snaptoken monotonicity, no lost
#      futures, bounded p99; plus the kill-and-restart drill (SIGKILL at
#      every WAL/checkpoint fault site, post-recovery parity vs a shadow
#      oracle), the device-fault drills (--device-chaos: OOM batch
#      bisection parity, compile-failure quarantine, device-loss
#      failover with bounded recovery), and the game-day election drill
#      (--election: SIGKILL the elected leader mid-traffic; a follower
#      must win the lease within 2x TTL with zero acked-write loss,
#      reads never stop, exactly one fencing-token lineage), and the
#      overload drill (--overload: offer ~10x measured capacity
#      open-loop with a criticality mix; goodput >= 0.8x capacity,
#      zero critical sheds, sheddable shed before default, retry
#      amplification <= 1.1x, brownout ladder steps back to normal)
#   5. replication gate — 1 leader + 2 followers in-process: checkpoint
#      bootstrap + WAL-tail convergence under a lag bound, token-
#      consistent reads on followers (wait AND bounce paths), read-only
#      follower write plane, replication metrics exported; plus the
#      cluster-federation drill: follower heartbeats land all 3 members
#      on the leader's /cluster/status, the leader's federated /metrics
#      (instance-labeled keto_cluster_* series) lints clean in both
#      exposition formats, and a hedged check pair stitches into ONE
#      cross-process trace on the leader's /debug/traces; ends with the
#      fast election drill: leader killed WITHOUT releasing its lease,
#      one follower self-promotes inside the bound, the demoted peer's
#      503 leader_hint is followed by the client, the loser retargets
#      its WAL tail, and the on-disk fencing lineage stays one chain
#   6. metrics lint — boot the serving stack (cluster federation on, so
#      the self-federated keto_cluster_* series are linted too), drive
#      traffic, scrape /metrics from both planes in Prometheus-text and
#      OpenMetrics formats, and fail on naming/duplicate-series/format
#      violations
#   7. reverse-index parity — the fast core of tests/test_listing.py:
#      list_objects/list_subjects answered from the transposed closure
#      D^T byte-identical to the brute-force forward-scan oracle
#      (random graphs, cycles, unicode, stale/cross-engine tokens) on
#      both query modes, plus the gather-fault breaker drill
#   8. closure microbench gate — tools/closure_microbench.py --gate:
#      incremental closure update after one edge >= 5x faster than a
#      full semiring rebuild (median-of-5 at m~2048); incremental D^T
#      maintenance >= 5x over a full re-transpose; list_objects via the
#      reverse index >= 10x over the per-candidate oracle scan
#   9. autotune gate — tools/autotune_gate.py: the in-process feedback
#      controller against a scripted ledger with a known response
#      surface: converge to the interior optimum, ride the monotone
#      knob to its bound, exercise the revert path, never apply a
#      value outside the declared bounds, freeze/thaw on a guard flip
#  10. scrub gate — tools/scrub_gate.py: every integrity fault site
#      (scrub.device_bitflip, wal.bitrot, replica.skip_delta) injected
#      against a real engine/WAL/follower, detected within the cycle
#      budget, auto-repaired, and the post-repair state byte-identical
#      to the host truth (oracle answers / cold recovery / leader set)
#  11. overload gate — tools/overload_gate.py: the overload-control
#      plane against a scripted 10x open-loop burst: goodput >= 0.8x
#      of capacity, sheds strictly sheddable-before-default and never
#      critical, accepted latency bounded by the CoDel/LIFO discipline,
#      the brownout ladder steps back to normal after the burst, every
#      transition lands in the flight recorder, and a RetryBudget caps
#      retry amplification at 1.1x under total shed
#  12. tier-1 tests — the ROADMAP.md tier-1 command, verbatim
#
# Usage: bash tools/check.sh            (from the repo root)
set -o pipefail
cd "$(dirname "$0")/.."

echo "== import hygiene =="
JAX_PLATFORMS=cpu python tools/verify_imports.py || exit 1

echo "== encoded wire parity =="
# fast-fail version of the bench encoded_parity gate: the id-native wire
# tier (vocab sync + BatchCheckEncoded on REST and gRPC) must agree with
# the per-tuple string path before anything slower runs
timeout -k 10 240 env JAX_PLATFORMS=cpu python -m pytest \
  tests/test_wire_encoded.py -q -p no:cacheprovider \
  -k "parity or resync or stale" || exit 1

echo "== sharded serving parity =="
# the sharded serving tier on an 8-way virtual CPU mesh: allowed bitsets
# must be byte-identical to the single-chip engine and the host oracle,
# the breaker must absorb injected launch faults, and append-only writes
# must re-shard incrementally
timeout -k 10 300 env JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 python -m pytest \
  tests/test_sharded_serving.py -q -p no:cacheprovider || exit 1

echo "== bench smoke: chip only (python bench.py --smoke on a TPU) =="

echo "== chaos soak smoke =="
timeout -k 10 420 env JAX_PLATFORMS=cpu python tools/soak.py --smoke --seed 4 --pool --restart --device-chaos --election --overload || exit 1

echo "== replication gate =="
timeout -k 10 240 env JAX_PLATFORMS=cpu python tools/replication_gate.py || exit 1

echo "== metrics lint =="
timeout -k 10 240 env JAX_PLATFORMS=cpu python tools/lint_metrics.py || exit 1

echo "== reverse-index parity =="
# the list-serving engine suite without the server fixture: reverse-index
# answers byte-identical to the forward-scan oracle, token staleness, and
# the breaker drill — the invariants the list APIs are built on
timeout -k 10 240 env JAX_PLATFORMS=cpu python -m pytest \
  tests/test_listing.py -q -p no:cacheprovider \
  -k "not Surface" || exit 1

echo "== closure microbench gate =="
# incremental closure update after 1 edge must stay >= 5x faster than a
# full rebuild (median-of-5, m~2048), incremental D^T maintenance >= 5x
# over a full re-transpose, and list_objects through the reverse index
# >= 10x over the brute-force oracle; regressions exit non-zero here
timeout -k 10 120 python tools/closure_microbench.py --gate || exit 1

echo "== autotune gate =="
# the online autotuner's controller logic, seeded + deterministic: must
# converge, never leave the knob bounds, and exercise a revert
timeout -k 10 120 env JAX_PLATFORMS=cpu python tools/autotune_gate.py || exit 1

echo "== scrub gate =="
# the integrity plane end to end: inject each fault site, require
# detection within the cycle budget, automatic repair, and byte-identical
# post-repair state (engine vs oracle, cold recovery vs live store,
# follower vs leader)
timeout -k 10 240 env JAX_PLATFORMS=cpu python tools/scrub_gate.py || exit 1

echo "== overload gate =="
# the overload-control plane, seeded + deterministic: goodput floor at
# 10x, strict criticality shed ordering, ladder recovery, retry budget
timeout -k 10 120 env JAX_PLATFORMS=cpu python tools/overload_gate.py || exit 1

echo "== tier-1 tests =="
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
  --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly \
  2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
exit $rc
