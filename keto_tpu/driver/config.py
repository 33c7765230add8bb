"""Config provider: schema-validated, file + env + overrides, hot-reloadable
namespaces.

Mirrors the reference's configx-based provider (internal/driver/config/
provider.go, config.schema.json): same key tree — ``dsn``,
``serve.read.{host,port,cors,max-depth}``, ``serve.write.{...}``, ``log``,
``tracing``, ``namespaces`` (inline array of {id,name} or a file/dir URI) —
plus a ``keto_tpu``-specific ``engine`` subtree controlling the device
evaluation path (mode, dense threshold, batching). DSN and serve keys are
treated as immutable after boot, like the reference (provider.go:70).

Env overrides use the same flattening configx applies: ``serve.read.port`` ->
``SERVE_READ_PORT`` (dots and dashes to underscores, uppercased), optionally
prefixed ``KETO_``. Values parse as JSON when possible (ints, bools), else
strings.
"""

from __future__ import annotations

import json
import os
from typing import Any, Optional

import jsonschema

from ..namespace.definitions import MemoryNamespaceManager, Namespace, NamespaceManager
from ..utils.errors import ErrMalformedInput
from ..utils.fileformat import load_structured_file

KEY_DSN = "dsn"
KEY_READ_PORT = "serve.read.port"
KEY_READ_HOST = "serve.read.host"
KEY_WRITE_PORT = "serve.write.port"
KEY_WRITE_HOST = "serve.write.host"
KEY_READ_MAX_DEPTH = "serve.read.max-depth"  # reference provider.go:32
KEY_NAMESPACES = "namespaces"

_UNSET = object()  # sentinel so falsy explicit defaults (0/False/"") are honored

_CORS_SCHEMA = {
    "type": "object",
    "properties": {
        "enabled": {"type": "boolean", "default": False},
        "allowed_origins": {"type": "array", "items": {"type": "string"}},
        "allowed_methods": {"type": "array", "items": {"type": "string"}},
        "allowed_headers": {"type": "array", "items": {"type": "string"}},
    },
    "additionalProperties": True,
}

_TLS_SCHEMA = {
    "type": "object",
    "properties": {
        "cert": {
            "type": "object",
            "properties": {"path": {"type": "string"}},
            "additionalProperties": True,
        },
        "key": {
            "type": "object",
            "properties": {"path": {"type": "string"}},
            "additionalProperties": True,
        },
    },
    "additionalProperties": True,
}

_PORT_SCHEMA = {
    "type": "object",
    "properties": {
        "port": {"type": "integer"},
        "host": {"type": "string"},
        "cors": _CORS_SCHEMA,
        "max-depth": {"type": "integer", "minimum": 1},
        "tls": _TLS_SCHEMA,
        # opt-in: bind the plaintext gRPC/HTTP backend ports on the public
        # host (for protocol-aware LBs); default keeps them loopback-only
        "expose_backend_ports": {"type": "boolean"},
        # read plane only: number of forked read-replica worker processes
        # sharing the port via SO_REUSEPORT (driver/replicas.py)
        "workers": {"type": "integer", "minimum": 1},
        # gRPC max receive/send message bytes on this plane's server (and
        # the cmd-side clients); large columnar BatchCheck payloads exceed
        # grpc's 4 MiB default. 0 = leave the grpc default
        "grpc-max-message-size": {"type": "integer", "minimum": 0},
        # read plane only: cap on any snaptoken freshness wait (seconds) —
        # hot-reloadable (HOT_SERVE_KEYS), unlike the rest of serve
        "max_freshness_wait_s": {"type": "number", "minimum": 0},
        # read plane only: serve the id-native wire tier (encoded
        # BatchCheck + /vocab bootstrap/delta feed, api/encoded.py)
        "encoded": {"type": "boolean"},
        # read plane only: serve the reverse-index list routes
        # (/relation-tuples/list-{objects,subjects} + the gRPC
        # ListService, engine/listing.py)
        "list": {"type": "boolean"},
        # read plane only: SO_REUSEPORT accept/parse worker processes for
        # the encoded path, funneling into one device batcher over the
        # shm ring (engine/shmring.py); rides the fork replica pool
        "wire_workers": {"type": "integer", "minimum": 1},
    },
    "additionalProperties": True,
}

# The same surface as the reference's config.schema.json (380 lines there;
# condensed here), extended with the engine subtree.
CONFIG_SCHEMA = {
    "$schema": "http://json-schema.org/draft-07/schema#",
    "$id": "keto_tpu/config.schema.json",
    "type": "object",
    "properties": {
        # version stamp accepted for compatibility with reference config
        # files (e.g. contrib examples); not interpreted
        "version": {"type": "string"},
        "dsn": {"type": "string"},
        "serve": {
            "type": "object",
            "properties": {"read": _PORT_SCHEMA, "write": _PORT_SCHEMA},
            "additionalProperties": False,
        },
        "log": {
            "type": "object",
            "properties": {
                "level": {
                    "enum": ["trace", "debug", "info", "warn", "error", "fatal"]
                },
                "format": {"enum": ["json", "text"]},
            },
            "additionalProperties": True,
        },
        "tracing": {
            "type": "object",
            "properties": {
                # "log" mirrors finished spans into the structured log;
                # "otlp" ships OTLP/HTTP JSON batches to
                # tracing.otlp.endpoint (any OpenTelemetry collector /
                # Jaeger); "" keeps them only in the in-process buffer
                "provider": {"enum": ["", "log", "otlp"]},
                "otlp": {
                    "type": "object",
                    "properties": {
                        "endpoint": {"type": "string"},
                        "service_name": {"type": "string"},
                    },
                    "additionalProperties": False,
                },
            },
            "additionalProperties": True,
        },
        "profiling": {"type": "string"},
        # durable write plane (store/wal.py, store/durable.py): only the
        # non-SQL stores (memory/columnar DSNs) honor these — SQL DSNs have
        # their own durability
        "store": {
            "type": "object",
            "properties": {
                "wal": {
                    "type": "object",
                    "properties": {
                        # "" disables the WAL (volatile store, the
                        # pre-durability behavior)
                        "dir": {"type": "string"},
                        # always: fsync every append before ack (zero
                        # acked-write loss); interval: fsync at most every
                        # sync-interval-ms (bounded loss window); off:
                        # leave flushing to the OS (bench/import mode)
                        "sync": {"enum": ["always", "interval", "off"]},
                        "sync-interval-ms": {"type": "number", "minimum": 0},
                        "segment-bytes": {"type": "integer", "minimum": 4096},
                    },
                    "additionalProperties": False,
                },
            },
            "additionalProperties": False,
        },
        "checkpoint": {
            "type": "object",
            "properties": {
                # "" defaults to <store.wal.dir>/checkpoints
                "dir": {"type": "string"},
                # cut a checkpoint when this many versions accumulated
                # past the last one …
                "interval-versions": {"type": "integer", "minimum": 1},
                # … or when the last one is this old (seconds; 0 disables
                # the age trigger)
                "interval-s": {"type": "number", "minimum": 0},
                # checkpoints retained on disk
                "keep": {"type": "integer", "minimum": 1},
            },
            "additionalProperties": False,
        },
        "namespaces": {
            "oneOf": [
                {
                    "type": "array",
                    "items": {
                        "type": "object",
                        "properties": {
                            "id": {"type": "integer"},
                            "name": {"type": "string"},
                        },
                        "required": ["name"],
                        "additionalProperties": True,
                    },
                },
                {"type": "string"},
            ]
        },
        "engine": {
            "type": "object",
            "properties": {
                "mode": {"enum": ["host", "auto", "closure"]},
                "max_batch": {"type": "integer", "minimum": 1},
                "batch_window_us": {"type": "number", "minimum": 0},
                "interior_limit": {"type": "integer", "minimum": 2},
                "query_mode": {"enum": ["auto", "host", "device"]},
                "freshness": {"enum": ["auto", "strong", "bounded"]},
                # single-check LRU result cache entries (0 disables); the
                # cache empties whenever the ANSWERING version advances
                # (engine.answering_version — NOT served_version, which
                # lags writes under strong freshness)
                "cache_size": {"type": "integer", "minimum": 0},
                "strong_freshness_edges": {"type": "integer", "minimum": 0},
                "rebuild_debounce_ms": {"type": "number", "minimum": 0},
                # dispatch queue bound before the batcher sheds load with
                # 429/RESOURCE_EXHAUSTED (0 = 8 * max_batch)
                "max_queue": {"type": "integer", "minimum": 0},
                # pipelined check dispatch (engine/batcher.py): batches in
                # flight on device (0 = serial, one batch at a time); only
                # engines with the split encode/launch/decode API pipeline —
                # others silently keep the serial loop
                "pipeline_depth": {"type": "integer", "minimum": 0},
                # host threads vocab-encoding queued requests into batches
                "encode_workers": {"type": "integer", "minimum": 1},
                # snapshot-versioned encoded-request cache in front of the
                # device stage, keyed (start, target, depth) ids (0 disables)
                "encoded_cache_size": {"type": "integer", "minimum": 0},
                # device-engine circuit breaker -> host-oracle fallback
                "fallback": {"type": "boolean"},
                "fallback_threshold": {"type": "integer", "minimum": 1},
                "fallback_cooldown_ms": {"type": "number", "minimum": 0},
                # sharded serving tier (parallel/serving.py): route live
                # check traffic through the edge-partitioned mesh closure
                # engine; auto-falls back to the single-chip engine when
                # the mesh has one device
                "sharding": {
                    "type": "object",
                    "properties": {
                        "enabled": {"type": "boolean"},
                        # mesh axes: data = batch parallelism, edge =
                        # node stripes (0 = all remaining devices)
                        "data": {"type": "integer", "minimum": 1},
                        "edge": {"type": "integer", "minimum": 0},
                        # values gathered per re-stripe chunk, bounding
                        # one incremental re-shard's temporaries
                        # (0 = unchunked)
                        "edge_chunk": {"type": "integer", "minimum": 0},
                        # tolerated host-oracle escalation fraction per
                        # batch before the breach is logged/counted —
                        # the rebalance alarm signal
                        "escalation_budget": {
                            "type": "number",
                            "minimum": 0,
                            "maximum": 1,
                        },
                    },
                    "additionalProperties": False,
                },
                # HBM admission control (engine/hbm.py): budget check-batch
                # device memory BEFORE the XLA allocator sees it
                "memory": {
                    "type": "object",
                    "properties": {
                        "admission": {"type": "boolean"},
                        # fraction of the smallest device's bytes_limit
                        # budgeted for in-flight check batches
                        "hbm_budget_frac": {
                            "type": "number",
                            "exclusiveMinimum": 0,
                            "maximum": 1,
                        },
                        # starting per-row footprint guess before the model
                        # learns from observed peak_bytes_in_use deltas
                        "bytes_per_row": {"type": "integer", "minimum": 1},
                    },
                    "additionalProperties": False,
                },
                # reverse closure index (engine/closure.py + graph/
                # reverse.py): keep the transposed closure D^T + reverse
                # boundary CSRs resident next to D so list queries are
                # masked row gathers instead of per-candidate check scans.
                # Off -> list routes answer from the exact (slow) oracle
                "reverse_index": {"type": "boolean"},
                # closure-build math (engine/closure.py): semiring =
                # masked-SpMV batched BFS with incremental dirty-row
                # rebuilds; matmul = the legacy dense-cube builder; auto
                # currently resolves to semiring
                "closure_builder": {"enum": ["auto", "matmul", "semiring"]},
                # thread-pool width for block-parallel closure builds
                # (0 = half the cores, capped at 8)
                "closure_block_workers": {"type": "integer", "minimum": 0},
                # default page budget (tree nodes) when an Expand client
                # requests paging without naming a size (0 = built-in 1024)
                "expand_page_size": {"type": "integer", "minimum": 0},
                # JAX persistent compilation cache directory ("" = off):
                # jitted kernels compiled once survive process restarts,
                # killing the cold-start recompile on boot/failover
                "compile_cache_dir": {"type": "string"},
                # runtime backend failover (driver/registry.py
                # DeviceSupervisor): on DEVICE_LOST, probe the home
                # platform in a killable child, hot-swap to CPU while it
                # is gone, swap back when it answers again
                "failover": {
                    "type": "object",
                    "properties": {
                        "enabled": {"type": "boolean"},
                        # child = subprocess probe (survives jax.devices()
                        # hangs, BENCH_r05 style); inproc = same-process
                        # probe for test meshes without fork headroom
                        "probe_mode": {"enum": ["child", "inproc"]},
                        "probe_timeout_s": {
                            "type": "number",
                            "exclusiveMinimum": 0,
                        },
                        "probe_interval_s": {
                            "type": "number",
                            "exclusiveMinimum": 0,
                        },
                        "max_backoff_s": {"type": "number", "minimum": 0},
                        # False pins serving to the host oracle while the
                        # home platform is gone (no jax default-device swap)
                        "allow_cpu": {"type": "boolean"},
                    },
                    "additionalProperties": False,
                },
            },
            "additionalProperties": False,
        },
        # introspection plane (telemetry/flight.py, telemetry/slo.py)
        "telemetry": {
            "type": "object",
            "properties": {
                "flight": {
                    "type": "object",
                    "properties": {
                        # ring-buffer entries retained (slow/errored/
                        # deadline-missed requests)
                        "capacity": {"type": "integer", "minimum": 1},
                        # a request at least this slow is flight-recorded
                        # even when it succeeded
                        "slow_ms": {"type": "number", "minimum": 0},
                        # "" keeps the ring memory-only; a directory arms
                        # the periodic disk flush + faulthandler fatal dump
                        "dir": {"type": "string"},
                        "flush_interval_s": {"type": "number", "minimum": 0.1},
                    },
                    "additionalProperties": False,
                },
                "slo": {
                    "type": "object",
                    "properties": {
                        # fraction of checks that must be fast-and-correct
                        "objective": {
                            "type": "number",
                            "exclusiveMinimum": 0,
                            "exclusiveMaximum": 1,
                        },
                        # a check slower than this counts against the
                        # error budget even when it succeeded
                        "latency_target_ms": {"type": "number", "minimum": 0},
                        "fast_window_s": {"type": "number", "minimum": 1},
                        "slow_window_s": {"type": "number", "minimum": 1},
                        # both windows must burn at this rate before the
                        # log alert fires
                        "alert_burn_rate": {"type": "number", "minimum": 0},
                        "alert_cooldown_s": {"type": "number", "minimum": 0},
                    },
                    "additionalProperties": False,
                },
                # wall-clock accounting ledger (telemetry/attribution.py):
                # per-stage time attribution behind /debug/attribution and
                # keto_time_attribution_seconds_total
                "attribution": {
                    "type": "object",
                    "properties": {
                        "enabled": {"type": "boolean"},
                    },
                    "additionalProperties": False,
                },
                # stdlib sampling profiler (telemetry/profiler.py) behind
                # /debug/pprof; enabled=true samples continuously from
                # registry bring-up, else /debug/pprof?seconds=N captures
                # on demand
                "profiler": {
                    "type": "object",
                    "properties": {
                        "enabled": {"type": "boolean"},
                        "hz": {
                            "type": "number",
                            "exclusiveMinimum": 0,
                            "maximum": 1000,
                        },
                        "max_stacks": {"type": "integer", "minimum": 1},
                    },
                    "additionalProperties": False,
                },
            },
            "additionalProperties": False,
        },
        # replicated read plane (replication/): leader ships WAL + newest
        # checkpoint over the write plane's HTTP surface; followers boot
        # from the checkpoint seed, replay the tail, and serve reads
        "replication": {
            "type": "object",
            "properties": {
                # "" = standalone (no replication); leader additionally
                # requires a WAL (store.wal.dir); follower requires
                # upstream + dir
                "role": {"enum": ["", "leader", "follower"]},
                # follower only: base URL of the leader's write plane,
                # e.g. http://leader:4467
                "upstream": {"type": "string"},
                # follower scratch directory for the checkpoint seed
                "dir": {"type": "string"},
                # follower tail-poll cadence when the long-poll returns
                # empty/errors
                "poll_interval_ms": {"type": "number", "minimum": 1},
                # records pulled per /replication/wal response
                "max_records_per_poll": {"type": "integer", "minimum": 1},
            },
            "additionalProperties": False,
        },
        # per-tenant admission control in front of the check batcher
        # (engine/qos.py): token bucket per namespace, 429 on drain
        "qos": {
            "type": "object",
            "properties": {
                "enabled": {"type": "boolean"},
                # tokens (check rows) per second per namespace; <= 0
                # admits everything for namespaces without an override
                "rate": {"type": "number"},
                "burst": {"type": "number", "minimum": 1},
                # per-namespace {"rate": .., "burst": ..} overrides
                "overrides": {
                    "type": "object",
                    "additionalProperties": {
                        "type": "object",
                        "properties": {
                            "rate": {"type": "number"},
                            "burst": {"type": "number", "minimum": 1},
                        },
                        "additionalProperties": False,
                    },
                },
            },
            "additionalProperties": False,
        },
        # online autotuner (engine/autotune.py): ledger-driven feedback
        # control of the hot serving knobs — reads the attribution
        # breakdown each interval, moves the bottleneck stage's knob one
        # bounded step, reverts on regression, freezes on SLO burn /
        # breaker / HBM-pressure guards. The kill switch (enabled) is
        # itself hot-reloadable: flipping it off in the config file stops
        # moves at the next tick without a restart
        "autotune": {
            "type": "object",
            "properties": {
                "enabled": {"type": "boolean"},
                # control interval between moves
                "interval_s": {"type": "number", "exclusiveMinimum": 0},
                # a window with fewer finished checks than this makes no
                # move (too little signal to attribute a bottleneck)
                "min_requests": {"type": "integer", "minimum": 1},
                # objective (checks/s) drop past this fraction of the
                # pre-move baseline reverts the move
                "revert_threshold": {"type": "number", "minimum": 0},
                # fast-window SLO burn rate at or above this freezes all
                # moves (0 = inherit telemetry.slo.alert_burn_rate)
                "freeze_burn_rate": {"type": "number", "minimum": 0},
                # ticks a knob sits out after one of its moves reverted
                "backoff_ticks": {"type": "integer", "minimum": 0},
                # /debug/autotune history ring entries retained
                "history": {"type": "integer", "minimum": 1},
                # per-knob overrides, keyed by knob name (e.g.
                # pipeline_depth, encode_workers, hbm_budget_frac):
                # tighten bounds/step, or pin a knob with enabled: false
                "knobs": {
                    "type": "object",
                    "additionalProperties": {
                        "type": "object",
                        "properties": {
                            "enabled": {"type": "boolean"},
                            "min": {"type": "number"},
                            "max": {"type": "number"},
                            "step": {"type": "number"},
                        },
                        "additionalProperties": False,
                    },
                },
            },
            "additionalProperties": False,
        },
        # integrity plane (engine/scrub.py): continuous online scrubbing
        # of derived state — device-resident closure rows, replayed live
        # checks, sealed WAL segments, checkpoint digests, and follower
        # anti-entropy — with a rate-limited repair ladder. Like the
        # autotuner, the kill switch is hot-reloadable and all repairs
        # freeze while the SLO is burning
        "scrub": {
            "type": "object",
            "properties": {
                "enabled": {"type": "boolean"},
                # scrub cycle cadence — the duty-cycle budget: each cycle
                # does a bounded slice of verification work, then sleeps
                "interval_s": {"type": "number", "exclusiveMinimum": 0},
                # device-resident closure rows re-derived per cycle
                "sample_rows": {"type": "integer", "minimum": 1},
                # recent live check requests retained for replay
                "reservoir": {"type": "integer", "minimum": 1},
                # reservoir entries replayed through the host oracle per
                # cycle (0 disables the replay pass)
                "replay_per_cycle": {"type": "integer", "minimum": 0},
                # sealed WAL segments CRC-rescanned per cycle, rolling
                # cursor (0 disables the WAL pass)
                "wal_segments_per_cycle": {"type": "integer", "minimum": 0},
                # repair-ladder rate limit: repairs applied per cycle
                # beyond this are deferred to the next cycle
                "max_repairs_per_cycle": {"type": "integer", "minimum": 0},
                # tuples per anti-entropy digest chunk (a divergent chunk
                # localizes damage to about this many rows)
                "digest_chunk_size": {"type": "integer", "minimum": 1},
                # fast-window SLO burn rate at or above this freezes
                # scrubbing (0 = inherit telemetry.slo.alert_burn_rate)
                "freeze_burn_rate": {"type": "number", "minimum": 0},
                # /debug/scrub history ring entries retained
                "history": {"type": "integer", "minimum": 1},
            },
            "additionalProperties": False,
        },
        # overload-control plane (engine/overload.py): adaptive admission
        # (AIMD concurrency limit + CoDel standing-queue target at batcher
        # admission), the criticality brownout ladder, and the SRE-style
        # accepts/requests server throttle. The kill switch (enabled) is
        # hot-reloadable: the controller re-reads it on every decision, so
        # flipping it off in the config file makes the plane admit-all at
        # the next request without a restart
        "overload": {
            "type": "object",
            "properties": {
                "enabled": {"type": "boolean"},
                # CoDel standing-queue delay target: queue delay above
                # this sustained for interval_ms flips FIFO->LIFO and
                # culls entries older than the target
                "target_delay_ms": {"type": "number", "exclusiveMinimum": 0},
                # AIMD adjustment cadence + the CoDel sustain window
                "interval_ms": {"type": "number", "exclusiveMinimum": 0},
                # the adaptive limit never decreases below this
                "min_limit": {"type": "integer", "minimum": 1},
                # latency inflation (recent EWMA over healthy baseline)
                # beyond this multiple triggers multiplicative decrease
                "tolerance": {"type": "number", "minimum": 1},
                # multiplicative-decrease factor and additive-increase
                # step of the AIMD limit
                "decrease": {
                    "type": "number", "exclusiveMinimum": 0, "maximum": 1,
                },
                "additive": {"type": "number", "exclusiveMinimum": 0},
                # the brownout ladder steps DOWN one rung only after
                # pressure stays below the rung for this long (no flap)
                "hysteresis_ms": {"type": "number", "exclusiveMinimum": 0},
                # minimum time between ladder step-UPS (one rung at a
                # time, every rung observable)
                "dwell_ms": {"type": "number", "minimum": 0},
                # sliding window + K of the server adaptive throttle
                # (reject probability max(0, (reqs - K*accepts)/(reqs+1)))
                "throttle_window_s": {
                    "type": "number", "exclusiveMinimum": 0,
                },
                "throttle_k": {"type": "number", "minimum": 1},
                # /debug/overload history ring entries retained
                "history": {"type": "integer", "minimum": 1},
                # criticality assigned to requests that carry no
                # X-Request-Criticality header / x-keto-criticality
                # metadata (critical is deliberately not assignable as
                # a blanket default: unlabeled traffic must stay
                # sheddable before labeled-critical traffic)
                "default_criticality": {
                    "type": "string",
                    "enum": ["default", "sheddable"],
                },
            },
            "additionalProperties": False,
        },
        # /debug surface on the read plane (api/debug.py)
        "debug": {
            "type": "object",
            "properties": {
                # false hides every /debug route as 404
                "enabled": {"type": "boolean"},
                # non-empty requires Authorization: Bearer <token> or
                # X-Debug-Token on every /debug request
                "token": {"type": "string"},
                # cap on /debug/profile?seconds=N captures
                "profile_max_s": {"type": "number", "minimum": 0.1},
            },
            "additionalProperties": False,
        },
        # fleet observability (cluster/, telemetry/federation.py):
        # followers heartbeat to the leader, the leader scrapes every
        # member into instance-labeled keto_cluster_* series and the
        # /cluster/status health rollup
        "cluster": {
            "type": "object",
            "properties": {
                "enabled": {"type": "boolean"},
                # metrics label + membership key; defaults to
                # "<role-or-leader>-<write-port>" when empty
                "instance_id": {"type": "string"},
                # how other members reach this node; default to the
                # loopback URLs of the bound serve ports
                "advertise_url": {"type": "string"},
                "advertise_write_url": {"type": "string"},
                "heartbeat_interval_ms": {"type": "number", "minimum": 10},
                "scrape_interval_ms": {"type": "number", "minimum": 10},
                # heartbeats older than this mark the member down
                "member_timeout_s": {"type": "number", "minimum": 0.1},
                # green/yellow/red rollup thresholds (federation.py
                # rollup_health); red >= yellow is the operator's job
                "health": {
                    "type": "object",
                    "properties": {
                        "lag_versions_yellow": {
                            "type": "integer", "minimum": 0
                        },
                        "lag_versions_red": {
                            "type": "integer", "minimum": 0
                        },
                        "lag_seconds_yellow": {
                            "type": "number", "minimum": 0
                        },
                        "lag_seconds_red": {
                            "type": "number", "minimum": 0
                        },
                        "staleness_yellow_s": {
                            "type": "number", "minimum": 0
                        },
                        "staleness_red_s": {
                            "type": "number", "minimum": 0
                        },
                        "burn_yellow": {"type": "number", "minimum": 0},
                        "burn_red": {"type": "number", "minimum": 0},
                    },
                    "additionalProperties": False,
                },
                # lease-based leader election over the shared WAL
                # directory (cluster/election.py): fencing-token leases,
                # automated follower promotion, write-plane fencing
                "election": {
                    "type": "object",
                    "properties": {
                        "enabled": {"type": "boolean"},
                        # how long a lease lives without renewal; failover
                        # completes within roughly one TTL
                        "lease_ttl_s": {"type": "number", "minimum": 0.1},
                        # leader renews / followers observe at this cadence;
                        # should be well under lease_ttl_s
                        "heartbeat_interval_ms": {
                            "type": "number", "minimum": 10
                        },
                        # higher-priority candidates campaign first
                        # (stagger = candidacy rank x heartbeat interval)
                        "priority": {"type": "integer"},
                        # lease/lineage directory; defaults to the
                        # store.wal.dir all members share
                        "wal_dir": {"type": "string"},
                    },
                    "additionalProperties": False,
                },
            },
            "additionalProperties": False,
        },
    },
    "additionalProperties": False,
}

DEFAULTS = {
    "dsn": "memory",
    "serve.read.port": 4466,
    "serve.read.host": "",
    "serve.read.max-depth": 5,
    "serve.read.workers": 1,
    "serve.read.grpc-max-message-size": 64 << 20,
    "serve.read.max_freshness_wait_s": 30.0,
    "serve.read.encoded": True,
    "serve.read.list": True,
    "serve.read.wire_workers": 1,
    "serve.write.port": 4467,
    "serve.write.host": "",
    "serve.write.grpc-max-message-size": 64 << 20,
    "log.level": "info",
    "log.format": "text",
    "tracing.provider": "",
    "namespaces": [],
    "engine.mode": "closure",
    "engine.max_batch": 4096,
    "engine.batch_window_us": 200,
    "engine.interior_limit": 16384,
    "engine.query_mode": "auto",
    "engine.freshness": "auto",
    "engine.strong_freshness_edges": 1 << 21,
    "engine.rebuild_debounce_ms": 50,
    "engine.cache_size": 65536,
    "engine.max_queue": 0,
    "engine.pipeline_depth": 2,
    "engine.encode_workers": 2,
    "engine.encoded_cache_size": 65536,
    "engine.fallback": True,
    "engine.fallback_threshold": 3,
    "engine.fallback_cooldown_ms": 1000,
    "engine.reverse_index": True,
    "engine.closure_builder": "auto",
    "engine.closure_block_workers": 0,
    "engine.expand_page_size": 0,
    "engine.compile_cache_dir": "",
    "engine.sharding.enabled": False,
    "engine.sharding.data": 1,
    "engine.sharding.edge": 0,
    "engine.sharding.edge_chunk": 0,
    "engine.sharding.escalation_budget": 0.05,
    "engine.memory.admission": True,
    "engine.memory.hbm_budget_frac": 0.8,
    "engine.memory.bytes_per_row": 4096,
    "engine.failover.enabled": True,
    "engine.failover.probe_mode": "child",
    "engine.failover.probe_timeout_s": 10.0,
    "engine.failover.probe_interval_s": 0.5,
    "engine.failover.max_backoff_s": 30.0,
    "engine.failover.allow_cpu": True,
    "store.wal.dir": "",
    "store.wal.sync": "always",
    "store.wal.sync-interval-ms": 50,
    "store.wal.segment-bytes": 16 << 20,
    "checkpoint.dir": "",
    "checkpoint.interval-versions": 10000,
    "checkpoint.interval-s": 300,
    "checkpoint.keep": 2,
    "telemetry.flight.capacity": 512,
    "telemetry.flight.slow_ms": 250,
    "telemetry.flight.dir": "",
    "telemetry.flight.flush_interval_s": 2.0,
    "telemetry.slo.objective": 0.999,
    "telemetry.slo.latency_target_ms": 250,
    "telemetry.slo.fast_window_s": 300,
    "telemetry.slo.slow_window_s": 3600,
    "telemetry.slo.alert_burn_rate": 2.0,
    "telemetry.slo.alert_cooldown_s": 300,
    "telemetry.attribution.enabled": True,
    "telemetry.profiler.enabled": False,
    # 67 Hz: off-round so sampling never phase-locks with 10ms-periodic
    # work (batch windows, flush timers) and under-counts it
    "telemetry.profiler.hz": 67.0,
    "telemetry.profiler.max_stacks": 10000,
    "replication.role": "",
    "replication.upstream": "",
    "replication.dir": "",
    "replication.poll_interval_ms": 50,
    "replication.max_records_per_poll": 512,
    "qos.enabled": False,
    "qos.rate": 0.0,
    "qos.burst": 100.0,
    "qos.overrides": {},
    "autotune.enabled": False,
    "autotune.interval_s": 5.0,
    "autotune.min_requests": 32,
    "autotune.revert_threshold": 0.05,
    "autotune.freeze_burn_rate": 0.0,
    "autotune.backoff_ticks": 3,
    "autotune.history": 256,
    "autotune.knobs": {},
    "scrub.enabled": False,
    "scrub.interval_s": 5.0,
    "scrub.sample_rows": 64,
    "scrub.reservoir": 256,
    "scrub.replay_per_cycle": 32,
    "scrub.wal_segments_per_cycle": 4,
    "scrub.max_repairs_per_cycle": 2,
    "scrub.digest_chunk_size": 1024,
    "scrub.freeze_burn_rate": 0.0,
    "scrub.history": 256,
    "overload.enabled": False,
    "overload.target_delay_ms": 100.0,
    "overload.interval_ms": 100.0,
    "overload.min_limit": 8,
    "overload.tolerance": 2.0,
    "overload.decrease": 0.9,
    "overload.additive": 1.0,
    "overload.hysteresis_ms": 1000.0,
    "overload.dwell_ms": 50.0,
    "overload.throttle_window_s": 30.0,
    "overload.throttle_k": 2.0,
    "overload.history": 256,
    "overload.default_criticality": "default",
    "debug.enabled": True,
    "debug.token": "",
    "debug.profile_max_s": 30,
    "cluster.enabled": False,
    "cluster.instance_id": "",
    "cluster.advertise_url": "",
    "cluster.advertise_write_url": "",
    "cluster.heartbeat_interval_ms": 1000,
    "cluster.scrape_interval_ms": 2000,
    "cluster.member_timeout_s": 10.0,
    "cluster.health.lag_versions_yellow": 100,
    "cluster.health.lag_versions_red": 10000,
    "cluster.health.lag_seconds_yellow": 5.0,
    "cluster.health.lag_seconds_red": 30.0,
    "cluster.health.staleness_yellow_s": 10.0,
    "cluster.health.staleness_red_s": 60.0,
    "cluster.health.burn_yellow": 1.0,
    "cluster.health.burn_red": 2.0,
    "cluster.election.enabled": False,
    "cluster.election.lease_ttl_s": 3.0,
    "cluster.election.heartbeat_interval_ms": 500,
    "cluster.election.priority": 0,
    "cluster.election.wal_dir": "",
}


def _flatten_env_key(key: str) -> str:
    return key.replace(".", "_").replace("-", "_").upper()


def _parse_env_value(raw: str) -> Any:
    try:
        return json.loads(raw)
    except json.JSONDecodeError:
        return raw


def load_config_file(path: str) -> dict:
    data = load_structured_file(path)
    if data is None:
        data = {}
    if not isinstance(data, dict):
        raise ErrMalformedInput(f"config root must be a mapping: {path}")
    return data


# keys frozen after boot: a changed DSN or serve block on live reload is
# ignored with a warning (reference provider.go:70 immutable settings)
IMMUTABLE_KEYS = ("dsn", "serve")

# carve-outs from the immutable ``serve`` block: tuning knobs that are safe
# to flip on a live server (no socket rebinds, no topology change). reload()
# grafts the fresh values into the otherwise-frozen boot subtree.
HOT_SERVE_KEYS = ("serve.read.max_freshness_wait_s",)

# the registered hot-knob table: every key here may be changed on a live
# server — by a file reload, an operator override, or the online autotuner
# (engine/autotune.py) — and is re-applied through a component seam that
# honors it mid-flight (driver/registry.py threads the appliers). The
# ``engine`` block is file-mutable already; registration here is what makes
# a key *live* (components read it per use or expose a resize seam) and
# what gates :meth:`Config.set_hot`'s validated write path.
HOT_ENGINE_KEYS = (
    "engine.pipeline_depth",
    "engine.encode_workers",
    "engine.encoded_cache_size",
    "engine.expand_page_size",
    "engine.sharding.escalation_budget",
    "engine.memory.hbm_budget_frac",
)
HOT_KNOB_KEYS = HOT_SERVE_KEYS + HOT_ENGINE_KEYS

_HOT_MISSING = object()


def knob_schema(key: str) -> Optional[dict]:
    """The per-key subschema for a dotted config key, dug out of
    CONFIG_SCHEMA's nested ``properties`` maps (None when the key has no
    declared schema)."""
    node: Any = CONFIG_SCHEMA
    for part in key.split("."):
        props = node.get("properties") if isinstance(node, dict) else None
        if not isinstance(props, dict) or part not in props:
            return None
        node = props[part]
    return node if isinstance(node, dict) else None


def validate_knob(key: str, value: Any) -> None:
    """Validate one hot-knob value against its schema bounds before it is
    grafted/applied anywhere. Raises ErrMalformedInput for unregistered
    keys or out-of-range values — a bad autotuner or operator write must
    never install an out-of-range knob on a live server."""
    if key not in HOT_KNOB_KEYS:
        raise ErrMalformedInput(
            f"{key} is not a registered hot knob "
            f"(HOT_KNOB_KEYS: {', '.join(HOT_KNOB_KEYS)})"
        )
    sub = knob_schema(key)
    if sub is None:
        raise ErrMalformedInput(f"hot knob {key} has no schema entry")
    try:
        jsonschema.validate(value, sub)
    except jsonschema.ValidationError as e:
        raise ErrMalformedInput(
            f"invalid value for hot knob {key}: {e.message}"
        ) from e


def _dig(data: dict, parts: list[str]):
    cur: Any = data
    for p in parts:
        if not isinstance(cur, dict) or p not in cur:
            return _HOT_MISSING
        cur = cur[p]
    return cur


def _graft(data: dict, parts: list[str], value: Any) -> None:
    cur = data
    for p in parts[:-1]:
        nxt = cur.get(p)
        if not isinstance(nxt, dict):
            nxt = {}
        else:
            nxt = dict(nxt)  # copy: boot subtree is shared, don't mutate it
        cur[p] = nxt
        cur = nxt
    if value is _HOT_MISSING:
        cur.pop(parts[-1], None)
    else:
        cur[parts[-1]] = value


def _strip_hot(block: Any, prefix: str) -> Any:
    """Copy of a top-level config block with its HOT_SERVE_KEYS removed,
    for change comparison — a serve diff confined to hot knobs must not
    trip the immutability warning."""
    if not isinstance(block, dict):
        return block
    out = json.loads(json.dumps(block))  # deep copy, config is plain JSON
    for dotted in HOT_SERVE_KEYS:
        top, _, rest = dotted.partition(".")
        if top != prefix:
            continue
        _graft(out, rest.split("."), _HOT_MISSING)
    return out


class Config:
    def __init__(
        self,
        values: Optional[dict] = None,
        config_file: Optional[str] = None,
        env: Optional[dict] = None,
        flag_overrides: Optional[dict[str, Any]] = None,
    ):
        data: dict = {}
        if config_file:
            data = load_config_file(config_file)
        if values:
            data = _deep_merge(data, values)
        self._data = data
        self.config_file = config_file
        self._values = dict(values or {})
        self._env = dict(env if env is not None else os.environ)
        self._overrides: dict[str, Any] = dict(flag_overrides or {})
        self.validate()
        self._namespace_manager: Optional[NamespaceManager] = None

    def reload(self) -> list[str]:
        """Re-read the config file (hot reload, reference provider.go:58-104).

        Returns the list of changed top-level keys that were APPLIED.
        Immutable keys (DSN, serve) keep their boot values; a changed
        ``namespaces`` spec rebuilds/refreshes the namespace manager in
        place so stores holding a reference see the new set. Raises
        ErrMalformedInput when the new file fails schema validation — the
        previous config keeps serving (rollback-to-last-good)."""
        if not self.config_file:
            return []
        fresh = load_config_file(self.config_file)
        if self._values:
            fresh = _deep_merge(fresh, self._values)
        try:
            jsonschema.validate(fresh, CONFIG_SCHEMA)
        except jsonschema.ValidationError as e:
            raise ErrMalformedInput(
                f"invalid configuration: {e.message} "
                f"(at {'/'.join(map(str, e.path))})"
            ) from e
        old = self._data
        changed = [
            k
            for k in set(old) | set(fresh)
            if old.get(k) != fresh.get(k)
        ]
        applied = []
        for key in changed:
            if key in IMMUTABLE_KEYS:
                if _strip_hot(old.get(key), key) == _strip_hot(
                    fresh.get(key), key
                ):
                    continue  # diff confined to hot knobs, handled below
                # frozen after boot — say so, or the operator believes the
                # new DSN/ports are live
                from ..telemetry import get_logger

                get_logger("config").warn(
                    "config key is immutable after boot; keeping the boot "
                    "value (restart to apply)",
                    key=key,
                )
                continue
            applied.append(key)
        merged = dict(fresh)
        for key in IMMUTABLE_KEYS:
            if key in old:
                merged[key] = old[key]
            else:
                merged.pop(key, None)
        # hot carve-outs: graft the fresh values of HOT_SERVE_KEYS into the
        # frozen boot subtree so these knobs really are live-reloadable.
        # Each value is re-validated against its own schema bounds first —
        # the whole-file validation above covers the fresh tree, but the
        # graft is the last write before a live component reads the knob,
        # so it gets the same guard set_hot() gives the autotuner path
        for dotted in HOT_SERVE_KEYS:
            parts = dotted.split(".")
            new_v = _dig(fresh, parts)
            if new_v != _dig(old, parts):
                if new_v is not _HOT_MISSING:
                    try:
                        validate_knob(dotted, new_v)
                    except ErrMalformedInput as e:
                        from ..telemetry import get_logger

                        get_logger("config").warn(
                            "hot knob reload value rejected; keeping the "
                            "previous value",
                            key=dotted,
                            error=str(e),
                        )
                        continue
                _graft(merged, parts, new_v)
                applied.append(dotted)
        self._data = merged
        if "namespaces" in applied:
            self._refresh_namespace_manager()
        return sorted(applied)

    def _refresh_namespace_manager(self) -> None:
        wrapper = self._namespace_manager
        if wrapper is None:
            return  # nothing built yet; next namespace_manager() call reads fresh
        inner = wrapper.inner
        spec = self.get(KEY_NAMESPACES)
        from ..namespace.watcher import NamespaceWatcher

        if isinstance(inner, MemoryNamespaceManager) and isinstance(
            spec, list
        ):
            inner.replace_all(
                [
                    Namespace(
                        name=n["name"],
                        id=int(n.get("id", 0)),
                        config=n.get("config", {}) or {},
                    )
                    for n in spec
                ]
            )
        elif (
            isinstance(inner, NamespaceWatcher)
            and isinstance(spec, str)
            and _uri_path(spec) == inner.path
        ):
            pass  # same URI: the watcher's own poll loop handles content
        else:
            # inline <-> URI flip (or new URI): swap the wrapped manager;
            # stores hold the stable wrapper, so they see the new set
            if hasattr(inner, "close"):
                inner.close()
            wrapper.inner = self._build_namespace_manager()

    def validate(self) -> None:
        try:
            jsonschema.validate(self._data, CONFIG_SCHEMA)
        except jsonschema.ValidationError as e:
            raise ErrMalformedInput(
                f"invalid configuration: {e.message} (at {'/'.join(map(str, e.path))})"
            ) from e

    # -- lookup ---------------------------------------------------------------

    def get(self, key: str, default: Any = _UNSET) -> Any:
        if key in self._overrides:
            return self._overrides[key]
        env_val = self._env.get("KETO_" + _flatten_env_key(key))
        if env_val is None:
            env_val = self._env.get(_flatten_env_key(key))
        if env_val is not None:
            return _parse_env_value(env_val)
        node: Any = self._data
        for part in key.split("."):
            if not isinstance(node, dict) or part not in node:
                # a caller-provided default wins even when falsy (0/False/"")
                if default is not _UNSET:
                    return default
                return DEFAULTS.get(key)
            node = node[part]
        return node

    def set_override(self, key: str, value: Any) -> None:
        self._overrides[key] = value

    def file_value(self, key: str) -> Any:
        """The config FILE's value for ``key`` (plus DEFAULTS), ignoring
        the override layer — how the reload watcher decides whether an
        operator actually edited a hot knob that the autotuner has since
        shadowed with a ``set_hot`` override."""
        node: Any = self._data
        for part in key.split("."):
            if not isinstance(node, dict) or part not in node:
                return DEFAULTS.get(key)
            node = node[part]
        return node

    def set_hot(self, key: str, value: Any) -> None:
        """Validated live write to a registered hot knob (HOT_KNOB_KEYS):
        the autotuner's (and an operator tool's) only write path. The
        value lands in the override layer, which wins over the file tree —
        a later file reload of other keys does not clobber a tuned knob.
        Raises ErrMalformedInput on unregistered keys or schema-bound
        violations, so an out-of-range value can never be installed."""
        validate_knob(key, value)
        self._overrides[key] = value

    def clear_hot(self, key: str) -> None:
        """Drop a hot-knob override, returning the key to its file/default
        value (how an operator un-pins an autotuned knob)."""
        self._overrides.pop(key, None)

    # -- typed accessors (reference provider.go) ------------------------------

    def dsn(self) -> str:
        return self.get(KEY_DSN)

    def read_api_host(self) -> str:
        return self.get(KEY_READ_HOST) or "0.0.0.0"

    def read_api_port(self) -> int:
        return int(self.get(KEY_READ_PORT))

    def write_api_host(self) -> str:
        return self.get(KEY_WRITE_HOST) or "0.0.0.0"

    def write_api_port(self) -> int:
        return int(self.get(KEY_WRITE_PORT))

    def read_api_max_depth(self) -> int:
        return int(self.get(KEY_READ_MAX_DEPTH))

    def cors(self, plane: str) -> Optional[dict]:
        return self.get(f"serve.{plane}.cors", default={}) or None

    def engine_mode(self) -> str:
        return self.get("engine.mode")

    def namespace_manager(self) -> NamespaceManager:
        """Inline array -> memory manager; string URI -> file/dir watcher with
        hot reload (reference provider.go:190-218 dispatch). Returned behind
        a stable delegating wrapper so config hot-reload can swap the
        underlying manager without invalidating store references."""
        if self._namespace_manager is None:
            self._namespace_manager = _SwappableNamespaceManager(
                self._build_namespace_manager()
            )
        return self._namespace_manager

    def _build_namespace_manager(self) -> NamespaceManager:
        spec = self.get(KEY_NAMESPACES)
        if isinstance(spec, str):
            if spec.startswith("ws://"):
                # remote config service pushing namespace documents over a
                # websocket (reference watcherx ws URIs,
                # namespace_watcher.go:48-89)
                from ..namespace.watcher import WsNamespaceWatcher

                return WsNamespaceWatcher(spec)
            from ..namespace.watcher import NamespaceWatcher

            return NamespaceWatcher(spec)
        nss = [
            Namespace(
                name=n["name"],
                id=int(n.get("id", 0)),
                config=n.get("config", {}) or {},
            )
            for n in (spec or [])
        ]
        return MemoryNamespaceManager(*nss)


def _uri_path(uri: str) -> str:
    from urllib.parse import urlparse

    if uri.startswith("file://"):
        return urlparse(uri).path
    return uri


class _SwappableNamespaceManager(NamespaceManager):
    """Stable handle over a replaceable NamespaceManager (config hot-reload
    swaps `inner`; stores and engines keep this wrapper)."""

    def __init__(self, inner: NamespaceManager):
        self.inner = inner

    def get_namespace_by_name(self, name: str):
        return self.inner.get_namespace_by_name(name)

    def namespaces(self):
        return self.inner.namespaces()

    def should_reload(self, page_payload=None) -> bool:
        return self.inner.should_reload(page_payload)

    def close(self) -> None:
        if hasattr(self.inner, "close"):
            self.inner.close()


def _deep_merge(base: dict, extra: dict) -> dict:
    out = dict(base)
    for k, v in extra.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _deep_merge(out[k], v)
        else:
            out[k] = v
    return out
