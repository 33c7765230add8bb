"""Closed loop of columnar BatchCheck calls.

``in_flight`` calls are outstanding at all times, spread over ``clients``
processes of ``in_flight / clients`` threads; each thread sends its next
call when the previous one answers. Every call checks ``rows_per_call``
rows (one list page); rows are uniform over a pool of check pairs,
``reachable_share`` of them on known paths and the rest at random, so the
result caches rarely hit. A thread cycles through its prepared calls; the
pool is far larger than the caches, so a row comes back long after it was
evicted.

Warm-up, all before the window: first :func:`prewarm` sends, one at a
time from the harness, a BatchCheck for every device query shape the
window can form (``load.shape_warmup``: each pair of fan-out widths at
each power-of-two batch size up to the engine's ``max_batch``, which the
batcher's coalescing reaches); then the clients send calls of their own,
drawn alike, for ``warmup_s`` seconds at the window's concurrency.

Metrics: checks answered per second of window (every row counts), and the
95th percentile of call latency from send to answer.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from .. import load
from ..load import OK, pair_pool, shape_warmup


def plan(cell, graph, rng, seconds: float) -> dict:
    t = cell.traffic
    max_depth = int(cell.config["max_depth"])
    pool_s, pool_t = pair_pool(graph, rng, int(t["pool_pairs"]),
                               float(t["reachable_share"]), max_depth)
    n_clients = int(t["clients"])
    threads = int(t["in_flight"]) // n_clients
    n_req = int(t["requests_per_client"])
    n_warm = int(t["warmup_requests_per_client"])
    warm_s = float(t["warmup_s"])
    rows_per_call = int(t["rows_per_call"])
    specs, rows_of = [], []
    for c in range(n_clients):
        sub_s, sub_t = pool_s[c::n_clients], pool_t[c::n_clients]
        offsets = np.arange(n_warm + n_req + 1, dtype=np.int64) * rows_per_call
        rows = rng.integers(len(sub_s), size=int(offsets[-1]))
        rows_of.append((sub_s[rows], sub_t[rows], offsets))
        specs.append({
            "threads": threads,
            "warmup": n_warm,
            "warmup_s": warm_s,
            "objs": list(graph.keys(sub_s)),
            "subs": [k[0] for k in graph.keys(sub_t)],
            "rows": rows,
            "offsets": offsets,
            "seconds": seconds,
        })
    shapes = [
        (list(graph.keys(o)), [k[0] for k in graph.keys(u)])
        for o, u in shape_warmup(graph, rng,
                                 int(cell.config["engine"]["max_batch"]))
    ]
    return {"specs": specs, "rows_of": rows_of, "warmup": n_warm,
            "shapes": shapes}


def prewarm(p: dict, read_port: int) -> int:
    """Send the plan's shape warm-up calls one at a time, so each reaches
    the device as a batch of its own shape; returns how many were sent."""
    from .. import wire

    ch = wire.channel(read_port)
    call = wire.method(ch, wire.BATCH_CHECK)
    for objs, subs in p["shapes"]:
        call(wire.batch_check_request(objs, subs), timeout=load.ANSWER_WAIT_S)
    ch.close()
    return len(p["shapes"])


def client_main(spec, ctl) -> dict:
    from .. import wire

    objs, subs = spec["objs"], spec["subs"]
    rows, off = spec["rows"], spec["offsets"]
    reqs = [
        wire.batch_check_request([objs[r] for r in rows[a:b]],
                                 [subs[r] for r in rows[a:b]])
        for a, b in zip(off[:-1], off[1:])
    ]
    ch = wire.channel(spec["read_port"])
    call = wire.method(ch, wire.BATCH_CHECK)
    n_threads, n_warm = spec["threads"], spec["warmup"]
    timeout = spec["seconds"] + load.ANSWER_WAIT_S

    warm_end = time.monotonic() + spec["warmup_s"]

    def warm(k):
        mine = list(range(k, n_warm, n_threads))
        j = 0
        while time.monotonic() < warm_end:
            call(reqs[mine[j % len(mine)]], timeout=timeout)
            j += 1

    ws = [threading.Thread(target=warm, args=(k,)) for k in range(n_threads)]
    for w in ws:
        w.start()
    for w in ws:
        w.join()
    t0 = ctl.ready()
    t_end = t0 + spec["seconds"]
    window = list(range(n_warm, len(reqs)))
    logs = [[] for _ in range(n_threads)]

    def drive(k):
        import grpc

        mine = window[k::n_threads]
        j = 0
        while time.monotonic() < t_end:
            i = mine[j % len(mine)]
            j += 1
            resp, status = None, OK
            sent = time.monotonic()
            try:
                resp = call(reqs[i], timeout=timeout)
            except grpc.RpcError as e:
                status = (load.NO_ANSWER
                          if e.code() == grpc.StatusCode.DEADLINE_EXCEEDED
                          else load.ERROR)
            logs[k].append((i, sent, time.monotonic(), status, resp))

    while time.monotonic() < t0:
        time.sleep(min(0.01, max(0.0, t0 - time.monotonic())))
    ds = [threading.Thread(target=drive, args=(k,)) for k in range(n_threads)]
    for d in ds:
        d.start()
    for d in ds:
        d.join()
    ch.close()
    calls = [c for log in logs for c in log]
    return {
        "req": np.array([c[0] for c in calls], dtype=np.int64),
        "sent": np.array([c[1] for c in calls]),
        "done": np.array([c[2] for c in calls]),
        "status": np.array([c[3] for c in calls], dtype=np.int8),
        "resp": [c[4] for c in calls],
    }


def summarize(p: dict, results: list, t0: float, seconds: float) -> dict:
    t_end = t0 + seconds
    lat, rows = [], 0
    for c, r in enumerate(results):
        _, _, off = p["rows_of"][c]
        sizes = off[1:] - off[:-1]
        lat.append(load.latencies(r, r["sent"]))
        ok_in = (r["status"] == OK) & (r["done"] <= t_end)
        rows += int(sizes[r["req"][ok_in]].sum())
    lat = np.concatenate(lat)
    return {
        "metrics": {
            "checks_per_s": rows / seconds,
            "check_p95_ms": load.p95(lat) * 1e3,
        },
        "attempted": int(len(lat)),
        "failed": int((~np.isfinite(lat)).sum()),
        "unanswered": int(sum((r["status"] == load.NO_ANSWER).sum()
                              for r in results)),
        "checks": rows,
        "lines": [load.latency_line("batch_check", lat),
                  {"phase": "window", "checks_answered": rows,
                   "calls": int(len(lat))}],
    }


def verify(p: dict, results: list, reference, rng, n_sample: int) -> dict:
    """Compare a seeded sample of the window's answered rows with the
    reference, and every answer's length with its request's."""
    from ..wire import batch_allowed

    picks = []  # (client, call index, row)
    bad_len = 0
    weights = []
    for c, r in enumerate(results):
        _, _, off = p["rows_of"][c]
        for j in np.nonzero(r["status"] == OK)[0]:
            i = r["req"][j]
            n = int(off[i + 1] - off[i])
            got = len(batch_allowed(r["resp"][j]))
            if got != n:
                bad_len += 1
            weights.append((c, j, n))
    if not weights:
        return {"check_mismatches": (0, 0, 0), "short_answers": (bad_len, 0, 0)}
    sizes = np.array([w[2] for w in weights], dtype=np.int64)
    cum = np.cumsum(sizes)
    draws = rng.integers(cum[-1], size=min(n_sample, int(cum[-1])))
    for d in draws:
        k = int(np.searchsorted(cum, d, side="right"))
        c, j, n = weights[k]
        picks.append((c, j, int(d - (cum[k] - n))))
    mismatches = 0
    for c, j, row in picks:
        r = results[c]
        s_arr, t_arr, off = p["rows_of"][c]
        i = r["req"][j]
        got = batch_allowed(r["resp"][j])
        if row >= len(got):
            mismatches += 1
            continue
        at = off[i] + row
        if bool(got[row]) != reference.check(int(s_arr[at]), int(t_arr[at])):
            mismatches += 1
    return {
        "check_mismatches": (mismatches, 0, len(picks)),
        "short_answers": (bad_len, 0, len(weights)),
    }


def control_results(p: dict, control, rows: int = 2048) -> list:
    """Window calls as if ``control`` (a reference) had answered them, for
    the control reading: each client's first calls, until ``rows`` rows in
    all (the verification samples from these, as from a run's answers)."""
    from ..wire import check_service_pb2

    per_client = rows // len(p["rows_of"]) + 1
    out = []
    for s_arr, t_arr, off in p["rows_of"]:
        req, resp, n_rows = [], [], 0
        for i in range(p["warmup"], len(off) - 1):
            if n_rows >= per_client:
                break
            req.append(i)
            resp.append(check_service_pb2.BatchCheckResponse(allowed=[
                control.check(int(s_arr[r]), int(t_arr[r]))
                for r in range(off[i], off[i + 1])
            ]).SerializeToString())
            n_rows += int(off[i + 1] - off[i])
        n = len(req)
        out.append({"req": np.array(req, dtype=np.int64),
                    "sent": np.zeros(n), "done": np.zeros(n),
                    "status": np.zeros(n, dtype=np.int8), "resp": resp})
    return out
