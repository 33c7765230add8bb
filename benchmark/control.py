"""The control reading: the plain reference, put in the program's place
with one stated guarantee broken (max-depth one below the configuration's),
compared with the reference by the run's own verification.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 \
        [--depth-delta -1]

Prints one JSON line per seed with every number compared. Needs no chip:
it runs where the cell's data fits, at the cell's own size.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_reading(cell, seed: int, seconds: float, delta: int = -1) -> dict:
    from benchmark.harness import VERIFY_CHECKS, streams
    from benchmark.reference import Reference

    g_rng, t_rng, v_rng = streams(seed)
    graph = cell.generator.build(cell.config["sizes"], g_rng)
    plan = cell.loop.plan(cell, graph, t_rng, seconds)
    depth = int(cell.config["max_depth"])
    reference = Reference(graph, depth)
    control = Reference(graph, depth + delta)
    results = cell.loop.control_results(plan, control)
    compared = cell.loop.verify(plan, results, reference, v_rng,
                                VERIFY_CHECKS)
    return {k: {"value": v, "limit": lim, "of": n}
            for k, (v, lim, n) in compared.items()}


def main(argv=None) -> int:
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark.manifest import find_cell

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--depth-delta", type=int, default=-1,
                    help="the control's max-depth minus the configured one")
    args = ap.parse_args(argv)
    cell = find_cell(ROOT, args.workload)
    seconds = args.seconds or json.load(
        open(os.path.join(ROOT, "BENCHMARK.json")))["run_seconds"]
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        out = control_reading(cell, seed, seconds, args.depth_delta)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control": f"max_depth {args.depth_delta:+d}",
                          "compared": out,
                          "seconds": time.monotonic() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
