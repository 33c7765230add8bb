"""What the per-layer metric files share. Each ``metrics/<name>.py`` has
``read(ctx) -> float | None``; None (nothing to read) leaves the metric out
of the line, and no reader returns 0 for a share of a roofline or peak.

``ctx`` is a :class:`Context`.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Optional

from . import kernels

#: ``/debug/attribution`` stages of the wire layer (transport handling
#: before the batcher, response build, bookkeeping after it) and of the
#: batcher (queue wait, vocabulary encode, launch, result decode)
WIRE_STAGES = ("admission", "serialize", "reply")
BATCHER_STAGES = ("queue", "encode", "launch", "decode")


@dataclass
class Context:
    window_s: float
    checks: int  # checks answered in the window (every row counts)
    attribution: dict  # stage -> seconds over the window
    build_phases: dict  # the set-up closure build, seconds by phase
    peak: dict  # kernels.peaks(device kind)
    trace: Optional[object] = None  # trace.Trace of the window


def us_per_check(ctx: Context, stages) -> Optional[float]:
    if ctx.checks <= 0 or not ctx.attribution:
        return None
    return sum(ctx.attribution.get(s, 0.0) for s in stages) / ctx.checks * 1e6


def idle_percent(ctx: Context) -> Optional[float]:
    t = ctx.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s)


#: closure_query's index operands in its HLO text: "s32[B,F0]{...} %f0.1"
#: and "s32[B,L]{...} %l.1" (the jitted function's arguments f0 and l)
_INDEX = re.compile(r"s32\[(\d+),(\d+)\]\{[^}]*\} %(f0|l)\.\d+\b")


def query_shapes(runs: dict) -> list:
    """(B, F0, L) of each ``closure_query`` execution, read from the
    shapes of its index operands in its ops' HLO text; executions whose
    shapes cannot be read are left out."""
    out = []
    for ops in runs.values():
        dims = {}
        for o in ops:
            for m in _INDEX.finditer(o.name):
                dims[m.group(3)] = (int(m.group(1)), int(m.group(2)))
        if "f0" in dims and "l" in dims and dims["f0"][0] == dims["l"][0]:
            out.append((dims["f0"][0], dims["f0"][1], dims["l"][1]))
    return out


def query_roofline_percent(ctx: Context) -> Optional[float]:
    """Least time of the window's ``closure_query`` executions (from
    their shapes) over their device time (the program's span), in
    percent, over the executions whose shapes can be read; None when
    there are none."""
    t = ctx.trace
    if t is None:
        return None
    least = device_s = 0.0
    runs = t.module_ops("closure_query")
    spans = t.module_spans("closure_query")
    for key, ops in runs.items():
        shape = query_shapes({key: ops})
        if shape:
            least += kernels.least_seconds(
                kernels.closure_query(*shape[0]), ctx.peak)
            device_s += spans[key]
    if device_s <= 0:
        return None
    return 100.0 * least / device_s
