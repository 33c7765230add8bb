"""Percent of its roofline that ``interior_walk`` reached in the traced
window: the least time its executions need over their device time.

An execution's rows B and frontier width W come from the shape of its
index operand ``f0`` [B, W] in its ops' HLO text, and its hops from the
number of its ``sort`` ops (the walk sorts twice a hop). L's width does
not show there (the walk takes ``l`` through a layout bitcast, which runs
no op), so ``l`` counts as one column: a lower bound. The least time
(``cost``) counts what the walk must touch: the index rows, extra, depth
and direct; per hop, two CSR offsets and a successor id for each of the
B*W frontier entries and the next frontier written; one membership test
per frontier entry at hop 0 and at each hop; the answers. Bound: memory.
Executions whose shapes cannot be read are left out.
"""

from .. import kernels, readers


def cost(b: int, w: int, hops: int) -> dict:
    index = 4 * b * w + 4 * b + 4 * b + 4 * b + b
    per_hop = b * w * (8 + 4 + 4)
    return {"bytes": index + hops * per_hop + 2 * b,
            "ops": (hops + 1) * b * w, "bound": "memory"}


def shape(ops) -> tuple | None:
    """(B, W, hops) of one execution, or None."""
    bw, sorts = None, 0
    for o in ops:
        if o.name.startswith("%sort"):
            sorts += 1
        elif bw is None and "%f0." in o.name:
            for m in readers._INDEX.finditer(o.name):
                if m.group(3) == "f0":
                    bw = (int(m.group(1)), int(m.group(2)))
    if bw is None:
        return None
    return bw[0], bw[1], sorts // 2


def read(ctx: readers.Context):
    t = ctx.trace
    if t is None:
        return None
    least = device_s = 0.0
    spans = t.module_spans("interior_walk")
    for key, ops in t.module_ops("interior_walk").items():
        s = shape(ops)
        if s is None or key not in spans:
            continue
        least += kernels.least_seconds(cost(*s), ctx.peak)
        device_s += spans[key]
    if device_s <= 0:
        return None
    return 100.0 * least / device_s
