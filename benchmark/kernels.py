"""Operations and bytes each kernel needs for one call, from its shapes,
and the least time the chip could take for it.

These count what the algorithm must touch, not what the hardware moves:
a gather of one byte counts one byte even where the memory system fetches
a whole burst. Each function says which bound applies.
"""

from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}")
    return table[device_kind]


def closure_query(b: int, f0: int, l: int) -> dict:
    """``ops/closure.py closure_query`` over a batch of b rows, F0 and L
    columns: gathers B*F0*L uint8 distances, reads the int32 index rows,
    the int32 extra and depth and the bool direct flag, writes B bools,
    and takes B*F0*L minimum compares. Bound: memory."""
    gathered = b * f0 * l
    index = 4 * b * f0 + 4 * b * l + 4 * b + 4 * b + b
    return {"bytes": gathered + index + b, "ops": b * f0 * l,
            "bound": "memory"}


def closure_insert_edge(m_pad: int) -> dict:
    """``ops/closure.py closure_insert_edge`` on an m_pad x m_pad uint8
    closure: reads one column, one row and the whole matrix, writes the
    matrix, and forms m_pad^2 candidates (add, compare, select, min).
    Bound: memory."""
    return {"bytes": 2 * m_pad * m_pad + 2 * m_pad,
            "ops": 4 * m_pad * m_pad, "bound": "memory"}


def semiring_step(g: int, m: int) -> dict:
    """One masked SpMV step of ``engine/pallas_spmv.py`` (bf16 0/1
    frontier [g, m] times adjacency [m, m], masked by reached [g, m]):
    2*g*m*m multiply-adds on the MXU; reads frontier, adjacency and
    reached, writes newly and reached. Bound: compute (bf16)."""
    return {"bytes": 2 * (g * m + m * m + g * m + 2 * g * m),
            "ops": 2 * g * m * m, "bound": "compute_bf16"}


def least_seconds(cost: dict, peak: dict) -> float:
    """The larger of operations over peak and bytes over bandwidth."""
    flops = peak["bf16_flops"] if cost["bound"] == "compute_bf16" \
        else peak["int8_ops"]
    return max(cost["ops"] / flops, cost["bytes"] / peak["hbm_bytes_per_s"])
