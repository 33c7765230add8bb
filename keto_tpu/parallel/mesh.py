"""The device mesh the sharded engines lay the graph over.

Mesh axes ``("data", "edge")``: requests are data-parallel over ``data``;
the graph's node-striped CSRs are partitioned over ``edge`` (each device
holds the rows of the nodes it owns — the HBM scale axis).
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh


def make_mesh(
    devices=None, data: int = 1, edge: Optional[int] = None
) -> Mesh:
    """(data, edge) mesh over the given devices (default: all)."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if edge is None:
        edge = n // data
    if data * edge != n:
        raise ValueError(f"mesh {data}x{edge} != {n} devices")
    arr = np.array(devices).reshape(data, edge)
    return Mesh(arr, axis_names=("data", "edge"))
