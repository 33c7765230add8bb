"""Device kernels: batched graph operations compiled by XLA for TPU.

The reference's hot loop is a mutually recursive DFS with one SQL round-trip
per subject-set node per page (internal/check/engine.go:82-114). Here the
same question — reachability through subject-set indirections, depth-limited
— is answered for a whole batch of requests at once from the resident
graph (SURVEY.md §7):

- ``build_closure`` / ``build_closure_packed`` build the interior distance
  matrix D once per snapshot;
- ``closure_query`` answers a batch by gathering D rows through each row's
  boundary CSRs (F0, L);
- ``interior_walk`` answers a batch whose interior is too large for D by
  walking the interior's CSR on the device, all rows in lockstep.
"""

from .closure import (
    INF_DIST,
    build_closure,
    build_closure_packed,
    closure_query,
    interior_walk,
    pack_adjacency,
)

__all__ = [
    "INF_DIST",
    "build_closure",
    "build_closure_packed",
    "closure_query",
    "interior_walk",
    "pack_adjacency",
]
