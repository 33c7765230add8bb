"""Multi-chip scale-out: the tuple graph sharded over a device mesh.

The reference scales horizontally with stateless replicas over a shared SQL
database and delegates data distribution to CockroachDB (SURVEY.md §2.10).
The TPU-native equivalent: stripe the closure engine's CSRs over an ICI
mesh with ``jax.sharding`` + ``shard_map``, replicate the interior distance
matrix, and answer a batch in one compiled program whose collectives ride
ICI — no host round-trips between phases.
"""

from .closure_sharded import ShardedClosureEngine
from .mesh import make_mesh
from .serving import ShardedServingEngine

__all__ = [
    "ShardedClosureEngine",
    "ShardedServingEngine",
    "make_mesh",
]
