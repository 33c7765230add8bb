"""Fused masked SpMV frontier-expansion kernel — device semiring builder.

Device twin of keto_tpu.engine.semiring: the closure build as a batched
multi-source BFS whose per-step kernel is

    newly   = (frontier x A  under OR-AND)  AND NOT  reached
    reached = reached OR newly

On TPU the step runs as a Pallas kernel that fuses the MXU tile matmul with
the reached-mask compare/select in VMEM — one pass over the adjacency tiles
per step instead of matmul + three elementwise kernels bouncing [G, M]
intermediates through HBM. Everywhere else (CPU CI, GPU) the same math runs
as a lax step (`_masked_step_lax`) so the builder is platform-complete;
the two are numerically identical (0/1 masks, f32 accumulation, 0.5
threshold). The step is chosen from the platform up front; a Pallas
failure on a TPU raises — it is never papered over with the lax step.

Masks live as bfloat16 0/1 rather than bool: the MXU consumes bf16 tiles
directly and counts up to the 16k interior limit are exact in the f32
accumulator, so `> 0.5` is an exact boolean-OR reduction.

Output contract matches ops.closure.build_closure_packed byte for byte
(uint8 distances clamped at k_max, INF elsewhere, live diagonal 0, padding
rows INF) — fuzz-enforced by tests/test_semiring.py.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.closure import INF_DIST

# Pallas tile sizes: MXU-aligned (the bf16 minimum tile is 16x128); the
# frontier block [TG, M] plus one adjacency stripe [M, TM] must fit VMEM
# (~16 MB/core) at the 16k interior limit -> 4 MB + 4 MB
_TG = 128
_TM = 128


def semiring_step() -> str:
    """The masked-SpMV step the builder runs on this platform: "pallas" on
    a TPU, "lax" everywhere else."""
    return "pallas" if jax.default_backend() == "tpu" else "lax"


def _masked_step_lax(frontier, adj, reached):
    """One masked SpMV step, bf16 0/1 masks: (newly, reached')."""
    nxt = (
        jnp.dot(frontier, adj, preferred_element_type=jnp.float32) > 0.5
    ).astype(jnp.bfloat16)
    newly = nxt * (jnp.bfloat16(1) - reached)
    return newly, jnp.maximum(reached, nxt)


def _spmv_kernel(f_ref, a_ref, r_ref, newly_ref, reach_ref):
    # one (TG, TM) output tile: full-K dot on the MXU, mask fused on the VPU
    nxt = (
        jnp.dot(f_ref[:], a_ref[:], preferred_element_type=jnp.float32)
        > 0.5
    ).astype(jnp.bfloat16)
    r = r_ref[:]
    newly_ref[:] = nxt * (jnp.bfloat16(1) - r)
    reach_ref[:] = jnp.maximum(r, nxt)


def _masked_step_pallas(frontier, adj, reached):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    g, m = frontier.shape
    grid = (g // _TG, m // _TM)
    out_shape = [
        jax.ShapeDtypeStruct((g, m), jnp.bfloat16),
        jax.ShapeDtypeStruct((g, m), jnp.bfloat16),
    ]
    tile = pl.BlockSpec(
        (_TG, _TM),
        lambda i, j: (i, j),
        memory_space=pltpu.VMEM,
    )
    newly, reach = pl.pallas_call(
        _spmv_kernel,
        out_shape=out_shape,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (_TG, m), lambda i, j: (i, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (m, _TM), lambda i, j: (0, j),
                memory_space=pltpu.VMEM,
            ),
            tile,
        ],
        out_specs=[tile, tile],
    )(frontier, adj, reached)
    return newly, reach


@partial(
    jax.jit, static_argnames=("m_pad", "k_max", "group", "use_pallas")
)
def _build_closure_semiring(
    packed, m, *, m_pad, k_max, group, use_pallas
):
    step = _masked_step_pallas if use_pallas else _masked_step_lax
    shifts = jnp.arange(7, -1, -1, dtype=jnp.uint8)  # np.packbits order
    adj_bits = (packed[:, :, None] >> shifts[None, None, :]) & jnp.uint8(1)
    adj = adj_bits.reshape(m_pad, m_pad).astype(jnp.bfloat16)
    inf = jnp.uint8(INF_DIST)

    def per_group(g):
        f0 = lax.dynamic_slice(
            adj, (g * group, 0), (group, m_pad)
        )  # distance-1 frontier = the sources' adjacency rows (0/1 bf16)
        d = jnp.where(f0 > 0, jnp.uint8(1), inf)

        def body(k, state):
            frontier, reached, d = state
            newly, reached = step(frontier, adj, reached)
            d = jnp.where(newly > 0, k.astype(jnp.uint8), d)
            return newly, reached, d

        if k_max >= 2:
            _, _, d = lax.fori_loop(2, k_max + 1, body, (f0, f0, d))
        return d

    d = lax.map(per_group, jnp.arange(m_pad // group, dtype=jnp.int32))
    d = d.reshape(m_pad, m_pad)
    # rows >= m have empty adjacency and stay INF; live diagonal = 0,
    # padding diagonal INF (the PAD index must be inert in queries)
    idx = jnp.arange(m_pad, dtype=jnp.int32)
    live = idx < m
    eye = idx[:, None] == idx[None, :]
    diag_vals = jnp.where(live, jnp.uint8(0), inf)
    return jnp.where(eye, diag_vals[:, None], d)


def build_closure_semiring(packed, m, *, m_pad, k_max, group=256):
    """Device semiring closure build: the fused Pallas step on a TPU, the
    lax step elsewhere (`semiring_step`)."""
    use_pallas = semiring_step() == "pallas"
    grp = group
    while m_pad % grp:
        grp //= 2  # m_pad is a multiple of 256 upstream; be safe anyway
    if use_pallas and grp % _TG:
        raise ValueError(
            f"m_pad={m_pad} is not a multiple of the Pallas row tile {_TG}"
        )
    return _build_closure_semiring(
        packed, m, m_pad=m_pad, k_max=k_max, group=grp, use_pallas=use_pallas
    )
