"""Compile the main path's kernels for a TPU v5e at real widths, without a
chip: the TPU compiler refuses here what it would refuse on the chip
(misaligned Pallas blocks, VMEM or HBM overruns, unpartitionable
kernels) at no chip time. Nothing runs, so nothing here says anything
about results or times.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
xdist worker imports this file. All cases stay in this one file so the
worker that loads the library runs them all.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

# the engine.interior_limit width, and the rbac1m deployment's sizes
_M_PAD_LIMIT = 16384
_M_PAD_RBAC1M = 11520  # ~11k interior subject sets
_BATCH = 4096


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep the cache out of these compiles
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_semiring_pallas_builder_at_interior_limit(one_chip):
    from keto_tpu.engine.pallas_spmv import _build_closure_semiring

    m_pad = _M_PAD_LIMIT
    compiled = _build_closure_semiring.lower(
        _spec((m_pad, m_pad // 8), jnp.uint8, one_chip),
        _spec((), jnp.int32, one_chip),
        m_pad=m_pad,
        k_max=4,
        group=256,
        use_pallas=True,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 10**9


def test_closure_query_at_rbac1m_width(one_chip):
    from keto_tpu.ops.closure import closure_query

    m, w = _M_PAD_RBAC1M, 8
    compiled = closure_query.lower(
        _spec((m, m), jnp.uint8, one_chip),
        _spec((_BATCH, w), jnp.int32, one_chip),
        _spec((_BATCH, w), jnp.int32, one_chip),
        _spec((_BATCH,), jnp.int32, one_chip),
        _spec((_BATCH,), jnp.int32, one_chip),
        _spec((_BATCH,), jnp.bool_, one_chip),
    ).compile()
    # a plain XLA gather/reduce: no custom kernel, but it must fit
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 10**9


def test_interior_walk_at_drive8m_size(one_chip):
    from keto_tpu.ops.closure import interior_walk

    # drive8m: ~2.4M interior sets and ~0.9M interior edges (a ~14 MB
    # CSR), bucketed as the engine buckets them; F0 and L at their fixed
    # width 32
    rows, edges, w = 2490368, 917504, 32
    compiled = interior_walk.lower(
        _spec((rows + 1 + edges,), jnp.int32, one_chip),
        _spec((_BATCH, w), jnp.int32, one_chip),
        _spec((_BATCH, w), jnp.int32, one_chip),
        _spec((_BATCH,), jnp.int32, one_chip),
        _spec((_BATCH,), jnp.int32, one_chip),
        _spec((_BATCH,), jnp.bool_, one_chip),
        rows=rows,
        hops=11,
        width=w,
        fanout=2 * w,
    ).compile()
    text = compiled.as_text()
    # the lockstep walk: one device loop; a hop gathers the frontier's
    # two CSR offsets and its successors, and sorts twice
    assert " while(" in text and text.count(" sort(") == 2
    assert text.count(" gather(") == 3
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


def test_sharded_serving_step_on_four_chips(topo):
    from keto_tpu.parallel.closure_sharded import _sharded_closure_check
    from keto_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(topo.devices, data=1)
    n = mesh.shape["edge"]
    assert n == 4
    pn, m_pad = 1 << 19, _M_PAD_RBAC1M
    rows = -(-pn // n) + 1
    nnz = (1 << 20) // n
    repl = NamedSharding(mesh, P())
    edge = NamedSharding(mesh, P("edge"))
    data = NamedSharding(mesh, P("data"))
    stripe = lambda k: _spec((n, k), jnp.int32, edge)  # noqa: E731
    batch = lambda dt: _spec((_BATCH,), dt, data)  # noqa: E731
    compiled = _sharded_closure_check.lower(
        _spec((m_pad, m_pad), jnp.uint8, repl),
        stripe(rows), stripe(nnz),  # F0 CSR
        stripe(rows), stripe(nnz),  # L CSR
        stripe(rows - 1),  # interior index
        stripe(rows), stripe(nnz),  # full-out CSR
        batch(jnp.int32), batch(jnp.int32), batch(jnp.bool_),
        batch(jnp.int32),
        mesh=mesh,
        n_shards=n,
        m_pad=m_pad,
        f0_max=32,
        l_max=32,
        pn=pn,
    ).compile()
    text = compiled.as_text()
    # the two pmin and the pmax exchanges across the edge axis
    assert "all-reduce" in text
    per_device = compiled.memory_analysis()
    assert per_device.temp_size_in_bytes < 16 * 10**9
    assert np.prod(list(mesh.shape.values())) == 4
