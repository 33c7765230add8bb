"""The drive8m configuration: its generator is deterministic from the
seed, gives exactly the asked number of distinct tuples and the stated
folder and group structure, and a tiny whole run on the CPU serves it
through the interior walk with answers the reference accepts."""

import time

import numpy as np
import pytest

from benchmark.harness import run_cell, streams

from conftest import cell

NAME = "drive8m.batch_closed"
COUNTS = ("tuples", "users", "docs", "folders", "groups", "group_members",
          "group_nesting")


def tiny(scale=0.001, **engine):
    """The cell with every count cut to ``scale`` (levels, shares and
    max-depth kept), batches of at most 64 rows, light traffic, and the
    closure limit under the tiny interior so the walk serves it."""
    c = cell(NAME)
    sizes = c.config["sizes"]
    for k in COUNTS:
        sizes[k] = max(int(sizes[k] * scale), 24)
    c.config["engine"].update(max_batch=64, interior_limit=64, **engine)
    c.traffic.update(pool_pairs=4096, rows_per_call=16,
                     requests_per_client=64, warmup_requests_per_client=8,
                     warmup_s=0.5)
    return c


def _build(seed, scale=0.001):
    c = tiny(scale)
    return c, c.generator.build(c.config["sizes"], streams(seed)[0])


def _interior(g):
    is_id = g.is_subject_id(np.arange(g.n_nodes))
    inner = np.zeros(g.n_nodes, dtype=bool)
    inner[g.dst[~is_id[g.dst]]] = True
    return inner


def test_same_seed_same_graph():
    _, a = _build(2**40 + 3)
    _, b = _build(2**40 + 3)
    _, c = _build(2**40 + 4)
    assert np.array_equal(a.src, b.src) and np.array_equal(a.dst, b.dst)
    assert not (np.array_equal(a.src, c.src) and np.array_equal(a.dst, c.dst))


def test_exact_distinct_tuples_and_interior():
    c, g = _build(5, scale=0.005)
    sizes = c.config["sizes"]
    keys = g.src * g.n_nodes + g.dst
    assert len(keys) == sizes["tuples"]
    assert len(np.unique(keys)) == len(keys)
    gen = c.generator
    # every document owner set and folder set, and nearly every group
    got = int(_interior(g).sum())
    want = gen.expected_interior(sizes)
    assert want - sizes["groups"] <= got <= want
    assert got > c.config["engine"]["interior_limit"]


def test_folder_and_group_levels_give_the_stated_depth():
    c, g = _build(6, scale=0.005)
    sizes = c.config["sizes"]
    folders, groups = g.kind("folder_view"), g.kind("group")
    f_per = folders.count // sizes["folder_levels"]
    up = ((g.src >= folders.start) & (g.src < folders.stop)
          & (g.dst >= folders.start) & (g.dst < folders.stop))
    child = (g.src[up] - folders.start) // f_per
    parent = (g.dst[up] - folders.start) // f_per
    assert np.all(parent == child - 1)
    # each folder below the roots has exactly one parent
    assert len(np.unique(g.src[up])) == up.sum() == (
        sizes["folder_levels"] - 1) * f_per
    g_per = groups.count // sizes["group_levels"]
    nest = ((g.src >= groups.start) & (g.src < groups.stop)
            & (g.dst >= groups.start) & (g.dst < groups.stop))
    outer = (g.src[nest] - groups.start) // g_per
    inner = (g.dst[nest] - groups.start) // g_per
    assert np.all(inner == outer + 1)
    assert c.config["max_depth"] == c.generator.longest_path(sizes) == 12


def test_full_size_interior_is_far_above_the_closure_limit():
    c = cell(NAME)
    sizes = c.config["sizes"]
    m = c.generator.expected_interior(sizes)
    assert m > 1_000_000
    assert m > 100 * c.config["engine"]["interior_limit"]
    # the stated counts add up to the tuples, shares filling the rest
    fixed = (3 * sizes["docs"] + 2 * sizes["folders"]
             + (sizes["folder_levels"] - 1) * sizes["folders"]
             // sizes["folder_levels"]
             + sizes["group_members"] + sizes["group_nesting"])
    assert 0 < sizes["tuples"] - fixed <= 0.15 * sizes["tuples"]


def test_reachable_pairs_are_reachable_at_max_depth():
    from benchmark.load import pair_pool
    from benchmark.reference import Reference

    _, g = _build(9, scale=0.005)
    s, t = pair_pool(g, np.random.default_rng(1), 200, 1.0, 12)
    ref = Reference(g, 12)
    assert all(ref.check(int(a), int(b)) for a, b in zip(s, t))


@pytest.mark.parametrize("query_mode", ["device", "host"])
def test_sound_tiny_run_walks_the_interior(query_mode, monkeypatch):
    from keto_tpu.engine import closure

    walks = {"device": 0, "host": 0}
    for name, where in (("interior_walk", "device"),
                        ("interior_walk_host", "host")):
        def spy(*a, _f=getattr(closure, name), _w=where, **kw):
            walks[_w] += 1
            return _f(*a, **kw)

        monkeypatch.setattr(closure, name, spy)
    c = tiny(query_mode=query_mode)
    out = run_cell(c, 2**35 + 17, 2.0, False, time.monotonic())
    assert out["correct"], out["compared"]
    assert out["compared"]["check_mismatches"]["of"] > 0
    assert out["failed"] == 0
    assert walks[query_mode] > 0
    assert walks["host" if query_mode == "device" else "device"] == 0


def test_walk_roofline_reads_shapes_and_hops():
    from benchmark import kernels, readers
    from benchmark.manifest import load_module
    from benchmark.trace import Op, Trace

    reader = load_module(cell(NAME).root, "metrics", "interior_walk_roofline")
    f0 = ("%fusion.15 = (pred[128]{0}, pred[32,128]{1,0}) "
          "fusion(s32[128,32]{0,1:T(8,128)} %f0.1), kind=kLoop")
    names = [f0] + ["%sort.14 = s32[64,128]{1,0} sort(...)"] * 22 + [
        "%fusion.27 = s32[8192]{0} fusion(...)"]
    ops = [Op("jit_interior_walk", n, 0.001 * i, 0.0001, 0)
           for i, n in enumerate(names)]
    assert reader.shape(ops) == (128, 32, 11)
    tr = Trace(window_s=1.0, devices={"/device:TPU:0": ops},
               modules={"/device:TPU:0": [("jit_interior_walk", 0.0, 0.5)]})
    peak = kernels.peaks("TPU v5 lite")
    ctx = readers.Context(window_s=1.0, checks=100, attribution={},
                          build_phases={}, peak=peak, trace=tr)
    least = kernels.least_seconds(reader.cost(128, 32, 11), peak)
    assert reader.read(ctx) == pytest.approx(100 * least / 0.5)
    assert 0 < reader.read(ctx) < 100
    # a program without the walk: nothing to read
    empty = Trace(window_s=1.0, devices={"/device:TPU:0": []},
                  modules={"/device:TPU:0": []})
    assert reader.read(readers.Context(1.0, 100, {}, {}, peak, empty)) is None
