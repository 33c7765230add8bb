"""The generators: deterministic from the seed, exactly the asked number
of distinct tuples, and the published mix kept at tiny sizes."""

import numpy as np
import pytest

from benchmark.harness import streams

from conftest import tiny_cell


def _build(name, seed, scale=0.01):
    cell = tiny_cell(name, scale)
    return cell, cell.generator.build(cell.config["sizes"], streams(seed)[0])


@pytest.mark.parametrize("name", ["rbac1m.batch_closed",
                                  "github6m.batch_closed"])
def test_same_seed_same_graph(name):
    _, a = _build(name, 2**40 + 3)
    _, b = _build(name, 2**40 + 3)
    _, c = _build(name, 2**40 + 4)
    assert np.array_equal(a.src, b.src) and np.array_equal(a.dst, b.dst)
    assert not (np.array_equal(a.src, c.src) and np.array_equal(a.dst, c.dst))


@pytest.mark.parametrize("name", ["rbac1m.batch_closed",
                                  "github6m.batch_closed"])
def test_exact_distinct_tuples(name):
    cell, g = _build(name, 5)
    keys = g.src * g.n_nodes + g.dst
    assert len(keys) == cell.config["sizes"]["tuples"]
    assert len(np.unique(keys)) == len(keys)


def _share(g, src_kind, dst_kinds):
    k = g.kind(src_kind)
    in_src = (g.src >= k.start) & (g.src < k.stop)
    in_dst = np.zeros(len(g.dst), dtype=bool)
    for name in dst_kinds:
        d = g.kind(name)
        in_dst |= (g.dst >= d.start) & (g.dst < d.stop)
    return float(np.mean(in_src & in_dst))


def test_rbac_mix():
    cell, g = _build("rbac1m.batch_closed", 7, scale=0.1)
    mix = cell.config["sizes"]["mix"]
    # collisions remove a few percent of the drawn edges of each stage
    assert _share(g, "group", ["user"]) == pytest.approx(
        mix["membership"], rel=0.05)
    assert _share(g, "role", ["group"]) == pytest.approx(
        mix["group_role"], rel=0.1)
    assert _share(g, "group", ["group"]) == pytest.approx(
        mix["group_nesting"], rel=0.1)
    # grants, topped up, are all the rest
    grants = _share(g, "resource", ["group", "role"])
    rest = (_share(g, "group", ["user", "group"])
            + _share(g, "role", ["group", "role"]))
    assert grants == pytest.approx(1 - rest, abs=1e-9)
    assert grants >= 1 - sum(mix.values())


def test_rbac_groups_nest_level_by_level():
    """A nesting tuple joins a group to one a level above it, every pair
    of adjacent levels has some, and so the longest chain of groups is
    ``group_levels`` long."""
    cell, g = _build("rbac1m.batch_closed", 8, scale=0.1)
    levels = cell.config["sizes"]["group_levels"]
    groups = g.kind("group")
    per = groups.count // levels
    nest = ((g.src >= groups.start) & (g.src < groups.stop)
            & (g.dst >= groups.start) & (g.dst < groups.stop))
    outer = (g.src[nest] - groups.start) // per
    inner = (g.dst[nest] - groups.start) // per
    assert np.all(outer == inner + 1)
    assert set(inner.tolist()) == set(range(levels - 1))


def test_github_mix():
    cell, g = _build("github6m.batch_closed", 7, scale=0.05)
    mix = cell.config["sizes"]["mix"]
    assert _share(g, "team", ["user"]) == pytest.approx(
        mix["membership"], rel=0.05)
    assert _share(g, "team", ["team"]) == pytest.approx(
        mix["nesting"], rel=0.1)
    repos = [k.name for k in g.kinds if k.name.startswith("repo_")]
    to_team = sum(_share(g, r, ["team"]) for r in repos)
    to_user = sum(_share(g, r, ["user"]) for r in repos)
    assert to_team / (to_team + to_user) == pytest.approx(
        mix["grant_to_team"], abs=0.02)
    # the four repository roles are drawn alike
    per_role = [sum(_share(g, r, ["team", "user"]) for r in [n])
                for n in repos]
    assert max(per_role) / min(per_role) < 1.2


def test_reachable_pairs_are_reachable():
    from benchmark.load import pair_pool
    from benchmark.reference import Reference

    cell, g = _build("rbac1m.batch_closed", 9)
    s, t = pair_pool(g, np.random.default_rng(1), 200, 1.0, 5)
    ref = Reference(g, 5)
    assert all(ref.check(int(a), int(b)) for a, b in zip(s, t))
