"""The benchmark's plain reference agrees with the program's host engine
(engine/check.py CheckEngine) on a small store."""

import numpy as np
import pytest

from benchmark.harness import streams
from benchmark.load import pair_pool
from benchmark.reference import Reference

from conftest import tiny_cell


def _store(graph):
    from keto_tpu.store import ColumnarTupleStore

    store = ColumnarTupleStore()
    store.bulk_load_edges(graph.keys(graph.src), graph.keys(graph.dst))
    return store


def _tuple(graph, s, t):
    from keto_tpu.relationtuple import RelationTuple, SubjectID, SubjectSet

    obj = graph.keys(np.array([s]))[0]
    sub = graph.keys(np.array([t]))[0]
    subject = (SubjectID(id=sub[0]) if len(sub) == 1
               else SubjectSet(namespace=sub[0], object=sub[1],
                               relation=sub[2]))
    return RelationTuple(namespace=obj[0], object=obj[1], relation=obj[2],
                         subject=subject)


def _graph(name, seed):
    cell = tiny_cell(name, 0.0005)
    g_rng, t_rng, _ = streams(seed)
    return cell.generator.build(cell.config["sizes"], g_rng), t_rng


@pytest.mark.parametrize("name", ["rbac1m.batch_closed",
                                  "github6m.batch_closed"])
def test_check_agrees_with_check_engine(name):
    from keto_tpu.engine.check import CheckEngine

    graph, rng = _graph(name, 3)
    store = _store(graph)
    s, t = pair_pool(graph, rng, 120, 0.5, 5)
    ref = Reference(graph, 5)
    engine = CheckEngine(store, max_depth=5)
    for depth in (0, 2):
        got = [ref.check(int(a), int(b), depth) for a, b in zip(s, t)]
        want = [engine.subject_is_allowed(_tuple(graph, a, b), depth)
                for a, b in zip(s, t)]
        assert got == want
    assert any(got) and not all(got)


def test_max_depth_is_a_guarantee():
    """A path of exactly max-depth tuples is allowed, and not at one
    below; a path one longer than max-depth is denied."""
    from benchmark.graph import Graph, Kind

    users = Kind("user", 0, 2, ("u{}",))
    sets = Kind("set", 2, 6, ("t", "o{}", "r"))
    chain = [2, 3, 4, 5, 6, 0]  # five tuples to u0
    src = np.array(chain[:-1] + [7], dtype=np.int64)
    dst = np.array(chain[1:] + [2], dtype=np.int64)  # six tuples 7 -> u0
    g = Graph(kinds=[users, sets], src=src, dst=dst, objects="set",
              subjects="user", grants=("set",))
    ref = Reference(g, 5)
    assert ref.check(2, 0, 0) and ref.check(2, 0, 5)
    assert not ref.check(2, 0, 4)
    assert not ref.check(7, 0, 0)
    assert Reference(g, 6).check(7, 0, 0)
