"""The trace reduction on a small trace recorded on one TPU v5e: a window
of cell rbac1m.batch_closed (data/rbac1m_batch_closed.xplane.pb)."""

import os

import pytest

from benchmark import kernels, readers, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "rbac1m_batch_closed.xplane.pb")


@pytest.fixture(scope="module")
def tr():
    return trace.load(DATA)


def _ctx(tr, **kw):
    base = dict(window_s=20.0, checks=1000, attribution={},
                build_phases={}, peak=kernels.peaks("TPU v5 lite"), trace=tr)
    base.update(kw)
    return readers.Context(**base)


def test_window_and_busy(tr):
    assert list(tr.devices) == ["/device:TPU:0"]
    assert 0.5 < tr.window_s < 5.0
    busy = tr.busy_s()
    assert 0 < busy < tr.window_s
    # busy is a union: never more than the summed op time
    ops = tr.devices["/device:TPU:0"]
    assert busy <= sum(o.dur for o in ops) + 1e-9
    idle = readers.idle_percent(_ctx(tr))
    assert idle == pytest.approx(100 * (1 - busy / tr.window_s))
    assert 0 < idle < 100


def test_closure_query_runs_and_shapes(tr):
    runs = tr.module_runs("closure_query")
    assert runs and runs == tr.module_runs("jit_closure_query")
    shapes = readers.query_shapes(tr.module_ops("closure_query"))
    assert len(shapes) == len(runs)
    for b, f0, l in shapes:
        assert b >= 8 and b & (b - 1) == 0  # power-of-two batch buckets
        assert f0 >= 1 and l >= 1


def test_roofline_is_a_share(tr):
    pct = readers.query_roofline_percent(_ctx(tr))
    assert 0 < pct <= 100
    # by hand: least time of all executions over their device time
    peak = kernels.peaks("TPU v5 lite")
    least = sum(kernels.least_seconds(kernels.closure_query(*s), peak)
                for s in readers.query_shapes(tr.module_ops("closure_query")))
    device = sum(d for _, d in tr.module_runs("closure_query"))
    assert pct == pytest.approx(100 * least / device)
    # one execution by hand: B*F0*L gathered bytes plus index rows
    b, f0, l = readers.query_shapes(tr.module_ops("closure_query"))[0]
    cost = kernels.closure_query(b, f0, l)
    assert cost["bytes"] >= b * f0 * l


def test_no_trace_reads_nothing():
    ctx = _ctx(None)
    assert readers.idle_percent(ctx) is None
    assert readers.query_roofline_percent(ctx) is None


def test_breakdown(tr):
    top = tr.top_ops(10)
    assert 0 < len(top) <= 10
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    assert top[0][0].startswith("jit_closure_query/")
    gaps = tr.idle_gaps(10)
    assert 0 < len(gaps) <= 10
    assert all(isinstance(n, str) and s > 0 for n, s in gaps)
    assert sum(s for _, s in gaps) <= tr.window_s - tr.busy_s() + 1e-9


def test_kernel_counts():
    q = kernels.closure_query(1024, 8, 16)
    assert q["bytes"] == 1024 * 8 * 16 + 4 * 1024 * (8 + 16 + 2) + 2 * 1024
    assert q["bound"] == "memory"
    assert kernels.semiring_step(256, 11520)["bound"] == "compute_bf16"
    assert kernels.closure_insert_edge(11520)["bytes"] == 2 * 11520 * 11521
    with pytest.raises(KeyError):
        kernels.peaks("TPU v9 imaginary")
