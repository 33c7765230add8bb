"""Readers of the program's named stages: the three ledger readers on a
synthetic window; and a trace recorded on a TPU v5e with the program's
``keto.*`` spans (data/rbac1m_batch_closed_stages.xplane.pb), whose idle
gaps the trace reduction names by program stage."""

import os

import pytest

from benchmark import kernels, readers, trace
from benchmark.manifest import load_module

from conftest import ROOT

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
LEDGER_READERS = {
    "wire_arrival_us_per_check.closed": ("pool_wait", "deserialize",
                                         "parse"),
    "engine_host_us_per_check.closed": ("lookup", "gather", "h2d",
                                        "dispatch", "oracle", "finish"),
    "device_wait_us_per_check.closed": ("device_wait",),
}


def _ctx(tr=None, **kw):
    base = dict(window_s=50.0, checks=1000, attribution={},
                build_phases={}, peak=kernels.peaks("TPU v5 lite"), trace=tr)
    base.update(kw)
    return readers.Context(**base)


def _reader(name):
    return load_module(ROOT, "metrics", name)


#: every stage the program's ledger can hold after a served window
WINDOW = {"pool_wait": 0.5, "deserialize": 0.02, "parse": 0.03,
          "admission": 0.2, "encode": 0.1, "lookup": 0.3, "gather": 0.4,
          "h2d": 0.05, "dispatch": 0.06, "device_wait": 0.07, "oracle": 0.01,
          "finish": 0.02, "kernel": 0.001, "decode": 0.03, "serialize": 0.04,
          "reply": 0.01}


@pytest.mark.parametrize("name", sorted(LEDGER_READERS))
def test_ledger_readers_sum_their_stages(name):
    want = sum(WINDOW[s] for s in LEDGER_READERS[name]) / 1000 * 1e6
    assert _reader(name).read(_ctx(attribution=WINDOW)) == pytest.approx(
        want)


@pytest.mark.parametrize("name", sorted(LEDGER_READERS))
def test_ledger_readers_read_nothing_without_the_stages(name):
    # the ledger of a program that marks none of them
    older = {k: WINDOW[k] for k in ("admission", "queue", "encode",
                                    "launch", "kernel", "decode",
                                    "serialize", "reply") if k in WINDOW}
    assert _reader(name).read(_ctx(attribution=older)) is None
    assert _reader(name).read(_ctx(attribution={}, checks=0)) is None


@pytest.fixture(scope="module")
def staged():
    return trace.load(os.path.join(DATA,
                                   "rbac1m_batch_closed_stages.xplane.pb"))


def test_recorded_trace_has_the_program_spans(staged):
    names = {n for n, _, _ in staged.host if n.startswith("keto.")}
    assert {"keto.lookup", "keto.gather", "keto.h2d", "keto.dispatch",
            "keto.device_wait", "keto.finish"} <= names


def test_recorded_trace_names_gaps_by_program_stage(staged):
    gaps = staged.idle_gaps(10)
    assert sum(n.startswith("keto.") for n, _ in gaps) >= 5
