"""The repo's entry points (``__graft_entry__.py``) run on the CPU: the
single-chip check step under jit, and the multi-chip dry run on a virtual
CPU mesh. Nothing else imports that file, so this keeps it from rotting
when the modules it reaches change."""

import json
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import __graft_entry__ as graft  # noqa: E402
from keto_tpu.engine.check import CheckEngine  # noqa: E402
from keto_tpu.relationtuple import RelationTuple  # noqa: E402


def test_entry_runs_under_jit():
    fn, args = graft.entry()
    assert all(isinstance(a, np.ndarray) for a in args)
    out = np.asarray(jax.jit(fn)(*args))
    assert out.shape == (8,)
    assert out[:4].tolist() == [True, False, True, False]
    assert not out[4:].any()  # padding rows are inert
    store, _ = graft._tiny_graph()
    oracle = CheckEngine(store, max_depth=5)
    reqs = [
        "app:doc1#view@alice", "app:doc1#view@bob",
        "app:doc2#view@bob", "app:a#r@carol",
    ]
    assert out[:4].tolist() == [
        oracle.subject_is_allowed(RelationTuple.from_string(r), 5)
        for r in reqs
    ]


@pytest.mark.parametrize("n_devices", [1, 2])
def test_dryrun_multichip(n_devices, capsys):
    graft.dryrun_multichip(n_devices)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    summary = json.loads(last)
    assert summary["multichip"] == n_devices and summary["ok"] is True
    assert (summary["skipped"] is None) == (n_devices >= 2)
