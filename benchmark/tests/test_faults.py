"""Whole runs on the CPU at tiny size, past the harness's look for a chip,
with the timed path broken underneath: ``correct`` comes out false for
each fault a cell can have, and true with nothing broken."""

import time

import pytest

from benchmark.harness import run_cell

from conftest import tiny_cell

SECONDS = 2.0


def _run(cell, seed=2**35 + 11):
    return run_cell(cell, seed, SECONDS, False, time.monotonic())


def _flip_every_tenth(monkeypatch):
    """A wrong answer where it is produced: every tenth BatchCheck
    response the read plane builds has each of its answers inverted."""
    from keto_tpu.api import services

    count = {"n": 0}
    orig = services.CheckServicer.BatchCheck

    def serve(self, request, context):
        resp = orig(self, request, context)
        count["n"] += 1
        if count["n"] % 10 == 0:
            flipped = [not a for a in resp.allowed]
            del resp.allowed[:]
            resp.allowed.extend(flipped)
        return resp

    monkeypatch.setattr(services.CheckServicer, "BatchCheck", serve)


CELLS = ["rbac1m.batch_closed", "github6m.batch_closed"]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    out = _run(tiny_cell(name))
    assert out["correct"], out["compared"]
    assert out["compared"]["unanswered"]["value"] == 0


@pytest.mark.parametrize("name", CELLS)
def test_altered_answer_is_not_correct(name, monkeypatch):
    _flip_every_tenth(monkeypatch)
    out = _run(tiny_cell(name))
    assert not out["correct"]
    assert out["compared"]["check_mismatches"]["value"] > 0


def test_half_the_batch_left_out_is_not_correct(monkeypatch):
    from keto_tpu.api import services

    orig = services.CheckServicer.BatchCheck

    def half(self, request, context):
        resp = orig(self, request, context)
        keep = list(resp.allowed)[: (len(resp.allowed) + 1) // 2]
        del resp.allowed[:]
        resp.allowed.extend(keep)
        return resp

    monkeypatch.setattr(services.CheckServicer, "BatchCheck", half)
    out = _run(tiny_cell("rbac1m.batch_closed"))
    assert not out["correct"]
    assert out["compared"]["short_answers"]["value"] > 0
