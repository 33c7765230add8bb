"""The batcher's dispatch choice and the check_batch bulk result cache:
caller-assembled batches probe the result cache in bulk and dispatch only
the missing rows; an engine without the split encode/launch/decode API
keeps the serial dispatcher whatever ``pipeline_depth`` asks for."""

import pytest

from keto_tpu.engine import CheckEngine
from keto_tpu.engine.batcher import CheckBatcher
from keto_tpu.engine.cache import CheckResultCache
from keto_tpu.engine.closure import ClosureCheckEngine
from keto_tpu.graph import SnapshotManager
from keto_tpu.relationtuple import RelationTuple
from keto_tpu.store import InMemoryTupleStore


def t(s: str) -> RelationTuple:
    return RelationTuple.from_string(s)


@pytest.fixture
def store():
    s = InMemoryTupleStore()
    # a small layered graph: direct grants, one- and two-level nesting,
    # a cycle, and enough distinct objects that concurrent batches span
    # multiple pow2 buckets
    tuples = []
    for i in range(24):
        tuples.append(t(f"n:doc{i}#view@(n:group{i % 6}#member)"))
    for g in range(6):
        tuples.append(t(f"n:group{g}#member@(n:team{g % 3}#member)"))
        tuples.append(t(f"n:group{g}#member@direct{g}"))
    for m in range(3):
        tuples.append(t(f"n:team{m}#member@alice{m}"))
    tuples.append(t("n:cyc#r@(n:cyc2#r)"))
    tuples.append(t("n:cyc2#r@(n:cyc#r)"))
    s.write_relation_tuples(*tuples)
    return s


def _workload():
    reqs = []
    for i in range(24):
        for who in ("alice0", "alice1", "alice2", "direct3", "nobody"):
            reqs.append(t(f"n:doc{i}#view@{who}"))
    reqs.append(t("n:cyc#r@alice0"))
    reqs.append(t("n:cyc#r@(n:cyc2#r)"))
    return reqs


class TestCheckBatchBulkCache:
    def test_check_batch_uses_result_cache(self, store):
        mgr = SnapshotManager(store)
        engine = ClosureCheckEngine(mgr, max_depth=5)
        calls = []
        real = engine.batch_check

        def counting(requests, max_depth=0, depths=None):
            calls.append(len(requests))
            return real(requests, max_depth, depths=depths)

        engine.batch_check = counting
        b = CheckBatcher(
            engine,
            window_s=0,
            cache=CheckResultCache(1024),
            version_fn=lambda: store.version,
        )
        try:
            oracle = CheckEngine(store, max_depth=5)
            reqs = _workload()[:20]
            want = [oracle.subject_is_allowed(r) for r in reqs]
            cold = b.check_batch(reqs)
            n_cold = sum(calls)
            hot = b.check_batch(reqs)
            assert cold == want and hot == want
            # the hot batch was answered from the bulk cache: no new
            # engine dispatches
            assert sum(calls) == n_cold
            # a partial miss dispatches ONLY the missing rows
            mixed = reqs[:10] + [t("n:docnew#view@alice0")]
            b.check_batch(mixed)
            assert sum(calls) == n_cold + 1 and calls[-1] == 1
        finally:
            b.close()

    def test_serial_fallback_for_engines_without_split_api(self, store):
        # host oracle has no encode/launch/decode: pipeline_depth is
        # silently ignored and the serial dispatcher serves correctly
        b = CheckBatcher(
            CheckEngine(store, max_depth=5), window_s=0, pipeline_depth=2
        )
        try:
            assert b.pipelined is False
            assert b.check(t("n:doc0#view@direct0"), timeout=30) is True
        finally:
            b.close()
