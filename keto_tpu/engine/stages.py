"""Closure-engine stages as host spans on the device trace's clock.

``with engine_stage("gather"): ...`` is the host span ``keto.gather`` in
the JAX profiler's trace, beside the device ops it leads to, and its end
marks ``gather`` on the ambient attribution ledger. The stage names are
``telemetry.attribution.ENGINE_STAGES``, so a trace span and a ledger
stage are the same word.
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation

from ..telemetry.attribution import ENGINE_STAGES, ledger_mark

#: stage -> its span name in the profiler's trace
TRACE_NAMES = {s: "keto." + s for s in ENGINE_STAGES}


class engine_stage:
    """One engine stage: a ``keto.<stage>`` span when a profiler session
    is on (otherwise no span object at all, a sub-microsecond no-op),
    and a ledger mark at its end."""

    __slots__ = ("stage", "_span")

    def __init__(self, stage: str):
        self.stage = stage
        name = TRACE_NAMES[stage]
        self._span = TraceAnnotation(name) if TraceAnnotation.is_enabled() \
            else None

    def __enter__(self):
        if self._span is not None:
            self._span.__enter__()
        return self

    def __exit__(self, *exc):
        if self._span is not None:
            self._span.__exit__(*exc)
        ledger_mark(self.stage)
        return False
