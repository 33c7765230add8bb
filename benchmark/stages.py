"""Readers of the program's named stages in the attribution ledger. A
program that marks none of a metric's stages reads nothing (None), never
0.
"""

from __future__ import annotations

from typing import Optional

from . import readers


def us_per_check(ctx: readers.Context, stages) -> Optional[float]:
    """Seconds of ``stages`` per check in microseconds, or None when the
    program marks none of them."""
    if not any(s in ctx.attribution for s in stages):
        return None
    return readers.us_per_check(ctx, stages)
