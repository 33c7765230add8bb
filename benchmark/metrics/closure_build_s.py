"""Seconds of the set-up closure build (the engine's own phase total)."""

from .. import readers


def read(ctx: readers.Context):
    return ctx.build_phases.get("total") or None
