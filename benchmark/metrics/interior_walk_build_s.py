"""Seconds of the set-up build's interior-walk phase (the engine's
``walk`` build phase: the interior CSR built and put on the device); none
where the program builds no walk state."""

from .. import readers


def read(ctx: readers.Context):
    return ctx.build_phases.get("walk")
