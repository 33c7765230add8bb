"""Percent of the traced closed-loop window in which no operation ran
on the device."""

from .. import readers


def read(ctx: readers.Context):
    return readers.idle_percent(ctx)
