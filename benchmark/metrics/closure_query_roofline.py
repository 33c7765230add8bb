"""Percent of its roofline that ``closure_query`` reached in the traced
window: the least time its calls need (bytes from each call's shapes
over the chip's bandwidth) over their device time."""

from .. import readers


def read(ctx: readers.Context):
    return readers.query_roofline_percent(ctx)
