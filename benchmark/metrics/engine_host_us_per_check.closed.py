"""Host seconds in the closure engine outside the wait for the device
(attribution stages lookup, gather, h2d, dispatch, oracle, finish) per
check answered in a closed-loop window."""

from .. import readers, stages

STAGES = ("lookup", "gather", "h2d", "dispatch", "oracle", "finish")


def read(ctx: readers.Context):
    return stages.us_per_check(ctx, STAGES)
