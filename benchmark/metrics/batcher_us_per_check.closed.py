"""Host seconds in the batcher (attribution stages queue, encode,
launch, decode) per check answered in a closed-loop window."""

from .. import readers


def read(ctx: readers.Context):
    return readers.us_per_check(ctx, readers.BATCHER_STAGES)
