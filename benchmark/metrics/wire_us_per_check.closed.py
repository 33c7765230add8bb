"""Host seconds in the wire layer (attribution stages admission,
serialize, reply) per check answered in a closed-loop window."""

from .. import readers


def read(ctx: readers.Context):
    return readers.us_per_check(ctx, readers.WIRE_STAGES)
