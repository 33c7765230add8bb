"""Host seconds blocked on reading back ``closure_query``'s answers
(attribution stage device_wait) per check answered in a closed-loop
window."""

from .. import readers, stages

STAGES = ("device_wait",)


def read(ctx: readers.Context):
    return stages.us_per_check(ctx, STAGES)
