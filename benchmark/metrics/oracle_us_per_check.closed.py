"""Host seconds answering rows on the host oracle (attribution stage
oracle: rows whose F0, L or walk frontier overflowed its width, or every
row where the program serves the snapshot from the oracle) per check
answered in a closed-loop window. 0 where the engine marked its device
stages and sent no row to the oracle; none where it marked neither."""

from .. import readers, stages

STAGES = ("oracle",)


def read(ctx: readers.Context):
    if "oracle" not in ctx.attribution and "dispatch" in ctx.attribution:
        return 0.0
    return stages.us_per_check(ctx, STAGES)
