"""Host seconds of a served call before its check record opens
(attribution stages pool_wait, deserialize, parse: gRPC's hand-off to a
handler thread, waiting for it or the interpreter, message receipt and
parse, servicer work) per check answered in a closed-loop window."""

from .. import readers, stages

STAGES = ("pool_wait", "deserialize", "parse")


def read(ctx: readers.Context):
    return stages.us_per_check(ctx, STAGES)
