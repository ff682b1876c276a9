"""Share of the device's idle time (gaps over 50 us on the first
device's op line) that lies in gaps owned by a span of the engine's
loop: the leaf span that covers most of the gap, after the spans were
put on the trace's clock (``span_reduce.align``).  ``llm.idle`` counts:
waiting for a request is a cause."""
from chipbench import span_reduce


def read(ctx):
    return span_reduce.value(ctx, "idle_gap_attributed_share")
