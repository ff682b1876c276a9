"""Median duration of ``llm.step.deliver``: one ``queue.put`` per
decoding row and the engine's bookkeeping."""
from chipbench import span_reduce


def read(ctx):
    return span_reduce.value(ctx, "step_deliver_ms_p50")
