"""Median of the engine's histogram ``llm_queue_wait_ms`` for admitted
requests: ``stream()`` pushed the request -> the slot admitter popped
it.  Over the replica's whole life, warm-up and drain included."""
from chipbench import span_reduce


def read(ctx):
    return span_reduce.value(ctx, "engine_queue_wait_ms_p50")
