"""Median of the engine's histogram ``llm_engine_ttft_ms``:
``stream()`` pushed the request -> its first token was put on its queue.
The client's ``ttft_p50_ms`` minus this is the serve plane's share of
the time to first token.  Over the replica's whole life."""
from chipbench import span_reduce


def read(ctx):
    return span_reduce.value(ctx, "engine_ttft_ms_p50")
