"""Median duration of ``llm.step.yield``: the ``asyncio.sleep(0)`` in
which every consumer coroutine takes its token and the streaming
transport sends it (its children in time are ``serve.stream_item``
spans of ``serve/replica.py``)."""
from chipbench import span_reduce


def read(ctx):
    return span_reduce.value(ctx, "step_serve_plane_ms_p50")
