"""Median host time from the start of an engine iteration's
``llm.step.build`` to the end of its ``llm.step.dispatch`` (the two
spans' durations added): token and position arrays, the thread hop, two
host-to-device copies and the launch.  Over the ``llm.step`` spans of
the traced window that admitted no request."""
from chipbench import span_reduce


def read(ctx):
    return span_reduce.value(ctx, "step_dispatch_ms_p50")
