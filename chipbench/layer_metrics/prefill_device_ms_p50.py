"""Median device duration of one execution of the engine's prefill
program (``jit_prefill_into_slot``, all prompt lengths together)."""
from chipbench import trace_reduce
from chipbench.loadgen import percentile


def read(ctx):
    ms = trace_reduce.module_durations_ms(ctx["planes"], "prefill_into_slot")
    return percentile(ms, 50) if ms else None
