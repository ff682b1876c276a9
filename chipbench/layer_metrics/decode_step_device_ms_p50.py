"""Median device duration of one execution of the engine's decode
program (``jit_decode_step_rowwise`` on the trace's module line)."""
from chipbench import trace_reduce
from chipbench.loadgen import percentile


def read(ctx):
    ms = trace_reduce.module_durations_ms(ctx["planes"], "decode_step_rowwise")
    return percentile(ms, 50) if ms else None
