"""What ``llm.step.sync`` costs beyond the device's own work: the
median duration of the span (argmax dispatched, device-to-host copy,
wake-up) minus the median device duration of ``jit_decode_step_rowwise``
in the same trace.  Two durations, no difference of clocks."""
from chipbench import span_reduce


def read(ctx):
    return span_reduce.value(ctx, "step_sync_overhead_ms_p50")
