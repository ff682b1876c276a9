"""Share of decode-step rows that produced a token a client received:
tokens after the first of each stream that arrived while the trace
was on (client clock) over decode executions in the trace times
``max_slots``.  The two intervals differ by the trace's start and stop
latency, a few percent of a window of seconds."""
from chipbench import trace_reduce


def read(ctx):
    f = ctx["facts"]
    steps = len(trace_reduce.module_durations_ms(ctx["planes"], "decode_step_rowwise"))
    if not steps:
        return None
    per_s_trace = steps / ctx["window_s"]
    per_s_client = f["tokens_while_traced"] / f["traced_client_s"]
    return 100.0 * per_s_client / (per_s_trace * f["max_slots"])
