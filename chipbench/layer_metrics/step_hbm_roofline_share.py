"""The whole decode step of a hybrid model as a share of its memory
roofline: the bytes a step HAD to move (``chipbench/gdn_cost.py:step_bytes``:
every layer's weights and the output head once, the K/V the live rows could
see in the full layers — the engine's count over the window's steps, per
step — and every row's recurrent state and convolution tail read and
written) over the median device time of the decode program's executions in
the trace, over the chip's peak memory bandwidth (``peaks.json``).  A share
of bandwidth and not of FLOP/s because 32 token rows do 32 FLOP a weight
byte against the chip's 240."""
from chipbench import gdn_cost, trace_reduce
from chipbench.loadgen import percentile


def read(ctx):
    f = ctx["facts"]
    steps = f.get("decode_steps_in_window")
    if not steps or f.get("kv_keys_visible_step") is None or "model" not in f:
        return None
    ms = trace_reduce.module_durations_ms(ctx["planes"], "decode_step_rowwise")
    if not ms:
        return None
    per_step = gdn_cost.step_bytes(
        f["model"], f["kv_keys_visible_step"] / steps, f["max_slots"])
    return 100.0 * per_step / ctx["peak"]["hbm_bytes_per_s"] / (percentile(ms, 50) / 1e3)
