"""The whole diffusion step as a share of its memory roofline: the bytes a
step HAD to move (``chipbench/gqa_cost.py:step_bytes``: every weight matrix
it touches once — of the routed experts those that owned a row, as the
program counted them — and the K/V the live rows' blocks could see) over the
median device time of the decode program's executions in the trace, over the
chip's peak memory bandwidth (``peaks.json``).  The cell's share of the whole
step; a share of bandwidth and not of FLOP/s because 128 token rows do 128
FLOP a weight byte against the chip's 240."""
from chipbench import gqa_cost, trace_reduce
from chipbench.loadgen import percentile


def read(ctx):
    f = ctx["facts"]
    steps = f.get("decode_steps_in_window")
    if not steps or f.get("kv_keys_visible_step") is None:
        return None
    ms = trace_reduce.module_durations_ms(ctx["planes"], "decode_step_rowwise")
    if not ms:
        return None
    model = f["model"]
    per_step = gqa_cost.step_bytes(
        model, f["moe_experts_touched_mean"] * f["moe_layer_steps"] / steps,
        f["kv_keys_visible_step"] / steps,
        gqa_cost.keys_written(model, 1, f["max_slots"], f["diffusion_block"]),
        f["moe_itemsize"],
    )
    return 100.0 * per_step / ctx["peak"]["hbm_bytes_per_s"] / (percentile(ms, 50) / 1e3)
