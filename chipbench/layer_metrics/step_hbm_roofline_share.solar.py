"""The WHOLE decode step of a Kimi-delta-and-gated-attention decoder over
held experts as a share of its memory roofline: the bytes a step HAD to move
(``chipbench/kda_cost.py:step_bytes``: every matrix outside the routed
experts and the output head once; of the HELD experts the matrices of those
that owned at least one row, as the program counted them a layer-step; the K
and V of every key the step's rows could see in the full layers, the engine's
count over the window's steps, per step; every row's recurrent state and
convolution tail read and written) over the median device time of the decode
program's executions in the trace, over the chip's peak memory bandwidth.
The prefills' expert layer-steps (one a prompt and layer, every held expert
touched) are taken out of the count of experts touched at their most, so the
share is counted from below.  A share of bandwidth and not of FLOP/s: 64 token
rows do 64 FLOP a weight byte against the chip's 240."""
from chipbench import kda_cost, trace_reduce
from chipbench.loadgen import percentile


def read(ctx):
    f = ctx["facts"]
    steps = f.get("decode_steps_in_window")
    if (not steps or f.get("kv_keys_visible_step") is None or "model" not in f
            or "linear_attn_config" not in f["model"]):
        return None
    ms = trace_reduce.module_durations_ms(ctx["planes"], "decode_step_rowwise")
    if not ms:
        return None
    # the decode steps' own layer-steps: what the prefills' chunks touched
    # (every held expert, a chunk) is taken out of the mean
    layer_steps = f["moe_layer_steps"]
    decode_layer_steps = steps * f["model"]["num_hidden_layers"]
    prefill_layer_steps = max(0, layer_steps - decode_layer_steps)
    touched_total = f["moe_experts_touched_mean"] * layer_steps
    touched = max(0.0, touched_total - prefill_layer_steps * f["model"]["n_routed_experts"]
                  ) / decode_layer_steps
    per_step = kda_cost.step_bytes(
        f["model"], touched, f["kv_keys_visible_step"] / steps, f["max_slots"])
    return 100.0 * per_step / ctx["peak"]["hbm_bytes_per_s"] / (percentile(ms, 50) / 1e3)
