"""The whole decode step of a model of shortcut-connected double layers as
a share of its memory roofline: the bytes a step HAD to move
(``chipbench/scmoe_cost.py:step_bytes``: every matrix outside the experts —
both latent attentions, both dense SwiGLUs and the router of every layer —
and the output head once; of the HELD experts the matrices of those that
owned at least one row, as the program counted them; the latent rows the
step's rows could see over all cache layers, two a layer; the rows written)
over the median device time of the decode program's executions in the
trace, over the chip's peak memory bandwidth (``peaks.json``).  The
program's count of experts touched is over decode steps and prefills
together: a prefill's layer-steps are taken out at their most (every held
expert touched), so the step's share is counted from below.  A share of
bandwidth and not of FLOP/s because 64 token rows do 64 FLOP a weight byte
against the chip's 240.  None where the program counted no identity
expert's choice or no latent row (a program without the block)."""
from chipbench import scmoe_cost, trace_reduce
from chipbench.loadgen import percentile


def read(ctx):
    f = ctx["facts"]
    steps = f.get("decode_steps_in_window")
    if (not steps or f.get("mla_keys_visible_step") is None
            or f.get("moe_zero_choices") is None or "model" not in f):
        return None
    ms = trace_reduce.module_durations_ms(ctx["planes"], "decode_step_rowwise")
    if not ms:
        return None
    model = f["model"]
    prefill_layer_steps = f["prefills_in_window"] * model["num_layers"]
    touched = max(0.0, f["moe_experts_touched_mean"] * f["moe_layer_steps"]
                  - prefill_layer_steps * model["n_routed_experts"])
    per_step = scmoe_cost.step_bytes(
        model, touched / steps, f["mla_keys_visible_step"] / steps,
        scmoe_cost.rows_written(model, 1, f["max_slots"]), f["moe_itemsize"],
    )
    return 100.0 * per_step / ctx["peak"]["hbm_bytes_per_s"] / (percentile(ms, 50) / 1e3)
