"""The decode steps' latent attention as a share of its memory roofline:
the bytes it HAD to move (``chipbench/mla_cost.py``: the latent row of every
position a row could see, once a row and layer however many queries the row
has, and the new rows written — from the rows visible the program counted
over the window's steps, per step, times the decode executions in the
trace) over the device time of the operations under ``mla_attn`` inside
those executions, over the chip's peak memory bandwidth (``peaks.json``).
Under 100% is what the kernel reads beyond that (the rest of the last
block) and the time it does not stream (the absorbed projections).  The
counters are the measured window's, the executions the traced three
seconds': the same traffic in both."""
from chipbench import mla_cost


def read(ctx):
    f = ctx["facts"]
    seconds = f.get("mla_attn_decode_device_s")
    steps = f.get("decode_steps_in_window")
    if not seconds or not steps or f.get("mla_keys_visible_step") is None:
        return None
    per_step = mla_cost.attention_bytes(
        f["mla_keys_visible_step"], mla_cost.rows_written(f["model"], steps, f["max_slots"]),
        mla_cost.latent_row_values(f["model"]), f["moe_itemsize"],
    ) / steps
    return (100.0 * per_step * f["decode_executions_traced"]
            / ctx["peak"]["hbm_bytes_per_s"] / seconds)
