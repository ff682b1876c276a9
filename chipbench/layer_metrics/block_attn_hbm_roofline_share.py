"""The diffusion steps' block attention as a share of its memory roofline:
the bytes it HAD to move (``chipbench/gqa_cost.py``: K and V of every key a
live row's block could see, once a row and layer however many queries the
block has, and the block's new rows written — from the keys visible the
engine counted over the window's steps, per step, times the decode
executions in the trace) over the device time of the operations under
``block_attn`` inside those executions, over the chip's peak memory bandwidth
(``peaks.json``).  Under 100% is what the attention reads beyond that (the
XLA attention is not length-aware: it reads each row's slab to ``max_len``)
and the time it does not stream.  The counters are the measured window's,
the executions the traced seconds': the same traffic in both."""
from chipbench import gqa_cost


def read(ctx):
    f = ctx["facts"]
    seconds = f.get("block_attn_decode_device_s")
    steps = f.get("decode_steps_in_window")
    if not seconds or not steps or f.get("kv_keys_visible_step") is None:
        return None
    model = f["model"]
    per_step = gqa_cost.attention_bytes(
        f["kv_keys_visible_step"],
        gqa_cost.keys_written(model, steps, f["max_slots"], f["diffusion_block"]),
        model, f["moe_itemsize"],
    ) / steps
    return (100.0 * per_step * f["decode_executions_traced"]
            / ctx["peak"]["hbm_bytes_per_s"] / seconds)

