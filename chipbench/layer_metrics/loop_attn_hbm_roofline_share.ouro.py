"""The looped decoder's attention as a share of its memory roofline: the K
and V bytes its calls HAD to move (``chipbench/loop_cost.py:attention_bytes``:
every key the steps' rows could see, the engine's count over the window's
steps, per step, and the new tokens' keys, in every (pass, layer)'s cache
layer) in the traced decode executions, over the device time under
``loop_attn`` in them, over the chip's peak memory bandwidth: the existing
kernel's reading in the regime this model puts it in, 192 calls a step with
every row inside its first two blocks of 128 keys, where a call's fixed cost
and not the stream sets the time.  None where the job found no such time."""
from chipbench import loop_cost


def read(ctx):
    f = ctx["facts"]
    steps, seconds = f.get("decode_steps_in_window"), f.get("loop_attn_decode_device_s")
    if (not steps or not seconds or f.get("kv_keys_visible_step") is None
            or not f.get("decode_executions_traced")
            or "total_ut_steps" not in f.get("model", {})):
        return None
    per_step = loop_cost.attention_bytes(
        f["kv_keys_visible_step"] / steps, f["max_slots"], f["model"])
    need = per_step * f["decode_executions_traced"] / ctx["peak"]["hbm_bytes_per_s"]
    return 100.0 * need / seconds
