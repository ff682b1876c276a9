"""Device time of a looped decoder's attention over its cache — the
operations traced under ``loop_attn`` (``ray_tpu/models/llama.py:
_kv_attention``: the new keys written into their (pass, layer)'s cache layer
and the kernel ``kv_decode`` of ``ops/kv_decode_attention.py`` over the rows'
live blocks, 192 calls a step at Ouro-2.6B's; projections and W_o are outside
it) — as a share of the decode program's device time in the traced window
(``chipbench/loop_trace.py``); None where the job found none."""


def read(ctx):
    f = ctx["facts"]
    seconds, decode = f.get("loop_attn_decode_device_s"), f.get("decode_device_s_traced")
    if not seconds or not decode:
        return None
    return 100.0 * seconds / decode
