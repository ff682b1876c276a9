"""Device time of the FULL-attention layers' attention — the operations traced
under ``full_attn`` (``ray_tpu/models/llama.py:_kind_attention``: the new keys'
write, the ``kv_decode`` kernel over each row's keys up to its own last one in
a decode step, the ``kv_prefill`` kernel over the prompt's causal tiles in a
prefill; decode steps and prefills alike) — as a share of the device's busy
time in the traced window (``chipbench/swa_trace.py:layer_shares``); None
where the job found none (a program without attention kinds)."""
from chipbench import swa_trace


def read(ctx):
    return swa_trace.share(ctx, "full_attn_time_share")
