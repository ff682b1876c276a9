"""Device time of the WINDOW layers' attention — the operations traced under
``swa_attn`` (``ray_tpu/models/llama.py:_kind_attention``: the new key into its
rolling slot, the ``kv_decode`` kernel over a row's one block of 128 slots with
its head's sink in a decode step, the ``kv_prefill`` kernel over the tiles
inside the band in a prefill; decode steps and prefills alike) — as a share of
the device's busy time in the traced window
(``chipbench/swa_trace.py:layer_shares``); None where the job found none."""
from chipbench import swa_trace


def read(ctx):
    return swa_trace.share(ctx, "swa_attn_time_share")
