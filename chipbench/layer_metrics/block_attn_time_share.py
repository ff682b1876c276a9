"""Device time of the block attention — the operations traced under
``block_attn`` (``ray_tpu/models/llama.py``: the block's K/V rows written,
the layer's slabs read, scores, block mask, softmax and mix of the grouped
attention; decode steps and prefills alike) — as a share of the device's busy time in the traced window
(``chipbench/diffusion_trace.py``); None where the job found none."""


def read(ctx):
    seconds = ctx["facts"].get("block_attn_device_s")
    if not seconds:
        return None
    return 100.0 * seconds / ctx["busy_s"]
