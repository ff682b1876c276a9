"""Device time of latent attention — the operations traced under
``mla_attn`` (``ray_tpu/models/llama.py``: the new rows' write, the
absorbed queries, the streamed kernel over the visible rows, the values'
projection; the module's block's too; decode steps and prefills alike) — as
a share of the device's busy time in the traced window
(``chipbench/mtp_trace.py``); None where the job found none."""


def read(ctx):
    seconds = ctx["facts"].get("mla_attn_device_s")
    if not seconds:
        return None
    return 100.0 * seconds / ctx["busy_s"]
