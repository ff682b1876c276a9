"""Device time of the gated delta rule's one-token update — the operations
traced under ``gdn_step`` (``ray_tpu/models/llama.py``: every row's state
read, both contractions, the update written back; the projections, the
convolution and the gated norm are outside it) — as a share of the decode
program's device time in the traced window (``chipbench/gdn_trace.py``);
None where the job found none."""


def read(ctx):
    f = ctx["facts"]
    seconds, decode = f.get("gdn_step_decode_device_s"), f.get("decode_device_s_traced")
    if not seconds or not decode:
        return None
    return 100.0 * seconds / decode
