"""Device time of Kimi delta attention's one-token update — the operations
traced under ``kda_step`` (``ray_tpu/models/llama.py:_kda_mixer``: the kernel
``kda_step`` of ``ops/gated_delta.py``, every row's state read once and
written once, and the operands made for it; projections, convolution and
gated norm are outside it) — as a share of the decode program's device time
in the traced window (``chipbench/kda_trace.py``); None where the job found
none."""


def read(ctx):
    f = ctx["facts"]
    seconds, decode = f.get("kda_step_decode_device_s"), f.get("decode_device_s_traced")
    if not seconds or not decode:
        return None
    return 100.0 * seconds / decode
