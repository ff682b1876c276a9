"""Device time of the expert layer's grouped-matmul kernels (the
Pallas custom calls ``gmm.N`` of ``ray_tpu/ops/grouped_matmul.py``:
gate, up and down of every layer, decode steps and prefills alike) as a
share of the device's busy time in the traced window.  None where the
trace holds no such kernel (a program without the expert layer)."""
from chipbench import trace_reduce


def is_gmm(name: str) -> bool:
    return trace_reduce.is_pallas(name) and name.startswith("gmm")


def read(ctx):
    seconds = trace_reduce.op_seconds(ctx["planes"], is_gmm)
    if seconds <= 0:
        return None
    return 100.0 * seconds / ctx["busy_s"]
