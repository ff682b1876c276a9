"""Device time of the Pallas flash-attention kernels (forward, dq,
dkv: the step's only custom calls) as a share of the device's busy
time in the traced window."""
from chipbench import trace_reduce


def read(ctx):
    seconds = trace_reduce.op_seconds(ctx["planes"], trace_reduce.is_pallas)
    if seconds <= 0:
        return None
    return 100.0 * seconds / ctx["busy_s"]
