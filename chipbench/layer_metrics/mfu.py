"""Model FLOP/s utilisation of a steady step: the benchmark's own
operations per token (``chipbench/flops.py``; recomputation under remat
not counted) times the step's tokens, over the median step time, the
chips and the chip's bf16 peak from ``chipbench/peaks.json``.  From the
median step and not the window's rate because this is read in the
traced run, where starting and stopping the profiler stalls the loop
for seconds.  Utilisation of the whole step, input placement and
dispatch included: not a kernel's roofline share."""
from chipbench.loadgen import percentile


def read(ctx):
    f = ctx["facts"]
    tokens_per_s_per_chip = (
        f["tokens_per_step"] / (percentile(f["step_ms"], 50) / 1e3) / ctx["cell"]["chips"]
    )
    return 100.0 * f["flops_per_token"] * tokens_per_s_per_chip / ctx["peak"]["bf16_flops_per_s"]
