"""The grouped-matmul kernels' share of their memory roofline: the
bytes they HAD to move (``chipbench/moe_cost.py``: rows in, the matrices
of the experts touched, rows out — from the window's mean rows and mean
experts touched per layer-step as the program counted them, times the
kernel executions in the trace) over their device time, over the chip's
peak memory bandwidth (``peaks.json``).  Memory-bound: a decode step's
256 rows do 0.6 FLOP a byte.  Under 100% is what the kernel re-reads
(an expert's matrix once per row tile that holds one of its rows) and
the time it does not stream.  The counters are the measured window's,
the executions the traced three seconds': the mix of decode steps and
prefills is the same traffic in both."""
from chipbench import moe_cost, trace_reduce
from chipbench.layer_metrics.gmm_time_share import is_gmm


def read(ctx):
    f = ctx["facts"]
    if f.get("moe_experts_touched_mean") is None:
        return None
    seconds = trace_reduce.op_seconds(ctx["planes"], is_gmm)
    lo, hi = trace_reduce.window_ns(ctx["planes"])
    ops = trace_reduce.line(ctx["planes"][0], trace_reduce.OPS_LINE)["events"]
    calls = sum(1 for name, s, d, _st in ops if is_gmm(name) and lo <= s and s + d <= hi)
    if seconds <= 0 or not calls:
        return None
    per_call = moe_cost.mean_gmm_call_bytes(
        f["moe_rows_per_layer_step_mean"], f["moe_embed"], f["moe_expert_dim"],
        f["moe_experts_touched_mean"], f["moe_itemsize"],
    )
    return 100.0 * per_call * calls / ctx["peak"]["hbm_bytes_per_s"] / seconds
