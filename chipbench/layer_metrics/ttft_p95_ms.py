"""Time from when a request was due (open loop) or sent (closed loop)
to its first token, 95th percentile.  Recorded, never judged: below the
knee the tail of 60 requests reads any stall of the shared host (PERF.md
section 6, PR 23); above it the queue grows all window, which is why the
batch cell's ``.saturated`` entry went at PR 58 (PERF.md section 3)."""
from chipbench.loadgen import percentile


def read(ctx):
    return percentile(ctx["facts"]["ttft_ms"], 95)
