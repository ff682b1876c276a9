"""Time from when a request was due (open loop) or sent (closed loop)
to its first token, 95th percentile.  Recorded, never judged: above the
knee (``.saturated``) the queue grows all window, and below it the
tail of 60 requests reads any stall of the shared host (PERF.md
section 6, PR 23)."""
from chipbench.loadgen import percentile


def read(ctx):
    return percentile(ctx["facts"]["ttft_ms"], 95)
