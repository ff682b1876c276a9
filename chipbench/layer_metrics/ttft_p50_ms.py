"""Median time from when a request was due to its first token."""
from chipbench.loadgen import percentile


def read(ctx):
    return percentile(ctx["facts"]["ttft_ms"], 50)
