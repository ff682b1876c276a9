"""Median host-clock time of one training step (batch placement,
dispatch, ``block_until_ready`` on the loss) over the window's steps."""
from chipbench.loadgen import percentile


def read(ctx):
    return percentile(ctx["facts"]["step_ms"], 50)
