"""Gap between consecutive tokens of one stream, 95th percentile over
all gaps of the window's requests.  As ``.saturated`` it is the batch
cell's record of what its clients see; the chat cell judges the same
quantity end to end."""
from chipbench.loadgen import percentile


def read(ctx):
    return percentile(ctx["facts"]["itl_ms"], 95)
