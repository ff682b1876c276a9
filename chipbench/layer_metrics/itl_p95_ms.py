"""Gap between consecutive tokens of one stream, 95th percentile over
all gaps of the window's requests.  As ``.saturated`` it is the batch
cell's record of what its clients see, as ``.mixed`` the mixed cell's
record of the number it was judged on before PR 52 (it reads one of
two modes there by chance: ``loadgen.interquantile_mean``); the chat
cell judges the same quantity end to end."""
from chipbench.loadgen import percentile


def read(ctx):
    return percentile(ctx["facts"]["itl_ms"], 95)
