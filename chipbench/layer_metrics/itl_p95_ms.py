"""Gap between consecutive tokens of one stream, 95th percentile over
all gaps of the window's requests.  As ``.mixed`` it is the mixed cell's
record of the number it was judged on before PR 52 (it reads one of
two modes there by chance: ``loadgen.interquantile_mean``); the chat
cell judges the same quantity end to end.  The batch cell's ``.saturated``
entry went at PR 58: a closed loop's gap is its decode step plus, in a
traced run, the profiler's freeze (PERF.md section 3)."""
from chipbench.loadgen import percentile


def read(ctx):
    return percentile(ctx["facts"]["itl_ms"], 95)
