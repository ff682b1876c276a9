"""Share of the window's token gaps over 1.5 times their median: the
gaps that waited for more than a decode step, which in the mixed mix
are those behind a prefill.  It says how far the population's edge lies
from any percentile one might read (5.1-5.2% at PR 48, on the 95th),
and falls if prefills stop stalling the decoding rows.  A record: it
judges nothing."""
from chipbench.loadgen import STALLED_GAP_FACTOR, share_over_median


def read(ctx):
    return share_over_median(ctx["facts"]["itl_ms"], STALLED_GAP_FACTOR)
