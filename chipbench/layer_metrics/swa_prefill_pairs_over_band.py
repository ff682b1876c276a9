"""(query, key) pairs the window layers' prefills computed scores for over the
pairs inside the band (a query's own key and the 127 before it), both as the
program counted them over the measured window, a head
(``llama._kind_amounts``; ``ops/kv_prefill_attention.py:pairs_computed``).  The
flash kernel computes whole live tiles, two tiles of 128 keys a tile of 128
queries: 2.0 at these prompts; a body that scored the causal triangle would
read 48 at 12,288 tokens, a dense one 96.  None where no prefill ran in the
window or the program counts no pairs by kind
(``chipbench/swa_trace.py:layer_shares``)."""
from chipbench import swa_trace


def read(ctx):
    return swa_trace.share(ctx, "swa_prefill_pairs_over_band")
