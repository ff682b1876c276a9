"""The WHOLE decode step of a window-and-full decoder over held experts as a
share of its memory roofline: the bytes a step HAD to move
(``chipbench/swa_cost.py:step_bytes``: every matrix outside the experts and the
output head once; of the HELD experts the matrices of those that owned at
least one row, as the program counted them; the K and V of every key the
step's rows could see, in the full layers up to each row's own last key and in
the window layers at most 128 a row; the keys written) over the median device
time of the decode program's executions in the trace, over the chip's peak
memory bandwidth.  The prefills' expert layer-steps (one a chunk of 2,048
tokens) are taken out of the count of experts touched at their most, so the
share is counted from below.  A share of bandwidth and not of FLOP/s: 64 token
rows do 64 FLOP a weight byte against the chip's 240
(``chipbench/swa_trace.py:layer_shares``)."""
from chipbench import swa_trace


def read(ctx):
    return swa_trace.share(ctx, "swa_step_hbm_roofline_share")
