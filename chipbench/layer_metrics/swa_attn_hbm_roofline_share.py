"""The decode steps' WINDOW layers' attention as a share of its memory
roofline: ``full_attn_hbm_roofline_share``'s reckoning for the other kind —
the K and V of the keys a row could see in each window layer, at most its 128
rolling slots (the program's count), and the new key a slot and layer, over
the device time under ``swa_attn`` inside the decode executions, over the
chip's peak memory bandwidth.  Each row is ONE block of 128 slots, 3.3 MB a
layer for 64 rows: what is under 100% here is mostly a call's fixed cost (the
first copies, 64 grid steps), not bytes read in vain
(``chipbench/swa_trace.py:layer_shares``)."""
from chipbench import swa_trace


def read(ctx):
    return swa_trace.share(ctx, "swa_attn_hbm_roofline_share")
