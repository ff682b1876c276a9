"""The decode steps' FULL-attention layers as a share of their memory
roofline: the bytes they HAD to move (``chipbench/swa_cost.py:
attention_bytes``: the K and V of every key a row could see, in each full
layer — the program's count over the measured window's steps, per step, times
the decode executions in the trace — and the new key a slot and layer) over
the device time under ``full_attn`` inside those executions, over the chip's
peak memory bandwidth (``peaks.json``).  Under 100% is what the kernel reads
beyond that (the rest of each row's last block of 128 keys) and the time it
does not stream (the write, a call's first copies).  Rows at 2k and at 13k
keys stand side by side: a body that read ``max_len`` keys for every row would
read 1.7 times what is visible (``chipbench/swa_trace.py:layer_shares``)."""
from chipbench import swa_trace


def read(ctx):
    return swa_trace.share(ctx, "full_attn_hbm_roofline_share")
