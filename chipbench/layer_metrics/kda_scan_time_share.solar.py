"""Device time of the prefills' chunked Kimi delta rule — the operations
traced under ``kda_scan`` (``ops/gated_delta.py:_scan_channels``: the
sub-chunks' decayed Gram matrices, the triangular solve, the loop over chunks
that carries the state) — as a share of the prefill program's device time in
the traced window (``chipbench/kda_trace.py``); None where the job found
none."""


def read(ctx):
    f = ctx["facts"]
    seconds, prefill = f.get("kda_scan_device_s"), f.get("prefill_device_s_traced")
    if not seconds or not prefill:
        return None
    return 100.0 * seconds / prefill
