"""The prefills' chunked Kimi delta rule as a share of its roofline: the
longer of the time its operations need at the chip's peak bf16 rate and the
time its bytes need at the peak memory bandwidth (``chipbench/kda_cost.py:
scan_flops`` / ``scan_bytes``, over the tokens of the traced prefills — each
execution's prompt length is its compiled version's, ``chipbench/
gdn_trace.py`` — times the KDA layers) over the device time under
``kda_scan``.  The rule runs in float32 at matmul precision ``highest``, six
passes of the unit the peak is stated for, takes an exponential a (pair,
channel) inside the sub-chunks, and XLA's body writes every intermediate of a
chunk to memory: all three show here as distance from 100%."""
from chipbench import kda_cost


def read(ctx):
    f = ctx["facts"]
    seconds, tokens = f.get("kda_scan_device_s"), f.get("prefill_tokens_traced")
    if not seconds or not tokens or "model" not in f:
        return None
    model = f["model"]
    token_layers = tokens * kda_cost.layers(model, kda_cost.LINEAR)
    need = max(
        kda_cost.scan_flops(model, token_layers, f["linear_chunk"])
        / ctx["peak"]["bf16_flops_per_s"],
        kda_cost.scan_bytes(model, token_layers) / ctx["peak"]["hbm_bytes_per_s"],
    )
    return 100.0 * need / seconds
