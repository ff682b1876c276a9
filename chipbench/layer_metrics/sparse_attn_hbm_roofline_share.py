"""The decode steps' sparse-attention path as a share of its memory
roofline: the bytes it HAD to move (``chipbench/dsa_cost.py``: the
indexer's key of every visible position, the selected latent rows, the
new rows written — from the keys visible and keys selected the program
counted over the window's decode steps, per step, times the decode
executions in the trace) over the device time of the operations under
``dsa_index`` / ``dsa_select`` / ``mla_attn`` inside those executions,
over the chip's peak memory bandwidth (``peaks.json``).  Under 100% is
what the program reads beyond that (the whole index-key slab whatever
``pos``, padded rows) and the time it does not stream (the top-k).  The
counters are the measured window's, the executions the traced three
seconds': the same traffic in both."""
from chipbench import dsa_cost


def read(ctx):
    f = ctx["facts"]
    seconds = f.get("sparse_attn_decode_device_s")
    steps = f.get("decode_steps_in_window")
    if not seconds or not steps or f.get("dsa_visible_step") is None:
        return None
    queries = steps * f["max_slots"] * f["dsa_layers"]
    per_step = dsa_cost.sparse_attention_bytes(
        f["dsa_visible_step"], f["dsa_selected_step"], queries,
        f["dsa_index_key_bytes"] // 2, f["dsa_latent_row_bytes"] // 2,
    ) / steps
    return (100.0 * per_step * f["decode_executions_traced"]
            / ctx["peak"]["hbm_bytes_per_s"] / seconds)
