"""Bytes a speculative decode step of a latent-attention model has to
move, counted from the configuration's numbers and from what the program
counted.  The benchmark's own copy, like ``flops.py``, ``moe_cost.py`` and
``dsa_cost.py``.

ATTENTION.  For one (row, layer) of a step the attention has to read the
latent row of every position the row's LAST query may see (its earlier
queries see prefixes of the same rows: a row's cache passes through fast
memory once however many queries it has) and to write the new tokens'
rows.  A row is what the cache stores for a token in a layer: the latent
and the rotary key in whole 128-lane tiles (``latent_row_values``: 512 + 64
-> 640 values, 1,280 B in bf16).  Queries, scores and the absorbed
projections' weights are not counted here (the weights are the step's,
below), so whatever implements the attention reads under 100% of this
bound.  Memory-bound: a latent row of 1,280 B is read once for 2 queries x
32 heads x 2 x 576 multiply-adds, 115 FLOP a byte against the chip's 240.

THE WHOLE STEP.  Every weight matrix the step touches is read at least
once: attention and router and shared expert of every layer, the dense
layer's feed-forward, the module's projection, the output head (once: the
module and the main model use the same matrix), the matrices of the experts
that own at least one row (``experts_touched``, per expert layer-step, as
the program counted them) — never of all that are held; plus the
attention's bytes above.  The embedding's rows (64 rows of 4 KB) and the
activations are noise beside them.
"""

from __future__ import annotations


def latent_row_values(cfg: dict) -> int:
    """Values of one cached row: latent + rotary key, in whole 128-lane
    tiles."""
    return -(-(cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) // 128) * 128


def attention_bytes(rows_visible: float, rows_written: float, row_values: int,
                    itemsize: int = 2) -> float:
    """``rows_visible``: latent rows the steps' rows could see, summed over
    (layer, row) — once a row, not once a query; ``rows_written``: new
    tokens' rows, summed over (layer, row, query)."""
    return (float(rows_visible) + float(rows_written)) * row_values * itemsize


def rows_written(cfg: dict, steps: float, slots: int, queries: int = 2) -> float:
    """New tokens' rows ``steps`` speculative steps write: ``queries`` a
    slot in every layer that keeps a cache (the module's block is one)."""
    layers = cfg["num_hidden_layers"] + cfg["num_nextn_predict_layers"]
    return float(steps) * slots * queries * layers


def attention_params(cfg: dict) -> int:
    """One layer's latent attention: W_qa, q_a_norm, W_qb, W_kva,
    kv_a_norm, W_kb, W_vb, W_o, and the block's two norms."""
    E, H = cfg["hidden_size"], cfg["num_attention_heads"]
    Q, C = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    Dn, Dr, Dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return (E * Q + Q + Q * H * (Dn + Dr) + E * (C + Dr) + C
            + C * H * Dn + C * H * Dv + H * Dv * E + 2 * E)


def expert_params(cfg: dict) -> int:
    """One routed (or shared) expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def fixed_params(cfg: dict) -> int:
    """Parameters every step touches whatever the routing: all layers'
    attention, the dense layers' feed-forward, every expert layer's router
    (weights and selection bias) and shared expert, the MTP module's
    norms and projection, the final norm and the output head.  Left out:
    the routed experts (``expert_params`` each, by what was touched) and
    the embedding (a few rows a step)."""
    E = cfg["hidden_size"]
    dense = cfg["first_k_dense_replace"]
    module = cfg["num_nextn_predict_layers"]
    expert_layers = cfg["num_hidden_layers"] - dense + module
    router = E * cfg["n_routed_experts_published"] + cfg["n_routed_experts_published"]
    return (
        (cfg["num_hidden_layers"] + module) * attention_params(cfg)
        + dense * 3 * E * cfg["intermediate_size"]
        + expert_layers * (router + cfg["n_shared_experts"] * expert_params(cfg))
        + module * (3 * E + 2 * E * E)
        + E + cfg["vocab_size"] * E
    )


def held_params(cfg: dict) -> int:
    """Everything this chip holds: ``fixed_params``, the routed experts
    held and the embedding."""
    expert_layers = (cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
                     + cfg["num_nextn_predict_layers"])
    return (fixed_params(cfg) + expert_layers * cfg["n_routed_experts"] * expert_params(cfg)
            + cfg["vocab_size"] * cfg["hidden_size"])


def step_bytes(cfg: dict, experts_touched: float, rows_visible: float,
               rows_written: float, itemsize: int = 2) -> float:
    """One step.  ``experts_touched``: experts with at least one row,
    summed over the step's expert layers (the module's is one);
    ``rows_visible`` / ``rows_written``: as ``attention_bytes``, of one
    step."""
    return (
        itemsize * (fixed_params(cfg) + experts_touched * expert_params(cfg))
        + attention_bytes(rows_visible, rows_written, latent_row_values(cfg), itemsize)
    )
