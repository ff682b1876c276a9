"""Parameters, bytes and operations of a shortcut-connected double layer
(LongCat-Flash's), counted from the configuration file's numbers and from
what the program counted.  The benchmark's own copy, like ``flops.py``,
``moe_cost.py`` and ``mla_cost.py``.

A LAYER holds two latent attentions, two dense SwiGLUs of width
``ffn_hidden_size``, four block norms, one router over ``n_routed_experts
(published) + zero_expert_num`` outputs with its selection bias, and the
routed experts HELD here (``n_routed_experts`` of the file: the chip's
share), each a SwiGLU of width ``expert_ffn_hidden_size``.  The identity
experts have no parameters.  Two cache layers a layer, a 640-lane latent row
a token each.

THE WHOLE DECODE STEP has to read every matrix it touches at least once:
both attentions, both dense SwiGLUs and the router of every layer, the final
norm and the output head — whatever the routing — and of the held experts
those that own at least one row (``experts_touched``, as the program counted
them: never all that are held); the latent row of every position a row's
query may see, in every cache layer; and it writes one new row a slot and
cache layer.  The embedding's rows (64 rows of 12 KB) and the activations
are noise beside them.  A share of BANDWIDTH: 64 token rows do 64 FLOP a
weight byte against the chip's 240 (``step_flops`` over ``step_bytes``).
"""

from __future__ import annotations


def latent_row_values(cfg: dict) -> int:
    """Values of one cached row: latent + rotary key, in whole 128-lane
    tiles."""
    return -(-(cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) // 128) * 128


def cache_layers(cfg: dict) -> int:
    """An attention each: two a layer."""
    return 2 * cfg["num_layers"]


def cache_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    return cache_layers(cfg) * latent_row_values(cfg) * itemsize


def router_outputs(cfg: dict) -> int:
    return cfg["n_routed_experts_published"] + cfg["zero_expert_num"]


def attention_params(cfg: dict) -> int:
    """One sub-layer's latent attention: W_qa, q_a_norm, W_qb, W_kva,
    kv_a_norm, W_kb, W_vb, W_o, and the sub-layer's two block norms."""
    E, H = cfg["hidden_size"], cfg["num_attention_heads"]
    Q, C = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    Dn, Dr, Dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return (E * Q + Q + Q * H * (Dn + Dr) + E * (C + Dr) + C
            + C * H * Dn + C * H * Dv + H * Dv * E + 2 * E)


def dense_params(cfg: dict) -> int:
    """One sub-layer's dense SwiGLU: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["ffn_hidden_size"]


def router_params(cfg: dict) -> int:
    """The router's matrix and its selection bias."""
    return (cfg["hidden_size"] + 1) * router_outputs(cfg)


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["expert_ffn_hidden_size"]


def layer_fixed_params(cfg: dict) -> int:
    """What every step touches of one layer, whatever the routing."""
    return 2 * (attention_params(cfg) + dense_params(cfg)) + router_params(cfg)


def layer_params(cfg: dict) -> int:
    """One layer as this chip holds it."""
    return layer_fixed_params(cfg) + cfg["n_routed_experts"] * expert_params(cfg)


def fixed_params(cfg: dict) -> int:
    """Parameters every step touches: every layer outside its experts, the
    final norm and the output head.  Left out: the routed experts (by what
    was touched) and the embedding (a few rows a step)."""
    return (cfg["num_layers"] * layer_fixed_params(cfg)
            + cfg["hidden_size"] + cfg["vocab_size"] * cfg["hidden_size"])


def held_params(cfg: dict) -> int:
    """Everything this chip holds: ``fixed_params``, the held experts and
    the embedding."""
    return (fixed_params(cfg)
            + cfg["num_layers"] * cfg["n_routed_experts"] * expert_params(cfg)
            + cfg["vocab_size"] * cfg["hidden_size"])


def rows_written(cfg: dict, steps: float, slots: int) -> float:
    """New rows ``steps`` decode steps write: one a slot and cache layer."""
    return float(steps) * slots * cache_layers(cfg)


def step_bytes(cfg: dict, experts_touched: float, rows_visible: float,
               rows_written: float, itemsize: int = 2) -> float:
    """One decode step.  ``experts_touched``: held experts with at least one
    row, summed over the step's expert layers; ``rows_visible``: latent rows
    the step's rows could see, summed over (cache layer, row);
    ``rows_written``: as ``rows_written``, of one step."""
    return (
        itemsize * (fixed_params(cfg) + experts_touched * expert_params(cfg))
        + (float(rows_visible) + float(rows_written)) * latent_row_values(cfg) * itemsize
    )


def step_flops(cfg: dict, rows: int, held_pairs: float, rows_visible: float) -> float:
    """Multiply-adds x 2 of one decode step of ``rows`` token rows: every
    fixed matrix for every row, an expert's three matrices for each of the
    ``held_pairs`` (token, choice) pairs that fell on a held expert, and the
    absorbed attention over the ``rows_visible`` latent rows (scores over
    latent + rotary key, mix over the latent, a head)."""
    per_key = cfg["num_attention_heads"] * (
        2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
    return 2.0 * (rows * fixed_params(cfg) + held_pairs * expert_params(cfg)
                  + rows_visible * per_key)
