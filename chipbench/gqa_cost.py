"""Bytes and operations a block-diffusion step of a grouped-query-attention
expert model has to move, counted from the configuration's numbers and from
what the program counted, whatever implements them.  The benchmark's own
copy, like ``flops.py``, ``moe_cost.py`` and ``mla_cost.py``.

BLOCK ATTENTION.  For one (row, layer) of a step the attention has to read
the K and the V row of every position the row's block may see — the
committed keys and the block's own: the B queries of a block see the same
keys, so a row's cache passes through fast memory once however many queries
it has — and to write the block's B new K and V rows.  A key is what the
cache stores for a token in a layer: ``num_key_value_heads x head_dim``
values for K and as many for V (4 x 128 x 2 x 2 B = 2,048 B in bf16).
Queries, scores and the projections' weights are not counted here (the
weights are the step's, below), so whatever implements the attention reads
under 100% of this bound.  Memory-bound: a key's 2,048 B are read once for 4
queries x 32 heads x 2 x 2 x 128 multiply-adds, 64 FLOP a byte against the
chip's 240.

THE WHOLE STEP.  Every weight matrix the step touches is read at least
once: attention, norms and router of every layer, the final norm, the
output head, the matrices of the experts that own at least one row
(``experts_touched``, per expert layer-step, as the program counted them) —
never of all that are held; plus the attention's bytes above.  The
embedding's rows (128 rows of 4 KB) and the activations are noise beside
them.
"""

from __future__ import annotations


def key_values(cfg: dict) -> int:
    """Values the cache holds for one token in one layer: K and V."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"]


def attention_bytes(keys_visible: float, keys_written: float, cfg: dict,
                    itemsize: int = 2) -> float:
    """``keys_visible``: keys the steps' rows could see, summed over (layer,
    row) — once a row, not once a query; ``keys_written``: new tokens' keys,
    summed over (layer, row, query)."""
    return (float(keys_visible) + float(keys_written)) * key_values(cfg) * itemsize


def attention_flops(keys_visible: float, cfg: dict, queries: int) -> float:
    """Scores and mix of ``queries`` queries a row over the keys visible
    (summed over (layer, row)): 2 products of ``heads x head_dim``
    multiply-adds a (query, key)."""
    return 4.0 * float(keys_visible) * queries * cfg["num_attention_heads"] * cfg["head_dim"]


def keys_written(cfg: dict, steps: float, slots: int, queries: int) -> float:
    """New keys ``steps`` steps write: ``queries`` a slot in every layer."""
    return float(steps) * slots * queries * cfg["num_hidden_layers"]


def attention_params(cfg: dict) -> int:
    """One layer's attention: Wq, Wk, Wv, Wo, the two per-head norms' scales
    and the block's two norms."""
    E, D = cfg["hidden_size"], cfg["head_dim"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return E * H * D + 2 * E * KV * D + H * D * E + 2 * D + 2 * E


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def fixed_params(cfg: dict) -> int:
    """Parameters every step touches whatever the routing: all layers'
    attention and router, the final norm and the output head.  Left out: the
    routed experts (``expert_params`` each, by what was touched) and the
    embedding (a few rows a step)."""
    E = cfg["hidden_size"]
    router = E * cfg["num_experts_published"]
    return (cfg["num_hidden_layers"] * (attention_params(cfg) + router)
            + E + cfg["vocab_size"] * E)


def held_params(cfg: dict) -> int:
    """Everything this chip holds: ``fixed_params``, the routed experts held
    and the embedding."""
    return (fixed_params(cfg)
            + cfg["num_hidden_layers"] * cfg["num_experts"] * expert_params(cfg)
            + cfg["vocab_size"] * cfg["hidden_size"])


def cache_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    return cfg["num_hidden_layers"] * key_values(cfg) * itemsize


def step_bytes(cfg: dict, experts_touched: float, keys_visible: float,
               keys_written: float, itemsize: int = 2) -> float:
    """One step.  ``experts_touched``: experts with at least one row, summed
    over the step's expert layers; ``keys_visible`` / ``keys_written``: as
    ``attention_bytes``, of one step."""
    return (
        itemsize * (fixed_params(cfg) + experts_touched * expert_params(cfg))
        + attention_bytes(keys_visible, keys_written, cfg, itemsize)
    )
