"""Bytes a decode step of a LOOPED decoder (Ouro's LoopLM: the whole stack run
``total_ut_steps`` times a token with shared weights, K and V of every (pass,
layer)) has to move, counted from the configuration's numbers and from what
the program counted, whatever implements them.  The benchmark's own copy, like
``flops.py`` and ``gqa_cost.py``.

THE WEIGHTS.  A pass needs the pass before it whole, and 48 blocks of 103 MB
do not stay in fast memory between two passes: every pass streams every
block's matrices again.  The output head is read once, after the last pass.
The final norm and the exit gate (8 KB a pass) and the embedding's rows (16
rows of 4 KB a step) are left out, so the bound is counted from below.

THE K/V.  ``gqa_cost.attention_bytes``' count with the CACHE layers for
layers: for one (cache layer, row) of a step the attention has to read the K
and the V row of every position the row can see and to write the new token's;
a key is ``num_key_value_heads x head_dim`` values for K and as many for V
(16 x 128 x 2 x 2 B = 8,192 B in bf16), and there are ``total_ut_steps x
num_hidden_layers`` cache layers (192): 1,572,864 B a token.  Queries, scores
and the projections' weights are not counted here.  Memory-bound: a key's
8,192 B are read once for 16 heads x 2 x 2 x 128 multiply-adds, one FLOP a
byte against the chip's 240.
"""

from __future__ import annotations

from chipbench import gqa_cost


def passes(cfg: dict) -> int:
    return int(cfg["total_ut_steps"])


def cache_layers(cfg: dict) -> int:
    """K/V cache layers: one a (pass, layer)."""
    return passes(cfg) * cfg["num_hidden_layers"]


def block_params(cfg: dict) -> int:
    """One block: Wq, Wk, Wv, Wo, the SwiGLU's three, and FOUR norms."""
    E, D = cfg["hidden_size"], cfg["head_dim"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * E * H * D + 2 * E * KV * D + 3 * E * cfg["intermediate_size"] + 4 * E


def blocks_params(cfg: dict) -> int:
    return cfg["num_hidden_layers"] * block_params(cfg)


def head_params(cfg: dict) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def held_params(cfg: dict) -> int:
    """Everything the chip holds: the blocks ONCE, the embedding and the
    untied head, the final norm, the gate and its bias."""
    E = cfg["hidden_size"]
    return blocks_params(cfg) + 2 * head_params(cfg) + E + E + 1


def cache_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    return cache_layers(cfg) * gqa_cost.key_values(cfg) * itemsize


def weight_bytes(cfg: dict, itemsize: int = 2) -> int:
    """What a step streams of the weights: the blocks a pass, the head once."""
    return itemsize * (passes(cfg) * blocks_params(cfg) + head_params(cfg))


def attention_bytes(keys_visible: float, slots: int, cfg: dict, itemsize: int = 2) -> float:
    """One step's K/V: ``keys_visible`` keys its rows could see, summed over
    (cache layer, row) as the engine counts them, and the ``slots`` new
    tokens' keys in every cache layer."""
    return gqa_cost.attention_bytes(
        keys_visible, float(slots) * cache_layers(cfg), cfg, itemsize)


def step_bytes(cfg: dict, keys_visible: float, slots: int, itemsize: int = 2) -> float:
    """One decode step: ``weight_bytes`` and ``attention_bytes``."""
    return weight_bytes(cfg, itemsize) + attention_bytes(keys_visible, slots, cfg, itemsize)
