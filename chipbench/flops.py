"""Operations per token, counted from shapes.  The benchmark's own
copy: ``gpt2.flops_per_token`` in the program may be edited by a later
PR, this may not."""

from __future__ import annotations


def gpt2_params(cfg: dict) -> int:
    """Parameters that do arithmetic for a token: everything but the
    position table (the token table counts: the output head is tied to
    it and its matmul is real work)."""
    e, layers, v = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    per_layer = 12 * e * e + 13 * e  # qkv, proj, fc, out + biases + 2 LayerNorms
    return v * e + layers * per_layer + 2 * e


def gpt2_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward: 6 per parameter (2 forward, 4 backward) plus
    attention's 12 * L * E * S (QK^T and PV, forward and backward, not
    halved for causality: the nanoGPT / PaLM convention).  Operations
    recomputed under remat are NOT counted: they are the price of
    memory, not model work."""
    return 6.0 * gpt2_params(cfg) + 12.0 * cfg["n_layer"] * cfg["n_embd"] * seq_len
