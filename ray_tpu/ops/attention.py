"""Baseline attention kernels (XLA einsum path).

The dense causal kernel lives here — not in the model zoo — so both
models and the ring/flash variants share one implementation.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def dense_attention(q, k, v, *, window: int = 0):
    """Causal attention, f32 softmax.  q,k,v: (B, S, H, D).

    ``window`` > 0 limits each query to the last ``window`` keys
    (Mistral-style sliding-window attention: position t attends to
    (t-window, t]; memory-for-range tradeoff long-context models use).
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) / math.sqrt(D)
    q_pos = jnp.arange(Sq)[:, None]
    k_pos = jnp.arange(Sk)[None, :]
    mask = q_pos >= k_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)
