"""Llama-shaped decoder forward (InternLM2 runs through it), plainly:
float32 ``jax.numpy``, no cache, no kernel, no batching, matmul
precision ``highest``.

Follows the InternLM2 technical report (Cai et al. 2024) and its
``modeling_internlm2.py``: pre-RMSNorm blocks (eps from the config),
rotary position embedding in the half-split ("rotate_half") form with
base ``rope_theta`` applied to queries and keys, grouped-query
attention (each key/value head serves ``num_heads // num_kv_heads``
query heads), causal softmax scaled by 1/sqrt(head size), SwiGLU MLP
``w_down(silu(w_gate x) * w_up x)``, final RMSNorm, untied output
head, no biases.  InternLM2 stores Q, K and V packed in one ``wqkv``
matrix; that is a storage layout, and the separate ``wq``/``wk``/``wv``
of the program's tree hold the same numbers.

It reads the program's parameter tree (``ray_tpu/models/llama.py``:
blocks stacked on a leading layer axis, ``wq`` (E, H, D), ``wk``/``wv``
(E, KV, D), ``wo`` (H, D, E)) one layer at a time, casting each layer
to float32 as it goes, because float32 copies of all layers of a 7B
model do not fit beside the bf16 weights on a 16 GB chip.

The tolerance is the configuration's own (``reference_tolerance`` in
its file), as for GPT-2 (reference/gpt2.py) on rms and max of
|system - reference| / std(reference) over the compared logits (3
positions x 92,544).  bf16 weights, activations and cache with float32
accumulation measure rms 0.036-0.039, max 0.17-0.20 at InternLM2's widths
on the chip (PR 23), and the error grows with the width (CPU, bf16:
max 0.03 at 256 wide, 0.06 at 1024, same depth); the bounds (0.08 /
0.4) are about twice the chip's reading.  The system side is prefill through the
cache and then decode steps, so a wrong cache row, position or mask
(error of the order of 1, tested on the CPU) lands far above them.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

FLOAT32_TOLERANCE = {"rms": 5e-5, "max": 2e-4}


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (S, H, D); rotate pairs (i, i + D/2) by position * theta**(-2i/D)."""
    S, _H, D = x.shape
    half = D // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _block(x, p, theta, eps):
    S, E = x.shape
    H, D = p["wq"].shape[1:]
    KV = p["wk"].shape[1]
    h = _rmsnorm(x, p["attn_norm"], eps)
    q = _rope((h @ p["wq"].reshape(E, H * D)).reshape(S, H, D), theta)
    k = _rope((h @ p["wk"].reshape(E, KV * D)).reshape(S, KV, D), theta)
    v = (h @ p["wv"].reshape(E, KV * D)).reshape(S, KV, D)
    group = H // KV
    q = q.reshape(S, KV, group, D)
    scores = jnp.einsum("qkgd,tkd->kgqt", q, k) / math.sqrt(D)
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    attn = jnp.einsum("kgqt,tkd->qkgd", probs, v).reshape(S, H * D)
    x = x + attn @ p["wo"].reshape(H * D, E)
    h = _rmsnorm(x, p["mlp_norm"], eps)
    return x + (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer(x, blocks, i, theta, eps):
    """Block ``i`` of the stacked tree, cast to float32 as it is read:
    float32 copies of all layers of a 7B model do not fit beside the
    bf16 weights on a 16 GB chip, one layer's does."""
    p = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False).astype(jnp.float32),
        blocks,
    )
    return _block(x, p, theta, eps)


@functools.partial(jax.jit, static_argnums=3)
def _head_rows(x, head, lo, rows):
    """Logits of ``rows`` vocabulary rows from ``lo``: bounds the
    float32 copy of the output head."""
    w = jax.lax.dynamic_slice_in_dim(head, lo, rows, axis=0).astype(jnp.float32)
    return x @ w.T


def forward(params, tokens, rope_theta: float, rms_eps: float, positions,
            head_rows: int = 16384):
    """tokens (S,) int32 -> logits (len(positions), V) float32 at the
    given positions.  The output head is applied to the largest equal
    slices of the vocabulary of at most ``head_rows`` rows."""
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed"][tokens].astype(jnp.float32)
        for i in range(params["blocks"]["attn_norm"].shape[0]):
            x = _layer(x, params["blocks"], i, float(rope_theta), float(rms_eps))
        x = _rmsnorm(x, params["final_norm"].astype(jnp.float32), rms_eps)
        x = x[jnp.asarray(positions)]
        head = params.get("lm_head", params["tok_embed"])
        vocab = head.shape[0]
        parts = next(k for k in range(1, vocab + 1)
                     if vocab % k == 0 and vocab // k <= head_rows)
        rows = vocab // parts
        return jnp.concatenate(
            [_head_rows(x, head, k * rows, rows) for k in range(parts)], axis=-1
        )
