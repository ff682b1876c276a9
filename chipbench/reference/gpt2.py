"""GPT-2 forward pass, plainly: float32 ``jax.numpy``, no kernel, no
scan, no mesh annotations, matmul precision ``highest``.

Follows Radford et al. 2019 / the ``transformers`` GPT2LMHeadModel:
learned position table, pre-LayerNorm blocks (eps 1e-5), fused QKV
projection, causal softmax attention scaled by 1/sqrt(head size), MLP
of width 4E with the tanh GELU (``gelu_new``), final LayerNorm, output
head tied to the token table.

It reads the *program's* parameter tree (``ray_tpu/models/gpt2.py``
layout: blocks stacked on a leading layer axis, ``qkv_kernel``
(E, 3H, D) with q, k, v as thirds of the middle axis, ``proj_kernel``
(H, D, E)) because the weights under test are the program's; that
layout is the one thing shared with the code under test.  Departure
from the publication: the vocabulary is padded 50,257 -> 50,304 rows,
as the configuration file says; logits of the padding rows are
compared like any other.

The tolerance is the configuration's own (``reference_tolerance`` in
``chipbench/configs/<name>.json``; jobs/train_spmd.py and the CPU test
read it there), on |system - reference| over the standard deviation of
the reference's logits, as root mean square and as the largest of all
logits.  The system computes in bfloat16 (8 bits of mantissa) with
float32 accumulation and float32 LayerNorm/softmax; what that costs
was measured on the chip (PR 23, TPU v5 lite, one 1024-token sequence,
51 M logits, on the state the window's steps have trained): rms
0.010-0.013, max 0.057-0.076 for GPT-2 medium after 130 steps
(tolerance 0.025 / 0.15); rms 0.027-0.029, max 0.117-0.140 for GPT-2 XL
(deeper and wider) after 33 steps, 0.015 / 0.079 after 6 (tolerance
0.06 / 0.3): the error grows as training sharpens the logits.  Each
configuration's bounds are about twice its own reading.  Matmuls in an
8-bit float (2**-4 relative, 32 times coarser) land an order of
magnitude above them, as does a dropped block, bias or mask (tested on
the CPU); float32 at tiny size lands at ~1e-5, inside
``FLOAT32_TOLERANCE``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

FLOAT32_TOLERANCE = {"rms": 5e-5, "max": 2e-4}


def _layernorm(x, scale, bias, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)
    ))


def _block(x, p, num_heads):
    S, E = x.shape
    D = E // num_heads
    h = _layernorm(x, p["ln1_scale"], p["ln1_bias"])
    qkv = h @ p["qkv_kernel"].reshape(E, 3 * E) + p["qkv_bias"].reshape(3 * E)
    q, k, v = (t.reshape(S, num_heads, D) for t in jnp.split(qkv, 3, axis=-1))
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(D)
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    attn = jnp.einsum("hqk,khd->qhd", probs, v).reshape(S, E)
    x = x + attn @ p["proj_kernel"].reshape(E, E) + p["proj_bias"]
    h = _layernorm(x, p["ln2_scale"], p["ln2_bias"])
    h = _gelu_new(h @ p["fc_kernel"] + p["fc_bias"])
    return x + h @ p["out_kernel"] + p["out_bias"]


@functools.partial(jax.jit, static_argnums=3)
def _layer(x, blocks, i, num_heads):
    """Block ``i`` of the stacked tree, cast to float32 as it is read
    (sliced inside the program: an eager slice per leaf per layer would
    be a compile each)."""
    p = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False).astype(jnp.float32),
        blocks,
    )
    return _block(x, p, num_heads)


def forward(params, tokens, num_heads: int):
    """tokens (S,) int32 -> logits (S, V) float32."""
    with jax.default_matmul_precision("highest"):
        wte = params["wte"].astype(jnp.float32)
        x = wte[tokens] + params["wpe"].astype(jnp.float32)[: tokens.shape[0]]
        for i in range(params["blocks"]["ln1_scale"].shape[0]):
            x = _layer(x, params["blocks"], i, num_heads)
        x = _layernorm(x, params["lnf_scale"].astype(jnp.float32),
                       params["lnf_bias"].astype(jnp.float32))
        return x @ wte.T
