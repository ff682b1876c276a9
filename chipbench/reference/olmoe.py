"""OLMoE decoder forward, plainly: float32 ``jax.numpy``, matmul
precision ``highest``, no cache, no kernel, no batching and no sorting
— each token's experts are applied to it one after the other.

Follows ``allenai/OLMoE-1B-7B-0125-Instruct``'s ``config.json`` and the
``modeling_olmoe.py`` of the transformers library (Muennighoff et al.
2024, "OLMoE: Open Mixture-of-Experts Language Models").  One pre-norm
block, ``x`` of width E:

1. ``h = RMSNorm(x; attn_norm, eps)``; ``q = h Wq``, ``k = h Wk``,
   ``v = h Wv`` (E -> H x D each, no bias; ``clip_qkv`` is null: no
   clipping).
2. QK-norm over the WHOLE projected vector, before the split into
   heads: ``q = RMSNorm(q; q_norm)``, ``k = RMSNorm(k; k_norm)``, scales
   of width H x D (``OlmoeAttention.q_norm`` / ``k_norm``; ``config.json``
   has no key for it: the configuration file lists it under ``assumed``).
3. RoPE in the half-split ("rotate_half") form with base ``rope_theta``
   on q and k; causal softmax attention scaled by 1/sqrt(D);
   ``x += attn Wo``.
4. ``h = RMSNorm(x; mlp_norm)``; router logits ``r = h Wr`` (E -> X, no
   bias); ``p = softmax(r)`` over all X experts in float32; the top-k
   probabilities and their experts; the weights are NOT renormalised
   (``norm_topk_prob`` false).
5. ``x += sum_i w_i W_down[e_i](silu(W_gate[e_i] h) * W_up[e_i] h)``;
   no shared expert.
6. Final RMSNorm, untied output head.

It reads the program's parameter tree (``ray_tpu/models/llama.py``:
blocks stacked on a leading layer axis; ``w_router`` (E, X), ``w_gate``
/ ``w_up`` (X, E, M), ``w_down`` (X, M, E), ``q_norm`` / ``k_norm``
(H x D)) one layer at a time, casting each to float32 as it goes (one
layer's float32 experts would be 1.6 GB at the published widths: they
are cast an expert at a time).  Within a
layer the experts are applied to every token and the k chosen ones
kept, an expert at a time (``lax.map``), so the largest intermediate is
(tokens, M): the arithmetic of each kept term is exactly step 5's.

``forward`` also returns, per layer and token, the experts it chose
and the margin between the k-th and the (k+1)-th router probability:
where that margin is below the rounding of the router's input in the
system's precision, the system may choose the other expert and be
right in its own arithmetic.

``forward``'s ``top_k``, ``renormalise`` and ``qk_norm`` arguments exist
for the tests: a tolerance is only worth stating if it refuses a
reference that leaves one expert out, renormalises the weights or
skips the QK-norm (``tests/chipbench_suite/test_chipbench_olmoe.py``).

The tolerance is the configuration's own (``reference_tolerance`` in
``chipbench/configs/olmoe-1b-7b-l12.json``: rms 0.02, max 0.1), on rms
and max of |system - reference| / std(reference) over the compared
logits (3 positions x 50,304), as for the other families.  It sits
between two readings taken on the chip at the published widths (PR 26,
PERF.md section 4): bf16 weights, activations and cache against this
float32 forward read rms 0.0072-0.0098 and max 0.032-0.058 over 12
seeds — that includes the 3.6-6.3% of (layer, token) pairs at which
bf16 rounding swaps the k-th and the (k+1)-th expert, each worth
little under N(0, 0.02) weights — and the same weights with their
mantissa cut to float8's 3 bits read rms 0.094-0.100, max 0.37-0.40.
So the limit is twice the largest honest reading and a fifth of the
next precision down; the free-running comparison was enough, the
reference never had to be fed the system's choices.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.reference.llama import (FLOAT32_TOLERANCE, _head_rows,  # noqa: F401
                                       _rmsnorm, _rope)


EXPERT_TENSORS = ("w_gate", "w_up", "w_down")


def _block(x, p, theta, eps, top_k, renormalise, qk_norm):
    S, E = x.shape
    H, D = p["wq"].shape[1:]
    h = _rmsnorm(x, p["attn_norm"], eps)
    q = h @ p["wq"].reshape(E, H * D)
    k = h @ p["wk"].reshape(E, H * D)
    v = (h @ p["wv"].reshape(E, H * D)).reshape(S, H, D)
    if qk_norm:
        q, k = _rmsnorm(q, p["q_norm"], eps), _rmsnorm(k, p["k_norm"], eps)
    q = _rope(q.reshape(S, H, D), theta)
    k = _rope(k.reshape(S, H, D), theta)
    scores = jnp.einsum("qhd,thd->hqt", q, k) / math.sqrt(D)
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    x = x + jnp.einsum("hqt,thd->qhd", probs, v).reshape(S, H * D) @ p["wo"].reshape(H * D, E)

    h = _rmsnorm(x, p["mlp_norm"], eps)
    router = jax.nn.softmax(h @ p["w_router"], axis=-1)          # (S, X)
    ranked = jnp.argsort(-router, axis=-1)
    chosen = ranked[:, :top_k]                                   # (S, k)
    by_rank = jnp.take_along_axis(router, ranked, axis=-1)
    margin = by_rank[:, top_k - 1] - by_rank[:, top_k]
    weight = by_rank[:, :top_k]
    if renormalise:
        weight = weight / weight.sum(-1, keepdims=True)

    def one_expert(args):
        e, w_gate, w_up, w_down = (a.astype(jnp.float32) for a in args)
        out = (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down     # (S, E)
        w = jnp.where(chosen == e, weight, 0.0).sum(-1)          # (S,)
        return out * w[:, None]

    experts = jnp.arange(p["w_gate"].shape[0])
    x = x + jax.lax.map(
        one_expert, (experts, p["w_gate"], p["w_up"], p["w_down"])
    ).sum(0)
    return x, chosen, margin


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _layer(x, blocks, i, theta, eps, top_k, renormalise, qk_norm):
    """Block ``i`` of the stacked tree, cast to float32 as it is read;
    the three expert tensors are cast an expert at a time where they
    are used (``one_expert``), so no float32 copy of a whole layer's
    experts (1.6 GB) is made beside the bf16 weights."""
    p = {
        k: jax.lax.dynamic_index_in_dim(a, i, keepdims=False)
        for k, a in blocks.items()
    }
    p = {k: a if k in EXPERT_TENSORS else a.astype(jnp.float32)
         for k, a in p.items()}
    return _block(x, p, theta, eps, top_k, renormalise, qk_norm)


def forward(params, tokens, rope_theta: float, rms_eps: float, top_k: int,
            positions, head_rows: int = 16384, renormalise: bool = False,
            qk_norm: bool = True):
    """tokens (S,) int32 -> (logits (len(positions), V) float32 at the
    given positions, {"experts": (L, S, top_k) int32 in order of
    falling probability, "margin": (L, S) float32})."""
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed"][tokens].astype(jnp.float32)
        experts, margins = [], []
        for i in range(params["blocks"]["attn_norm"].shape[0]):
            x, chosen, margin = _layer(
                x, params["blocks"], i, float(rope_theta), float(rms_eps),
                int(top_k), bool(renormalise), bool(qk_norm),
            )
            experts.append(chosen)
            margins.append(margin)
        x = _rmsnorm(x, params["final_norm"].astype(jnp.float32), rms_eps)
        x = x[jnp.asarray(positions)]
        head = params["lm_head"]
        vocab = head.shape[0]
        parts = next(k for k in range(1, vocab + 1)
                     if vocab % k == 0 and vocab // k <= head_rows)
        rows = vocab // parts
        logits = jnp.concatenate(
            [_head_rows(x, head, k * rows, rows) for k in range(parts)], axis=-1
        )
    return logits, {"experts": jnp.stack(experts), "margin": jnp.stack(margins)}
