"""Ouro (LoopLM) forward, plainly: float32 ``jax.numpy``, no cache, no
kernel, no batching, matmul precision ``highest``.

Follows Zhu et al., *Scaling Latent Reasoning via Looped Language Models*
(arXiv:2510.25741) and the model repository's ``modeling_ouro.py``, whose
module names stand in brackets:

* a block has FOUR RMSNorms, a sandwich: ``x += N2(Attn(N1(x)))``; ``x +=
  N4(SwiGLU(N3(x)))`` (``input_layernorm``, ``input_layernorm_2``,
  ``post_attention_layernorm``, ``post_attention_layernorm_2``); attention is
  plain multi-head (as many K/V heads as query heads in Ouro-2.6B; grouped
  heads are written out all the same), no bias, no q/k norm, rotary position
  embedding over the whole head in half-split ("rotate_half") pairs, causal
  softmax scaled by 1/sqrt(head size); SwiGLU ``w_down(silu(w_gate x) * w_up
  x)``;
* the model: ``x_0 = Embed(ids)``; for pass ``t = 1..T``: ``x_t =
  N_f(Blocks_1..L(x_{t-1}))`` — the SAME L blocks' weights in every pass, and
  the final norm ``N_f`` (``norm``) closes every pass, so pass ``t + 1`` reads
  normed states; logits are ``Head(x_T)``, the head untied;
* block ``l`` in pass ``t`` attends over the keys block ``l`` made IN PASS
  ``t`` at the earlier positions (the repository's ``UniversalTransformerCache``
  keeps cache layer ``(t - 1) L + l``): over a whole sequence without a cache
  that is simply each pass's own causal attention;
* the exit gate (``early_exit_gate``, hidden -> 1 with bias) reads each pass's
  output: ``lambda_t = sigmoid(x_t w_g + b_g)``; the exit distribution is ``p_t
  = lambda_t prod_{s<t} (1 - lambda_s)`` for ``t < T`` and ``p_T = prod_{s<T}
  (1 - lambda_s)``; a token's logits come from the first pass whose cumulative
  ``sum_{s<=t} p_s`` reaches ``early_exit_threshold``.

Departures from the published description: the threshold is the published 1,
which only pass T reaches, so ``forward`` hands back pass T's logits for
every token and the gate's ``lambda_t`` / ``p_t`` beside them, and a lower
threshold is not written; weights are random from a seed (the file's
``assumed``), not the published checkpoint.

It reads the program's parameter tree (``ray_tpu/models/llama.py``: blocks
stacked on a leading layer axis, ``wq`` (E, H, D), ``wk`` / ``wv`` (E, KV, D),
``wo`` (H, D, E), the four norms ``attn_norm`` / ``attn_norm_out`` /
``mlp_norm`` / ``mlp_norm_out``, ``exit_gate`` {``w`` (E,), ``b`` (1,)}) one
layer at a time, each cast to float32 as it is read: float32 copies of all 48
layers do not fit beside the bf16 weights and a 6.4 GB cache on a 16 GB chip.

``Spec``'s last three fields leave ONE piece of the mathematics out, for the
readings that show the comparison would catch it (the honest model has them
at their defaults; one pass fewer is ``passes - 1``): no final norm between
the passes, pre-norm only (``N2`` and ``N4`` dropped), and ONE cache layer a
parameter layer shared by the passes — what a cached path gets if it forgets
the pass in its cache layer: from position ``shared_cache_from`` on (the
prompt's length: a prefill writes a pass's whole run before it reads it and
cannot see the fault) a token's pass ``t`` sees, at the EARLIER positions,
the keys their LAST pass left there.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


class Spec(NamedTuple):
    passes: int
    rope_theta: float
    rms_eps: float
    norm_between_passes: bool = True
    sandwich: bool = True
    shared_cache_from: Optional[int] = None


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, start, theta):
    """x: (S, H, D) at positions ``start`` ..; rotate pairs (i, i + D/2) by
    position * theta**(-2i/D)."""
    S, _H, D = x.shape
    half = D // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = (start + jnp.arange(S, dtype=jnp.float32))[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _block(x, p, spec: Spec, k_before, v_before):
    """One block over the tokens ``x`` (S, E) at positions ``len(k_before)``
    .., which see ``k_before`` / ``v_before`` (S0, KV, D) — the keys of the
    earlier positions, none in the honest forward over a whole sequence —
    and each other causally.  -> (x, this run's k, its v)."""
    S, E = x.shape
    H, D = p["wq"].shape[1:]
    KV = p["wk"].shape[1]
    S0 = k_before.shape[0]
    h = _rmsnorm(x, p["attn_norm"], spec.rms_eps)
    q = _rope((h @ p["wq"].reshape(E, H * D)).reshape(S, H, D), S0, spec.rope_theta)
    k = _rope((h @ p["wk"].reshape(E, KV * D)).reshape(S, KV, D), S0, spec.rope_theta)
    v = (h @ p["wv"].reshape(E, KV * D)).reshape(S, KV, D)
    keys, values = jnp.concatenate([k_before, k]), jnp.concatenate([v_before, v])
    q = q.reshape(S, KV, H // KV, D)
    scores = jnp.einsum("qkgd,tkd->kgqt", q, keys) / math.sqrt(D)
    sees = jnp.arange(S0 + S)[None, :] <= S0 + jnp.arange(S)[:, None]
    probs = jax.nn.softmax(jnp.where(sees, scores, -jnp.inf), axis=-1)
    attn = jnp.einsum("kgqt,tkd->qkgd", probs, values).reshape(S, H * D) @ p["wo"].reshape(H * D, E)
    x = x + (_rmsnorm(attn, p["attn_norm_out"], spec.rms_eps) if spec.sandwich else attn)
    h = _rmsnorm(x, p["mlp_norm"], spec.rms_eps)
    mlp = (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]
    return x + (_rmsnorm(mlp, p["mlp_norm_out"], spec.rms_eps) if spec.sandwich else mlp), k, v


@functools.partial(jax.jit, static_argnums=3)
def _layer(x, blocks, i, spec: Spec, k_before, v_before):
    """Block ``i`` of the stacked tree, cast to float32 as it is read."""
    p = jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False).astype(jnp.float32),
        blocks)
    return _block(x, p, spec, k_before, v_before)


@functools.partial(jax.jit, static_argnums=3)
def _head_rows(x, head, lo, rows):
    """Logits of ``rows`` vocabulary rows from ``lo``: bounds the float32
    copy of the output head."""
    w = jax.lax.dynamic_slice_in_dim(head, lo, rows, axis=0).astype(jnp.float32)
    return x @ w.T


def _run(params, x, spec: Spec, earlier=None):
    """The ``spec.passes`` passes over the tokens' embeddings ``x`` (S, E).
    ``earlier``: None — every pass attends over its own keys alone, the whole
    sequence being here — or, the fault, ``[layer: (k, v)]`` of the earlier
    positions, ONE entry a parameter layer that every pass reads.  -> (x_T (S,
    E), lambda (passes, S), [layer: (k, v)] as the LAST pass made them)."""
    blocks, gate = params["blocks"], params["exit_gate"]
    layers = blocks["attn_norm"].shape[0]
    final = params["final_norm"].astype(jnp.float32)
    w, b = gate["w"].astype(jnp.float32), gate["b"].astype(jnp.float32)[0]
    none = jnp.zeros((0, *blocks["wk"].shape[2:]), jnp.float32)
    lam, wrote = [], [None] * layers
    for t in range(spec.passes):
        for i in range(layers):
            x, *wrote[i] = _layer(x, blocks, i, spec, *(earlier[i] if earlier else (none, none)))
        if spec.norm_between_passes or t == spec.passes - 1:
            x = _rmsnorm(x, final, spec.rms_eps)
        lam.append(jax.nn.sigmoid(x @ w + b))
    return x, jnp.stack(lam), wrote


def exit_distribution(lam):
    """(T, ...) ``lambda_t`` -> (T, ...) ``p_t``: ``lambda_t prod_{s<t} (1 -
    lambda_s)`` for t < T, and ``prod_{s<T} (1 - lambda_s)`` for the last."""
    p, stayed = [], jnp.ones_like(lam[0])
    for t in range(lam.shape[0] - 1):
        p.append(lam[t] * stayed)
        stayed = stayed * (1.0 - lam[t])
    return jnp.stack(p + [stayed])


def forward(params, tokens, spec: Spec, positions, head_rows: int = 16384):
    """tokens (S,) int32 -> (logits (len(positions), V) float32 of pass T at
    the given positions, lambda (passes, len(positions)) the exit gate behind
    every pass there).  The output head is applied to the largest equal slices
    of the vocabulary of at most ``head_rows`` rows."""
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed"][tokens].astype(jnp.float32)
        if spec.shared_cache_from is None:
            x, lam, _ = _run(params, x, spec)
        else:
            # the fault: the prompt as one run, then a token at a time, all
            # passes of a layer over ONE list of keys
            cut, seen, outs = spec.shared_cache_from, None, []
            for a, b in [(0, cut)] + [(s, s + 1) for s in range(cut, x.shape[0])]:
                *out, wrote = _run(params, x[a:b], spec, seen)
                outs.append(out)
                seen = [tuple(jnp.concatenate(pair) for pair in zip(old, new))
                        for old, new in zip(seen, wrote)] if seen else wrote
            x = jnp.concatenate([o[0] for o in outs])
            lam = jnp.concatenate([o[1] for o in outs], axis=1)
        at = jnp.asarray(positions)
        x, lam = x[at], lam[:, at]
        head = params.get("lm_head", params["tok_embed"])
        vocab = head.shape[0]
        parts = next(k for k in range(1, vocab + 1)
                     if vocab % k == 0 and vocab // k <= head_rows)
        rows = vocab // parts
        logits = jnp.concatenate(
            [_head_rows(x, head, k * rows, rows) for k in range(parts)], axis=-1)
        return logits, lam
