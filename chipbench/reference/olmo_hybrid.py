"""Olmo-Hybrid-7B's decoder forward (``layer_types``: three gated-delta-rule
layers, one full-attention layer, eight times over), plainly: float32
``jax.numpy``, matmul precision ``highest``, no cache, no chunks, no kernel,
no batching, one sequence, the recurrence TOKEN BY TOKEN.

Follows ``allenai/Olmo-Hybrid-7B``'s ``config.json`` and, for what the
``linear_*`` keys mean, the gated delta rule as ``fla``'s ``GatedDeltaNet``
and ``transformers``' ``Qwen3NextGatedDeltaNet`` compute it
(``torch_recurrent_gated_delta_rule`` with ``use_qk_l2norm_in_kernel``, behind
that class's projections, convolution and gated norm), with beta doubled
(``linear_allow_neg_eigval``).  ``x`` (S, E); H heads, keys of d_k, values of
d_v, K taps.

LINEAR layer's mixer, token t::

    q~ = x Wq   k~ = x Wk   (H d_k each)      v~ = x Wv   z = x Wz   (H d_v each)
    a = x Wa    b = x Wb    (H each)          no biases
    u = [q~; k~; v~];   u-bar_t[c] = silu(sum_{j<K} w[j, c] u_{t-(K-1)+j}[c]),  u before the sequence = 0
    per head:  q = q-bar / sqrt(sum q-bar^2 + 1e-6) / sqrt(d_k)    k = k-bar / sqrt(sum k-bar^2 + 1e-6)    v = v-bar
    beta = 2 sigmoid(b)        alpha = exp(-exp(A_log) softplus(a + dt_bias))
    S_0 = 0 (d_k, d_v);   S' = alpha_t S_{t-1};   S_t = S' + k_t (x) (beta_t (v_t - S'^T k_t));   o_t = S_t^T q_t
    y = [RMSNorm_{d_v}(o) g * silu(z)] Wo          one (d_v,) scale g for all heads, eps rms_norm_eps

FULL layer's mixer: ``q = RMSNorm(x Wq)``, ``k = RMSNorm(x Wk)`` over the WHOLE
projection (all heads together), ``v = x Wv``; causal softmax attention
scaled 1/sqrt(head size), grouped where KV < H; Wo.  NO rotation.

BLOCK, both kinds (OLMo-2's, OLMo-3's): ``x = x + RMSNorm(mixer(x))``; ``x = x
+ RMSNorm(SwiGLU(x))``; mixer and SwiGLU read x un-normed.  Final RMSNorm,
untied head.

ASSUMED, as the configuration's file lists it (``assumed``): no rotary
embedding in the full layers (``rope_theta`` null); the block above; the
state in float32; ``A_log`` = log U(0, 16) and ``dt_bias`` = 1 as ``fla``
starts them (the weights are random, from ``--seed``).

It reads the program's parameter tree (``ray_tpu/models/llama.py``: one stack
a kind, ``gdn_blocks`` and ``blocks``, each on a leading layer axis; the
convolution's taps in front, ``gdn_conv`` (K, channels)) a layer at a time,
each matrix cast to float32 where it is used.  The weights are ARGUMENTS of
the jitted layers: one compile a sequence length serves every seed.

THE LIMITS (``reference_tolerance`` in ``chipbench/configs/
olmo-hybrid-7b-l16.json``, which says how they were set; the comparison is
``jobs/serve_hybrid.py``'s ``system_run`` / ``against_reference``).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from chipbench.reference.llama import _head_rows, _rmsnorm

LINEAR, FULL = "linear_attention", "full_attention"


class Spec(NamedTuple):
    """What the parameter tree's shapes do not say."""

    layer_types: tuple
    rms_eps: float
    neg_eigval: bool = True


def _l2norm(x):
    return x / jnp.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def _linear_mixer(x, p, spec: Spec):
    f = jnp.float32
    S = x.shape[0]
    H, dk = p["gdn_wq"].shape[1:]
    dv = p["gdn_wv"].shape[2]

    def heads(name):  # (E, H, d) -> (S, H * d)
        return jnp.einsum("se,ehd->shd", x, p[name].astype(f)).reshape(S, -1)

    u = jnp.concatenate([heads("gdn_wq"), heads("gdn_wk"), heads("gdn_wv")], axis=-1)
    z = heads("gdn_wz").reshape(S, H, dv)
    a, b = x @ p["gdn_wa"].astype(f), x @ p["gdn_wb"].astype(f)
    taps = p["gdn_conv"].astype(f)                                    # (K, channels)
    K = taps.shape[0]
    before = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), f), u])
    u = jax.nn.silu(sum(before[j:j + S] * taps[j] for j in range(K)))
    q, k, v = jnp.split(u, [H * dk, 2 * H * dk], axis=-1)
    q = _l2norm(q.reshape(S, H, dk)) / math.sqrt(dk)
    k, v = _l2norm(k.reshape(S, H, dk)), v.reshape(S, H, dv)
    beta = jax.nn.sigmoid(b) * (2.0 if spec.neg_eigval else 1.0)
    alpha = jnp.exp(-jnp.exp(p["a_log"].astype(f)) * jax.nn.softplus(a + p["dt_bias"].astype(f)))

    def token(state, t):
        q_t, k_t, v_t, alpha_t, beta_t = t
        state = alpha_t[:, None, None] * state
        seen = jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + k_t[:, :, None] * (beta_t[:, None] * (v_t - seen))[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((H, dk, dv), f), (q, k, v, alpha, beta))
    o = _rmsnorm(o, p["gdn_norm"].astype(f), spec.rms_eps) * jax.nn.silu(z)
    return jnp.einsum("shv,hve->se", o, p["gdn_wo"].astype(f))


def _full_mixer(x, p, spec: Spec):
    f = jnp.float32
    S, E = x.shape
    H, D = p["wq"].shape[1:]
    KV = p["wk"].shape[1]
    q = _rmsnorm(x @ p["wq"].astype(f).reshape(E, H * D), p["q_norm"].astype(f), spec.rms_eps)
    k = _rmsnorm(x @ p["wk"].astype(f).reshape(E, KV * D), p["k_norm"].astype(f), spec.rms_eps)
    v = x @ p["wv"].astype(f).reshape(E, KV * D)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def group(args):  # one KV head and the H / KV query heads that share it
        qg, kg, vg = args                                             # (S, G, D), (S, D), (S, D)
        s = jnp.einsum("igd,jd->gij", qg, kg) / math.sqrt(D)
        probs = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("gij,jd->igd", probs, vg)

    o = jax.lax.map(group, (
        q.reshape(S, KV, H // KV, D).swapaxes(0, 1),
        k.reshape(S, KV, D).swapaxes(0, 1), v.reshape(S, KV, D).swapaxes(0, 1),
    ))                                                                # (KV, S, G, D)
    return jnp.einsum("shd,hde->se", o.swapaxes(0, 1).reshape(S, H, D), p["wo"].astype(f))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer(x, blocks, i, kind: str, spec: Spec):
    """Block ``i`` of one kind's stacked tree."""
    f = jnp.float32
    p = {k: jax.lax.dynamic_index_in_dim(a, i, keepdims=False) for k, a in blocks.items()}
    mixer = _linear_mixer if kind == LINEAR else _full_mixer
    x = x + _rmsnorm(mixer(x, p, spec), p["attn_norm"].astype(f), spec.rms_eps)
    y = (jax.nn.silu(x @ p["w_gate"].astype(f)) * (x @ p["w_up"].astype(f))) @ p["w_down"].astype(f)
    return x + _rmsnorm(y, p["mlp_norm"].astype(f), spec.rms_eps)


def forward(params, ids, spec: Spec, rows=None, head_rows: int = 16384):
    """ids (S,) int32 -> logits (S, V) float32 — of positions ``rows`` only,
    where given."""
    stack = {LINEAR: params["gdn_blocks"], FULL: params["blocks"]}
    seen = {LINEAR: 0, FULL: 0}
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed"][jnp.asarray(ids, jnp.int32)].astype(jnp.float32)
        for kind in spec.layer_types:
            x = _layer(x, stack[kind], seen[kind], kind, spec)
            seen[kind] += 1
        x = _rmsnorm(x, params["final_norm"].astype(jnp.float32), spec.rms_eps)
        if rows is not None:
            x = x[jnp.asarray(rows)]
        head = params["lm_head"]
        vocab = head.shape[0]
        parts = next(k for k in range(1, vocab + 1)
                     if vocab % k == 0 and vocab // k <= head_rows)
        per = vocab // parts
        return jnp.concatenate(
            [_head_rows(x, head, k * per, per) for k in range(parts)], axis=-1
        )
