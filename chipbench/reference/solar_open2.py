"""Solar-Open2's decoder forward (``upstage/Solar-Open2-250B``,
``config.json``'s keys, ``model_type`` ``solar_open2``), plainly: float32
``jax.numpy``, matmul precision ``highest``, no cache, no kernel, no chunked
rule, one sequence, the recurrence token by token.

A LAYER (``N`` an RMSNorm with its own scale, eps ``rms_norm_eps``; no
biases; pre-norm — ASSUMED, the family's)::

    h = x + Mixer_i(N_1(x));    y = h + MoE(N_2(h))

Layer ``i`` is GQA where ``i in gqa_layers`` (i % 4 == 0), else KDA.  Final
RMSNorm, untied output head.

``KDA`` (Kimi Delta Attention: Kimi Linear, arXiv:2510.26692; ``fla``'s
``KimiDeltaAttention``), H = 64 heads, d_k = d_v = 128, n the normed input,
``conv4`` a causal depthwise convolution of 4 taps with zeros before the
sequence and no bias:

* ``q = l2norm(silu(conv4(W_q n))) / sqrt(d_k)``, ``k = l2norm(silu(conv4(W_k
  n)))`` per head (eps 1e-6 under the root), ``v = silu(conv4(W_v n))``;
* the decay, a number a KEY CHANNEL of every head: ``g_t = -exp(A_log_h)
  softplus((W_f_up W_f_down n)_t + dt_bias)`` <= 0, (H, d_k), the projection
  low-rank through 128 columns (``kda_use_full_proj`` false);
* ``beta_t = 2 sigmoid(W_b n)`` a head (``kda_allow_neg_eigval``: a state's
  eigenvalues may reach -1);
* ``S_t = (I - beta_t k_t k_t^T) Diag(e^{g_t}) S_{t-1} + beta_t k_t v_t^T``,
  ``o_t = S_t^T q_t``; S (d_k, d_v) float32 a head, zero before the sequence;
* ``y = W_o concat_h(RMSNorm_{d_v}(o_t) w * sigmoid(W_g_up W_g_down n))``,
  the gate low-rank through 128 columns, one (d_v,) scale ``w`` for all heads.

``GQA``: 64 query heads over 8 KV heads of 128, NO rotation (``use_rope``
false), ``s[t, j] = q_t . k_j / sqrt(128)``, causal softmax over every key;
``use_gqa_gate``: ``y = W_o concat_h(o_h * sigmoid(W_gate n)_h)``, W_gate
4,096 x 8,192, elementwise (ASSUMED: the gated attention of the Qwen3-Next
line).

``MoE``: ``s = sigmoid(W_r n)`` over ``n_routed_experts`` = 320 experts
(ASSUMED: the config names no score function; its keys are DeepSeek-V3's), the
top ``num_experts_per_tok`` = 8 of ``s + bias`` chosen (the selection-only
bias of a ``noaux_tc`` router), weights ``s_chosen / sum s_chosen``
(``norm_topk_prob``) x ``routed_scaling_factor`` = 1, ``sum_e w_e
SwiGLU_e(n)`` of width ``moe_intermediate_size`` = 1,280, plus ONE shared
SwiGLU of the same width for every token (ASSUMED width: ``n_shared_experts``
x ``moe_intermediate_size``).  ``first_k_dense_replace`` 0: no dense layer,
``intermediate_size`` is unused.

THE CHIP'S SHARE: the tree's expert tensors hold experts ``expert_offset ..
expert_offset + held`` of the router's 320; only those contribute, in the
program and here alike (the weights are still divided by the sum over ALL
chosen), and the shared expert is added whole.  ``forward`` can be GIVEN the
system's choices (``experts``), as ``reference/mimo_v2_flash.py``'s: one
swapped eighth choice switches a held expert's term on or off.

It reads the program's parameter tree — ``blocks`` the GQA layers,
``gdn_blocks`` the KDA layers, every leaf stacked over its own layers — and
imports nothing of the program (the norm, the SwiGLU, the cut of a stacked
leaf and the routed experts are ``reference/mimo_v2_flash.py``'s).  No layer is cast whole: every use cuts its
own block out of the stacked leaf (a head, a group of heads, an expert).

``Spec`` carries what the shapes do not say, so a comparison can be run with
ONE piece left out or bent: ``gqa_gate`` False, ``neg_eigval`` False,
``channel_decay`` False (the channels' mean decay for all: the scalar gate),
``shared_expert`` False, ``state_dtype`` bfloat16 (the state rounded after
every token).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference import mimo_v2_flash
from chipbench.reference.mimo_v2_flash import _cut, _rmsnorm, _swiglu

FULL, LINEAR = "full_attention", "linear_attention"
#: queries that attend at once; KDA heads whose recurrence runs together
QUERY_BLOCK = 1024
HEAD_GROUP = 8


class Spec(NamedTuple):
    """What the parameter tree's shapes do not say."""

    layer_types: tuple
    rms_eps: float
    experts_per_token: int
    expert_offset: int = 0
    neg_eigval: bool = True
    gqa_gate: bool = True
    channel_decay: bool = True
    shared_expert: bool = True
    state_dtype: str = "float32"


def _gqa(n, stack, i, spec: Spec):
    """GQA layer ``i`` of ``stack``.  n (S, E) normed -> (S, E)."""
    S = n.shape[0]
    layer = ((0, i),)
    H, D = stack["wq"].shape[2:]
    KV = stack["wk"].shape[2]
    pos = jnp.arange(S)

    def head(h):
        kv = h // (H // KV)
        q = n @ _cut(stack["wq"], (*layer, (2, h)))
        k = n @ _cut(stack["wk"], (*layer, (2, kv)))
        v = n @ _cut(stack["wv"], (*layer, (2, kv)))
        out = []
        for lo in range(0, S, QUERY_BLOCK):
            hi = min(S, lo + QUERY_BLOCK)
            s = (q[lo:hi] @ k[:hi].T) / math.sqrt(D)
            s = jnp.where(pos[None, :hi] <= pos[lo:hi, None], s, -jnp.inf)
            out.append(jax.nn.softmax(s, axis=-1) @ v[:hi])
        o = jnp.concatenate(out)                                     # (S, D)
        if spec.gqa_gate:
            o = o * jax.nn.sigmoid(n @ _cut(stack["w_og"], (*layer, (2, h))))
        return o

    o = lax.map(head, jnp.arange(H))                                 # (H, S, D)
    return jnp.einsum("hsv,hve->se", o, _cut(stack["wo"], layer))


def _kda(n, stack, i, spec: Spec):
    """KDA layer ``i`` of ``stack``, ``HEAD_GROUP`` heads at a time, each
    token after the one before.  n (S, E) normed -> (S, E)."""
    S = n.shape[0]
    layer = ((0, i),)
    H, dk = stack["gdn_wq"].shape[2:]
    dv = stack["gdn_wv"].shape[3]
    G = math.gcd(H, HEAD_GROUP)
    taps = _cut(stack["gdn_conv"], layer)                            # (K, H (2 d_k + d_v))
    K = taps.shape[0]
    f_mid = n @ _cut(stack["kda_wf_a"], layer)                       # (S, rank)
    g_mid = n @ _cut(stack["kda_wg_a"], layer)
    low = jnp.dtype(spec.state_dtype)

    def conv(u, first, width):  # u (S, G, d): the group's channels from ``first`` on
        w = lax.dynamic_slice(taps, (0, first), (K, G * width)).reshape(K, G, width)
        padded = jnp.concatenate([jnp.zeros((K - 1, G, width), jnp.float32), u])
        return jax.nn.silu(sum(padded[j:j + S] * w[j] for j in range(K)))

    def l2norm(t):
        return t / jnp.sqrt((t * t).sum(-1, keepdims=True) + 1e-6)

    def group(total, lo):
        heads = (2, lo, G)

        def proj(name):  # (S, G, d)
            return jnp.einsum("se,ehd->shd", n, _cut(stack[name], layer, heads))

        q = l2norm(conv(proj("gdn_wq"), lo * dk, dk)) / math.sqrt(dk)
        k = l2norm(conv(proj("gdn_wk"), H * dk + lo * dk, dk))
        v = conv(proj("gdn_wv"), 2 * H * dk + lo * dv, dv)
        beta = jax.nn.sigmoid(n @ _cut(stack["gdn_wb"], layer, heads))
        beta = beta * (2.0 if spec.neg_eigval else 1.0)              # (S, G)
        a = jnp.exp(_cut(stack["a_log"], layer, (1, lo, G)))         # (G,)
        g = -a[:, None] * jax.nn.softplus(
            jnp.einsum("sc,chd->shd", f_mid, _cut(stack["kda_wf_b"], layer, heads))
            + _cut(stack["dt_bias"], layer, (1, lo, G)))             # (S, G, d_k)
        if not spec.channel_decay:
            g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)

        def token(state, t):
            q_t, k_t, v_t, g_t, b_t = t
            state = jnp.exp(g_t)[..., None] * state                  # Diag(alpha) S
            seen = jnp.einsum("gkv,gk->gv", state, k_t)
            state = state + jnp.einsum("gk,gv->gkv", k_t, b_t[:, None] * (v_t - seen))
            state = state.astype(low).astype(jnp.float32)
            return state, jnp.einsum("gkv,gk->gv", state, q_t)

        _, o = lax.scan(token, jnp.zeros((G, dk, dv), jnp.float32), (q, k, v, g, beta))
        o = _rmsnorm(o, _cut(stack["gdn_norm"], layer), spec.rms_eps)
        o = o * jax.nn.sigmoid(
            jnp.einsum("sc,chd->shd", g_mid, _cut(stack["kda_wg_b"], layer, heads)))
        return total + jnp.einsum(
            "shv,hve->se", o, _cut(stack["gdn_wo"], layer, (1, lo, G))), None

    return lax.scan(group, jnp.zeros_like(n), jnp.arange(0, H, G))[0]


def _moe(g, stack, i, spec: Spec, forced=None):
    """Layer ``i``'s expert layer: ``mimo_v2_flash._moe`` — the same sigmoid
    router with its selection-only bias, the same held experts, the same
    ``forced`` choices (it reads ``experts_per_token`` and ``expert_offset``
    of the spec) — and the shared expert added for every token.  g (S, E)
    normed -> (its output (S, E), the reference's own choice (S, k), its
    margin (S,))."""
    y, chosen, margin = mimo_v2_flash._moe(g, stack, i, spec, forced)
    if spec.shared_expert:
        at = ((0, i),)
        y = y + _swiglu(g, _cut(stack["ws_gate"], at), _cut(stack["ws_up"], at),
                        _cut(stack["ws_down"], at))
    return y, chosen, margin


def layer(x, stack, i, kind, spec: Spec, forced=None):
    """Layer ``i`` of ``stack`` (kind ``kind``): the equations at the top."""
    at = ((0, i),)
    mixer = _kda if kind == LINEAR else _gqa
    x = x + mixer(_rmsnorm(x, _cut(stack["attn_norm"], at), spec.rms_eps), stack, i, spec)
    g = _rmsnorm(x, _cut(stack["mlp_norm"], at), spec.rms_eps)
    y, chosen, margin = _moe(g, stack, i, spec, forced)
    return x + y, chosen, margin


_layer = jax.jit(layer, static_argnums=(3, 4))


def stack_of(spec: Spec):
    """[(the tree's stack, the layer's index in it, its kind)] in layer order."""
    seen, out = {}, []
    for kind in spec.layer_types:
        name = "gdn_blocks" if kind == LINEAR else "blocks"
        out.append((name, seen.get(name, 0), kind))
        seen[name] = seen.get(name, 0) + 1
    return out


def forward(params, tokens, spec: Spec, experts=None):
    """tokens (S,) int32 -> (the final-normed hidden states (S, E) float32,
    {"experts": (layers, S, k) the reference's own choices, "expert_margin":
    (layers, S)}).  ``experts`` (layers, S, k): the choices every token is
    GIVEN (the system's)."""
    chose, margins = [], []
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed"][tokens].astype(jnp.float32)
        for at, (name, i, kind) in enumerate(stack_of(spec)):
            forced = None if experts is None else experts[at]
            x, chosen, margin = _layer(x, params[name], jnp.int32(i), kind, spec, forced)
            chose.append(chosen)
            margins.append(margin)
        x = _rmsnorm(x, params["final_norm"].astype(jnp.float32), spec.rms_eps)
    return x, {"experts": jnp.stack(chose), "expert_margin": jnp.stack(margins)}


def logits(params, x):
    """Normed states (N, E) -> logits (N, V) float32."""
    with jax.default_matmul_precision("highest"):
        return x @ params["lm_head"].astype(jnp.float32).T
