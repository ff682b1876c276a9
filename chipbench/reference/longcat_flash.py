"""LongCat-Flash's decoder forward — the language model of
``meituan-longcat/LongCat-Flash-Omni`` (``config.json``'s keys; the public
``modeling_longcat_flash.py``; the audio / vision encoders and the codec
decoder are no part of it) — plainly: float32 ``jax.numpy``, matmul
precision ``highest``, no cache, no absorption of the latent projections,
no kernel, no batching, one sequence.

ONE LAYER is a shortcut-connected DOUBLE layer (``N`` an RMSNorm with its
own scale, eps ``rms_norm_eps``; x a token's residual of width E)::

    h0 = N_in0(x);    x = x + MLA_0(h0)
    g0 = N_post0(x);  m = MoE(g0)                # kept aside: the shortcut
                      x = x + SwiGLU_0(g0)       # dense, ffn_hidden_size
    h1 = N_in1(x);    x = x + MLA_1(h1)
    g1 = N_post1(x);  x = x + SwiGLU_1(g1) + m   # the expert layer lands here

``MLA_i`` (own weights; token t): ``c_q = N(h W_qa) * sqrt(E /
q_lora_rank)`` (``mla_scale_q_lora``); ``[q_nope | q_rope] = c_q W_qb`` a
head; ``[c_kv | k_rope] = h W_kva``, ``c_kv <- N(c_kv) * sqrt(E /
kv_lora_rank)`` (``mla_scale_kv_lora``) BEFORE ``W_kvb`` expands it, so a
head's ``k_nope = c_kv W_kb,h`` and ``v = c_kv W_vb,h`` carry the factor
and ``k_rope`` does not; rotary on ``q_rope`` / ``k_rope`` over INTERLEAVED
pairs, base ``rope_theta``, no scaling; ``score_h(t, s) = (q_nope . k_nope(s)
+ q_rope . k_rope(s)) / sqrt(nope + rope)`` for EVERY s <= t; softmax;
``x += [o_1 .. o_H] W_o``.

``MoE(g)``: ``logits = g W_r`` over ``n_routed_experts + zero_expert_num``
outputs; ``s = softmax(logits)``; the top ``moe_topk`` of ``s + b`` (``b``:
``e_score_correction_bias``, in the choice only) are chosen; weights ``w_j =
routed_scaling_factor x s_j``, NOT divided by their sum; output ``sum_j w_j
f_j(g)``, ``f_j`` a SwiGLU of width ``expert_ffn_hidden_size`` for ``j <
n_routed_experts`` and the IDENTITY, ``f_j(g) = g``, for the zero-compute
experts behind them.  No shared expert, no leading dense layer, no group
limit.  Final RMSNorm, untied output head.

ASSUMED, as the configuration's file lists it: no bias on the router's
matrix; the chosen weights are not renormalised; the identity experts are
the router's LAST ``zero_expert_num`` outputs; SiLU; no YaRN factor; where
the two LoRA scales apply (above).

THE CHIP'S SHARE: the tree's expert tensors hold experts ``expert_offset
.. expert_offset + held`` of the router's ``n_routed_experts``; only those
contribute, in the program and here alike — and the identity term, which
needs no weights, is every chip's for the tokens it holds: in the sum over
a layer's shares it counts once, like the dense path.  Because one swapped
twelfth choice switches a held expert's term (or an identity term) on or
off, ``forward`` can be GIVEN the system's choices (``experts``): the
logits are then compared under the same routing, and the routers are held
to account apart, choice by choice (``jobs/serve_scmoe.py``), as GLM-5's
and JoyAI's are.

It reads the program's parameter tree (``blocks``: every leaf stacked over
the layers; the attention, the norms and the dense SwiGLU, ``wd_*``, also
over a layer's two sub-layers, (L, 2, ..); the expert layer's ``w_router``,
``router_bias``, ``w_gate`` / ``w_up`` / ``w_down`` (L, held, ..) once a
layer) and imports nothing of the program.  A layer of it is 2.5 GB in
bf16 at the published widths, beside 13.7 GB that are live when the
comparison runs: so no layer, and no matrix wider than 2,048 columns, is
ever copied or cast whole — every use cuts its own block out of the stacked
leaf (``_cut``), a head, an expert or 2,048 columns of a dense SwiGLU at a
time, and sums as it goes.  The jitted pieces take the tree as an ARGUMENT
and the layer's index traced: one compile serves every layer and every
seed.

THE LIMITS of the comparison that decides ``correct``
(``reference_tolerance`` in ``chipbench/configs/longcat-flash-omni-ep32-l4
.json``, which gives each number's two readings; the comparison is
``jobs/serve_scmoe.py``'s ``system_run`` / ``against_reference`` /
``passes``).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference.llama import _head_rows, _rmsnorm

#: columns of a dense SwiGLU (and of nothing else: an expert is this wide)
#: that are cast to float32 at once
DENSE_BLOCK = 2048


class Spec(NamedTuple):
    """What the parameter tree's shapes do not say."""

    rope_theta: float
    rms_eps: float
    qk_rope_head_dim: int
    experts_per_token: int
    routed_scaling_factor: float
    #: the router's real experts, wherever they are held: its outputs from
    #: here on are the identity experts
    n_routed_experts: int
    expert_offset: int = 0
    scale_q_lora: bool = True
    scale_kv_lora: bool = True


def _cut(a, at, span=None):
    """``a`` with axis k fixed at the (traced) index j for every (k, j) of
    ``at`` and, with ``span`` = (axis, lo, n), that axis cut to [lo, lo +
    n): ONE dynamic slice of the stacked leaf, cast to float32."""
    start, size = [0] * a.ndim, list(a.shape)
    for k, j in at:
        start[k], size[k] = j, 1
    if span is not None:
        start[span[0]], size[span[0]] = span[1], span[2]
    out = lax.dynamic_slice(a, [jnp.asarray(j, jnp.int32) for j in start], size)
    fixed = {k for k, _ in at}
    return out.reshape([n for k, n in enumerate(size) if k not in fixed]).astype(jnp.float32)


def _rope_pairs(x, theta):
    """x: (S, D): turn pair (x[2i], x[2i+1]) of token t by t * theta**(-2i/D)."""
    S, D = x.shape
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    even, odd = x[:, 0::2], x[:, 1::2]
    out = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                     odd * jnp.cos(ang) + even * jnp.sin(ang)], axis=-1)
    return out.reshape(S, D)


def _mla(h, blocks, i, s, spec: Spec):
    """Sub-layer ``s`` of layer ``i``'s latent attention over every visible
    key, a head at a time.  h: (S, E) normed -> (S, E)."""
    S, E = h.shape
    sub = ((0, i), (1, s))
    Q, H, Dq = blocks["w_qb"].shape[2:]
    C = blocks["w_kb"].shape[2]
    rope, theta = spec.qk_rope_head_dim, spec.rope_theta
    c_q = _rmsnorm(h @ _cut(blocks["w_qa"], sub), _cut(blocks["q_a_norm"], sub), spec.rms_eps)
    if spec.scale_q_lora:
        c_q = c_q * math.sqrt(E / Q)
    kv = h @ _cut(blocks["w_kva"], sub)
    c_kv = _rmsnorm(kv[:, :C], _cut(blocks["kv_a_norm"], sub), spec.rms_eps)
    if spec.scale_kv_lora:
        c_kv = c_kv * math.sqrt(E / C)
    k_rope = _rope_pairs(kv[:, C:], theta)                            # (S, rope)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def head(n):
        mine = (*sub, (3, n))
        q = c_q @ _cut(blocks["w_qb"], mine)                          # (S, Dq)
        q_nope, q_rope = q[:, :Dq - rope], _rope_pairs(q[:, Dq - rope:], theta)
        k_nope = c_kv @ _cut(blocks["w_kb"], mine)
        scores = (q_nope @ k_nope.T + q_rope @ k_rope.T) / math.sqrt(Dq)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return probs @ (c_kv @ _cut(blocks["w_vb"], mine))            # (S, Dv)

    o = lax.map(head, jnp.arange(H))                                  # (H, S, Dv)
    return jnp.einsum("hsv,hve->se", o, _cut(blocks["wo"], sub))


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def _dense(g, blocks, i, s):
    """Sub-layer ``s`` of layer ``i``'s dense SwiGLU, ``DENSE_BLOCK`` of its
    columns at a time (they add up: the activation is column by column)."""
    sub = ((0, i), (1, s))
    M = blocks["wd_gate"].shape[-1]
    width = next(w for w in range(min(M, DENSE_BLOCK), 0, -1) if M % w == 0)

    def block(total, b):
        lo = b * width
        return total + _swiglu(
            g, _cut(blocks["wd_gate"], sub, (3, lo, width)),
            _cut(blocks["wd_up"], sub, (3, lo, width)),
            _cut(blocks["wd_down"], sub, (2, lo, width)),
        ), None

    return lax.scan(block, jnp.zeros_like(g), jnp.arange(M // width))[0]


def _moe(g, blocks, i, spec: Spec, forced=None):
    """Layer ``i``'s expert layer.  g: (S, E) normed -> (its output (S, E),
    the reference's own choice (S, k) of router outputs in order of falling
    selection score, its margin (S,): k-th minus (k+1)-th selection score).
    ``forced`` (S, k): the outputs to APPLY instead of the reference's own
    choice; the weights are the reference's scores of the forced outputs."""
    k = spec.experts_per_token
    layer = ((0, i),)
    score = jax.nn.softmax(g @ _cut(blocks["w_router"], layer), axis=-1)   # (S, outputs)
    biased = score + _cut(blocks["router_bias"], layer)
    ranked = jnp.argsort(-biased, axis=-1, stable=True)
    chosen = ranked[:, :k]
    by_rank = jnp.take_along_axis(biased, ranked, axis=-1)
    margin = by_rank[:, k - 1] - by_rank[:, k]
    used = chosen if forced is None else forced
    weight = jnp.take_along_axis(score, used, axis=-1) * spec.routed_scaling_factor

    def expert(total, e):
        mine = (*layer, (1, e))
        w = jnp.where(used == spec.expert_offset + e, weight, 0.0).sum(-1)
        return total + w[:, None] * _swiglu(
            g, _cut(blocks["w_gate"], mine), _cut(blocks["w_up"], mine),
            _cut(blocks["w_down"], mine)), None

    held = blocks["w_gate"].shape[1]
    y = lax.scan(expert, jnp.zeros_like(g), jnp.arange(held))[0]
    identity = jnp.where(used >= spec.n_routed_experts, weight, 0.0).sum(-1)
    return y + identity[:, None] * g, chosen, margin


def layer(x, blocks, i, spec: Spec, forced=None):
    """Double layer ``i`` of the stacked tree: the equations at the top."""
    def normed(x, name, s):
        return _rmsnorm(x, _cut(blocks[name], ((0, i), (1, s))), spec.rms_eps)

    x = x + _mla(normed(x, "attn_norm", 0), blocks, i, 0, spec)
    g = normed(x, "mlp_norm", 0)
    m, chosen, margin = _moe(g, blocks, i, spec, forced)
    x = x + _dense(g, blocks, i, 0)
    x = x + _mla(normed(x, "attn_norm", 1), blocks, i, 1, spec)
    return x + _dense(normed(x, "mlp_norm", 1), blocks, i, 1) + m, chosen, margin


_layer = jax.jit(layer, static_argnums=(3,))


def forward(params, tokens, spec: Spec, experts=None):
    """tokens (S,) int32 -> (the final-normed hidden states (S, E) float32,
    {"experts": (L, S, k) the reference's own choices of router outputs,
    "expert_margin": (L, S)}).  ``experts`` (L, S, k): the choices every
    token is GIVEN (the system's)."""
    blocks = params["blocks"]
    chose, margins = [], []
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed"][tokens].astype(jnp.float32)
        for i in range(blocks["w_router"].shape[0]):
            forced = None if experts is None else experts[i]
            x, chosen, margin = _layer(x, blocks, jnp.int32(i), spec, forced)
            chose.append(chosen)
            margins.append(margin)
        x = _rmsnorm(x, params["final_norm"].astype(jnp.float32), spec.rms_eps)
    return x, {"experts": jnp.stack(chose), "expert_margin": jnp.stack(margins)}


def logits(params, x, head_rows: int = 16384):
    """Normed states (N, E) -> logits (N, V) float32, the output head
    applied in equal slices of at most ``head_rows`` rows."""
    head = params["lm_head"]
    vocab = head.shape[0]
    parts = next(k for k in range(1, vocab + 1)
                 if vocab % k == 0 and vocab // k <= head_rows)
    rows = vocab // parts
    with jax.default_matmul_precision("highest"):
        return jnp.concatenate(
            [_head_rows(x, head, k * rows, rows) for k in range(parts)], axis=-1
        )
