"""MiMo-V2-Flash's decoder forward (``XiaomiMiMo/MiMo-V2-Flash``,
``config.json``'s keys, ``model_type`` ``mimo_v2_flash``), plainly: float32
``jax.numpy``, matmul precision ``highest``, no cache, no kernel, no
batching, one sequence.

A LAYER (``N`` an RMSNorm with its own scale, eps ``layernorm_epsilon``; no
biases)::

    h = x + Attn_kind(N_1(x));    y = h + FFN(N_2(h))

``Attn_kind``, kind in {full, window} by ``hybrid_layer_pattern`` (0 full, 1
window), H = ``num_attention_heads`` query heads, KV = ``num_key_value_heads``
(full) | ``swa_num_key_value_heads`` (window), D = ``head_dim`` = 192, D_v =
``v_head_dim`` = 128, n the normed input:

* ``q = W_q n`` (H x D), ``k = W_k n`` (KV x D), ``v = a W_v n`` (KV x D_v),
  ``a = attention_value_scale`` (0.707) on v;
* the FIRST ``int(D x partial_rotary_factor)`` = 64 values of every q and k
  head turn, half-split pairs (i, i + 32), base ``rope_theta`` (full) |
  ``swa_rope_theta`` (window); the other 128 stay; no q/k norm;
* ``s[t, j] = q_t . k_j / sqrt(D)``; visible ``j <= t`` (full), ``t - W < j
  <= t`` with W = ``sliding_window`` = 128 (window: the query's own key and
  the 127 before it);
* a window layer's softmax has one learned float ``b_h`` a query head in its
  denominator, which carries no value (``add_swa_attention_sink_bias``):
  ``p[t, j] = exp(s[t, j]) / (exp(b_h) + sum_j' exp(s[t, j']))``; a full
  layer's is the plain softmax;
* ``o = W_o concat_h(sum_j p[t, j] v_j)``.

``FFN``: the first ``first_dense`` layers a SwiGLU of width
``intermediate_size``; the others ``s = sigmoid(W_r n)`` over
``n_routed_experts`` experts, the top ``num_experts_per_tok`` of ``s + bias``
chosen (the bias in the choice only), weights ``s_chosen / sum s_chosen``,
``sum_e w_e SwiGLU_e(n)`` of width ``moe_intermediate_size``; no shared
expert.  Final RMSNorm, untied output head.

THE CHIP'S SHARE: the tree's expert tensors hold experts ``expert_offset ..
expert_offset + held`` of the router's ``n_routed_experts``; only those
contribute, in the program and here alike (the weights are still divided by
the sum over ALL chosen).  Because one swapped eighth choice switches a held
expert's term on or off, ``forward`` can be GIVEN the system's choices
(``experts``): the logits are then compared under the same routing, and the
routers are held to account apart, choice by choice (``jobs/serve_swa.py``).

It reads the program's parameter tree — one stack a (kind, feed-forward)
pair, every leaf stacked over its own layers: ``dense_blocks`` (the leading
dense full layers), ``swa_blocks`` (window layers, with ``sink`` (layers,
H)), ``blocks`` (full expert layers) — and imports nothing of the program.
No layer is cast whole: every use cuts its own block out of the stacked leaf
(``_cut``: a head, an expert, 2,048 columns of the dense SwiGLU) and sums as
it goes; attention runs a head and ``QUERY_BLOCK`` queries at a time over
the keys that block may see, so 12,288 tokens fit beside what is live.

``Spec`` carries every number of the equations, so a comparison can also be
run with ONE piece left out or bent (``softmax_dtype`` bfloat16, ``sink``
False, ``value_scale`` 1, a kind's base, ``window`` +- 1, ``rotary_dim``
192): the configuration's ``reference_tolerance`` says what each reads.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

FULL, SLIDING = "full_attention", "sliding_attention"
#: queries that attend at once, and columns of the dense SwiGLU cast at once
QUERY_BLOCK = 1024
DENSE_BLOCK = 2048


class Spec(NamedTuple):
    """What the parameter tree's shapes do not say."""

    layer_types: tuple
    first_dense: int
    rope_theta: float
    swa_rope_theta: float
    window: int
    rotary_dim: int
    value_scale: float
    rms_eps: float
    experts_per_token: int
    expert_offset: int = 0
    sink: bool = True
    softmax_dtype: str = "float32"


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


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


def _turn(x, theta, rotary_dim):
    """x (S, D): the first ``rotary_dim`` values turned, pairs (i, i +
    rotary_dim / 2) of token t by ``t theta**(-2i / rotary_dim)``."""
    S = x.shape[0]
    half = rotary_dim // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    a, b = x[:, :half], x[:, half:rotary_dim]
    return jnp.concatenate(
        [a * jnp.cos(ang) - b * jnp.sin(ang), b * jnp.cos(ang) + a * jnp.sin(ang),
         x[:, rotary_dim:]], axis=-1)


def _attention(n, stack, i, kind, spec: Spec):
    """Layer ``i`` of ``stack``'s attention of ``kind``.  n (S, E) normed
    -> (S, E)."""
    S = n.shape[0]
    layer = ((0, i),)
    H, D = stack["wq"].shape[2:]
    KV = stack["wk"].shape[2]
    window = spec.window if kind == SLIDING else 0
    theta = spec.swa_rope_theta if kind == SLIDING else spec.rope_theta
    sunk = kind == SLIDING and spec.sink
    low = jnp.dtype(spec.softmax_dtype)
    pos = jnp.arange(S)

    def head(h):
        kv = h // (H // KV)
        q = _turn(n @ _cut(stack["wq"], (*layer, (2, h))), theta, spec.rotary_dim)
        k = _turn(n @ _cut(stack["wk"], (*layer, (2, kv))), theta, spec.rotary_dim)
        v = (n @ _cut(stack["wv"], (*layer, (2, kv)))) * spec.value_scale
        out = []
        for lo in range(0, S, QUERY_BLOCK):
            hi = min(S, lo + QUERY_BLOCK)
            first = max(0, lo - window + 1) if window else 0
            s = (q[lo:hi] @ k[first:hi].T) / math.sqrt(D)
            t, j = pos[lo:hi, None], pos[None, first:hi]
            seen = (j <= t) & ((t - j < window) if window else True)
            s = jnp.where(seen, s, -jnp.inf).astype(low)
            top = s.max(-1, keepdims=True)
            under = 0.0
            if sunk:
                b = _cut(stack["sink"], (*layer, (1, h))).astype(low)
                top = jnp.maximum(top, b)
                under = jnp.exp(b - top)
            e = jnp.exp(s - top)
            p = e / (e.sum(-1, keepdims=True) + under)
            out.append(p.astype(jnp.float32) @ v[first:hi])
        return jnp.concatenate(out)                              # (S, D_v)

    o = lax.map(head, jnp.arange(H))                             # (H, S, D_v)
    return jnp.einsum("hsv,hve->se", o, _cut(stack["wo"], layer))


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def _dense(g, stack, i):
    """Layer ``i``'s dense SwiGLU, ``DENSE_BLOCK`` of its columns at a time
    (they add up: the activation is column by column)."""
    layer = ((0, i),)
    M = stack["w_gate"].shape[-1]
    width = next(w for w in range(min(M, DENSE_BLOCK), 0, -1) if M % w == 0)

    def block(total, b):
        lo = b * width
        return total + _swiglu(
            g, _cut(stack["w_gate"], layer, (2, lo, width)),
            _cut(stack["w_up"], layer, (2, lo, width)),
            _cut(stack["w_down"], layer, (1, lo, width)),
        ), None

    return lax.scan(block, jnp.zeros_like(g), jnp.arange(M // width))[0]


def _moe(g, stack, i, spec: Spec, forced=None):
    """Layer ``i``'s expert layer.  g (S, E) normed -> (its output (S, E),
    the reference's own choice (S, k) in order of falling selection score,
    its margin (S,): k-th minus (k+1)-th selection score).  ``forced`` (S,
    k): the experts to APPLY instead; the weights are the reference's
    scores of the forced experts, divided by their sum."""
    k = spec.experts_per_token
    layer = ((0, i),)
    score = jax.nn.sigmoid(g @ _cut(stack["w_router"], layer))    # (S, experts)
    biased = score + _cut(stack["router_bias"], layer)
    ranked = jnp.argsort(-biased, axis=-1, stable=True)
    chosen = ranked[:, :k]
    by_rank = jnp.take_along_axis(biased, ranked, axis=-1)
    margin = by_rank[:, k - 1] - by_rank[:, k]
    used = chosen if forced is None else forced
    weight = jnp.take_along_axis(score, used, axis=-1)
    weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)

    def expert(total, e):
        mine = (*layer, (1, e))
        w = jnp.where(used == spec.expert_offset + e, weight, 0.0).sum(-1)
        return total + w[:, None] * _swiglu(
            g, _cut(stack["w_gate"], mine), _cut(stack["w_up"], mine),
            _cut(stack["w_down"], mine)), None

    held = stack["w_gate"].shape[1]
    return lax.scan(expert, jnp.zeros_like(g), jnp.arange(held))[0], chosen, margin


def layer(x, stack, i, kind, spec: Spec, forced=None):
    """Layer ``i`` of ``stack`` (kind ``kind``; an expert layer where the
    stack has a router): the equations at the top."""
    at = ((0, i),)
    x = x + _attention(_rmsnorm(x, _cut(stack["attn_norm"], at), spec.rms_eps),
                       stack, i, kind, spec)
    g = _rmsnorm(x, _cut(stack["mlp_norm"], at), spec.rms_eps)
    if "w_router" not in stack:
        return x + _dense(g, stack, i), None, None
    y, chosen, margin = _moe(g, stack, i, spec, forced)
    return x + y, chosen, margin


_layer = jax.jit(layer, static_argnums=(3, 4))


def stack_of(spec: Spec):
    """[(the tree's stack, the layer's index in it, its kind)] in layer order."""
    seen, out = {}, []
    for i, kind in enumerate(spec.layer_types):
        name = ("dense_" if i < spec.first_dense else "") + (
            "swa_blocks" if kind == SLIDING else "blocks")
        out.append((name, seen.get(name, 0), kind))
        seen[name] = seen.get(name, 0) + 1
    return out


def forward(params, tokens, spec: Spec, experts=None):
    """tokens (S,) int32 -> (the final-normed hidden states (S, E) float32,
    {"experts": (expert layers, S, k) the reference's own choices,
    "expert_margin": (expert layers, S)}).  ``experts`` (expert layers, S,
    k): the choices every token is GIVEN (the system's)."""
    chose, margins = [], []
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed"][tokens].astype(jnp.float32)
        for name, i, kind in stack_of(spec):
            forced = None
            if experts is not None and "w_router" in params[name]:
                forced = experts[len(chose)]
            x, chosen, margin = _layer(x, params[name], jnp.int32(i), kind, spec, forced)
            if chosen is not None:
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
        return jnp.concatenate([
            x @ lax.dynamic_slice_in_dim(head, k * rows, rows, axis=0).astype(jnp.float32).T
            for k in range(parts)
        ], axis=-1)
