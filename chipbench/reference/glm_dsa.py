"""GLM-5's decoder forward (``model_type`` ``glm_moe_dsa``), plainly:
float32 ``jax.numpy``, matmul precision ``highest``, no cache, no
absorption of the latent projections, no sorting of rows by expert, no
kernel; the indexer's selection by ``argsort`` of its scores.

Follows ``zai-org/GLM-5``'s ``config.json`` and the published
descriptions its keys point to: multi-head latent attention as
DeepSeek-V2/V3 define it, the lightning indexer of DeepSeek-V3.2's
sparse attention, DeepSeek-V3's ``noaux_tc`` router.  One pre-norm
block, ``x`` of width E, token ``t``:

1. ``h = RMSNorm(x; attn_norm)``.  ``c_q = RMSNorm(h W_qa; q_a_norm)``
   (``q_lora_rank``); ``q = c_q W_qb`` -> H heads of ``[q_nope | q_rope]``
   (``qk_nope_head_dim`` | ``qk_rope_head_dim``); ``q_rope <- RoPE(q_rope,
   t)`` over INTERLEAVED pairs (x[2i], x[2i+1]) with base ``rope_theta``.
2. ``[c_kv | k_rope] = h W_kva`` (``kv_lora_rank`` | rope); ``c_kv <-
   RMSNorm(c_kv; kv_a_norm)``; ``k_rope <- RoPE(k_rope, t)``, ONE rotary
   key for all heads.  Head h: ``k_nope = c_kv W_kb,h``, ``v = c_kv W_vb,h``.
3. Indexer: ``q^I = c_q W_iq`` (J heads of Di), ``k^I = LayerNorm(h
   W_ik; ik_norm, ik_bias)`` (eps 1e-6), the first ``qk_rope_head_dim``
   of both rotated the same way, ``w = h W_iw`` (J).  ``I(t, s) = sum_j
   w_j relu(q^I_j(t) . k^I(s))`` for s <= t.  ``S_t`` = the ``min(index_topk,
   t + 1)`` keys s <= t of largest I, ties to the lower s.
4. ``score_h(t, s) = (q_nope . k_nope(s) + q_rope . k_rope(s)) / sqrt(nope
   + rope)`` for s in ``S_t``; softmax over ``S_t``; ``o_h = sum_s p_s
   v_h(s)``; ``x += [o_1 .. o_H] W_o``.
5. ``h = RMSNorm(x; mlp_norm)``.  A dense block: ``x += W_down(silu(W_gate
   h) * W_up h)``.  An expert block: ``s = sigmoid(h W_r)`` (all
   ``n_routed_experts``), the ``k`` experts of largest ``s + b`` (b: the
   selection-only bias; ``n_group`` = ``topk_group`` = 1: no group
   limit), ``g_e = scale * s_e / sum of the chosen s`` (``norm_topk_prob``,
   ``routed_scaling_factor``), ``x += sum_e g_e E_e(h) + E_shared(h)``,
   every E a SwiGLU.
6. Final RMSNorm, untied output head.

Left out, as the configuration's file lists (``changed`` / ``assumed``):
the multi-token-prediction module (a draft head, not part of the
model's own next-token distribution), the published inference code's
Hadamard rotation of ``q^I`` and ``k^I`` (orthogonal: every dot product
is unchanged) and its FP8 storage of ``k^I`` (the configuration states
bfloat16), the positive constants on ``w`` (they change no order).

THE CHIP'S SHARE.  It reads the program's parameter tree
(``ray_tpu/models/llama.py``: ``dense_blocks`` and ``blocks`` stacked on
a leading layer axis), whose expert tensors hold the experts
``expert_offset .. expert_offset + held`` of the router's
``n_routed_experts``: only those contribute, as in the program (the
guide's section 4); a token none of whose experts is held gets the
shared expert alone.  With all experts held it is the whole layer.

Computed a layer at a time, a head and an expert at a time inside it,
each matrix cast to float32 where it is used, so that at the published
widths it fits beside the replica's weights and cache on one chip.

``forward`` also returns every (layer, token)'s selected set, chosen
experts, and the margins (k-th minus (k+1)-th) of the indexer's and the
router's selection scores: where a margin is below the rounding of the
system's precision the system may choose otherwise and be right in its
own arithmetic.  That matters more here than for a whole expert layer:
with one expert in sixteen held, a token's routed part is one expert's
term or nothing, so ONE swapped eighth expert switches the whole routed
part of that (layer, token) on or off.  So ``forward`` can be GIVEN the
system's expert choices (``experts``): the logits then compare the
arithmetic, and the choices are compared for themselves.

``attend_all`` switches the selection off (every visible key attended
to): for the tests that show the comparison refuses a program without
the mechanism.

THE LIMITS (``reference_tolerance`` in ``chipbench/configs/
glm-5-ep16-l6.json``; the comparison is ``jobs/serve_dsa.py``'s
``system_run`` / ``against_reference`` / ``passes``: a 2,560-token
prompt — 1.25 x ``index_topk``, so the last fifth of its queries and
both decode steps must choose — through ``llama.prefill_into_slot`` and
two ``llama.decode_step_rowwise`` steps in the engine's own cache: THE
LOGITS COMPARED ARE THOSE TWO SERVED EXECUTABLES'.  They hand back no
choices, so the same tokens then go through ``llama.choices_cached``,
the same step compiled with its choices as outputs, which lends the
selected sets and the experts, and whose logits have to be the served
programs' bit for bit: 0 of 3 x 19,360 differed in every reading
below).  Each limit lies between two readings taken on the chip at the
published widths (my chip runs, PR 30: calls 10 and 13, the served
programs, each 8 weight seeds x 2 prompts, bf16 weights, activations
and cache, and the same programs on weights with their mantissa cut to
float8 e4m3's 3 bits, 3 seeds x 2 prompts — call 10 with the selection
bias as drawn, call 13 with it balanced as it is served
(``jobs/serve_dsa.py:balance_router``), and the two read alike; call 5,
the choices-returning program alone, 8 x 3 and 4 x 2; the cell's own
runs):

- logits, the reference given the system's experts: rms and max of
  |system - reference| / std(reference) over 3 positions x 19,360.
  bf16: rms 0.021-0.051, max 0.13-0.26.  Cut to 3 bits: rms 0.163-0.176,
  max 0.70-0.87 (calls 10 / 13: bf16 0.027-0.046 / 0.13-0.26, cut
  0.165-0.176 / 0.71-0.83).  Limits **rms 0.09, max 0.45**: 1.8x / 1.7x the largest
  honest reading, 0.55x / 0.64x the smallest cut one.  (Free-running,
  the reference choosing its own experts, bf16 reads rms 0.036-0.120 and
  max 0.17-0.79 over 25 readings, in two clusters — a held expert
  switched on or off at one of the three compared positions, or not —
  and the cut reads 0.17-0.24: they overlap, which is why the
  comparison gives the choices.)  Every key attended to (selection off,
  the system's ``index_topk`` at the cache's length): rms 0.39-0.40, max
  1.69-1.84.
- selected sets, over the (layer, query) pairs that see more than
  ``index_topk`` keys: every set exactly 2,048 keys (selection off: no);
  mean share of the reference's set the system's holds, bf16
  0.9969-0.9972, cut 0.9824-0.9827, floor **0.99**; share of sets EQUAL
  to the reference's, bf16 0.084-0.112 (the 2,048th and 2,049th of 2,560
  index scores differ by 0.09 at the median, of scores that bf16
  rounds by as much), cut 0.004-0.008, floor **0.03**.
- experts: share of (expert layer, token) pairs that chose another set
  of 8 than the reference did from its own float32 state, bf16
  0.104-0.119 (median margin 0.0030 between the 8th and 9th of 256
  scores), cut 0.70-0.71, limit **0.3**; and, because the logits are
  compared under the system's own choices and so cannot see a router
  that chooses wrongly, the LARGEST reference margin among the swapped
  pairs — how clear a call the system overturned: bf16 0.0069-0.0150
  over 52 readings (all but one under 0.0127), cut 0.0252-0.0406 over
  12, limit **0.022** (1.47x / 0.87x).  What it is for is not the cut,
  which four other limits refuse: a selection bias or a score that is
  wrong for a few experts overturns clear calls while the share swapped
  stays under 0.3 (PERF.md section 4 has such a program's reading;
  ``tests/test_llama_mla_dsa.py`` a small one).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from chipbench.reference.llama import _head_rows, _rmsnorm

STACKS = ("dense_blocks", "blocks")
EXPERT_TENSORS = ("w_gate", "w_up", "w_down")
INDEX_NORM_EPS = 1e-6


class Spec(NamedTuple):
    """What the parameter tree's shapes do not say."""

    rope_theta: float
    rms_eps: float
    qk_rope_head_dim: int
    index_topk: int
    experts_per_token: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    expert_offset: int = 0
    attend_all: bool = False


def _rope_pairs(x, theta):
    """x: (S, ..., D): turn pair (x[2i], x[2i+1]) of token t by t *
    theta**(-2i/D)."""
    S, D = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape(S, *(1,) * (x.ndim - 2), D // 2)
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                     odd * jnp.cos(ang) + even * jnp.sin(ang)], axis=-1)
    return out.reshape(x.shape)


def _partly_rotated(x, rope, theta):
    return jnp.concatenate([_rope_pairs(x[..., :rope], theta), x[..., rope:]], axis=-1)


def _swiglu(h, w_gate, w_up, w_down):
    f = jnp.float32
    return (jax.nn.silu(h @ w_gate.astype(f)) * (h @ w_up.astype(f))) @ w_down.astype(f)


def _attention(h, p, spec: Spec):
    f = jnp.float32
    S = h.shape[0]
    rope, theta = spec.qk_rope_head_dim, spec.rope_theta
    Q, H, Dq = p["w_qb"].shape
    C = p["w_kb"].shape[0]
    c_q = _rmsnorm(h @ p["w_qa"].astype(f), p["q_a_norm"].astype(f), spec.rms_eps)
    kv = h @ p["w_kva"].astype(f)
    c_kv = _rmsnorm(kv[:, :C], p["kv_a_norm"].astype(f), spec.rms_eps)
    k_rope = _rope_pairs(kv[:, C:], theta)                            # (S, rope)
    causal = jnp.tril(jnp.ones((S, S), bool))

    # ---- the indexer: I (S, S), a head at a time
    k_i = h @ p["w_ik"].astype(f)
    k_i = k_i - k_i.mean(-1, keepdims=True)
    k_i = k_i / jnp.sqrt((k_i * k_i).mean(-1, keepdims=True) + INDEX_NORM_EPS)
    k_i = _partly_rotated(
        k_i * p["ik_norm"].astype(f) + p["ik_bias"].astype(f), rope, theta)
    w_i = h @ p["w_iw"].astype(f)                                     # (S, J)

    def index_head(args):
        w_iq, w = args                                                # (Q, Di), (S,)
        q_i = _partly_rotated(c_q @ w_iq.astype(f), rope, theta)
        return w[:, None] * jax.nn.relu(q_i @ k_i.T)

    index = jax.lax.map(index_head, (p["w_iq"].swapaxes(0, 1), w_i.T)).sum(0)
    index = jnp.where(causal, index, -jnp.inf)
    order = jnp.argsort(-index, axis=-1, stable=True)                 # best first
    rank = jnp.argsort(order, axis=-1)
    k = spec.index_topk
    selected = causal if spec.attend_all else causal & (rank < k)
    if S > k:
        ranked = jnp.take_along_axis(index, order[:, :k + 1], axis=-1)
        margin = ranked[:, k - 1] - ranked[:, k]       # inf while t + 1 <= k
    else:
        margin = jnp.full((S,), jnp.inf)

    # ---- attention, a head at a time
    def head(args):
        w_qb, w_kb, w_vb = (a.astype(f) for a in args)  # (Q, Dq), (C, Dn), (C, Dv)
        q = c_q @ w_qb
        q_nope, q_rope = q[:, :Dq - rope], _rope_pairs(q[:, Dq - rope:], theta)
        scores = (q_nope @ (c_kv @ w_kb).T + q_rope @ k_rope.T) / math.sqrt(Dq)
        probs = jax.nn.softmax(jnp.where(selected, scores, -jnp.inf), axis=-1)
        return probs @ (c_kv @ w_vb)                                  # (S, Dv)

    o = jax.lax.map(head, (p["w_qb"].swapaxes(0, 1), p["w_kb"].swapaxes(0, 1),
                           p["w_vb"].swapaxes(0, 1)))                 # (H, S, Dv)
    wo = p["wo"].astype(f)                                            # (H, Dv, E)
    return jnp.einsum("hsv,hve->se", o, wo), selected, margin


def _experts(h, p, spec: Spec, forced=None):
    """``forced`` (S, k): the experts to APPLY instead of the reference's
    own choice (which is still what is returned, with its margin): the
    weights are the reference's scores of the forced experts."""
    f = jnp.float32
    k = spec.experts_per_token
    score = jax.nn.sigmoid(h @ p["w_router"].astype(f))               # (S, X)
    biased = score + p["router_bias"].astype(f)
    ranked = jnp.argsort(-biased, axis=-1, stable=True)
    chosen = ranked[:, :k]
    by_rank = jnp.take_along_axis(biased, ranked, axis=-1)
    margin = by_rank[:, k - 1] - by_rank[:, k]
    used = chosen if forced is None else forced
    weight = jnp.take_along_axis(score, used, axis=-1)
    if spec.norm_topk_prob:
        weight = weight / weight.sum(-1, keepdims=True)
    weight = weight * spec.routed_scaling_factor

    def one_expert(args):
        e, w_gate, w_up, w_down = args
        w = jnp.where(used == e, weight, 0.0).sum(-1)                 # (S,)
        return _swiglu(h, w_gate, w_up, w_down) * w[:, None]

    held = spec.expert_offset + jnp.arange(p["w_gate"].shape[0])
    y = jax.lax.map(one_expert, (held, p["w_gate"], p["w_up"], p["w_down"])).sum(0)
    if "ws_gate" in p:
        y = y + _swiglu(h, p["ws_gate"], p["ws_up"], p["ws_down"])
    return y, chosen, margin


@functools.partial(jax.jit, static_argnums=(3,))
def _layer(x, blocks, i, spec: Spec, forced=None):
    """Block ``i`` of one stack; every matrix is cast to float32 where
    it is used, a head and an expert at a time."""
    f = jnp.float32
    p = {k: jax.lax.dynamic_index_in_dim(a, i, keepdims=False) for k, a in blocks.items()}
    h = _rmsnorm(x, p["attn_norm"].astype(f), spec.rms_eps)
    attn, selected, select_margin = _attention(h, p, spec)
    x = x + attn
    h = _rmsnorm(x, p["mlp_norm"].astype(f), spec.rms_eps)
    if "w_router" not in p:
        return x + _swiglu(h, p["w_gate"], p["w_up"], p["w_down"]), selected, select_margin, None, None
    y, chosen, expert_margin = _experts(h, p, spec, forced)
    return x + y, selected, select_margin, chosen, expert_margin


def forward(params, tokens, spec: Spec, positions, head_rows: int = 16384,
            experts=None):
    """``experts`` (expert layers, S, k) int32: the experts every token
    is GIVEN in every expert layer (the system's choices), instead of the
    reference's own; the reference's own choice at each layer — from its
    float32 state, which then followed the given choices through the
    layers before — is what it returns either way.

    tokens (S,) int32 -> (logits (len(positions), V) float32 at the
    given positions, {"selected": (L, S, S) bool — [l, t, s]: key s is
    in token t's set in layer l, "select_margin": (L, S) (inf while the
    set is every visible key), "experts": (expert layers, S, k) int32 in
    order of falling selection score, "expert_margin": (expert layers,
    S)})."""
    out = {"selected": [], "select_margin": [], "experts": [], "expert_margin": []}
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed"][tokens].astype(jnp.float32)
        for stack in STACKS:
            if stack not in params:
                continue
            routed = "w_router" in params[stack]
            for i in range(params[stack]["attn_norm"].shape[0]):
                forced = experts[i] if routed and experts is not None else None
                x, selected, s_margin, chosen, e_margin = _layer(
                    x, params[stack], i, spec, forced)
                out["selected"].append(selected)
                out["select_margin"].append(s_margin)
                if chosen is not None:
                    out["experts"].append(chosen)
                    out["expert_margin"].append(e_margin)
        x = _rmsnorm(x, params["final_norm"].astype(jnp.float32), spec.rms_eps)
        x = x[jnp.asarray(positions)]
        head = params["lm_head"]
        vocab = head.shape[0]
        parts = next(k for k in range(1, vocab + 1)
                     if vocab % k == 0 and vocab // k <= head_rows)
        rows = vocab // parts
        logits = jnp.concatenate(
            [_head_rows(x, head, k * rows, rows) for k in range(parts)], axis=-1
        )
    return logits, {k: jnp.stack(v) for k, v in out.items() if v}
