"""JoyAI-LLM-Flash's decoder forward (``model_type`` ``joyai_llm_flash``),
its multi-token-prediction (MTP) module and the speculative-sampling rule,
plainly: float32 ``jax.numpy``, matmul precision ``highest``, no cache, no
absorption of the latent projections, no kernel, no batching, one sequence.

Follows ``jdopensource/JoyAI-LLM-Flash``'s ``config.json``, every key of
which is DeepSeek-V3's, and the DeepSeek-V3 technical report its keys point
to.  One pre-norm block, ``x`` of width E, token ``t``:

1. ``h = RMSNorm(x; attn_norm)``.  ``c_q = RMSNorm(h W_qa; q_a_norm)``
   (``q_lora_rank``); ``q = c_q W_qb`` -> H heads of ``[q_nope | q_rope]``;
   ``q_rope <- RoPE(q_rope, t)`` over INTERLEAVED pairs, base ``rope_theta``,
   no scaling (``rope_scaling`` null).
2. ``[c_kv | k_rope] = h W_kva``; ``c_kv <- RMSNorm(c_kv; kv_a_norm)``;
   ``k_rope <- RoPE(k_rope, t)``, ONE rotary key for all heads.  Head h:
   ``k_nope = c_kv W_kb,h``, ``v = c_kv W_vb,h``.
3. ``score_h(t, s) = (q_nope . k_nope(s) + q_rope . k_rope(s)) / sqrt(nope +
   rope)`` for EVERY s <= t (no indexer); softmax; ``o_h = sum_s p_s v_h(s)``;
   ``x += [o_1 .. o_H] W_o``.
4. ``h = RMSNorm(x; mlp_norm)``.  The leading dense block: ``x +=
   W_down(silu(W_gate h) * W_up h)``.  An expert block: DeepSeek-V3's
   ``noaux_tc`` router with one group, a shared expert beside the routed
   ones (``glm_dsa._experts``: the same router, written there).
5. Final RMSNorm ``h_t``, untied output head.

The MTP module (report section 2.2; depth 1), for the pair (the main
model's final-normed ``h_i``, the next token ``t_{i+1}``), i = 0 .. S - 2:
``x_i = [RMSNorm(Emb(t_{i+1}); enorm) ; RMSNorm(h_i; hnorm)] W_eh``; one
expert block as above over the pairs' sequence (rotary position i, causal);
``RMSNorm(. ; head_norm)``; the main model's head.  Its logits at pair i are
over the token at position i + 2.

Speculative sampling (Leviathan et al. 2023, Chen et al. 2023), one drafted
token, at temperature T > 0 with ``p = softmax(main logits / T)``, ``q =
softmax(module logits / T)``: the draft ``d ~ q``; accepted iff ``u < min(1,
p(d) / q(d))``, ``u`` uniform on [0, 1); accepted: the token behind it is
drawn from the main model's next distribution; rejected: the token in its
place is drawn from ``norm(max(p - q, 0))``.  THE KEY SCHEDULE, shared with
the program as written words, not as code: the key of a draw is
``fold_in(fold_in(fold_in(key(seed), request), position), purpose)`` with
``position`` that of the token the draw decides and ``purpose`` 0 a token
from the main model's own distribution, 1 the draft, 2 the uniform number, 3
the token in a rejected draft's place; categorical draws by
``jax.random.categorical`` over ``logits / T`` (over ``log max(p - q, 0)``
for the residual), the uniform by ``jax.random.uniform``.

DEPARTURES, as the configuration's file lists them (``assumed``): the order
inside the concatenation is the checkpoint loaders' (embedding first; the
report writes the hidden state first: with seeded weights a permutation of
``W_eh``'s rows); the hidden state fed is the one AFTER the main model's
final norm; the pair's rotary position is i.  The module is taken to be
DeepSeek-V3's at all: the config says only ``num_nextn_predict_layers: 1``.

THE CHIP'S SHARE: as ``glm_dsa``: the tree's expert tensors hold the experts
``expert_offset .. expert_offset + held`` of the router's 256; only those
contribute, in the program and here alike.  Because one swapped eighth
expert switches a held expert's term on or off, ``forward`` and
``module_forward`` can be GIVEN the system's expert choices.

THE LIMITS (``reference_tolerance`` in ``chipbench/configs/
joyai-llm-flash-ep32.json``; the comparison is ``jobs/serve_mtp.py``'s
``system_run`` / ``against_reference`` / ``passes``: one check prompt of
512 and one of 1,536 ids through the two served executables, 12
speculative steps).  Each lies between two readings taken on the chip at
the published widths (my chip runs, PR 32: 12 weight seeds as served, bf16
weights, activations and cache; 3 with the weights' mantissa cut to float8
e4m3's 3 bits, the reference reading the weights as served), at their
geometric mean — 3x the largest honest reading, a third of the smallest cut
one:

- the main model's logits at every verified position, rms and max of
  |system - reference| / std(reference): bf16 0.0150-0.0161 / 0.073-0.084,
  cut 0.148-0.152 / 0.70-0.82: limits **0.05 / 0.24**;
- the module's draft logits: bf16 0.0111-0.0117 / 0.051-0.058, cut
  0.112-0.114 / 0.53-0.69: limits **0.036 / 0.17**;
- expert sets that differ from the reference's own choice (main layers and
  the module's): bf16 11.2-11.8% of (layer, token) pairs, cut 76-77%: limit
  **0.3**; the largest reference margin (8th minus 9th selection score)
  among them: bf16 0.0078-0.0122, cut 0.054-0.063: limit **0.025** — the
  logits are compared under the system's own choices, so the routers are
  held to account apart, as GLM-5's is;
- the rule's replay on the program's own logits and keys: exact, 0
  mismatches of 24 in every reading; both outcomes among the 24.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from chipbench.reference.glm_dsa import _experts, _rope_pairs, _swiglu
from chipbench.reference.llama import _head_rows, _rmsnorm

STACKS = ("dense_blocks", "blocks")
#: the last word of a draw's key
TOKEN, DRAFT, UNIFORM, RESIDUAL = 0, 1, 2, 3


class Spec(NamedTuple):
    """What the parameter tree's shapes do not say."""

    rope_theta: float
    rms_eps: float
    qk_rope_head_dim: int
    experts_per_token: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    expert_offset: int = 0


def _attention(h, p, spec: Spec):
    """Multi-head latent attention over every visible key, a head at a
    time.  h: (S, E) normed."""
    f = jnp.float32
    S = h.shape[0]
    rope, theta = spec.qk_rope_head_dim, spec.rope_theta
    Dq = p["w_qb"].shape[-1]
    C = p["w_kb"].shape[0]
    c_q = _rmsnorm(h @ p["w_qa"].astype(f), p["q_a_norm"].astype(f), spec.rms_eps)
    kv = h @ p["w_kva"].astype(f)
    c_kv = _rmsnorm(kv[:, :C], p["kv_a_norm"].astype(f), spec.rms_eps)
    k_rope = _rope_pairs(kv[:, C:], theta)                            # (S, rope)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def head(args):
        w_qb, w_kb, w_vb = (a.astype(f) for a in args)  # (Q, Dq), (C, Dn), (C, Dv)
        q = c_q @ w_qb
        q_nope, q_rope = q[:, :Dq - rope], _rope_pairs(q[:, Dq - rope:], theta)
        scores = (q_nope @ (c_kv @ w_kb).T + q_rope @ k_rope.T) / math.sqrt(Dq)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return probs @ (c_kv @ w_vb)                                  # (S, Dv)

    o = jax.lax.map(head, (p["w_qb"].swapaxes(0, 1), p["w_kb"].swapaxes(0, 1),
                           p["w_vb"].swapaxes(0, 1)))                 # (H, S, Dv)
    return jnp.einsum("hsv,hve->se", o, p["wo"].astype(f))


@functools.partial(jax.jit, static_argnums=(3,))
def _layer(x, blocks, i, spec: Spec, forced=None):
    """Block ``i`` of one stack; every matrix is cast to float32 where it
    is used, a head and an expert at a time."""
    f = jnp.float32
    p = {k: jax.lax.dynamic_index_in_dim(a, i, keepdims=False) for k, a in blocks.items()}
    x = x + _attention(_rmsnorm(x, p["attn_norm"].astype(f), spec.rms_eps), p, spec)
    h = _rmsnorm(x, p["mlp_norm"].astype(f), spec.rms_eps)
    if "w_router" not in p:
        return x + _swiglu(h, p["w_gate"], p["w_up"], p["w_down"]), None, None
    y, chosen, margin = _experts(h, p, spec, forced)
    return x + y, chosen, margin


def forward(params, tokens, spec: Spec, experts=None):
    """tokens (S,) int32 -> (the main model's final-normed hidden states
    (S, E) float32, {"experts": (expert layers, S, k) the reference's own
    choices in order of falling selection score, "expert_margin": (expert
    layers, S) k-th minus (k+1)-th selection score}).  ``experts`` (expert
    layers, S, k): the choices every token is GIVEN (the system's)."""
    out = {"experts": [], "expert_margin": []}
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed"][tokens].astype(jnp.float32)
        for stack in STACKS:
            if stack not in params:
                continue
            routed = "w_router" in params[stack]
            for i in range(params[stack]["attn_norm"].shape[0]):
                at = len(out["experts"])
                forced = experts[at] if routed and experts is not None else None
                x, chosen, margin = _layer(x, params[stack], i, spec, forced)
                if chosen is not None:
                    out["experts"].append(chosen)
                    out["expert_margin"].append(margin)
        x = _rmsnorm(x, params["final_norm"].astype(jnp.float32), spec.rms_eps)
    return x, {k: jnp.stack(v) for k, v in out.items() if v}


@functools.partial(jax.jit, static_argnums=(3,))
def _module(params, hidden, nxt, spec: Spec, forced=None):
    f = jnp.float32
    m = params["mtp"]
    emb = params["tok_embed"][nxt].astype(f)
    x = jnp.concatenate([
        _rmsnorm(emb, m["enorm"].astype(f), spec.rms_eps),
        _rmsnorm(hidden, m["hnorm"].astype(f), spec.rms_eps),
    ], axis=-1) @ m["eh_proj"].astype(f)
    x, chosen, margin = _layer.__wrapped__(x, m["block"], 0, spec, forced)
    return _rmsnorm(x, m["head_norm"].astype(f), spec.rms_eps), chosen, margin


def module_forward(params, hidden, tokens, spec: Spec, experts=None):
    """The MTP module over the pairs (``hidden[i]``, ``tokens[i + 1]``), i =
    0 .. len(tokens) - 2: hidden (>= S - 1, E) the main model's final-normed
    states, tokens (S,).  -> (its normed output (S - 1, E), {"experts": (S -
    1, k), "expert_margin": (S - 1,)}); row i's logits are over position i +
    2.  ``experts`` (S - 1, k): the choices GIVEN."""
    n = tokens.shape[0] - 1
    with jax.default_matmul_precision("highest"):
        y, chosen, margin = _module(
            params, jnp.asarray(hidden, jnp.float32)[:n], tokens[1:], spec, experts
        )
    return y, {"experts": chosen, "expert_margin": margin}


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


def draw_key(key, request: int, position: int, purpose: int):
    k = jax.random.fold_in(jax.random.fold_in(key, request), position)
    return jax.random.fold_in(k, purpose)


def draft(q_logits, key, request: int, position: int, temperature: float) -> int:
    """The module's draft for ``position``.  q_logits (V,)."""
    return int(jax.random.categorical(
        draw_key(key, request, position, DRAFT), q_logits / temperature
    ))


def accept(p_logits, q_logits, drafted: int, key, request: int, position: int,
           temperature: float):
    """The rule, for one row.  p_logits (2, V): the main model's at the
    draft's ``position`` and at the one behind it; q_logits (V,): the
    module's at the draft's position.  -> (accepted, [the token at
    ``position``, the token behind it — meaningful where accepted])."""
    p = jax.nn.softmax(jnp.asarray(p_logits[0], jnp.float32) / temperature)
    q = jax.nn.softmax(jnp.asarray(q_logits, jnp.float32) / temperature)
    u = jax.random.uniform(draw_key(key, request, position, UNIFORM))
    accepted = bool(u < jnp.minimum(1.0, p[drafted] / q[drafted]))
    if accepted:
        first = drafted
    else:
        first = int(jax.random.categorical(
            draw_key(key, request, position, RESIDUAL),
            jnp.log(jnp.maximum(p - q, 0.0)),
        ))
    behind = int(jax.random.categorical(
        draw_key(key, request, position + 1, TOKEN),
        jnp.asarray(p_logits[1], jnp.float32) / temperature,
    ))
    return accepted, [first, behind]
