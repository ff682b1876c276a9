"""SDAR-30B-A3B-Chat's decoder forward (``model_type`` ``sdar_moe``) under
its block mask, and the block-diffusion transfer rule, plainly: float32
``jax.numpy``, matmul precision ``highest``, no cache, no kernel, no
batching, one sequence.

Follows ``JetLM/SDAR-30B-A3B-Chat``'s ``config.json`` (its block is
Qwen3-MoE's) and the model card's generation loop, as the writer of ISSUE 36
remembers the card (there is no network here).  One pre-norm block, ``x`` (S,
E), block length B::

    a = RMSNorm(x; attn_norm)                               eps rms_norm_eps
    q = a Wq -> (S, H, D)   k = a Wk -> (S, KV, D)   v = a Wv -> (S, KV, D)   no bias
    q = RMSNorm_D(q; q_norm)   k = RMSNorm_D(k; k_norm)     per head, one (D,) scale for all heads
    q, k = RoPE(q, k; rope_theta, all D values, halves rotated, position = index)
    s[h, i, j] = q[i, h] . k[j, h // (H / KV)] / sqrt(D);   j visible to i  iff  j // B <= i // B
    x = x + softmax_j(s) v Wo
    m = RMSNorm(x; mlp_norm)
    p = softmax(m Wr) over ALL experts;  (w, e) = top_k(p);  w = w / sum(w)
    x = x + sum_k w_k . Wd[e_k] (silu(Wg[e_k] m) * Wu[e_k] m)
    logits = RMSNorm(x_final; final_norm) Wh      row i: the distribution of token i ITSELF (no shift)

THE RULE (``low_confidence_dynamic``), for the masked positions of one block
whose logits are given: ``x0_i ~ softmax(logits_i / T)`` with the MASK id's
logit left out, ``c_i`` that probability; ``high = {masked i: c_i >
threshold}``; ``transfer = high`` if it has at least ``B / denoising_steps``
members, else the ``B / denoising_steps`` masked i of largest ``c_i`` (ties
to the lower position).  THE KEY SCHEDULE, shared with the program as
written words, not as code: the key of the draw at (request, position, pass)
is ``fold_in(fold_in(fold_in(fold_in(key(seed), request), position), 4),
pass)``; the draw is ``jax.random.categorical`` over ``logits / T``.

DEPARTURES, as the configuration's file lists them (``assumed``): block
length 4 and the six generation settings; no logit shift; the MASK token's
row; MASK left out of the draw; the draw's key; the per-head norms' scale
shared by the heads; rotary halves.

THE CHIP'S SHARE: the tree's expert tensors hold experts ``expert_offset ..
expert_offset + held`` of the router's 128; only those contribute, in the
program and here alike; the chosen weights are renormalised over the 8
chosen wherever they live.  Because one swapped eighth expert switches a held
expert's term on or off, ``forward`` can be GIVEN the system's expert
choices (GLM-5's and JoyAI's finding).

THE LIMITS (``reference_tolerance`` in ``chipbench/configs/
sdar-30b-a3b-ep8.json``; the comparison is ``jobs/serve_diffusion.py``'s
``system_run`` / ``against_reference`` / ``passes``: one check prompt of 256,
one of 512 and one of 510 ids through the two served executables, 15 steps
of the full batch, 45 passes of 4 logits rows a reading).  Each lies between
two readings taken on the chip at the published widths (my chip run, PR 36,
call 1: 12 weight seeds as served, bf16 weights, activations and cache; 3
with the weights' mantissa cut to float8 e4m3's 3 bits in place, the
reference reading the weights as served), at their geometric mean — 3x the
largest honest reading, a third of the smallest cut one:

- every pass's logits of the block, rms and max of |system - reference| /
  std(reference): bf16 0.01105-0.01150 / 0.0560-0.0617, cut 0.0985-0.1043 /
  0.442-0.482: limits **0.034 / 0.165**;
- expert sets that differ from the reference's own choice: bf16 6.6-7.6% of
  (layer, token) pairs, cut 55.6-60.0%: limit **0.2**; the largest reference
  margin (8th minus 9th probability) among them: bf16 0.00083-0.00113, cut
  0.0059-0.0072: limit **0.0026** — the logits are compared under the
  system's own choices, so the router is held to account apart;
- exact, no limit: the rule's replay on the program's own logits and keys
  and the delivery (0 mismatches in all 15 readings), three commits a row.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.llama import _head_rows, _rmsnorm, _rope

#: the fourth word of a candidate's key (the pass is the fifth)
UNMASK = 4


class Spec(NamedTuple):
    """What the parameter tree's shapes do not say."""

    rope_theta: float
    rms_eps: float
    experts_per_token: int
    block: int
    expert_offset: int = 0


def _attention(a, p, spec: Spec):
    """a: (S, E) normed -> (S, E): grouped-query attention under the block
    mask, a KV head at a time."""
    f = jnp.float32
    S = a.shape[0]
    H, D = p["wq"].shape[1:]
    KV = p["wk"].shape[1]

    def heads(w):  # (E, n, D) -> (S, n, D)
        return jnp.einsum("se,end->snd", a, w.astype(f))

    q, k, v = heads(p["wq"]), heads(p["wk"]), heads(p["wv"])
    q = _rope(_rmsnorm(q, p["q_norm"].astype(f), spec.rms_eps), spec.rope_theta)
    k = _rope(_rmsnorm(k, p["k_norm"].astype(f), spec.rms_eps), spec.rope_theta)
    at = jnp.arange(S) // spec.block
    visible = at[None, :] <= at[:, None]                              # (i, j)

    def group(args):  # one KV head and the H / KV query heads that share it
        qg, kg, vg = args                                             # (S, G, D), (S, D), (S, D)
        s = jnp.einsum("igd,jd->gij", qg, kg) / math.sqrt(D)
        probs = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
        return jnp.einsum("gij,jd->igd", probs, vg)

    o = jax.lax.map(group, (
        q.reshape(S, KV, H // KV, D).swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1)
    ))                                                                # (KV, S, G, D)
    return jnp.einsum("shd,hde->se", o.swapaxes(0, 1).reshape(S, H, D), p["wo"].astype(f))


def _experts(m, p, spec: Spec, forced=None):
    """-> (this chip's part of the layer (S, E), the reference's own choice
    (S, k) in order of falling probability, its margin (S,): k-th minus (k +
    1)-th probability).  ``forced`` (S, k): the experts to APPLY instead (the
    weights are the reference's probabilities of those)."""
    f = jnp.float32
    k = spec.experts_per_token
    probs = jax.nn.softmax(m @ p["w_router"].astype(f), axis=-1)      # (S, all experts)
    ranked = jnp.argsort(-probs, axis=-1, stable=True)
    chosen = ranked[:, :k]
    by_rank = jnp.take_along_axis(probs, ranked, axis=-1)
    margin = by_rank[:, k - 1] - by_rank[:, k]
    used = chosen if forced is None else forced
    weight = jnp.take_along_axis(probs, used, axis=-1)
    weight = weight / weight.sum(-1, keepdims=True)

    def one_expert(args):
        e, w_gate, w_up, w_down = args
        out = (jax.nn.silu(m @ w_gate.astype(f)) * (m @ w_up.astype(f))) @ w_down.astype(f)
        return out * jnp.where(used == e, weight, 0.0).sum(-1)[:, None]

    held = spec.expert_offset + jnp.arange(p["w_gate"].shape[0])
    y = jax.lax.map(one_expert, (held, p["w_gate"], p["w_up"], p["w_down"])).sum(0)
    return y, chosen, margin


@functools.partial(jax.jit, static_argnums=(3,))
def _layer(x, blocks, i, spec: Spec, forced=None):
    """Block ``i`` of the stacked tree — the weights are ARGUMENTS, so one
    compile a sequence length serves every seed — each matrix cast to
    float32 where it is used, a KV head and an expert at a time."""
    f = jnp.float32
    p = {k: jax.lax.dynamic_index_in_dim(a, i, keepdims=False) for k, a in blocks.items()}
    x = x + _attention(_rmsnorm(x, p["attn_norm"].astype(f), spec.rms_eps), p, spec)
    y, chosen, margin = _experts(
        _rmsnorm(x, p["mlp_norm"].astype(f), spec.rms_eps), p, spec, forced
    )
    return x + y, chosen, margin


def forward(params, ids, spec: Spec, experts=None, rows=None, head_rows: int = 16384):
    """ids (S,) int32, whose last block may hold MASK ids -> (logits (S, V)
    float32 under the block mask — of positions ``rows`` only, where given —
    {"experts": (L, S, k) the reference's own choices, "expert_margin": (L,
    S)}).  ``experts`` (L, S, k): the choices every token is GIVEN (the
    system's)."""
    chosen, margins = [], []
    blocks = params["blocks"]
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed"][jnp.asarray(ids, jnp.int32)].astype(jnp.float32)
        for i in range(blocks["attn_norm"].shape[0]):
            forced = None if experts is None else jnp.asarray(experts[i], jnp.int32)
            x, mine, margin = _layer(x, blocks, i, spec, forced)
            chosen.append(mine)
            margins.append(margin)
        x = _rmsnorm(x, params["final_norm"].astype(jnp.float32), spec.rms_eps)
        if rows is not None:
            x = x[jnp.asarray(rows)]
        head = params["lm_head"]
        vocab = head.shape[0]
        parts = next(k for k in range(1, vocab + 1)
                     if vocab % k == 0 and vocab // k <= head_rows)
        per = vocab // parts
        logits = jnp.concatenate(
            [_head_rows(x, head, k * per, per) for k in range(parts)], axis=-1
        )
    return logits, {"experts": jnp.stack(chosen), "expert_margin": jnp.stack(margins)}


def candidate(logits, key, request: int, position: int, passes: int,
              temperature: float, mask_id: int):
    """The candidate for one masked position and its confidence.  logits
    (V,).  -> (x0, c)."""
    logits = np.array(logits, np.float32)
    logits[mask_id] = -np.inf
    if temperature > 0.0:
        logits = logits / np.float32(temperature)
        k = jax.random.fold_in(jax.random.fold_in(key, request), position)
        k = jax.random.fold_in(jax.random.fold_in(k, UNMASK), passes)
        x0 = int(jax.random.categorical(k, jnp.asarray(logits)))
    else:
        x0 = int(np.argmax(logits))
    e = np.exp(logits - logits.max())
    return x0, float(e[x0] / e.sum())


def transfers(conf, masked, threshold: float, per_pass: int):
    """The rule for one block.  conf, masked (B,) -> (the positions this
    pass unmasks (B,) bool, by the threshold?)."""
    conf, masked = np.asarray(conf, np.float32), np.asarray(masked, bool)
    high = masked & (conf > np.float32(threshold))
    if high.sum() >= per_pass:
        return high, True
    best = np.argsort(-np.where(masked, conf, -1.0), kind="stable")[:per_pass]
    top = np.zeros_like(masked)
    top[best] = True
    return top & masked, False
