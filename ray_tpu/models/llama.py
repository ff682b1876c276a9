"""Llama-family decoder: RMSNorm + RoPE + GQA + SwiGLU, TPU-first.

Second LM family beside GPT-2 (models/gpt2.py), matching the serving
workload the reference's release tests target (ray:
release/serve_tests Llama configs; doc/source/serve LLM examples).
Same design language as gpt2.py: stacked-layer params (one pytree leaf
per parameter kind, lax.scan-friendly), logical-axis sharding
annotations compiled by pjit (parallel/sharding.py rule table), bf16
matmuls with f32 layernorms/softmax, optional ring attention for
sequence parallelism, and a chunked cross-entropy for HBM-sized logits.

Grouped-query attention: num_kv_heads < num_heads shares each KV head
across num_heads // num_kv_heads query heads (Llama-2-70B/Llama-3
layout; num_kv_heads == num_heads gives classic MHA).

The feed-forward is ONE function, ``_ffn``, called by the two block
bodies (training ``_block``, and ``_block_step`` for every path that
keeps a KV cache).  With ``num_experts > 0`` it is a sparse
expert layer as OLMoE has it: softmax router over all experts in
float32, top-k, weights NOT renormalised, every routed (token, expert)
pair computed through ``ops/grouped_matmul.py`` — no capacity, no
dropped token, no expert applied to a token that did not choose it.
``qk_norm`` adds OLMoE's RMSNorm over the whole projected query and
key vectors before they are split into heads.  ``forward`` / ``loss_fn``
run the same layer, which is what the CPU comparison with the float32
reference needs; there is no auxiliary load-balancing loss and no
training cell for it (ROADMAP R1, training half).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.parallel.sharding import constrain

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    max_seq_len: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    embed_dim: int = 4096
    mlp_dim: int = 11008
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attention_impl: str = "dense"  # "dense" | "ring"
    # sliding-window attention (Mistral-style): > 0 limits every query
    # to the last `sliding_window` keys, in training AND in the cached
    # decode paths.  0 = full causal.
    sliding_window: int = 0
    remat: bool = True
    xent_chunk: int = 0
    scan_unroll: int = 1
    tie_embeddings: bool = False
    # sparse experts (OLMoE): 0 = dense SwiGLU of width mlp_dim.  With
    # num_experts > 0 every block's feed-forward is num_experts SwiGLU
    # experts of width expert_dim, experts_per_token of them per token.
    num_experts: int = 0
    experts_per_token: int = 0
    expert_dim: int = 0
    # RMSNorm over the whole projected q and k vectors (before heads)
    qk_norm: bool = False

    @property
    def head_dim(self) -> int:
        return self.embed_dim // self.num_heads

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // self.num_kv_heads

    @staticmethod
    def llama2_7b(**kw) -> "LlamaConfig":
        return LlamaConfig(**kw)

    @staticmethod
    def mistral_7b(**kw) -> "LlamaConfig":
        """Mistral-7B shape: GQA (8 KV heads) + 4096-token sliding
        window over a 32k context."""
        kw.setdefault("vocab_size", 32000)
        kw.setdefault("max_seq_len", 32768)
        kw.setdefault("num_layers", 32)
        kw.setdefault("num_heads", 32)
        kw.setdefault("num_kv_heads", 8)
        kw.setdefault("embed_dim", 4096)
        kw.setdefault("mlp_dim", 14336)
        kw.setdefault("sliding_window", 4096)
        return LlamaConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        defaults = dict(
            vocab_size=256, max_seq_len=128, num_layers=2, num_heads=4,
            num_kv_heads=2, embed_dim=64, mlp_dim=160,
            dtype=jnp.float32, remat=False,
        )
        defaults.update(kw)
        return LlamaConfig(**defaults)


def param_logical_axes(config: LlamaConfig) -> Dict[str, Any]:
    """Per-parameter logical axis names (parallel/sharding.py specs)."""
    blk = {
        "attn_norm": ("layers", "embed"),
        "wq": ("layers", "embed", "heads", None),
        "wk": ("layers", "embed", "kv", None),
        "wv": ("layers", "embed", "kv", None),
        "wo": ("layers", "heads", None, "embed"),
        "mlp_norm": ("layers", "embed"),
        "w_gate": ("layers", "embed", "mlp"),
        "w_up": ("layers", "embed", "mlp"),
        "w_down": ("layers", "mlp", "embed"),
    }
    if config.num_experts:
        blk.update({
            "w_router": ("layers", "embed", None),
            "w_gate": ("layers", "expert", "embed", "mlp"),
            "w_up": ("layers", "expert", "embed", "mlp"),
            "w_down": ("layers", "expert", "mlp", "embed"),
        })
    if config.qk_norm:
        blk.update({"q_norm": ("layers", None), "k_norm": ("layers", None)})
    out = {
        "tok_embed": ("vocab", "embed"),
        "blocks": blk,
        "final_norm": ("embed",),
    }
    if not config.tie_embeddings:
        out["lm_head"] = ("vocab", "embed")
    return out


def init(rng, config: LlamaConfig) -> Params:
    c = config
    dt = c.param_dtype
    L, E, H, KV, D = (
        c.num_layers, c.embed_dim, c.num_heads, c.num_kv_heads, c.head_dim,
    )
    # dense: one SwiGLU of width mlp_dim; experts: the same three
    # matrices behind a leading expert axis, of width expert_dim
    X = (c.num_experts,) if c.num_experts else ()
    M = c.expert_dim if c.num_experts else c.mlp_dim
    k = jax.random.split(rng, 8)
    std = 0.02
    resid_std = std / math.sqrt(2 * L)

    def norm(key, shape, s):
        return (jax.random.normal(key, shape, jnp.float32) * s).astype(dt)

    params: Params = {
        "tok_embed": norm(k[0], (c.vocab_size, E), std),
        "blocks": {
            "attn_norm": jnp.ones((L, E), dt),
            "wq": norm(k[1], (L, E, H, D), std),
            "wk": norm(k[2], (L, E, KV, D), std),
            "wv": norm(k[3], (L, E, KV, D), std),
            "wo": norm(k[4], (L, H, D, E), resid_std),
            "mlp_norm": jnp.ones((L, E), dt),
            "w_gate": norm(k[5], (L, *X, E, M), std),
            "w_up": norm(k[6], (L, *X, E, M), std),
            "w_down": norm(k[7], (L, *X, M, E), resid_std),
        },
        "final_norm": jnp.ones((E,), dt),
    }
    if c.num_experts:
        params["blocks"]["w_router"] = norm(
            jax.random.fold_in(k[5], 1), (L, E, c.num_experts), std
        )
    if c.qk_norm:
        params["blocks"].update({
            "q_norm": jnp.ones((L, H * D), dt),
            "k_norm": jnp.ones((L, KV * D), dt),
        })
    if not c.tie_embeddings:
        params["lm_head"] = norm(
            jax.random.fold_in(k[0], 1), (c.vocab_size, E), std
        )
    return params


def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, positions, theta):
    """Rotary embedding over the last dim.  x: (B, S, H, D)."""
    D = x.shape[-1]
    half = D // 2
    freqs = jnp.exp(
        -math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
    )
    angles = positions[:, :, None].astype(jnp.float32) * freqs[None, None, :]
    cos = jnp.cos(angles)[:, :, None, :]  # (B, S, 1, half)
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return rotated.astype(x.dtype)


def _attention(q, k, v, config: LlamaConfig):
    if config.attention_impl == "ring":
        if config.sliding_window:
            raise NotImplementedError(
                "sliding_window with ring attention: window the KV ring "
                "instead (sp shards already bound the lookback)"
            )
        from ray_tpu.ops.ring_attention import ring_attention

        return ring_attention(q, k, v)
    from ray_tpu.ops.attention import dense_attention

    return dense_attention(q, k, v, window=config.sliding_window)


_EXPERT_TENSORS = ("w_gate", "w_up", "w_down")


def _layer_params(blocks: Params, config: LlamaConfig):
    """``(xs, whole)`` for a layer loop: ``xs`` is what it scans over
    (every stacked leaf, and the layer's index), ``whole`` what its body
    adds unsliced to the layer's parameters: an expert config's three
    expert tensors in the compute dtype (see ``_ffn``; cast here, once
    a forward and not once a layer), nothing for a dense config."""
    layers = jnp.arange(config.num_layers)
    if not config.num_experts:
        return (blocks, layers), {}
    whole = {k: blocks[k].astype(config.dtype) for k in _EXPERT_TENSORS}
    rest = {k: v for k, v in blocks.items() if k not in whole}
    return (rest, layers), whole


def _qkv(h, p, positions, config: LlamaConfig):
    """Projections of the normed input: q (B, S, H, D) and k (B, S, KV,
    D) with rotary positions applied, v (B, S, KV, D).  With ``qk_norm``
    q and k are RMS-normed over their WHOLE projected width (all heads
    together, scales ``q_norm`` / ``k_norm``) before the rotation."""
    c = config
    B, S = h.shape[:2]

    def normed(x, scale):
        if not c.qk_norm:
            return x
        return _rmsnorm(x.reshape(B, S, -1), p[scale], c.rms_eps).reshape(x.shape)

    q = jnp.einsum("bse,ehd->bshd", h, p["wq"].astype(c.dtype))
    q = _rope(normed(q, "q_norm"), positions, c.rope_theta)
    kk = jnp.einsum("bse,ekd->bskd", h, p["wk"].astype(c.dtype))
    kk = _rope(normed(kk, "k_norm"), positions, c.rope_theta)
    vv = jnp.einsum("bse,ekd->bskd", h, p["wv"].astype(c.dtype))
    return q, kk, vv


def _ffn(h, p, config: LlamaConfig):
    """The ONE feed-forward body.  h: (B, S, E), already normed.
    Returns ``(y, routing)``: y (B, S, E) to add to the residual, and
    for an expert config ``{"rows": (num_experts,) int32 rows each
    expert computed in this call, "experts": (B, S, k) the experts each
    token chose}`` (None for a dense config).

    Dense: SwiGLU, ``w_down(silu(w_gate h) * w_up h)``.

    Experts (``num_experts`` > 0): router logits ``h @ w_router`` and a
    softmax over ALL experts in float32; ``lax.top_k`` picks
    ``experts_per_token`` of them and their probabilities are the
    weights as they are (not renormalised: OLMoE's ``norm_topk_prob``
    false).  The B*S*k (token, choice) rows are sorted by expert, the
    three matrices are applied as grouped matmuls over the sorted rows,
    and the results are put back in token order, weighted and summed
    over the k choices.  Every routed pair is computed: no capacity, no
    dropped token, no dense (rows, experts, width) intermediate.

    The three expert tensors arrive STACKED over the layers, (L, X, ..),
    with ``p["layer"]`` saying which layer this is (``_layer_params``):
    a layer loop that handed the kernel one layer's slice would copy
    that slice first (805 MB a layer at OLMoE's widths), so the kernel
    gets all L * X matrices as its groups and sizes that are zero
    outside this layer's X."""
    c = config
    if not c.num_experts:
        gate = jnp.einsum("bse,em->bsm", h, p["w_gate"].astype(c.dtype))
        up = jnp.einsum("bse,em->bsm", h, p["w_up"].astype(c.dtype))
        act = constrain(jax.nn.silu(gate) * up, ("batch", "seq", "mlp"))
        return jnp.einsum("bsm,me->bse", act, p["w_down"].astype(c.dtype)), None
    from ray_tpu.ops.grouped_matmul import grouped_matmul

    B, S, E = h.shape
    X, K = c.num_experts, c.experts_per_token
    x = h.reshape(B * S, E)
    with jax.named_scope("moe_route"):
        logits = jnp.einsum(
            "ne,ex->nx", x, p["w_router"].astype(c.dtype),
            preferred_element_type=jnp.float32,
        )
        weight, expert = lax.top_k(jax.nn.softmax(logits, axis=-1), K)
        flat = expert.reshape(-1)                      # (N*K,) row -> expert
        order = jnp.argsort(flat, stable=True)         # sorted row -> row
        rows = (flat[:, None] == jnp.arange(X)[None, :]).sum(
            0, dtype=jnp.int32
        )                                              # bincount, (X,)
        xs = x[order // K]                             # (N*K, E) by expert
    with jax.named_scope("moe_experts"):
        L = p["w_gate"].shape[0]
        sizes = lax.dynamic_update_slice(
            jnp.zeros((L * X,), jnp.int32), rows, (p["layer"] * X,)
        )
        w_gate, w_up, w_down = (
            p[k].reshape(L * X, *p[k].shape[2:]) for k in _EXPERT_TENSORS
        )
        gate = grouped_matmul(xs, w_gate, sizes)
        up = grouped_matmul(xs, w_up, sizes)
        ys = grouped_matmul(jax.nn.silu(gate) * up, w_down, sizes)
    with jax.named_scope("moe_combine"):
        back = jnp.argsort(order)                      # row -> sorted row
        y = ys[back].reshape(B * S, K, E).astype(jnp.float32)
        y = (y * weight[:, :, None]).sum(1).astype(c.dtype)
    return y.reshape(B, S, E), {
        "rows": rows, "experts": expert.reshape(B, S, K),
    }


def _block(x, p, positions, config: LlamaConfig):
    c = config
    h = _rmsnorm(x, p["attn_norm"], c.rms_eps)
    q, kk, vv = _qkv(h, p, positions, c)
    # GQA: repeat each KV head across its query group
    if c.q_per_kv > 1:
        kk = jnp.repeat(kk, c.q_per_kv, axis=2)
        vv = jnp.repeat(vv, c.q_per_kv, axis=2)
    q = constrain(q, ("batch", "seq", "heads", None))
    kk = constrain(kk, ("batch", "seq", "heads", None))
    vv = constrain(vv, ("batch", "seq", "heads", None))
    attn = _attention(q, kk, vv, c)
    x = x + jnp.einsum("bshd,hde->bse", attn, p["wo"].astype(c.dtype))
    x = constrain(x, ("batch", "seq", "embed"))
    y, routing = _ffn(_rmsnorm(x, p["mlp_norm"], c.rms_eps), p, c)
    x = constrain(x + y, ("batch", "seq", "embed"))
    return x, routing and routing["experts"]


def _features_and_choices(params: Params, tokens, config: LlamaConfig):
    c = config
    B, S = tokens.shape
    emb = constrain(params["tok_embed"], (None, None)).astype(c.dtype)
    x = emb[tokens]
    x = constrain(x, ("batch", "seq", "embed"))
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))

    xs, whole = _layer_params(params["blocks"], c)

    def body(carry, layer):
        fn = _block
        if c.remat:
            fn = jax.checkpoint(_block, static_argnums=(3,))
        p, l = layer
        return fn(carry, dict(p, layer=l, **whole), positions, c)

    x, experts = lax.scan(body, x, xs, unroll=max(1, c.scan_unroll))
    return _rmsnorm(x, params["final_norm"], c.rms_eps), experts


def features(params: Params, tokens, config: LlamaConfig):
    """tokens (B, S) int32 → final-RMSNorm features (B, S, E)."""
    return _features_and_choices(params, tokens, config)[0]


def expert_choices(params: Params, tokens, config: LlamaConfig):
    """tokens (B, S) int32 → (L, B, S, k) int32: the experts every token
    chose in every layer of the no-cache forward, in order of falling
    router probability.  For comparisons with a reference's routing
    (which pairs swap under rounding); no serving path calls it."""
    return _features_and_choices(params, tokens, config)[1]


def _head_weight(params: Params, config: LlamaConfig):
    return params["tok_embed"] if config.tie_embeddings else params["lm_head"]


def forward(params: Params, tokens, config: LlamaConfig):
    """tokens (B, S) int32 → logits (B, S, vocab) f32."""
    x = features(params, tokens, config)
    logits = jnp.einsum(
        "bse,ve->bsv",
        x,
        _head_weight(params, config).astype(config.dtype),
        preferred_element_type=jnp.float32,
    )
    return constrain(logits, ("batch", "seq", "vocab"))


def loss_fn(params: Params, batch, config: LlamaConfig):
    """Next-token cross-entropy; same contract as gpt2.loss_fn
    (tokens | inputs/targets, optional mask, optional chunked head)."""
    if "tokens" in batch:
        inputs = batch["tokens"][:, :-1]
        targets = batch["tokens"][:, 1:]
    else:
        inputs, targets = batch["inputs"], batch["targets"]
    mask = batch.get("mask")
    c = config
    if c.xent_chunk and inputs.shape[1] % c.xent_chunk == 0:
        from ray_tpu.models.xent import chunked_xent

        x = features(params, inputs, config)
        return chunked_xent(
            x, _head_weight(params, c), targets, mask, c.xent_chunk,
            c.dtype,
        )
    logits = forward(params, inputs, config)
    lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
    tl = jnp.take_along_axis(
        logits.astype(jnp.float32), targets[..., None], axis=-1
    )[..., 0]
    ll = tl - lse
    if mask is None:
        return -ll.mean()
    mask = mask.astype(jnp.float32)
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def num_params(config: LlamaConfig) -> int:
    shapes = jax.eval_shape(partial(init, config=config), jax.random.key(0))
    return sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))


def flops_per_token(config: LlamaConfig, seq_len: Optional[int] = None) -> float:
    """fwd+bwd FLOPs per token: 6N + attention quadratic term."""
    c = config
    S = seq_len or c.max_seq_len
    n = num_params(c) - c.vocab_size * c.embed_dim * (
        0 if c.tie_embeddings else 1
    )
    attn = 12 * c.num_layers * c.embed_dim * S  # 2*2*3 * L * E * S
    return 6.0 * n + attn


def generate(params: Params, prompt, config: LlamaConfig, *,
             max_new_tokens: int = 32, temperature: float = 0.0,
             rng=None):
    """Greedy/sampled decode (B, S) → (B, S + max_new_tokens).

    The context is padded once to the fixed bucket S + max_new_tokens
    and the step function takes the current length as a traced index —
    ONE compiled executable serves every decode step (no per-token
    recompile).  Each step recomputes the full context (O(S²) total)
    through the no-cache ``forward``: this is the plain reference the
    tests hold the cached paths and the serving engine to, not a path
    to serve from.  temperature 0 is argmax; otherwise categorical
    sampling."""
    tokens = jnp.asarray(prompt, jnp.int32)
    B, S0 = tokens.shape
    if max_new_tokens <= 0:
        return tokens
    total = S0 + max_new_tokens
    padded = jnp.zeros((B, total), jnp.int32).at[:, :S0].set(tokens)
    temperature = float(temperature or 0.0)  # None == greedy
    key = rng if rng is not None else jax.random.key(0)
    for i in range(max_new_tokens):
        key, sub = jax.random.split(key)
        padded = _gen_step(params, padded, jnp.int32(S0 + i), sub,
                           config=config, temperature=temperature)
    return padded


@partial(jax.jit, static_argnames=("config", "temperature"))
def _gen_step(params, padded, length, key, *, config, temperature):
    """One full-recompute decode step — MODULE-LEVEL jit, so its cache
    is keyed by (config, shapes), not per-call closures: repeat
    generate() calls reuse one executable."""
    logits = forward(params, padded, config)  # (B, total, V)
    B = padded.shape[0]
    # causal attention: position length-1 only sees real tokens, so the
    # padding beyond it cannot leak into this readout
    last = jnp.take_along_axis(
        logits, (length - 1)[None, None, None].repeat(B, 0), axis=1
    )[:, 0, :]
    nxt = _pick_token(last, key, temperature=temperature)
    return lax.dynamic_update_slice(
        padded, nxt[:, None].astype(jnp.int32), (0, length)
    )


# ---------------------------------------------------------------------------
# KV-cache decoding: ONE cached step behind prefill, decode and generate_kv
# ---------------------------------------------------------------------------


def init_cache(config: LlamaConfig, batch_size: int, max_len: int) -> Params:
    """Fixed-bucket KV cache: (L, B, max_len, KV, D) per tensor, bf16.
    Static shapes — one compiled prefill per prompt length + one compiled
    decode step serve any request up to max_len.  The jitted entry
    points take it donated and hand it back: the one step behind them
    (``_cached_step``) carries it whole through its layer loop, writes
    only the new tokens' K/V in place, and attention reads it as stored.

    With ``sliding_window`` the cache is a ROLLING buffer (slot =
    position mod max_len), so ``max_len`` can be as small as
    ``window + max_prefill_chunk - 1`` regardless of how long decoding
    runs — the Mistral memory win (8x at 32k context / 4k window).
    Positions older than the window are overwritten in place; the
    attention mask reconstructs each slot's position implicitly.

    An expert config adds int32 running totals that ride the donated
    cache like K and V, so no step pays a device-to-host copy for them
    (``serve/llm.py`` reads them in ``stats()``): ``moe_expert_tokens``
    (L, X) rows each expert of each layer computed, ``moe_experts_touched``
    (L,) experts with at least one row, summed over the calls, and
    ``moe_layer_steps`` (L,) calls.  They count what the kernel did:
    every row of a decode step routes, also the rows the engine treats
    as inactive."""
    c = config
    shape = (c.num_layers, batch_size, max_len, c.num_kv_heads, c.head_dim)
    cache = {
        "k": jnp.zeros(shape, c.dtype),
        "v": jnp.zeros(shape, c.dtype),
    }
    if c.num_experts:
        cache["moe_expert_tokens"] = jnp.zeros(
            (c.num_layers, c.num_experts), jnp.int32
        )
        cache["moe_experts_touched"] = jnp.zeros((c.num_layers,), jnp.int32)
        cache["moe_layer_steps"] = jnp.zeros((c.num_layers,), jnp.int32)
    return cache


def _with_expert_counts(cache: Params, k, v, expert_rows) -> Params:
    """The cache after one call: new K/V and, for an expert config, the
    running totals plus this call's (L, X) rows per expert."""
    out = dict(cache, k=k, v=v)
    if expert_rows is not None:
        out["moe_expert_tokens"] = cache["moe_expert_tokens"] + expert_rows
        out["moe_experts_touched"] = cache["moe_experts_touched"] + (
            expert_rows > 0
        ).sum(-1, dtype=jnp.int32)
        out["moe_layer_steps"] = cache["moe_layer_steps"] + 1
    return out


def rolling_cache_len(config: LlamaConfig, prefill_chunk: int) -> int:
    """Smallest safe rolling-cache length for unbounded windowed
    decoding: ``window + prefill_chunk - 1`` slots guarantees a wrapped
    write can only land on a position already outside every live
    query's window (the Mistral memory bound — independent of how long
    decoding runs)."""
    assert config.sliding_window > 0, "rolling caches need sliding_window"
    return config.sliding_window + max(1, prefill_chunk) - 1


def _cache_mask(positions, T: int, window: int):
    """(R, Sq, T), True where the query at ``positions[r, s]`` may see
    cache slot t.  Full causal: slot t holds position t.  With a window
    the cache is a rolling buffer: slot t as seen by query position q
    holds position q - ((q - t) mod T) — the newest position <= q
    congruent to t — valid iff non-negative and inside the window (slot
    correctness needs T >= window + Sq - 1: ``rolling_cache_len``)."""
    q_pos = positions[:, :, None]
    t_idx = jnp.arange(T)
    if not window:
        return t_idx <= q_pos
    t_pos = q_pos - ((q_pos - t_idx) % T)
    return (t_pos >= 0) & (t_pos > q_pos - window)


def _grouped_attention(q, k_cache, v_cache, mask, config: LlamaConfig):
    """The ONE cached-attention body.  q: (B, Sq, H, D) attends over the
    cache AS STORED, (B, T, KV, D): the H = KV * G query heads fold to
    (KV, G) and contract against their KV head directly, so K/V are
    never expanded (no ``jnp.repeat``) and every cached byte is read
    once, in the cache's dtype.  MHA is G = 1, the same code.  mask:
    (B, Sq, T), True where query q may see slot t.  Scores and softmax
    in f32, probabilities and values in ``config.dtype``."""
    c = config
    B, Sq, H, D = q.shape
    KV = k_cache.shape[2]
    q = q.reshape(B, Sq, KV, H // KV, D)
    scores = jnp.einsum(
        "bqkgd,btkd->bkgqt", q, k_cache, preferred_element_type=jnp.float32
    ) / math.sqrt(D)
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(c.dtype)
    out = jnp.einsum("bkgqt,btkd->bqkgd", probs, v_cache)
    return out.reshape(B, Sq, H, D)


def _write_and_read(cache, new, layer, slot, positions, config: LlamaConfig):
    """The one place K/V enter a cache.  Writes ``new`` (R, Sq, KV, D)
    into the WHOLE carried ``cache`` (L, B, T, KV, D) at ``layer``, rows
    ``slot`` (None: all B rows) and ``positions``; returns the cache and
    the (R, T, KV, D) slab of those rows, new tokens included, for
    attention.  Which of the two comes first is chosen from static
    shapes, because each order copies where the other is in place:

    - one token for every row (the decode step): write the B rows into
      the carried cache, THEN index the layer's slab out of it.  XLA
      reads that slab where it lies; taken first, with the rows put
      into the copy, 67 MB of slab would move a layer.
    - a run of tokens (a prefill, a chunk): take the addressed rows'
      slab FIRST, put the run into that copy for attention, and write
      the run into the cache separately.  Written first and then
      sliced, XLA copies the whole K and V cache every call (2 GiB at
      the serving widths; tests/test_llama_decode_compile.py)."""
    L, B, T, KV, D = cache.shape
    R, Sq = positions.shape
    rolling = config.sliding_window > 0

    def write(buf, *lead):
        # buf[*lead[r], slot of positions[r, s]] = new[r, s]
        if rolling or Sq == 1:
            # one row write per token: a decode step's tokens lie in R
            # different rows; in a rolling buffer position t lives in
            # slot t mod T, so a run may wrap
            slots = positions % T if rolling else positions
            return buf.at[(*(i[:, None] for i in lead), slots)].set(new)
        # full causal: a run is contiguous, ONE block write per row (R
        # is 1 for a prefill).  As a scatter of R windows XLA carries
        # the cache through the layer loop in another layout and copies
        # it whole twice a call; token by token it is Sq row writes.
        for r in range(R):
            at = (*(i[r] for i in lead), positions[r, 0], 0, 0)
            buf = lax.dynamic_update_slice(buf, new[r][(None,) * len(lead)], at)
        return buf

    rows, row0 = jnp.arange(R), 0 if slot is None else slot
    lead = (jnp.full((R,), layer), rows + row0)
    if slot is None and Sq == 1:
        cache = write(cache, *lead)
        return cache, lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)
    slab = lax.dynamic_slice(cache, (layer, row0, 0, 0, 0), (1, R, T, KV, D))[0]
    return write(cache, *lead), write(slab, rows)


def _block_step(x, p, cache_k, cache_v, slot, positions, config: LlamaConfig):
    """Block ``p["layer"]`` over each row's run of new tokens.  x: (R,
    Sq, E); positions: (R, Sq) absolute position of every new token;
    cache_k/v: the WHOLE (L, B, T, KV, D) cache, of which only the new
    tokens' entries are written; slot: the one cache row addressed, or
    None for all B.  Returns (x, cache_k, cache_v, expert_rows)."""
    c = config
    with jax.named_scope("decode_attn"):
        h = _rmsnorm(x, p["attn_norm"], c.rms_eps)
        q, kk, vv = _qkv(h, p, positions, c)
        cache_k, slab_k = _write_and_read(
            cache_k, kk.astype(c.dtype), p["layer"], slot, positions, c
        )
        cache_v, slab_v = _write_and_read(
            cache_v, vv.astype(c.dtype), p["layer"], slot, positions, c
        )
        mask = _cache_mask(positions, cache_k.shape[2], c.sliding_window)
        attn = _grouped_attention(q, slab_k, slab_v, mask, c)
        x = x + jnp.einsum("bshd,hde->bse", attn, p["wo"].astype(c.dtype))
    with jax.named_scope("decode_mlp"):
        y, routing = _ffn(_rmsnorm(x, p["mlp_norm"], c.rms_eps), p, c)
    return x + y, cache_k, cache_v, routing and routing["rows"]


def _cached_step(params: Params, tokens, cache: Params, slot, start,
                 config: LlamaConfig):
    """The ONE cached step: a contiguous run of Sq new tokens per row,
    each row's starting at its own offset, through all layers.

    tokens: (R, Sq); start: (R,) absolute position of tokens[:, 0];
    slot: None when the R rows are all B rows of the cache, else the
    (traced) index of the one row addressed (R = 1).  Returns
    (last-token logits (R, V) f32, new cache).  The cache rides the
    layer loop whole, as its carry: under a jit that donates it every
    layer writes the new tokens' K/V in place and no slab is copied."""
    c = config
    positions = start[:, None] + jnp.arange(tokens.shape[1])
    x = params["tok_embed"].astype(c.dtype)[tokens]
    xs, whole = _layer_params(params["blocks"], c)

    def body(carry, layer):
        xx, ck, cv = carry
        p, l = layer
        *carry, expert_rows = _block_step(
            xx, dict(p, layer=l, **whole), ck, cv, slot, positions, c
        )
        return tuple(carry), expert_rows

    (x, new_k, new_v), expert_rows = lax.scan(
        body, (x, cache["k"], cache["v"]), xs
    )
    x = _rmsnorm(x, params["final_norm"], c.rms_eps)
    logits = jnp.einsum(
        "be,ve->bv",
        x[:, -1, :],
        _head_weight(params, c).astype(c.dtype),
        preferred_element_type=jnp.float32,
    )
    return logits, _with_expert_counts(cache, new_k, new_v, expert_rows)


def forward_cached(params: Params, tokens, cache: Params, start,
                   config: LlamaConfig):
    """Run Sq new tokens of every row through all layers, updating the
    cache.  Returns (last_logits (B, V), new_cache).  `start` is the
    absolute position of tokens[:, 0], the same for all rows (0 for
    prefill) — a traced scalar, so one compile covers every chunk."""
    if config.sliding_window:
        T, Sq = cache["k"].shape[2], tokens.shape[1]
        # structural bound only: a chunk longer than the cache would
        # self-overwrite within one write-set.  Whether WRAPPING (a
        # position overwriting position-minus-T) is safe depends on how
        # far the caller decodes: positions < T never wrap (generate_kv
        # sizes exactly so), and truly rolling callers size via
        # rolling_cache_len() so wrapped slots are always out-of-window.
        assert Sq <= T, (
            f"prefill chunk {Sq} exceeds cache length {T}; prefill long "
            "prompts in chunks"
        )
    start = jnp.full((tokens.shape[0],), start, jnp.int32)
    return _cached_step(params, tokens, cache, None, start, config)


@partial(jax.jit, static_argnames=("config",), donate_argnames=("cache",))
def decode_step_rowwise(params, tokens, cache, pos, config: LlamaConfig):
    """One token for every row at per-row positions — the primitive a
    continuous batcher needs: each cache row b has its own length.

    tokens: (B,) int32 last token per row; pos: (B,) its absolute
    position.  Returns (logits (B, V) f32, new cache).  Inactive rows
    simply keep decoding garbage into their own slots — the engine masks
    them out — so the compiled shape never changes.  In place: one new
    K/V row per sequence and layer, each slab read once, unexpanded."""
    return _cached_step(params, tokens[:, None], cache, None, pos, config)


@jax.jit
def set_row(tokens, row, token):
    """``tokens`` (B,) with ``tokens[row] = token``, on the device: how a
    batcher that feeds each decode step the argmax of the one before
    hands it the first token of a row it has just prefilled."""
    return tokens.at[row].set(token)


@partial(jax.jit, static_argnames=("config",), donate_argnames=("cache",))
def prefill_into_slot(params, tokens, cache, slot, config: LlamaConfig):
    """Prefill ONE sequence into batched-cache row ``slot``.

    tokens: (1, S) prompt; cache: the engine's (L, B, T, KV, D) batch
    cache, donated and written in place at that row only.  Returns
    (last-token logits (1, V), updated cache).  One compile per
    prompt-bucket length serves every slot (slot is traced)."""
    return _cached_step(
        params, tokens, cache, slot, jnp.zeros((1,), jnp.int32), config
    )


@partial(jax.jit, static_argnames=("temperature",))
def _pick_token(logits, key, *, temperature):
    if temperature > 0.0:
        return jax.random.categorical(key, logits / temperature).astype(
            jnp.int32
        )
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def generate_kv(params: Params, prompt, config: LlamaConfig, *,
                max_new_tokens: int = 32, temperature: float = 0.0,
                rng=None):
    """KV-cache decode (B, S) → (B, S + max_new_tokens) with the serving
    engine's two programs: ``prefill_into_slot`` once per row, then one
    ``decode_step_rowwise`` per token with every row at the same
    position.  Same tokens as ``generate``'s full recompute; a
    convenience for scripts and tests — ``serve.llm.LlamaDeployment``
    is what batches requests at different positions."""
    tokens = jnp.asarray(prompt, jnp.int32)
    B, S0 = tokens.shape
    if max_new_tokens <= 0:
        return tokens
    cache = init_cache(config, B, S0 + max_new_tokens)
    temperature = float(temperature or 0.0)  # None == greedy
    rows = []
    for b in range(B):
        row, cache = prefill_into_slot(
            params, tokens[b:b + 1], cache, jnp.int32(b), config
        )
        rows.append(row)
    logits = jnp.concatenate(rows, axis=0)
    key = rng if rng is not None else jax.random.key(0)
    out = [tokens]
    for i in range(max_new_tokens):
        if i:  # the first token comes from the prefills' logits
            pos = jnp.full((B,), S0 + i - 1, jnp.int32)
            logits, cache = decode_step_rowwise(
                params, out[-1][:, 0], cache, pos, config
            )
        key, sub = jax.random.split(key)
        out.append(_pick_token(logits, sub, temperature=temperature)[:, None])
    return jnp.concatenate(out, axis=1)
