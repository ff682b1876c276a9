"""GPT-2 decoder-only transformer, TPU-first.

The flagship model for the framework's Train path (SURVEY.md §7 config 3:
GPT-2-124M with FSDP-style sharding).  Design choices for the MXU/XLA:

- bf16 activations & matmuls, f32 params and softmax/layernorm numerics;
- layers stacked into one pytree and iterated with `lax.scan` (one
  compiled block body, O(1) HLO size in depth);
- every weight and activation carries a logical axis name so the same
  model runs pure-DP, FSDP, TP, SP or any combination via the rule table
  in ray_tpu.parallel.sharding;
- attention is pluggable ("dense" einsum or "ring" over the sp axis).

Functional API (params in, arrays out) — no Module system, so the whole
step is a single traced function for pjit.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import dense_attention as _dense_attention
from ray_tpu.parallel.sharding import constrain

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304  # 50257 padded to a multiple of 128 for the MXU
    max_seq_len: int = 1024
    num_layers: int = 12
    num_heads: int = 12
    embed_dim: int = 768
    mlp_ratio: int = 4
    dtype: Any = jnp.bfloat16  # activation/matmul dtype
    param_dtype: Any = jnp.float32
    attention_impl: str = "dense"  # "dense" | "ring" (sp-sharded)
    remat: bool = True  # rematerialize each block in the backward pass
    # chunked cross-entropy: apply the lm_head + logsumexp per sequence
    # chunk of this many tokens (0 = dense).  Caps the largest activation
    # at O(B·chunk·V) instead of O(B·S·V) — what lets B=16+ fit in HBM.
    xent_chunk: int = 0
    # layer-scan unroll factor (1 = rolled loop).  Fully unrolling (set
    # to num_layers) removes the XLA while-loop overhead and lets the
    # scheduler overlap across layer boundaries: measured 99.5 → 80.4
    # ms/step (MFU 0.358 → 0.442) on v5e at B=8, S=1024.  Rolled stays
    # the default for compile-time and for remat-heavy configs.
    scan_unroll: int = 1
    # Mixture-of-Experts: num_experts > 0 replaces every block's dense
    # MLP with a top-1 (switch) MoE — experts shard over the mesh's ep
    # axis ("expert" logical axis), token dispatch/combine compile to
    # all_to_all over ICI.  GShard-style dense one-hot dispatch with a
    # per-expert capacity; overflow tokens pass through the residual.
    num_experts: int = 0
    moe_capacity_factor: float = 1.25
    moe_aux_coeff: float = 0.01  # Switch load-balancing aux loss weight

    @property
    def head_dim(self) -> int:
        return self.embed_dim // self.num_heads

    @property
    def mlp_dim(self) -> int:
        return self.mlp_ratio * self.embed_dim

    @staticmethod
    def gpt2_124m(**kw) -> "GPTConfig":
        return GPTConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "GPTConfig":
        kw.setdefault("vocab_size", 256)
        kw.setdefault("max_seq_len", 128)
        kw.setdefault("num_layers", 2)
        kw.setdefault("num_heads", 4)
        kw.setdefault("embed_dim", 64)
        return GPTConfig(**kw)


def param_logical_axes(config: GPTConfig) -> Params:
    """Logical axis names for every param (see parallel.sharding rules).

    Block params carry a leading "layers" axis (scan-stacked).
    """
    blk = {
        "ln1_scale": ("layers", "embed"),
        "ln1_bias": ("layers", "embed"),
        "qkv_kernel": ("layers", "embed", "heads", "kv"),
        "qkv_bias": ("layers", "heads", "kv"),
        "proj_kernel": ("layers", "heads", "kv", "embed"),
        "proj_bias": ("layers", "embed"),
        "ln2_scale": ("layers", "embed"),
        "ln2_bias": ("layers", "embed"),
    }
    if config.num_experts > 0:
        blk.update({
            "router": ("layers", "embed", "expert"),
            "moe_in": ("layers", "expert", "embed", "mlp"),
            "moe_out": ("layers", "expert", "mlp", "embed"),
        })
    else:
        blk.update({
            "fc_kernel": ("layers", "embed", "mlp"),
            "fc_bias": ("layers", "mlp"),
            "out_kernel": ("layers", "mlp", "embed"),
            "out_bias": ("layers", "embed"),
        })
    return {
        "wte": ("vocab", "embed"),
        "wpe": (None, "embed"),
        "blocks": blk,
        "lnf_scale": ("embed",),
        "lnf_bias": ("embed",),
    }


def init(rng, config: GPTConfig) -> Params:
    """GPT-2 initialization: N(0, 0.02), residual projections scaled by
    1/sqrt(2*num_layers)."""
    c = config
    dt = c.param_dtype
    k = jax.random.split(rng, 8)
    std = 0.02
    resid_std = std / math.sqrt(2 * c.num_layers)
    L, E, H, D, M = c.num_layers, c.embed_dim, c.num_heads, c.head_dim, c.mlp_dim

    def norm(key, shape, s):
        return (jax.random.normal(key, shape, jnp.float32) * s).astype(dt)

    blocks = {
        "ln1_scale": jnp.ones((L, E), dt),
        "ln1_bias": jnp.zeros((L, E), dt),
        "qkv_kernel": norm(k[0], (L, E, 3 * H, D), std),
        "qkv_bias": jnp.zeros((L, 3 * H, D), dt),
        "proj_kernel": norm(k[1], (L, H, D, E), resid_std),
        "proj_bias": jnp.zeros((L, E), dt),
        "ln2_scale": jnp.ones((L, E), dt),
        "ln2_bias": jnp.zeros((L, E), dt),
    }
    if c.num_experts > 0:
        X = c.num_experts
        blocks.update({
            "router": norm(k[6], (L, E, X), std),
            "moe_in": norm(k[2], (L, X, E, M), std),
            "moe_out": norm(k[3], (L, X, M, E), resid_std),
        })
    else:
        blocks.update({
            "fc_kernel": norm(k[2], (L, E, M), std),
            "fc_bias": jnp.zeros((L, M), dt),
            "out_kernel": norm(k[3], (L, M, E), resid_std),
            "out_bias": jnp.zeros((L, E), dt),
        })
    return {
        "wte": norm(k[4], (c.vocab_size, E), std),
        "wpe": norm(k[5], (c.max_seq_len, E), 0.01),
        "blocks": blocks,
        "lnf_scale": jnp.ones((E,), dt),
        "lnf_bias": jnp.zeros((E,), dt),
    }


from ray_tpu.models.common import layernorm as _layernorm  # noqa: E402


def _attention(q, k, v, config: GPTConfig):
    if config.attention_impl == "ring":
        from ray_tpu.ops.ring_attention import ring_attention

        return ring_attention(q, k, v)
    return _dense_attention(q, k, v)


def _moe_mlp(h, p, config: GPTConfig, mask=None):
    """Top-1 (switch) MoE MLP.  h (B, S, E) post-norm → (delta, aux).

    GShard-style dense dispatch, GROUPED BY BATCH ROW: each row routes
    its S tokens independently with per-row expert capacity
    C = ceil(cap_factor · S / X), so the one-hot dispatch tensor is
    (B, S, X, C) — O(B·S²·cap/X·X) = O(B·S²·cap) memory instead of the
    O((B·S)²) a globally-flattened dispatch costs, and the routing
    cumsum runs along S (no serialization across the dp-sharded batch
    axis).  Expert FFN weights shard over ep ("expert" logical axis);
    under pjit the dispatch/combine einsums compile to all_to_all over
    ICI.  Tokens past capacity pass through the residual (standard
    switch behavior).  aux is the Switch load-balancing loss
    X·Σ f_i·P_i (1.0 at perfect balance).

    `mask` (B, S) zeroes padding tokens out of routing entirely: they
    consume no expert capacity and the aux statistics count only real
    tokens."""
    c = config
    B, S, E = h.shape
    X = c.num_experts
    C = max(1, math.ceil(c.moe_capacity_factor * S / X))
    router_logits = jnp.einsum(
        "bse,ex->bsx", h.astype(jnp.float32),
        p["router"].astype(jnp.float32),
    )
    probs = jax.nn.softmax(router_logits, axis=-1)  # (B, S, X) f32
    gate = probs.max(axis=-1)
    expert = jnp.argmax(probs, axis=-1)
    onehot = jax.nn.one_hot(expert, X, dtype=jnp.float32)  # (B, S, X)
    if mask is not None:
        onehot = onehot * mask[..., None].astype(jnp.float32)
    # position of each token within its row's expert capacity buffer
    pos = jnp.cumsum(onehot, axis=1) * onehot - 1.0
    disp = jnp.where((pos >= 0) & (pos < C), onehot, 0.0)
    pos_idx = jnp.clip(pos, 0, C - 1).astype(jnp.int32)
    disp_bsxc = disp[..., None] * jax.nn.one_hot(pos_idx, C,
                                                 dtype=jnp.float32)
    expert_in = jnp.einsum(
        "bsxc,bse->bxce", disp_bsxc, h.astype(jnp.float32)
    ).astype(c.dtype)
    expert_in = constrain(expert_in, ("batch", "expert", None, "embed"))
    hmid = jax.nn.gelu(jnp.einsum(
        "bxce,xem->bxcm", expert_in, p["moe_in"].astype(c.dtype)
    ))
    hmid = constrain(hmid, ("batch", "expert", None, "mlp"))
    expert_out = jnp.einsum(
        "bxcm,xme->bxce", hmid, p["moe_out"].astype(c.dtype)
    )
    expert_out = constrain(expert_out, ("batch", "expert", None, "embed"))
    combine = (disp_bsxc * gate[..., None, None]).astype(c.dtype)
    out = jnp.einsum("bsxc,bxce->bse", combine, expert_out)
    if mask is None:
        f = onehot.mean(axis=(0, 1))
        P = probs.mean(axis=(0, 1))
    else:
        m = mask[..., None].astype(jnp.float32)
        denom = jnp.maximum(m.sum(), 1.0)
        f = onehot.sum(axis=(0, 1)) / denom
        P = (probs * m).sum(axis=(0, 1)) / denom
    aux = (X * jnp.sum(f * P)).astype(jnp.float32)
    return out, aux


def _block(x, p, config: GPTConfig, mask=None):
    """One transformer block. x: (B, S, E); p: per-layer param slice.
    Returns (x, moe_aux) — aux is 0.0 for dense-MLP blocks."""
    c = config
    S = x.shape[1]
    h = _layernorm(x, p["ln1_scale"], p["ln1_bias"])
    if c.attention_impl == "flash" and S % 128 == 0:
        # The kernels' own layout, (B, S, H x D): the projections write and
        # read it as plain matmuls (the weights' head axes folded), so no
        # copy surrounds the kernels and a head pair is a 128-lane column
        # block.  Non-128-multiple S goes the dense way below — the
        # kernels want whole tiles.
        from ray_tpu.ops.flash_attention import sharded_flash_attention

        E = x.shape[-1]
        qkv = jnp.einsum(
            "bse,ef->bsf", h, p["qkv_kernel"].astype(c.dtype).reshape(E, -1)
        ) + p["qkv_bias"].astype(c.dtype).reshape(-1)
        q, k, v = (
            constrain(t, ("batch", "seq", "heads"))
            for t in jnp.split(qkv, 3, axis=-1)
        )
        attn = sharded_flash_attention(q, k, v, c.head_dim)
        x = x + jnp.einsum(
            "bsf,fe->bse", attn, p["proj_kernel"].astype(c.dtype).reshape(-1, E)
        ) + p["proj_bias"].astype(c.dtype)
    else:
        qkv = (
            jnp.einsum("bse,ehd->bshd", h, p["qkv_kernel"].astype(c.dtype))
            + p["qkv_bias"].astype(c.dtype)
        )
        q, k, v = jnp.split(qkv, 3, axis=2)
        q = constrain(q, ("batch", "seq", "heads", None))
        k = constrain(k, ("batch", "seq", "heads", None))
        v = constrain(v, ("batch", "seq", "heads", None))
        attn = _attention(q, k, v, c)
        x = x + jnp.einsum(
            "bshd,hde->bse", attn, p["proj_kernel"].astype(c.dtype)
        ) + p["proj_bias"].astype(c.dtype)
    x = constrain(x, ("batch", "seq", "embed"))
    h = _layernorm(x, p["ln2_scale"], p["ln2_bias"])
    if "moe_in" in p:
        delta, aux = _moe_mlp(h, p, c, mask)
        x = x + delta
    else:
        h = jnp.einsum("bse,em->bsm", h, p["fc_kernel"].astype(c.dtype))
        h = jax.nn.gelu(h + p["fc_bias"].astype(c.dtype))
        h = constrain(h, ("batch", "seq", "mlp"))
        x = x + jnp.einsum(
            "bsm,me->bse", h, p["out_kernel"].astype(c.dtype)
        ) + p["out_bias"].astype(c.dtype)
        aux = jnp.float32(0.0)
    return constrain(x, ("batch", "seq", "embed")), aux


def _features_aux(params: Params, tokens, config: GPTConfig, mask=None):
    """tokens (B, S) int32 → (final-layernorm features (B, S, E),
    summed MoE aux loss).

    The pre-head backbone, split out so the chunked cross-entropy can
    apply the lm_head per sequence chunk instead of materializing the
    full (B, S, vocab) f32 logits (the single largest activation — 3.3
    GB at B=16, S=1024, V=50304)."""
    c = config
    B, S = tokens.shape
    # Explicitly all-gather the embedding table for the lookup: a gather
    # from the (vocab/tp, embed/fsdp)-sharded table forces SPMD into
    # "involuntary full rematerialization" (replicate + repartition every
    # step).  Constraining the operand replicated makes the all-gather a
    # deliberate, one-per-step collective and lets the gather partition
    # cleanly along the tokens' batch/seq sharding.  The lm_head einsum
    # below still consumes the sharded table.
    wte_lookup = constrain(params["wte"], (None, None)).astype(c.dtype)
    x = wte_lookup[tokens]
    x = x + params["wpe"].astype(c.dtype)[:S]
    x = constrain(x, ("batch", "seq", "embed"))

    def body(carry, layer_params):
        xx, aux_sum = carry
        fn = _block
        if c.remat:
            # Everything of the block is recomputed in the backward pass
            # but the flash kernel's (o, lse): one forward call costs
            # eight times what the next dearest recompute (qkv) does per
            # byte kept.  The dense path has no such names.
            from ray_tpu.ops.flash_attention import RESIDUAL_NAMES

            fn = jax.checkpoint(
                _block, static_argnums=(2,),
                policy=jax.checkpoint_policies.save_only_these_names(
                    *RESIDUAL_NAMES
                ),
            )
        xx, aux = fn(xx, layer_params, c, mask)
        return (xx, aux_sum + aux), None

    (x, aux), _ = lax.scan(
        body, (x, jnp.float32(0.0)), params["blocks"],
        unroll=max(1, c.scan_unroll),
    )
    return _layernorm(x, params["lnf_scale"], params["lnf_bias"]), aux


def features(params: Params, tokens, config: GPTConfig):
    """tokens (B, S) int32 → final-layernorm features (B, S, E)."""
    return _features_aux(params, tokens, config)[0]


def _head(params: Params, x, config: GPTConfig):
    """Tied lm_head: features (B, S, E) → logits (B, S, V) f32."""
    logits = jnp.einsum(
        "bse,ve->bsv",
        x,
        params["wte"].astype(config.dtype),
        preferred_element_type=jnp.float32,
    )
    return constrain(logits, ("batch", "seq", "vocab"))


def forward(params: Params, tokens, config: GPTConfig):
    """tokens (B, S) int32 → logits (B, S, vocab) in f32."""
    return _head(params, features(params, tokens, config), config)


def loss_fn(params: Params, batch, config: GPTConfig):
    """Next-token cross-entropy.  batch: {"tokens": (B, S+1) int32} or
    {"inputs", "targets"} each (B, S).  With config.xent_chunk set (and
    S divisible by it) the lm_head+softmax runs per sequence chunk,
    capping peak logits memory."""
    if "tokens" in batch:
        inputs = batch["tokens"][:, :-1]
        targets = batch["tokens"][:, 1:]
    else:
        inputs, targets = batch["inputs"], batch["targets"]
    x, aux = _features_aux(params, inputs, config, batch.get("mask"))
    aux_term = (
        config.moe_aux_coeff * aux if config.num_experts > 0 else 0.0
    )
    if config.xent_chunk and inputs.shape[1] % config.xent_chunk == 0:
        from ray_tpu.models.xent import chunked_xent

        return chunked_xent(
            x, params["wte"], targets, batch.get("mask"),
            config.xent_chunk, config.dtype,
        ) + aux_term
    logits = _head(params, x, config)
    # lse − target_logit instead of log_softmax + gather: avoids writing a
    # second full (B, S, V) f32 array (1.6 GB at B=8, S=1024, V=50k).
    lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
    tl = jnp.take_along_axis(
        logits.astype(jnp.float32), targets[..., None], axis=-1
    )[..., 0]
    ll = tl - lse
    mask = batch.get("mask")
    if mask is None:
        return -ll.mean() + aux_term
    mask = mask.astype(jnp.float32)
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0) + aux_term


def num_params(config: GPTConfig) -> int:
    shapes = jax.eval_shape(partial(init, config=config), jax.random.key(0))
    return sum(
        math.prod(a.shape) for a in jax.tree.leaves(shapes)
    )


def flops_per_token(config: GPTConfig, seq_len: Optional[int] = None) -> float:
    """Approximate training FLOPs/token: 6N + attention term.

    N excludes the position table but keeps wte — the lm_head is tied to
    it, so its matmul is real executed compute (nanoGPT estimate_mfu
    convention; under-counting it would overstate MFU headroom).
    """
    c = config
    s = seq_len or c.max_seq_len
    n = num_params(c) - c.max_seq_len * c.embed_dim  # minus wpe only
    if c.num_experts > 1:
        # top-1 routing executes ONE expert FFN per token: count 1/X of
        # the expert-FFN params as active compute (else MoE MFU would be
        # overstated ~X-fold)
        moe_ffn = 2 * c.num_layers * c.num_experts * c.embed_dim * c.mlp_dim
        n = n - moe_ffn + moe_ffn // c.num_experts
    return 6 * n + 12 * c.num_layers * c.embed_dim * s
