"""HuggingFace Transformers interop for the flagship GPT-2.

Role-equivalent of ray: python/ray/train/huggingface/ (Transformers
integration) — here the useful TPU form: convert a `transformers`
GPT2LMHeadModel's torch weights into this repo's stacked-layer jax
params (models/gpt2.py layout) so pretrained checkpoints train/serve on
the TPU stack.  The reverse of a "wrapper": weights move into the
TPU-native model rather than wrapping torch in actors.

Layout notes:
- HF Conv1D stores (in, out); our einsum kernels are (in, ...) too, so
  no transposes except the qkv head split.
- HF c_attn is (E, 3E) = [q|k|v]; ours is (E, 3H, D) with q heads at
  [0:H], k at [H:2H], v at [2H:3H] (models/gpt2.py _block split).
- Per-layer tensors stack into a leading L axis (lax.scan-friendly,
  one pytree leaf per parameter kind instead of L dicts).
- The vocab pads with zero rows to a multiple of 128 for MXU tiling
  (models/gpt2.py GPTConfig.vocab_size comment).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np

from ray_tpu.models.gpt2 import GPTConfig


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def config_from_hf(hf_config, *, pad_vocab_to: int = 128,
                   **overrides) -> GPTConfig:
    """Map a transformers GPT2Config onto GPTConfig."""
    import jax.numpy as jnp

    kwargs: Dict[str, Any] = dict(
        vocab_size=_round_up(hf_config.vocab_size, pad_vocab_to),
        max_seq_len=hf_config.n_positions,
        num_layers=hf_config.n_layer,
        num_heads=hf_config.n_head,
        embed_dim=hf_config.n_embd,
        dtype=jnp.bfloat16,
    )
    kwargs.update(overrides)
    return GPTConfig(**kwargs)


def params_from_hf(model, *, pad_vocab_to: int = 128,
                   **config_overrides) -> Tuple[Dict[str, Any], GPTConfig]:
    """(params, config) from a transformers GPT2LMHeadModel instance.

    Works on any loaded checkpoint (`GPT2LMHeadModel.from_pretrained` or
    a fresh config-built model); no network access here.
    """
    import jax.numpy as jnp

    config = config_from_hf(
        model.config, pad_vocab_to=pad_vocab_to, **config_overrides
    )
    sd = {
        k: v.detach().cpu().numpy() for k, v in model.state_dict().items()
    }
    L, E, H = config.num_layers, config.embed_dim, config.num_heads
    D = config.head_dim
    dt = config.param_dtype

    def stacked(key_fmt: str) -> np.ndarray:
        return np.stack(
            [sd[key_fmt.format(i=i)] for i in range(L)], axis=0
        )

    # qkv: (L, E, 3E) -> (L, E, 3, H, D) -> (L, E, 3H, D)
    c_attn_w = stacked("transformer.h.{i}.attn.c_attn.weight")
    qkv_kernel = c_attn_w.reshape(L, E, 3, H, D).reshape(L, E, 3 * H, D)
    c_attn_b = stacked("transformer.h.{i}.attn.c_attn.bias")
    qkv_bias = c_attn_b.reshape(L, 3, H, D).reshape(L, 3 * H, D)
    # attn out proj: (L, E, E) -> (L, H, D, E)
    proj_kernel = stacked("transformer.h.{i}.attn.c_proj.weight").reshape(
        L, H, D, E
    )

    wte = sd["transformer.wte.weight"]
    if config.vocab_size > wte.shape[0]:
        pad = np.zeros(
            (config.vocab_size - wte.shape[0], E), wte.dtype
        )
        wte = np.concatenate([wte, pad], axis=0)

    j = lambda a: jnp.asarray(a, dt)  # noqa: E731
    params = {
        "wte": j(wte),
        "wpe": j(sd["transformer.wpe.weight"]),
        "blocks": {
            "ln1_scale": j(stacked("transformer.h.{i}.ln_1.weight")),
            "ln1_bias": j(stacked("transformer.h.{i}.ln_1.bias")),
            "qkv_kernel": j(qkv_kernel),
            "qkv_bias": j(qkv_bias),
            "proj_kernel": j(proj_kernel),
            "proj_bias": j(stacked("transformer.h.{i}.attn.c_proj.bias")),
            "ln2_scale": j(stacked("transformer.h.{i}.ln_2.weight")),
            "ln2_bias": j(stacked("transformer.h.{i}.ln_2.bias")),
            "fc_kernel": j(stacked("transformer.h.{i}.mlp.c_fc.weight")),
            "fc_bias": j(stacked("transformer.h.{i}.mlp.c_fc.bias")),
            "out_kernel": j(stacked("transformer.h.{i}.mlp.c_proj.weight")),
            "out_bias": j(stacked("transformer.h.{i}.mlp.c_proj.bias")),
        },
        "lnf_scale": j(sd["transformer.ln_f.weight"]),
        "lnf_bias": j(sd["transformer.ln_f.bias"]),
    }
    return params, config


# ---------------------------------------------------------------------------
# Llama family (models/llama.py layout)
# ---------------------------------------------------------------------------


#: ``model_type``s whose block is Qwen3's: q and k RMS-normed per head
#: (``q_norm`` / ``k_norm``, one (head_dim,) scale each); the ``_moe`` ones
#: with a softmax router over ``num_experts`` experts of width
#: ``moe_intermediate_size`` in every layer.  SDAR (``sdar``, ``sdar_moe``)
#: is the same block served by diffusion over blocks
#: (``models/block_diffusion.py``)
_PER_HEAD_NORM = ("qwen3", "qwen3_moe", "sdar", "sdar_moe")


def llama_config_from_hf(hf_config, **overrides):
    """Map a transformers LlamaConfig — or a Qwen3-MoE / SDAR one: ``head_dim``,
    the per-head ``q_norm`` / ``k_norm``, the experts and ``norm_topk_prob``;
    or an Ouro one (``model_type`` ``ouro``): ``ouro_fields`` — onto
    LlamaConfig."""
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    kwargs: Dict[str, Any] = dict(
        vocab_size=hf_config.vocab_size,
        max_seq_len=hf_config.max_position_embeddings,
        num_layers=hf_config.num_hidden_layers,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=getattr(
            hf_config, "num_key_value_heads", hf_config.num_attention_heads
        ),
        embed_dim=hf_config.hidden_size,
        mlp_dim=hf_config.intermediate_size,
        rope_theta=getattr(hf_config, "rope_theta", 10000.0),
        rms_eps=hf_config.rms_norm_eps,
        tie_embeddings=bool(getattr(hf_config, "tie_word_embeddings", False)),
        dtype=jnp.bfloat16,
        # 0: embed_dim // num_heads
        head_dim=getattr(hf_config, "head_dim", None) or 0,
    )
    family = getattr(hf_config, "model_type", "")
    if family in _PER_HEAD_NORM:
        kwargs["qk_norm"] = "head"
    if family.endswith("_moe"):
        if getattr(hf_config, "mlp_only_layers", None) or getattr(
                hf_config, "decoder_sparse_step", 1) != 1:
            raise NotImplementedError("dense blocks among the expert blocks")
        kwargs.update(
            mlp_dim=0, num_experts=hf_config.num_experts,
            experts_per_token=hf_config.num_experts_per_tok,
            expert_dim=hf_config.moe_intermediate_size,
            router_norm_topk=bool(hf_config.norm_topk_prob),
        )
    if family == "solar_open2":
        kwargs.update(solar_open2_fields(hf_config))
    if family == "ouro":
        kwargs.update(ouro_fields(hf_config))
    kwargs.update(overrides)
    return LlamaConfig(**kwargs)


def ouro_fields(hf_config) -> Dict[str, Any]:
    """Ouro's keys (``model_type`` ``ouro``, a LoopLM) as LlamaConfig fields:
    the stack run ``total_ut_steps`` times a token with shared weights, the
    final norm behind every pass, K and V of every (pass, layer), the
    four-norm sandwich block, the exit gate and its ``early_exit_threshold``
    (1 as published; anything else LlamaConfig refuses)."""
    get = _getter(hf_config)
    if get("use_sliding_window") or get("rope_scaling"):
        raise NotImplementedError(
            "an Ouro config with a sliding window or scaled rotary positions is "
            "not written")
    return dict(
        loop_passes=int(get("total_ut_steps")), sandwich_norm=True,
        early_exit_threshold=float(get("early_exit_threshold", 1.0)),
        rope_theta=float(get("rope_theta")),
    )


def _getter(hf_config):
    """``get(key, default=None)`` of a published config, be it a dict (a
    benchmark configuration's file) or a transformers config object."""
    if isinstance(hf_config, dict):
        return hf_config.get
    return lambda k, d=None: getattr(hf_config, k, d)


def solar_open2_fields(hf_config) -> Dict[str, Any]:
    """Solar-Open2's keys (``model_type`` ``solar_open2``) as LlamaConfig
    fields: layer ``i`` is gated GQA without rotation where ``i in
    gqa_layers``, else Kimi delta attention (``linear_attn_config``: heads,
    one size for keys and values, the short convolution's taps;
    ``kda_use_full_proj`` false: the decay's and the output gate's
    projections are low-rank, ``head_dim`` wide as ``fla``'s; ``kda_allow_
    neg_eigval``: write strength up to 2); every layer from
    ``first_k_dense_replace`` on over ``n_routed_experts`` experts and
    ``n_shared_experts`` shared ones of ``moe_intermediate_size``.  The
    config names neither the router's score function nor the shared expert's
    width: sigmoid scores, and one expert's width a shared expert, are
    ASSUMED (the DeepSeek-V3 line's, whose key names these are)."""
    from ray_tpu.models.llama import FULL, LINEAR

    get = _getter(hf_config)
    lin = get("linear_attn_config")
    if get("kda_use_full_proj") or get("first_k_dense_replace"):
        raise NotImplementedError(
            "full-rank KDA gate projections and leading dense blocks are not written")
    gqa = set(get("gqa_layers"))
    return dict(
        rope_theta=float(get("rope_theta")) if get("use_rope") else None,
        head_dim=get("head_dim"), attn_output_gate=bool(get("use_gqa_gate")),
        layer_types=tuple(FULL if i in gqa else LINEAR
                          for i in range(get("num_hidden_layers"))),
        linear_kind="kda", linear_num_heads=lin["num_heads"],
        linear_key_head_dim=lin["head_dim"], linear_value_head_dim=lin["head_dim"],
        linear_conv_kernel=lin["short_conv_kernel_size"],
        linear_gate_rank=lin["head_dim"],
        linear_neg_eigval=bool(get("kda_allow_neg_eigval")),
        num_experts=get("n_routed_experts"),
        experts_per_token=get("num_experts_per_tok"),
        expert_dim=get("moe_intermediate_size"),
        shared_expert_dim=get("n_shared_experts") * get("moe_intermediate_size"),
        router_scoring="sigmoid", router_norm_topk=bool(get("norm_topk_prob")),
        router_scale=float(get("routed_scaling_factor") or 1.0),
    )


def llama_params_from_hf(model, **config_overrides):
    """(params, config) from a transformers LlamaForCausalLM instance.

    HF Linear weights are (out, in); our einsum kernels are (in, ...) so
    every projection transposes, and q/k/o reshape their flat head dim
    into (heads, head_dim).  HF checkpoints already use the rotate-half
    RoPE convention this model implements, so no head permutation is
    needed.
    """
    import jax.numpy as jnp

    config = llama_config_from_hf(model.config, **config_overrides)
    sd = {
        k: v.detach().cpu().numpy() for k, v in model.state_dict().items()
    }
    L, E, H, KV, D = (
        config.num_layers, config.embed_dim, config.num_heads,
        config.num_kv_heads, config.head_dim,
    )
    dt = config.param_dtype

    def stacked(fmt: str) -> np.ndarray:
        return np.stack([sd[fmt.format(i=i)] for i in range(L)], axis=0)

    j = lambda a: jnp.asarray(a, dt)  # noqa: E731
    wq = stacked("model.layers.{i}.self_attn.q_proj.weight")  # (L, H*D, E)
    wk = stacked("model.layers.{i}.self_attn.k_proj.weight")
    wv = stacked("model.layers.{i}.self_attn.v_proj.weight")
    wo = stacked("model.layers.{i}.self_attn.o_proj.weight")  # (L, E, H*D)
    params = {
        "tok_embed": j(sd["model.embed_tokens.weight"]),
        "blocks": {
            "attn_norm": j(
                stacked("model.layers.{i}.input_layernorm.weight")
            ),
            "wq": j(wq.transpose(0, 2, 1).reshape(L, E, H, D)),
            "wk": j(wk.transpose(0, 2, 1).reshape(L, E, KV, D)),
            "wv": j(wv.transpose(0, 2, 1).reshape(L, E, KV, D)),
            "wo": j(wo.transpose(0, 2, 1).reshape(L, H, D, E)),
            "mlp_norm": j(
                stacked("model.layers.{i}.post_attention_layernorm.weight")
            ),
        },
        "final_norm": j(sd["model.norm.weight"]),
    }
    blocks = params["blocks"]
    if config.sandwich_norm:
        # Ouro's block: ``x += N2(Attn(N1(x)))``; ``x += N4(SwiGLU(N3(x)))``
        blocks["attn_norm_out"] = j(stacked("model.layers.{i}.input_layernorm_2.weight"))
        blocks["mlp_norm_out"] = j(
            stacked("model.layers.{i}.post_attention_layernorm_2.weight"))
    if config.loop_passes > 1:
        params["exit_gate"] = {
            "w": j(sd["model.early_exit_gate.weight"].reshape(E)),
            "b": j(sd["model.early_exit_gate.bias"].reshape(1)),
        }
    if config.qk_norm == "head":
        blocks["q_norm"] = j(stacked("model.layers.{i}.self_attn.q_norm.weight"))
        blocks["k_norm"] = j(stacked("model.layers.{i}.self_attn.k_norm.weight"))
    if config.num_experts:
        # (L, X, in, out): HF keeps one Linear (out, in) an expert
        def experts(name: str) -> np.ndarray:
            return np.stack([
                np.stack([
                    sd[f"model.layers.{i}.mlp.experts.{e}.{name}.weight"].T
                    for e in range(config.num_experts)
                ]) for i in range(L)
            ])

        blocks["w_router"] = j(
            stacked("model.layers.{i}.mlp.gate.weight").transpose(0, 2, 1)
        )
    else:
        def experts(name: str) -> np.ndarray:
            return stacked(
                "model.layers.{i}.mlp." + name + ".weight"
            ).transpose(0, 2, 1)
    blocks["w_gate"] = j(experts("gate_proj"))
    blocks["w_up"] = j(experts("up_proj"))
    blocks["w_down"] = j(experts("down_proj"))
    if not config.tie_embeddings:
        params["lm_head"] = j(sd["lm_head.weight"])
    return params, config
