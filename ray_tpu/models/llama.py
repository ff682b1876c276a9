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

``kv_lora_rank`` > 0 makes the attention of every block multi-head LATENT
attention (DeepSeek-V2/V3's), whose cache row is one normed latent and
one rotary key shared by all heads (``_latent_attention``).  With
``index_topk`` > 0 a learned INDEXER beside it scores every visible key
and attention sees the ``index_topk`` best of them (DeepSeek-V3.2's,
GLM-5's): such a config keeps two kinds of state per layer in the cache
(``init_cache``) and a decode step attends to the rows the indexer chose.
With ``index_topk`` == 0 there is no indexer (DeepSeek-V3's own form,
JoyAI-LLM-Flash's): the cache holds the latent rows alone and a step
attends to every visible key.  Either runs decode in the absorbed form
over the cache where it lies (``ops/latent_decode_attention.py``) and
prefill in the expanded form (``ops/latent_prefill_attention.py``: one
flash kernel, or XLA over query blocks), may lead with
``first_dense_layers`` dense blocks before its expert blocks (two
parameter stacks under the one block body), routes with DeepSeek-V3's
sigmoid router beside a shared expert, and may hold only ``experts_held``
of the experts its router routes over (one chip's share of an
expert-parallel layer).  It runs on the cached paths only: ``forward`` /
``loss_fn`` refuse it.

The cached step takes a run of Sq new tokens per row at per-row offsets.
Every caller but one has Sq == 1 for all rows (a decode step) or one row
from position 0 (a prefill); ``mtp_layers`` = 1 adds DeepSeek-V3's
multi-token-prediction module — one more block with its own cache layer,
``params["mtp"]`` — and ``models/mtp.py`` drafts a token with it and
verifies it with a step of Sq == 2 for every row, which yields one or two
tokens a row.  ``_pick_token`` is the one sampler of every path, and
``draw_keys`` the one schedule of its keys.

``mask_block`` B > 1 makes the cached paths' mask BLOCK-causal (a query
sees every key up to the end of its own block of B positions), which is
what a model generating by diffusion over blocks (SDAR) is run under:
``models/block_diffusion.py`` refines a block of B tokens a row in place
with steps of Sq == B for every row — the K/V path's every-row run,
written into the cache in place (``_write_and_read``) — and commits it.
``head_dim`` is a field (the projections' width need not be
``embed_dim``), ``qk_norm="head"`` norms each head of q and k apart, and
the softmax router renormalises its chosen weights with
``router_norm_topk``: Qwen3-MoE's block, SDAR's.

``layer_types`` gives every layer its own MIXER kind (Olmo-Hybrid's,
Qwen3-Next's): ``full_attention`` as above, or ``linear_attention`` — the
gated delta rule (``ops/gated_delta.py``; ``_gated_delta_mixer``), whose
state is no list of per-token rows but ONE (d_k, d_v) float32 matrix a
head and the last inputs of a short convolution.  Each kind has its own
parameter stack, the one layer loop (``_layer_loop``) scans over the
pattern's periods, and the cache holds both kinds of state in one tree
(``init_cache``): K/V for the full layers only, ``gdn_state`` / ``gdn_conv``
for the linear ones.  A prefill leaves a row the state of its own tokens
from ZERO, whatever the row held; a decode step updates it where it lies.
``post_norm`` is OLMo-2's block (the norm on each sub-layer's OUTPUT), and
``rope_theta`` None leaves q and k unrotated.

A window may be the WHOLE model's or a layer KIND's.  ``sliding_window`` > 0
is Mistral's: every layer's queries see the last ``sliding_window`` keys,
over ONE rolling cache that the engine sizes (``rolling_cache_len``).
``SLIDING`` in ``layer_types`` is MiMo-V2-Flash's: window layers BESIDE full
ones, each kind with its own KV heads, rotary base, window and learned sink
(``AttentionKind``; ``LlamaConfig.sliding``), its own parameter stack
(``swa_blocks``) and its own K/V pair in the one cache tree — ``k`` / ``v``
at every position for the full layers, ``swa_k`` / ``swa_v`` of ``window``
rolling slots a row for the window layers (``init_cache``).  Keys may be
wider than values (``v_head_dim``), only the leading ``rotary_dim`` of a head
turned, the values scaled (``value_scale``); such a model goes with experts,
leading dense blocks and ``experts_held``, runs ``_kind_attention`` under the
one ``_block_step`` (``ops/kv_prefill_attention.py`` for a prompt,
``ops/kv_decode_attention.py`` for a step), prefills whole prompts and steps
one token a row, and runs on the cached paths only: ``forward`` /
``loss_fn`` refuse it by name.

``block_form`` "shortcut" is LongCat-Flash's shortcut-connected DOUBLE
layer (``_shortcut_block_step``): two latent attentions and two dense
SwiGLUs in series — a layer owns TWO cache layers — and one expert layer
(the same ``_ffn``) that reads the first sub-layer's normed residual and is
added only at the layer's end.  Its softmax router chooses by score + a
selection-only bias among ``num_experts`` experts and ``zero_experts``
identity ("zero-compute") experts, whose term is weight x input and costs
no matrix; ``mla_scale_q_lora`` / ``mla_scale_kv_lora`` scale the two
latents.  Cached paths only, like every latent config.

``loop_passes`` T > 1 is a LOOPED decoder (Ouro's LoopLM): the whole stack of
``num_layers`` blocks is run T times a token with the SAME weights, the final
norm behind every pass (pass t + 1 reads normed states), logits from pass T.
The passes ride the ONE layer loop — one ``lax.scan`` over (pass, layer),
``_looped`` — so a program holds one block and not T x L unrolled; every
(pass, layer) pair keeps K and V of its own — cache layer ``pass * num_layers
+ l``, T x ``num_layers`` of them for ``num_layers`` parameter layers
(``kv_layers``) — because block l in pass t attends over what block l wrote IN
PASS t at the earlier positions.
An exit gate (``params["exit_gate"]``, embed -> 1 with bias) reads each pass's
output, ``lambda_t = sigmoid(x_t w + b)``; the exit distribution is
``exit_distribution``'s.  At the published ``early_exit_threshold`` 1 only the
last pass reaches it: every token runs every pass, the gate changes no logit,
and the cache counts what a lower threshold could save (``loop_exit_mass``).
``sandwich_norm`` is such a model's block: a norm before AND behind each
sub-layer, four scales a block.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.ops import (
    gated_delta,
    kv_decode_attention,
    kv_prefill_attention,
    latent_decode_attention,
    latent_prefill_attention,
    topk_mask,
)
from ray_tpu.parallel.sharding import constrain

Params = Dict[str, Any]

#: a layer's mixer kind (``LlamaConfig.layer_types``; the published names)
LINEAR, FULL, SLIDING = "linear_attention", "full_attention", "sliding_attention"


@dataclasses.dataclass(frozen=True)
class AttentionKind:
    """What ONE kind of K/V attention layer has of its own (``LlamaConfig.
    attention_kind``): its KV heads, its rotary base, and — a window kind —
    the keys a query sees (its own and the ``window - 1`` before it, held in
    ``window`` rolling slots a row) and whether every query head has a
    learned SINK, a float that joins the softmax's denominator and carries no
    value."""
    num_kv_heads: int
    rope_theta: Optional[float] = 10000.0
    window: int = 0
    sink: bool = False


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    max_seq_len: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    embed_dim: int = 4096
    mlp_dim: int = 11008
    rope_theta: Optional[float] = 10000.0  # None: no rotation
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attention_impl: str = "dense"  # "dense" | "ring"
    # a MODEL-WIDE sliding window (Mistral-style): > 0 limits every query of
    # every layer to the last `sliding_window` keys, in training AND in the
    # cached decode paths, over ONE rolling cache sized by the engine
    # (``rolling_cache_len``).  0 = full causal.  A window that only SOME
    # layers have is no number here but a layer KIND: ``SLIDING`` in
    # ``layer_types``, whose window, KV heads, rotary base and sink are
    # ``sliding``'s and whose cache is its own (``init_cache``)
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
    # RMSNorm of q and k before the rotation: True over the WHOLE
    # projected vectors, all heads together (OLMoE) | "head" over each
    # head's head_dim values, one (head_dim,) scale for all heads (Qwen3,
    # SDAR)
    qk_norm: Any = False
    # latent attention (MLA, DeepSeek-V2/V3, GLM-5): kv_lora_rank > 0
    # replaces wq/wk/wv by low-rank projections; the cache holds
    # kv_lora_rank + qk_rope_head_dim numbers a token, for all heads
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    # (on the K/V path ``v_head_dim`` > 0 is the width of a VALUE head where
    # it is not ``head_dim``: MiMo-V2-Flash's 192-wide keys over 128-wide
    # values)
    v_head_dim: int = 0
    # the sparse-attention indexer beside it (DeepSeek-V3.2, GLM-5):
    # index_n_heads heads of index_head_dim score every visible key and
    # attention sees the index_topk best (all, while there are fewer)
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # leading blocks with a dense SwiGLU of width mlp_dim before the
    # expert blocks (DeepSeek's first_k_dense_replace); num_layers
    # counts both
    first_dense_layers: int = 0
    # a SwiGLU of this width added for every token beside the routed
    # experts (0: none)
    shared_expert_dim: int = 0
    # the router: "softmax" probabilities over all its outputs (OLMoE) |
    # "sigmoid" scores (DeepSeek-V3, GLM-5).  A sigmoid router, and a
    # softmax router over zero_experts (LongCat-Flash), chooses by score +
    # a selection-only bias (``router_bias``) and weighs by the score
    # alone; either's chosen weights divided by their sum with
    # router_norm_topk, times router_scale
    router_scoring: str = "softmax"
    router_norm_topk: bool = False
    router_scale: float = 1.0
    # one chip's share of an expert-parallel layer: the router keeps
    # num_experts outputs, this program holds experts [expert_offset,
    # expert_offset + experts_held) and computes their part only
    # (0: all of them are held)
    experts_held: int = 0
    expert_offset: int = 0
    # multi-token-prediction modules behind the last layer (DeepSeek-V3's
    # num_nextn_predict_layers; 0 or 1): one more expert block of the
    # model's shape with its own cache layer, which drafts the token
    # after the next for a speculative step (``models/mtp.py``)
    mtp_layers: int = 0
    # values a head (0: embed_dim // num_heads).  A field because the
    # projections' width num_heads * head_dim need not be embed_dim
    # (SDAR-30B-A3B: 32 heads of 128 on 2,048)
    head_dim: int = 0
    # the cached paths' mask: a query sees every key up to the END of its
    # own block of mask_block positions (block diffusion: the tokens of a
    # block see each other); 1 is causal
    mask_block: int = 1
    # the mixer of every layer, in layer order: LINEAR | FULL | SLIDING
    # (Olmo-Hybrid: 3 linear, 1 full, repeated; MiMo-V2-Flash: 1 full, then
    # runs of 4 or 5 window layers each closed by a full one); () = all
    # full attention.  LINEAR and SLIDING do not go together
    layer_types: tuple = ()
    # the SLIDING layers' kind (the FULL layers' is the model's own
    # ``num_kv_heads`` and ``rope_theta``, no window, no sink)
    sliding: Optional[AttentionKind] = None
    # the leading values of every q and k head that are rotated (0: all
    # ``head_dim``; MiMo-V2-Flash: int(192 x 0.334) = 64), half-split pairs
    # (i, i + rotary_dim / 2) as ``_rope`` turns them
    rotary_dim: int = 0
    # a factor on every value head (MiMo-V2-Flash's attention_value_scale),
    # applied to v before it is cached
    value_scale: float = 1.0
    # the linear layers' gated delta rule: heads (keys' and values' alike),
    # a key's and a value's size, the short convolution's taps, write
    # strength in (0, 2) (``linear_allow_neg_eigval``) or (0, 1), and the
    # tokens of a prefill's chunk
    linear_num_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel: int = 4
    linear_neg_eigval: bool = False
    linear_chunk: int = 64
    # the linear layers' rule: "gated_delta" — one decay a head (Qwen3-Next's
    # GatedDeltaNet, Olmo-Hybrid's) | "kda" — Kimi Delta Attention (Kimi
    # Linear, Solar-Open2): a decay a KEY CHANNEL of every head, made by a
    # low-rank projection of width ``linear_gate_rank`` (as is the output's
    # sigmoid gate), ``dt_bias`` a channel (``_kda_mixer``).  A "kda" run is
    # taken ``linear_segment`` tokens at a time, state and convolution tail
    # carried from segment to segment, so that what a 16k-token prompt's
    # projections and chunked rule hold at once is a segment's
    linear_kind: str = "gated_delta"
    linear_gate_rank: int = 0
    linear_segment: int = 2048
    # full-attention layers with an output gate (Solar-Open2's
    # ``use_gqa_gate``, the gated attention of the Qwen3-Next line): the heads'
    # outputs times sigmoid(h W_og), elementwise, before W_o
    attn_output_gate: bool = False
    # the block's residual path: x + f(norm(x)) (False) | x + norm(f(x)),
    # OLMo-2's and OLMo-3's (True); the same two scales either way
    post_norm: bool = False
    # the block's form: "serial" — one mixer, then one feed-forward, dense
    # OR experts | "shortcut" — LongCat-Flash's shortcut-connected double
    # layer: two (latent attention, dense SwiGLU of width mlp_dim)
    # sub-layers in series, each attention with its own cache layer, and
    # ONE expert layer that reads the first sub-layer's normed
    # post-attention residual and is added only at the layer's end, across
    # the second attention and dense SwiGLU (``_shortcut_block_step``)
    block_form: str = "serial"
    # identity ("zero-compute") experts behind the num_experts routed
    # ones: the router has num_experts + zero_experts outputs, and a choice
    # that falls on one of the last zero_experts adds weight x the expert
    # layer's input — no matrix, whichever chip holds which experts
    zero_experts: int = 0
    # latent attention's LoRA scales (LongCat-Flash's keys of these names):
    # the normed query latent times sqrt(embed_dim / q_lora_rank), the
    # normed key-value latent times sqrt(embed_dim / kv_lora_rank) — so a
    # head's nope key and its value carry the factor, the rotary key not
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    # a LOOPED decoder (Ouro's ``total_ut_steps``): the stack of num_layers
    # blocks run this many times a token with the same weights, final_norm
    # behind every pass, a K/V cache layer a (pass, layer), an exit gate
    # that reads every pass's output (1: each layer once, no gate)
    loop_passes: int = 1
    # the cumulative exit probability at which a token's logits are taken
    # (Ouro's key of this name).  1 — only the last pass reaches it, every
    # token runs every pass — is what is written
    early_exit_threshold: float = 1.0
    # the block's residual path, a third form beside ``post_norm``'s two:
    # x + norm_out(f(norm_in(x))), a norm on BOTH sides of each sub-layer
    # (``attn_norm`` / ``attn_norm_out``, ``mlp_norm`` / ``mlp_norm_out``)
    sandwich_norm: bool = False

    def __post_init__(self):
        if not self.head_dim:
            object.__setattr__(self, "head_dim", self.embed_dim // self.num_heads)
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.linear_kind not in ("gated_delta", "kda"):
            raise ValueError(
                f"linear_kind is 'gated_delta' or 'kda'; got {self.linear_kind!r}")
        if self.layer_types:
            if (len(self.layer_types) != self.num_layers
                    or set(self.layer_types) - {LINEAR, FULL, SLIDING}):
                raise ValueError(
                    f"layer_types names each of the {self.num_layers} layers "
                    f"{LINEAR!r}, {FULL!r} or {SLIDING!r}; got {self.layer_types}"
                )
            if self.latent or self.mtp_layers or self.mask_block > 1:
                raise NotImplementedError(
                    "layer_types goes with K/V attention: no latent attention, "
                    "multi-token-prediction module or block mask"
                )
            if LINEAR in self.layer_types and (
                    (self.num_experts and self.linear_kind != "kda")
                    or self.first_dense_layers or SLIDING in self.layer_types):
                raise NotImplementedError(
                    "linear-attention layers go with full attention and a dense "
                    "SwiGLU (under linear_kind 'kda' also with an expert layer): "
                    "no experts, leading dense blocks or window layers"
                )
        if self.linear_kind == "kda" and LINEAR in self.layer_types and (
                self.linear_gate_rank < 1 or self.post_norm):
            raise NotImplementedError(
                "Kimi-delta layers make their decay and their output gate through "
                "linear_gate_rank > 0 columns, in a pre-norm block"
            )
        if self.attn_output_gate and (
                self.latent or self.sliding is not None or self.mask_block > 1):
            raise NotImplementedError(
                "attn_output_gate is written for plain K/V full-attention layers"
            )
        if (SLIDING in self.layer_types) != (self.sliding is not None):
            raise ValueError(
                f"``sliding`` is the kind of the {SLIDING!r} layers of "
                "layer_types: one goes with the other"
            )
        if self.sliding is not None and (
                self.sliding.window < 1 or self.sliding_window or self.qk_norm
                or self.post_norm or self.attention_impl != "dense"):
            raise NotImplementedError(
                "window layers see a window of at least one key and go without a "
                "model-wide sliding_window, qk_norm, post_norm or ring attention"
            )
        if (self.v_head_dim or self.rotary_dim or self.value_scale != 1.0) and (
                self.sliding is None and not self.latent):
            raise NotImplementedError(
                "a value width, a rotary share or a value scale of its own is "
                "written for the K/V path of a model with attention kinds "
                "(layer_types with window layers)"
            )
        if self.block_form not in ("serial", "shortcut"):
            raise ValueError(
                f"block_form is 'serial' or 'shortcut'; got {self.block_form!r}"
            )
        if self.block_form == "shortcut" and (
            not self.latent or not self.num_experts or not self.mlp_dim
            or self.index_topk or self.first_dense_layers or self.mtp_layers
            or self.layer_types or self.post_norm
        ):
            raise NotImplementedError(
                "the shortcut-connected double layer is two latent attentions "
                "over every visible key, two dense SwiGLUs and one expert "
                "layer: no indexer, leading dense blocks, multi-token-"
                "prediction module, layer_types or post_norm"
            )
        if self.zero_experts and not self.num_experts:
            raise ValueError("zero_experts stand behind num_experts routed experts")
        if self.loop_passes < 1:
            raise ValueError(f"loop_passes counts the passes, 1 or more; got {self.loop_passes}")
        if self.early_exit_threshold != 1:
            raise NotImplementedError(
                "early_exit_threshold under 1 lets rows leave the loop at different "
                "passes, which is a change to the scheduler and not to the model: "
                "every token runs all loop_passes passes (threshold 1) in what is written"
            )
        if self.loop_passes > 1 and (
                self.latent or self.layer_types or self.block_form != "serial"
                or self.mtp_layers or self.mask_block > 1 or self.num_experts
                or self.first_dense_layers):
            raise NotImplementedError(
                "loop_passes > 1 is written for plain K/V attention over a dense "
                "SwiGLU: no latent attention, layer_types, shortcut block, multi-"
                "token-prediction module, block mask, experts or leading dense blocks"
            )
        if self.sandwich_norm and (
                self.post_norm or self.latent or self.layer_types
                or self.block_form != "serial"):
            raise NotImplementedError(
                "sandwich_norm is the serial K/V block's third residual form: not "
                "with post_norm, latent attention, layer_types or the shortcut block"
            )

    @property
    def period(self) -> tuple:
        """The shortest run of kinds that ``layer_types`` repeats (the
        whole list, where nothing shorter tiles it)."""
        return _periodic(self.layer_types)

    @property
    def linear_layers(self) -> int:
        return self.layer_types.count(LINEAR)

    @property
    def kv_layers(self) -> int:
        """Layers that keep K and V per token at every position (``k`` /
        ``v``): all, or the full-attention ones of a config with
        ``layer_types`` — one a (pass, layer) of a looped config."""
        if self.layer_types:
            return self.layer_types.count(FULL)
        return self.num_layers * self.loop_passes

    @property
    def sliding_layers(self) -> int:
        """Window layers: K and V of a row's last ``sliding.window``
        positions, in rolling slots (``swa_k`` / ``swa_v``)."""
        return self.layer_types.count(SLIDING)

    @property
    def value_dim(self) -> int:
        """Values a K/V head's VALUE has: ``head_dim`` unless the config
        says otherwise (``v_head_dim``)."""
        return self.v_head_dim or self.head_dim

    def attention_kind(self, kind: str) -> AttentionKind:
        """The numbers of one K/V attention kind: ``sliding``'s own, or
        (FULL) the model's."""
        if kind == SLIDING:
            return self.sliding
        return AttentionKind(self.num_kv_heads, self.rope_theta)

    @property
    def latent(self) -> bool:
        """The attention kind: latent rows (and, with ``index_topk``, the
        indexer's keys) in the cache (True) or K and V per KV head
        (False)."""
        return self.kv_lora_rank > 0

    @property
    def mixers_per_layer(self) -> int:
        """Attentions a layer runs, each with a cache layer of its own."""
        return 2 if self.block_form == "shortcut" else 1

    @property
    def cache_layers(self) -> int:
        """Layers of token state in the cache: one an attention of the
        model's (layer l of a shortcut-connected config owns 2l and 2l +
        1; layer l of a looped config owns ``t * num_layers + l`` in pass t)
        and the multi-token-prediction module's behind them."""
        return (self.num_layers * self.mixers_per_layer * self.loop_passes
                + self.mtp_layers)

    @property
    def expert_layers(self) -> int:
        """Layers with routed experts, the module's block among them."""
        if not self.num_experts:
            return 0
        return self.num_layers + self.mtp_layers - self.first_dense_layers

    @property
    def experts_here(self) -> int:
        return self.experts_held or self.num_experts

    @property
    def router_outputs(self) -> int:
        """The router's width: the routed experts (all of them, wherever
        they are held) and the identity experts behind them."""
        return self.num_experts + self.zero_experts

    @property
    def router_bias(self) -> bool:
        """Does the router choose by score + a selection-only bias?"""
        return self.router_scoring == "sigmoid" or self.zero_experts > 0

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

    @staticmethod
    def olmo_hybrid_7b(**kw) -> "LlamaConfig":
        """Olmo-Hybrid-7B's published shape: 32 layers of (3 gated-delta-rule,
        1 full attention), OLMo-2's block, no rotation.  ``num_layers`` may
        be cut to fewer whole periods."""
        layers = kw.setdefault("num_layers", 32)
        defaults = dict(
            vocab_size=100352, max_seq_len=65536, num_heads=30, num_kv_heads=30,
            embed_dim=3840, mlp_dim=11008, rope_theta=None, rms_eps=1e-6,
            qk_norm=True, post_norm=True,
            layer_types=(LINEAR, LINEAR, LINEAR, FULL) * (layers // 4),
            linear_num_heads=30, linear_key_head_dim=96,
            linear_value_head_dim=192, linear_neg_eigval=True,
        )
        defaults.update(kw)
        return LlamaConfig(**defaults)

    @staticmethod
    def tiny_hybrid(**kw) -> "LlamaConfig":
        """``olmo_hybrid_7b`` at toy widths: two periods, chunks of four."""
        defaults = dict(
            num_layers=8, num_kv_heads=4, rope_theta=None, qk_norm=True,
            post_norm=True, layer_types=(LINEAR, LINEAR, LINEAR, FULL) * 2,
            linear_num_heads=4, linear_key_head_dim=8,
            linear_value_head_dim=16, linear_neg_eigval=True, linear_chunk=4,
        )
        defaults.update(kw)
        return LlamaConfig.tiny(**defaults)

    @staticmethod
    def solar_open2(**kw) -> "LlamaConfig":
        """Solar-Open2-250B's published shape: 48 layers, every fourth (0, 4,
        ..) gated GQA without rotation (64 Q / 8 KV x 128), the others Kimi
        delta attention (64 heads, keys and values of 128, low-rank gates of
        128, write strength up to 2), every layer over 320 sigmoid-routed
        experts of 1,280, top 8 renormalised, and one shared expert.
        ``num_layers`` may be cut to fewer whole periods."""
        layers = kw.setdefault("num_layers", 48)
        defaults = dict(
            vocab_size=196608, max_seq_len=1048576, num_heads=64, num_kv_heads=8,
            head_dim=128, embed_dim=4096, mlp_dim=10240, rope_theta=None,
            rms_eps=1e-6, attn_output_gate=True,
            layer_types=((FULL, LINEAR, LINEAR, LINEAR) * -(-layers // 4))[:layers],
            linear_kind="kda", linear_num_heads=64, linear_key_head_dim=128,
            linear_value_head_dim=128, linear_gate_rank=128, linear_neg_eigval=True,
            num_experts=320, experts_per_token=8, expert_dim=1280,
            shared_expert_dim=1280, router_scoring="sigmoid", router_norm_topk=True,
        )
        defaults.update(kw)
        return LlamaConfig(**defaults)

    @staticmethod
    def tiny_kda(**kw) -> "LlamaConfig":
        """``solar_open2`` at toy widths: two periods, chunks of four, runs
        in segments of eight, 8 experts top 2 and a shared one."""
        defaults = dict(
            num_layers=8, num_heads=4, num_kv_heads=2, rope_theta=None,
            attn_output_gate=True, layer_types=(FULL, LINEAR, LINEAR, LINEAR) * 2,
            linear_kind="kda", linear_num_heads=4, linear_key_head_dim=8,
            linear_value_head_dim=16, linear_gate_rank=8, linear_neg_eigval=True,
            linear_chunk=4, linear_segment=8, num_experts=8, experts_per_token=2,
            expert_dim=32, shared_expert_dim=32, router_scoring="sigmoid",
            router_norm_topk=True,
        )
        defaults.update(kw)
        return LlamaConfig.tiny(**defaults)

    @staticmethod
    def mimo_v2_flash(**kw) -> "LlamaConfig":
        """MiMo-V2-Flash's published shape (309B-A15B): 48 layers, layer 0
        full attention over a dense SwiGLU, then runs of window layers (the
        first four long, the other seven five) each closed by a full layer,
        all 47 with 256 sigmoid-routed experts, top 8; 64 query heads of 192
        (the first 64 rotated) over values of 128 scaled by 0.707; full
        layers 4 KV heads at rotary base 5e6, window layers 8 KV heads at
        1e4, 128 keys and a learned sink a head.  ``layer_types`` may be
        given cut to fewer layers (with ``num_layers``)."""
        layers = kw.setdefault("num_layers", 48)
        pattern = (FULL,) + (SLIDING,) * 4 + ((FULL,) + (SLIDING,) * 5) * 7 + (FULL,)
        defaults = dict(
            vocab_size=152576, max_seq_len=262144, num_heads=64, num_kv_heads=4,
            embed_dim=4096, mlp_dim=16384, rope_theta=5e6, rms_eps=1e-5,
            head_dim=192, v_head_dim=128, rotary_dim=64, value_scale=0.707,
            layer_types=pattern[:layers],
            sliding=AttentionKind(num_kv_heads=8, rope_theta=1e4, window=128, sink=True),
            first_dense_layers=1, num_experts=256, experts_per_token=8,
            expert_dim=2048, router_scoring="sigmoid", router_norm_topk=True,
        )
        defaults.update(kw)
        return LlamaConfig(**defaults)

    @staticmethod
    def tiny_swa(**kw) -> "LlamaConfig":
        """``mimo_v2_flash`` at toy widths: layer 0 full and dense, then two
        periods of (2 window, 1 full) with 8 experts, top 2; heads of 12 (8
        rotated) over values of 8; a window of 8 keys."""
        defaults = dict(
            num_layers=7, num_heads=4, num_kv_heads=1, head_dim=12, v_head_dim=8,
            rotary_dim=8, value_scale=0.707, rope_theta=5e6, mlp_dim=96,
            layer_types=(FULL,) + (SLIDING, SLIDING, FULL) * 2,
            sliding=AttentionKind(num_kv_heads=2, rope_theta=1e4, window=8, sink=True),
            first_dense_layers=1, num_experts=8, experts_per_token=2,
            expert_dim=32, router_scoring="sigmoid", router_norm_topk=True,
        )
        defaults.update(kw)
        return LlamaConfig.tiny(**defaults)

    @staticmethod
    def ouro_2_6b(**kw) -> "LlamaConfig":
        """Ouro-2.6B's published shape, a LoopLM: 48 sandwich-norm blocks of
        plain multi-head attention (16 x 128, rotary base 1e6) over a SwiGLU
        of 5,632, run 4 times a token with shared weights, 192 K/V cache
        layers, the exit gate at threshold 1."""
        defaults = dict(
            vocab_size=49152, max_seq_len=65536, num_layers=48, num_heads=16,
            num_kv_heads=16, head_dim=128, embed_dim=2048, mlp_dim=5632,
            rope_theta=1e6, rms_eps=1e-6, loop_passes=4, sandwich_norm=True,
        )
        defaults.update(kw)
        return LlamaConfig(**defaults)

    @staticmethod
    def tiny_loop(**kw) -> "LlamaConfig":
        """``ouro_2_6b`` at toy widths: 3 blocks of 4 heads of 16, 3 passes."""
        defaults = dict(
            num_layers=3, num_kv_heads=4, rope_theta=1e6, rms_eps=1e-6,
            loop_passes=3, sandwich_norm=True,
        )
        defaults.update(kw)
        return LlamaConfig.tiny(**defaults)

    @staticmethod
    def longcat_flash(**kw) -> "LlamaConfig":
        """LongCat-Flash's published language model (LongCat-Flash-Omni's
        ``config.json``): 28 shortcut-connected double layers, latent
        attention with both LoRA scales, a softmax router over 512 experts
        and 256 identity experts, top 12, weights x 6."""
        defaults = dict(
            vocab_size=131072, max_seq_len=131072, num_layers=28, num_heads=64,
            num_kv_heads=64, embed_dim=6144, mlp_dim=12288, rope_theta=1e7,
            rms_eps=1e-5, q_lora_rank=1536, kv_lora_rank=512,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            mla_scale_q_lora=True, mla_scale_kv_lora=True,
            block_form="shortcut", num_experts=512, zero_experts=256,
            experts_per_token=12, expert_dim=2048, router_scale=6.0,
        )
        defaults.update(kw)
        return LlamaConfig(**defaults)

    @staticmethod
    def tiny_shortcut(**kw) -> "LlamaConfig":
        """``longcat_flash`` at toy widths: two double layers, 8 experts
        and 4 identity experts, top 3."""
        defaults = dict(
            num_kv_heads=4, mlp_dim=96, q_lora_rank=32, kv_lora_rank=24,
            qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16,
            mla_scale_q_lora=True, mla_scale_kv_lora=True,
            block_form="shortcut", num_experts=8, zero_experts=4,
            experts_per_token=3, expert_dim=32, router_scale=6.0,
        )
        defaults.update(kw)
        return LlamaConfig.tiny(**defaults)


#: the parameter stack of each mixer kind (``_stacks``)
_STACK_OF = {LINEAR: "gdn_blocks", FULL: "blocks", SLIDING: "swa_blocks"}
_KIND_OF = {stack: kind for kind, stack in _STACK_OF.items()}


def _layer_order(config: LlamaConfig):
    """Every layer of a config with ``layer_types``, in layer order:
    ``[(its stack's name, its index in that stack, its kind, its index among
    the layers that keep its kind of state, expert FFN?)]``.  A kind's layers
    lie in its own stack (``_STACK_OF``), the ``first_dense_layers`` leading
    ones in a stack of their kind's name behind ``dense_``."""
    c = config
    in_stack, in_cache, out = {}, {}, []
    for i, kind in enumerate(c.layer_types):
        lead = i < c.first_dense_layers
        name = ("dense_" if lead else "") + _STACK_OF[kind]
        out.append((name, in_stack.get(name, 0), kind, in_cache.get(kind, 0),
                    bool(c.num_experts) and not lead))
        in_stack[name] = in_stack.get(name, 0) + 1
        in_cache[kind] = in_cache.get(kind, 0) + 1
    return out


def _stack_kind(name: str) -> str:
    """The mixer kind of the layers of the stack ``name``."""
    return _KIND_OF[name.removeprefix("dense_")]


def _stacks(config: LlamaConfig):
    """The parameter stacks of the one layer loop, in layer order:
    ``[(name in the tree, layers, first layer's index, expert FFN?)]``.
    One stack, ``blocks``, unless dense blocks lead the expert blocks —
    or the layers are of several mixer kinds (``layer_types``): then one
    stack a kind (``_STACK_OF``: ``gdn_blocks`` the linear layers',
    ``swa_blocks`` the window layers', ``blocks`` the full ones'; leading
    dense blocks apart, ``_layer_order``), each in its own layers' order, and
    the loop interleaves them (``_layer_loop``)."""
    c = config
    if c.layer_types:
        stacks = {}
        for name, _i, _kind, _cl, experts in _layer_order(c):
            stacks[name] = (stacks.get(name, (0,))[0] + 1, experts)
        return [(name, n, 0, experts) for name, (n, experts) in stacks.items()]
    if not c.first_dense_layers:
        return [("blocks", c.num_layers, 0, bool(c.num_experts))]
    return [
        ("dense_blocks", c.first_dense_layers, 0, False),
        ("blocks", c.num_layers - c.first_dense_layers,
         c.first_dense_layers, bool(c.num_experts)),
    ]


_EXPERT_TENSORS = ("w_gate", "w_up", "w_down")
#: a shortcut-connected layer's tensors that are one a SUB-LAYER, (L, 2, ..):
#: the latent attention, the two norms, the dense SwiGLU (``wd_*``: the
#: plain names are the expert layer's)
_SUB_LAYER = ("attn_norm", "w_qa", "q_a_norm", "w_qb", "w_kva", "kv_a_norm",
              "w_kb", "w_vb", "wo", "mlp_norm", "wd_gate", "wd_up", "wd_down")


def param_logical_axes(config: LlamaConfig) -> Dict[str, Any]:
    """Per-parameter logical axis names (parallel/sharding.py specs)."""
    c = config
    if c.latent:
        attn = {
            "w_qa": ("layers", "embed", None), "q_a_norm": ("layers", None),
            "w_qb": ("layers", None, "heads", None),
            "w_kva": ("layers", "embed", None), "kv_a_norm": ("layers", None),
            "w_kb": ("layers", None, "heads", None),
            "w_vb": ("layers", None, "heads", None),
        }
        if c.index_topk:
            attn.update({
                "w_iq": ("layers", None, None, None),
                "w_ik": ("layers", "embed", None),
                "ik_norm": ("layers", None), "ik_bias": ("layers", None),
                "w_iw": ("layers", "embed", None),
            })
    else:
        attn = {
            "wq": ("layers", "embed", "heads", None),
            "wk": ("layers", "embed", "kv", None),
            "wv": ("layers", "embed", "kv", None),
        }
        if c.qk_norm:
            attn.update({"q_norm": ("layers", None), "k_norm": ("layers", None)})
        if c.attn_output_gate:
            attn["w_og"] = ("layers", "embed", "heads", None)
    dense = {
        "attn_norm": ("layers", "embed"),
        **attn,
        "wo": ("layers", "heads", None, "embed"),
        "mlp_norm": ("layers", "embed"),
        "w_gate": ("layers", "embed", "mlp"),
        "w_up": ("layers", "embed", "mlp"),
        "w_down": ("layers", "mlp", "embed"),
    }
    if c.sandwich_norm:
        dense.update({"attn_norm_out": ("layers", "embed"),
                      "mlp_norm_out": ("layers", "embed")})
    expert = dict(dense, **{
        "w_router": ("layers", "embed", None),
        "w_gate": ("layers", "expert", "embed", "mlp"),
        "w_up": ("layers", "expert", "embed", "mlp"),
        "w_down": ("layers", "expert", "mlp", "embed"),
    })
    if c.router_bias:
        expert["router_bias"] = ("layers", None)
    if c.shared_expert_dim:
        expert.update({
            "ws_gate": ("layers", "embed", "mlp"),
            "ws_up": ("layers", "embed", "mlp"),
            "ws_down": ("layers", "mlp", "embed"),
        })
    if c.block_form == "shortcut":
        # a double layer: its two sub-layers' tensors (``_SUB_LAYER``, the
        # dense SwiGLUs under names of their own) have the sub-layer behind
        # the layer, the one expert layer's are as above
        twice = {k: (v[0], None, *v[1:]) for k, v in dense.items()}
        expert = {
            **{k: v for k, v in expert.items() if k not in dense},
            **{k: v for k, v in twice.items() if k not in _EXPERT_TENSORS},
            **{"wd" + k[1:]: twice[k] for k in _EXPERT_TENSORS},
            **{k: expert[k] for k in _EXPERT_TENSORS},
        }
    out = {"tok_embed": ("vocab", "embed"), "final_norm": ("embed",)}
    for name, _n, _first, experts in _stacks(c):
        out[name] = expert if experts else dense
        if c.sliding is not None and c.sliding.sink and _stack_kind(name) == SLIDING:
            out[name] = dict(out[name], sink=("layers", "heads"))
    if LINEAR in c.layer_types:
        name = _STACK_OF[LINEAR]
        kda = c.linear_kind == "kda"
        gates = {
            "kda_wf_a": ("layers", "embed", None),
            "kda_wf_b": ("layers", None, "heads", None),
            "kda_wg_a": ("layers", "embed", None),
            "kda_wg_b": ("layers", None, "heads", None),
        } if kda else {
            "gdn_wz": ("layers", "embed", "heads", None),
            "gdn_wa": ("layers", "embed", "heads"),
        }
        out[name] = {
            **{k: v for k, v in out[name].items() if k not in (*attn, "wo")},
            "gdn_wq": ("layers", "embed", "heads", None),
            "gdn_wk": ("layers", "embed", "heads", None),
            "gdn_wv": ("layers", "embed", "heads", None),
            **gates,
            "gdn_wb": ("layers", "embed", "heads"),
            "gdn_wo": ("layers", "heads", None, "embed"),
            "gdn_conv": ("layers", None, None), "gdn_norm": ("layers", None),
            "a_log": ("layers", "heads"),
            "dt_bias": ("layers", "heads", None) if kda else ("layers", "heads"),
        }
    if not c.tie_embeddings:
        out["lm_head"] = ("vocab", "embed")
    if c.loop_passes > 1:
        out["exit_gate"] = {"w": ("embed",), "b": (None,)}
    if c.mtp_layers:
        out["mtp"] = {
            "enorm": ("embed",), "hnorm": ("embed",), "head_norm": ("embed",),
            "eh_proj": (None, "embed"),
            "block": expert if c.num_experts else dense,
        }
    return out


def _init_blocks(rng, config: LlamaConfig, layers: int, experts: bool,
                 kind: str = FULL) -> Params:
    """One stack of ``layers`` blocks: a leading layer axis on every
    leaf.  ``experts``: the feed-forward is the expert layer (router,
    ``experts_here`` SwiGLUs of width ``expert_dim``, the shared expert
    if any), else one SwiGLU of width ``mlp_dim``.  With ``sandwich_norm``
    two more scales a block, ``attn_norm_out`` and ``mlp_norm_out``.  ``kind``
    LINEAR: the mixer is the gated delta rule (``_gated_delta_mixer`` names its tensors;
    ``a_log`` = log U(0, 16) and ``dt_bias`` = 1 as ``fla``'s
    ``GatedDeltaNet`` starts them; under ``linear_kind`` "kda" ``_kda_mixer``
    names them, ``a_log`` = log U(1, 16) a head and ``dt_bias`` a key channel
    the inverse softplus of a step drawn log-uniformly in [0.001, 0.1], as
    ``fla``'s ``KimiDeltaAttention`` starts them); FULL | SLIDING: K/V attention with the
    kind's KV heads (``LlamaConfig.attention_kind``), values of
    ``value_dim``, and where the kind has one a ``sink`` a query head,
    which starts at zero.  A shortcut-connected stack
    (``block_form``): the attention, the norms and a dense SwiGLU
    (``wd_*``) twice a layer, (L, 2, ..), beside the one expert layer."""
    c = config
    dt = c.param_dtype
    linear = kind == LINEAR
    L, E, H, D = layers, c.embed_dim, c.num_heads, c.head_dim
    KV = c.num_kv_heads if linear else c.attention_kind(kind).num_kv_heads
    A = (L, 2) if c.block_form == "shortcut" else (L,)  # a sub-layer's lead
    X = (c.experts_here,) if experts else ()
    M = c.expert_dim if experts else c.mlp_dim
    k = jax.random.split(rng, 8)
    std = 0.02
    resid_std = std / math.sqrt(2 * c.num_layers)

    def norm(key, shape, s):
        return (jax.random.normal(key, shape, jnp.float32) * s).astype(dt)

    def more(i):  # keys for tensors newer than the eight above
        return jax.random.fold_in(k[1], i)

    if linear:
        Hl, Dk, Dv = c.linear_num_heads, c.linear_key_head_dim, c.linear_value_head_dim
        blk = {
            "gdn_wq": norm(k[1], (L, E, Hl, Dk), std),
            "gdn_wk": norm(k[2], (L, E, Hl, Dk), std),
            "gdn_wv": norm(k[3], (L, E, Hl, Dv), std),
            "gdn_wz": norm(more(1), (L, E, Hl, Dv), std),
            "gdn_wa": norm(more(2), (L, E, Hl), std),
            "gdn_wb": norm(more(3), (L, E, Hl), std),
            "gdn_wo": norm(k[4], (L, Hl, Dv, E), resid_std),
            # the taps in front: a tap's channels lie along the lanes
            "gdn_conv": norm(more(4), (L, c.linear_conv_kernel, Hl * (2 * Dk + Dv)), std),
            "gdn_norm": jnp.ones((L, Dv), dt),
            "a_log": jnp.log(jax.random.uniform(
                more(5), (L, Hl), jnp.float32, 1e-6, 16.0)).astype(dt),
            "dt_bias": jnp.ones((L, Hl), dt),
        }
        if c.linear_kind == "kda":
            r = c.linear_gate_rank
            for gone in ("gdn_wz", "gdn_wa"):
                del blk[gone]
            step = jnp.exp(jax.random.uniform(
                more(14), (L, Hl, Dk), jnp.float32, math.log(1e-3), math.log(0.1)))
            blk.update({
                "kda_wf_a": norm(more(1), (L, E, r), std),
                "kda_wf_b": norm(more(2), (L, r, Hl, Dk), std),
                "kda_wg_a": norm(more(15), (L, E, r), std),
                "kda_wg_b": norm(more(16), (L, r, Hl, Dv), std),
                "a_log": jnp.log(jax.random.uniform(
                    more(5), (L, Hl), jnp.float32, 1.0, 16.0)).astype(dt),
                "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
            })
    elif c.latent:
        Q, C = c.q_lora_rank, c.kv_lora_rank
        Dn, Dr, Dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        J, Di = c.index_n_heads, c.index_head_dim
        blk = {
            "w_qa": norm(k[1], (*A, E, Q), std),
            "q_a_norm": jnp.ones((*A, Q), dt),
            "w_qb": norm(more(1), (*A, Q, H, Dn + Dr), std),
            "w_kva": norm(k[2], (*A, E, C + Dr), std),
            "kv_a_norm": jnp.ones((*A, C), dt),
            "w_kb": norm(more(2), (*A, C, H, Dn), std),
            "w_vb": norm(k[3], (*A, C, H, Dv), std),
            "wo": norm(k[4], (*A, H, Dv, E), resid_std),
        }
        if c.index_topk:
            blk.update({
                "w_iq": norm(more(3), (L, Q, J, Di), std),
                "w_ik": norm(more(4), (L, E, Di), std),
                "ik_norm": jnp.ones((L, Di), dt),
                "ik_bias": jnp.zeros((L, Di), dt),
                "w_iw": norm(more(5), (L, E, J), std),
            })
    else:
        blk = {
            "wq": norm(k[1], (L, E, H, D), std),
            "wk": norm(k[2], (L, E, KV, D), std),
            "wv": norm(k[3], (L, E, KV, c.value_dim), std),
            "wo": norm(k[4], (L, H, c.value_dim, E), resid_std),
        }
        if c.attention_kind(kind).sink:
            blk["sink"] = jnp.zeros((L, H), dt)
        if c.attn_output_gate:
            blk["w_og"] = norm(more(13), (L, E, H, c.value_dim), std)
        if c.qk_norm:
            blk.update({
                "q_norm": jnp.ones((L, D if c.qk_norm == "head" else H * D), dt),
                "k_norm": jnp.ones((L, D if c.qk_norm == "head" else KV * D), dt),
            })
    blk.update({
        "attn_norm": jnp.ones((*A, E), dt),
        "mlp_norm": jnp.ones((*A, E), dt),
        "w_gate": norm(k[5], (L, *X, E, M), std),
        "w_up": norm(k[6], (L, *X, E, M), std),
        "w_down": norm(k[7], (L, *X, M, E), resid_std),
    })
    if c.sandwich_norm:
        blk.update({"attn_norm_out": jnp.ones((L, E), dt),
                    "mlp_norm_out": jnp.ones((L, E), dt)})
    if c.block_form == "shortcut":
        blk.update({
            "wd_gate": norm(more(10), (*A, E, c.mlp_dim), std),
            "wd_up": norm(more(11), (*A, E, c.mlp_dim), std),
            "wd_down": norm(more(12), (*A, c.mlp_dim, E), resid_std),
        })
    if experts:
        blk["w_router"] = norm(
            jax.random.fold_in(k[5], 1), (L, E, c.router_outputs), std
        )
        if c.router_scoring == "softmax" and c.router_bias:
            # as the published buffer starts: softmax scores lie near 1 /
            # router_outputs, a hundred times under a sigmoid's, and a bias
            # drawn as below would decide every choice alone
            blk["router_bias"] = jnp.zeros((L, c.router_outputs), dt)
        elif c.router_bias:
            # selection-only: it moves which experts are chosen, never
            # their weights.  A hundredth of a sigmoid's range, the size
            # of the gaps between the best scores: at a tenth the bias
            # alone chose the experts and one took 5-10x the mean load
            # (my chip run, PR 30)
            blk["router_bias"] = norm(more(6), (L, c.router_outputs), 0.01)
        if c.shared_expert_dim:
            Ms = c.shared_expert_dim
            blk.update({
                "ws_gate": norm(more(7), (L, E, Ms), std),
                "ws_up": norm(more(8), (L, E, Ms), std),
                "ws_down": norm(more(9), (L, Ms, E), resid_std),
            })
    return blk


def init(rng, config: LlamaConfig) -> Params:
    c = config
    dt = c.param_dtype
    std = 0.02
    k0 = jax.random.split(rng, 8)[0]

    def norm(key, shape, s):
        return (jax.random.normal(key, shape, jnp.float32) * s).astype(dt)

    params: Params = {
        "tok_embed": norm(k0, (c.vocab_size, c.embed_dim), std),
        "final_norm": jnp.ones((c.embed_dim,), dt),
    }
    for name, layers, first, experts in _stacks(c):
        # the one stack of a config without leading dense blocks draws
        # from ``rng`` itself, as it always did
        key = jax.random.fold_in(rng, first) if first else rng
        kind = _stack_kind(name)
        if kind != FULL:
            key = jax.random.fold_in(rng, (1 << 21) if kind == LINEAR else (1 << 22))
        if name.startswith("dense_") and c.layer_types:
            key = jax.random.fold_in(key, 1 << 23)
        params[name] = _init_blocks(key, c, layers, experts, kind)
    if not c.tie_embeddings:
        params["lm_head"] = norm(
            jax.random.fold_in(k0, 1), (c.vocab_size, c.embed_dim), std
        )
    if c.loop_passes > 1:
        # the exit gate: embed -> 1 with bias, read off every pass's output
        params["exit_gate"] = {
            "w": norm(jax.random.fold_in(rng, 1 << 24), (c.embed_dim,), std),
            "b": jnp.zeros((1,), dt),
        }
    if c.mtp_layers:
        if c.mtp_layers != 1 or not c.latent or c.index_topk:
            raise NotImplementedError(
                "one multi-token-prediction module, behind a latent-attention "
                "model without an indexer, is what is written"
            )
        key = jax.random.fold_in(rng, 1 << 20)
        params["mtp"] = {
            "enorm": jnp.ones((c.embed_dim,), dt),
            "hnorm": jnp.ones((c.embed_dim,), dt),
            "head_norm": jnp.ones((c.embed_dim,), dt),
            # rows: the next token's embedding first, the hidden state behind
            "eh_proj": norm(key, (2 * c.embed_dim, c.embed_dim), std),
            "block": _init_blocks(
                jax.random.fold_in(key, 1), c, 1, bool(c.num_experts)
            ),
        }
    return params


def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, positions, theta):
    """Rotary embedding over the last dim.  x: (B, S, H, D).  ``theta``
    None: no rotation."""
    if theta is None:
        return x
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


def _layer_params(blocks: Params, config: LlamaConfig):
    """``(xs, whole)`` for a layer loop over one stack: ``xs`` is what
    it scans over (every stacked leaf, and the layer's index in the
    stack), ``whole`` what its body adds unsliced to the layer's
    parameters: an expert stack's three expert tensors in the compute
    dtype (see ``_ffn``; cast here, once a forward and not once a
    layer) — and a shortcut-connected stack's sub-layer tensors, as they
    are — nothing for a dense stack."""
    layers = jnp.arange(blocks["attn_norm"].shape[0])
    if "w_router" not in blocks:
        return (blocks, layers), {}
    whole = {k: blocks[k].astype(config.dtype) for k in _EXPERT_TENSORS}
    if config.block_form == "shortcut":
        # the sub-layers' tensors whole too, as (2L, ..) — sub-layer i of
        # layer l is 2l + i — for ``_shortcut_block_step`` to take each
        # matrix's slice where it uses it: sliced a layer at a time by the
        # scan and then a sub-layer, every matrix outside the experts was
        # COPIED once a call, 13.4 ms of a 27 ms decode step at
        # LongCat-Flash's widths (my chip run, PR 49, call 2)
        whole.update({
            k: blocks[k].reshape(-1, *blocks[k].shape[2:]) for k in _SUB_LAYER
        })
    rest = {k: v for k, v in blocks.items() if k not in whole}
    return (rest, layers), whole


def _rope_part(x, positions, theta, rotary_dim: int):
    """``_rope`` over the first ``rotary_dim`` values of the last dim, the
    others as they are (0: all of them turn)."""
    if not rotary_dim or rotary_dim == x.shape[-1]:
        return _rope(x, positions, theta)
    turned = _rope(x[..., :rotary_dim], positions, theta)
    return jnp.concatenate([turned, x[..., rotary_dim:]], axis=-1)


def _qkv(h, p, positions, config: LlamaConfig, kind: Optional[AttentionKind] = None):
    """Projections of the normed input: q (B, S, H, D) and k (B, S, KV,
    D) with rotary positions applied (to their first ``rotary_dim`` values,
    at ``kind``'s base where the layer is of an attention kind), v (B, S,
    KV, value_dim) times ``value_scale``.  With ``qk_norm``
    q and k are RMS-normed before the rotation (scales ``q_norm`` /
    ``k_norm``): over their WHOLE projected width, all heads together, or
    (``"head"``) each head over its own D values under one (D,) scale."""
    c = config
    B, S = h.shape[:2]
    theta = kind.rope_theta if kind is not None else c.rope_theta

    def normed(x, scale):
        if not c.qk_norm:
            return x
        if c.qk_norm == "head":
            return _rmsnorm(x, p[scale], c.rms_eps)
        return _rmsnorm(x.reshape(B, S, -1), p[scale], c.rms_eps).reshape(x.shape)

    q = jnp.einsum("bse,ehd->bshd", h, p["wq"].astype(c.dtype))
    q = _rope_part(normed(q, "q_norm"), positions, theta, c.rotary_dim)
    kk = jnp.einsum("bse,ekd->bskd", h, p["wk"].astype(c.dtype))
    kk = _rope_part(normed(kk, "k_norm"), positions, theta, c.rotary_dim)
    vv = jnp.einsum("bse,ekd->bskd", h, p["wv"].astype(c.dtype))
    if c.value_scale != 1.0:
        vv = (vv.astype(jnp.float32) * c.value_scale).astype(vv.dtype)
    return q, kk, vv


def _swiglu(h, w_gate, w_up, w_down, config: LlamaConfig):
    c = config
    gate = jnp.einsum("bse,em->bsm", h, w_gate.astype(c.dtype))
    up = jnp.einsum("bse,em->bsm", h, w_up.astype(c.dtype))
    act = constrain(jax.nn.silu(gate) * up, ("batch", "seq", "mlp"))
    return jnp.einsum("bsm,me->bse", act, w_down.astype(c.dtype))


def _route(x, p, config: LlamaConfig):
    """Router of the expert layer.  x: (N, E).  Returns ``(weight (N,
    k) float32, output (N, k) int32)`` in order of falling selection
    score, over ALL ``router_outputs`` (the identity experts are the
    outputs from ``num_experts`` on).  Scores in float32: ``softmax``
    probabilities over all outputs, or ``sigmoid(logits)`` (DeepSeek-V3's
    ``noaux_tc`` with one group).  Without a bias (OLMoE, Qwen3-MoE, SDAR:
    ``LlamaConfig.router_bias``) the top k scores are chosen; with it
    (every sigmoid router; LongCat-Flash's softmax router) the top k of
    score + ``router_bias`` are, and the bias is in the choice only.  The
    weights are the chosen SCORES, divided by their sum if
    ``router_norm_topk``, times ``router_scale``."""
    c = config
    logits = jnp.einsum(
        "ne,ex->nx", x, p["w_router"].astype(c.dtype),
        preferred_element_type=jnp.float32,
    )
    if not c.router_bias:
        weight, expert = lax.top_k(
            jax.nn.softmax(logits, axis=-1), c.experts_per_token
        )
        if not c.router_norm_topk and c.router_scale == 1.0:  # OLMoE: as they are
            return weight, expert
    else:
        score = (jax.nn.softmax(logits, axis=-1) if c.router_scoring == "softmax"
                 else jax.nn.sigmoid(logits))
        _, expert = lax.top_k(
            score + p["router_bias"].astype(jnp.float32), c.experts_per_token
        )
        weight = jnp.take_along_axis(score, expert, axis=-1)
    if c.router_norm_topk:
        weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    return weight * c.router_scale, expert


#: rows of the smallest block ``_ffn`` gathers where not every (token,
#: choice) row has a group: the N * K rows of every decode step fit one
_HELD_BLOCK_MIN = 1024
#: a block's rows over what a uniform router sends the experts held here:
#: room for an uneven one (a layer's fullest expert has 1.3-1.8 times the
#: mean, PERF.md section 5; sixteen of them together far less)
_HELD_BLOCK_ROOM = 1.5


def _held_block(rows: int, config: LlamaConfig) -> int:
    """R, the rows ``_ffn`` gathers at a time where only some of its
    ``rows`` (token, choice) rows chose an expert held here:
    ``_HELD_BLOCK_ROOM`` times this program's share of a uniform router's
    rows (``experts_here`` / ``router_outputs``), at least
    ``_HELD_BLOCK_MIN``, at most all of them, in whole row tiles of the
    grouped matmul."""
    from ray_tpu.ops.grouped_matmul import ROW_TILE

    c = config
    want = math.ceil(_HELD_BLOCK_ROOM * rows * c.experts_here / c.router_outputs)
    return -(-min(rows, max(_HELD_BLOCK_MIN, want)) // ROW_TILE) * ROW_TILE


def _ffn(h, p, config: LlamaConfig):
    """The ONE feed-forward body.  h: (B, S, E), already normed.
    Returns ``(y, routing)``: y (B, S, E) to add to the residual, and
    for an expert block ``{"rows": (experts held,) int32 rows each
    expert computed in this call, "experts": (B, S, k) the router
    outputs each token chose, with ``zero_experts`` also "zero": () int32
    (token, choice) pairs that fell on identity experts, where not every
    row has a group also "tiles": () int32 row tiles (``grouped_matmul.
    ROW_TILE`` rows each) gathered and handed the kernel}`` (None for a
    dense block: one without ``w_router``).

    Dense: SwiGLU, ``w_down(silu(w_gate h) * w_up h)``.

    Experts: ``_route`` picks ``experts_per_token`` experts and their
    weights for every token.  The B*S*k (token, choice) rows are sorted
    by expert, the three matrices are applied as grouped matmuls over
    the sorted rows, and the results are put back in token order,
    weighted and summed over the k choices.  Every routed pair is
    computed: no capacity, no dropped token, no dense (rows, experts,
    width) intermediate.  ``shared_expert_dim`` adds one more SwiGLU of
    every token, unweighted.

    ``experts_held``: this program holds experts [``expert_offset``,
    ``expert_offset + experts_held``) of the ``num_experts`` the router
    routes over.  Rows that chose another expert belong to no group and
    count as zero: y is this chip's part of the layer (its experts' terms
    and the shared expert), and a token whose k experts all live
    elsewhere gets the shared expert alone.

    ``zero_experts``: the router's outputs from ``num_experts`` on are
    identity experts, whose term is ``weight x h``.  Their rows belong
    to no group like rows held elsewhere — what a token costs hangs on
    how many REAL experts it chose, 0 to k — and their weights' sum
    times h is added behind the combine, for every token, on whichever
    chip it lives (like a shared expert, it belongs to no chip's share).

    With either, only the rows of a group are moved.  What is SORTED is
    the (B*S*k,) int32 keys alone (a held row's expert, ``experts_here``
    for the others, which sort last): the held rows' indices come first in
    the order, by expert.  What is GATHERED is a block of R of them at a
    time — R a static size read from the call's shape and the config's
    share of a uniform router's rows (``_held_block``) — R rows of the
    input, through the three grouped matmuls at (R, ·), each weighted by
    its router weight in float32 and SCATTER-ADDED into y (B*S, E)
    float32 at its token: as the product of the (B*S, R) matrix of ones
    where a row is a token's with the weighted rows, in float32 on the
    MXU (XLA's own scatter takes the TPU 0.1-0.6 us a row, more than
    this product at every served shape but one: PERF.md section 6, PR
    54).  R is a block and not a capacity: a call whose held rows fill
    more than one block runs further blocks (a loop over the call's
    ``ceil(B*S*k / R)`` blocks whose iterations past the held rows are
    skipped, a static trip count, so it differentiates), and every held
    row is computed for any routing.  A call of at most R rows (every
    decode step) is one block without the loop.

    The three expert tensors arrive STACKED over the stack's layers,
    (L, X, ..), with ``p["layer"]`` saying which layer this is
    (``_layer_params``): a layer loop that handed the kernel one
    layer's slice would copy that slice first (805 MB a layer at
    OLMoE's widths), so the kernel gets all L * X matrices as its
    groups and sizes that are zero outside this layer's X."""
    c = config
    if "w_router" not in p:
        return _swiglu(h, p["w_gate"], p["w_up"], p["w_down"], c), None
    from ray_tpu.ops.grouped_matmul import ROW_TILE, grouped_matmul

    B, S, E = h.shape
    X, K = c.experts_here, c.experts_per_token
    x = h.reshape(B * S, E)

    def experts(xs, rows):
        """Sorted rows xs (R, E), of which expert g owns ``rows[g]``
        behind those of the experts before it, through this layer's X
        matrices: (R, E), undefined past the groups."""
        L = p["w_gate"].shape[0]
        sizes = lax.dynamic_update_slice(
            jnp.zeros((L * X,), jnp.int32), rows, (p["layer"] * X,)
        )
        w_gate, w_up, w_down = (
            p[k].reshape(L * X, *p[k].shape[2:]) for k in _EXPERT_TENSORS
        )
        gate = grouped_matmul(xs, w_gate, sizes)
        up = grouped_matmul(xs, w_up, sizes)
        return grouped_matmul(jax.nn.silu(gate) * up, w_down, sizes)

    with jax.named_scope("moe_route"):
        weight, expert = _route(x, p, c)
        flat = expert.reshape(-1)                      # (N*K,) row -> expert
        some = bool(c.experts_held or c.zero_experts)  # not every row has a group
        if c.zero_experts:
            zero = expert >= c.num_experts             # (N, K) identity choices
            zero_weight = jnp.where(zero, weight, 0.0).sum(-1)
        if some:
            flat = flat - c.expert_offset              # computed here? or sorts last
            flat = jnp.where((flat >= 0) & (flat < X), flat, X)
        order = jnp.argsort(flat, stable=True)         # sorted row -> row
        rows = (flat[:, None] == jnp.arange(X)[None, :]).sum(
            0, dtype=jnp.int32
        )                                              # bincount, (X,)
    routing = {"rows": rows, "experts": expert.reshape(B, S, K)}
    if not some:
        with jax.named_scope("moe_route"):
            xs = x[order // K]                         # (N*K, E) by expert
        with jax.named_scope("moe_experts"):
            ys = experts(xs, rows)
        with jax.named_scope("moe_combine"):
            back = jnp.argsort(order)                  # row -> sorted row
            y = ys[back].reshape(B * S, K, E).astype(jnp.float32)
            y = (y * weight[:, :, None]).sum(1)
    else:
        R = _held_block(B * S * K, c)
        blocks = -(-B * S * K // R)
        with jax.named_scope("moe_route"):
            ends = jnp.cumsum(rows)                    # (X,) a group's end
            held = ends[-1]                            # the rows of a group
            order = jnp.pad(order, (0, blocks * R - B * S * K))
            weight = weight.reshape(-1)

        def block(b, y):
            """Sorted rows [b R, (b + 1) R) into y (N, E) float32."""
            with jax.named_scope("moe_route"):
                at = lax.dynamic_slice(order, (b * R,), (R,))
                live = b * R + jnp.arange(R) < held
                token = at // K
                xs = x[token]                          # (R, E) by expert
                # a group's rows in the block, from where it ends there
                sizes = jnp.diff(jnp.clip(ends - b * R, 0, R), prepend=0)
            with jax.named_scope("moe_experts"):
                ys = experts(xs, sizes)
            with jax.named_scope("moe_combine"):
                # rows of no group come back undefined
                ys = jnp.where(live[:, None], ys.astype(jnp.float32), 0.0)
                lands = jnp.arange(B * S)[:, None] == token[None, :]
                return y + jnp.dot(
                    lands.astype(jnp.float32), ys * weight[at][:, None],
                    precision=lax.Precision.HIGHEST,
                )

        y = jnp.zeros((B * S, E), jnp.float32)
        if blocks == 1:
            y, ran = block(0, y), 1
        else:
            y = lax.fori_loop(0, blocks, lambda b, y: lax.cond(
                b * R < held, block, lambda b, y: y, b, y), y)
            ran = -(-held // R)
        routing["tiles"] = jnp.int32(ran * (R // ROW_TILE))
    if c.zero_experts:
        with jax.named_scope("moe_zero"):
            y = y + zero_weight[:, None] * x.astype(jnp.float32)
            routing["zero"] = zero.sum(dtype=jnp.int32)
    y = y.astype(c.dtype).reshape(B, S, E)
    if c.shared_expert_dim:
        with jax.named_scope("moe_shared"):
            y = y + _swiglu(h, p["ws_gate"], p["ws_up"], p["ws_down"], c)
    return y, routing


def _norm_in(x, p, name: str, config: LlamaConfig):
    """A sub-layer's input: the residual normed, or (``post_norm``) as it is."""
    return x if config.post_norm else _rmsnorm(x, p[name], config.rms_eps)


def _norm_out(y, p, name: str, config: LlamaConfig):
    """A sub-layer's output as it is, or (``post_norm``) normed: the same
    scale on the other side of the sub-layer — or (``sandwich_norm``) normed
    by a scale of its own, ``<name>_out``, the input's norm staying too."""
    if config.sandwich_norm:
        return _rmsnorm(y, p[name + "_out"], config.rms_eps)
    return _rmsnorm(y, p[name], config.rms_eps) if config.post_norm else y


def _gated_delta_mixer(x, p, config: LlamaConfig, states=None, tail=None):
    """A linear-attention layer's mixer: the gated delta rule behind its
    projections and short convolution (Qwen3-Next's ``GatedDeltaNet``,
    Olmo-Hybrid's).  x: (R, S, E).  Per token: ``q~, k~`` (H d_k each),
    ``v~, z`` (H d_v each), ``a, b`` (H each), all plain projections of x;
    ``[q~; k~; v~]`` through a causal depthwise convolution of
    ``linear_conv_kernel`` taps and a silu; per head ``q = l2norm(q) /
    sqrt(d_k)``, ``k = l2norm(k)``; ``beta = sigmoid(b)`` (doubled with
    ``linear_neg_eigval``), ``log alpha = -exp(a_log) softplus(a +
    dt_bias)``, float32; the rule (``ops/gated_delta.py``); ``y = W_o
    [RMSNorm_{d_v}(o) gdn_norm * silu(z)]``.

    ``states``, the cache's whole ``gdn_state`` (``gated_delta.packed``: L,
    R, d_k, H d_v) float32, and ``tail`` (K - 1, R, channels), the last
    pre-activation inputs of the convolution: ONE token of every row is a
    ``gated_delta.step_layer`` on layer ``p["cache_layer"]`` of the leaf,
    where it lies.  Without them the run starts from nothing (zero state,
    zeros in front of the convolution) and goes through the chunked
    ``gated_delta.scan``.  Returns (y (R, S, E), the leaf after the step |
    the run's final state (R, H, d_k, d_v), the new tail)."""
    c = config
    R, S, _ = x.shape
    H, Dk, Dv, K = (c.linear_num_heads, c.linear_key_head_dim,
                    c.linear_value_head_dim, c.linear_conv_kernel)
    dt, f32 = c.dtype, jnp.float32
    with jax.named_scope("gdn_proj"):
        def heads(name):  # (R, S, H * d) in the compute dtype
            return jnp.einsum("rse,ehd->rshd", x, p[name].astype(dt)).reshape(R, S, -1)

        u = jnp.concatenate([heads("gdn_wq"), heads("gdn_wk"), heads("gdn_wv")], -1)
        z = heads("gdn_wz").reshape(R, S, H, Dv)
        a = jnp.einsum("rse,eh->rsh", x, p["gdn_wa"].astype(dt), preferred_element_type=f32)
        b = jnp.einsum("rse,eh->rsh", x, p["gdn_wb"].astype(dt), preferred_element_type=f32)
        u = jnp.swapaxes(u, 0, 1)                               # (S, R, channels)
        if tail is None:
            tail = jnp.zeros((K - 1, *u.shape[1:]), dt)
        window = jnp.concatenate([tail, u])                     # (K - 1 + S, R, channels)
        taps = p["gdn_conv"].astype(f32)
        mixed = jax.nn.silu(sum(
            window[j:j + S].astype(f32) * taps[j] for j in range(K)
        ))
        mixed = jnp.swapaxes(mixed, 0, 1)                       # (R, S, channels)
        q, k, v = (part.reshape(R, S, H, -1) for part in jnp.split(
            mixed, [H * Dk, 2 * H * Dk], axis=-1))

        def l2norm(t):
            return t * lax.rsqrt((t * t).sum(-1, keepdims=True) + 1e-6)

        q, k = l2norm(q) / math.sqrt(Dk), l2norm(k)
        beta = jax.nn.sigmoid(b) * (2.0 if c.linear_neg_eigval else 1.0)
        log_alpha = -jnp.exp(p["a_log"].astype(f32)) * jax.nn.softplus(
            a + p["dt_bias"].astype(f32))
    if states is not None:
        # the kernel's custom call and what feeds it go by this scope in a
        # trace (``chipbench/gdn_trace.py``)
        with jax.named_scope("gdn_step"):
            o, state = gated_delta.step_layer(
                q[:, 0], k[:, 0], v[:, 0], log_alpha[:, 0], beta[:, 0], states,
                p["cache_layer"])
            o = o[:, None]
    else:
        with jax.named_scope("gdn_scan"):
            o, state = gated_delta.scan(
                q, k, v, log_alpha, beta, jnp.zeros((R, H, Dk, Dv), f32),
                chunk=c.linear_chunk)
    with jax.named_scope("gdn_out"):
        o = o * lax.rsqrt((o * o).mean(-1, keepdims=True) + c.rms_eps)
        o = o * p["gdn_norm"].astype(f32) * jax.nn.silu(z.astype(f32))
        y = jnp.einsum("rshv,hve->rse", o.astype(dt), p["gdn_wo"].astype(dt))
    return y, state, window[S:]


def _output_gated(attn, h, p, config: LlamaConfig):
    """A full-attention layer's heads' outputs (R, S, H, D_v) times the
    sigmoid of the layer's gate projection of its normed input ``h``
    (``attn_output_gate``), or as they are."""
    if "w_og" not in p:
        return attn
    gate = jnp.einsum("bse,ehd->bshd", h, p["w_og"].astype(config.dtype),
                      preferred_element_type=jnp.float32)
    return (attn.astype(jnp.float32) * jax.nn.sigmoid(gate)).astype(attn.dtype)


def _linear_mixer(config: LlamaConfig):
    """The linear layers' mixer by the config's rule (``linear_kind``)."""
    return _kda_mixer if config.linear_kind == "kda" else _gated_delta_mixer


def _kda_mixer(x, p, config: LlamaConfig, states=None, tail=None):
    """A Kimi-delta-attention layer's mixer (Kimi Linear, arXiv:2510.26692;
    ``fla``'s ``KimiDeltaAttention``; Solar-Open2's ``kda_*`` keys): the
    delta rule with a decay A KEY CHANNEL.  x: (R, S, E).  Per token ``q~,
    k~`` (H d_k each) and ``v~`` (H d_v) plain projections of x, each through
    a causal depthwise convolution of ``linear_conv_kernel`` taps and a silu
    (one ``gdn_conv`` over the three side by side: depthwise, so the same);
    per head ``q = l2norm(q) / sqrt(d_k)``, ``k = l2norm(k)``; ``beta =
    sigmoid(x W_b)`` (doubled with ``linear_neg_eigval``); ``log alpha =
    -exp(a_log_h) softplus(x W_f_a W_f_b + dt_bias)``, (H, d_k) float32, the
    low-rank projection ``linear_gate_rank`` wide; the rule
    (``ops/gated_delta.py``, ``log_alpha`` a channel); ``y = W_o
    [RMSNorm_{d_v}(o) gdn_norm * sigmoid(x W_g_a W_g_b)]``.

    ``states`` / ``tail`` as ``_gated_delta_mixer``'s: ONE token of every row
    is a ``gated_delta.step_layer`` on layer ``p["cache_layer"]`` of the
    leaf.  Without them the run starts from nothing and goes through the
    chunked ``gated_delta.scan`` ``linear_segment`` tokens at a time, state
    and tail carried (a run of no whole number of equal segments at most that
    long goes in one).  Same returns."""
    c = config
    R, S, _ = x.shape
    H, Dk, Dv, K = (c.linear_num_heads, c.linear_key_head_dim,
                    c.linear_value_head_dim, c.linear_conv_kernel)
    dt, f32 = c.dtype, jnp.float32

    def inputs(x, tail):
        """A run's (q, k, v, log_alpha, beta, gate, the new tail)."""
        S = x.shape[1]
        with jax.named_scope("kda_proj"):
            def heads(name):
                return jnp.einsum("rse,ehd->rshd", x, p[name].astype(dt)).reshape(R, S, -1)

            def low_rank(a, b):  # (R, S, H, d) float32 through ``linear_gate_rank``
                mid = jnp.einsum("rse,ec->rsc", x, p[a].astype(dt))
                return jnp.einsum("rsc,chd->rshd", mid, p[b].astype(dt),
                                  preferred_element_type=f32)

            u = jnp.concatenate([heads("gdn_wq"), heads("gdn_wk"), heads("gdn_wv")], -1)
            u = jnp.swapaxes(u, 0, 1)                               # (S, R, channels)
            window = jnp.concatenate([tail, u])                     # (K - 1 + S, R, channels)
            taps = p["gdn_conv"].astype(f32)
            mixed = jax.nn.silu(sum(
                window[j:j + S].astype(f32) * taps[j] for j in range(K)
            ))
            mixed = jnp.swapaxes(mixed, 0, 1)                       # (R, S, channels)
            q, k, v = (part.reshape(R, S, H, -1) for part in jnp.split(
                mixed, [H * Dk, 2 * H * Dk], axis=-1))

            def l2norm(t):
                return t * lax.rsqrt((t * t).sum(-1, keepdims=True) + 1e-6)

            q, k = l2norm(q) / math.sqrt(Dk), l2norm(k)
            b = jnp.einsum("rse,eh->rsh", x, p["gdn_wb"].astype(dt), preferred_element_type=f32)
            beta = jax.nn.sigmoid(b) * (2.0 if c.linear_neg_eigval else 1.0)
            log_alpha = -jnp.exp(p["a_log"].astype(f32))[:, None] * jax.nn.softplus(
                low_rank("kda_wf_a", "kda_wf_b") + p["dt_bias"].astype(f32))
            gate = jax.nn.sigmoid(low_rank("kda_wg_a", "kda_wg_b"))
        return q, k, v, log_alpha, beta, gate, window[S:]

    def output(o, gate):
        with jax.named_scope("kda_out"):
            o = o * lax.rsqrt((o * o).mean(-1, keepdims=True) + c.rms_eps)
            o = o * p["gdn_norm"].astype(f32) * gate
            return jnp.einsum("rshv,hve->rse", o.astype(dt), p["gdn_wo"].astype(dt))

    if states is not None:
        q, k, v, log_alpha, beta, gate, tail = inputs(x, tail)
        with jax.named_scope("kda_step"):
            o, state = gated_delta.step_layer(
                q[:, 0], k[:, 0], v[:, 0], log_alpha[:, 0], beta[:, 0], states,
                p["cache_layer"])
        return output(o[:, None], gate), state, tail

    def segment(carry, x):
        state, tail = carry
        q, k, v, log_alpha, beta, gate, tail = inputs(x, tail)
        with jax.named_scope("kda_scan"):
            o, state = gated_delta.scan(q, k, v, log_alpha, beta, state, chunk=c.linear_chunk)
        return (state, tail), output(o, gate)

    start = (jnp.zeros((R, H, Dk, Dv), f32), jnp.zeros((K - 1, R, H * (2 * Dk + Dv)), dt))
    n = -(-S // c.linear_segment)
    if n == 1 or S % n:
        (state, tail), y = segment(start, x)
        return y, state, tail
    (state, tail), y = lax.scan(
        segment, start, jnp.swapaxes(x.reshape(R, n, S // n, -1), 0, 1))
    return jnp.swapaxes(y, 0, 1).reshape(R, S, -1), state, tail


def _block(x, p, positions, config: LlamaConfig):
    c = config
    h = _norm_in(x, p, "attn_norm", c)
    if "a_log" in p:
        y = _linear_mixer(c)(h, p, c)[0]
    else:
        q, kk, vv = _qkv(h, p, positions, c)
        # GQA: repeat each KV head across its query group
        if c.q_per_kv > 1:
            kk = jnp.repeat(kk, c.q_per_kv, axis=2)
            vv = jnp.repeat(vv, c.q_per_kv, axis=2)
        q = constrain(q, ("batch", "seq", "heads", None))
        kk = constrain(kk, ("batch", "seq", "heads", None))
        vv = constrain(vv, ("batch", "seq", "heads", None))
        attn = _output_gated(_attention(q, kk, vv, c), h, p, c)
        y = jnp.einsum("bshd,hde->bse", attn, p["wo"].astype(c.dtype))
    x = x + _norm_out(y, p, "attn_norm", c)
    x = constrain(x, ("batch", "seq", "embed"))
    y, routing = _ffn(_norm_in(x, p, "mlp_norm", c), p, c)
    x = constrain(x + _norm_out(y, p, "mlp_norm", c), ("batch", "seq", "embed"))
    return x, routing and routing["experts"]


def _periodic(seq: tuple) -> tuple:
    """The shortest run that ``seq`` repeats (all of it, where nothing
    shorter tiles it)."""
    for n in range(1, len(seq) + 1):
        if len(seq) % n == 0 and seq[:n] * (len(seq) // n) == seq:
            return seq[:n]
    return ()


def _segments(seq: tuple):
    """``seq`` as ``[(period, repeats)]``: an irregular head, once, and
    behind it whole repeats of one period — the split with the fewest layers
    in the two loop bodies together (MiMo-V2-Flash's 48: its first six
    layers once, then seven times (5 window, 1 full); Olmo-Hybrid's: no
    head, (3 linear, 1 full))."""
    head = min(range(len(seq)), key=lambda h: (h + len(_periodic(seq[h:])), h))
    period = _periodic(seq[head:])
    rest = [(period, (len(seq) - head) // len(period))]
    return ([(seq[:head], 1)] if head else []) + rest


def _layer_loop(params: Params, config: LlamaConfig, carry, block, unroll: int = 1,
                close=None):
    """The ONE layer loop: ``carry`` through every block in layer order.
    ``block(carry, p, cache_layer) -> (carry, aux)``: p one layer's
    parameters (and ``p["layer"]``, its index in its stack; with
    ``layer_types`` also ``p["kind"]``, its mixer kind's NAME),
    ``cache_layer`` its index among the layers that keep its kind of
    state.  Returns (carry, aux with a leading axis over the layers that
    gave it).

    One ``lax.scan`` a parameter stack, in turn (``_stacks``) — or, where
    the layers are of several mixer kinds, ONE scan over the pattern's
    periods whose body runs a period's layers in order, each on its own
    kind's stack: a period of (3 linear, 1 full) is four blocks in the loop's
    body and not ``num_layers`` unrolled.  A pattern with an irregular head
    is two such scans (``_segments``).  A looped config (``loop_passes``) runs
    its one stack that many times in ONE scan (``_looped``), ``close`` behind
    every pass."""
    c = config
    if c.layer_types:
        order = _layer_order(c)
        experts = {name: x for name, _i, _kind, _cl, x in order}
        kept, done = {}, 0
        for kinds, periods in _segments(tuple((n, k) for n, _i, k, _cl, _x in order)):
            # a period's layers of each stack and of each kind (two sets of
            # names that do not meet), and where the segment's first of each lies
            per = collections.Counter([n for n, _k in kinds] + [k for _n, k in kinds])
            base = {}
            for name, at, kind, cache_at, _x in order[done:done + len(kinds)]:
                base.setdefault(name, at)
                base.setdefault(kind, cache_at)

            def body(carry, period, kinds=kinds, per=per, base=base):
                seen, kept = collections.Counter(), {}

                def index(n, k, b):
                    i = period * n + k
                    return i + b if b else i

                for name, kind in kinds:
                    in_stack = (per[name], seen[name], base[name])
                    in_cache = (per[kind], seen[kind], base[kind])
                    seen.update((name, kind))
                    l = index(*in_stack)
                    # the layer's index waits for the layers before it, and its
                    # weights' slices with the index.  Left free, XLA slices the
                    # three linear layers' weights at the top of the body in one
                    # fusion whose outputs do not all fit fast memory: 110 MB a
                    # period go out to HBM and come back (1.2 ms of a 20 ms
                    # decode step at Olmo-Hybrid's widths, PR 48; the program
                    # before it escaped only by being large enough for XLA's
                    # rematerialisation to start)
                    leaves, tree = jax.tree.flatten(carry)
                    leaves[0], l = lax.optimization_barrier((leaves[0], l))
                    carry = jax.tree.unflatten(tree, leaves)
                    # a kind with one stack: the index in it IS the cache layer
                    cache_layer = l if in_cache == in_stack else index(*in_cache)
                    # the layer's slice of each stacked leaf, taken where it is
                    # used: sliced a period at a time (the scan's ``xs``) and
                    # then a layer, every weight is copied once a call.  An
                    # expert stack's three expert tensors go WHOLE (``_ffn``
                    # hands the kernel all L * X matrices, ``_layer_params``)
                    stack = params[name]
                    whole = {k: stack[k].astype(c.dtype) for k in _EXPERT_TENSORS
                             } if experts[name] else {}
                    p = jax.tree.map(
                        lambda a: lax.dynamic_index_in_dim(a, l, 0, keepdims=False),
                        {k: v for k, v in stack.items() if k not in whole},
                    )
                    carry, aux = block(
                        carry, dict(p, layer=l, kind=kind, **whole), cache_layer)
                    for key, v in aux.items():
                        kept.setdefault(key, []).append(v)
                return carry, {key: jnp.stack(v) for key, v in kept.items()}

            carry, aux = lax.scan(body, carry, jnp.arange(periods), unroll=unroll)
            for k, v in aux.items():
                kept.setdefault(k, []).append(v.reshape(-1, *v.shape[2:]))
            done += len(kinds) * periods
        return carry, {k: v[0] if len(v) == 1 else jnp.concatenate(v) for k, v in kept.items()}
    if c.loop_passes > 1:
        return _looped(params, c, carry, block, close, unroll)
    kept = {}
    for name, _layers, first, _experts in _stacks(c):
        xs, whole = _layer_params(params[name], c)

        def body(carry, layer, first=first, whole=whole):
            p, l = layer
            return block(carry, dict(p, layer=l, **whole), l + first if first else l)

        carry, aux = lax.scan(body, carry, xs, unroll=unroll)
        for k, v in aux.items():
            kept.setdefault(k, []).append(v)
    return carry, {k: v[0] if len(v) == 1 else jnp.concatenate(v) for k, v in kept.items()}


def _looped(params: Params, config: LlamaConfig, carry, block, close, unroll: int = 1):
    """``_layer_loop`` of a looped config: the one stack ``loop_passes`` times
    over, the same weights every pass, as ONE ``lax.scan`` over (pass, layer)
    — the program holds one block and not T x L unrolled — whose body takes
    layer l's slice of each stacked leaf where it uses it, hands ``block`` the
    cache layer ``t num_layers + l``, and behind a pass's last layer runs
    ``close(x) -> (x, y)`` on the carry's FIRST leaf (the residual: the final
    norm, and the exit gate's reading ``y``) under a ``lax.cond`` that the
    rest of the carry — a cache of gigabytes — stays out of.  Returns (carry,
    {"closed": y stacked over the passes}).

    One scan and not a scan over passes around the layers' scan: through two
    nested loops XLA carried the weights and the cache in layouts of its own
    and copied them at the program's start — two of wq / wk / wv (2 x 384 MB
    a decode step) and, in a prefill, K and V whole (2 x 3 GB: Ouro-2.6B's
    prefill did not compile for a 16 GB chip); compiled for a described v5e,
    PR 63."""
    c = config
    (name, layers, _first, _experts), = _stacks(c)
    stack = params[name]
    leaves, tree = jax.tree.flatten(carry)
    shape = jax.eval_shape(lambda x: close(x)[1], leaves[0])
    closed = jnp.zeros((c.loop_passes, *shape.shape), shape.dtype)

    def body(state, i):
        carry, closed = state
        t, l = i // layers, i % layers
        with jax.named_scope("loop_pass"):
            p = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, l, 0, keepdims=False), stack)
            carry, _aux = block(carry, dict(p, layer=l), i)
            leaves, tree = jax.tree.flatten(carry)

            def end(x, closed):
                x, y = close(x)
                return x, lax.dynamic_update_index_in_dim(closed, y, t, 0)

            leaves[0], closed = lax.cond(
                l == layers - 1, end, lambda x, closed: (x, closed), leaves[0], closed)
        return (jax.tree.unflatten(tree, leaves), closed), None

    (carry, closed), _ = lax.scan(
        body, (carry, closed), jnp.arange(c.loop_passes * layers), unroll=unroll)
    return carry, {"closed": closed}


def _features_and_choices(params: Params, tokens, config: LlamaConfig):
    c = config
    if c.latent or c.first_dense_layers or c.sliding is not None:
        raise NotImplementedError(
            "a latent-attention or dense-leading config, and one with window "
            "layers beside full ones (layer_types: sliding_attention), runs on "
            "the cached paths only (prefill_into_slot / decode_step_rowwise)"
        )
    B, S = tokens.shape
    emb = constrain(params["tok_embed"], (None, None)).astype(c.dtype)
    x = emb[tokens]
    x = constrain(x, ("batch", "seq", "embed"))
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))

    def block(carry, p, _cache_layer):
        fn = _block
        if c.remat:
            fn = jax.checkpoint(_block, static_argnums=(3,))
        p = {k: v for k, v in p.items() if k != "kind"}  # a name, no array
        x, experts = fn(carry, p, positions, c)
        return x, {} if experts is None else {"experts": experts}

    def close(x):  # behind a pass of a looped config: the norm, the gate
        x = _rmsnorm(x, params["final_norm"], c.rms_eps)
        return x, _exit_lambda(params, x, c)

    x, aux = _layer_loop(params, c, x, block, unroll=max(1, c.scan_unroll), close=close)
    if c.loop_passes > 1:  # the last pass has normed it
        return x, None, aux["closed"]
    return _rmsnorm(x, params["final_norm"], c.rms_eps), aux.get("experts"), None


def _exit_lambda(params: Params, x, config: LlamaConfig):
    """A pass's final-normed output (..., E) -> the exit gate's ``lambda``
    (...,) float32: sigmoid(x w + b)."""
    gate = params["exit_gate"]
    z = jnp.einsum("...e,e->...", x, gate["w"].astype(config.dtype),
                   preferred_element_type=jnp.float32)
    return jax.nn.sigmoid(z + gate["b"].astype(jnp.float32)[0])


def exit_distribution(lam):
    """(T, ...) ``lambda_t`` of a looped model's T passes -> (T, ...) ``p_t``,
    the probability of leaving after pass t: ``lambda_t prod_{s<t} (1 -
    lambda_s)`` for t < T, and what is left, ``prod_{s<T} (1 - lambda_s)``,
    for the last.  A token's logits come from the first pass whose cumulative
    sum reaches ``early_exit_threshold``: at 1, the last."""
    stayed = jnp.cumprod(1.0 - lam[:-1], axis=0)
    before = jnp.concatenate([jnp.ones_like(lam[:1]), stayed], axis=0)
    return jnp.concatenate([lam[:-1] * before[:-1], before[-1:]], axis=0)


def features(params: Params, tokens, config: LlamaConfig):
    """tokens (B, S) int32 → final-RMSNorm features (B, S, E): of a looped
    config, the last pass's."""
    return _features_and_choices(params, tokens, config)[0]


def expert_choices(params: Params, tokens, config: LlamaConfig):
    """tokens (B, S) int32 → (L, B, S, k) int32: the experts every token
    chose in every layer of the no-cache forward, in order of falling
    router probability.  For comparisons with a reference's routing
    (which pairs swap under rounding); no serving path calls it."""
    return _features_and_choices(params, tokens, config)[1]


def exit_gates(params: Params, tokens, config: LlamaConfig):
    """tokens (B, S) int32 → (passes, B, S) float32: the exit gate's
    ``lambda_t`` behind every pass of a looped config's no-cache forward
    (``exit_distribution`` makes ``p_t`` of them)."""
    if config.loop_passes == 1:
        raise ValueError("a config with one pass has no exit gate")
    return _features_and_choices(params, tokens, config)[2]


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
    """fwd+bwd FLOPs per token: 6N + the attention term.  N counts what
    ``init`` makes (the experts HELD here, the shared expert, the latent
    projections, the indexer and the multi-token-prediction module where
    there is one) less the embedding.  K/V attention: scores and mix over
    the whole context.  Latent attention: scores (head size nope + rope)
    and mix (``v_head_dim``) over the keys a query may see — the
    ``index_topk`` best where there is an indexer, whose ``index_n_heads x
    index_head_dim`` over the whole context is added, else all of them —
    in every layer that keeps a cache (the module's block is one; a
    shortcut-connected layer's two attentions are two).  With
    ``zero_experts`` a token's routed experts are COUNTED, not taken for
    ``experts_per_token``: under an even router the share ``experts_here /
    router_outputs`` of its k choices falls on an expert held here (8 of
    LongCat-Flash's 12 on a real expert where all 512 are held), and N
    holds that many experts a layer in place of all the held ones.  A
    looped config (``loop_passes``) counts a block's weights once a PASS
    (``num_params`` counts them once) and its attention over every (pass,
    layer)'s keys (``kv_layers``)."""
    c = config
    S = seq_len or c.max_seq_len
    n = num_params(c) - c.vocab_size * c.embed_dim * (
        0 if c.tie_embeddings else 1
    )
    if c.loop_passes > 1:
        # a weight is held once and used once a pass: the blocks', the final
        # norm's and the gate's ``loop_passes`` times, the head's once
        head = c.vocab_size * c.embed_dim
        n = (n - head) * c.loop_passes + head
    if c.zero_experts:
        chosen_here = c.experts_per_token * c.experts_here / c.router_outputs
        n -= c.expert_layers * 3 * c.embed_dim * c.expert_dim * (
            c.experts_here - chosen_here)
    if c.latent:
        seen = min(S, c.index_topk) if c.index_topk else S
        indexer = c.index_n_heads * c.index_head_dim * S if c.index_topk else 0
        per_key = c.num_heads * (c.qk_nope_head_dim + c.qk_rope_head_dim + c.v_head_dim)
        attn = 6 * c.cache_layers * (per_key * seen + indexer)
    else:
        attn = 12 * c.kv_layers * c.num_heads * c.head_dim * S  # 2*2*3 * L * HD * S
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
    """Fixed-bucket KV cache: (L, B, max_len, KV x D) per tensor, bf16: a
    token's KV heads side by side in ONE row of whole 128-lane tiles, head
    ``kv`` the static lane slice ``[kv D, (kv + 1) D)``.  (With the heads
    an axis of their own, (..., KV, D), 4 or 8 of them lie on the chip's
    sublanes, and XLA's attention first copied each layer's slab into
    another layout: 2 x 50 MB a layer in the block-diffusion step, PR 36;
    no kernel can fetch a block of keys out of that without the same
    relayout.)  Static shapes — one compiled prefill per prompt length +
    one compiled decode step serve any request up to max_len.  The jitted
    entry points take it donated and hand it back: the one step behind
    them (``_cached_step``) carries it whole through its layer loop,
    writes only the new tokens' K/V in place, and an every-row step's
    attention reads it where it lies, block by block up to each row's
    last visible key (``ops/kv_decode_attention.py``).

    With ``sliding_window`` the cache is a ROLLING buffer (slot =
    position mod max_len), so ``max_len`` can be as small as
    ``window + max_prefill_chunk - 1`` regardless of how long decoding
    runs — the Mistral memory win (8x at 32k context / 4k window).
    Positions older than the window are overwritten in place; the
    attention mask reconstructs each slot's position implicitly.

    A latent config (``kv_lora_rank`` > 0) keeps TWO KINDS OF STATE per
    layer instead of K and V, written together and read differently:
    ``ckv`` (L, B, max_len, kv_lora_rank + qk_rope_head_dim, rounded up
    to whole 128-lane tiles: 576 -> 640, zeros behind), the normed
    latent and the one rotary key all heads share, of which a decode
    step attends to the rows the indexer chose — streaming each row's
    blocks up to ``pos`` through one kernel with the choice as its mask,
    or, in a cache too long for that to pay, gathering the chosen rows
    (``ops/latent_decode_attention.py``); and ``ik`` (L, B, max_len,
    index_head_dim), the indexer's keys, of which it reads every row up
    to ``pos``.  Beside them ``dsa_keys`` (L, 3, 2, 2) int32: keys
    visible / keys selected / keys read (a single-token step: latent rows
    fetched from ``ckv``, blocks streamed or rows gathered; a run: the
    (query, key) pairs its attention computed scores for, a head — whole
    live tiles under the prefill kernel, the causal groups' rectangles in
    XLA's body), summed over every (row, query) of every call, for runs
    (prefills) / single-token steps apart, each as two words (millions,
    rest: ``_add_wide``) because 32 rows at 10k keys are 2 M a step and
    int32 would last 1,000 steps.

    A config with ``layer_types`` keeps TWO KINDS OF STATE in the one
    tree, each for its own layers: ``k`` / ``v`` as above for the FULL
    layers only (4 of Olmo-Hybrid's 16 here, not 16), and for the linear
    layers no per-token rows at all but, a row, ``gdn_state`` (linear
    layers, B, d_k, H d_v) float32, the gated delta rule's (d_k, d_v) matrix
    a head with the heads side by side (``gated_delta.packed``: whole (8,
    128) tiles at Olmo-Hybrid's 96 x 30 x 192, where a (96, 192) matrix a
    head is padded to 256 lanes, a third more bytes to hold and to move),
    and ``gdn_conv`` (linear layers, K - 1, B, H (2 d_k + d_v)), the last
    K - 1 pre-activation inputs of the short convolution — the taps in
    front of the rows, so that the two minor dimensions are (rows,
    channels) and no tile is padded from 3 rows to 16.  Neither grows with
    ``max_len``; a row's length says nothing about them, so a prefill
    WRITES the row's state from zero and nobody "forgets" it by a length.
    ``gdn_counts`` (``GDN_COUNTS``, 2) rides beside them as ``_add_wide``
    pairs.

    A config with window layers (``SLIDING`` in ``layer_types``) keeps a
    ``k`` / ``v`` pair A KIND in the one tree, each for its own layers and in
    its own shape: ``k`` (full layers, B, max_len, KV x D) and ``v`` (.., KV x
    D_v) as above for the FULL layers (2 of MiMo-V2-Flash's 7 here: 4 x 192 |
    4 x 128), and for the window layers ``swa_k`` (window layers, B, window,
    KV_w x D) and ``swa_v`` (.., KV_w x D_v): ``window`` ROLLING slots a row,
    slot = position mod window, whatever ``max_len`` is (5 layers x 128 slots
    x 8 x 192 | 8 x 128: 0.21 GB at 64 rows where ``max_len`` 13,312 would
    take 21.8).  A prefill leaves a row's last ``window`` keys in their
    slots, whatever the slot held; a step overwrites the one slot whose key
    has just left the window.  Every row of either is whole 128-lane tiles.
    Beside them ``attn_keys`` (``ATTENTION_KINDS``, 2, 2, 2) int32: per kind,
    over its layers, keys VISIBLE / keys READ, for runs (prefills: (query,
    key) pairs inside the mask / pairs scored) / one-token steps (keys a
    row's query could see / keys fetched for it) apart, each an
    ``_add_wide`` pair (``_kind_amounts``).

    An expert config adds int32 running totals that ride the donated
    cache like K and V, so no step pays a device-to-host copy for them
    (``serve/llm.py`` reads them in ``stats()``): ``moe_expert_tokens``
    (expert layers, experts held) rows each expert of each layer
    computed, ``moe_experts_touched`` (expert layers,) experts with at
    least one row, summed over the calls, and ``moe_layer_steps``
    calls.  They count what the kernel did: every row of a decode step
    routes, also the rows the engine treats as inactive.  With
    ``zero_experts`` also ``moe_zero_choices`` (expert layers,): (token,
    choice) pairs that fell on identity experts.  Where not every row has
    a group (``experts_held`` or ``zero_experts``) ``moe_layer_steps`` is
    (expert layers, 2): the calls, and beside them the row tiles
    (``grouped_matmul.ROW_TILE`` rows each) ``_ffn`` gathered and handed
    the kernel, blocks x R (a column and not an entry of its own: three of
    the benchmark's tests hold the set of a cache's entries).

    A shortcut-connected config (``block_form``) keeps a latent config's
    one kind of state, TWO cache layers a layer: its attentions' ``ckv``
    and ``mla_keys`` rows 2l and 2l + 1, and one row a layer of the
    experts' counters.

    A looped config (``loop_passes`` T) keeps ``k`` / ``v`` of T x
    ``num_layers`` layers, layer l's of pass t at ``t num_layers + l``, and
    two running totals: ``loop_passes`` (2,) int32, the passes run summed
    over every (row, call) — T a row-step while every token runs every pass,
    the number an early exit would move — and beside it the (row, call)s
    themselves; ``loop_exit_mass`` () float32, the sum over (row, call) of
    the exit mass BEFORE the last pass, ``1 - p_T`` of each row's last new
    token (``exit_distribution``): what a threshold under 1 could save."""
    c = config
    if c.latent and not c.index_topk:
        # no indexer: one kind of state, every visible key attended to;
        # ``mla_keys`` (layers, 2, 2): keys visible to a row (to its last
        # query: the others see prefixes) / latent rows read for it, over
        # every row of every step, as ``_add_wide`` pairs.
        # The multi-token-prediction module's block is one more layer
        cache = {
            "ckv": jnp.zeros(
                (c.cache_layers, batch_size, max_len, _latent_row(c)), c.dtype
            ),
            "mla_keys": jnp.zeros((c.cache_layers, 2, 2), jnp.int32),
        }
    elif c.latent:
        lead = (c.num_layers, batch_size, max_len)
        cache = {
            "ckv": jnp.zeros((*lead, _latent_row(c)), c.dtype),
            "ik": jnp.zeros((*lead, c.index_head_dim), c.dtype),
            "dsa_keys": jnp.zeros((c.num_layers, 3, 2, 2), jnp.int32),
        }
    else:
        lead = (c.kv_layers, batch_size, max_len)
        cache = {
            "k": jnp.zeros((*lead, c.num_kv_heads * c.head_dim), c.dtype),
            "v": jnp.zeros((*lead, c.num_kv_heads * c.value_dim), c.dtype),
        }
    if c.sliding is not None:
        lead = (c.sliding_layers, batch_size, c.sliding.window)
        cache.update({
            "swa_k": jnp.zeros((*lead, c.sliding.num_kv_heads * c.head_dim), c.dtype),
            "swa_v": jnp.zeros((*lead, c.sliding.num_kv_heads * c.value_dim), c.dtype),
            "attn_keys": jnp.zeros((len(ATTENTION_KINDS), 2, 2, 2), jnp.int32),
        })
    if LINEAR in c.layer_types:
        H, Dk, Dv = c.linear_num_heads, c.linear_key_head_dim, c.linear_value_head_dim
        cache.update({
            "gdn_state": jnp.zeros((c.linear_layers, batch_size, Dk, H * Dv), jnp.float32),
            "gdn_conv": jnp.zeros(
                (c.linear_layers, c.linear_conv_kernel - 1, batch_size, H * (2 * Dk + Dv)),
                c.dtype,
            ),
            "gdn_counts": jnp.zeros((len(GDN_COUNTS), 2), jnp.int32),
        })
    if c.num_experts:
        layers = c.expert_layers
        cache["moe_expert_tokens"] = jnp.zeros(
            (layers, c.experts_here), jnp.int32
        )
        cache["moe_experts_touched"] = jnp.zeros((layers,), jnp.int32)
        some = c.experts_held or c.zero_experts
        cache["moe_layer_steps"] = jnp.zeros((layers, 2) if some else (layers,), jnp.int32)
        if c.zero_experts:
            cache["moe_zero_choices"] = jnp.zeros((layers,), jnp.int32)
    if c.loop_passes > 1:
        cache["loop_passes"] = jnp.zeros((2,), jnp.int32)
        cache["loop_exit_mass"] = jnp.zeros((), jnp.float32)
    return cache


def _latent_row(config: LlamaConfig) -> int:
    """Width of a latent cache row: the latent and the rotary key, in
    whole 128-lane tiles.  At a width that is no multiple of 128 the
    TPU's default layout puts the POSITIONS minor-most, and every step
    copies the whole cache into a row-major layout and back (2 x 2.3 GB
    at GLM-5's 576; compile-only, PR 30); row-major it would be padded
    to the same 640 anyway."""
    return -(-(config.kv_lora_rank + config.qk_rope_head_dim) // 128) * 128


#: the cache's entries that hold tokens' state (the rest are counters)
_STATE = ("k", "v", "swa_k", "swa_v", "ckv", "ik", "gdn_state", "gdn_conv")
#: what a latent attention hands the layer loop, an entry a cache layer
_PER_CACHE_LAYER = ("mla_keys", "ckv_rows", "selected")
#: ``cache["gdn_counts"]``'s rows, each an ``_add_wide`` pair, all over
#: (row, linear layer): one-token updates of decode steps (every row
#: steps, free slots too); prompt tokens the prefills' chunked rule ran
#: and the identity positions that filled their last chunks; bytes of
#: recurrent state the decode steps read and wrote (once each)
GDN_COUNTS = ("gdn_rows_stepped", "gdn_tokens_scanned", "gdn_tokens_padded",
              "gdn_state_bytes_step")
_WIDE = 1 << 20


def _add_wide(total, amount):
    """``total`` (..., 2) int32 = (amount // 2**20, amount % 2**20) of a
    count too large for one int32, plus ``amount`` (...,) int32 >= 0."""
    low = total[..., 1] + amount % _WIDE
    high = total[..., 0] + amount // _WIDE + low // _WIDE
    return jnp.stack([high, low % _WIDE], axis=-1)


def _gdn_amounts(config: LlamaConfig, rows: int, run: int, step: bool):
    """What one call adds to ``gdn_counts``, from its static shapes alone
    (int64, split into words before it meets an int32)."""
    c = config
    L = c.linear_layers
    state = c.linear_num_heads * c.linear_key_head_dim * c.linear_value_head_dim * 4
    if step:
        return np.asarray([rows * L, 0, 0, 2 * rows * L * state], np.int64)
    return np.asarray(
        [0, rows * run * L, rows * (-run % c.linear_chunk) * L, 0], np.int64
    )


def wide_total(total) -> int:
    """A ``_add_wide`` total, summed over its leading axes, as an int
    (host side: numpy in, Python int out)."""
    t = np.asarray(total).astype(np.int64).reshape(-1, 2)
    return int(t[:, 0].sum()) * _WIDE + int(t[:, 1].sum())


def _with_counts(cache: Params, state: Params, aux: Params, step: bool,
                 first: int = 0) -> Params:
    """The cache after one call: the new state and the running totals
    plus this call's ``aux`` (the layer loop's stacked outputs): an
    expert config's (expert layers, experts held) rows per expert (and
    (expert layers,) choices of identity experts, row tiles gathered), a
    latent config's (L, 3) keys visible, selected and read (a single-token
    step: latent rows fetched; a run: (query, key) pairs its attention
    computed scores for); without an indexer (L, 2) keys visible and rows
    read, of steps only; a looped config's ``exit_lambda`` (passes, rows)
    into ``loop_passes`` and ``loop_exit_mass``.  ``first``: the cache layer
    ``aux`` starts at, where it covers a part of the layers (the model
    without its multi-token-prediction module, or that module alone)."""
    out = dict(cache, **state)
    if "expert_rows" in aux:
        rows = aux["expert_rows"]
        # the expert layers ``aux`` covers: all, or a part — the model's,
        # or (behind them) the multi-token-prediction module's
        at = cache["moe_layer_steps"].shape[0] - rows.shape[0] if first else 0
        part = slice(at, at + rows.shape[0])
        calls = 1
        if "row_tiles" in aux:  # (calls, row tiles gathered) a layer
            calls = jnp.stack([jnp.ones_like(aux["row_tiles"]), aux["row_tiles"]], -1)
        if rows.shape[0] == cache["moe_layer_steps"].shape[0]:
            out["moe_expert_tokens"] = cache["moe_expert_tokens"] + rows
            out["moe_experts_touched"] = cache["moe_experts_touched"] + (
                rows > 0
            ).sum(-1, dtype=jnp.int32)
            out["moe_layer_steps"] = cache["moe_layer_steps"] + calls
        else:
            touched = (rows > 0).sum(-1, dtype=jnp.int32)
            out["moe_expert_tokens"] = cache["moe_expert_tokens"].at[part].add(rows)
            out["moe_experts_touched"] = cache["moe_experts_touched"].at[part].add(touched)
            out["moe_layer_steps"] = cache["moe_layer_steps"].at[part].add(calls)
        if "zero_choices" in aux:
            out["moe_zero_choices"] = cache["moe_zero_choices"].at[part].add(
                aux["zero_choices"])
    if "dsa_keys" in aux:
        kind = int(step)  # runs at [:, :, 0], single-token steps at [:, :, 1]
        out["dsa_keys"] = cache["dsa_keys"].at[:, :, kind].set(
            _add_wide(cache["dsa_keys"][:, :, kind], aux["dsa_keys"])
        )
    if "mla_keys" in aux:
        part = slice(first, first + aux["mla_keys"].shape[0])
        out["mla_keys"] = cache["mla_keys"].at[part].set(
            _add_wide(cache["mla_keys"][part], aux["mla_keys"])
        )
    if "exit_lambda" in aux:
        lam = aux["exit_lambda"]  # (passes, rows): each row's last new token
        passes, rows = lam.shape
        out["loop_passes"] = cache["loop_passes"] + jnp.asarray(
            [passes * rows, rows], jnp.int32)
        out["loop_exit_mass"] = cache["loop_exit_mass"] + (
            1.0 - exit_distribution(lam)[-1]).sum()
    return out


def rolling_cache_len(config: LlamaConfig, prefill_chunk: int) -> int:
    """Smallest safe rolling-cache length for unbounded windowed
    decoding: ``window + prefill_chunk - 1`` slots guarantees a wrapped
    write can only land on a position already outside every live
    query's window (the Mistral memory bound — independent of how long
    decoding runs)."""
    assert config.sliding_window > 0, "rolling caches need sliding_window"
    return config.sliding_window + max(1, prefill_chunk) - 1


def _cache_mask(positions, T: int, window: int, block: int = 1):
    """(R, Sq, T), True where the query at ``positions[r, s]`` may see
    cache slot t.  Full causal: slot t holds position t.  With ``block``
    B > 1 a query sees up to the END of its own block of B positions, ``t
    <= (q // B) * B + B - 1``: the tokens of a block see each other and
    every block before theirs (block diffusion).  With a window
    the cache is a rolling buffer: slot t as seen by query position q
    holds position q - ((q - t) mod T) — the newest position <= q
    congruent to t — valid iff non-negative and inside the window (slot
    correctness needs T >= window + Sq - 1: ``rolling_cache_len``)."""
    q_pos = positions[:, :, None]
    t_idx = jnp.arange(T)
    if block > 1:
        if window:
            raise NotImplementedError("mask_block with a sliding window")
        return t_idx <= (q_pos // block) * block + (block - 1)
    if not window:
        return t_idx <= q_pos
    t_pos = q_pos - ((q_pos - t_idx) % T)
    return (t_pos >= 0) & (t_pos > q_pos - window)


def _last_visible(positions, block: int = 1):
    """(R, Sq): the last cache slot the query at ``positions[r, s]`` may see
    in a full-causal cache — its own, or under ``block`` B > 1 the END of
    its block of B positions (``_cache_mask``'s rule as a position)."""
    if block > 1:
        return (positions // block) * block + (block - 1)
    return positions


def _grouped_attention(q, k_cache, v_cache, mask, config: LlamaConfig,
                       kv_heads: int = 0, sink=None):
    """The ONE cached-attention body in plain XLA.  q: (B, Sq, H, D)
    attends over the rows' slabs of the cache, (B, T, KV x D) keys and (B,
    T, KV x Dv) values: the H = KV
    * G query heads fold to (KV, G) and contract against their KV head
    directly, so K/V are never expanded (no ``jnp.repeat``) and every
    cached byte is read once, in the cache's dtype.  MHA is G = 1, the
    same code.  mask: (B, Sq, T), True where query q may see slot t.
    Scores and softmax in f32, probabilities and values in
    ``config.dtype``.  ``kv_heads``: the layer's kind's, where it is not
    the model's.  ``sink`` (H,) float32: a learned number a query head that
    joins the softmax's denominator and carries no value.  (An every-row
    step over whole blocks runs the same mathematics in flash order:
    ``ops/kv_decode_attention.py``.)"""
    c = config
    B, Sq, H, D = q.shape
    KV = kv_heads or c.num_kv_heads
    k_cache = k_cache.reshape(*k_cache.shape[:2], KV, D)
    v_cache = v_cache.reshape(*v_cache.shape[:2], KV, -1)
    q = q.reshape(B, Sq, KV, H // KV, D)
    scores = jnp.einsum(
        "bqkgd,btkd->bkgqt", q, k_cache, preferred_element_type=jnp.float32
    ) / math.sqrt(D)
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    if sink is None:
        probs = jax.nn.softmax(scores, axis=-1).astype(c.dtype)
    else:
        sink = sink.reshape(1, KV, H // KV, 1, 1)
        top = jnp.maximum(scores.max(-1, keepdims=True), sink)
        probs = jnp.exp(scores - top)
        probs = (probs / (probs.sum(-1, keepdims=True) + jnp.exp(sink - top))).astype(c.dtype)
    out = jnp.einsum("bkgqt,btkd->bqkgd", probs, v_cache)
    return out.reshape(B, Sq, H, -1)


#: tokens a row of an every-row step may bring and still be written into
#: the carried cache in place (``_write_and_read``)
_STEP_RUN = 8


def _write_and_read(cache, new, layer, slot, positions, config: LlamaConfig,
                    slab: bool = True):
    """The one place K/V enter a cache.  Writes ``new`` (R, Sq, KV, D)
    into the WHOLE carried ``cache`` (L, B, T, KV x D) at ``layer``, rows
    ``slot`` (None: all B rows) and ``positions``; returns the cache and
    the (R, T, KV x D) slab of those rows, new tokens included, for
    attention in plain XLA — or, with ``slab`` False, no slab: the
    attention kernel reads the carried cache itself.  Which of the two
    comes first is chosen from static shapes, because each order copies
    where the other is in place:

    - a token, or a few (at most ``_STEP_RUN``: a block-diffusion step's
      block), for every row: write them into the carried cache, THEN
      read.  ``ops/kv_decode_attention.py`` fetches the live blocks of
      each row out of the carried cache and nothing is made in front of
      it; where its static rule keeps XLA's body (a cache of no whole
      blocks, a rolling one), the layer's slab is indexed out of the
      cache and read where it lies.
    - a longer run of tokens (a prefill, a chunk): take the addressed rows'
      slab FIRST, put the run into that copy for attention, and write
      the run into the cache separately.  Written first and then
      sliced, XLA copies the whole K and V cache every call (2 GiB at
      the serving widths; tests/test_llama_decode_compile.py)."""
    L, B, T, row = cache.shape
    R, Sq = positions.shape
    new = new.reshape(R, Sq, row)
    rolling = config.sliding_window > 0

    in_place = slot is None and Sq <= _STEP_RUN

    def write(buf, *lead):
        # buf[*lead[r], slot of positions[r, s]] = new[r, s]
        if rolling or in_place:
            # one row write per token: a step's tokens lie in R
            # different rows; in a rolling buffer position t lives in
            # slot t mod T, so a run may wrap
            slots = positions % T if rolling else positions
            return buf.at[(*(i[:, None] for i in lead), slots)].set(new)
        # full causal: a run is contiguous, ONE block write per row (R
        # is 1 for a prefill).  As a scatter of R windows XLA carries
        # the cache through the layer loop in another layout and copies
        # it whole twice a call; token by token it is Sq row writes.
        for r in range(R):
            at = (*(i[r] for i in lead), positions[r, 0], 0)
            buf = lax.dynamic_update_slice(buf, new[r][(None,) * len(lead)], at)
        return buf

    rows, row0 = jnp.arange(R), 0 if slot is None else slot
    lead = (jnp.full((R,), layer), rows + row0)
    if in_place:
        cache = write(cache, *lead)
        if not slab:
            return cache, None
        return cache, lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)
    if not slab:  # a run that attends to its own tokens alone
        return write(cache, *lead), None
    taken = lax.dynamic_slice(cache, (layer, row0, 0, 0), (1, R, T, row))[0]
    return write(cache, *lead), write(taken, rows)


def _kv_attention(h, p, state, slot, positions, config: LlamaConfig):
    """Attention over a K/V cache: projections, the new tokens' K/V
    written, then attention over the rows' keys — for an every-row step
    over whole blocks the kernel of ``ops/kv_decode_attention.py`` on the
    carried cache (which body: its ``implementation``, from static
    shapes), else grouped attention over the rows' slabs.  Returns (the
    heads' outputs (R, Sq, H, D), state, no counters)."""
    c = config
    if c.sliding is not None:
        return _kind_attention(h, p, state, slot, positions, c)
    q, kk, vv = _qkv(h, p, positions, c)
    T = state["k"].shape[2]
    # named where it is the block attention, so that a trace finds its
    # operations: the block's rows written, the keys read, scores and mix
    # (and where the model is looped, ``loop_attn``: 192 calls a step at Ouro's)
    scope = contextlib.nullcontext()
    if c.mask_block > 1 or c.loop_passes > 1:
        scope = jax.named_scope("block_attn" if c.mask_block > 1 else "loop_attn")
    if (c.layer_types or c.loop_passes > 1) and slot is not None:
        # beside linear layers a run starts at position 0 (they can do
        # nothing else): its own tokens are all the keys there are, and
        # the slab's other max_len - Sq rows need not be read or scored.
        # A looped config's run into a slot starts at 0 as well (``slot``
        # given: ``prefill_into_slot``) and takes this path: 192 slabs a
        # prompt would be read for nothing at Ouro-2.6B's cache layers
        write = partial(_write_and_read, layer=p["cache_layer"], slot=slot,
                        positions=positions, config=c, slab=False)
        with scope:
            cache_k, _ = write(state["k"], kk.astype(c.dtype))
            cache_v, _ = write(state["v"], vv.astype(c.dtype))
            if c.q_per_kv > 1 and q.shape[0] == 1:
                # grouped queries: the flash kernel that repeats no KV head
                attn = kv_prefill_attention.attention(q[0], kk[0], vv[0])[None]
            else:
                attn = _run_attention(q, kk, vv, c)
        return attn.astype(c.dtype), dict(state, k=cache_k, v=cache_v), {}
    streamed = (
        slot is None and positions.shape[1] <= _STEP_RUN
        and kv_decode_attention.implementation(T, c.head_dim, c.sliding_window)
        == "streamed"
    )
    with scope:
        cache_k, slab_k = _write_and_read(
            state["k"], kk.astype(c.dtype), p["cache_layer"], slot, positions, c,
            slab=not streamed,
        )
        cache_v, slab_v = _write_and_read(
            state["v"], vv.astype(c.dtype), p["cache_layer"], slot, positions, c,
            slab=not streamed,
        )
        if streamed:
            attn = kv_decode_attention.kv_decode_attention(
                q, cache_k, cache_v, p["cache_layer"],
                _last_visible(positions, c.mask_block),
            )
        else:
            mask = _cache_mask(positions, T, c.sliding_window, c.mask_block)
            attn = _grouped_attention(q, slab_k, slab_v, mask, c)
    return attn, dict(state, k=cache_k, v=cache_v), {}


def _write_run(cache, new, layer, slot, rolling: bool):
    """One row's run from position 0, ``new`` (S, width), into the carried
    ``cache`` (L, B, T, width) at ``layer``, row ``slot``: positions 0..S-1
    as they are, or (``rolling``: slot = position mod T) the run's last
    ``min(S, T)`` positions, each in its slot — one block write either way
    (S is static, so the rotation is two slices)."""
    S, T = new.shape[0], cache.shape[2]
    if rolling and S > T:
        new = jnp.roll(new[S - T:], S % T, axis=0)
    return lax.dynamic_update_slice(cache, new[None, None], (layer, slot, 0, 0))


def _kind_step_streams(config: LlamaConfig, kind: AttentionKind, cache_len: int) -> bool:
    """Does a one-token step of a layer of ``kind`` over ``cache_len`` slots
    run the kernel of ``ops/kv_decode_attention.py`` (its rule, from static
    shapes), or XLA's body over the slab?"""
    return kv_decode_attention.implementation(
        cache_len, config.head_dim, kv_heads=kind.num_kv_heads,
        v_head_dim=config.value_dim) == "streamed"


def _kind_attention(h, p, state, slot, positions, config: LlamaConfig):
    """``_kv_attention`` of a layer of a model with attention KINDS
    (``layer_types`` with window layers: MiMo-V2-Flash's).  The layer's
    kind, ``p["kind"]``, says which K/V pair of the cache is its own
    (``k`` / ``v``: every position; ``swa_k`` / ``swa_v``: ``window``
    rolling slots), how many KV heads it has, its rotary base, and whether
    a query sees every earlier key or its own and the ``window - 1`` before
    it, with a learned sink a head in the softmax's denominator.

    One row's run from position 0 (a prefill): the run's K/V are written —
    a full layer's all, a window layer's last ``window`` positions into
    their slots — and attention is over the run's OWN keys
    (``ops/kv_prefill_attention.py``: a flash kernel with grouped queries
    over the live tiles — those inside the band, for a window — or, toy
    heads, XLA's dense body).  One token for every row (a decode step): the
    token's K/V written where they belong, then attention over the carried
    cache where it lies (``ops/kv_decode_attention.py``: a full layer's row
    up to its own last key, a window layer's one block of slots), or, a
    cache of no whole blocks, ``_grouped_attention`` over the layer's
    slab.  Returns (the heads' outputs (R, Sq, H, value_dim), state, no
    counters: what a call saw and read is known from its positions,
    ``_kind_amounts``)."""
    c = config
    sliding = p["kind"] == SLIDING
    kind = c.attention_kind(p["kind"])
    names = ("swa_k", "swa_v") if sliding else ("k", "v")
    R, Sq = positions.shape
    layer = p["cache_layer"]
    q, kk, vv = _qkv(h, p, positions, c, kind)
    sink = p["sink"].astype(jnp.float32) if kind.sink else None
    cache_k, cache_v = (state[n] for n in names)
    T = cache_k.shape[2]
    with jax.named_scope("swa_attn" if sliding else "full_attn"):
        new_k = kk.reshape(R, Sq, -1).astype(c.dtype)
        new_v = vv.reshape(R, Sq, -1).astype(c.dtype)
        if slot is not None and R == 1:
            cache_k = _write_run(cache_k, new_k[0], layer, slot, sliding)
            cache_v = _write_run(cache_v, new_v[0], layer, slot, sliding)
            attn = kv_prefill_attention.attention(
                q[0], kk[0], vv[0], window=kind.window, sink=sink)[None]
        elif slot is None and Sq == 1:
            rows = jnp.arange(R)[:, None]
            at = positions % T if sliding else positions
            cache_k = cache_k.at[layer, rows, at].set(new_k)
            cache_v = cache_v.at[layer, rows, at].set(new_v)
            # in slots: a window layer's T slots are its window, all of them
            # live once the row has T keys
            visible = jnp.minimum(positions, T - 1)
            if _kind_step_streams(c, kind, T):
                attn = kv_decode_attention.kv_decode_attention(
                    q, cache_k, cache_v, layer, visible, sink=sink)
            else:
                slab_k, slab_v = (lax.dynamic_index_in_dim(t, layer, 0, keepdims=False)
                                  for t in (cache_k, cache_v))
                mask = jnp.arange(T) <= visible[:, :, None]
                attn = _grouped_attention(
                    q, slab_k, slab_v, mask, c, kind.num_kv_heads, sink)
        else:
            raise NotImplementedError(
                "a model with window layers prefills whole prompts, one row at a "
                "time, and steps one token a row (prefill_into_slot / "
                "decode_step_rowwise): a run that continues a row, or a step of "
                "several tokens a row, would need the keys a rolling cache has "
                "already overwritten"
            )
    return attn.astype(c.dtype), dict(state, **dict(zip(names, (cache_k, cache_v)))), {}


#: ``cache["attn_keys"]``'s first axis: the attention kinds that count
ATTENTION_KINDS = (FULL, SLIDING)


def _kind_amounts(config: LlamaConfig, positions, step: bool, cache_len: int):
    """What one call adds to ``attn_keys``: (kinds, 2) = per kind
    (``ATTENTION_KINDS``), over its layers and the call's rows, a head: keys
    VISIBLE and keys READ.  A step (one token a row, ``positions`` (R, 1)):
    keys a row's query could see, and keys fetched for it (whole blocks up
    to its last visible key where the kernel streams them, the whole slab
    otherwise; a window layer's slots are one block).  A run of S tokens
    from position 0: (query, key) pairs inside the mask, and pairs its
    attention computed scores for (``kv_prefill_attention.pairs_computed``).
    A step's are traced int32 (64 rows x 13k keys x 9 layers are 8 M), a
    run's numpy int64 from its static shape."""
    c = config
    layers = {FULL: c.kv_layers, SLIDING: c.sliding_layers}
    out = []
    for name in ATTENTION_KINDS:
        kind = c.attention_kind(name)
        T = kind.window if kind.window else cache_len
        if step:
            pos = positions[:, 0]
            seen = jnp.minimum(pos + 1, T).sum(dtype=jnp.int32)
            if _kind_step_streams(c, kind, T):
                block = kv_decode_attention.BLOCK_KEYS
                read = ((jnp.minimum(pos, T - 1) // block + 1) * block).sum(dtype=jnp.int32)
            else:
                read = jnp.int32(pos.shape[0] * T)
            out.append(jnp.stack([seen, read]) * layers[name])
        else:
            S = positions.shape[1]
            w = min(kind.window or S, S)
            inside = S * w - w * (w - 1) // 2
            computed = kv_prefill_attention.pairs_computed(
                S, c.head_dim, c.value_dim, kind.window)
            out.append(np.asarray([inside, computed], np.int64) * layers[name])
    return jnp.stack(out) if step else np.stack(out)


def _run_attention(q, kk, vv, config: LlamaConfig):
    """Causal attention of a run from position 0 over its own keys.  q:
    (R, S, H, D); kk, vv: (R, S, KV, D).  Heads of whole 128-lane tiles go
    through the flash kernel (``ops/flash_attention.py``; the run padded
    up to its 128-token blocks: a padded key lies behind every real
    query), so no (S, S) score reaches memory; toy heads through XLA's
    dense body."""
    c = config
    if c.q_per_kv > 1:
        kk = jnp.repeat(kk, c.q_per_kv, axis=2)
        vv = jnp.repeat(vv, c.q_per_kv, axis=2)
    if c.head_dim % 128:
        from ray_tpu.ops.attention import dense_attention

        return dense_attention(q, kk, vv)
    from ray_tpu.ops.flash_attention import flash_attention

    S = q.shape[1]
    pad = ((0, 0), (0, -S % 128), (0, 0), (0, 0))
    return flash_attention(*(jnp.pad(t, pad) for t in (q, kk, vv)))[:, :S]


def _rope_pairs(x, positions, theta):
    """Rotary embedding over INTERLEAVED pairs of the last dim: (x[2i],
    x[2i+1]) turns by ``position * theta**(-2i/D)`` (DeepSeek's and
    GLM-5's ``rope_interleave``; ``_rope`` pairs x[i] with x[i + D/2]).
    x: (B, S, H, D)."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(
        -math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
    )
    angles = positions[:, :, None].astype(jnp.float32) * freqs[None, None, :]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], half, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    rotated = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return rotated.reshape(x.shape).astype(x.dtype)


def _layernorm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    x32 = x32 - x32.mean(-1, keepdims=True)
    y = x32 * lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (
        y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    ).astype(x.dtype)


#: eps of the indexer's key LayerNorm (torch's default; ``assumed``)
_INDEX_NORM_EPS = 1e-6
#: queries per block of a latent prefill's attention, at most, and the
#: (heads, block, keys) float32 scores that may be live at once: 2**25
#: = 128 MiB, which is 128 queries at 64 heads x 4,096 keys and 64 at
#: 8,192.  At 256 MiB the chip's compiler gives the softmax fusion a
#: schedule it prices at 2**63 cycles, and an 8,192-token prefill took
#: 10.1 s where 4,096 took 0.44 (my chip run, PR 30)
_QUERY_BLOCK = 128
_SCORE_ELEMENTS = 1 << 25
#: runs of queries a prefill of a whole multiple is cut into, each
#: attending only to the keys up to its own end
_CAUSAL_GROUPS = 4


def _index_scores(qi, wi, ki):
    """The indexer's score of every (query, key) pair: ``sum_j w_j *
    relu(q_j . k)``.  qi: (..., Q, J, Di), wi: (..., Q, J), ki: (..., T,
    Di) -> (..., Q, T) float32."""
    dots = jnp.einsum(
        "...qjd,...td->...qjt", qi, ki, preferred_element_type=jnp.float32
    )
    return jnp.einsum("...qjt,...qj->...qt", jax.nn.relu(dots), wi)


def _select_mask(scores, k: int):
    """(Q, T) bool: for each query the ``k`` keys of largest score,
    EXACTLY k of them where more than k are visible (ties at the k-th
    value go to the lower index, as ``lax.top_k`` orders them), every
    visible key where at most k are.  ``scores`` (Q, T) float32 holds
    -inf at the keys a query may not see.  ``ops/topk_mask.py`` says by
    the block's shape how the k-th value and the tie are found
    (``implementation``): ``counted`` — whole (8, 128) tiles: one Pallas
    kernel counts its way to both, no score sorted or moved; ``sorted`` —
    everything else: ``lax.top_k`` and a running count.  The same set
    either way."""
    return topk_mask.topk_mask(scores, k)


def _latent_attention(h, p, state, slot, positions, config: LlamaConfig,
                      collect: bool = False):
    """GLM-5's attention (DeepSeek-V3.2's): multi-head latent attention
    over the keys a learned indexer selects.  h: (R, Sq, E) normed.

    Per token: ``c_q = RMSNorm(h W_qa)``; ``q = c_q W_qb`` -> H heads of
    [nope | rope], the rope part rotated; ``[c_kv | k_rope] = h W_kva``,
    ``c_kv`` RMS-normed, ``k_rope`` rotated, ONE for all heads (with
    ``mla_scale_q_lora`` / ``mla_scale_kv_lora`` the normed ``c_q`` /
    ``c_kv`` times ``sqrt(E / rank)``: LongCat-Flash's).  The
    cache row (``ckv``) is ``[c_kv | k_rope]``.  Head h's key is ``[W_kb,h
    c_kv | k_rope]`` and its value ``W_vb,h c_kv``; scores over head
    size nope + rope.  Indexer: ``q^I = c_q W_iq`` (J heads of Di),
    ``k^I = LayerNorm(h W_ik)`` (cached in ``ik``), the first
    ``qk_rope_head_dim`` of both rotated, ``w = h W_iw``; ``I(t, s) =
    sum_j w_j relu(q^I_j . k^I_s)``; a query attends to the
    ``index_topk`` visible keys of largest I, exactly (all of them
    while there are fewer).

    One token for every row (decode): the rows' new state is written,
    the indexer reads the layer's whole ``ik`` slab, and attention runs
    in the absorbed form over the ``index_topk`` rows of ``ckv`` it
    chose — ``W_kb`` carried into the query, ``W_vb`` applied to the
    weighted sum of latents: keys and values are never expanded over the
    cache.  ``ops/latent_decode_attention.py`` says by the cache's length
    how the chosen rows are reached: ``streamed`` — the selection is a
    mask (``_select_mask``: the k-th score and the tie rule found by
    counting in the kernel of ``ops/topk_mask.py``, nothing sorted) and
    one Pallas kernel reads each row's blocks of ``ckv`` up to ``pos``
    where they lie, once; ``gathered`` (a cache past the crossover, or no
    whole number of blocks) — this body alone needs INDICES and sorts for
    them: ``lax.top_k``'s, those rows gathered, two einsums.  The same
    set either way.

    A run (prefill; one row, from position 0: the run's own tokens are
    all the keys there are): K and V are expanded from the run's latents
    once, and the indexer's selection is made block by block over the
    queries (``_QUERY_BLOCK``; four causal groups, each given only the keys
    up to its own end; a block's mask by the same ``_select_mask``, a group
    of no more than ``index_topk`` keys takes what is visible).
    ``ops/latent_prefill_attention.py`` says by the
    run's shape which body attends (``implementation``): ``flash`` — a run
    of whole tiles, four or more, with value heads of whole 128-lane tiles:
    the blocks' selections are laid into ONE (Sq, Sq) int8 mask and one kernel
    (online softmax over the live causal tiles) reads it, so no score ever
    reaches HBM; a key head that is no whole lane tiles (LongCat's and
    JoyAI's nope 128 | rope 64 = 192) is handed over with zeros behind it
    up to one, written where q and k are put together; ``blocked`` —
    everything else (tiny, short and ragged runs, a value head of half a
    lane tile): XLA's body, each block of queries' float32 scores
    (heads x block x keys) written, masked, softmaxed and multiplied out
    inside the same loop as its selection.  The same selection and the
    same mathematics either way; ``collect`` takes the same body.

    Returns (the heads' outputs (R, Sq, H, v_head_dim), state,
    {"dsa_keys": (3,) int32 keys visible and selected over all queries,
    and keys read: latent rows a step fetched from ``ckv``, (query, key)
    pairs a run computed scores for}
    — with ``collect`` also ``"selected"``: (R, Sq, T') bool, the keys
    each query attended to (T' = the cache's length for a step, Sq for
    a run))."""
    c = config
    R, Sq, _ = h.shape
    C, Dn, Dr = c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim
    K = c.index_topk
    layer = p["cache_layer"]
    scale = 1.0 / math.sqrt(Dn + Dr)
    dt = c.dtype
    with jax.named_scope("mla_proj"):
        # a LoRA scale rides its norm's scale: applied in float32, before
        # the latent is rounded to the compute type — and for the
        # key-value latent before it is cached, so the stored row carries
        # it (the rotary key beside it does not)
        q_a_norm, kv_a_norm = p["q_a_norm"], p["kv_a_norm"]
        if c.mla_scale_q_lora:
            q_a_norm = q_a_norm.astype(jnp.float32) * math.sqrt(c.embed_dim / c.q_lora_rank)
        if c.mla_scale_kv_lora:
            kv_a_norm = kv_a_norm.astype(jnp.float32) * math.sqrt(c.embed_dim / C)
        c_q = _rmsnorm(
            jnp.einsum("rse,eq->rsq", h, p["w_qa"].astype(dt)),
            q_a_norm, c.rms_eps,
        )
        q = jnp.einsum("rsq,qhd->rshd", c_q, p["w_qb"].astype(dt))
        q_nope = q[..., :Dn]
        q_rope = _rope_pairs(q[..., Dn:], positions, c.rope_theta)
        kv = jnp.einsum("rse,ec->rsc", h, p["w_kva"].astype(dt))
        c_kv = _rmsnorm(kv[..., :C], kv_a_norm, c.rms_eps)
        k_rope = _rope_pairs(kv[:, :, None, C:], positions, c.rope_theta)[:, :, 0]
        fill = jnp.zeros((R, Sq, _latent_row(c) - C - Dr), dt)
        new_ckv = jnp.concatenate([c_kv.astype(dt), k_rope.astype(dt), fill], axis=-1)
    aux = {}

    if slot is None and not K:  # ---- Sq tokens for every row, no indexer
        ckv = state["ckv"]
        T = ckv.shape[2]
        rows = jnp.arange(R)
        with jax.named_scope("mla_attn"):
            ckv = ckv.at[layer, rows[:, None], positions].set(new_ckv)
            q_lat = jnp.einsum("rshn,chn->rshc", q_nope, p["w_kb"].astype(dt))
            qq = jnp.concatenate(
                [q_lat, q_rope, jnp.zeros((R, Sq, c.num_heads, fill.shape[-1]), dt)],
                axis=-1,
            )                                                   # (R, Sq, H, row)
            if latent_decode_attention.implementation(T) == "streamed":
                attend = latent_decode_attention.visible_decode_attention
                read = latent_decode_attention.keys_read(positions[:, -1])
            else:
                attend = latent_decode_attention.dense_decode_attention
                read = jnp.full((R,), T, jnp.int32)
            mix = attend(qq, ckv, layer, positions, latent=C, scale=scale)
            out = jnp.einsum("rshc,chv->rshv", mix, p["w_vb"].astype(dt))
        # a row's queries see prefixes of what its last one sees
        aux["mla_keys"] = jnp.stack([
            (positions[:, -1] + 1).sum(dtype=jnp.int32), read.sum(dtype=jnp.int32)
        ])
        return out, {"ckv": ckv}, aux

    if K:
        with jax.named_scope("dsa_index"):
            def turned(x):  # the first Dr of the last dim rotated, (R, Sq, J, Di)
                return jnp.concatenate(
                    [_rope_pairs(x[..., :Dr], positions, c.rope_theta), x[..., Dr:]],
                    axis=-1,
                )

            qi = turned(jnp.einsum("rsq,qjd->rsjd", c_q, p["w_iq"].astype(dt)))
            ki = _layernorm(
                jnp.einsum("rse,ed->rsd", h, p["w_ik"].astype(dt)),
                p["ik_norm"], p["ik_bias"], _INDEX_NORM_EPS,
            )
            ki = turned(ki[:, :, None])[:, :, 0].astype(dt)
            wi = jnp.einsum(
                "rse,ej->rsj", h, p["w_iw"].astype(dt),
                preferred_element_type=jnp.float32,
            )

    if slot is None and Sq == 1:  # ---- one token for every row
        ckv, ik = state["ckv"], state["ik"]
        T = ckv.shape[2]
        rows, pos = jnp.arange(R), positions[:, 0]
        with jax.named_scope("dsa_index"):
            ckv = ckv.at[layer, rows, pos].set(new_ckv[:, 0])
            ik = ik.at[layer, rows, pos].set(ki[:, 0])
            slab = lax.dynamic_index_in_dim(ik, layer, 0, keepdims=False)
            visible = jnp.arange(T)[None, :] <= pos[:, None]        # (R, T)
            scores = jnp.where(
                visible, _index_scores(qi, wi, slab)[:, 0], -jnp.inf
            )
        streamed = latent_decode_attention.implementation(T) == "streamed"
        with jax.named_scope("dsa_select"):
            if streamed:
                hit = _select_mask(scores, K)                       # (R, T)
                selected = hit.sum(dtype=jnp.int32)
                read = latent_decode_attention.keys_read(pos).sum(dtype=jnp.int32)
            else:
                _, chosen = lax.top_k(scores, min(K, T))            # (R, K)
                valid = chosen <= pos[:, None]
                selected = read = valid.sum(dtype=jnp.int32)
        with jax.named_scope("mla_attn"):
            q_lat = jnp.einsum("rhn,chn->rhc", q_nope[:, 0], p["w_kb"].astype(dt))
            qq = jnp.concatenate(
                [q_lat, q_rope[:, 0], fill[:, :1].repeat(c.num_heads, 1)], axis=-1
            )                                                       # (R, H, row)
            if streamed:
                mix = latent_decode_attention.latent_decode_attention(
                    qq, ckv, layer, pos, hit, latent=C, scale=scale
                )
            else:
                mix = latent_decode_attention.gathered_decode_attention(
                    qq, ckv, layer, chosen, valid, latent=C, scale=scale
                )
            out = jnp.einsum("rhc,chv->rhv", mix, p["w_vb"].astype(dt))[:, None]
        aux["dsa_keys"] = jnp.stack([
            visible.sum(dtype=jnp.int32), selected, read
        ])
        if collect:
            if not streamed:
                hit = jnp.zeros((R, T), bool).at[rows[:, None], chosen].set(valid)
            aux["selected"] = hit[:, None, :]
        return out, {"ckv": ckv, "ik": ik}, aux

    # ---- a run of one row, from position 0
    if slot is None or R != 1:
        raise NotImplementedError(
            "a latent config prefills whole prompts, one row at a time "
            "(prefill_into_slot); chunks and batched runs are not written"
        )
    # the run's state is not written here: no layer of a run reads the
    # cache, so ``_cached_step`` writes all layers' rows at once after
    # the loop.  Written here, layer by layer, XLA carries the cache
    # through the loop with the POSITIONS minor-most (the layout the
    # projections' outputs have) and copies it whole, in and out, every
    # call (3 GB; compile-only, PR 30).
    aux["ckv_rows"] = new_ckv[0]
    if K:
        aux["ik_rows"] = ki[0]
    H = c.num_heads
    flash = latent_prefill_attention.implementation(
        Sq, Dn + Dr, c.v_head_dim
    ) == "flash"
    # the kernel reads key heads of whole lane tiles: the zeros behind a
    # head that is none (192 -> 256) are written where the head is put
    # together, not in a pass of their own; XLA's body is given none
    behind = latent_prefill_attention.lanes_behind(Dn + Dr) if flash else 0
    zeros = [jnp.zeros((Sq, H, behind), dt)] if behind else []
    with jax.named_scope("mla_proj"):
        lat = new_ckv[0, :, :C]
        k_nope = jnp.einsum("sc,chn->shn", lat, p["w_kb"].astype(dt))
        keys = jnp.concatenate([
            k_nope,
            jnp.broadcast_to(new_ckv[0, :, None, C:C + Dr], (Sq, H, Dr)),
            *zeros,
        ], axis=-1)                                                 # (Sq, H, Dn+Dr[+behind])
        values = jnp.einsum("sc,chv->shv", lat, p["w_vb"].astype(dt))
        qq = jnp.concatenate([q_nope[0], q_rope[0], *zeros], axis=-1)
    blk = max(8, min(_QUERY_BLOCK, Sq, _SCORE_ELEMENTS // (H * Sq)))
    groups = _CAUSAL_GROUPS if Sq % (_CAUSAL_GROUPS * blk) == 0 else 1
    per = Sq // groups

    def attend(lo, n_keys):
        """Queries [lo, lo + per) over keys [0, n_keys), in blocks: their
        selection and, in XLA's body, their attention."""
        pad = -per % blk  # only a run that is one group has one

        def blocked(a):  # (per, ...) -> (blocks, blk, ...); the pad attends, is dropped
            a = jnp.pad(a[lo:lo + per], ((0, pad),) + ((0, 0),) * (a.ndim - 1))
            return a.reshape(-1, blk, *a.shape[1:])

        real = blocked(jnp.ones((Sq,), bool))
        # the pad's queries see every key
        when = jnp.where(real, blocked(positions[0]), n_keys)
        kb, vb = keys[:n_keys], values[:n_keys]
        kib = ki[0, :n_keys] if K else None

        def block(args):
            if K:
                qb, qib, wib, tb, rb = args
                with jax.named_scope("dsa_index"):
                    seen = jnp.arange(n_keys)[None, :] <= tb[:, None]   # (blk, keys)
                    scores = jnp.where(seen, _index_scores(qib, wib, kib), -jnp.inf)
                with jax.named_scope("dsa_select"):
                    chosen = _select_mask(scores, K)
            else:  # no indexer: every visible key
                qb, tb, rb = args
                chosen = seen = jnp.arange(n_keys)[None, :] <= tb[:, None]
            count = jnp.stack([
                (seen & rb[:, None]).sum(dtype=jnp.int32),
                (chosen & rb[:, None]).sum(dtype=jnp.int32),
            ])
            if flash:  # the kernel's mask operand, a block of its lines
                return None, count, chosen.astype(jnp.int8)
            with jax.named_scope("mla_attn"):
                att = jnp.einsum(
                    "qhd,shd->hqs", qb, kb, preferred_element_type=jnp.float32
                ) * scale
                att = jnp.where(chosen[None], att, -1e30)
                probs = jax.nn.softmax(att, axis=-1).astype(dt)
                ob = jnp.einsum("hqs,shv->qhv", probs, vb)
            return ob, count, (chosen if collect else None)

        args = (blocked(qq),)
        if K:
            args += (blocked(qi[0]), blocked(wi[0]))
        ob, count, chosen = lax.map(block, (*args, when, real))
        if chosen is not None:
            chosen = jnp.pad(
                chosen.reshape(-1, n_keys)[:per], ((0, 0), (0, Sq - n_keys))
            )
        if ob is not None:
            ob = ob.reshape(-1, *ob.shape[2:])[:per]
        return ob, count.sum(0), chosen

    # a query sees no key behind it: the g-th of ``groups`` runs of
    # queries is given the first g + 1 runs of keys only, which leaves
    # out 3/8 of a masked-everywhere attention's work at four groups
    spans = [(g * per, (g + 1) * per) for g in range(groups)]
    # without an indexer the kernel needs no mask: visibility is causal
    parts = [attend(lo, n_keys) for lo, n_keys in spans] if K or not flash else []
    if flash:
        mask = jnp.concatenate([part[2] for part in parts]) if K else None
        with jax.named_scope("mla_attn"):
            out = latent_prefill_attention.latent_prefill_attention(
                qq, keys, values, mask, scale=scale
            )[None]
        read = latent_prefill_attention.pairs_computed(Sq)
        if collect:
            hit = mask != 0 if K else jnp.tril(jnp.ones((Sq, Sq), bool))
            aux["selected"] = hit[None]
    else:
        out = jnp.concatenate([part[0] for part in parts])[None]
        read = sum(per * n_keys for _, n_keys in spans)
        if collect:
            aux["selected"] = jnp.concatenate([part[2] for part in parts])[None]
    if K:  # (query, key) pairs: visible, selected, scores computed for
        aux["dsa_keys"] = jnp.concatenate([
            sum(part[1] for part in parts), jnp.full((1,), read, jnp.int32)
        ])
    return out, state, aux


def _gated_delta_state(h, p, state, slot, positions, config: LlamaConfig):
    """A linear layer's mixer over the cache's recurrent state:
    ``gdn_state`` (linear layers, B, d_k, H d_v) float32 and ``gdn_conv``
    (linear layers, K - 1, B, channels), layer ``p["cache_layer"]`` of
    both.  One token for every row: each row's state and tail are read,
    updated and written back where they lie (the state by
    ``gated_delta.step_layer``: in one pass where its kernel runs).  A run
    of the one row ``slot``: it starts from ZERO — whatever the slot held is another
    request's — and the row is given the state and the tail its own tokens
    leave.  Returns (y (R, Sq, E), state, no counters: what a call does is
    known from its shapes, ``_gdn_amounts``)."""
    R, Sq = positions.shape
    layer = p["cache_layer"]
    rec, conv = state["gdn_state"], state["gdn_conv"]
    if slot is None and Sq == 1:
        y, rec, tail = _linear_mixer(config)(
            h, p, config, rec, lax.dynamic_index_in_dim(conv, layer, 0, keepdims=False)
        )
        conv = lax.dynamic_update_index_in_dim(conv, tail, layer, 0)
    elif slot is not None and R == 1:
        # the run reads no state, so it writes none here: ``_cached_step``
        # writes every layer's row at once after the loop.  Written here,
        # layer by layer, XLA carries ``gdn_conv`` through the loop with
        # the taps minor-most (the layout the projections' output has),
        # padded 3 -> 128, and copies it in and out every call (1.1 GB;
        # compile-only, PR 46)
        y, new, tail = _linear_mixer(config)(h, p, config)
        return y, state, {"gdn_state_rows": new, "gdn_conv_rows": tail}
    else:
        raise NotImplementedError(
            "a config with linear-attention layers prefills whole prompts, one "
            "row at a time, and steps one token a row (prefill_into_slot / "
            "decode_step_rowwise): a run that continues a row's state is not "
            "written"
        )
    return y, dict(state, gdn_state=rec, gdn_conv=conv), {}


#: token rows of a run above which its feed-forward goes through the
#: expert layer in chunks.  Where every expert is held, the sorted (token,
#: choice) rows of 8,192 tokens x 8 are 805 MB a copy at 6,144 wide, 201
#: MB a chunk.  Where only some are (``_ffn`` gathers a block of the held
#: rows: PR 54), a chunk's largest arrays are the (2,048, E) float32 sum
#: and a block's rows, 50 MB + 25-38 MB at 6,144 wide, and what the
#: chunk still bounds is the landing's (tokens, block) product, which
#: grows with the square of the run
_FFN_CHUNK = 2048


def _ffn_in_chunks(h, p, config: LlamaConfig):
    """``_ffn`` of a long run, ``_FFN_CHUNK`` tokens at a time (the same
    result: every token's feed-forward is its own)."""
    B, S, E = h.shape
    if "w_router" not in p or S <= _FFN_CHUNK or S % _FFN_CHUNK:
        return _ffn(h, p, config)
    chunks = h.reshape(B, S // _FFN_CHUNK, _FFN_CHUNK, E).swapaxes(0, 1)
    y, routing = lax.map(lambda hc: _ffn(hc, p, config), chunks)
    k = routing["experts"].shape[-1]
    experts = routing.pop("experts").swapaxes(0, 1).reshape(B, S, k)
    # the counts (``rows``, ``zero``) add up over the chunks
    return y.swapaxes(0, 1).reshape(B, S, E), {
        **{name: count.sum(0) for name, count in routing.items()},
        "experts": experts,
    }


def _shortcut_block_step(x, p, state, slot, positions, config: LlamaConfig,
                         collect: bool = False):
    """``_block_step`` of a shortcut-connected double layer (LongCat-
    Flash's; ``N`` an RMSNorm with its own scale)::

        h0 = N(x);  x = x + MLA_0(h0)
        g0 = N(x);  m = MoE(g0)               # kept aside: the shortcut
                    x = x + SwiGLU_0(g0)
        h1 = N(x);  x = x + MLA_1(h1)
        g1 = N(x);  x = x + SwiGLU_1(g1) + m  # the expert layer lands here

    The two attentions are ``_latent_attention`` on sub-layer i's own
    weights (``_SUB_LAYER``: slice ``2 p["layer"] + i`` of the whole
    stack, ``_layer_params``) and cache layer ``2 p["cache_layer"] + i``,
    the expert layer the one ``_ffn``; nothing between ``m``'s making and
    its landing needs it, so a schedule (or, across chips, an exchange)
    may overlap it with the second attention and dense SwiGLU.  aux: the
    attentions' entries stacked (2, ..) — a cache layer each,
    ``_cached_step`` lays them out — beside the expert layer's
    ``expert_rows`` and ``zero_choices``."""
    c = config
    kept = []
    for i in range(2):
        at = 2 * p["layer"] + i
        sub = {k: lax.dynamic_index_in_dim(p[k], at, 0, keepdims=False) for k in _SUB_LAYER}
        sub["cache_layer"] = 2 * p["cache_layer"] + i
        with jax.named_scope(f"scmoe_attn{i}"):
            h = _rmsnorm(x, sub["attn_norm"], c.rms_eps)
            attn, state, aux = _latent_attention(h, sub, state, slot, positions, c, collect)
            x = x + jnp.einsum("bshd,hde->bse", attn, sub["wo"].astype(c.dtype))
            g = _rmsnorm(x, sub["mlp_norm"], c.rms_eps)
        kept.append(aux)
        if i == 0:
            with jax.named_scope("scmoe_experts"):
                m, routing = _ffn_in_chunks(g, p, c)
        with jax.named_scope(f"scmoe_dense{i}"):
            x = x + _swiglu(g, sub["wd_gate"], sub["wd_up"], sub["wd_down"], c)
    aux = {k: jnp.stack([kept[0][k], kept[1][k]]) for k in kept[0]}
    _note_routing(aux, routing, collect)
    return x + m, state, aux


def _note_routing(aux: Params, routing: Params, collect: bool) -> None:
    """An expert layer's ``routing`` (``_ffn``) into its block's ``aux``:
    the counts ``_with_counts`` adds up, with ``collect`` the choices."""
    aux["expert_rows"] = routing["rows"]
    if "zero" in routing:
        aux["zero_choices"] = routing["zero"]
    if "tiles" in routing:
        aux["row_tiles"] = routing["tiles"]
    if collect:
        aux["experts"] = routing["experts"]


def _block_step(x, p, state, slot, positions, config: LlamaConfig,
                collect: bool = False):
    """Block ``p["cache_layer"]`` over each row's run of new tokens.  x:
    (R, Sq, E); positions: (R, Sq) absolute position of every new token;
    state: the WHOLE cache's token state (``k``/``v``, or ``ckv``/``ik``
    for a latent config: the attention and what the cache holds go by
    the config's attention kind), of which only the new tokens' entries
    are written; slot: the one cache row addressed, or None for all B.
    Returns (x, state, aux): aux holds ``expert_rows`` for an expert
    block and ``dsa_keys`` for a latent config (``_with_counts``), with
    ``collect`` also the choices made (``selected``, ``experts``)."""
    c = config
    if c.block_form == "shortcut":
        return _shortcut_block_step(x, p, state, slot, positions, c, collect)
    with jax.named_scope("decode_attn"):
        h = _norm_in(x, p, "attn_norm", c)
        if "a_log" in p:
            y, state, aux = _gated_delta_state(h, p, state, slot, positions, c)
        else:
            if c.latent:
                attn, state, aux = _latent_attention(
                    h, p, state, slot, positions, c, collect
                )
            else:
                attn, state, aux = _kv_attention(h, p, state, slot, positions, c)
                attn = _output_gated(attn, h, p, c)
            y = jnp.einsum("bshd,hde->bse", attn, p["wo"].astype(c.dtype))
        x = x + _norm_out(y, p, "attn_norm", c)
    with jax.named_scope("decode_mlp"):
        y, routing = _ffn_in_chunks(_norm_in(x, p, "mlp_norm", c), p, c)
        y = _norm_out(y, p, "mlp_norm", c)
    if routing:
        _note_routing(aux, routing, collect)
    return x + y, state, aux


def _logits(params: Params, x, config: LlamaConfig):
    """Final-normed hidden states (..., E) -> logits (..., V) float32."""
    return jnp.einsum(
        "...e,ve->...v", x, _head_weight(params, config).astype(config.dtype),
        preferred_element_type=jnp.float32,
    )


def _cached_step(params: Params, tokens, cache: Params, slot, start,
                 config: LlamaConfig, collect: bool = False,
                 hidden: bool = False):
    """The ONE cached step: a contiguous run of Sq new tokens per row,
    each row's starting at its own offset, through all layers.

    tokens: (R, Sq); start: (R,) absolute position of tokens[:, 0];
    slot: None when the R rows are all B rows of the cache, else the
    (traced) index of the one row addressed (R = 1).  Returns
    (last-token logits (R, V) f32, new cache) — with ``hidden`` the
    final-normed hidden state of EVERY new token (R, Sq, E) in the
    logits' place, for a caller that wants more positions' logits
    (``_logits``) or feeds the multi-token-prediction module
    (``models/mtp.py``).  The cache rides the
    layer loop whole, as its carry: under a jit that donates it every
    layer writes the new tokens' state in place and no slab is copied.
    A config with leading dense blocks loops over its two parameter
    stacks in turn, with the same body.  ``collect``: returns (logits,
    cache, the choices every layer made) — for comparisons with a
    reference, no serving path asks."""
    c = config
    positions = start[:, None] + jnp.arange(tokens.shape[1])
    x = params["tok_embed"].astype(c.dtype)[tokens]
    # every row steps (one token each, or the few of a speculative
    # step's verification or of a block-diffusion step's block) | one
    # row's run from position 0
    step = slot is None and (tokens.shape[1] <= _STEP_RUN or c.latent)
    state = {k: cache[k] for k in _STATE if k in cache}
    # a latent config's run reads no cache: its state stays out of the
    # loop and gets the run's rows after it (``_latent_attention``)
    riding = {} if c.latent and not step else state

    def block(carry, p, cache_layer):
        xx, st = carry
        xx, st, aux = _block_step(
            xx, dict(p, cache_layer=cache_layer), st, slot, positions, c, collect
        )
        return (xx, st), aux

    def close(xx):
        # behind a pass of a looped config: the final norm (the next pass
        # reads normed states), and the exit gate on each row's last new token
        xx = _rmsnorm(xx, params["final_norm"], c.rms_eps)
        return xx, _exit_lambda(params, xx[:, -1, :], c)

    (x, riding), aux = _layer_loop(params, c, (x, riding), block, close=close)
    if c.loop_passes > 1:
        aux = {"exit_lambda": aux["closed"]}  # (passes, R)
    if c.mixers_per_layer > 1:
        # (layers, attentions a layer, ..) -> (cache layers, ..)
        aux = {k: v.reshape(-1, *v.shape[2:]) if k in _PER_CACHE_LAYER else v
               for k, v in aux.items()}
    state = dict(state, **riding)
    for k in ("ckv", "ik"):
        if k + "_rows" in aux:  # (L, Sq, width) -> every layer, row ``slot``
            state[k] = lax.dynamic_update_slice(
                state[k], aux.pop(k + "_rows")[:, None], (0, slot, 0, 0)
            )
    if "gdn_state_rows" in aux:  # (linear layers, 1, ...) -> row ``slot``
        state["gdn_state"] = lax.dynamic_update_slice(
            state["gdn_state"], gated_delta.packed(aux.pop("gdn_state_rows")),
            (0, slot, 0, 0)
        )
        state["gdn_conv"] = lax.dynamic_update_slice(
            state["gdn_conv"], aux.pop("gdn_conv_rows"), (0, 0, slot, 0)
        )
    if c.loop_passes == 1:  # a looped config's last pass has normed it
        x = _rmsnorm(x, params["final_norm"], c.rms_eps)
    logits = x if hidden else _logits(params, x[:, -1, :], c)
    cache = _with_counts(cache, state, aux, step)
    if LINEAR in c.layer_types:
        cache["gdn_counts"] = _add_wide(
            cache["gdn_counts"], _gdn_amounts(c, *tokens.shape, step)
        )
    if c.sliding is not None:
        when = int(step)  # runs at [:, :, 0], one-token steps at [:, :, 1]
        cache["attn_keys"] = cache["attn_keys"].at[:, :, when].set(_add_wide(
            cache["attn_keys"][:, :, when],
            _kind_amounts(c, positions, step, cache["k"].shape[2]),
        ))
    return (logits, cache, aux) if collect else (logits, cache)


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
def choices_cached(params, tokens, cache, slot, pos, config: LlamaConfig):
    """``prefill_into_slot`` (``slot`` an index, tokens (1, S), ``pos``
    ignored) or ``decode_step_rowwise`` (``slot`` None, tokens (B,), pos
    (B,)) that also hands back what each layer chose: (logits, cache,
    {"selected": (L, R, Sq, T') bool keys each query attended to (latent
    configs), "experts": (expert layers, R, Sq, k) (expert configs)}).
    The same ``_cached_step`` with its choices kept: for comparisons
    with a reference's choices; no serving path calls it."""
    if slot is None:
        return _cached_step(params, tokens[:, None], cache, None, pos, config, True)
    return _cached_step(
        params, tokens, cache, slot, jnp.zeros((1,), jnp.int32), config, True
    )


@partial(jax.jit, static_argnames=("config",), donate_argnames=("cache",))
def decode_step_rowwise(params, tokens, cache, pos, config: LlamaConfig):
    """One token for every row at per-row positions — the primitive a
    continuous batcher needs: each cache row b has its own length.

    tokens: (B,) int32 last token per row; pos: (B,) its absolute
    position.  Returns (logits (B, V) f32, new cache).  Inactive rows
    simply keep decoding garbage into their own slots — the engine masks
    them out — so the compiled shape never changes.  In place: one new
    K/V row per sequence and layer; each row's keys are read once,
    unexpanded, and over a cache of whole blocks only up to the block
    that holds ``pos`` (``ops/kv_decode_attention.py``)."""
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

    tokens: (1, S) prompt; cache: the engine's (L, B, T, KV x D) batch
    cache, donated and written in place at that row only.  Returns
    (last-token logits (1, V), updated cache).  One compile per
    prompt-bucket length serves every slot (slot is traced)."""
    return _cached_step(
        params, tokens, cache, slot, jnp.zeros((1,), jnp.int32), config
    )


#: what a draw is for, the last word of its key (``draw_keys``)
DRAW_TOKEN, DRAW_DRAFT, DRAW_ACCEPT, DRAW_RESIDUAL, DRAW_UNMASK = 0, 1, 2, 3, 4


def draw_keys(key, request, position, purpose: int):
    """One key a row: ``fold_in(fold_in(fold_in(key, request), position),
    purpose)``.  ``key``: the deployment's (``jax.random.key(seed)``);
    ``request`` (B,): the request each row serves; ``position`` (B,): the
    position of the token the draw decides; ``purpose``: ``DRAW_TOKEN`` a
    token from the model's own distribution, ``DRAW_DRAFT`` the draft for
    that position, ``DRAW_ACCEPT`` the uniform number that accepts it or
    not, ``DRAW_RESIDUAL`` the token in its place where it is rejected,
    ``DRAW_UNMASK`` a block-diffusion pass's candidate for a masked position
    (``models/block_diffusion.py`` folds the pass's number in behind it).
    So a token's draw hangs on nothing but its request and its position:
    not on which rows share its step, nor on how many steps it took to get
    there, and a reference can replay it."""
    def one(r, p):
        k = jax.random.fold_in(jax.random.fold_in(key, r), p)
        return jax.random.fold_in(k, purpose)

    return jax.vmap(one)(request, position)


@partial(jax.jit, static_argnames=("temperature",))
def _pick_token(logits, key, *, temperature):
    """The ONE sampler: ``argmax`` at temperature 0, else a categorical
    draw from ``softmax(logits / temperature)`` — with one key for the
    whole batch, or one a row (a typed key array, ``draw_keys``)."""
    if temperature > 0.0:
        draw = jax.random.categorical
        if jnp.issubdtype(key.dtype, jax.dtypes.prng_key) and key.ndim:
            draw = jax.vmap(draw)
        return draw(key, logits / temperature).astype(jnp.int32)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


@partial(jax.jit, static_argnames=("temperature",))
def sample_rows(logits, key, request, position, *, temperature):
    """logits (B, V) -> (B,) int32: row b's token for ``position[b]`` of
    ``request[b]``, drawn with its own key (``draw_keys``)."""
    keys = draw_keys(key, request, position, DRAW_TOKEN)
    return _pick_token(logits, keys, temperature=temperature)


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
