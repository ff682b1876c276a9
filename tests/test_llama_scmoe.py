"""The shortcut-connected double layer (LongCat-Flash's; ``LlamaConfig.
block_form = "shortcut"``): two latent attentions, two dense SwiGLUs and one
expert layer across them, identity ("zero-compute") experts among the
router's outputs, both LoRA scales — the cached path against the float32
reference (``chipbench/reference/longcat_flash.py``, which imports nothing
of the program), the chip's share against the uncut layer, the identity
experts' corner cases, the counters the cache carries, and the configs that
were there lowering to the programs they lowered to."""

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import longcat_flash as ref
from ray_tpu.models import llama
from ray_tpu.models.llama import LlamaConfig

SLOTS, MAX_LEN = 4, 64


def spec_of(cfg):
    return ref.Spec(float(cfg.rope_theta), cfg.rms_eps, cfg.qk_rope_head_dim,
                    cfg.experts_per_token, cfg.router_scale, cfg.num_experts,
                    cfg.expert_offset, cfg.mla_scale_q_lora, cfg.mla_scale_kv_lora)


def weights(cfg, seed=1):
    """Seeded weights four times ``init``'s (so that attention and routing
    are decisive at toy widths), norms off one, a selection bias that
    matters."""
    params = llama.init(jax.random.key(seed), cfg)
    params = jax.tree.map(lambda a: a * 4 if a.ndim > 2 else a, params)
    blocks = dict(params["blocks"])
    for i, name in enumerate(("attn_norm", "mlp_norm", "q_a_norm", "kv_a_norm")):
        blocks[name] = 1 + 0.1 * jax.random.normal(jax.random.key(40 + i), blocks[name].shape)
    blocks["router_bias"] = 0.02 * jax.random.normal(
        jax.random.key(5), blocks["router_bias"].shape)
    return dict(params, blocks=blocks)


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig.tiny_shortcut()
    return cfg, weights(cfg)


@pytest.fixture(scope="module")
def served(model):
    """Two prompts prefilled into slots 1 and 2 and six greedy decode steps
    through the cache, with the choices every layer made."""
    cfg, params = model
    cache = llama.init_cache(cfg, SLOTS, MAX_LEN)
    rng = np.random.default_rng(0)
    seqs = {1: rng.integers(0, cfg.vocab_size, 20).tolist(),
            2: rng.integers(0, cfg.vocab_size, 9).tolist()}
    logits, chose = {r: [] for r in seqs}, {r: [] for r in seqs}
    for r, seq in seqs.items():
        out, cache, c = llama.choices_cached(
            params, jnp.asarray([seq], jnp.int32), cache, jnp.int32(r), None, cfg)
        logits[r].append(out[0])
        chose[r].append(np.asarray(c["experts"])[:, 0])
    for _ in range(6):
        tokens, pos = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
        for r, seq in seqs.items():
            seq.append(int(jnp.argmax(logits[r][-1])))
            tokens[r], pos[r] = seq[-1], len(seq) - 1
        out, cache, c = llama.choices_cached(
            params, jnp.asarray(tokens), cache, None, jnp.asarray(pos), cfg)
        for r in seqs:
            logits[r].append(out[r])
            chose[r].append(np.asarray(c["experts"])[:, r])
    return seqs, logits, chose, cache


def test_prefill_then_decode_through_the_cache_is_the_reference(model, served):
    cfg, params = model
    seqs, logits, chose, _cache = served
    for r, seq in seqs.items():
        first = len(seq) - 7
        hidden, info = ref.forward(params, jnp.asarray(seq, jnp.int32), spec_of(cfg))
        want = ref.logits(params, hidden[first:])
        np.testing.assert_allclose(jnp.stack(logits[r]), want, rtol=0, atol=2e-5)
        assert float(jnp.std(want)) > 0.05
        # the routers agree choice by choice, identity experts among them
        mine = np.concatenate(chose[r], axis=1)
        assert np.array_equal(np.sort(mine, -1), np.sort(np.asarray(info["experts"]), -1))
        assert (mine >= cfg.num_experts).any() and (mine < cfg.num_experts).any()


def test_the_served_programs_give_the_same_logits_without_the_choices(model, served):
    cfg, params = model
    seqs, logits, _chose, _cache = served
    prompt = seqs[1][:20]
    out, cache = llama.prefill_into_slot(
        params, jnp.asarray([prompt], jnp.int32), llama.init_cache(cfg, SLOTS, MAX_LEN),
        jnp.int32(1), cfg)
    np.testing.assert_array_equal(out[0], logits[1][0])
    tokens, pos = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
    tokens[1], pos[1] = seqs[1][20], 20
    out, cache = llama.decode_step_rowwise(
        params, jnp.asarray(tokens), cache, jnp.asarray(pos), cfg)
    np.testing.assert_allclose(out[1], logits[1][1], rtol=0, atol=1e-6)


def test_the_cache_has_two_layers_a_layer_and_counts_what_was_routed(model, served):
    cfg, _params = model
    seqs, _logits, _chose, cache = served
    assert (cfg.cache_layers, cfg.expert_layers, cfg.router_outputs) == (4, 2, 12)
    assert cache["ckv"].shape == (4, SLOTS, MAX_LEN, 128)
    assert cache["mla_keys"].shape == (4, 2, 2)
    assert cache["moe_expert_tokens"].shape == (2, 8)
    assert cache["moe_zero_choices"].shape == (2,)
    rows = 20 + 9 + 6 * SLOTS                      # every row of a step routes
    # the calls and, beside them, the row tiles the expert layer gathered:
    # every call here is one block of one tile
    assert np.asarray(cache["moe_layer_steps"]).tolist() == [[8, 8], [8, 8]]
    held = np.asarray(cache["moe_expert_tokens"]).sum(-1)
    zero = np.asarray(cache["moe_zero_choices"])
    assert (held + zero).tolist() == [rows * cfg.experts_per_token] * 2
    assert (zero > 0).all() and (held > 0).all()
    # keys visible to the steps' rows, the same in all four cache layers: the
    # two rows at their positions, the two empty slots at position 0
    visible = sum(20 + i + 1 + 9 + i + 1 + 2 for i in range(6))
    keys = np.asarray(cache["mla_keys"])
    assert [llama.wide_total(keys[l, 0]) for l in range(4)] == [visible] * 4
    # both rows' latents lie in all four cache layers, the scaled latent in front
    ckv = np.asarray(cache["ckv"])
    assert (np.abs(ckv[:, 1, :26, :24]).max(-1) > 0).all()
    assert (np.abs(ckv[:, 1, 26:]).max() == 0) and (np.abs(ckv[:, 3, 1:]).max() == 0)


def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """32 chips, 2 of the 64 experts each: the expert layer's parts the
    shares compute, with the identity experts' term (which every chip
    computes alike, as it does the dense path) counted once, add up to the
    uncut reference's expert layer — and so the layers do."""
    chips, held = 32, 2
    X = chips * held
    cfg = LlamaConfig.tiny_shortcut(num_layers=1, num_experts=X, zero_experts=X // 2,
                                    experts_per_token=6)
    params = weights(cfg, seed=2)
    blocks = params["blocks"]
    h = jax.random.normal(jax.random.key(9), (2, 24, cfg.embed_dim), jnp.float32)
    flat = h.reshape(-1, cfg.embed_dim)
    spec = spec_of(cfg)
    # the identity term alone: a share whose experts no token can choose
    nobody = spec._replace(expert_offset=cfg.router_outputs)
    moe = jax.jit(ref._moe, static_argnums=(2, 3))
    with jax.default_matmul_precision("highest"):
        whole, chosen, _ = moe(flat, blocks, 0, spec)
        identity = moe(flat, blocks, 0, nobody)[0]
    assert float(jnp.abs(identity).max()) > 1e-3

    total, rows, zero = 0.0, [], set()
    for rank in range(chips):
        share = dataclasses.replace(cfg, experts_held=held, expert_offset=held * rank)
        mine = {k: blocks[k][:, held * rank:held * (rank + 1)] for k in llama._EXPERT_TENSORS}
        p = dict({k: v[0] for k, v in blocks.items()}, layer=jnp.int32(0), **mine)
        y, routing = llama._ffn(h, p, share)
        total = total + (y.reshape(-1, cfg.embed_dim) - identity)
        rows.append(np.asarray(routing["rows"]))
        zero.add(int(routing["zero"]))
        if rank in (0, 17, chips - 1):
            # a share's reference is the reference given the same held set
            with jax.default_matmul_precision("highest"):
                part = moe(flat, {**blocks, **mine}, 0, spec_of(share))[0]
            np.testing.assert_allclose(y.reshape(-1, cfg.embed_dim), part, rtol=0, atol=3e-6)
    np.testing.assert_allclose(total + identity, whole, rtol=0, atol=1e-5)
    # every choice of a real expert was computed by exactly one share, and
    # every share counted the same identity choices
    chosen = np.asarray(chosen).ravel()
    assert np.array_equal(np.concatenate(rows), np.bincount(chosen[chosen < X], minlength=X))
    assert zero == {int((chosen >= X).sum())} and zero != {0}
    # ... and so do the layers: a share's layer is the dense path (x, both
    # attentions, both dense SwiGLUs), the identity term and its experts' part
    x = jax.random.normal(jax.random.key(3), (24, cfg.embed_dim), jnp.float32)
    layer = jax.jit(ref.layer, static_argnums=(2, 3))
    with jax.default_matmul_precision("highest"):
        uncut = layer(x, blocks, 0, spec)[0]
        common = layer(x, blocks, 0, nobody)[0]
        g = ref._rmsnorm(x + ref._mla(ref._rmsnorm(
            x, blocks["attn_norm"][0, 0], spec.rms_eps), blocks, 0, 0, spec),
            blocks["mlp_norm"][0, 0], spec.rms_eps)
        parts = moe(g, blocks, 0, spec)[0] - moe(g, blocks, 0, nobody)[0]
    np.testing.assert_allclose(common + parts, uncut, rtol=0, atol=2e-5)


def test_a_token_of_identity_choices_alone_and_one_with_none():
    """The router's matrix laid out so that one token's k choices are all
    identity experts and another's are all real: the first gets ``sum of its
    weights x its input`` and costs no expert row, the second no identity
    term."""
    cfg = LlamaConfig.tiny_shortcut(num_layers=1)
    params = weights(cfg, seed=4)
    blocks = dict(params["blocks"])
    E, K = cfg.embed_dim, cfg.experts_per_token
    u = jax.random.normal(jax.random.key(2), (E,), jnp.float32)
    u = u / jnp.sqrt((u * u).mean())
    sign = jnp.where(jnp.arange(cfg.router_outputs) >= cfg.num_experts, 1.0, -1.0)
    noise = 0.01 * jax.random.normal(jax.random.key(3), (E, cfg.router_outputs))
    blocks["w_router"] = (0.05 * u[:, None] * sign[None, :] + noise)[None]
    blocks["router_bias"] = jnp.zeros_like(blocks["router_bias"])
    other = jax.random.normal(jax.random.key(6), (E,), jnp.float32)
    h = jnp.stack([u, -u, other])[None]                       # (1, 3, E)
    p = dict({k: v[0] for k, v in blocks.items()}, layer=jnp.int32(0),
             **{k: blocks[k] for k in llama._EXPERT_TENSORS})
    y, routing = llama._ffn(h, p, cfg)
    chose = np.asarray(routing["experts"])[0]
    assert (chose[0] >= cfg.num_experts).all() and (chose[1] < cfg.num_experts).all()
    weight, _ = llama._route(h[0], p, cfg)
    np.testing.assert_allclose(y[0, 0], weight[0].sum() * h[0, 0], rtol=1e-5, atol=1e-6)
    assert float(weight[0].sum()) == pytest.approx(
        6 * float(jnp.sort(jax.nn.softmax(h[0, 0] @ p["w_router"]))[-K:].sum()), rel=1e-5)
    # the rows the experts computed: none for token 0, k for token 1
    mixed = int((chose[2] < cfg.num_experts).sum())
    assert int(np.asarray(routing["rows"]).sum()) == K + mixed
    assert int(routing["zero"]) == K + (K - mixed)
    moe = jax.jit(ref._moe, static_argnums=(2, 3))
    with jax.default_matmul_precision("highest"):
        want = moe(h[0], blocks, 0, spec_of(cfg))[0]
        real_only = moe(h[0], blocks, 0, spec_of(cfg)._replace(
            n_routed_experts=cfg.router_outputs))[0]
    np.testing.assert_allclose(y[0], want, rtol=0, atol=3e-6)
    np.testing.assert_allclose(y[0, 1], real_only[1], rtol=0, atol=3e-6)
    assert float(jnp.abs(real_only[0]).max()) == 0.0


@pytest.mark.parametrize("leave_out", ["mla_scale_q_lora", "mla_scale_kv_lora", "router_scale",
                                       "zero_experts", "late_landing"])
def test_the_reference_tells_each_piece_of_the_block(model, served, leave_out):
    """The reference with one piece of the mathematics left out is far from
    the program: what the chip's comparison has to refuse."""
    cfg, params = model
    seqs, logits, chose, _cache = served
    spec = spec_of(cfg)
    layer = ref._layer
    if leave_out == "late_landing":
        def early(x, blocks, i, spec, forced=None):          # m lands before sub-layer 1
            def normed(x, name, s):
                return ref._rmsnorm(x, ref._cut(blocks[name], ((0, i), (1, s))), spec.rms_eps)

            x = x + ref._mla(normed(x, "attn_norm", 0), blocks, i, 0, spec)
            g = normed(x, "mlp_norm", 0)
            m, chosen, margin = ref._moe(g, blocks, i, spec, forced)
            x = x + ref._dense(g, blocks, i, 0) + m
            x = x + ref._mla(normed(x, "attn_norm", 1), blocks, i, 1, spec)
            return x + ref._dense(normed(x, "mlp_norm", 1), blocks, i, 1), chosen, margin

        layer = early
    else:
        spec = {"mla_scale_q_lora": spec._replace(scale_q_lora=False),
                "mla_scale_kv_lora": spec._replace(scale_kv_lora=False),
                "router_scale": spec._replace(routed_scaling_factor=1.0),
                "zero_experts": spec._replace(n_routed_experts=cfg.router_outputs)}[leave_out]
    seq = seqs[1]
    x = params["tok_embed"][jnp.asarray(seq)].astype(jnp.float32)
    forced = np.concatenate(chose[1], axis=1)
    with jax.default_matmul_precision("highest"):
        for i in range(cfg.num_layers):
            x = layer(x, params["blocks"], i, spec, jnp.asarray(forced[i]))[0]
        x = ref._rmsnorm(x, params["final_norm"].astype(jnp.float32), spec.rms_eps)
    want = ref.logits(params, x[len(seq) - 7:])
    err = float(jnp.abs(jnp.stack(logits[1]) - want).max() / jnp.std(want))
    assert err > 0.05, err


def test_what_the_block_form_goes_with():
    with pytest.raises(NotImplementedError, match="shortcut-connected double layer"):
        LlamaConfig.tiny_shortcut(mtp_layers=1)
    with pytest.raises(NotImplementedError, match="shortcut-connected double layer"):
        LlamaConfig.tiny(block_form="shortcut")                # K/V attention, no experts
    with pytest.raises(ValueError, match="block_form is"):
        LlamaConfig.tiny(block_form="parallel")
    with pytest.raises(ValueError, match="zero_experts stand behind"):
        LlamaConfig.tiny(zero_experts=4)
    with pytest.raises(NotImplementedError, match="cached paths only"):
        llama.forward(None, jnp.zeros((1, 4), jnp.int32), LlamaConfig.tiny_shortcut())


def test_the_published_shape_counts_to_the_parameter():
    full = LlamaConfig.longcat_flash()
    assert (full.num_layers, full.cache_layers, full.expert_layers) == (28, 56, 28)
    assert (full.router_outputs, full.experts_per_token, full.router_scale) == (768, 12, 6.0)
    assert full.router_bias and full.router_scoring == "softmax"
    assert llama.num_params(full) == 560_664_980_480          # "560B"
    cut = LlamaConfig.longcat_flash(num_layers=4, vocab_size=16384, experts_held=16)
    assert llama.num_params(cut) == 5_172_749_312
    # a token's experts are counted: 8 of its 12 choices on real experts
    # where all are held, 12 x 16 / 768 = a quarter of one on this chip's 16
    expert = 3 * 6144 * 2048
    fixed = llama.num_params(cut) - 16384 * 6144 - 4 * 16 * expert
    attn = 6 * 8 * 64 * (128 + 64 + 128) * 4096
    assert llama.flops_per_token(cut, 4096) == 6.0 * (fixed + 4 * 0.25 * expert) + attn
    whole = llama.num_params(full) - 131072 * 6144 - 28 * 512 * expert
    assert llama.flops_per_token(full, 4096) == pytest.approx(
        6.0 * (whole + 28 * 8 * expert) + 7 * attn)
    axes = llama.param_logical_axes(LlamaConfig.tiny_shortcut())["blocks"]
    shapes = jax.eval_shape(functools.partial(llama.init, config=LlamaConfig.tiny_shortcut()),
                            jax.random.key(0))["blocks"]
    assert set(axes) == set(shapes)
    assert all(len(axes[k]) == len(shapes[k].shape) for k in axes)
    assert axes["wd_gate"] == ("layers", None, "embed", "mlp")
    assert axes["w_gate"] == ("layers", "expert", "embed", "mlp")


# ---- the configs that were there: their programs are the parent commit's ----

_LATENT = dict(q_lora_rank=32, kv_lora_rank=24, qk_nope_head_dim=12, qk_rope_head_dim=8,
               v_head_dim=16, num_kv_heads=4, mlp_dim=96)
_EXPERTS = dict(num_layers=3, first_dense_layers=1, num_experts=16, experts_per_token=4,
                expert_dim=32, shared_expert_dim=32, router_scoring="sigmoid",
                router_norm_topk=True, router_scale=2.5, experts_held=4)
#: sha256 of the lowered text with ``tests/test_llama_hybrid.py:lowered``.
#: ``hybrid`` (every row of its expert layers has a group): taken on PR 49's
#: parent commit (17810fd) and unchanged since.  The six held-experts
#: entries: taken anew on PR 54's commit, whose ``_ffn`` gathers the held
#: rows alone — they changed by design, and that ``hybrid``'s two and every
#: pin of ``tests/test_llama_hybrid.py`` did not is the proof that no
#: program whose rows all have a group was touched
PARENT = {
    ("latent_mtp", "decode_step_rowwise"): "a59eaa7aec60752b",
    ("latent_mtp", "prefill_into_slot"): "0f13e312577d2067",
    ("latent_indexer", "decode_step_rowwise"): "9512cfd5a6627c53",
    ("latent_indexer", "prefill_into_slot"): "8465100f620cc448",
    ("block_mask_held", "decode_step_rowwise"): "d85b28cb0aa22498",
    ("block_mask_held", "prefill_into_slot"): "24331be150a84ed0",
    ("hybrid", "decode_step_rowwise"): "2fd4573b8865fdc3",
    ("hybrid", "prefill_into_slot"): "9a43e8a8ca3da42d",
}
EXISTING = {
    # JoyAI-LLM-Flash's, GLM-5's, SDAR's and Olmo-Hybrid's shapes at toy widths
    "latent_mtp": lambda: LlamaConfig.tiny(**_LATENT, **_EXPERTS, mtp_layers=1),
    "latent_indexer": lambda: LlamaConfig.tiny(
        **_LATENT, **_EXPERTS, index_n_heads=4, index_head_dim=16, index_topk=8),
    "block_mask_held": lambda: LlamaConfig.tiny(
        num_experts=8, experts_per_token=2, expert_dim=32, qk_norm="head", head_dim=32,
        router_norm_topk=True, experts_held=4, mask_block=4),
    "hybrid": lambda: LlamaConfig.tiny_hybrid(),
}


@pytest.mark.parametrize("name, program", sorted(PARENT))
def test_the_other_configs_programs_are_unchanged(name, program):
    """(``tests/test_llama_hybrid.py`` pins the K/V and the softmax-expert
    configs the same way.)"""
    from tests.test_llama_hybrid import lowered

    text = lowered(EXISTING[name](), program)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENT[(name, program)]
