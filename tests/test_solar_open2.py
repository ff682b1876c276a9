"""Solar-Open2 through ``LlamaConfig``: the Kimi-delta mixer, the gated GQA
without rotation and the expert layer under both, against the plain reference,
through the two cached programs and through ``LLMEngine``; the chip's share of
the expert layer.  The delta rule itself (``ops/gated_delta.py``) is
``tests/test_solar_open2_ops.py``'s."""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import errors
from chipbench.reference import solar_open2 as reference
from ray_tpu.models import hf, llama
from ray_tpu.models.llama import FULL, LINEAR, LlamaConfig
from ray_tpu.ops import gated_delta as gd

CATALOG_CONFIG = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
                           "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64, "head_dim": 128,
    "num_key_value_heads": 8, "vocab_size": 196608, "intermediate_size": 10240,
    "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05, "rope_theta": 10000,
    "tie_word_embeddings": False, "max_position_embeddings": 1048576,
    "first_k_dense_replace": 0, "use_rope": False, "gqa_interval": 3,
    "gqa_layers": list(range(0, 48, 4)), "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 320, "n_shared_experts": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 1, "num_experts_per_tok": 8,
}


def test_the_published_keys_give_the_published_shape():
    config = hf.llama_config_from_hf(type("Cfg", (), CATALOG_CONFIG)())
    want = LlamaConfig.solar_open2(rms_eps=1e-5, dtype=jnp.bfloat16)
    assert config == want
    assert config.layer_types[:5] == (FULL, LINEAR, LINEAR, LINEAR, FULL)
    assert config.layer_types.count(FULL) == 12 and config.rope_theta is None
    assert (config.linear_kind, config.linear_gate_rank, config.attn_output_gate) == (
        "kda", 128, True)
    assert (config.num_experts, config.experts_per_token, config.expert_dim,
            config.shared_expert_dim, config.router_scoring) == (320, 8, 1280, 1280, "sigmoid")
    tree = jax.eval_shape(lambda: llama.init(jax.random.key(0), dataclasses.replace(
        want, num_layers=4, layer_types=want.layer_types[:4], vocab_size=24576,
        experts_held=40)))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree)) == 3_308_353_344
    assert tree["gdn_blocks"]["dt_bias"].shape == (3, 64, 128)
    assert tree["blocks"]["w_og"].shape == (1, 4096, 64, 128)
    axes = llama.param_logical_axes(want)
    assert set(axes["gdn_blocks"]) == set(tree["gdn_blocks"])
    assert set(axes["blocks"]) == set(tree["blocks"])


def test_what_does_not_go_together_is_refused_by_name():
    with pytest.raises(NotImplementedError, match="linear_gate_rank > 0 columns"):
        LlamaConfig.tiny_kda(linear_gate_rank=0)
    with pytest.raises(ValueError, match="'gated_delta' or 'kda'"):
        LlamaConfig.tiny_kda(linear_kind="mamba")
    with pytest.raises(NotImplementedError, match="no experts, leading dense blocks or window"):
        LlamaConfig.tiny_hybrid(num_experts=8, experts_per_token=2, expert_dim=32)
    with pytest.raises(NotImplementedError, match="plain K/V full-attention layers"):
        LlamaConfig.tiny_shortcut(attn_output_gate=True)
    with pytest.raises(NotImplementedError, match="full-rank KDA gate projections"):
        hf.solar_open2_fields(dict(CATALOG_CONFIG, kda_use_full_proj=True))


@pytest.fixture(scope="module")
def toy():
    config = LlamaConfig.tiny_kda(experts_held=4, expert_offset=2)
    params = llama.init(jax.random.key(0), config)
    # the seeded 0.02 weights leave every mixer near zero: four times them
    params = jax.tree.map(lambda a: a * 4 if a.ndim > 2 else a, params)
    tokens = jax.random.randint(jax.random.key(1), (1, 29), 0, config.vocab_size)
    spec = reference.Spec(tuple(config.layer_types), float(config.rms_eps),
                          config.experts_per_token, config.expert_offset)
    hidden, info = reference.forward(params, tokens[0], spec)
    return config, params, tokens, spec, np.asarray(reference.logits(params, hidden)), info


@pytest.mark.limit(170)
def test_prefill_in_segments_and_decode_are_the_references_full_forward(toy):
    """A prompt of 21 ids is three segments of seven (``linear_segment`` 8:
    the state and the tail carried twice, a last chunk of three), then eight
    steps of the full batch, in a slot that served another request first."""
    config, params, tokens, spec, want, _ = toy
    assert config.linear_segment == 8 and config.linear_chunk == 4
    cache = llama.init_cache(config, 4, 64)
    other = jax.random.randint(jax.random.key(2), (1, 16), 0, config.vocab_size)
    _, cache = llama.prefill_into_slot(params, other, cache, jnp.int32(2), config)
    logits, cache = llama.prefill_into_slot(params, tokens[:, :21], cache, jnp.int32(2), config)
    assert errors(logits[0], want[20])["max"] < 1e-4
    fed, pos = np.zeros(4, np.int32), np.zeros(4, np.int32)
    for t in range(21, 29):
        fed[2], pos[2] = int(tokens[0, t]), t
        logits, cache = llama.decode_step_rowwise(
            params, jnp.asarray(fed), cache, jnp.asarray(pos), config)
        assert errors(logits[2], want[t])["max"] < 1e-4, t
    counts = {n: llama.wide_total(np.asarray(cache["gdn_counts"])[i])
              for i, n in enumerate(llama.GDN_COUNTS)}
    assert counts["gdn_rows_stepped"] == 8 * 4 * 6
    assert counts["gdn_tokens_scanned"] == (16 + 21) * 6
    assert counts["gdn_state_bytes_step"] == 8 * 4 * 6 * 2 * 4 * 8 * 16 * 4
    assert int(np.asarray(cache["moe_layer_steps"])[:, 0].sum()) == 10 * 8


def test_the_uncached_forward_is_the_reference_too(toy):
    config, params, tokens, _spec, want, info = toy
    got = llama.forward(params, tokens, config)
    assert errors(got[0], want)["max"] < 1e-4
    chose = np.asarray(llama.expert_choices(params, tokens, config))[:, 0]
    assert (np.sort(chose, -1) == np.sort(np.asarray(info["experts"]), -1)).all()


@pytest.mark.parametrize("piece, bent, least", [
    ("no output gate on the GQA layers", dict(gqa_gate=False), 1.0),
    ("beta under 1", dict(neg_eigval=False), 1.0),
    # a head's channels differ by dt_bias and a low-rank projection of seeded
    # weights: their mean for all moves the logits a hundred times what the
    # program is off by, not ten thousand
    ("one decay a head", dict(channel_decay=False), 0.005),
    ("no shared expert", dict(shared_expert=False), 1.0),
    ("the state through bfloat16", dict(state_dtype="bfloat16"), 0.05),
])
def test_each_piece_left_out_of_the_reference_shows(toy, piece, bent, least):
    config, params, tokens, spec, want, _ = toy
    hidden, _ = reference.forward(params, tokens[0], spec._replace(**bent))
    assert errors(reference.logits(params, hidden), want)["max"] > least, piece


@pytest.mark.limit(170)
def test_the_engine_serves_it_greedily(toy):
    """Through ``LLMEngine`` and the hybrid cache, the normal entry point: the
    ids are the argmax of the reference's logits given the ids before."""
    from ray_tpu.serve.llm import LLMEngine

    config, params, tokens, spec, _want, _ = toy

    async def main():
        engine = LLMEngine(params, config, max_slots=2, max_len=64)
        prompt = tokens[0, :21].tolist()
        async def ids(prompt):
            return [t async for t in engine.stream(prompt, 6)]

        a, b = await asyncio.gather(ids(prompt), ids(tokens[0, 5:15].tolist()))
        counters = await engine.cache_counters()
        return prompt, a, b, counters

    prompt, a, _b, counters = asyncio.run(main())
    assert len(a) == 6
    seq = prompt + a
    hidden, _ = reference.forward(params, jnp.asarray(seq, jnp.int32), spec)
    greedy = np.asarray(reference.logits(params, hidden)).argmax(-1)
    assert a == greedy[20:26].tolist()
    assert counters["gdn_tokens_scanned"] == (21 + 10) * 6
    assert counters["moe_held_pairs_total"] > 0 and counters["gated_delta_step"] == "xla"
    # four heads of 16, chunk 4: the toy's prefills trace XLA's body; the served
    # shape (64 heads of 128 x 128, chunk 64, a decay a channel) the kernel
    assert counters["gated_delta_scan"] == "xla"
    assert gd.scan_implementation(64, 128, 128, 64, True) == "kernel"


def test_speculation_and_diffusion_are_refused_beside_the_state(toy):
    from ray_tpu.serve.llm import LLMEngine

    config, params = toy[0], toy[1]
    with pytest.raises(ValueError, match="diffusion_block does not go with"):
        LLMEngine(params, config, max_slots=2, max_len=64, diffusion_block=4)


@pytest.mark.parametrize("shares", [8, 4, 2])
def test_the_chips_shares_add_up_to_the_uncut_layer(shares):
    """Each chip's routed part (its output less the shared expert, which every
    chip adds whole) summed over the chips, and the shared expert once, is
    the layer with every expert held."""
    base = dict(num_experts=16, experts_per_token=4, expert_dim=24, shared_expert_dim=24)
    whole = LlamaConfig.tiny_kda(**base)
    blocks = llama._init_blocks(jax.random.key(3), whole, 1, True)
    p = {k: v[0] for k, v in blocks.items() if k not in llama._EXPERT_TENSORS}
    tensors = {k: blocks[k] * 8 for k in llama._EXPERT_TENSORS}
    h = jax.random.normal(jax.random.key(4), (2, 9, whole.embed_dim))
    want, routing = llama._ffn(h, dict(p, layer=jnp.int32(0), **tensors), whole)
    shared = llama._swiglu(h, p["ws_gate"], p["ws_up"], p["ws_down"], whole)
    held = 16 // shares
    total, rows = shared, 0
    for chip in range(shares):
        part = dataclasses.replace(whole, experts_held=held, expert_offset=chip * held)
        mine = {k: v[:, chip * held:(chip + 1) * held] for k, v in tensors.items()}
        y, r = llama._ffn(h, dict(p, layer=jnp.int32(0), **mine), part)
        total = total + (y - shared)
        rows += int(r["rows"].sum())
        np.testing.assert_array_equal(r["experts"], routing["experts"])
    assert rows == 2 * 9 * 4
    np.testing.assert_allclose(total, want, atol=2e-5)
