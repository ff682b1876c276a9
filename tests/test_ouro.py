"""Ouro (a LoopLM) through ``LlamaConfig``: the stack run ``loop_passes`` times
a token with shared weights, the final norm behind every pass, a K/V cache
layer a (pass, layer), the four-norm sandwich block and the exit gate —
against the plain reference (``chipbench/reference/ouro.py``), without a cache,
through the two cached programs and through ``LLMEngine``; each piece left out
of the reference fails; a one-pass config is what it was."""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import errors
from chipbench.reference import ouro as reference
from ray_tpu.models import hf, llama
from ray_tpu.models.llama import LlamaConfig

#: the catalog row's ``config`` (``layer_types``: "full_attention" x 48)
CATALOG_CONFIG = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5632,
    "layer_types": ["full_attention"] * 48, "max_position_embeddings": 65536,
    "max_window_layers": 48, "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152,
}
#: float32 against float32: rounding alone
CLOSE = {"rms": 2e-5, "max": 2e-4}
PROMPT, STEPS = 11, 6


def spec_of(config, **bent):
    return reference.Spec(config.loop_passes, float(config.rope_theta),
                          float(config.rms_eps))._replace(**bent)


@pytest.fixture(scope="module")
def toy():
    config = LlamaConfig.tiny_loop()
    params = llama.init(jax.random.key(0), config)
    # the seeded 0.02 weights leave every sub-layer near zero and the norms
    # at one: four times the matrices, and scales that differ
    params = jax.tree.map(lambda a: a * 4 if a.ndim > 2 else a, params)
    for i, name in enumerate(("attn_norm", "attn_norm_out", "mlp_norm", "mlp_norm_out")):
        scale = params["blocks"][name]
        params["blocks"][name] = scale + 0.3 * jax.random.normal(
            jax.random.key(10 + i), scale.shape)
    params["final_norm"] = params["final_norm"] + 0.3 * jax.random.normal(
        jax.random.key(20), params["final_norm"].shape)
    params["exit_gate"] = {"w": params["exit_gate"]["w"] * 8, "b": jnp.asarray([0.3])}
    tokens = jax.random.randint(jax.random.key(1), (1, PROMPT + STEPS), 0, config.vocab_size)
    return config, params, tokens


@pytest.fixture(scope="module")
def cached(toy):
    """The prompt through ``prefill_into_slot`` into row 2 of a 4-row cache,
    then every further token of ``tokens`` through ``decode_step_rowwise``
    (the other rows idle at position 0): logits and the gate's lambda at the
    prompt's last position and at every decoded one."""
    config, params, tokens = toy
    row, rows = 2, 4
    cache = llama.init_cache(config, rows, 32)
    logits, cache, aux = llama.choices_cached(
        params, tokens[:, :PROMPT], cache, jnp.int32(row), None, config)
    got, lam = [logits[0]], [aux["exit_lambda"][:, 0]]
    for s in range(PROMPT, PROMPT + STEPS):
        tok = jnp.zeros((rows,), jnp.int32).at[row].set(tokens[0, s])
        pos = jnp.zeros((rows,), jnp.int32).at[row].set(s)
        logits, cache, aux = llama.choices_cached(params, tok, cache, None, pos, config)
        got.append(logits[row])
        lam.append(aux["exit_lambda"][:, row])
    return np.asarray(jnp.stack(got)), np.asarray(jnp.stack(lam, axis=1)), cache


def test_the_published_keys_give_the_published_shape():
    config = hf.llama_config_from_hf(type("Cfg", (), CATALOG_CONFIG)())
    assert config == LlamaConfig.ouro_2_6b(dtype=jnp.bfloat16)
    assert (config.loop_passes, config.sandwich_norm, config.early_exit_threshold) == (4, True, 1)
    assert (config.kv_layers, config.cache_layers, config.num_layers) == (192, 192, 48)
    tree = jax.eval_shape(lambda: llama.init(jax.random.key(0), config))
    assert llama.num_params(config) == 2_667_974_657 == sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    # a block by hand: four projections, the SwiGLU, FOUR norms
    block = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert block == 51_388_416
    assert 48 * block + 2 * 49152 * 2048 + 2048 + 2049 == 2_667_974_657
    assert tree["exit_gate"]["w"].shape == (2048,) and tree["exit_gate"]["b"].shape == (1,)
    axes = llama.param_logical_axes(config)
    assert set(axes["blocks"]) == set(tree["blocks"]) and set(axes) == set(tree)
    assert set(axes["exit_gate"]) == {"w", "b"}
    # a weight is counted once and used four times: the head once
    head, S = 49152 * 2048, 200
    used = 4 * (48 * block + 2048 + 2049) + head
    assert llama.flops_per_token(config, S) == 6.0 * used + 12 * 192 * 16 * 128 * S
    # 1.5 MiB of K and V a token, 192 cache layers: 16 rows of 256 fill 6.4 GB
    cache = jax.eval_shape(lambda: llama.init_cache(
        dataclasses.replace(config, param_dtype=jnp.bfloat16), 16, 256))
    assert cache["k"].shape == cache["v"].shape == (192, 16, 256, 2048)
    size = sum(int(np.prod(cache[k].shape)) * cache[k].dtype.itemsize for k in "kv")
    assert size == 16 * 256 * 1_572_864 == 6_442_450_944
    assert cache["loop_passes"].shape == (2,) and cache["loop_exit_mass"].shape == ()


def test_what_does_not_go_together_is_refused_by_name():
    with pytest.raises(NotImplementedError, match="leave the loop at different passes"):
        LlamaConfig.tiny_loop(early_exit_threshold=0.9)
    with pytest.raises(NotImplementedError, match="leave the loop at different passes"):
        hf.llama_config_from_hf(type("Cfg", (), dict(CATALOG_CONFIG, early_exit_threshold=0.5))())
    with pytest.raises(ValueError, match="1 or more"):
        LlamaConfig.tiny(loop_passes=0)
    for kw in (dict(num_experts=8, experts_per_token=2, expert_dim=32), dict(mask_block=4),
               dict(first_dense_layers=1)):
        with pytest.raises(NotImplementedError, match="plain K/V attention over a dense SwiGLU"):
            LlamaConfig.tiny_loop(**kw)
    for preset in (LlamaConfig.tiny_hybrid, LlamaConfig.tiny_swa, LlamaConfig.tiny_kda,
                   LlamaConfig.tiny_shortcut):
        with pytest.raises(NotImplementedError, match="loop_passes > 1 is written for"):
            preset(loop_passes=2)
        with pytest.raises(NotImplementedError, match="sandwich_norm is the serial K/V block"):
            preset(sandwich_norm=True)
    with pytest.raises(NotImplementedError, match="not with post_norm"):
        LlamaConfig.tiny(sandwich_norm=True, post_norm=True)
    with pytest.raises(NotImplementedError, match="sliding window or scaled rotary"):
        hf.ouro_fields(dict(CATALOG_CONFIG, use_sliding_window=True))
    with pytest.raises(ValueError, match="no exit gate"):
        llama.exit_gates({}, jnp.zeros((1, 4), jnp.int32), LlamaConfig.tiny())


def test_forward_is_the_references(toy):
    config, params, tokens = toy
    at = list(range(tokens.shape[1]))
    want, lam = reference.forward(params, tokens[0], spec_of(config), at)
    assert errors(llama.forward(params, tokens, config)[0], want)["max"] < CLOSE["max"]
    got = llama.exit_gates(params, tokens, config)[:, 0]
    np.testing.assert_allclose(got, lam, atol=1e-5)
    # the gate does something here, and the distribution is one
    assert 0.05 < float(lam.min()) and float(lam.max()) < 0.95 and float(lam.std()) > 0.02
    p = llama.exit_distribution(got)
    np.testing.assert_allclose(p, reference.exit_distribution(lam), atol=1e-5)
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-5)
    np.testing.assert_allclose(p[1], lam[1] * (1 - lam[0]), atol=1e-5)
    np.testing.assert_allclose(p[2], (1 - lam[0]) * (1 - lam[1]), atol=1e-5)


def test_prefill_then_decode_through_the_cache_is_the_references_full_forward(toy, cached):
    config, params, tokens = toy
    logits, lam, cache = cached
    at = list(range(PROMPT - 1, PROMPT + STEPS))
    want, want_lam = reference.forward(params, tokens[0], spec_of(config), at)
    err = errors(logits, want)
    assert err["rms"] < CLOSE["rms"] and err["max"] < CLOSE["max"], err
    np.testing.assert_allclose(lam, want_lam, atol=1e-5)
    # every (pass, layer) wrote K and V of its own for the row's 17 positions
    k = np.asarray(cache["k"])
    assert k.shape[0] == 9 and (np.abs(k[:, 2, :PROMPT + STEPS]).sum((1, 2)) > 0).all()
    assert len({k[i, 2, 3].tobytes() for i in range(9)}) == 9
    assert not k[:, 2, PROMPT + STEPS:].any() and not k[:, 1, 1:].any()
    # 3 passes a (row, call): 1 row's prefill and STEPS steps of 4 rows
    calls = 1 + 4 * STEPS
    assert np.asarray(cache["loop_passes"]).tolist() == [3 * calls, calls]
    assert 0 < float(cache["loop_exit_mass"]) < calls


@pytest.mark.parametrize("piece, bent", [
    ("one pass fewer", dict(passes=2)),
    ("no final norm between the passes", dict(norm_between_passes=False)),
    ("pre-norm only: N2 and N4 dropped", dict(sandwich=False)),
    ("one cache layer a parameter layer, shared by the passes", dict(shared_cache_from=PROMPT)),
])
def test_each_piece_left_out_fails(toy, cached, piece, bent):
    config, params, tokens = toy
    logits, _lam, _cache = cached
    at = list(range(PROMPT - 1, PROMPT + STEPS))
    off, _ = reference.forward(params, tokens[0], spec_of(config, **bent), at)
    err = errors(logits, off)
    assert err["rms"] > 1000 * CLOSE["rms"] and err["max"] > 1000 * CLOSE["max"], (piece, err)
    if "shared" in piece:
        # only the cached path can get this one wrong: the prompt's last
        # position is still right (a prefill writes a pass before it reads it)
        assert errors(logits[:1], off[:1])["max"] < CLOSE["max"]
        assert errors(logits[1:], off[1:])["rms"] > 1000 * CLOSE["rms"]


def test_the_engine_serves_it_and_counts_the_passes(toy):
    from ray_tpu.serve.llm import LLMEngine

    config, params, tokens = toy
    engine = LLMEngine(params, config, max_slots=2, max_len=32)
    prompts = [tokens[0, :7].tolist(), tokens[0, 3:8].tolist(), tokens[0, 5:14].tolist()]

    async def main():
        async def one(prompt):
            return [t async for t in engine.stream(prompt, 5)]
        return await asyncio.gather(*map(one, prompts)), await engine.cache_counters()

    outs, counters = asyncio.run(main())
    for prompt, out in zip(prompts, outs):
        want = llama.generate(params, jnp.asarray([prompt]), config, max_new_tokens=5)
        assert out == np.asarray(want)[0, len(prompt):].tolist()
    assert counters["loop_row_steps"] == 3 + 2 * engine.decode_steps_total
    assert counters["loop_passes"] == 3 * counters["loop_row_steps"]
    assert 0 < counters["loop_exit_mass"] < counters["loop_row_steps"]
    # a token's cache is reckoned by cache layers: 9 here, not 3
    assert engine.kv_keys_visible_step % 9 == 0 and engine.kv_keys_visible_step > 0


def test_a_traced_step_carries_the_loop_totals(toy):
    """``loop_passes`` / ``loop_exit_mass`` on the engine's step span, as that
    step left the cache's totals (the running totals ``stats()`` reports)."""
    from ray_tpu.serve.llm import LLMEngine
    from ray_tpu.util import tracing

    config, params, tokens = toy
    engine = LLMEngine(params, config, max_slots=2, max_len=32)

    async def main():
        out = [t async for t in engine.stream(tokens[0, :5].tolist(), 6)]
        return out, await engine.cache_counters()

    was = tracing._enabled
    tracing.enable()
    tracing.clear()
    try:
        out, counters = asyncio.run(main())
    finally:
        if not was:
            tracing.disable()
    steps = sorted((s for s in tracing.spans()
                    if s["name"] == "llm.step" and s["attributes"].get("active")),
                   key=lambda s: s["start_ns"])
    assert len(out) == 6 and len(steps) == 5  # the prefill gives the first token
    row_steps = [s["attributes"]["loop_row_steps"] for s in steps]
    assert row_steps == [1 + 2 * (k + 1) for k in range(5)]   # a prefill, 2 rows a step
    assert all(s["attributes"]["loop_passes"] == 3 * n for s, n in zip(steps, row_steps))
    mass = [s["attributes"]["loop_exit_mass"] for s in steps]
    assert mass == sorted(mass) and mass[0] > 0
    assert mass[-1] == pytest.approx(counters["loop_exit_mass"])
    tracing.clear()


def test_a_one_pass_config_is_what_it_was():
    """InternLM2's tiny preset: the tree, the cache and the logits as the
    parent commit made them, and its programs with ONE loop (no scan of length
    one around the layer loop, no pass scope, no gate)."""
    config = LlamaConfig.tiny()
    assert (config.loop_passes, config.sandwich_norm, config.early_exit_threshold) == (1, False, 1)
    params = llama.init(jax.random.key(7), config)
    assert jax.tree.map(lambda a: a.shape, params) == {
        "blocks": {"attn_norm": (2, 64), "mlp_norm": (2, 64), "w_down": (2, 160, 64),
                   "w_gate": (2, 64, 160), "w_up": (2, 64, 160), "wk": (2, 64, 2, 16),
                   "wo": (2, 4, 16, 64), "wq": (2, 64, 4, 16), "wv": (2, 64, 2, 16)},
        "final_norm": (64,), "lm_head": (256, 64), "tok_embed": (256, 64)}
    cache = llama.init_cache(config, 2, 16)
    assert {k: (v.shape, str(v.dtype)) for k, v in cache.items()} == {
        "k": ((2, 2, 16, 32), "float32"), "v": ((2, 2, 16, 32), "float32")}
    tokens = jax.random.randint(jax.random.key(8), (2, 9), 0, config.vocab_size)
    logits = llama.forward(params, tokens, config)
    np.testing.assert_allclose(logits[0, -1, :4], [
        0.16948838531970978, -0.1933300495147705, -0.08187441527843475,
        0.42285847663879395], rtol=0, atol=2e-7)
    np.testing.assert_allclose(logits[1, 3, 100:103], [
        0.03628622740507126, -0.13631418347358704, 0.15504668653011322], rtol=0, atol=2e-7)
    out = llama.generate_kv(params, tokens, config, max_new_tokens=5)
    assert np.asarray(out)[:, 9:].tolist() == [[3, 11, 130, 16, 167], [216, 5, 89, 206, 185]]
    tok = jax.ShapeDtypeStruct((2,), jnp.int32)
    for text in (
        llama.decode_step_rowwise.lower(params, tok, cache, tok, config).as_text(),
        llama.prefill_into_slot.lower(
            params, jax.ShapeDtypeStruct((1, 8), jnp.int32), cache,
            jax.ShapeDtypeStruct((), jnp.int32), config).as_text(debug_info=True),
    ):
        assert text.count("stablehlo.while") == 1
        assert "stablehlo.case" not in text and "loop_pass" not in text
    # the looped program: ONE loop over (pass, layer) too, not passes unrolled
    loop = LlamaConfig.tiny_loop()
    looped = llama.decode_step_rowwise.lower(
        jax.eval_shape(lambda: llama.init(jax.random.key(0), loop)), tok,
        jax.eval_shape(lambda: llama.init_cache(loop, 2, 16)), tok, loop,
    ).as_text(debug_info=True)
    assert looped.count("stablehlo.while") == 1 and "loop_pass" in looped
    assert "loop_attn" in looped and looped.count("stablehlo.dot_general") < 12


class _Tensor:
    """What ``llama_params_from_hf`` asks of a checkpoint's tensor."""

    def __init__(self, a):
        self.a = np.asarray(a, np.float32)

    def detach(self):
        return self

    def cpu(self):
        return self

    def numpy(self):
        return self.a


def test_the_checkpoints_names_give_the_tree(toy):
    """``modeling_ouro.py``'s names -> the tree: the four norms, the gate."""
    config, params, tokens = toy
    b = params["blocks"]
    L, E, H, D = config.num_layers, config.embed_dim, config.num_heads, config.head_dim
    sd = {"model.embed_tokens.weight": params["tok_embed"], "lm_head.weight": params["lm_head"],
          "model.norm.weight": params["final_norm"],
          "model.early_exit_gate.weight": params["exit_gate"]["w"][None, :],
          "model.early_exit_gate.bias": params["exit_gate"]["b"]}
    for i in range(L):
        at = f"model.layers.{i}."
        sd.update({
            at + "input_layernorm.weight": b["attn_norm"][i],
            at + "input_layernorm_2.weight": b["attn_norm_out"][i],
            at + "post_attention_layernorm.weight": b["mlp_norm"][i],
            at + "post_attention_layernorm_2.weight": b["mlp_norm_out"][i],
            at + "self_attn.q_proj.weight": b["wq"][i].reshape(E, H * D).T,
            at + "self_attn.k_proj.weight": b["wk"][i].reshape(E, -1).T,
            at + "self_attn.v_proj.weight": b["wv"][i].reshape(E, -1).T,
            at + "self_attn.o_proj.weight": b["wo"][i].reshape(H * D, E).T,
            at + "mlp.gate_proj.weight": b["w_gate"][i].T,
            at + "mlp.up_proj.weight": b["w_up"][i].T,
            at + "mlp.down_proj.weight": b["w_down"][i].T,
        })
    published = dict(
        CATALOG_CONFIG, hidden_size=E, intermediate_size=config.mlp_dim, head_dim=D,
        num_attention_heads=H, num_key_value_heads=config.num_kv_heads,
        num_hidden_layers=L, vocab_size=config.vocab_size, total_ut_steps=3,
        max_position_embeddings=config.max_seq_len)
    model = type("Model", (), {
        "config": type("Cfg", (), published)(),
        "state_dict": lambda self: {k: _Tensor(v) for k, v in sd.items()}})()
    got, got_config = hf.llama_params_from_hf(model, dtype=jnp.float32, remat=False)
    assert got_config == config
    assert jax.tree.structure(got) == jax.tree.structure(params)
    for a, b_ in zip(jax.tree.leaves(got), jax.tree.leaves(params)):
        np.testing.assert_array_equal(a, b_)
    # and a norm that went to the wrong side would show: N2 is not N1
    assert not np.array_equal(got["blocks"]["attn_norm"], got["blocks"]["attn_norm_out"])
