"""``LLMEngine`` / ``LlamaDeployment`` over a config with linear-attention
layers: a slot holds a recurrent state that no length describes, so a request
that takes over a slot must start it from zero; more requests than slots,
prompts that fill no whole chunk, one step in flight ahead — and every
request gets the tokens of the full recompute.  The three engine kinds that
take positions back are refused in words; ``stats()`` carries the recurrent
layers' counters; ``serve.run`` of the deployment streams the same tokens."""

import asyncio
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops import gated_delta
from ray_tpu.serve.llm import LlamaDeployment, LLMEngine

SLOTS, MAX_LEN = 3, 48
PROMPTS = [9, 16, 13, 7, 11, 16, 5]        # seven requests on three slots
BUDGETS = [12, 5, 9, 1, 14, 0, 8]


def prompt(n, seed):
    return np.random.default_rng([seed, n]).integers(0, 256, n).tolist()


@pytest.fixture(scope="module")
def replica():
    return LlamaDeployment.func_or_class(
        config=LlamaConfig.tiny_hybrid(), max_slots=SLOTS, max_len=MAX_LEN, seed=4)


@pytest.fixture(scope="module")
def streamed(replica):
    engine = replica.engine

    async def one(i):
        return [t async for t in engine.stream(prompt(PROMPTS[i], i), BUDGETS[i])]

    async def run():
        before = await replica.stats()
        got = await asyncio.gather(*(one(i) for i in range(len(PROMPTS))))
        return before, got, await replica.stats()

    return asyncio.run(run())


@pytest.mark.parametrize("i", range(len(PROMPTS)))
def test_a_request_gets_the_full_recomputes_tokens_whatever_its_slot_held(
        replica, streamed, i):
    _, got, _ = streamed
    want = llama.generate(
        replica.engine.params, jnp.asarray([prompt(PROMPTS[i], i)], jnp.int32),
        replica.config, max_new_tokens=BUDGETS[i])
    assert got[i] == np.asarray(want[0, PROMPTS[i]:]).tolist()
    assert len(got[i]) == BUDGETS[i]


def test_stats_carry_the_recurrent_layers_counters(replica, streamed):
    before, _, stats = streamed
    cfg, eng = replica.config, replica.engine
    assert all(before[k] == 0 for k in llama.GDN_COUNTS)
    lin = cfg.linear_layers
    steps = stats["decode_steps_total"]
    admitted = [n for n, b in zip(PROMPTS, BUDGETS) if b > 0]
    assert stats["gdn_rows_stepped"] == steps * SLOTS * lin and steps > 0
    assert stats["gdn_tokens_scanned"] == sum(admitted) * lin
    assert stats["gdn_tokens_padded"] == sum(-n % cfg.linear_chunk for n in admitted) * lin
    state = 4 * 8 * 16 * 4
    assert stats["gdn_state_bytes_step"] == 2 * steps * SLOTS * lin * state
    # K/V counters over the FULL layers only: 2 of the 8
    assert stats["kv_keys_read_step"] == steps * SLOTS * MAX_LEN * cfg.kv_layers
    assert 0 < stats["kv_keys_visible_step"] < stats["kv_keys_read_step"]
    assert set(stats["cache_bytes"]) == {"k", "v", "gdn_state", "gdn_conv", "gdn_counts"}
    assert stats["cache_bytes"]["k"] == cfg.kv_layers * SLOTS * MAX_LEN * 4 * 16 * 4
    # a (d_k, heads x d_v) float32 matrix a row and linear layer, no lane of padding
    assert eng.cache["gdn_state"].shape == (lin, SLOTS, 8, 4 * 16)
    assert stats["cache_bytes"]["gdn_state"] == lin * SLOTS * state
    # four heads of 16 are half a lane tile: the toy's update is XLA's body
    assert stats["gated_delta_step"] == "xla"
    # and a decay a head takes the chunked rule's ``jax.numpy`` body whatever
    # the widths: Olmo-Hybrid's served shape by its numbers, no engine built
    assert stats["gated_delta_scan"] == "xla"
    assert gated_delta.scan_implementation(30, 96, 192, 64, False) == "xla"
    assert eng.slots == [None] * SLOTS and eng.steps_launched_ahead_total > 0
    assert stats["programs"]["prefill_into_slot"] >= len(set(admitted))


@pytest.mark.parametrize("kw, why", [
    (dict(speculative_tokens=1), "speculative_tokens does not go with .*rejected draft"),
    (dict(diffusion_block=4), "diffusion_block does not go with .*positions again"),
    (dict(sliding_window=8, max_prompt_len=16), "sliding_window does not go with .*rolling cache"),
])
def test_the_engine_refuses_what_takes_positions_back(replica, kw, why):
    cfg = replica.config
    kw = dict(kw)
    if "sliding_window" in kw:
        cfg = dataclasses.replace(cfg, sliding_window=kw.pop("sliding_window"))
    with pytest.raises(ValueError, match=why):
        LLMEngine(replica.engine.params, cfg, max_slots=2, max_len=32, **kw)


def test_serve_run_streams_the_same_tokens():
    """The normal path from its entry point: ``serve.run`` of the
    deployment, requests over the handle's streaming path."""
    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init(num_cpus=2, num_tpus=0)
    try:
        cfg = LlamaConfig.tiny_hybrid()
        h = serve.run(
            LlamaDeployment.options(name="hybrid").bind(
                config=cfg, max_slots=2, max_len=MAX_LEN, seed=4),
            name="hybrid_app", route_prefix=None)
        got = [
            list(h.options(method_name="generate", stream=True).remote(
                prompt(PROMPTS[i], i), max_new_tokens=6))
            for i in (0, 2, 3)          # three requests through two slots
        ]
        params = llama.init(__import__("jax").random.key(4), cfg)
        for i, toks in zip((0, 2, 3), got):
            want = llama.generate(params, jnp.asarray([prompt(PROMPTS[i], i)], jnp.int32),
                                  cfg, max_new_tokens=6)
            assert toks == np.asarray(want[0, PROMPTS[i]:]).tolist()
        stats = h.options(method_name="stats").remote().result(timeout_s=60)
        assert stats["gdn_tokens_scanned"] == (9 + 13 + 7) * cfg.linear_layers
        serve.delete("hybrid_app")
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
