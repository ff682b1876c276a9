"""The float32 references against the program's models, at tiny size
on the CPU (on the chip the jobs make the same comparison at the
published widths, outside the timed window)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import contract, flops
from chipbench.reference import errors, within
from chipbench.reference import gpt2 as ref_gpt2
from chipbench.reference import llama as ref_llama
from ray_tpu.models import gpt2, llama


def tolerance(config):
    with open(os.path.join(contract.ROOT, "chipbench", "configs", config + ".json")) as f:
        return json.load(f)["reference_tolerance"]


def test_gpt2_forward_matches_the_reference_in_float32():
    cfg = gpt2.GPTConfig.tiny(dtype=jnp.float32, remat=False)
    params = gpt2.init(jax.random.key(3), cfg)
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 64), dtype=np.int32)
    system = gpt2.forward(params, tokens, cfg)[0]
    reference = ref_gpt2.forward(params, tokens[0], cfg.num_heads)
    assert within(errors(system, reference), ref_gpt2.FLOAT32_TOLERANCE)


@pytest.mark.parametrize("config", ["gpt2-medium", "gpt2-xl"])
def test_gpt2_in_bfloat16_is_inside_its_tolerance_and_a_dropped_block_is_not(config):
    tol = tolerance(config)
    cfg = gpt2.GPTConfig.tiny(remat=False)  # bf16 compute, as served
    params = gpt2.init(jax.random.key(3), cfg)
    tokens = np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 64), dtype=np.int32)
    reference = ref_gpt2.forward(params, tokens[0], cfg.num_heads)
    system = gpt2.forward(params, tokens, cfg)[0]
    assert within(errors(system, reference), tol)
    fewer = dict(params, blocks=jax.tree.map(lambda a: a[:1], params["blocks"]))
    broken = gpt2.forward(fewer, tokens, cfg)[0]
    assert not within(errors(broken, reference), tol)


def test_llama_prefill_then_decode_through_the_cache_matches_the_reference():
    cfg = llama.LlamaConfig.tiny(rope_theta=1e6)  # float32, GQA 4Q/2KV
    params = llama.init(jax.random.key(5), cfg)
    seq = np.random.default_rng(1).integers(0, cfg.vocab_size, 24).tolist()
    slots, slot = 3, 1
    cache = llama.init_cache(cfg, slots, 64)
    logits, cache = llama.prefill_into_slot(
        params, jnp.asarray([seq], jnp.int32), cache, jnp.int32(slot), cfg)
    system = [logits[0]]
    for _ in range(2):
        seq.append(int(jnp.argmax(system[-1])))
        tokens = np.zeros((slots,), np.int32)
        pos = np.zeros((slots,), np.int32)
        tokens[slot], pos[slot] = seq[-1], len(seq) - 1
        logits, cache = llama.decode_step_rowwise(
            params, jnp.asarray(tokens), cache, jnp.asarray(pos), cfg)
        system.append(logits[slot])
    reference = ref_llama.forward(
        params, jnp.asarray(seq, jnp.int32), cfg.rope_theta, cfg.rms_eps,
        positions=[23, 24, 25], head_rows=64)
    assert reference.shape == (3, cfg.vocab_size)
    assert within(errors(jnp.stack(system), reference), ref_llama.FLOAT32_TOLERANCE)
    # a wrong position (the reference is not fed the last token) is far outside
    shifted = ref_llama.forward(
        params, jnp.asarray(seq, jnp.int32), cfg.rope_theta, cfg.rms_eps,
        positions=[22, 23, 24], head_rows=64)
    wrong = errors(jnp.stack(system), shifted)
    assert wrong["rms"] > 0.5 and not within(wrong, tolerance("internlm2-7b-l16"))


def test_flops_per_token_by_hand_and_against_the_programs_count():
    tiny = {"n_embd": 64, "n_layer": 2, "vocab_size": 256}
    # 256*64 + 2*(12*64*64 + 13*64) + 2*64 = 16384 + 99968 + 128
    assert flops.gpt2_params(tiny) == 116480
    assert flops.gpt2_train_flops_per_token(tiny, 128) == 6 * 116480 + 12 * 2 * 64 * 128
    cfg = gpt2.GPTConfig.tiny()
    assert flops.gpt2_train_flops_per_token(tiny, 128) == gpt2.flops_per_token(cfg, 128)
    medium = {"n_embd": 1024, "n_layer": 24, "vocab_size": 50304}
    assert flops.gpt2_train_flops_per_token(medium, 1024) == 2424926208.0  # 2.42 GFLOP
