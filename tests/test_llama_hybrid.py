"""A config whose layers are of two mixer kinds (``layer_types``:
Olmo-Hybrid's three gated-delta-rule layers to one of full attention) through
``models/llama.py``: the cache paths against the float32 reference, what the
cache holds for which layers, a slot's reuse, padding, the counters — and that
the configs without ``layer_types`` lower to the programs they lowered to
before the loop learned the pattern."""

import dataclasses
import functools
import hashlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import errors
from chipbench.reference import olmo_hybrid as reference
from ray_tpu.models import llama
from ray_tpu.models.llama import FULL, LINEAR, LlamaConfig

os.environ.setdefault("TPU_LOG_DIR", "disabled")

SLOTS, MAX_LEN = 4, 64
PROMPTS = (9, 16, 13, 7)            # chunks of four: three of them leave a rest
# float32 rounding through this block (a norm on every sub-layer's OUTPUT lifts
# whatever the sub-layer gave to unit size, its rounding with it); a wrong
# state, tail or row reads 1e-1 and more
FLOAT32 = {"rms": 3e-4, "max": 3e-3}


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig.tiny_hybrid()
    params = llama.init(jax.random.key(1), cfg)
    # N(0, 0.02) at 64 wide leaves every mixer a whisper beside the residual
    params = jax.tree.map(lambda a: a * 4 if a.ndim > 2 else a, params)
    return cfg, params


def prompt(n, seed=0):
    return np.random.default_rng([seed, n]).integers(0, 256, n).tolist()


def spec_of(cfg):
    return reference.Spec(cfg.layer_types, cfg.rms_eps, cfg.linear_neg_eigval)


@pytest.fixture(scope="module")
def served(model):
    """Four prompts prefilled into their rows, then 24 steps of the batch:
    {"cache", "seqs", "logits": per row (25, V)}."""
    cfg, params = model
    cache = llama.init_cache(cfg, SLOTS, MAX_LEN)
    seqs, rows = [prompt(n) for n in PROMPTS], []
    for b, ids in enumerate(seqs):
        logits, cache = llama.prefill_into_slot(
            params, jnp.asarray([ids], jnp.int32), cache, jnp.int32(b), cfg)
        rows.append([logits[0]])
    for _ in range(24):
        tok = jnp.stack([jnp.argmax(r[-1]) for r in rows]).astype(jnp.int32)
        pos = jnp.asarray([len(s) for s in seqs], jnp.int32)
        for b in range(SLOTS):
            seqs[b].append(int(tok[b]))
        logits, cache = llama.decode_step_rowwise(params, tok, cache, pos, cfg)
        for b in range(SLOTS):
            rows[b].append(logits[b])
    return {"cache": cache, "seqs": seqs, "logits": [np.asarray(jnp.stack(r)) for r in rows]}


@pytest.mark.parametrize("row", range(SLOTS))
def test_prefill_and_decode_through_the_cache_equal_the_reference(model, served, row):
    """Logits, not tokens: the prefill's row and each of 24 steps' against
    the reference's full forward over [prompt; tokens so far]."""
    cfg, params = model
    ids, got = served["seqs"][row], served["logits"][row]
    first = PROMPTS[row] - 1
    want = reference.forward(params, jnp.asarray(ids, jnp.int32), spec_of(cfg),
                             rows=list(range(first, first + len(got))))
    err = errors(got, want)
    assert err["rms"] < FLOAT32["rms"] and err["max"] < FLOAT32["max"], err


def test_the_no_cache_forward_walks_the_same_pattern(model, served):
    cfg, params = model
    ids = served["seqs"][2]
    got = llama.forward(params, jnp.asarray([ids], jnp.int32), cfg)[0]
    want = reference.forward(params, jnp.asarray(ids, jnp.int32), spec_of(cfg))
    err = errors(got, want)
    assert err["rms"] < FLOAT32["rms"] and err["max"] < FLOAT32["max"], err
    assert llama.generate(params, jnp.asarray([ids[:9]]), cfg, max_new_tokens=6).tolist() == (
        llama.generate_kv(params, jnp.asarray([ids[:9]]), cfg, max_new_tokens=6).tolist())


def test_the_cache_holds_each_kind_of_state_for_its_own_layers(model, served):
    cfg, _ = model
    cache = served["cache"]
    assert (cfg.num_layers, cfg.kv_layers, cfg.linear_layers) == (8, 2, 6)
    assert cfg.period == (LINEAR, LINEAR, LINEAR, FULL)
    assert set(cache) == {"k", "v", "gdn_state", "gdn_conv", "gdn_counts"}
    assert cache["k"].shape == cache["v"].shape == (2, SLOTS, MAX_LEN, 4 * 16)
    assert cache["gdn_state"].shape == (6, SLOTS, 8, 4 * 16)   # gated_delta.packed
    assert cache["gdn_state"].dtype == jnp.float32
    assert cache["gdn_conv"].shape == (6, 3, SLOTS, 4 * (2 * 8 + 16))
    # what the calls did, from their shapes: 4 prefills, 24 steps of 4 rows
    counts = {name: llama.wide_total(np.asarray(cache["gdn_counts"])[i])
              for i, name in enumerate(llama.GDN_COUNTS)}
    assert counts == {
        "gdn_rows_stepped": 24 * SLOTS * 6, "gdn_tokens_scanned": sum(PROMPTS) * 6,
        "gdn_tokens_padded": sum(-n % 4 for n in PROMPTS) * 6,
        "gdn_state_bytes_step": 24 * 2 * SLOTS * 6 * 4 * 8 * 16 * 4,
    }


@pytest.mark.parametrize("n", [11, 16])
def test_a_slot_that_served_another_request_starts_from_zero(model, served, n):
    """Row 0 holds the state of 9 + 24 tokens of another request: the next
    prefill's logits, and the state and tail it leaves, are a fresh cache's."""
    cfg, params = model
    ids = jnp.asarray([prompt(n, seed=5)], jnp.int32)
    used = jax.tree.map(jnp.copy, served["cache"])
    got, used = llama.prefill_into_slot(params, ids, used, jnp.int32(0), cfg)
    want, fresh = llama.prefill_into_slot(
        params, ids, llama.init_cache(cfg, SLOTS, MAX_LEN), jnp.int32(0), cfg)
    np.testing.assert_array_equal(got, want)
    for k, rows_axis in (("gdn_state", 1), ("gdn_conv", 2)):
        np.testing.assert_array_equal(used[k].take(0, axis=rows_axis),
                                      fresh[k].take(0, axis=rows_axis))
    # and the other rows keep theirs
    np.testing.assert_array_equal(used["gdn_state"][:, 1:], served["cache"]["gdn_state"][:, 1:])


@pytest.mark.parametrize("n", [9, 13, 3])
def test_padding_leaves_the_state_of_the_real_tokens(model, n):
    """A prompt that fills no whole chunk against the same prompt in chunks
    of ONE token, which pads nothing."""
    cfg, params = model
    ids = jnp.asarray([prompt(n, seed=2)], jnp.int32)
    by_one = dataclasses.replace(cfg, linear_chunk=1)
    got_logits, got = llama.prefill_into_slot(
        params, ids, llama.init_cache(cfg, SLOTS, MAX_LEN), jnp.int32(1), cfg)
    want_logits, want = llama.prefill_into_slot(
        params, ids, llama.init_cache(by_one, SLOTS, MAX_LEN), jnp.int32(1), by_one)
    # the first layer reads the embedding itself: to float32's last bits;
    # the later ones what the two orders of summation left of each other
    np.testing.assert_allclose(got["gdn_state"][0], want["gdn_state"][0], atol=2e-6)
    np.testing.assert_array_equal(got["gdn_conv"][0], want["gdn_conv"][0])
    np.testing.assert_allclose(got["gdn_state"], want["gdn_state"], atol=3e-4)
    np.testing.assert_allclose(got["gdn_conv"], want["gdn_conv"], atol=3e-4)
    np.testing.assert_allclose(got_logits, want_logits, atol=2e-3)
    assert float(jnp.abs(got["gdn_state"][:, 1]).max()) > 0
    assert float(jnp.abs(got["gdn_state"][:, 0]).max()) == 0     # the other rows untouched
    pads = llama.wide_total(np.asarray(got["gdn_counts"])[2])
    assert pads == (-n % 4) * 6 and llama.wide_total(np.asarray(want["gdn_counts"])[2]) == 0


@pytest.mark.parametrize("kw, error, why", [
    (dict(layer_types=(LINEAR, FULL)), ValueError, "each of the 8 layers"),
    (dict(layer_types=(LINEAR, "windowed") * 4), ValueError, "linear_attention"),
    (dict(layer_types=(LINEAR,) * 3 + (FULL,) * 5), None, None),  # one period of eight
    (dict(num_experts=4, experts_per_token=2, expert_dim=16), NotImplementedError, "experts"),
    (dict(mask_block=4), NotImplementedError, "block mask"),
])
def test_what_layer_types_goes_with(kw, error, why):
    if error is None:
        assert len(LlamaConfig.tiny_hybrid(**kw).period) == 8
        return
    with pytest.raises(error, match=why):
        LlamaConfig.tiny_hybrid(**kw)


def test_a_run_that_continues_a_rows_state_is_refused(model):
    cfg, params = model
    with pytest.raises(NotImplementedError, match="continues a row's state"):
        llama.forward_cached(params, jnp.zeros((SLOTS, 12), jnp.int32),
                             llama.init_cache(cfg, SLOTS, MAX_LEN), 0, cfg)


def test_the_published_shape_counts_to_the_parameter():
    full = LlamaConfig.olmo_hybrid_7b()
    assert (full.num_layers, full.linear_layers, full.kv_layers) == (32, 24, 8)
    assert full.rope_theta is None and full.post_norm and full.qk_norm is True
    assert llama.num_params(full) == 7_430_870_688
    assert llama.num_params(LlamaConfig.olmo_hybrid_7b(num_layers=16)) == 4_100_788_944


# ---- the configs that were there: their programs are the parent commit's ----

#: sha256 of the lowered text, taken on the parent commit (5925b7c) with
#: ``lowered`` below
PARENT = {
    ("tiny", "decode_step_rowwise"): "25631a6d7e31deaf",
    ("tiny", "prefill_into_slot"): "6d314943030ee47b",
    ("tiny_expert", "decode_step_rowwise"): "3083389db5360768",
    ("tiny_expert", "prefill_into_slot"): "4daf7ac7c9eca0ae",
}
EXISTING = {
    "tiny": lambda: LlamaConfig.tiny(),
    "tiny_expert": lambda: LlamaConfig.tiny(
        num_experts=4, experts_per_token=2, expert_dim=32, qk_norm=True),
}


def lowered(cfg, program, slots=4, max_len=64, prompt_len=16):
    params = jax.eval_shape(functools.partial(llama.init, config=cfg), jax.random.key(0))
    cache = jax.eval_shape(lambda: llama.init_cache(cfg, slots, max_len))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    if program == "decode_step_rowwise":
        return llama.decode_step_rowwise.lower(params, i32(slots), cache, i32(slots), cfg).as_text()
    return llama.prefill_into_slot.lower(params, i32(1, prompt_len), cache, i32(), cfg).as_text()


@pytest.mark.parametrize("name, program", sorted(PARENT))
def test_the_existing_configs_programs_are_unchanged(name, program):
    text = lowered(EXISTING[name](), program)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENT[(name, program)]


# ---- the served size, compiled for the chip without the chip ----------------

@pytest.fixture(scope="module")
def v5e_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / unknown topology
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def test_the_decode_step_updates_both_kinds_of_state_where_they_lie(
        v5e_chip, no_compile_cache, monkeypatch):
    """The 16-layer configuration's step at 32 x 2,560: the donated ``k``,
    ``v``, ``gdn_state`` and ``gdn_conv`` are the outputs' buffers, and the
    program's temporaries stay far under one layer's slab — no state is
    copied on its way through the loop, no weight sliced out a period at a
    time (1.4 GB of temporaries when the loop scanned over periods' slices)."""
    from ray_tpu.ops import gated_delta, kv_decode_attention

    monkeypatch.setattr(kv_decode_attention, "_interpret", lambda: False)
    monkeypatch.setattr(gated_delta, "_interpret", lambda: False)
    cfg = LlamaConfig.olmo_hybrid_7b(
        num_layers=16, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    slots, max_len = 32, 2560

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e_chip), tree)

    params = on_chip(jax.eval_shape(functools.partial(llama.init, config=cfg),
                                    jax.random.key(0)))
    cache = on_chip(jax.eval_shape(functools.partial(llama.init_cache, cfg, slots, max_len)))
    assert cache["k"].shape == (4, slots, max_len, 30 * 128)     # the full layers only
    rows = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=v5e_chip)
    compiled = llama.decode_step_rowwise.lower(params, rows, cache, rows, cfg).compile()
    mem = compiled.memory_analysis()
    state = sum(int(np.prod(cache[k].shape)) * cache[k].dtype.itemsize
                for k in ("k", "v", "gdn_state", "gdn_conv"))
    assert state == 5_909_053_440
    assert mem.alias_size_in_bytes >= state, mem
    assert mem.temp_size_in_bytes < 2**30, mem
    assert mem.temp_size_in_bytes < 64 * 2**20, mem
    text = compiled.as_text()
    assert "tpu_custom_call" in text            # the full layers' decode attention kernel
    assert text.count(" while(") == 1           # one loop over four periods
    # the linear layers' update is the kernel, three calls in the loop's body,
    # each on the WHOLE leaf and giving it back (operand 3 -> output 1), under
    # the scope the benchmark's reader finds it by
    calls = re.findall(r"%(gated_delta_step[.\d]*) = .*?custom-call\(.*", text)
    assert len(calls) == 3, calls
    for line in (ln for ln in text.splitlines() if " custom-call(" in ln and "%gated_delta_step" in ln):
        assert "f32[12,32,96,5760]" in line and "output_to_operand_aliasing={{1}: (3, {})}" in line
        assert re.search(r'op_name="[^"]*/gdn_step/', line), line
    from chipbench import gdn_trace

    found = gdn_trace.version(text)["scopes"]["gdn_step"]
    assert set(calls) <= set(found)
    # and nothing slices the layer out or writes it back
    assert not re.search(r"= f32\[12,32,96,5760\]\S* dynamic-update-slice\(", text)
