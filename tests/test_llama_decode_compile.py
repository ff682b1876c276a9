"""The serving decode step at real widths, compiled for the chip without
the chip (see tests/test_chip_compile.py for the kernels' version).

What is asserted is what PR 25 removed and a later change to
``models/llama.py`` could bring back unseen by any CPU test: K/V slabs
expanded across their query group (`jnp.repeat`, or an einsum the
compiler lowers to a broadcast) and whole-slab copies through the layer
loop.  Both show as temporaries of the compiled program — 3.36 GB
before, under 1 MB after — and as arrays of the expanded shape in its
text.  A compile that passes is not a chip run: nothing executes here.
"""

import dataclasses
import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import llama

# the compiler otherwise logs under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

CONFIG_FILE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "chipbench", "configs", "internlm2-7b-l16.json",
)


@pytest.fixture(scope="module")
def v5e_chip():
    """One device of a described v5e 2x2, or skip."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu / unknown topology
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """An entry written for a described chip cannot be read back without
    one, and the next run would warn about it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


OLMOE_FILE = os.path.join(os.path.dirname(CONFIG_FILE), "olmoe-1b-7b-l12.json")


@pytest.mark.parametrize("served_file, aliased_gib, shape", [
    pytest.param(CONFIG_FILE, 2, (32, 1024, 8, 4, 128), id="internlm2"),
    pytest.param(OLMOE_FILE, 3, (32, 1024, 16, 1, 128), id="olmoe"),
])
def test_decode_step_reads_the_cache_once(
        v5e_chip, no_compile_cache, monkeypatch, served_file, aliased_gib, shape):
    """The one-token step as the benchmark's serving cells run it.  Since
    PR 37 its attention is ``ops/kv_decode_attention.py``'s kernel on the
    carried cache: the program holds the kernel's custom call and no
    instruction makes, slices or copies a layer's K or V slab (the XLA
    body's two fetches of the whole slab into fast memory, ``S(1)``, were
    67 MB a layer whatever the rows' ``pos``: 16.6% of InternLM2's step,
    20% of OLMoE's; ledger PR 36)."""
    from chipbench.jobs.serve_llm import llama_config
    from chipbench.jobs.serve_moe import moe_config
    from ray_tpu.ops import grouped_matmul, kv_decode_attention

    monkeypatch.setattr(grouped_matmul, "implementation", lambda: "pallas_gmm")
    # jax's default backend is the CPU here; compile the kernel, as the chip does
    monkeypatch.setattr(kv_decode_attention, "_interpret", lambda: False)
    with open(served_file) as f:
        served = json.load(f)
    config = (moe_config if served_file == OLMOE_FILE else llama_config)(served)
    slots, max_len = served["serving"]["max_slots"], served["serving"]["max_len"]

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e_chip),
            tree,
        )

    params = on_chip(jax.eval_shape(
        functools.partial(llama.init, config=config), jax.random.key(0)
    ))
    cache = on_chip(jax.eval_shape(
        functools.partial(llama.init_cache, config, slots, max_len)
    ))
    rows = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=v5e_chip)
    compiled = llama.decode_step_rowwise.lower(
        params, rows, cache, rows, config
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2**20, mem
    # the donated cache is updated in place, not returned as a fresh one
    assert mem.alias_size_in_bytes >= aliased_gib * 2**30, mem
    text = compiled.as_text()
    kv, g, d = config.num_kv_heads, config.q_per_kv, config.head_dim
    assert (slots, max_len, kv, g, d) == shape
    assert kv_decode_attention.implementation(max_len, d) == "streamed"
    assert re.findall(r"%kv_decode\S* = \S+ custom-call\(.*tpu_custom_call", text)
    for expanded in (f"[{slots},{max_len},{kv},{g},{d}]",
                     f"[{slots},{max_len},{kv * g},{d}]"):
        assert expanded not in text, f"a K/V slab is expanded to {expanded}"
    # a layer's slab, as the cache stores it or as heads: never an
    # instruction's result
    for slab in (f"{slots},{max_len},{kv * d}]", f"{slots},{max_len},{kv},{d}]"):
        made = re.findall(rf"= bf16\[(?:1,)?{re.escape(slab)}\S* \S+\(", text)
        assert not made, f"a layer's slab is made in front of the attention: {made}"
    # nor its float32 scores, nor a (rows, queries, keys) mask
    assert f"f32[{slots},{kv},{g},1,{max_len}]" not in text
    assert f"pred[{slots},1,{max_len}]" not in text


def test_expert_decode_step_routes_without_a_dense_intermediate(
        v5e_chip, no_compile_cache, monkeypatch):
    """OLMoE's decode step at the published widths (12 layers), compiled
    for the chip with the kernel the chip runs.  What a later edit of
    ``_ffn`` could bring back unseen by any CPU test: every expert
    applied to every row and masked ("dense" routing: an array of rows x
    experts x width), or one layer's expert tensors copied out of the
    stacked weights before the kernel reads them (805 MB a layer; the
    program had 270 MB of temporaries while it sliced them, 1.9 MB
    since)."""
    from chipbench.jobs.serve_moe import moe_config
    from ray_tpu.ops import grouped_matmul, kv_decode_attention

    # jax's default backend is the CPU here; the chip's body is what the
    # replica traces on the chip and what is compiled for it
    monkeypatch.setattr(grouped_matmul, "implementation", lambda: "pallas_gmm")
    monkeypatch.setattr(kv_decode_attention, "_interpret", lambda: False)
    with open(OLMOE_FILE) as f:
        served = json.load(f)
    config = moe_config(served)
    slots, max_len = served["serving"]["max_slots"], served["serving"]["max_len"]

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e_chip),
            tree,
        )

    params = on_chip(jax.eval_shape(
        functools.partial(llama.init, config=config), jax.random.key(0)
    ))
    cache = on_chip(jax.eval_shape(
        functools.partial(llama.init_cache, config, slots, max_len)
    ))
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    assert 10.4e9 < weights < 10.6e9  # 5.24 B parameters in bf16
    rows = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=v5e_chip)
    compiled = llama.decode_step_rowwise.lower(
        params, rows, cache, rows, config
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2**20, mem
    # cache and counters are updated in place
    assert mem.alias_size_in_bytes >= 3 * 2**30, mem
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "%gmm." in text
    x, k, e, m = (config.num_experts, config.experts_per_token,
                  config.embed_dim, config.expert_dim)
    assert (slots * k, x, e, m) == (256, 64, 2048, 1024)
    for n in (slots, slots * k):
        for width in (e, m, 2 * m):
            for dense in (f"[{n},{x},{width}]", f"[{x},{n},{width}]"):
                assert dense not in text, f"every expert on every row: {dense}"
    # no copy of a layer's experts out of the stacked tensors
    for sliced in (f"bf16[{x},{e},{m}]", f"bf16[{x},{m},{e}]",
                   f"bf16[1,{x},{e},{m}]", f"bf16[1,{x},{m},{e}]"):
        assert sliced not in text, f"one layer's experts are copied: {sliced}"


@pytest.mark.parametrize("prompt_len", [256, 768])
@pytest.mark.parametrize("served_file, aliased_gib", [
    pytest.param(CONFIG_FILE, 2, id="internlm2"),
    pytest.param(OLMOE_FILE, 3, id="olmoe"),
])
def test_prefill_writes_the_donated_cache_in_place(
        v5e_chip, no_compile_cache, monkeypatch, served_file, aliased_gib,
        prompt_len):
    """``prefill_into_slot`` at the serving cells' widths carries the
    engine's WHOLE cache through its layer loop (one cached step since
    PR 28), so one stray copy of that cache is 2 GiB (InternLM2) or no
    program at all (OLMoE: 13.7 GB live of 16).  Seen at compile time
    while the step was merged: the run written before the slab is read,
    or written as a scatter of windows, and XLA copies K and V whole
    (2,050 MiB of temporaries).  The program that sliced a one-row
    cache out and wrote it back had 128-268 MiB."""
    from chipbench.jobs.serve_llm import llama_config
    from chipbench.jobs.serve_moe import moe_config
    from ray_tpu.ops import grouped_matmul

    monkeypatch.setattr(grouped_matmul, "implementation", lambda: "pallas_gmm")
    with open(served_file) as f:
        served = json.load(f)
    config = (moe_config if served_file == OLMOE_FILE else llama_config)(served)
    slots, max_len = served["serving"]["max_slots"], served["serving"]["max_len"]

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e_chip),
            tree,
        )

    params = on_chip(jax.eval_shape(
        functools.partial(llama.init, config=config), jax.random.key(0)
    ))
    cache = on_chip(jax.eval_shape(
        functools.partial(llama.init_cache, config, slots, max_len)
    ))
    compiled = llama.prefill_into_slot.lower(
        params,
        jax.ShapeDtypeStruct((1, prompt_len), jnp.int32, sharding=v5e_chip),
        cache,
        jax.ShapeDtypeStruct((), jnp.int32, sharding=v5e_chip),
        config,
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= aliased_gib * 2**30, mem
    assert mem.temp_size_in_bytes < 64 * 2**20, mem
    # the prompt's K and V go in as one block each, not token by token
    whole = "bf16[{}]".format(",".join(map(str, cache["k"].shape)))
    text = compiled.as_text()

    def making_a_cache(op):  # instructions whose result is a whole K or V
        return re.findall(rf"{re.escape(whole)}\S* {op}\(", text)

    assert len(making_a_cache("dynamic-update-slice")) == 2
    assert not making_a_cache("scatter")


GLM_FILE = os.path.join(os.path.dirname(CONFIG_FILE), "glm-5-ep16-l6.json")


@pytest.mark.parametrize("program", ["decode", "prefill4096"])
def test_latent_cache_is_never_copied_nor_expanded(
        v5e_chip, no_compile_cache, monkeypatch, program):
    """GLM-5's two programs at the published widths (``glm-5-ep16-l6``:
    12.5 GB of weights and cache live of 16), compiled for the chip.
    What was seen at compile time while they were written (PR 30) and a
    later edit could bring back unseen by any CPU test: a latent row of
    576 values (no multiple of the 128 lanes) made the chip's default
    layout put the positions minor-most, and every decode step copied
    the whole 2.3 GB cache into a row-major layout and back (2.52 GB of
    temporaries; 88 MB since the row is 640); a prefill that wrote its
    rows layer by layer inside the layer loop carried the cache through
    it transposed and copied all 3 GB in and out (4.7 GB of temporaries
    at 4,096 tokens; 1.3 GB since the rows are written once, after the
    loop).  And what the path is for: the decode step attends to 2,048
    latent rows a row where they lie in the cache, through one Pallas
    kernel (PR 31: the 32 x 2,048 rows were gathered before, 84 MB a
    layer written and read back twice), and never makes keys or values
    of the cache, nor a slice of it in front of the kernel (419 MB a
    layer)."""
    from chipbench.jobs.serve_dsa import dsa_config
    from ray_tpu.ops import grouped_matmul, latent_decode_attention

    monkeypatch.setattr(grouped_matmul, "implementation", lambda: "pallas_gmm")
    # the default backend is the CPU here: compile the kernel, as the chip does
    monkeypatch.setattr(latent_decode_attention, "_interpret", lambda: False)
    with open(GLM_FILE) as f:
        served = json.load(f)
    config = dsa_config(served)
    slots, max_len = served["serving"]["max_slots"], served["serving"]["max_len"]

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e_chip),
            tree,
        )

    params = on_chip(jax.eval_shape(
        functools.partial(llama.init, config=config), jax.random.key(0)
    ))
    cache = on_chip(jax.eval_shape(
        functools.partial(llama.init_cache, config, slots, max_len)
    ))
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    assert 9.4e9 < weights < 9.5e9           # 4.73 B parameters in bf16
    held = sum(a.size * a.dtype.itemsize for k, a in cache.items() if k in ("ckv", "ik"))
    assert held == 32 * 10240 * 6 * (640 + 128) * 2   # 3.02 GB
    rows = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=v5e_chip)
    if program == "decode":
        compiled = llama.decode_step_rowwise.lower(
            params, rows, cache, rows, config).compile()
        # 4.06 MB before the kernel walked a list of live items with its own
        # ring of copies (PR 33), 4.22 MB since: the ring is fast memory
        limit = 5 * 2**20
    else:
        compiled = llama.prefill_into_slot.lower(
            params, jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=v5e_chip),
            cache, jax.ShapeDtypeStruct((), jnp.int32, sharding=v5e_chip), config,
        ).compile()
        limit = 1536 * 2**20
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= held, mem          # both written in place
    assert mem.temp_size_in_bytes < limit, mem
    text = compiled.as_text()
    whole = "bf16[{}]".format(",".join(map(str, cache["ckv"].shape)))
    assert not re.findall(rf"{re.escape(whole)}\S* copy\(", text), "the cache is copied"
    assert "{2,3,1,0" not in "".join(re.findall(rf"{re.escape(whole)}\S*", text))
    if program == "decode":
        # the kernel reads the cache itself: no gathered rows, no layer's
        # slab cut out for it; no key or value of the cache's length per
        # head (64 heads x 192 / 256 over 10,240)
        assert latent_decode_attention.implementation(max_len) == "streamed"
        calls = re.findall(r"%latent_decode\S* = \S+ custom-call\(.*tpu_custom_call.*", text)
        # the dense layer's and the expert layers' loop body: one kernel
        # each, handed the whole cache where it lies
        assert len(calls) == 2 and all(whole in c for c in calls), calls
        for made in ("bf16[65536,640]", "bf16[32,2048,640]",
                     "bf16[32,10240,640]", "bf16[1,32,10240,640]"):
            assert made not in text, f"latent rows are copied out of the cache: {made}"
        for expanded in ("10240,64,192]", "10240,64,256]", "64,10240,192]", "64,10240,256]"):
            assert expanded not in text, f"keys or values expanded over the cache: {expanded}"


JOYAI_FILE = os.path.join(
    os.path.dirname(GLM_FILE), "joyai-llm-flash-ep32.json"
)


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_joyais_speculative_programs_fit_and_stream_a_rows_blocks_once(
        v5e_chip, monkeypatch, program):
    """JoyAI-LLM-Flash's two DRAFTING programs (``models/mtp.py``) at the
    served shapes (32 slots x 4,096 positions, all 40 layers and the
    module: 12.7 GB of weights and cache live of 16), compiled for the
    chip: the 6.9 GB cache is written in place by the module's block and by
    the 40 layers of the verification, never copied and never laid out
    otherwise; a step's temporaries are megabytes; and each of the 41
    attention calls of a step is ONE ``latent_verify`` kernel over both
    queries of a row (2 x 32 heads as 64 query rows), never keys or values
    of the cache's length."""
    from chipbench.jobs.serve_mtp import joyai_config
    from ray_tpu.models import mtp
    from ray_tpu.ops import grouped_matmul, latent_decode_attention

    monkeypatch.setattr(grouped_matmul, "implementation", lambda: "pallas_gmm")
    monkeypatch.setattr(latent_decode_attention, "_interpret", lambda: False)
    with open(JOYAI_FILE) as f:
        served = json.load(f)
    config = joyai_config(served)
    slots, max_len = served["serving"]["max_slots"], served["serving"]["max_len"]

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e_chip),
            tree,
        )

    params = on_chip(jax.eval_shape(
        functools.partial(llama.init, config=config), jax.random.key(0)
    ))
    cache = on_chip(jax.eval_shape(
        functools.partial(llama.init_cache, config, slots, max_len)
    ))
    state = on_chip(jax.eval_shape(functools.partial(mtp.init_state, config, slots)))
    key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=v5e_chip)
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    assert weights == 2 * 2_918_719_488          # 5.84 GB
    held = cache["ckv"].size * 2
    assert held == 41 * 32 * 4096 * 640 * 2      # 6.88 GB
    if program == "decode":
        compiled = mtp.decode_step_rowwise.lower(
            params, state, cache, key, config, 1.0).compile()
        # 8.60 MB with the kernel on a (32 x 4) grid, 8.57 MB since it walks
        # a list of live items (PR 33): the ring of copies is fast memory
        # and costs the device's no byte, so no slot
        limit = 9 * 2**20
    else:
        compiled = mtp.prefill_into_slot.lower(
            params, jax.ShapeDtypeStruct((1, 1536), jnp.int32, sharding=v5e_chip),
            cache, scalar, state, key, scalar, scalar, config, 1.0,
        ).compile()
        limit = 512 * 2**20
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= held, mem          # written in place
    assert mem.temp_size_in_bytes < limit, mem
    assert weights + held + mem.temp_size_in_bytes < 14e9
    text = compiled.as_text()
    whole = "bf16[{}]".format(",".join(map(str, cache["ckv"].shape)))
    assert not re.findall(rf"{re.escape(whole)}\S* copy\(", text), "the cache is copied"
    assert "{2,3,1,0" not in "".join(re.findall(rf"{re.escape(whole)}\S*", text))
    if program == "decode":
        assert latent_decode_attention.implementation(max_len) == "streamed"
        # the module's block and the two parameter stacks' loop bodies (the
        # dense layer, the 39 expert layers): one kernel each, 64 query rows
        # a grid step, the cache handed over whole and where it lies
        calls = re.findall(r"%latent_verify\S* = (\S+ custom-call\(.*tpu_custom_call.*)", text)
        assert len(calls) == 3 and all(
            c.startswith("bf16[32,64,512]") and whole in c for c in calls), calls
        for made in ("bf16[32,4096,640]", "bf16[1,32,4096,640]"):
            assert made not in text, f"a layer's slab is cut out of the cache: {made}"
        for expanded in ("4096,32,192]", "4096,32,128]", "32,4096,192]", "32,4096,128]"):
            assert expanded not in text, f"keys or values expanded over the cache: {expanded}"


SDAR_FILE = os.path.join(os.path.dirname(CONFIG_FILE), "sdar-30b-a3b-ep8.json")


def test_block_diffusion_step_copies_no_cache_slab(
        v5e_chip, no_compile_cache, monkeypatch):
    """SDAR's step program at the published widths (``sdar-30b-a3b-ep8``:
    14.07 GB of weights and cache live of 16), compiled for the chip.  The
    step brings FOUR tokens for every row.  Written through the
    run-of-tokens branch of ``_write_and_read`` (slab first, the run put into
    the copy, the run written into the cache apart) XLA copies K and V WHOLE,
    2.25 GB each, and the program does not fit the chip (17.6 GB of 15.75;
    compile-only, PR 36).  Written in place, both caches are aliased.  Until
    PR 37 XLA's attention then made 85 MB of temporaries a layer: ONE layer's
    slab copied into a layout with the positions on the sublanes (50 MB, K's
    and then V's: 31.6% of the step's busy time) and the float32 scores (25
    MB).  Since PR 37 the attention is ``ops/kv_decode_attention.py``'s
    kernel on the carried cache, under ``block_attn`` where the benchmark's
    trace reader looks for it, and the program makes no slab, no score array
    and no mask: 6.3 MB of temporaries."""
    from chipbench.jobs.serve_diffusion import sdar_config
    from ray_tpu.models import block_diffusion
    from chipbench import diffusion_trace
    from ray_tpu.ops import grouped_matmul, kv_decode_attention
    from ray_tpu.serve.llm import LLMEngine  # noqa: F401 — the options' one reader

    monkeypatch.setattr(grouped_matmul, "implementation", lambda: "pallas_gmm")
    monkeypatch.setattr(kv_decode_attention, "_interpret", lambda: False)
    with open(SDAR_FILE) as f:
        served = json.load(f)
    serving = served["serving"]
    config = dataclasses.replace(
        sdar_config(served), mask_block=serving["diffusion_block"])
    slots, max_len = serving["max_slots"], serving["max_len"]
    settings = block_diffusion.Settings(
        block=serving["diffusion_block"], denoising_steps=serving["denoising_steps"],
        threshold=serving["confidence_threshold"], mask_id=config.vocab_size - 1,
    )

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e_chip),
            tree,
        )

    params = on_chip(jax.eval_shape(
        functools.partial(llama.init, config=config), jax.random.key(0)
    ))
    cache = on_chip(jax.eval_shape(
        functools.partial(llama.init_cache, config, slots, max_len)
    ))
    state = on_chip(jax.eval_shape(
        functools.partial(block_diffusion.init_state, config, slots, settings)
    ))
    key = on_chip(jax.eval_shape(lambda: jax.random.key(0)))
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    assert weights == 2 * 4_620_433_408  # the configuration's reckoning, bf16
    compiled = block_diffusion.decode_step_rowwise.lower(
        params, state, cache, key, config, 1.0, settings
    ).compile()
    mem = compiled.memory_analysis()
    # K and V (2 x 2.42 GB) are updated in place
    assert mem.alias_size_in_bytes >= 4.8e9, mem
    assert mem.temp_size_in_bytes < 16 * 2**20, mem
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "%gmm." in text
    # the attention kernel, where ``chipbench/diffusion_trace.py`` finds it
    under_block_attn = diffusion_trace.version(text)["scopes"]["block_attn"]
    assert [n for n in under_block_attn if n.startswith("kv_decode")], under_block_attn
    kv, d = config.num_kv_heads, config.head_dim
    assert (slots, max_len, kv, d) == (32, 1536, 4, 128)
    # no instruction makes a new K or V cache: the block's rows go into the
    # carried one (a scatter of 128 rows)
    whole = "bf16[{}]".format(",".join(map(str, cache["k"].shape)))
    assert not re.findall(rf"= {re.escape(whole)}\S* copy\(", text)
    # nor a layer's slab, as the cache stores it or as heads, nor the float32
    # scores of a block's four queries, nor their mask
    g = config.q_per_kv
    for slab in (f"{slots},{max_len},{kv * d}]", f"{slots},{max_len},{kv},{d}]"):
        made = re.findall(rf"= bf16\[(?:1,)?{re.escape(slab)}\S* \S+\(", text)
        assert not made, f"a layer's slab is made in front of the attention: {made}"
    assert f"f32[{slots},{kv},{g},4,{max_len}]" not in text
    assert f"pred[{slots},4,{max_len}]" not in text
