"""The main path's kernels, compiled for the chip without the chip.

libtpu's compiler is installed wherever the tests run, and it compiles
for a TPU that is described, not attached (`topologies.get_topology_desc`).
That catches what Pallas interpret mode cannot — a block the Mosaic
compiler refuses for its tiling, a kernel that wants more VMEM than it
may have — at about a second per kernel and no chip time.  A compile
that passes is not a chip run: nothing executes here.

The shapes are the two head layouts the repo trains and serves at
width: GPT-2-124M at B=8 x S=1024 (12 heads x 64) and the Llama family
at one 2048-token sequence (32 heads x 128); and, whole, the two
training cells' step programs (GPT-2 medium on one chip, GPT-2 XL on
the four of the 2x2 under `fsdp=4`): the forward kernel once a layer,
and XL inside its chips' memory; and the latent configs' prefill
attention kernel at GLM-5's 64 heads of 256 | 256 and LongCat's (64) and
JoyAI's (32) of 192 | 128, and GLM-5's top-2,048 selection kernel at the
six block shapes the cell sends.
"""

import json
import math
import os
import re
from collections import Counter

import jax
import jax.numpy as jnp
import optax
import pytest

from ray_tpu.models import gpt2
from ray_tpu.ops import flash_attention as fa
from ray_tpu.ops import gated_delta as gd
from ray_tpu.ops import latent_prefill_attention as lpa
from ray_tpu.ops import topk_mask as tkm
from ray_tpu.parallel import mesh as mesh_mod
from ray_tpu.parallel import spmd

# the compiler otherwise logs under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

#: (B, H, S, D)
HEAD_SHAPES = {
    "gpt2_124m": (8, 12, 1024, 64),
    "gpt2_xl": (8, 25, 1024, 64),       # an odd count of 64-wide heads
    "llama_7b": (1, 32, 2048, 128),
    "olmo_hybrid": (1, 30, 2048, 128),  # the forward alone is served
}


@pytest.fixture(scope="module")
def v5e_2x2():
    """The four devices of a described v5e 2x2, or skip."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu / unknown topology
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")
    return list(topo.devices)


@pytest.fixture(scope="module")
def v5e_chip(v5e_2x2):
    """One of them."""
    return jax.sharding.SingleDeviceSharding(v5e_2x2[0])


@pytest.fixture
def compiled_not_interpreted(monkeypatch):
    """Steer the kernel's mode switch the way a chip would (jax.devices()
    here is the CPU), and keep these compiles out of the persistent
    cache: an entry written for a described chip cannot be read back
    without one, and the next run would warn about it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setattr(fa, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _loss(q, k, v):
    return fa.flash_attention(q, k, v).astype(jnp.float32).sum()


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("shape_name", sorted(HEAD_SHAPES))
def test_flash_kernel_compiles_for_v5e(
    v5e_chip, compiled_not_interpreted, shape_name, direction
):
    B, H, S, D = HEAD_SHAPES[shape_name]
    x = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16, sharding=v5e_chip)
    if direction == "forward":
        fn, n_kernels = fa.flash_attention, 1
    else:
        # forward (for the residuals) + the backward kernel
        fn, n_kernels = jax.grad(_loss, argnums=(0, 1, 2)), 2
    text = jax.jit(fn).lower(x, x, x).compile().as_text()
    assert text.count("tpu_custom_call") == n_kernels, (
        f"{shape_name} {direction}: expected {n_kernels} compiled Pallas "
        f"kernel(s) in the program"
    )
    # the kernels read and write (B, S, H x D), whole 128-lane rows (a
    # head pair a column block, half of XL's last one outside the array):
    # nothing is transposed to heads-major for them, and under no remat
    # policy the residuals' names are identities
    kernels = [l for l in text.splitlines() if "tpu_custom_call" in l]
    assert all(f"bf16[{B},{S},{H * D}]" in l for l in kernels)
    assert f"bf16[{B},{H},{S},{D}]" not in text


@pytest.mark.parametrize("run_len,heads,qk,v,masked", [
    (8192, 64, 256, 256, True),     # GLM-5: the cell's longest prompt,
    (2560, 64, 256, 256, True),     # the comparison's,
    (4096, 64, 256, 256, False),    # and no indexer: causal from an iota
    (4096, 64, 192, 128, False),    # LongCat's two prompt lengths: keys of
    (2048, 64, 192, 128, False),    # 192 padded to 256, no indexer
    (2048, 32, 192, 128, False),    # JoyAI's 32 heads at the shortest run the
                                    # kernel takes (its cell's prompts, 512 and
                                    # 1,536 tokens, keep XLA's body)
])
def test_latent_prefill_kernel_compiles_for_v5e(
    v5e_chip, compiled_not_interpreted, monkeypatch, run_len, heads, qk, v, masked
):
    """GLM-5's prefill attention at its published widths, 64 heads of 256 |
    256 over a run of whole tiles, the selection an int8 mask operand, and
    LongCat's (64 heads) and JoyAI's (32) of 192 | 128, causal, four heads a
    grid step
    (``ops/latent_prefill_attention.py``): one kernel, inside its fast
    memory, and no float32 score array of heads x queries x keys."""
    monkeypatch.setattr(lpa, "_interpret", lambda: False)
    assert lpa.implementation(run_len, qk, v) == "flash"
    # as the model hands them over: the zeros behind a head already written
    padded = qk + lpa.lanes_behind(qk)
    x = jax.ShapeDtypeStruct((run_len, heads, padded), jnp.bfloat16, sharding=v5e_chip)
    values = jax.ShapeDtypeStruct((run_len, heads, v), jnp.bfloat16, sharding=v5e_chip)
    mask = jax.ShapeDtypeStruct((run_len, run_len), jnp.int8, sharding=v5e_chip)

    def attend(q, k, v, *mask):
        return lpa.latent_prefill_attention(q, k, v, *mask, scale=qk ** -0.5)

    args = (x, x, values, mask) if masked else (x, x, values)
    compiled = jax.jit(attend).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert f"f32[{heads},{run_len},{run_len}]" not in text
    # at most a copy of q, k and v each, at the padded width (these are
    # parameters in the default layout; inside the model their producers
    # write the kernel's): the float32 scores would be 4 x heads x run_len^2
    # bytes
    assert compiled.memory_analysis().temp_size_in_bytes < (
        1.035 * heads * run_len * (2 * padded + v) * 2)


@pytest.mark.parametrize("rows,keys", [
    (32, 10240),                                # a layer of the decode step
    (64, 4096), (64, 6144), (64, 8192),         # an 8,192-token prefill's blocks
    (128, 3072), (128, 4096),                   # a 4,096-token one's
])
def test_topk_mask_kernel_compiles_for_v5e(
    v5e_chip, compiled_not_interpreted, monkeypatch, rows, keys
):
    """GLM-5's exact top-2,048 at the shapes its cell sends
    (``ops/topk_mask.py``): one kernel, its block, the keys' scratch and
    the tie rule's position search inside fast memory, and no sort."""
    monkeypatch.setattr(tkm, "_interpret", lambda: False)
    assert tkm.implementation(rows, keys, 2048) == "counted"
    scores = jax.ShapeDtypeStruct((rows, keys), jnp.float32, sharding=v5e_chip)
    compiled = jax.jit(lambda s: tkm.topk_mask(s, 2048)).lower(scores).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert " sort(" not in text and "reduce-window" not in text


def test_gated_delta_step_kernel_compiles_for_v5e(
    v5e_chip, compiled_not_interpreted, monkeypatch
):
    """Olmo-Hybrid's one-token update at the cell's shape — 32 rows x 30
    heads x (96, 192), the 12 linear layers' states stacked in the cache's
    leaf (``ops/gated_delta.py``): one kernel inside the fast memory it
    asks for, the donated leaf its output's buffer and not a byte of it
    copied, compiled in a small part of the few seconds ``setup_s`` has
    (three of these calls stand in the decode program's loop body)."""
    import time

    monkeypatch.setattr(gd, "_interpret", lambda: False)
    L, B, H, dk, dv = 12, 32, 30, 96, 192
    assert gd.implementation(B, H, dk, dv) == "in_place"

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=v5e_chip)

    args = (f32(B, H, dk), f32(B, H, dk), f32(B, H, dv), f32(B, H), f32(B, H),
            f32(L, B, dk, H * dv),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=v5e_chip))
    started = time.perf_counter()
    compiled = jax.jit(gd.step_layer, donate_argnums=(5,)).lower(*args).compile()
    took = time.perf_counter() - started
    assert took < 10.0, took    # 1.0-1.6 s alone here; tier-1 runs six workers beside it
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert text.count("tpu_custom_call") == 1 and "gated_delta_step" in text
    leaf = L * B * dk * H * dv * 4
    assert leaf == 849_346_560 and mem.alias_size_in_bytes == leaf
    assert mem.temp_size_in_bytes < 2**20, mem
    # a step's blocks of the state, in and out in two buffers each
    assert 4 * 8 * dk * gd._heads_a_step(H, dk, dv) * dv * 4 < gd.VMEM_LIMIT_BYTES


def test_kda_step_kernel_compiles_for_v5e(v5e_chip, compiled_not_interpreted, monkeypatch):
    """Solar-Open2's one-token update at the cell's shape — 64 rows x 64 heads
    x (128, 128), a decay a key channel, the 3 KDA layers' states stacked in
    the cache's leaf: the kernel ``kda_step``, the donated leaf its output's
    buffer and not a byte of it copied.  At one lane tile a head a
    one-sublane load at a traced index does not lower (``dynamic load with
    unaligned indices``: found here, before a chip, PR 59); the kernel takes
    the row's gates out of their tile by a mask."""
    monkeypatch.setattr(gd, "_interpret", lambda: False)
    L, B, H, dk, dv = 3, 64, 64, 128, 128
    assert gd.implementation(B, H, dk, dv) == "in_place"

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=v5e_chip)

    args = (f32(B, H, dk), f32(B, H, dk), f32(B, H, dv), f32(B, H, dk), f32(B, H),
            f32(L, B, dk, H * dv),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=v5e_chip))
    compiled = jax.jit(gd.step_layer, donate_argnums=(5,)).lower(*args).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert text.count("tpu_custom_call") == 1 and "kda_step" in text
    assert "gated_delta_step" not in text
    leaf = L * B * dk * H * dv * 4
    assert leaf == 805_306_368 and mem.alias_size_in_bytes == leaf
    assert mem.temp_size_in_bytes < 2**22, mem
    assert 4 * gd.ROWS_A_STEP * dk * gd._heads_a_step(H, dk, dv) * dv * 4 < gd.VMEM_LIMIT_BYTES


@pytest.mark.parametrize("dk, dv", [(128, 256), (256, 128)])
def test_kda_scan_kernel_takes_heads_of_several_lane_tiles(
    v5e_chip, compiled_not_interpreted, monkeypatch, dk, dv
):
    """What ``scan_implementation`` promises beyond the served shape: d_k or
    d_v of two lane tiles (one group of heads, two chunks) compiles too."""
    monkeypatch.setattr(gd, "_interpret", lambda: False)
    B, S, H = 1, 128, 8
    assert gd.scan_implementation(H, dk, dv, gd.CHUNK, True) == "kernel"

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=v5e_chip)

    args = (f32(B, S, H, dk), f32(B, S, H, dk), f32(B, S, H, dv), f32(B, S, H, dk),
            f32(B, S, H), f32(B, H, dk, dv))
    text = jax.jit(gd.scan).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 1 and "kda_chunk" in text


def test_kda_scan_kernel_compiles_for_v5e(v5e_chip, compiled_not_interpreted, monkeypatch):
    """Solar-Open2's chunked rule at the served shape — one row x a segment of
    1,024 tokens x 64 heads x (128, 128), chunk 64, float32, a decay a key
    channel: ONE kernel, ``kda_chunk``, whose operands are the mixer's own (no
    transposed copy of q, k, v or the decay beside it: the program's
    temporaries stay under one operand's size), and a grid step's blocks and
    scratch inside the kernel's stated VMEM limit."""
    monkeypatch.setattr(gd, "_interpret", lambda: False)
    B, S, H, dk, dv = 1, 1024, 64, 128, 128
    assert gd.scan_implementation(H, dk, dv, gd.CHUNK, True) == "kernel"

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=v5e_chip)

    args = (f32(B, S, H, dk), f32(B, S, H, dk), f32(B, S, H, dv), f32(B, S, H, dk),
            f32(B, S, H), f32(B, H, dk, dv))
    compiled = jax.jit(gd.scan).lower(*args).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert text.count("tpu_custom_call") == 1 and "kda_chunk" in text
    assert mem.temp_size_in_bytes < B * S * H * dk * 4, mem
    # a step's blocks (q, k, v, the decay in, o out; the state in and out), two
    # buffers each, and the scratch: the same six a head in front
    hb, C = gd.HEADS_A_STEP, gd.CHUNK
    tokens, state = C * hb * 128 * 4, hb * dk * dv * 4
    assert 2 * (5 * tokens + 2 * state) + 6 * tokens < gd.SCAN_VMEM_LIMIT_BYTES


@pytest.mark.limit(300)
def test_shortcut_decode_step_reads_every_weight_where_it_lies(
    v5e_chip, compiled_not_interpreted, monkeypatch
):
    """LongCat-Flash's decode step at the cell's shape (4 double layers, 64
    slots x 5,120): the sub-layers' matrices are sliced out of their stacks
    where the matmuls read them (``llama._layer_params`` hands the block the
    whole ``(2L, ..)`` leaves) — scanned a layer at a time and then indexed a
    sub-layer, every matrix outside the experts was COPIED once a step, 13.4
    of 27 ms on the chip (PR 49) — the donated cache is the output's buffer,
    and the program fits beside 13.7 GB of weights and cache."""
    import functools

    from ray_tpu.models import llama
    from ray_tpu.ops import grouped_matmul, latent_decode_attention

    for mod in (latent_decode_attention, grouped_matmul):
        if hasattr(mod, "_interpret"):
            monkeypatch.setattr(mod, "_interpret", lambda: False)
    cfg = llama.LlamaConfig.longcat_flash(
        num_layers=4, vocab_size=16384, experts_held=16,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    slots, max_len = 64, 5120

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e_chip), tree)

    params = on_chip(jax.eval_shape(functools.partial(llama.init, config=cfg),
                                    jax.random.key(0)))
    cache = on_chip(jax.eval_shape(functools.partial(llama.init_cache, cfg, slots, max_len)))
    rows = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=v5e_chip)
    compiled = llama.decode_step_rowwise.lower(params, rows, cache, rows, cfg).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 8 * slots * max_len * 640 * 2, mem
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_PROGRAM_LIMIT - MARGIN, mem
    # one dense matrix is 151 MB: no temporary holds one
    assert mem.temp_size_in_bytes < 256 * 2**20, mem
    # and no instruction outside a fusion's body MAKES one (a bitcast of a
    # parameter is no copy)
    made, inside = [], None
    for line in compiled.as_text().splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            inside = head.group(1)
            continue
        m = re.match(r"^\s+(?:ROOT )?%[\w.\-]+ = bf16\[(6144,12288|12288,6144|64,128,6144)\]\S* (\w[\w\-]*)\(", line)
        if m and inside and not inside.startswith("fused_computation") and m.group(2) not in (
                "bitcast", "parameter", "get-tuple-element"):
            made.append(line.strip()[:120])
    assert not made, made


@pytest.mark.limit(300)
def test_window_and_full_decode_step_fetches_no_full_layer_slab_whole(
    v5e_chip, compiled_not_interpreted, monkeypatch
):
    """MiMo-V2-Flash's decode step at the cell's shape (7 layers, 64 slots x
    13,312): both kinds' attention is the ``kv_decode`` kernel on the carried
    cache — seven calls, the full layers' K with its heads off a lane tile's
    edge (4 x 192) — the donated cache is the output's buffer, and nothing
    makes a full layer's slab (64 x 13,312 x 768 bf16, 1.3 GB): the program's
    temporaries stay under 64 MB.  The prefills at the cell's two prompt
    lengths compile with the ``kv_prefill`` kernel and fit beside the weights
    and the cache."""
    import functools

    from ray_tpu.models import llama
    from ray_tpu.ops import grouped_matmul, kv_decode_attention, kv_prefill_attention

    for mod in (kv_decode_attention, kv_prefill_attention, grouped_matmul):
        if hasattr(mod, "_interpret"):
            monkeypatch.setattr(mod, "_interpret", lambda: False)
    cfg = llama.LlamaConfig.mimo_v2_flash(
        num_layers=7, layer_types=(llama.FULL,) + (llama.SLIDING,) * 5 + (llama.FULL,),
        vocab_size=19072, experts_held=16, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    slots, max_len = 64, 13312

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e_chip), tree)

    params = on_chip(jax.eval_shape(functools.partial(llama.init, config=cfg),
                                    jax.random.key(0)))
    cache = on_chip(jax.eval_shape(functools.partial(llama.init_cache, cfg, slots, max_len)))
    rows = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=v5e_chip)
    compiled = llama.decode_step_rowwise.lower(params, rows, cache, rows, cfg).compile()
    mem = compiled.memory_analysis()
    state = sum(math.prod(cache[k].shape) * 2 for k in ("k", "v", "swa_k", "swa_v"))
    assert state == 4_362_076_160 + 209_715_200
    assert mem.alias_size_in_bytes >= state, mem
    assert mem.temp_size_in_bytes < 64 * 2**20, mem
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_PROGRAM_LIMIT - MARGIN, mem
    text = compiled.as_text()
    assert len(re.findall(r"%kv_decode[.\d]* = .*?custom-call\(", text)) == 7
    # no instruction makes a full layer's slab of keys or of values
    assert not re.search(r"= bf16\[64,13312,(768|512)\]", text)
    for n in (2048, 12288):
        prompt = jax.ShapeDtypeStruct((1, n), jnp.int32, sharding=v5e_chip)
        slot = jax.ShapeDtypeStruct((), jnp.int32, sharding=v5e_chip)
        prefill = llama.prefill_into_slot.lower(params, prompt, cache, slot, cfg).compile()
        mem = prefill.memory_analysis()
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_PROGRAM_LIMIT - MARGIN, mem
        assert len(re.findall(r"%kv_prefill[.\d]* = .*?custom-call\(", prefill.as_text())) == 7
        # no (S, S) score of any head count reaches memory
        assert not re.search(rf"f32\[[\d,]*{n},{n}\]", prefill.as_text())


# ---- the training cells' step programs ----------------------------------

BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "chipbench"
)
#: cell -> (configuration, traffic, chips): BENCHMARK.json's two training cells
TRAIN_CELLS = {
    "train_gpt2m_1chip": ("gpt2-medium", "train_8x1024", 1),
    "train_gpt2xl_mesh4": ("gpt2-xl", "train_32x1024_fsdp4", 4),
}
#: what the compiler allows one v5e program (CHANGES.md, PR 28: "15.76 of
#: 15.75 GB"), and what a step has to leave under it.  GPT-2 XL's,
#: measured at PR 35: 14.92 GiB by `memory_analysis()`'s arguments +
#: temporaries (12.59 before the block kept `o` and `lse`), 11.93 GiB by
#: the buffer assignment's own total — `temp_size_in_bytes` counts more
#: than the program holds at once, and the benchmark's
#: `memory_peak_bytes` is made from it.
V5E_PROGRAM_LIMIT = 15.75 * 2**30
MARGIN = 0.5 * 2**30


def _compiled_step(cell, devices):
    """`chipbench/jobs/train_spmd.py`'s step program for `cell`, compiled
    for `devices`: the configuration's model, the traffic's mesh and
    batch, AdamW, the donated state — from shapes, nothing is placed."""
    from chipbench.jobs.train_spmd import gpt_config

    config, traffic, chips = TRAIN_CELLS[cell]
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", traffic + ".json")) as f:
        traffic = json.load(f)
    model = gpt_config(cfg, traffic["seq_len"])
    optimizer = optax.adamw(
        cfg["optimizer"]["learning_rate"],
        weight_decay=cfg["optimizer"]["weight_decay"],
    )
    mesh = mesh_mod.make_mesh(
        mesh_mod.MeshConfig(**traffic["mesh"]), devices=devices[:chips]
    )
    try:
        def init(rng):
            return gpt2.init(rng, model)

        key = jax.random.key(0)
        state = jax.tree.map(
            lambda x, sharding: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=sharding
            ),
            jax.eval_shape(spmd._full_init(init, optimizer), key),
            spmd.state_shardings(
                mesh, init, key, gpt2.param_logical_axes(model), optimizer
            ),
        )
        batch = {"tokens": jax.ShapeDtypeStruct(
            (traffic["batch"], traffic["seq_len"] + 1), jnp.int32,
            sharding=spmd.batch_sharding(mesh),
        )}
        step = spmd.compile_train_step(
            lambda p, b: gpt2.loss_fn(p, b, model), optimizer
        )
        with mesh_mod.use(mesh):
            return model, traffic, step.lower(state, batch).compile()
    finally:
        mesh_mod.set_current_mesh(None)


@pytest.mark.limit(400)
@pytest.mark.parametrize("cell", sorted(TRAIN_CELLS))
def test_training_step_runs_the_forward_kernel_once_and_fits(
    v5e_2x2, compiled_not_interpreted, cell
):
    model, traffic, compiled = _compiled_step(cell, v5e_2x2)
    text = compiled.as_text()
    kernels = Counter(re.findall(
        r'custom_call_target="tpu_custom_call".*op_name="[^"]*/(\w+)/pallas_call"',
        text,
    ))
    # the rolled layer scan has one body forward and one backward; a block
    # that recomputed its attention would show `flash_fwd` in both
    assert kernels == {"flash_fwd": 1, "flash_bwd": 1}

    # what the forward scan stacks of the kernel's output is `o` as the
    # kernel writes it, (B, S, H x D): whole 128-lane rows (XL's 1,600
    # lanes are twelve and a half: the stack pads 4%, where a 64-wide
    # minor dimension took twice the bytes)
    chips = TRAIN_CELLS[cell][2]
    L, H, D = model.num_layers, model.num_heads, model.head_dim
    B, S = traffic["batch"] // chips, traffic["seq_len"]
    assert f"bf16[{L},{B},{S},{H * D}]" in text
    assert f"bf16[{L},{B},{H},{S},{D}]" not in text
    assert f"bf16[{L},{B},{S},{H},{D}]" not in text

    m = compiled.memory_analysis()
    held = (
        m.argument_size_in_bytes + m.temp_size_in_bytes
        + m.output_size_in_bytes - m.alias_size_in_bytes
    )
    assert held + MARGIN < V5E_PROGRAM_LIMIT, (
        f"{cell}: the step holds {held / 2**30:.2f} GiB a chip by the "
        f"compiler's analysis; a v5e program may hold 15.75"
    )
