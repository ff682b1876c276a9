"""The every-row step's K/V attention kernel (``ops/kv_decode_attention.py``),
the very kernel in Pallas interpret mode, against ``llama._grouped_attention``
over the same cache.  Every call is jitted: interpreted eagerly the file took
minutes (PR 33's lesson with the latent kernels)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.ops import kv_decode_attention as kd

D, T, L, LAYER = 128, 384, 2, 1          # three blocks of 128 keys a row


def _config(H, KV):
    return llama.LlamaConfig.tiny(
        num_heads=H, num_kv_heads=KV, embed_dim=H * D, dtype=jnp.bfloat16)


def _inputs(R, Sq, H, KV, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (R, Sq, H, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (L, R, T, KV * D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (L, R, T, KV * D), jnp.bfloat16)
    return q, k, v


@functools.partial(jax.jit, static_argnames=("config", "block"))
def _reference(q, k, v, positions, config, block):
    mask = llama._cache_mask(positions, T, 0, block)
    return llama._grouped_attention(q, k[LAYER], v[LAYER], mask, config)


@functools.partial(jax.jit, static_argnames=("block",))
def _kernel(q, k, v, positions, block):
    return kd.kv_decode_attention(
        q, k, v, jnp.int32(LAYER), llama._last_visible(positions, block))


def _positions(starts, Sq):
    return jnp.asarray(starts, jnp.int32)[:, None] + jnp.arange(Sq)


def _close(got, want):
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=2e-2)


# a free row at 0, rows ending at a block's last and first key, one inside
# a block, one full
ROWS = {1: [0, 127, 128, 300, T - 1], 4: [0, 124, 128, 300, T - 4]}


@pytest.mark.parametrize("Sq, group, kv_heads, block", [
    (1, 1, 2, 1), (1, 4, 2, 1), (1, 8, 1, 1),
    (4, 1, 2, 4), (4, 4, 2, 1), (4, 8, 1, 4),
])
def test_the_kernel_is_grouped_attention(Sq, group, kv_heads, block):
    H = group * kv_heads
    q, k, v = _inputs(len(ROWS[Sq]), Sq, H, kv_heads)
    positions = _positions(ROWS[Sq], Sq)
    _close(_kernel(q, k, v, positions, block),
           _reference(q, k, v, positions, _config(H, kv_heads), block))


@pytest.mark.parametrize("Sq, block", [(1, 1), (4, 4)])
def test_all_rows_full(Sq, block):
    q, k, v = _inputs(3, Sq, 8, 2, seed=1)
    positions = _positions([T - Sq] * 3, Sq)
    _close(_kernel(q, k, v, positions, block),
           _reference(q, k, v, positions, _config(8, 2), block))


def test_behind_the_last_visible_block_nothing_is_fetched_or_computed():
    """NaN in every block behind a row's last visible one — in K and in V —
    changes no bit: those blocks are no item of the work list."""
    starts = [0, 127, 128, 200]
    q, k, v = _inputs(4, 4, 8, 2)
    positions = _positions(starts, 4)
    want = np.asarray(_kernel(q, k, v, positions, 4), np.float32)
    behind = jnp.arange(T)[None, :] >= jnp.asarray(
        [(s + 3) // kd.BLOCK_KEYS + 1 for s in starts])[:, None] * kd.BLOCK_KEYS
    poison = lambda c: jnp.where(behind[None, :, :, None], jnp.nan, c)  # noqa: E731
    got = np.asarray(_kernel(q, poison(k), poison(v), positions, 4), np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("item_bytes, max_blocks, in_flight", [
    (1 << 19, 1, 3), (1 << 19, 4, 1), (1 << 16, 4, 2),
])
def test_the_schedule_changes_no_bit(monkeypatch, item_bytes, max_blocks, in_flight):
    """Items of one block or of several, one copy in flight or three.  The
    copies in flight change nothing by construction; an item's length moves
    the cuts of the running softmax, and at these sizes the outputs'
    bfloat16 bits stay the same."""
    q, k, v = _inputs(5, 4, 8, 2)
    positions = _positions(ROWS[4], 4)
    call = lambda: np.asarray(  # noqa: E731 — traced anew: the constants are read at trace time
        jax.jit(kd.kv_decode_attention)(
            q, k, v, jnp.int32(LAYER), llama._last_visible(positions, 4)), np.float32)
    want = call()
    monkeypatch.setattr(kd, "ITEM_BYTES", item_bytes)
    monkeypatch.setattr(kd, "MAX_ITEM_BLOCKS", max_blocks)
    monkeypatch.setattr(kd, "COPIES_IN_FLIGHT", in_flight)
    assert kd.item_blocks(T, 2 * D * 2) == min(max_blocks, item_bytes // (128 * 512), 3)
    np.testing.assert_array_equal(call(), want)


def test_the_work_list_holds_the_live_blocks_row_after_row():
    from ray_tpu.ops.latent_decode_attention import _work_list

    last = jnp.asarray([0, 127, 128, 300, 383], jnp.int32)
    # blocks 1, 1, 2, 3, 3: one item each while an item holds up to 4 ...
    np.testing.assert_array_equal(_work_list(last, 128, 4), [0, 1, 2, 3, 4, 5])
    # ... and 1, 1, 1, 2, 2 items of up to 2 blocks
    np.testing.assert_array_equal(_work_list(last, 128, 2), [0, 1, 2, 3, 5, 7])
    np.testing.assert_array_equal(_work_list(last, 128, 1), [0, 1, 2, 4, 7, 10])
    # what is fetched: whole blocks up to the last visible key, one for a
    # free row; the whole slab where XLA's body runs
    np.testing.assert_array_equal(
        kd.keys_read(np.asarray(last), T, D), [128, 128, 256, 384, 384])
    np.testing.assert_array_equal(kd.keys_read(np.asarray(last), 400, D), [400] * 5)
    np.testing.assert_array_equal(kd.keys_read(np.asarray(last), T, 64), [T] * 5)


def test_implementation_goes_by_static_shapes():
    assert kd.implementation(1536, 128) == "streamed"       # SDAR's cell
    assert kd.implementation(1024, 128) == "streamed"       # InternLM2's, OLMoE's
    assert kd.implementation(1024, 256) == "streamed"
    assert kd.implementation(64, 16) == "slab"              # tier-1's tiny caches
    assert kd.implementation(1000, 128) == "slab"           # no whole blocks
    assert kd.implementation(1024, 64) == "slab"            # half a lane tile a head
    assert kd.implementation(1024, 128, window=512) == "slab"  # a rolling cache
    assert kd.item_blocks(1536, 4 * 128 * 2) == 4            # SDAR: 1 KiB a key
    assert kd.item_blocks(1024, 8 * 128 * 2) == 2            # InternLM2: 2 KiB
    assert kd.item_blocks(1024, 16 * 128 * 2) == 1           # OLMoE: 4 KiB
    assert kd.item_blocks(128, 4 * 128 * 2) == 1             # a cache of one block


def test_a_ragged_cache_is_refused():
    q, k, v = _inputs(2, 1, 4, 2)
    visible = jnp.zeros((2, 1), jnp.int32)
    with pytest.raises(ValueError, match="whole blocks of 128 keys"):
        kd.kv_decode_attention(q, k[:, :, :200], v[:, :, :200], 0, visible)
    with pytest.raises(ValueError, match="one cache row a query row"):
        kd.kv_decode_attention(q, k[:, :1], v[:, :1], 0, visible)
    with pytest.raises(ValueError, match="visible"):
        kd.kv_decode_attention(q, k, v, 0, visible[:1])


@pytest.mark.parametrize("block", [1, 4])
def test_the_cached_step_runs_the_kernel_and_agrees_with_the_slab_body(monkeypatch, block):
    """``_kv_attention`` wired through: an every-row step of a model with
    128-wide heads over a cache of whole blocks traces the kernel, and its
    logits are those of the same step through XLA's body; a prefill (one
    row's run) keeps XLA's body."""
    cfg = llama.LlamaConfig.tiny(
        num_heads=2, num_kv_heads=1, embed_dim=256, mlp_dim=256,
        dtype=jnp.bfloat16, mask_block=block)
    params = llama.init(jax.random.key(0), cfg)
    prompt = jax.random.randint(jax.random.key(1), (1, 12), 0, cfg.vocab_size)
    tokens = jax.random.randint(jax.random.key(2), (2, block), 0, cfg.vocab_size)

    def run():
        cache = llama.init_cache(cfg, 2, 256)
        assert cache["k"].shape == (2, 2, 256, 128)
        _, cache = llama.prefill_into_slot(params, prompt, cache, jnp.int32(1), cfg)
        step = jax.jit(lambda c: llama._cached_step(
            params, tokens, c, None, jnp.asarray([0, 12], jnp.int32), cfg))
        text = str(step.trace(cache).jaxpr)
        logits, cache = step(cache)
        return text, np.asarray(logits, np.float32), cache

    text, got, cache = run()
    assert "kv_decode" in text
    assert "kv_decode" not in str(llama.prefill_into_slot.trace(
        params, prompt, llama.init_cache(cfg, 2, 256), jnp.int32(1), cfg).jaxpr)
    monkeypatch.setattr(kd, "implementation", lambda *a, **kw: "slab")
    jax.clear_caches()
    slab_text, want, slab_cache = run()
    assert "kv_decode" not in slab_text
    np.testing.assert_allclose(got, want, atol=3e-2)
    # the first layer's new rows hang on no attention: the same bits
    np.testing.assert_array_equal(np.asarray(cache["k"][0], np.float32),
                                  np.asarray(slab_cache["k"][0], np.float32))
    np.testing.assert_allclose(np.asarray(cache["v"], np.float32),
                               np.asarray(slab_cache["v"], np.float32), atol=3e-2)


def test_the_engine_counts_the_keys_its_steps_fetch():
    """``kv_keys_read_step`` beside ``kv_keys_visible_step`` in ``stats()``:
    what the steps' attention fetched, for every row — read >= visible, equal
    where every row ends at a block's last key, one block for a free slot —
    from the positions the host feeds the step; no copy from the device."""
    import asyncio

    from ray_tpu.serve.llm import LlamaDeployment

    cfg = llama.LlamaConfig.tiny(
        num_heads=1, num_kv_heads=1, embed_dim=128, mlp_dim=128, dtype=jnp.bfloat16)
    replica = LlamaDeployment.func_or_class(config=cfg, max_slots=3, max_len=256, seed=0)
    eng = replica.engine

    async def one(prompt, new):
        return [t async for t in eng.stream(prompt, max_new_tokens=new)]

    async def run():
        got = await asyncio.gather(one([3, 7, 11, 2, 9], 4), one([5, 1, 9], 6))
        return got, await replica.stats()

    got, stats = asyncio.run(run())
    assert [len(g) for g in got] == [4, 6]
    layers, steps = cfg.num_layers, stats["decode_steps_total"]
    # every row of every step inside its first block: one block a row, the
    # free third slot's too
    assert stats["kv_keys_read_step"] == steps * 3 * kd.BLOCK_KEYS * layers
    # a request's first token is its prefill's; the steps feed it at
    # position len ... its last but one at len + new - 2, and a token at
    # position p sees p + 1 keys
    seen = sum(sum(range(p + 1, p + n)) for p, n in ((5, 4), (3, 6)))
    assert stats["kv_keys_visible_step"] == seen * layers < stats["kv_keys_read_step"]
    before = eng.kv_keys_visible_step, eng.kv_keys_read_step
    eng._count_kv_keys(128 + 256, np.asarray([127, 255, 0]))
    eng._count_kv_keys(129, np.asarray([128, 0, 0]))
    assert eng.kv_keys_visible_step - before[0] == (128 + 256 + 129) * layers
    assert eng.kv_keys_read_step - before[1] == (128 + 256 + 128 + 256 + 128 + 128) * layers
    # a tiny cache takes XLA's body, which reads every row's slab whole
    small = LlamaDeployment.func_or_class(max_slots=2, max_len=32).engine
    small._count_kv_keys(7, np.asarray([6, 0]))
    assert small.kv_keys_read_step == 2 * 32 * small.config.num_layers
