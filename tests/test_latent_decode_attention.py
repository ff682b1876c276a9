"""``ops/latent_decode_attention.py``: the streamed kernel (Pallas
interpret mode here, the very kernel the chip compiles) against the
gathered body on the same inputs, the selection made as
``models/llama.py`` makes it: a mask for the one (``_select_mask``),
``lax.top_k``'s indices for the other."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ray_tpu.models import llama
from ray_tpu.ops import latent_decode_attention as lda

R, H, W, C, L, K = 4, 8, 256, 128, 2, 16
T = 64
SCALE = 0.125


def case(pos, *, tied=False, dtype=jnp.bfloat16, seed=0):
    """Inputs of one decode step of R rows at ``pos``: queries, a cache,
    index scores (``tied``: drawn from four values, so the K-th is shared
    by many keys), and the selection in both forms."""
    ks = jax.random.split(jax.random.key(seed), 3)
    qq = jax.random.normal(ks[0], (R, H, W), dtype)
    ckv = jax.random.normal(ks[1], (L, R, T, W), dtype)
    scores = jax.random.normal(ks[2], (R, T), jnp.float32)
    if tied:
        # + 0.0: no -0.0, which ``lax.top_k`` orders below 0.0 and
        # ``_select_mask`` (the prefill's selection, and now the streamed
        # decode step's) holds equal to it
        scores = jnp.round(scores) + 0.0
    pos = jnp.asarray(pos, jnp.int32)
    scores = jnp.where(jnp.arange(T)[None, :] <= pos[:, None], scores, -jnp.inf)
    mask = llama._select_mask(scores, K)
    _, chosen = lax.top_k(scores, K)
    return qq, ckv, pos, mask, chosen, chosen <= pos[:, None]


def both(monkeypatch, block, qq, ckv, pos, mask, chosen, valid, layer=1):
    monkeypatch.setattr(lda, "BLOCK_KEYS", block)
    kw = dict(latent=C, scale=SCALE)
    streamed = lda.latent_decode_attention(qq, ckv, jnp.int32(layer), pos, mask, **kw)
    gathered = lda.gathered_decode_attention(
        qq, ckv, jnp.int32(layer), chosen, valid, **kw)
    return np.asarray(streamed, np.float32), np.asarray(gathered, np.float32)


POSITIONS = {
    "fewer_than_topk": [K - 3, 5, 2, K - 2],
    "exactly_topk": [K - 1] * R,
    "one_more": [K] * R,
    "a_blocks_last_key": [15, 31, 47, 63],
    "the_next_blocks_first": [16, 32, 48, 16],
    "the_caches_last": [T - 1] * R,
    "an_idle_slot": [0, 40, 0, 22],
    "mixed_lengths": [3, 17, 38, 63],
}


@pytest.mark.parametrize("block", [8, 16])
@pytest.mark.parametrize("tied", [False, True], ids=["distinct", "tied_at_kth"])
@pytest.mark.parametrize("pos", POSITIONS.values(), ids=POSITIONS.keys())
def test_streamed_equals_gathered(monkeypatch, pos, tied, block):
    qq, ckv, p, mask, chosen, valid = case(pos, tied=tied)
    # the same set: the mask holds exactly the positions top_k's indices do
    hit = np.zeros((R, T), bool)
    hit[np.arange(R)[:, None], np.asarray(chosen)] = np.asarray(valid)
    assert np.array_equal(np.asarray(mask), hit)
    assert np.asarray(mask).sum(-1).tolist() == [min(K, t + 1) for t in pos]
    streamed, gathered = both(monkeypatch, block, qq, ckv, p, mask, chosen, valid)
    # float32 scores and sums; what differs is where bf16 rounds a
    # probability: before the division by the sum, or after
    np.testing.assert_allclose(streamed, gathered, rtol=0, atol=0.02)
    assert np.abs(gathered).max() > 0.5


@pytest.mark.parametrize("block", [8, 16])
def test_streamed_in_float32_is_the_gathered_body_to_rounding(monkeypatch, block):
    """The tiny models of ``test_llama_mla_dsa.py`` run in float32: no
    probability is rounded, the bodies differ by the order of their sums."""
    args = case(POSITIONS["mixed_lengths"], dtype=jnp.float32)
    streamed, gathered = both(monkeypatch, block, *args, layer=0)
    np.testing.assert_allclose(streamed, gathered, rtol=0, atol=2e-5)


@pytest.mark.parametrize("block", [8, 16])
def test_what_lies_behind_pos_is_neither_fetched_nor_computed(monkeypatch, block):
    """NaNs in every block wholly behind a row's ``pos``, in the other
    layer and in rows' caches no query of the call owns change nothing."""
    pos = POSITIONS["mixed_lengths"]
    qq, ckv, p, mask, chosen, valid = case(pos)
    clean, _ = both(monkeypatch, block, qq, ckv, p, mask, chosen, valid)
    behind = (jnp.arange(T)[None, :] // block) > (p[:, None] // block)    # (R, T)
    poisoned = jnp.where(behind[None, :, :, None], jnp.nan, ckv)
    poisoned = poisoned.at[0].set(jnp.nan)                  # the other layer
    assert bool(jnp.isnan(poisoned[1]).any())
    dirty, _ = both(monkeypatch, block, qq, poisoned, p, mask, chosen, valid)
    assert np.array_equal(clean, dirty)


def test_implementation_goes_by_the_caches_length(monkeypatch):
    block, longest = lda.BLOCK_KEYS, lda.MAX_STREAMED_KEYS
    assert longest % block == 0
    assert lda.implementation(10 * block) == "streamed"     # the serving cell's 10,240
    assert lda.implementation(longest) == "streamed"
    assert lda.implementation(longest + block) == "gathered"   # past the crossover
    assert lda.implementation(131072) == "gathered"
    assert lda.implementation(10 * block + 8) == "gathered"    # no whole blocks
    assert lda.implementation(48) == "gathered"                # tier-1's tiny caches
    monkeypatch.setattr(lda, "BLOCK_KEYS", 8)
    assert lda.implementation(48) == "streamed"
    assert np.asarray(lda.keys_read(jnp.asarray([0, 7, 8, 47]))).tolist() == [8, 8, 16, 48]


def test_a_cache_of_other_rows_or_ragged_blocks_is_refused(monkeypatch):
    qq, ckv, p, mask, _, _ = case(POSITIONS["mixed_lengths"])
    monkeypatch.setattr(lda, "BLOCK_KEYS", 24)              # 64 is no multiple
    with pytest.raises(ValueError, match="whole blocks"):
        lda.latent_decode_attention(qq, ckv, jnp.int32(0), p, mask, latent=C, scale=SCALE)
    monkeypatch.setattr(lda, "BLOCK_KEYS", 8)
    with pytest.raises(ValueError, match="one cache row a query row"):
        lda.latent_decode_attention(
            qq[:2], ckv, jnp.int32(0), p[:2], mask[:2], latent=C, scale=SCALE)


# ---- every visible key, several queries a row (``latent_verify``) ------------

VERIFY_POSITIONS = {
    "mixed_lengths": [3, 17, 38, 61],
    "a_blocks_last_key": [15, 31, 47, 7],       # the second query opens a block
    "the_next_blocks_first": [16, 32, 48, 8],
    "the_caches_last_two": [T - 2] * R,
    "an_idle_slot": [0, 40, 0, 22],
}


def verify_case(pos, dtype=jnp.bfloat16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 2)
    qq = jax.random.normal(ks[0], (R, 2, H, W), dtype)
    ckv = jax.random.normal(ks[1], (L, R, T, W), dtype)
    visible = jnp.asarray(pos, jnp.int32)[:, None] + jnp.arange(2)
    return qq, ckv, visible


@pytest.mark.parametrize("block", [8, 16])
@pytest.mark.parametrize("pos", VERIFY_POSITIONS.values(), ids=VERIFY_POSITIONS.keys())
def test_two_queries_a_row_equal_two_one_query_calls(monkeypatch, pos, block):
    """The verify kernel — a row's two queries as 2 x H query rows, each
    with its own visibility, the row's blocks streamed once — against the
    same kernel called with one query a row, twice, and against plain XLA."""
    monkeypatch.setattr(lda, "BLOCK_KEYS", block)
    qq, ckv, visible = verify_case(pos)
    kw = dict(latent=C, scale=SCALE)
    both_at_once = lda.visible_decode_attention(qq, ckv, jnp.int32(1), visible, **kw)
    one_by_one = jnp.stack([
        lda.visible_decode_attention(qq[:, j:j + 1], ckv, jnp.int32(1), visible[:, j:j + 1], **kw)[:, 0]
        for j in range(2)
    ], axis=1)
    # the same sums in the same order but for the matmuls' own (2 x H query
    # rows in one, H in the other): one bfloat16 rounding at most
    np.testing.assert_allclose(np.asarray(both_at_once, np.float32),
                               np.asarray(one_by_one, np.float32), rtol=0, atol=0.004)
    dense = lda.dense_decode_attention(qq, ckv, jnp.int32(1), visible, **kw)
    np.testing.assert_allclose(np.asarray(both_at_once, np.float32),
                               np.asarray(dense, np.float32), rtol=0, atol=0.02)
    # one query a row with every visible key chosen IS the selection kernel
    everything = jnp.arange(T)[None, :] <= visible[:, :1]
    selected = lda.latent_decode_attention(
        qq[:, 0], ckv, jnp.int32(1), visible[:, 0], everything, **kw)
    np.testing.assert_allclose(np.asarray(selected, np.float32),
                               np.asarray(both_at_once[:, 0], np.float32),
                               rtol=0, atol=0.004)
    assert np.abs(np.asarray(dense, np.float32)).max() > 0.5


@pytest.mark.parametrize("block", [8, 16])
def test_the_verify_kernel_reads_a_rows_blocks_up_to_its_last_query(monkeypatch, block):
    """NaNs in every block wholly behind a row's LAST query and in the other
    layer change nothing; ``keys_read`` goes by that query's position."""
    monkeypatch.setattr(lda, "BLOCK_KEYS", block)
    pos = VERIFY_POSITIONS["a_blocks_last_key"]
    qq, ckv, visible = verify_case(pos, dtype=jnp.float32)
    kw = dict(latent=C, scale=SCALE)
    clean = lda.visible_decode_attention(qq, ckv, jnp.int32(1), visible, **kw)
    last = visible[:, -1]
    behind = (jnp.arange(T)[None, :] // block) > (last[:, None] // block)
    poisoned = jnp.where(behind[None, :, :, None], jnp.nan, ckv).at[0].set(jnp.nan)
    dirty = lda.visible_decode_attention(qq, poisoned, jnp.int32(1), visible, **kw)
    assert np.array_equal(np.asarray(clean), np.asarray(dirty))
    assert np.asarray(lda.keys_read(last)).tolist() == [
        (int(p) // block + 1) * block for p in last]
    dense = lda.dense_decode_attention(qq, ckv, jnp.int32(1), visible, **kw)
    np.testing.assert_allclose(clean, dense, rtol=0, atol=2e-5)


def test_the_verify_kernel_refuses_ragged_blocks_and_other_rows(monkeypatch):
    qq, ckv, visible = verify_case(VERIFY_POSITIONS["mixed_lengths"])
    monkeypatch.setattr(lda, "BLOCK_KEYS", 24)
    with pytest.raises(ValueError, match="whole blocks"):
        lda.visible_decode_attention(qq, ckv, jnp.int32(0), visible, latent=C, scale=SCALE)
    monkeypatch.setattr(lda, "BLOCK_KEYS", 8)
    with pytest.raises(ValueError, match="one cache row a query row"):
        lda.visible_decode_attention(
            qq[:2], ckv, jnp.int32(0), visible[:2], latent=C, scale=SCALE)
