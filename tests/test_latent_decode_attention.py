"""``ops/latent_decode_attention.py``: the streamed kernel (Pallas
interpret mode here, the very kernel the chip compiles) against the
gathered body on the same inputs, the selection made as
``models/llama.py`` makes it: a mask for the one (``_select_mask``),
``lax.top_k``'s indices for the other."""

import functools

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


def _schedule():
    return lda.BLOCK_KEYS, lda.ITEM_BLOCKS, lda.COPIES_IN_FLIGHT


@functools.partial(jax.jit, static_argnames=("schedule", "latent", "scale"))
def _decode(qq, ckv, layer, pos, mask, *, schedule, latent, scale):
    return lda.latent_decode_attention(qq, ckv, layer, pos, mask, latent=latent, scale=scale)


@functools.partial(jax.jit, static_argnames=("schedule", "latent", "scale"))
def _verify(qq, ckv, layer, visible, *, schedule, latent, scale):
    return lda.visible_decode_attention(qq, ckv, layer, visible, latent=latent, scale=scale)


def streamed_decode(*args, **kw):
    """``lda.latent_decode_attention`` jitted, traced once a schedule (the
    module's three constants as the test has set them) and shape: run
    eagerly, the interpreted kernel costs seconds a call."""
    return _decode(*args, schedule=_schedule(), **kw)


def streamed_verify(*args, **kw):
    return _verify(*args, schedule=_schedule(), **kw)


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


def both(monkeypatch, block, qq, ckv, pos, mask, chosen, valid, layer=1,
         flight=None, span=None):
    monkeypatch.setattr(lda, "BLOCK_KEYS", block)
    if flight is not None:
        monkeypatch.setattr(lda, "COPIES_IN_FLIGHT", flight)
    if span is not None:
        monkeypatch.setattr(lda, "ITEM_BLOCKS", span)
    kw = dict(latent=C, scale=SCALE)
    streamed = streamed_decode(qq, ckv, jnp.int32(layer), pos, mask, **kw)
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
    # the hand-over between rows: the ring's copies run ahead into the next
    # row's first blocks while this row's last are computed on
    "the_last_row_is_the_longest": [1, 9, 20, 63],
    "the_first_row_is_the_longest": [63, 20, 9, 1],
    "every_row_inside_one_block": [1, 5, 7, 2],
    "every_row_at_position_0": [0] * R,
}


#: cases (by name, in ``POSITIONS`` and ``VERIFY_POSITIONS``) that exercise
#: the ring's hand-over between rows / an item's last block
HAND_OVERS = ["mixed_lengths", "the_last_row_is_the_longest",
              "the_first_row_is_the_longest", "every_row_at_position_0"]
ITEM_ENDS = ["mixed_lengths", "a_blocks_last_key", "the_next_blocks_first",
             "the_first_row_is_the_longest"]


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
    assert streamed.shape == (R, H, C)
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
    """``keys_read`` is what the schedule fetches, rows for rows: NaNs in
    every key behind a row's ``keys_read``, in the other layer and in rows'
    caches no query of the call owns change nothing; a NaN in the LAST key
    inside it (fetched with ``pos``'s block, weighted by zero) reaches that
    row's result and no other's."""
    pos = POSITIONS["mixed_lengths"]
    qq, ckv, p, mask, chosen, valid = case(pos)
    clean, _ = both(monkeypatch, block, qq, ckv, p, mask, chosen, valid)
    read = np.asarray(lda.keys_read(p))
    assert read.tolist() == [(t // block + 1) * block for t in pos]
    behind = jnp.arange(T)[None, :] >= read[:, None]                      # (R, T)
    poisoned = jnp.where(behind[None, :, :, None], jnp.nan, ckv)
    poisoned = poisoned.at[0].set(jnp.nan)                  # the other layer
    assert bool(jnp.isnan(poisoned[1]).any())
    dirty, _ = both(monkeypatch, block, qq, poisoned, p, mask, chosen, valid)
    assert np.array_equal(clean, dirty)
    for r in (0, 2):                                        # 3 and 38 end no block
        inside = ckv.at[1, r, read[r] - 1].set(jnp.nan)
        dirty, _ = both(monkeypatch, block, qq, inside, p, mask, chosen, valid)
        assert np.isnan(dirty[r]).all()
        others = np.arange(R) != r
        assert np.array_equal(clean[others], dirty[others])


@pytest.mark.parametrize("flight", [1, 2, 5])
@pytest.mark.parametrize("pos", HAND_OVERS)
def test_the_copies_in_flight_change_no_bit(monkeypatch, pos, flight):
    """The ring's depth is the schedule's alone — 5 copies in flight are
    more than the call's four rows of one block have items: the same blocks
    meet the same sums in the same order."""
    args = case(POSITIONS[pos])
    as_shipped, _ = both(monkeypatch, 8, *args)
    streamed, gathered = both(monkeypatch, 8, *args, flight=flight)
    assert np.array_equal(as_shipped, streamed)
    np.testing.assert_allclose(streamed, gathered, rtol=0, atol=0.02)


@pytest.mark.parametrize("span", [1, 2, 3])
@pytest.mark.parametrize("pos", ITEM_ENDS)
def test_items_of_any_number_of_blocks_attend_to_the_same_keys(monkeypatch, pos, span):
    """Blocks of 8 keys in items of 1, 2 or 3 (as shipped: 4): a row's last
    item is as long as its live blocks need, 3 do not divide the cache's 8."""
    args = case(POSITIONS[pos])
    streamed, gathered = both(monkeypatch, 8, *args, span=span)
    np.testing.assert_allclose(streamed, gathered, rtol=0, atol=0.02)


def test_the_work_list_holds_the_live_blocks_row_after_row():
    last = jnp.asarray([0, 7, 8, 47, 16], jnp.int32)        # 1, 1, 2, 6, 3 blocks of 8
    assert np.asarray(lda._work_list(last, 8, 1)).tolist() == [0, 1, 2, 4, 10, 13]
    # items of up to 4 blocks: 1, 1, 2, 4 + 2, 3
    assert np.asarray(lda._work_list(last, 8, 4)).tolist() == [0, 1, 2, 3, 5, 6]
    assert np.asarray(lda._work_list(last, 8, 3)).tolist() == [0, 1, 2, 3, 5, 6]
    assert np.asarray(lda._work_list(last, 8, 2)).tolist() == [0, 1, 2, 3, 6, 8]


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
    "the_last_row_is_the_longest": [2, 9, 20, 61],
    "the_first_row_is_the_longest": [61, 20, 9, 2],
    "every_row_inside_one_block": [0, 3, 5, 2],
    "every_row_at_position_0": [0] * R,
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
    both_at_once = streamed_verify(qq, ckv, jnp.int32(1), visible, **kw)
    one_by_one = jnp.stack([
        streamed_verify(qq[:, j:j + 1], ckv, jnp.int32(1), visible[:, j:j + 1], **kw)[:, 0]
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
    selected = streamed_decode(
        qq[:, 0], ckv, jnp.int32(1), visible[:, 0], everything, **kw)
    np.testing.assert_allclose(np.asarray(selected, np.float32),
                               np.asarray(both_at_once[:, 0], np.float32),
                               rtol=0, atol=0.004)
    assert np.abs(np.asarray(dense, np.float32)).max() > 0.5


@pytest.mark.parametrize("block", [8, 16])
def test_the_verify_kernel_reads_a_rows_blocks_up_to_its_last_query(monkeypatch, block):
    """``keys_read`` goes by the row's LAST query's position: NaNs in every
    key behind it and in the other layer change nothing; a NaN in the last
    key inside it reaches that row's two queries and no other row."""
    monkeypatch.setattr(lda, "BLOCK_KEYS", block)
    pos = VERIFY_POSITIONS["a_blocks_last_key"]
    qq, ckv, visible = verify_case(pos, dtype=jnp.float32)
    kw = dict(latent=C, scale=SCALE)
    clean = streamed_verify(qq, ckv, jnp.int32(1), visible, **kw)
    last = visible[:, -1]
    read = np.asarray(lda.keys_read(last))
    assert read.tolist() == [(int(p) // block + 1) * block for p in last]
    behind = jnp.arange(T)[None, :] >= read[:, None]
    poisoned = jnp.where(behind[None, :, :, None], jnp.nan, ckv).at[0].set(jnp.nan)
    dirty = streamed_verify(qq, poisoned, jnp.int32(1), visible, **kw)
    assert np.array_equal(np.asarray(clean), np.asarray(dirty))
    inside = ckv.at[1, 0, read[0] - 1].set(jnp.nan)         # row 0: queries at 15, 16
    dirty = np.asarray(streamed_verify(qq, inside, jnp.int32(1), visible, **kw))
    assert np.isnan(dirty[0]).all()
    assert np.array_equal(np.asarray(clean)[1:], dirty[1:])
    dense = lda.dense_decode_attention(qq, ckv, jnp.int32(1), visible, **kw)
    np.testing.assert_allclose(clean, dense, rtol=0, atol=2e-5)


@pytest.mark.parametrize("flight", [1, 2, 5])
@pytest.mark.parametrize("pos", HAND_OVERS)
def test_the_verify_kernels_copies_in_flight_change_no_bit(monkeypatch, pos, flight):
    monkeypatch.setattr(lda, "BLOCK_KEYS", 8)
    qq, ckv, visible = verify_case(VERIFY_POSITIONS[pos])
    kw = dict(latent=C, scale=SCALE)
    as_shipped = streamed_verify(qq, ckv, jnp.int32(1), visible, **kw)
    monkeypatch.setattr(lda, "COPIES_IN_FLIGHT", flight)
    deeper = streamed_verify(qq, ckv, jnp.int32(1), visible, **kw)
    assert np.array_equal(np.asarray(as_shipped), np.asarray(deeper))
    dense = lda.dense_decode_attention(qq, ckv, jnp.int32(1), visible, **kw)
    np.testing.assert_allclose(np.asarray(deeper, np.float32),
                               np.asarray(dense, np.float32), rtol=0, atol=0.02)


@pytest.mark.parametrize("span", [1, 2, 3])
@pytest.mark.parametrize("pos", ITEM_ENDS)
def test_the_verify_kernels_items_of_any_number_of_blocks(monkeypatch, pos, span):
    monkeypatch.setattr(lda, "BLOCK_KEYS", 8)
    monkeypatch.setattr(lda, "ITEM_BLOCKS", span)
    qq, ckv, visible = verify_case(VERIFY_POSITIONS[pos])
    kw = dict(latent=C, scale=SCALE)
    mix = streamed_verify(qq, ckv, jnp.int32(1), visible, **kw)
    dense = lda.dense_decode_attention(qq, ckv, jnp.int32(1), visible, **kw)
    np.testing.assert_allclose(np.asarray(mix, np.float32),
                               np.asarray(dense, np.float32), rtol=0, atol=0.02)


def test_a_position_outside_the_cache_is_no_copy_outside_it(monkeypatch):
    """A retired row's position may stand at the cache's end or past it:
    the work list stops at the cache's last block."""
    monkeypatch.setattr(lda, "BLOCK_KEYS", 8)
    qq, ckv, visible = verify_case([T - 1, 3, T + 5, 0])    # second queries at T, T + 6
    kw = dict(latent=C, scale=SCALE)
    mix = streamed_verify(qq, ckv, jnp.int32(1), visible, **kw)
    dense = lda.dense_decode_attention(qq, ckv, jnp.int32(1), visible, **kw)
    np.testing.assert_allclose(np.asarray(mix, np.float32),
                               np.asarray(dense, np.float32), rtol=0, atol=0.02)


def test_the_verify_kernel_refuses_ragged_blocks_and_other_rows(monkeypatch):
    qq, ckv, visible = verify_case(VERIFY_POSITIONS["mixed_lengths"])
    monkeypatch.setattr(lda, "BLOCK_KEYS", 24)
    with pytest.raises(ValueError, match="whole blocks"):
        lda.visible_decode_attention(qq, ckv, jnp.int32(0), visible, latent=C, scale=SCALE)
    monkeypatch.setattr(lda, "BLOCK_KEYS", 8)
    with pytest.raises(ValueError, match="one cache row a query row"):
        lda.visible_decode_attention(
            qq[:2], ckv, jnp.int32(0), visible[:2], latent=C, scale=SCALE)
