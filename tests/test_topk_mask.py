"""``ops/topk_mask.py``: the counted top-k (the Pallas kernel, interpreted
here) against the sorted one (``lax.top_k`` + a running count), bit for bit,
at shapes the kernel's tiling takes; and which shapes those are."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.ops import topk_mask

INF = np.float32(np.inf)
ONE = np.float32(1)


def _random(rng, shape):
    return rng.standard_normal(shape).astype(np.float32)


def _four_values(rng, shape):
    return rng.choice(np.array([0.5, -1.0, 2.0, 0.0], np.float32), shape)


def _all_equal(rng, shape):
    return np.full(shape, 3.0, np.float32)


def _zeros_of_both_signs(rng, shape):
    s = rng.choice(np.array([0.0, -0.0], np.float32), shape)
    s[:, ::17] = 1.0        # a few above the threshold, which is at zero
    s[:, 5::19] = -2.0
    return s


def _negatives(rng, shape):
    return -np.abs(_random(rng, shape)) - np.float32(0.25)


def _subnormals(rng, shape):
    # both branches of the key mapping below the smallest normal, beside
    # normal scores of both signs and a zero
    s = _random(rng, shape) * np.float32(1e-40)
    s[:, ::5] = _random(rng, s[:, ::5].shape)
    s[:, 3] = 0.0
    return s


def _visible_prefixes(rng, shape):
    """Rows that see fewer than, exactly, one more than and far more than
    any k of the cases, in ONE block."""
    s = _random(rng, shape)
    seen = np.resize([1, 3, 15, 16, 17, 31, 32, 33, 100, shape[1]], shape[0])
    seen[-1] = shape[1]
    return np.where(np.arange(shape[1])[None, :] < seen[:, None], s, -INF)


def _one_ulp(rng, shape):
    s = np.ones(shape, np.float32)
    s[:, 7::11] = np.nextafter(ONE, np.float32(2))
    s[:, 9::13] = np.nextafter(ONE, np.float32(0))
    return s


FAMILIES = {
    "random": _random,
    "four_values_tie_at_the_kth": _four_values,
    "all_equal": _all_equal,
    "negative_zero_beside_zero": _zeros_of_both_signs,
    "negatives": _negatives,
    "subnormals": _subnormals,
    "fewer_exactly_and_more_than_k_visible": _visible_prefixes,
    "a_margin_of_one_ulp": _one_ulp,
}


@pytest.mark.parametrize("rows,keys,k", [(8, 256, 16), (16, 1024, 100), (8, 128, 32)])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_counted_mask_is_the_sorted_one(family, rows, keys, k):
    assert topk_mask.implementation(rows, keys, k) == "counted"
    scores = FAMILIES[family](np.random.default_rng(rows + keys + k), (rows, keys))
    got = np.asarray(topk_mask.topk_mask(jnp.asarray(scores), k))
    want = np.asarray(topk_mask.sorted_mask(jnp.asarray(scores), k))
    assert np.array_equal(got, want)
    visible = (scores > -INF).sum(-1)
    assert np.array_equal(got.sum(-1), np.minimum(k, visible))
    assert not got[scores == -INF].any()


def test_a_row_keeps_the_lower_positions_of_a_tie_and_only_as_many_as_it_is_owed():
    """Spelled out, beside the comparison with the sorted body: row 0's
    threshold is 2.0 with three keys above it, so of the 2.0s the first
    k - 3 by position; row 1 has no tie and must not be touched by row 0's."""
    k = 6
    scores = np.zeros((8, 128), np.float32)
    scores[0, [100, 3, 50]] = 9.0
    scores[0, [90, 10, 70, 20, 60]] = 2.0
    scores[1] = np.arange(128, dtype=np.float32)
    got = np.asarray(topk_mask.counted_mask(jnp.asarray(scores), k))
    assert np.flatnonzero(got[0]).tolist() == [3, 10, 20, 50, 60, 100]
    assert np.flatnonzero(got[1]).tolist() == list(range(122, 128))
    assert np.flatnonzero(got[2]).tolist() == list(range(k))      # all tied at zero


@pytest.mark.parametrize("rows,keys,body", [
    # what the GLM-5 cell sends: a layer of the decode step, the blocks of an
    # 8,192-token prefill's three later causal groups, of a 4,096-token one's
    (32, 10240, "counted"),
    (64, 4096, "counted"), (64, 6144, "counted"), (64, 8192, "counted"),
    (128, 3072, "counted"), (128, 4096, "counted"),
    # tier-1's tiny and ragged runs; a group of no more keys than k (which
    # returns what is visible before either body); a row past fast memory
    (3, 40, "sorted"), (8, 40, "sorted"), (21, 128, "sorted"), (1, 10240, "sorted"),
    (64, 2048, "sorted"), (8, 1 << 20, "sorted"),
])
def test_the_body_is_named_by_the_shape(rows, keys, body):
    assert topk_mask.implementation(rows, keys, 2048) == body


def test_a_block_too_large_for_one_step_is_cut_by_rows():
    """128 rows of 16,384 keys are 8 MiB: the kernel takes them 32 rows a
    grid step (2 MiB), not at all where 8 rows do not fit."""
    assert topk_mask._rows_a_step(128, 16384) == 32
    assert topk_mask._rows_a_step(64, 8192) == 64
    assert topk_mask._rows_a_step(24, 65536) == 8
    assert topk_mask._rows_a_step(8, 1 << 17) == 0
    scores = np.random.default_rng(5).standard_normal((16, 128)).astype(np.float32)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(topk_mask, "STEP_BYTES", 8 * 128 * 4)     # two grid steps
        got = topk_mask.counted_mask(jnp.asarray(scores), 9)
    assert np.array_equal(got, topk_mask.sorted_mask(jnp.asarray(scores), 9))


def test_the_models_selection_takes_the_kernel_where_the_shape_allows():
    """``llama._select_mask`` has no argument but the scores and k: the
    traced program holds the kernel for whole tiles and none for a ragged
    block."""
    def kernels(rows, keys):
        text = str(jax.make_jaxpr(lambda s: llama._select_mask(s, 8))(
            jnp.zeros((rows, keys), jnp.float32)))
        return text.count("pallas_call"), text.count("top_k")

    assert kernels(8, 128) == (1, 0)
    assert kernels(3, 40) == (0, 1)
    assert kernels(8, 8) == (0, 0)      # no more keys than k: what is visible


def test_the_kernel_refuses_what_its_tiling_does_not_take():
    with pytest.raises(ValueError, match="whole"):
        topk_mask.counted_mask(jnp.zeros((3, 128), jnp.float32), 4)
    with pytest.raises(ValueError, match="more keys than k"):
        topk_mask.counted_mask(jnp.zeros((8, 128), jnp.float32), 128)
