"""A decode step's attention over the latent rows an indexer chose.

    mix[r, h] = softmax_t(scale * qq[r, h] . ckv[layer, r, t])  @  ckv[layer, r, t, :latent]
                over the keys t the selection holds for row r

``qq`` (R, H, W) are the absorbed queries of multi-head latent attention
(``models/llama.py:_latent_attention``: ``[q W_kb | q_rope | 0]``), ``ckv``
(L, B, T, W) the WHOLE latent cache, B = R, one row of width W a token a
layer for all heads: the key is the row, the value its first ``latent``
values.  Scores and softmax in float32, probabilities cast to the cache's
dtype before they meet the values, as every attention body of the model
casts them.

Two bodies, chosen by the cache's length in ONE place (``implementation``):

* ``streamed`` — the Pallas kernel ``latent_decode`` (the custom call shows
  as ``latent_decode.N`` on the trace's op line).  Grid (rows, key blocks),
  a flash-decoding body: each row's VISIBLE part of the cache streams
  through fast memory once, in blocks of ``BLOCK_KEYS`` keys, the selection
  is a mask on the scores, running max / sum / accumulator in float32
  scratch.  ``layer`` and ``pos`` are scalar-prefetch operands and the
  block's index map clamps at the block that holds ``pos[r]``: a block
  behind it is neither fetched (the same block index again is no new copy)
  nor computed (``pl.when``).  No slice of the cache is ever made in front
  of the call.  Keys behind ``pos`` inside the last block are read and
  weighted by zero: they hold zeros or an earlier request's rows.
* ``gathered`` — plain XLA: the chosen rows gathered out of the cache, two
  einsums over them.  One 1,280-byte transfer a chosen row, 15 ns each on a
  v5e whatever ``pos`` (84 GB/s; PERF.md section 6, PR 30).

Which is faster hangs on one number, the share of the visible keys that is
chosen: streaming costs about 2.1 ns a VISIBLE key, gathering 15 ns a CHOSEN
one, so 2,048 of 7,000 stream and 2,048 of 128k gather.  The code sees the
cache's length, an upper bound of what is visible, in a shape
(``MAX_STREAMED_KEYS``; the measurements are in PERF.md section 6, PR 31).

A config WITHOUT an indexer attends to every visible key, and a
speculative step brings SEVERAL queries a row (``visible_decode_attention``:
the draft's verification has two, at ``pos`` and ``pos + 1``).  The same
streamed body, kernel ``latent_verify``: a row's Q queries are Q x H query
rows of one grid step, each with its own visibility ``t <= visible[r, j]``
made from an iota in the kernel — no mask array is built or read — so a
row's blocks stream through fast memory ONCE for all its queries.
``keys_read`` is then a function of the row's LAST query's position: the
whole blocks up to the one that holds ``visible[r, -1]``, fetched once, not
once a query.  A cache that is no whole number of blocks (tier-1's tiny
ones) takes ``dense_decode_attention``, plain XLA over the layer's slab.

Off the chip the kernel runs in Pallas interpret mode (``_interpret`` of
``ops/flash_attention.py``, as its kernels do), so the tests run the very kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.flash_attention import _interpret

NEG_INF = -1e30
#: keys per block of the streamed body.  Swept on a v5e at the serving
#: cell's shapes (32 rows x 64 heads, a 6 x 32 x 10,240 x 640 bf16 cache,
#: rows at 4k-10k keys, 7,090 visible on average; ms a layer): 256 keys
#: 0.762, 512 0.566, **1,024 0.502** (313 MB at 624 GB/s, 76% of the
#: chip's 819), 2,048 0.524; the gathered body 1.101 (PERF.md section 6,
#: PR 31).  A block is 1.3 MB, two of them in flight
BLOCK_KEYS = 1024
#: the longest cache the streamed body takes.  32 rows, every one at the
#: same ``pos`` (ms a layer, same call): streamed 0.250 / 0.374 / 0.621 /
#: 0.714 / 1.256 / 1.750 / 2.211 at 2,048 / 4,096 / 8,192 / 10,240 /
#: 16,384 / 24,576 / 32,768 visible keys (0.12 + 2.0 ns a key a row),
#: gathered 1.10 whatever ``pos``: they cross at 14,600 VISIBLE keys, which
#: a cache of 16,384 holds only in its last rows' last steps.  A Pallas
#: loop of row copies is no third body: one row cannot be cut out of the
#: cache's bf16 tiling (Mosaic: a slice of the position axis must be a
#: multiple of 8), and the aligned 8 rows around a chosen one cost 44 ns
#: a copy with 16-64 in flight, 2.9 ms a layer
MAX_STREAMED_KEYS = 16384


def implementation(cache_len: int) -> str:
    """Which body a decode step over a cache of ``cache_len`` positions
    traces: ``"streamed"`` up to the crossover, in whole blocks, else
    ``"gathered"``."""
    if cache_len <= MAX_STREAMED_KEYS and cache_len % BLOCK_KEYS == 0:
        return "streamed"
    return "gathered"


def keys_read(pos):
    """(R,) int32: latent rows the streamed body fetches for a row whose
    newest position — its LAST query's, where it has several — is ``pos``:
    the whole blocks up to the one that holds ``pos``, once for all the
    row's queries."""
    return (pos // BLOCK_KEYS + 1) * BLOCK_KEYS


def _init(j, m_ref, l_ref, acc_ref):
    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)


def _accumulate(qq_ref, ckv_ref, keep, m_ref, l_ref, acc_ref, scale, latent):
    """One block of keys into the running max / sum / accumulator.
    ``keep``: what of the (query rows, block) scores counts."""
    rows = ckv_ref[...]                                     # (block, W)
    s = lax.dot_general(
        qq_ref[...], rows, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                               # (H, block)
    s = jnp.where(keep(s.shape), s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + lax.dot_general(
        p.astype(rows.dtype), rows[:, :latent], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _finish(j, mix_ref, l_ref, acc_ref):
    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        mix_ref[...] = (acc_ref[...] / l_ref[...]).astype(mix_ref.dtype)


def _kernel(layer_ref, pos_ref, qq_ref, ckv_ref, mask_ref, mix_ref,
            m_ref, l_ref, acc_ref, *, scale, latent):
    """Grid (R, T // block), key blocks innermost.  qq (H, W), ckv
    (block, W), mask (1, block) int32, mix (H, latent)."""
    del layer_ref
    r, j = pl.program_id(0), pl.program_id(1)
    _init(j, m_ref, l_ref, acc_ref)

    @pl.when(j * ckv_ref.shape[0] <= pos_ref[r])
    def _block():
        _accumulate(qq_ref, ckv_ref, lambda shape: mask_ref[...] != 0,
                    m_ref, l_ref, acc_ref, scale, latent)

    _finish(j, mix_ref, l_ref, acc_ref)


def _visible_kernel(layer_ref, visible_ref, qq_ref, ckv_ref, mix_ref,
                    m_ref, l_ref, acc_ref, *, scale, latent, queries):
    """As ``_kernel`` with qq (Q x H, W) — query j's heads are rows [j H,
    (j + 1) H) — and no mask operand: query j of row r sees the keys t <=
    ``visible_ref[r * Q + j]``, non-decreasing in j."""
    del layer_ref
    r, j = pl.program_id(0), pl.program_id(1)
    block = ckv_ref.shape[0]
    heads = qq_ref.shape[0] // queries
    _init(j, m_ref, l_ref, acc_ref)

    def keep(shape):
        t = j * block + lax.broadcasted_iota(jnp.int32, shape, 1)
        row = lax.broadcasted_iota(jnp.int32, shape, 0)
        limit = jnp.full(shape, visible_ref[r * queries], jnp.int32)
        for k in range(1, queries):
            limit = jnp.where(row >= k * heads, visible_ref[r * queries + k], limit)
        return t <= limit

    @pl.when(j * block <= visible_ref[r * queries + queries - 1])
    def _block():
        _accumulate(qq_ref, ckv_ref, keep, m_ref, l_ref, acc_ref, scale, latent)

    _finish(j, mix_ref, l_ref, acc_ref)


def latent_decode_attention(qq, ckv, layer, pos, chosen_mask, *, latent: int,
                            scale: float):
    """The streamed body.  qq (R, H, W), ckv (L, R, T, W) whole, layer ()
    int32, pos (R,) int32 the rows' newest positions, chosen_mask (R, T)
    bool the keys each row attends to (at least one of them, none behind
    ``pos``) -> mix (R, H, latent) in ``ckv``'s dtype.  T is a whole
    number of ``BLOCK_KEYS``."""
    R, H, W = qq.shape
    _, B, T, _ = ckv.shape
    block = BLOCK_KEYS
    if B != R or T % block or chosen_mask.shape != (R, T):
        raise ValueError(
            f"streamed latent attention wants one cache row a query row and "
            f"whole blocks of {block} keys: qq {qq.shape}, ckv {ckv.shape}, "
            f"mask {chosen_mask.shape}"
        )

    def last(j, r, pos_ref):  # the block that holds pos[r], at most
        return jnp.minimum(j, pos_ref[r] // block)

    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, latent=latent),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(R, T // block),
            in_specs=[
                pl.BlockSpec((None, H, W), lambda r, j, layer, pos: (r, 0, 0)),
                pl.BlockSpec(
                    (None, None, block, W),
                    lambda r, j, layer, pos: (layer[0], r, last(j, r, pos), 0),
                ),
                pl.BlockSpec(
                    (None, 1, block),
                    lambda r, j, layer, pos: (r, 0, last(j, r, pos)),
                ),
            ],
            out_specs=pl.BlockSpec(
                (None, H, latent), lambda r, j, layer, pos: (r, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, latent), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((R, H, latent), ckv.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=_interpret(),
        name="latent_decode",
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        pos.astype(jnp.int32),
        qq,
        ckv,
        chosen_mask.astype(jnp.int32)[:, None, :],
    )


def gathered_decode_attention(qq, ckv, layer, chosen, valid, *, latent: int,
                              scale: float):
    """The gathered body.  ``chosen`` (R, K) int32 positions, ``valid``
    (R, K) bool which of them count -> mix (R, H, latent)."""
    rows = jnp.arange(qq.shape[0])
    picked = ckv[layer, rows[:, None], chosen]                  # (R, K, W)
    att = jnp.einsum(
        "rhc,rkc->rhk", qq, picked, preferred_element_type=jnp.float32
    ) * scale
    att = jnp.where(valid[:, None, :], att, NEG_INF)
    probs = jax.nn.softmax(att, axis=-1).astype(ckv.dtype)
    return jnp.einsum("rhk,rkc->rhc", probs, picked[..., :latent])


def visible_decode_attention(qq, ckv, layer, visible, *, latent: int, scale: float):
    """The streamed body over EVERY visible key, Q queries a row.  qq (R,
    Q, H, W), ckv (L, R, T, W) whole, layer () int32, visible (R, Q) int32
    >= 0, non-decreasing along Q: query j of row r attends to the keys t <=
    visible[r, j] -> mix (R, Q, H, latent) in ``ckv``'s dtype.  T is a whole
    number of ``BLOCK_KEYS``.  A row's blocks up to the one that holds
    ``visible[r, -1]`` are fetched once for all Q queries."""
    R, Q, H, W = qq.shape
    _, B, T, _ = ckv.shape
    block = BLOCK_KEYS
    if B != R or T % block or visible.shape != (R, Q):
        raise ValueError(
            f"streamed latent attention wants one cache row a query row and "
            f"whole blocks of {block} keys: qq {qq.shape}, ckv {ckv.shape}, "
            f"visible {visible.shape}"
        )

    def last(j, r, vis_ref):  # the block that holds the row's last visible key
        return jnp.minimum(j, vis_ref[r * Q + Q - 1] // block)

    mix = pl.pallas_call(
        functools.partial(_visible_kernel, scale=scale, latent=latent, queries=Q),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(R, T // block),
            in_specs=[
                pl.BlockSpec((None, Q * H, W), lambda r, j, layer, vis: (r, 0, 0)),
                pl.BlockSpec(
                    (None, None, block, W),
                    lambda r, j, layer, vis: (layer[0], r, last(j, r, vis), 0),
                ),
            ],
            out_specs=pl.BlockSpec(
                (None, Q * H, latent), lambda r, j, layer, vis: (r, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((Q * H, 1), jnp.float32),
                pltpu.VMEM((Q * H, 1), jnp.float32),
                pltpu.VMEM((Q * H, latent), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((R, Q * H, latent), ckv.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=_interpret(),
        name="latent_verify",
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        visible.astype(jnp.int32).reshape(R * Q),
        qq.reshape(R, Q * H, W),
        ckv,
    )
    return mix.reshape(R, Q, H, latent)


def dense_decode_attention(qq, ckv, layer, visible, *, latent: int, scale: float):
    """``visible_decode_attention`` in plain XLA over the layer's whole
    slab, for a cache that is no whole number of blocks."""
    slab = lax.dynamic_index_in_dim(ckv, layer, 0, keepdims=False)  # (R, T, W)
    att = jnp.einsum(
        "rqhc,rtc->rqht", qq, slab, preferred_element_type=jnp.float32
    ) * scale
    seen = jnp.arange(slab.shape[1])[None, None, :] <= visible[:, :, None]
    att = jnp.where(seen[:, :, None, :], att, NEG_INF)
    probs = jax.nn.softmax(att, axis=-1).astype(ckv.dtype)
    return jnp.einsum("rqht,rtc->rqhc", probs, slab[..., :latent])
