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
  as ``latent_decode.N`` on the trace's op line), a flash-decoding body:
  each row's VISIBLE part of the cache streams through fast memory once,
  the selection is a mask on the scores, running max / sum / accumulator
  in float32 scratch.  The schedule (PR 33) is a list of live work and
  nothing else: the cache is fetched in blocks of ``BLOCK_KEYS`` keys, a
  row's live blocks are those up to the one that holds ``pos[r]``, and an
  ITEM of the list is up to ``ITEM_BLOCKS`` of them in a run — one copy,
  one pair of matmuls.  One grid step a row; the cache stays in HBM
  (``pl.ANY``) and the kernel copies item after item into a ring of
  ``COPIES_IN_FLIGHT + 1`` slots, the copies that many items ahead of the
  matmuls and running on from one row's last item into the next row's
  first, so a row change waits for nothing.  XLA makes R + 1 int32 in
  front of the call (``_work_list``: where each row's items begin);
  ``layer`` and those are scalar-prefetch operands.  A block behind
  ``pos`` is neither fetched nor computed; no slice of the cache is ever
  made in front of the call.  Keys behind ``pos`` inside its block are
  read and weighted by zero: they hold zeros or an earlier request's rows.
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

What the schedule's three numbers were measured against (a v5e, PR 33;
ms a call, the kernel alone in a loop over the layers whose own cost is
0.008, at BOTH cells' shapes: JoyAI 32 rows x 2 x 32 query rows, a 41 x
32 x 4,096 x 640 bf16 cache, rows at 0.5k-2.9k keys, 1,702 on average =
69.7 MB visible a call; GLM-5 32 x 64 query rows, 6 x 32 x 10,240 x 640,
rows at 4k-10k keys, 7,090 on average = 290 MB):

    schedule                                        JoyAI      GLM-5
    PR 31's grid (rows, T // 1,024), two buffers    0.189      0.483
      every row inside its first block (fixed cost) 0.131      0.145
    a grid over the live items, blocks of
      128 / 256 / 512 / 1,024, two buffers          0.289 / 0.202 / 0.157 / 0.137
    this ring, items of ONE block, 3 in flight
      128 / 256 / 512 / 1,024 keys                  0.259 / 0.171 / 0.121 / 0.134
                                                    0.929 / 0.595 / 0.414 / 0.429
    blocks of 256 in items of up to 2, 3 in flight  0.121      0.406
    blocks of 256 in items of up to 4,
      1 / 2 / 3 in flight                           0.131 / 0.114 / **0.114**
                                                    0.433 / 0.406 / **0.405**
    blocks of 512 in items of up to 2, 3 in flight  0.121      0.414
    blocks of 128 in items of up to 8, 3 in flight  0.111      (the mask's load does not compile)
      every row inside its first block, 256 x 4     0.040      0.042
      every row full (3,600 / 10,239 keys)          0.238 -> 0.222      0.576 -> 0.570

What an ITEM costs decides it.  Its chain of matmul, row maximum, exp,
sum, matmul takes about 0.36 us before the first key whether 128 or
1,024 follow (blocks of 256: 229 items a JoyAI call one by one, 70 in
runs of 4, 57 us apart), and 256 keys arrive in 0.45 us: under 512 keys
an item the matmuls, not the copies, set the pace, which is why PR 31's
sweep chose 1,024 with the grid's 0.37 us a step on top.  A block is
what is FETCHED and what ``keys_read`` counts; an item is what is
COMPUTED ON at once; cut apart, 256-key blocks read 1.077 / 1.018 times
what is visible (1,024: 1.298 / 1.079) at 1,024-key items' pace, 716-736
GB/s of what is read where the rows are long, 88-90% of the chip's 819.
At the cells' sizes an item's zeros-weighted tail is all that differs from
PR 31's blocks: results equal its bit for bit.  A second copy in flight is
worth 13-15%, a third nothing measurable at these shapes (it is kept: 1.3
MB of fast memory against a late copy).  A grid over the items (shape
(a) of ISSUE 33) is the ring with one copy in flight and one-block items,
to the microsecond (0.279 / 0.200 / 0.157 / 0.136): jax 0.9.0's TPU
lowering holds a grid's operand to two buffers (``pl.Buffered(n)``, n >
2, is refused) and a BlockSpec's block to one length.

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
#: keys per block the streamed body FETCHES: a row's live blocks are those
#: up to the one that holds its last visible key.  256 reads 1.077 (JoyAI's
#: rows, 1,702 keys on average) / 1.018 (GLM-5's, 7,090) times what is
#: visible, 1,024 1.298 / 1.079; 128 would read 1.037 and saves 3 us a JoyAI
#: call, but the selection kernel's mask load does not compile at it (the
#: module's text has the sweep)
BLOCK_KEYS = 256
#: blocks an ITEM of the work list is long, at most: one copy, one pair of
#: matmuls.  1,024 keys an item hide its 0.36 us of fixed cost behind the
#: copy; 512 cost JoyAI 0.121 against 0.114 ms a call
ITEM_BLOCKS = 4
#: copies on their way while an item is computed on: 1 / 2 / 3 = 0.131 /
#: 0.114 / 0.114 (JoyAI), 0.433 / 0.406 / 0.405 (GLM-5) ms a call
COPIES_IN_FLIGHT = 3
#: the longest cache the streamed body takes.  32 rows, every one at the
#: same ``pos`` (ms a layer, same call): streamed 0.250 / 0.374 / 0.621 /
#: 0.714 / 1.256 / 1.750 / 2.211 at 2,048 / 4,096 / 8,192 / 10,240 /
#: 16,384 / 24,576 / 32,768 visible keys (0.12 + 2.0 ns a key a row; PR 31's
#: grid: the work list since PR 33 costs 0.03 + 1.6 ns), gathered 1.10
#: whatever ``pos``: they cross at 14,600 VISIBLE keys, which
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


def _work_list(last, block: int, span: int):
    """The call's live work as the kernel walks it.  Row r's live blocks
    are 0 .. ``last[r] // block``; an item is up to ``span`` of them in a
    run, so a row's items are ``span`` blocks long but its last, which ends
    with the row's last live block; the items stream row after row.  ->
    ``first`` (R + 1,): the index of each row's first item, ``first[R]``
    their number.  What item i of row r is follows from ``first`` and
    ``last`` by scalar arithmetic in the kernel: R + 1 int32, made by XLA in
    front of the call."""
    ends = jnp.cumsum((last // block + span) // span, dtype=jnp.int32)  # (R,)
    return jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])


def _accumulate(qq_ref, rows, keep, m_ref, l_ref, acc_ref, scale, latent):
    """One item's keys, ``rows`` (keys, W), into the running max / sum /
    accumulator.  ``keep``: what of the (query rows, keys) scores counts."""
    s = lax.dot_general(
        qq_ref[...], rows, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale                                               # (H, keys)
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


def _stream(keep, layer_ref, first_ref, last_ref, qq_ref, ckv_ref, mix_ref,
            ring, arrived, head_ref, m_ref, l_ref, acc_ref, *, block, scale, latent):
    """Grid step r: row r's items ``first[r] .. first[r + 1]`` through the
    running softmax.  ``ckv_ref`` is the whole cache where it lies (HBM);
    item i's blocks come by ONE copy into slot ``i % depth`` of ``ring``
    (depth, span x block, W), started ``depth - 1`` items ahead of the one
    computed on, whatever row it belongs to (``head_ref``: the row of the
    item last sent for): the ring runs on through the rows' hand-over, so
    only the call's first copies are waited for with nothing to do.  A
    copy's and a matmul's length are static, so an item takes one of
    ``span`` branches by its number of blocks.  ``keep(j, shape)``: what of
    the scores of the row's item j counts."""
    r, rows = pl.program_id(0), pl.num_programs(0)
    depth, span = ring.shape[0], ring.shape[1] // block
    lo, hi, live = first_ref[r], first_ref[r + 1], first_ref[rows]

    def by_length(i, row, then):
        left = last_ref[row] // block + 1 - (i - first_ref[row]) * span
        blocks = jnp.minimum(left, span)
        for n in range(1, span + 1):
            pl.when(blocks == n)(functools.partial(then, n * block))

    def copy(i, row, keys):
        at = pl.multiple_of((i - first_ref[row]) * (span * block), block)
        return pltpu.make_async_copy(
            ckv_ref.at[layer_ref[0], row, pl.ds(at, keys)],
            ring.at[i % depth, pl.ds(0, keys)], arrived.at[i % depth],
        )

    def send_for(i):  # items follow each other: the same row again, or the next
        row = head_ref[0]
        row = row + (i >= first_ref[row + 1]).astype(jnp.int32)
        head_ref[0] = row
        by_length(i, row, lambda keys: copy(i, row, keys).start())

    @pl.when(r == 0)
    def _first_copies():
        head_ref[0] = 0
        for i in range(depth - 1):
            pl.when(i < live)(functools.partial(send_for, i))

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def item(i, carry):
        ahead = i + depth - 1
        pl.when(ahead < live)(functools.partial(send_for, ahead))  # into item i - 1's slot

        def arrived_keys(keys):
            copy(i, r, keys).wait()
            _accumulate(qq_ref, ring[i % depth, pl.ds(0, keys)],
                        functools.partial(keep, i - lo), m_ref, l_ref, acc_ref,
                        scale, latent)

        by_length(i, r, arrived_keys)
        return carry

    lax.fori_loop(lo, hi, item, 0)
    mix_ref[...] = (acc_ref[...] / l_ref[...]).astype(mix_ref.dtype)


def _kernel(layer_ref, first_ref, last_ref, qq_ref, ckv_ref, mask_ref, mix_ref,
            *scratch, **kw):
    """qq (H, W), mask (items a row, span x block) int32: the row's
    selection, an item a line, mix (H, latent)."""
    def keep(j, shape):
        return mask_ref[pl.ds(j, 1), pl.ds(0, shape[1])] != 0

    _stream(keep, layer_ref, first_ref, last_ref, qq_ref, ckv_ref, mix_ref,
            *scratch, **kw)


def _visible_kernel(layer_ref, first_ref, last_ref, visible_ref, qq_ref, ckv_ref,
                    mix_ref, *scratch, queries, **kw):
    """qq (Q x H, W) — query k's heads are rows [k H, (k + 1) H) — and no
    mask operand: query k of row r sees the keys t <=
    ``visible_ref[r * Q + k]``, non-decreasing in k."""
    r = pl.program_id(0)
    item_keys = scratch[0].shape[1]
    heads = qq_ref.shape[0] // queries

    def keep(j, shape):
        t = j * item_keys + lax.broadcasted_iota(jnp.int32, shape, 1)
        row = lax.broadcasted_iota(jnp.int32, shape, 0)
        limit = jnp.full(shape, visible_ref[r * queries], jnp.int32)
        for k in range(1, queries):
            limit = jnp.where(row >= k * heads, visible_ref[r * queries + k], limit)
        return t <= limit

    _stream(keep, layer_ref, first_ref, last_ref, qq_ref, ckv_ref, mix_ref,
            *scratch, **kw)


def _streamed_call(kernel, name, qq, ckv, layer, last, scalars=(), mask=None,
                   *, latent):
    """The ``pallas_call`` both streamed kernels are: grid (rows,), qq
    (R, N, W) a row a step, ``ckv`` (L, R, T, W) left where it lies,
    ``last`` (R,) each row's last key to read; further scalar-prefetch
    operands ``scalars``; ``mask`` (R, T) or none, handed over an item a
    line."""
    R, N, W = qq.shape
    T = ckv.shape[2]
    block = BLOCK_KEYS
    span = min(ITEM_BLOCKS, T // block)
    # a position outside the cache must not become a copy outside it
    last = jnp.clip(last.astype(jnp.int32), 0, T - 1)
    lines = -(-T // (span * block))                     # items a row, at most
    operands = []
    if mask is not None:
        mask = jnp.pad(mask, ((0, 0), (0, lines * span * block - T)))
        operands.append(mask.reshape(R, lines, span * block))

    def a_row(*shape):
        return pl.BlockSpec((None, *shape), lambda r, *_: (r,) + (0,) * len(shape))

    return pl.pallas_call(
        functools.partial(kernel, block=block, latent=latent),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3 + len(scalars),
            grid=(R,),
            in_specs=[a_row(N, W), pl.BlockSpec(memory_space=pl.ANY),
                      *(a_row(*x.shape[1:]) for x in operands)],
            out_specs=a_row(N, latent),
            scratch_shapes=[
                pltpu.VMEM((COPIES_IN_FLIGHT + 1, span * block, W), ckv.dtype),
                pltpu.SemaphoreType.DMA((COPIES_IN_FLIGHT + 1,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((N, 1), jnp.float32),
                pltpu.VMEM((N, 1), jnp.float32),
                pltpu.VMEM((N, latent), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((R, N, latent), ckv.dtype),
        compiler_params=pltpu.CompilerParams(
            # the ring of copies runs from one row into the next
            dimension_semantics=("arbitrary",),
        ),
        interpret=_interpret(),
        name=name,
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32), _work_list(last, block, span),
        last, *scalars, qq, ckv, *operands,
    )


def _refuse(qq_rows, ckv, what, got, want):
    if ckv.shape[1] != qq_rows or ckv.shape[2] % BLOCK_KEYS or got != want:
        raise ValueError(
            f"streamed latent attention wants one cache row a query row and "
            f"whole blocks of {BLOCK_KEYS} keys: {qq_rows} query rows, ckv "
            f"{ckv.shape}, {what} {got}"
        )


def latent_decode_attention(qq, ckv, layer, pos, chosen_mask, *, latent: int,
                            scale: float):
    """The streamed body.  qq (R, H, W), ckv (L, R, T, W) whole, layer ()
    int32, pos (R,) int32 the rows' newest positions, chosen_mask (R, T)
    bool the keys each row attends to (at least one of them, none behind
    ``pos``) -> mix (R, H, latent) in ``ckv``'s dtype.  T is a whole
    number of ``BLOCK_KEYS``."""
    R, T = qq.shape[0], ckv.shape[2]
    _refuse(R, ckv, "mask", chosen_mask.shape, (R, T))
    return _streamed_call(
        functools.partial(_kernel, scale=scale), "latent_decode",
        qq, ckv, layer, pos, mask=chosen_mask.astype(jnp.int32), latent=latent,
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
    _refuse(R, ckv, "visible", visible.shape, (R, Q))
    visible = visible.astype(jnp.int32)
    mix = _streamed_call(
        functools.partial(_visible_kernel, scale=scale, queries=Q), "latent_verify",
        qq.reshape(R, Q * H, W), ckv, layer, visible[:, -1],
        scalars=(visible.reshape(R * Q),), latent=latent,
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
