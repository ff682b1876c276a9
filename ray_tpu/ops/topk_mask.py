"""The exact top-k of every row as a mask, by counting instead of sorting.

    mask[q, t] = scores[q, t] is one of row q's k largest visible scores

``scores`` (Q, T) float32 are an indexer's scores of Q queries over T keys
(``models/llama.py:_select_mask``: GLM-5's decode step, one row a cache row,
and a prefill's blocks of queries), ``-inf`` at the keys a query may not
see.  The contract is ``_select_mask``'s: EXACTLY k keys where more than k
are visible, ties at the k-th value to the lower index (as ``lax.top_k``
orders them), every visible key where at most k are, never a key at
``-inf``; ``-0.0`` equals ``0.0``.

Two bodies, chosen from the operand's SHAPE in ONE place
(``implementation``):

* ``counted`` — the Pallas kernel ``topk_mask`` (the custom call shows as
  ``topk_mask.N`` on the trace's op line).  Each float32 becomes the int32
  whose integer order is the floats' order (``ordered_keys``), once, into
  fast memory.  The k-th largest key is then built bit by bit from the top:
  a round asks "how many keys are >= the candidate?" — a compare and a row
  sum over a block that never leaves VMEM — and keeps the bit where the
  answer is at least k.  No score is moved, no index is produced, nothing is
  approximate: the threshold is the float ``lax.top_k(scores, k)[0][:, -1]``
  returns.  A row that holds more keys AT the threshold than it is owed (a
  tie at the k-th value) gets the tie rule by the same counting, over the
  bits of the POSITION: the largest P with ``count(tied & t < P) <= need``.
  That search runs only in a block in which some row has such a tie
  (``pl.when``); float32 scores of a trained or a random indexer almost
  never do.
* ``sorted`` — plain XLA: ``lax.top_k`` for the k-th value, a running count
  (``cumsum``) for the tie rule.  Everything the kernel's tiling does not
  take: rows that are no whole number of 8-row sublanes, keys that are no
  whole number of 128-lane tiles (tier-1's tiny and ragged runs), a row too
  long for fast memory.

The same set either way, bit for bit.

What was swept (a v5e, PR 47, call 1; ms a block, a ``lax.map`` over 48
blocks in one program as a prefill's blocks run, k = 2,048, rows seeing
between half and all of the keys and one of them fewer than k; the loop
alone — the block sliced, one compare, the mask written — is 0.015-0.018 of
every number; "ties": scores drawn from four values, so every block takes
the position search; every variant equal to the sorted body bit for bit):

    rows a grid step x bits a round          32 x 10,240   64 x 8,192    128 x 4,096
    ``lax.top_k`` + ``cumsum``               0.3445        0.4213        0.2208
    8 rows, 1 bit, unrolled / looped         .0435 / .0439 .0522 / .0545 .0776 / .0807
    8 rows, 2 bits                           .0357 / .0339 .0464 / .0455 .0592 / .0610
    16 rows, 1 bit                           .0305 / .0302 .0378 / .0410 .0504 / .0547
    16 rows, 2 bits                          .0305 / .0297 .0370 / .0391 .0436 / .0464
    32 rows, 1 bit                           .0257 / .0268 .0347 / .0359 .0402 / .0430
    32 rows, 2 bits                          .0277 / .0272 .0355 / .0356 .0398 / .0407
    64 rows, 1 bit                                         .0297 / .0330 .0355 / .0361
    64 rows, 2 bits                                        .0329 / .0341 .0360 / .0370
    128 rows, 1 bit                                                      .0299 / .0319
    128 rows, 2 bits                                                     .0347 / .0353
    the whole block, 4 bits, looped          .0379         .0554         .0550
    ties: 8 rows, 1 bit                      .0539 / .0565 .0717 / .0736 .1042 / .1107
    ties: **the whole block, 1 bit**         .0303 / **.0346** .0381 / **.0410** .0364 / **.0395**
    compile, the whole block, 1 bit (s)      3.2 / 0.4     4.8 / 0.7     5.7 / 0.6

and the cell's other three shapes, the whole block, 1 bit, unrolled / looped
(sorted): 64 x 4,096 .0234 / .0255 (.2511), 64 x 6,144 .0258 / .0272
(.4245), 128 x 3,072 .0252 / .0287 (.1781).  A round's fixed cost — the
candidate broadcast along the lanes, the lane reduction of the count, the
select — is paid a grid step, so the WHOLE block a step wins at every shape
(``STEP_BYTES`` holds the cell's largest, 64 x 8,192 and 128 x 4,096);
counting 2**b - 1 candidates against one read of the block saves reads and
costs compares, which is a gain only where steps are small.  The rounds are
a ``fori_loop``, not 32 copies of their body: 0.001-0.003 ms a block dearer
(under half a percent of a prefill, nothing of a step) for a tenth of the
compile time, which is paid once a causal group in every prefill program.
Without the loop's own cost the kernel takes 0.010-0.016 ms where the sort
and the running count took 0.16-0.41.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.flash_attention import _interpret

#: bytes of one grid step's block of scores, at most (the keys' scratch and
#: the mask are as large again, scores and mask in two buffers each)
STEP_BYTES = 2 << 20
#: the int32 key of ``-inf``: every visible score's key is above it
_NEG_INF_KEY = int(np.array(-np.inf, np.float32).view(np.int32)) ^ 0x7FFFFFFF
_INT32_MIN = -(1 << 31)


def _rows_a_step(rows: int, keys: int) -> int:
    """Rows of one grid step of the kernel over a (rows, keys) block: the
    whole block where it fits ``STEP_BYTES``, else the largest whole number
    of sublane tiles that divides ``rows`` and fits; 0 where the kernel does
    not take the shape."""
    if rows % 8 or keys % 128:
        return 0
    fit = STEP_BYTES // (4 * keys) // 8 * 8
    return next((n for n in range(min(rows, fit), 0, -8) if rows % n == 0), 0)


def implementation(rows: int, keys: int, k: int) -> str:
    """Which body ``topk_mask`` traces for (rows, keys) scores: ``"counted"``
    — the kernel — for whole sublane tiles of rows over whole lane tiles of
    keys, a step's rows inside fast memory; else ``"sorted"``.  (``keys <=
    k`` needs neither: the mask is what is visible.)"""
    return "counted" if keys > k and _rows_a_step(rows, keys) else "sorted"


def ordered_keys(scores):
    """float32 -> int32 whose signed order is the floats' order: a
    non-negative float's bits as they are, a negative one's with all but the
    sign bit flipped.  What compares equal to zero is made ``0.0`` first —
    ``-0.0``, and a subnormal wherever the device's own comparison flushes
    it, as XLA's on the CPU does: the keys order what ``>`` and ``==``
    order.  ``-inf`` maps below every other float but a NaN with its sign
    bit set; a NaN with it clear maps above ``+inf``."""
    bits = lax.bitcast_convert_type(
        jnp.where(scores == 0.0, 0.0, scores), jnp.int32
    )
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _kernel(scores_ref, mask_ref, key_ref, *, k):
    """scores (rows, T) float32 -> mask (rows, T) int32 0 / 1; key scratch
    (rows, T) int32."""
    rows, T = key_ref.shape
    key_ref[...] = ordered_keys(scores_ref[...])

    def count(hit):  # (rows, T) bool -> (rows, 1) int32
        return jnp.sum(hit.astype(jnp.int32), axis=-1, keepdims=True)

    def threshold_round(i, kth):  # the next bit from the top: do k keys reach it?
        step = jnp.int32(1) << (31 - i)
        reached = count(key_ref[...] >= kth + step) >= k
        return kth + reached.astype(jnp.int32) * step

    # the k-th largest key: the largest value that at least k keys reach,
    # built in the keys' order from int32's minimum up (additions wrap)
    kth = lax.fori_loop(
        0, 32, threshold_round, jnp.full((rows, 1), _INT32_MIN, jnp.int32)
    )
    # a row with fewer than k visible keys found -inf's key: all above it
    floor = jnp.maximum(kth, _NEG_INF_KEY + 1)
    at_least = key_ref[...] >= floor
    excess = count(at_least) - k
    mask_ref[...] = at_least.astype(jnp.int32)

    @pl.when(jnp.max(excess) > 0)
    def _ties():
        # rows that hold more keys AT the floor than they are owed keep
        # the ``need`` of lowest position: the largest ``end`` that no more
        # than ``need`` of them lie before, bit by bit again
        tied = key_ref[...] == floor
        need = count(tied) - excess
        t = lax.broadcasted_iota(jnp.int32, (rows, T), 1)
        bits = T.bit_length()

        def position_round(i, end):
            step = jnp.int32(1) << (bits - 1 - i)
            fits = count(tied & (t < end + step)) <= need
            return end + fits.astype(jnp.int32) * step

        end = lax.fori_loop(
            0, bits, position_round, jnp.zeros((rows, 1), jnp.int32)
        )
        keep = (key_ref[...] > floor) | (tied & (t < end))
        mask_ref[...] = keep.astype(jnp.int32)


def counted_mask(scores, k: int):
    """The kernel.  scores (Q, T) float32, Q a whole number of 8, T of 128,
    T > k -> (Q, T) bool."""
    Q, T = scores.shape
    n = _rows_a_step(Q, T)
    if not n or T <= k:
        raise ValueError(
            f"the counted top-k wants whole (8, 128) tiles of scores, a row "
            f"inside {STEP_BYTES} bytes and more keys than k: scores "
            f"{scores.shape}, k {k}"
        )
    block = pl.BlockSpec((n, T), lambda i: (i, 0))
    mask = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=(Q // n,),
        in_specs=[block],
        out_specs=block,
        scratch_shapes=[pltpu.VMEM((n, T), jnp.int32)],
        out_shape=jax.ShapeDtypeStruct((Q, T), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=8 * STEP_BYTES,
        ),
        interpret=_interpret(),
        name="topk_mask",
    )(scores)
    return mask != 0


def sorted_mask(scores, k: int):
    """The plain body.  scores (Q, T) float32, T > k -> (Q, T) bool."""
    visible = scores > -jnp.inf
    kth = lax.top_k(scores, k)[0][:, -1:]
    above = scores > kth
    tied = (scores == kth) & visible
    need = k - above.sum(-1, keepdims=True, dtype=jnp.int32)
    return above | (tied & (jnp.cumsum(tied, axis=-1, dtype=jnp.int32) <= need))


def topk_mask(scores, k: int):
    """(Q, T) bool: for each row the ``k`` keys of largest score, exactly k
    where more than k are visible (ties at the k-th value to the lower
    index), every visible key where at most k are.  ``scores`` (Q, T)
    float32 holds -inf at the keys a row may not see; a NaN is outside the
    contract (the kernel orders it by its bits: ``ordered_keys``)."""
    Q, T = scores.shape
    if T <= k:
        return scores > -jnp.inf
    if implementation(Q, T, k) == "counted":
        return counted_mask(scores, k)
    return sorted_mask(scores, k)
