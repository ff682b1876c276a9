"""An every-row step's attention over the K/V cache where it lies.

    out[r, j, h] = softmax_t(q[r, j, h] . K[layer, r, t, kv(h)] / sqrt(D))  @  V[layer, r, t, kv(h)]
                   over the keys t <= visible[r, j]

``q`` (R, Sq, H, D) are the queries of a step in which EVERY cache row
brings a token (the one-token decode step) or a few (a block-diffusion
step's block of four): ``models/llama.py:_kv_attention`` with ``slot`` None
and ``Sq <= _STEP_RUN``.  ``k_cache`` / ``v_cache`` (L, B, T, KV x D) are the
WHOLE caches, B = R, a token's KV heads side by side in one row of whole
128-lane tiles (``llama.init_cache``): head ``kv`` is the static lane slice
``[kv D, (kv + 1) D)``, so a block of keys is one contiguous copy and no
relayout stands between the cache as stored and the kernel.  Scores and
running max / sum / accumulator in float32, probabilities cast to the
cache's dtype before they meet V, scale ``1 / sqrt(D)`` on the float32
scores: what ``llama._grouped_attention`` computes, in flash order.

Two bodies, chosen from static shapes in ONE place (``implementation``):

* ``streamed`` — the Pallas kernel ``kv_decode`` (the custom call shows as
  ``kv_decode.N`` on the trace's op line), PR 33's schedule of
  ``ops/latent_decode_attention.py`` over two caches: one grid step a row,
  K and V left in HBM (``pl.ANY``), the row's live blocks of ``BLOCK_KEYS``
  keys — those up to the one that holds its last visible key — walked in
  ITEMS of up to ``item_blocks`` of them (one copy of K, one of V, a pair of
  matmuls a KV head), the copies ``COPIES_IN_FLIGHT`` items ahead in a ring
  that runs on from one row's last item into the next row's first.  All
  ``Sq x G`` query rows of a (row, KV head) share one pass over that head's
  keys, so a row's cache goes through fast memory ONCE however many queries
  or query heads it has; a query's visibility comes from an iota in the
  kernel, so no (R, Sq, T) mask is built or read.  ``layer``, the work list
  (R + 1 int32, ``_work_list``) and ``visible`` are scalar-prefetch
  operands.  A block behind a row's last visible key is neither fetched nor
  computed; a free slot (the engine feeds it ``pos`` 0) costs one block.
  Keys behind ``visible`` inside its block are read and weighted by zero:
  they hold zeros or an earlier request's rows.
* ``slab`` — ``llama._grouped_attention`` over the layer's slab, plain XLA:
  a cache that is no whole number of blocks (tier-1's tiny ones), a head
  that is no whole number of 128-lane tiles, a rolling cache (sliding
  window: slot = position mod T, which the work list's "blocks up to the
  last visible key" does not describe), and every run of ONE row's tokens
  (a prefill, ``slot`` given): ``_kv_attention`` asks only for every-row
  steps.

A layer KIND's step (``llama._kind_attention``: MiMo-V2-Flash's full layers, 4
KV heads of 192 over values of 128, and its window layers, 8 KV heads in 128
rolling slots with a learned sink a query head) runs the same kernel: values
and the key ROW are whole lane tiles, key HEADS need not be — the queries then
arrive block-diagonal over the whole row and one product gives every head's
scores (``_accumulate``); ``sink`` starts each query row's running maximum
and its sum at ``exp(0)``; a window layer's cache is ONE block, every slot a
key of the row once it has 128.  ``implementation`` answers for a kind when
given ``kv_heads`` and ``v_head_dim``.

What the schedule's numbers were measured against (a v5e, PR 37; ms a
call, the kernel alone in a loop over the layers whose own cost is 0.006 -
0.024, at the three shapes that run it: SDAR 32 rows x 4 queries x 32 heads
/ 4 KV x 128, a 48 x 32 x 1,536 x 512 bf16 cache, rows at 260-1,020 keys,
642 visible on average; InternLM2 32 x 1 x 32 / 8 x 128, 16 x 32 x 1,024 x
1,024, rows at 130-510, 321 on average; OLMoE 32 x 1 x 16 / 16 x 128, 12 x
32 x 1,024 x 2,048, the same rows; "first" = every row inside its first
block, "full" = every row at its cache's last key):

    body / schedule (block keys : item bytes :       SDAR            InternLM2        OLMoE
      blocks an item at most : copies in flight)     rows first full rows first full  rows first full
    XLA, cache (L, B, T, KV, D) (PR 36's body)       .286 .286 .286  .247 .246 .246   .393 .393 .394
    XLA, this cache through a reshape to heads       .304 .304 .304  .665 .665 .665   2.82 2.82 2.82
    a chain of matmul / softmax / matmul A HEAD,
      128 : 512 KiB : 4 : 3                          .093 .050 .160  .124 .054 .302   .231 .090 .580
      128 : 512 KiB : 1 : 3                          .198 .047 .416  .128 .052 .312   .231 .089 .580
      128 : 1 MiB : 8 : 3                            .072 .055 .142  .094 .056 .188   .229 .091 .567
    the heads' scores STACKED, one softmax an item
      **128 : 512 KiB : 4 : 3**                      .069 .033 .141  .076 .032 .187   .144 .055 .366
      128 : 1 MiB : 8 : 3                            .069 .037 .141  .076 .034 .188   .145 .055 .366
      128 : 2 MiB : 16 : 3                           .069 .042 .141  .076 .038 .188   .146 .055 .367
      256 : 1 MiB : 4 : 3                            .075 .035 .141  .084 .053 .187   .159 .100 .366
      128 : 512 KiB..4 MiB : 8 : 2                   .069 .038 .141  .076 .035 .187   .145 .055 .365
      128 : 2 MiB : 8 : 1                            .072 .038 .146  .077 .039 .187   .145 .055 .367

What an item's ARITHMETIC costs decided it, not its copies.  A chain a
head — matmul, row maximum, exp, sum, matmul on (8 to 32, keys) scores —
took 0.14-0.27 us an item and head whatever the keys, so 16 heads of one
query row (OLMoE) stood at 53% of the chip's bandwidth and items had to be
1,024 keys long to hide it.  With the KV heads' scores one under the other
((KV x query rows, keys) = 64-128 whole sublane tiles: one maximum, one exp,
one sum an item; only the matmuls go head by head) every shape reads its
LIVE blocks at 660-690 GB/s and a full cache at 715-730 (87-89% of 819),
and items, blocks an item and copies in flight stop mattering: the first
schedule that is no worse anywhere is kept.  Blocks of 128 keys read 1.096
(SDAR) / 1.198 times what is visible at these rows, 256 read 1.196 / 1.323
and cost 8-10% more; a call's fixed cost (every row inside its first
block) is 0.032-0.055 ms.  At OLMoE's one query row a head the kernel
beats XLA's fetch of the slab at the cell's rows (0.144 against 0.393) and
even on a full cache (0.366), so no shape keeps the XLA body for speed.

Off the chip the kernel runs in Pallas interpret mode (``_interpret`` of
``ops/flash_attention.py``, as its kernels do), so the tests run the very kernel.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.flash_attention import _interpret
from ray_tpu.ops.latent_decode_attention import NEG_INF, _work_list

#: keys per block the streamed body FETCHES and ``keys_read`` counts: a
#: row's live blocks are those up to the one that holds its last visible key
BLOCK_KEYS = 128
#: bytes of ONE cache an item may bring at most: an item is as many blocks
#: (up to ``MAX_ITEM_BLOCKS``) as fit, so a wide row (OLMoE: 16 heads, 4 KiB
#: a key) takes shorter items than a narrow one (SDAR: 4 heads, 1 KiB)
ITEM_BYTES = 1 << 19
MAX_ITEM_BLOCKS = 4
#: copies (of K and of V each) on their way while an item is computed on
COPIES_IN_FLIGHT = 3
#: fast memory the kernel may use beside its two rings: the queries and
#: outputs in two buffers, the running state, an item's scores
_VMEM_BESIDE_THE_RINGS = 16 << 20
#: query rows a KV head are padded to whole sublane tiles of the scores
_QUERY_ROWS = 8


def implementation(cache_len: int, head_dim: int, window: int = 0, *,
                   kv_heads: int = 0, v_head_dim: int = 0) -> str:
    """Which body an every-row step over a K/V cache of ``cache_len``
    positions with heads of ``head_dim`` values traces: ``"streamed"`` —
    the kernel — for a full-causal cache of whole blocks and whole-tile
    heads, else ``"slab"``.  ``window``: a MODEL-WIDE window's rolling cache
    (slab).  With ``kv_heads`` and ``v_head_dim`` the question is a layer
    KIND's (``llama._kind_attention``): value heads of whole tiles and a
    key ROW of whole tiles are enough — key heads may start off a tile's
    edge (4 x 192), the kernel then takes the row whole — and a window
    kind's cache of ``window`` rolling slots is one block, all of it a
    row's keys."""
    if kv_heads:
        whole = v_head_dim % 128 == 0 and (kv_heads * head_dim) % 128 == 0
        return "streamed" if whole and cache_len % BLOCK_KEYS == 0 else "slab"
    if not window and cache_len % BLOCK_KEYS == 0 and head_dim % 128 == 0:
        return "streamed"
    return "slab"


def keys_read(last, cache_len: int, head_dim: int, window: int = 0):
    """Keys fetched for a row whose last visible key is ``last`` (an array
    of any shape, numpy or jax): the whole blocks up to the one that holds
    it, once for all the row's queries and heads, where the kernel runs;
    the whole row of the slab otherwise."""
    if implementation(cache_len, head_dim, window) == "streamed":
        return (last // BLOCK_KEYS + 1) * BLOCK_KEYS
    return np.full(np.shape(last), cache_len)


def item_blocks(cache_len: int, row_bytes: int) -> int:
    """Blocks an item of the work list is long, at most, for a cache whose
    key (all KV heads) takes ``row_bytes``."""
    fit = max(1, ITEM_BYTES // (BLOCK_KEYS * row_bytes))
    return min(MAX_ITEM_BLOCKS, fit, cache_len // BLOCK_KEYS)


def _accumulate(q_ref, k_ref, v_ref, slot, keys, keep, m_ref, l_ref, acc_ref, scale, heads):
    """One item's ``keys`` keys in ring slot ``slot`` into the running max
    / sum / accumulator of every KV head at once: the heads' scores stand
    one under the other, (KV x query rows, keys), so the softmax's
    arithmetic is ONE pass over whole tiles and only the matmuls go head
    by head (a chain a head cost 0.14-0.2 us an item each: 16 heads of one
    query row were latency-bound at 53% of the bandwidth).  ``keep``: what
    of the scores counts.  Key heads that start off a lane tile's edge (4 x
    192): the queries arrive BLOCK-DIAGONAL, (KV x query rows, KV x Dk) with
    head kv's rows zero outside its own lanes, and ONE product with the
    whole key row gives every head's scores one under the other — no slice
    of a key head, and no more passes of the MXU over the keys than the
    heads' own products would take."""
    n, Dv = acc_ref.shape[0] // heads, acc_ref.shape[1]

    def of_head(h, ref, D):
        return ref[slot, pl.ds(0, keys), pl.ds(h * D, D)]

    if len(q_ref.shape) == 2:
        s = lax.dot_general(
            q_ref[...], k_ref[slot, pl.ds(0, keys), :], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
    else:
        s = jnp.concatenate([
            lax.dot_general(
                q_ref[h], of_head(h, k_ref, q_ref.shape[2]), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) for h in range(heads)
        ], axis=0) * scale                                  # (KV x query rows, keys)
    s = jnp.where(keep, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.concatenate([
        lax.dot_general(
            p[h * n:(h + 1) * n].astype(v_ref.dtype), of_head(h, v_ref, Dv),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        ) for h in range(heads)
    ], axis=0)


def _kernel(layer_ref, first_ref, last_ref, visible_ref, q_ref, *rest,
            block, scale, queries, group, heads, sunk):
    """Grid step r: row r's items ``first[r] .. first[r + 1]`` through the
    running softmax of each KV head.  q (KV, query rows, D): row j G + g of
    head kv is query j of query head kv G + g (zero rows behind them, to a
    whole tile) — or block-diagonal, (KV x query rows, KV x D)
    (``_accumulate``); ``k_hbm`` / ``v_hbm`` the whole caches where they lie; item
    i's blocks come by one copy each into slot ``i % depth`` of ``ring_k`` /
    ``ring_v`` (depth, span x block, KV x D), started ``depth - 1`` items
    ahead of the one computed on, whatever row it belongs to (``head_ref``:
    the row of the item last sent for).  A copy's and a matmul's length are
    static, so an item takes one of ``span`` branches by its number of
    blocks.  Query j of the row sees the keys t <= ``visible_ref[r queries
    + j]``, non-decreasing in j.  ``sunk``: ``sink_ref`` (KV x query rows,
    1) float32 stands in the operands behind q, a learned number a query
    row that joins its softmax's denominator and carries no value: the
    running maximum starts there and the running sum at ``exp(0)``."""
    sink_ref = rest[0] if sunk else None
    (k_hbm, v_hbm, out_ref, ring_k, ring_v, arrived, head_ref,
     m_ref, l_ref, acc_ref) = rest[sunk:]
    r, rows = pl.program_id(0), pl.num_programs(0)
    depth, span = ring_k.shape[0], ring_k.shape[1] // block
    lo, hi, live = first_ref[r], first_ref[r + 1], first_ref[rows]

    def by_length(i, row, then):
        left = last_ref[row] // block + 1 - (i - first_ref[row]) * span
        blocks = jnp.minimum(left, span)
        for n in range(1, span + 1):
            pl.when(blocks == n)(functools.partial(then, n * block))

    def copies(i, row, keys):
        at = pl.multiple_of((i - first_ref[row]) * (span * block), block)
        return [
            pltpu.make_async_copy(
                hbm.at[layer_ref[0], row, pl.ds(at, keys)],
                ring.at[i % depth, pl.ds(0, keys)], arrived.at[n, i % depth],
            )
            for n, (hbm, ring) in enumerate(((k_hbm, ring_k), (v_hbm, ring_v)))
        ]

    def start(i, row, keys):
        for copy in copies(i, row, keys):
            copy.start()

    def send_for(i):  # items follow each other: the same row again, or the next
        row = head_ref[0]
        row = row + (i >= first_ref[row + 1]).astype(jnp.int32)
        head_ref[0] = row
        by_length(i, row, functools.partial(start, i, row))

    @pl.when(r == 0)
    def _first_copies():
        head_ref[0] = 0
        for i in range(depth - 1):
            pl.when(i < live)(functools.partial(send_for, i))

    if sunk:
        m_ref[...] = sink_ref[...]
        l_ref[...] = jnp.ones_like(l_ref)
    else:
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    n = m_ref.shape[0] // heads
    # score row h n + j G + g is query j of head (h, g); the tile's zero
    # rows behind them take the last query's limit
    row_of = lax.broadcasted_iota(jnp.int32, (heads * n, 1), 0) % n
    limit = jnp.full((heads * n, 1), visible_ref[r * queries], jnp.int32)
    for j in range(1, queries):
        limit = jnp.where(row_of >= j * group, visible_ref[r * queries + j], limit)

    def item(i, carry):
        ahead = i + depth - 1
        pl.when(ahead < live)(functools.partial(send_for, ahead))  # into item i - 1's slot

        def arrived_keys(keys):
            for copy in copies(i, r, keys):
                copy.wait()
            t = (i - lo) * (span * block) + lax.broadcasted_iota(
                jnp.int32, (heads * n, keys), 1
            )
            _accumulate(q_ref, ring_k, ring_v, i % depth, keys, t <= limit,
                        m_ref, l_ref, acc_ref, scale, heads)

        by_length(i, r, arrived_keys)
        return carry

    lax.fori_loop(lo, hi, item, 0)
    out = (acc_ref[...] / l_ref[...]).astype(out_ref.dtype)
    for h in range(heads):
        out_ref[h] = out[h * n:(h + 1) * n]


def kv_decode_attention(q, k_cache, v_cache, layer, visible, sink=None):
    """The streamed body.  q (R, Sq, H, D), k_cache (L, R, T, KV x D) and
    v_cache (L, R, T, KV x Dv) whole, layer () int32, visible (R, Sq) int32
    >= 0, non-decreasing
    along Sq: query j of row r attends to the keys t <= visible[r, j] -> (R,
    Sq, H, Dv) in the cache's dtype.  T is a whole number of ``BLOCK_KEYS``;
    D and Dv are whole 128-lane tiles, or (``implementation``'s rule for a
    layer kind) Dv and the key ROW are.  ``sink`` (H,) float32: a learned
    number a query head that joins the softmax's denominator and carries no
    value.  A row's blocks up to the one that holds
    ``visible[r, -1]`` are fetched once for all its queries and heads."""
    R, Sq, H, D = q.shape
    L, B, T, row = k_cache.shape
    KV = row // D
    Dv = v_cache.shape[-1] // KV
    if (implementation(T, D, kv_heads=KV, v_head_dim=Dv) != "streamed" or B != R
            or KV * D != row or H % KV or v_cache.shape != (L, B, T, KV * Dv)
            or visible.shape != (R, Sq)):
        raise ValueError(
            f"streamed K/V attention wants one cache row a query row, whole "
            f"blocks of {BLOCK_KEYS} keys, key rows and value heads of whole "
            f"128-lane tiles: q {q.shape}, caches {k_cache.shape} / "
            f"{v_cache.shape}, visible {visible.shape}"
        )
    G = H // KV
    block = BLOCK_KEYS
    span = item_blocks(T, row * k_cache.dtype.itemsize)
    depth = COPIES_IN_FLIGHT + 1
    N = -(-Sq * G // _QUERY_ROWS) * _QUERY_ROWS
    # (R, Sq, KV, G, D) -> (R, KV, Sq x G, D): a head's query rows together
    qq = q.reshape(R, Sq, KV, G, D).transpose(0, 2, 1, 3, 4).reshape(R, KV, Sq * G, D)
    qq = jnp.pad(qq.astype(k_cache.dtype), ((0, 0), (0, 0), (0, N - Sq * G), (0, 0)))
    visible = visible.astype(jnp.int32)
    # a position outside the cache must not become a copy outside it
    last = jnp.clip(visible[:, -1], 0, T - 1)

    def a_row(*shape):
        return pl.BlockSpec((None, *shape), lambda r, *_: (r,) + (0,) * len(shape))

    q_spec = a_row(KV, N, D)
    if D % 128:  # block-diagonal: head kv's rows against its own lanes alone
        qq = jnp.einsum("rknd,kj->rknjd", qq, jnp.eye(KV, dtype=qq.dtype))
        qq, q_spec = qq.reshape(R, KV * N, row), a_row(KV * N, row)
    operands, in_specs = [qq], [q_spec]
    if sink is not None:
        # (H,) -> a number a query row of every KV head, 0 on the tile's
        # zero rows: (KV x N, 1), the same for every cache row
        rows = jnp.tile(sink.astype(jnp.float32).reshape(KV, 1, G), (1, Sq, 1))
        rows = jnp.pad(rows.reshape(KV, Sq * G), ((0, 0), (0, N - Sq * G)))
        operands.append(rows.reshape(KV * N, 1))
        in_specs.append(pl.BlockSpec((KV * N, 1), lambda r, *_: (0, 0)))

    out = pl.pallas_call(
        functools.partial(_kernel, block=block, scale=1.0 / math.sqrt(D),
                          queries=Sq, group=G, heads=KV, sunk=sink is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(R,),
            in_specs=[*in_specs, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=a_row(KV, N, Dv),
            scratch_shapes=[
                pltpu.VMEM((depth, span * block, row), k_cache.dtype),
                pltpu.VMEM((depth, span * block, KV * Dv), v_cache.dtype),
                pltpu.SemaphoreType.DMA((2, depth)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((KV * N, 1), jnp.float32),
                pltpu.VMEM((KV * N, 1), jnp.float32),
                pltpu.VMEM((KV * N, Dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((R, KV, N, Dv), k_cache.dtype),
        compiler_params=pltpu.CompilerParams(
            # the ring of copies runs from one row into the next
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * depth * span * block * row * k_cache.dtype.itemsize
            + _VMEM_BESIDE_THE_RINGS,
        ),
        interpret=_interpret(),
        name="kv_decode",
    )(
        jnp.reshape(layer, (1,)).astype(jnp.int32), _work_list(last, block, span),
        last, visible.reshape(R * Sq), *operands, k_cache, v_cache,
    )
    out = out[:, :, :Sq * G].reshape(R, KV, Sq, G, Dv)
    return out.transpose(0, 2, 1, 3, 4).reshape(R, Sq, H, Dv)
