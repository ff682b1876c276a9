"""A latent config's prefill attention: one flash forward kernel.

    out[t, h] = softmax_s(scale * q[t, h] . k[s, h])  @  v[s, h]
                over the keys s the mask holds for query t (s <= t without one)

``q`` and ``k`` (Sq, H, Dqk), ``v`` (Sq, H, Dv) are ONE row's run from
position 0 (``models/llama.py:_latent_attention``: keys and values expanded
from the run's own latents), ``mask`` (Sq, Sq) int8 the indexer's selection,
a subset of the causal triangle.  Scores and softmax in float32, masked
entries at ``NEG_INF``, probabilities cast to the values' dtype before they
meet them, as every attention body of the model casts them.

Two bodies, chosen by the run's shape in ONE place (``implementation``):

* ``flash`` — the Pallas kernel ``latent_prefill`` (``latent_prefill.N`` on
  the trace's op line): a tile of ``TILE`` queries against the tiles of
  ``TILE`` keys at or before it, blocked online softmax with running max /
  sum / accumulator in float32 scratch, so nothing of size heads x queries x
  keys is ever in HBM.  The grid is (heads / heads a step, live tile
  pairs): the pairs are a list (two scalar-prefetch operands made at trace
  time), so a tile behind the diagonal costs neither a fetch nor a grid
  step.  q, k, v and the output stay (Sq, H x D) as the projections leave
  them — a head is a 128-lane-aligned column block, which is why the value
  head must be whole lane tiles and a key head that is none (LongCat-Flash's
  and JoyAI-LLM-Flash's nope 128 | rope 64 = 192) comes with zeros behind
  it up to one (``lanes_behind``: 192 -> 256; zeros add nothing to a score,
  and ``scale`` stays the published head's) — and no transpose is made.
  The model writes those zeros in the ``concatenate`` that puts q and k
  together, so no pass of its own is made over them.  The mask's
  (TILE, TILE) tile is read once a grid step for all its heads.  A query
  with NO selected key inside a tile adds ``exp(0)`` a key to its running
  sum; the first tile that holds one of its keys wipes that (``exp(NEG_INF -
  m)`` is 0), as ``ops/latent_decode_attention.py``'s running maximum does,
  and every query has a selected key at or before its own tile's end.
  Without a mask operand visibility is causal, from an iota in the kernel.
* ``blocked`` — XLA's body in ``_latent_attention`` (blocks of queries,
  float32 scores of a block in HBM, four causal groups): a run that is no
  whole number of tiles, or a value head that is no whole lane tiles
  (tier-1's tiny runs, ragged lengths).

``pairs_computed`` is what the kernel computes scores for — whole live
tiles — and what ``dsa_keys``' run slot of keys read counts
(``LLMEngine.cache_counters``: ``dsa_read_run``).

What the tile was measured against (a v5e, PR 40; GLM-5's shapes, 64 heads
of 256 | 256 in bfloat16, a mask of ~2,048 keys a query; ms ONE call as a
timing loop saw it — XLA hoisted the call out of the loop, and the call
includes a copy of each operand from the default layout of a program's
parameter into the kernel's, which the model's producers write directly —
at 8,192 / 4,096 tokens, query tile x key tile x heads a step):

    128 x 128 x 4     63.26 / 17.47        512 x 512 x 1     22.00 / 7.30
    256 x 256 x 2     36.23 / 10.82        512 x 512 x 2   **20.41 / 6.77**
    256 x 256 x 8     32.20 /  9.70        512 x 512 x 4     20.43 / 6.79
    512 x 256 x 2     33.61 / 10.26        512 x 512 x 8     20.55 / 6.89
    128 x 512 x 4     31.20 /  9.64        512 x 1,024 x 2   20.52 / 7.16
    256 x 512 x 2     24.42 /  7.92        1,024 x 512 x 2   20.03 / 6.91
    256 x 1,024 x 4   20.67 /  7.18        1,024 x 1,024 x 1 19.85 / 6.93
    no mask operand, 512 x 512 x 2         20.15 / 6.69

The KEY tile's width decides it: every key tile rescales the (queries, Dv)
float32 accumulator and reduces a row maximum and a row sum, whatever its
width, and 512 keys hide that behind the two matmuls where 256 do not.
Past 512 x 512 nothing is left to win (3% at 1,024 x 1,024, which computes
1.125 / 1.25 times the visible pairs where 512 computes 1.0625 / 1.125, and
does not divide the reference comparison's 2,560 tokens); a second head a
step is worth 7% (the mask's tile and the grid step shared), further heads
nothing.  Inside a traced prefill the kernel takes 16.46 / 4.47 ms a layer
at 8,192 / 4,096 tokens: 2.2 / 0.55 TFLOP of causal work at 134 / 123
TFLOP/s of the chip's 197 (XLA's body: 28).  Head-major operands ((H, Sq,
D) blocks, the transposes left to XLA's layout assignment) make the same
prefill 183.5 / 601.4 ms against 186.9 / 602.1: the copies of q and k into
(Sq, H x D) cost 1-3 ms a prefill and are not worth a second layout.

The same sweep at LongCat-Flash's and JoyAI-LLM-Flash's widths (a v5e, PR
56: key heads of 192 with zeros up to 256 | values of 128, causal with no
mask operand, bfloat16; ms ONE call, the kernel's own device time in a
profiler trace; 64 heads at 4,096 / 2,048 tokens, then 32 heads at 1,536 /
512; "-" where the tile does not divide the run, "vmem" where the compiler
refuses the step's blocks):

    128 x 128 x 4   17.17 / 4.44 / 1.302 / 0.176     512 x 512 x 1     5.05 / 1.448 / 0.451 / 0.082
    256 x 256 x 2    8.04 / 2.16 / 0.649 / 0.100     512 x 512 x 2     4.84 / 1.393 / 0.432 / 0.079
    256 x 256 x 8    7.22 / 1.96 / 0.596 / 0.091     512 x 512 x 4   **3.83 / 1.111 / 0.348 / 0.064**
    512 x 256 x 4    7.47 / 2.12 / 0.652 / 0.115     512 x 512 x 8     vmem
    256 x 512 x 2    4.39 / 1.26 / 0.401 / 0.073     512 x 1,024 x 2   3.49 / 1.091 / - / -
    256 x 512 x 4    4.16 / 1.20 / 0.385 / 0.070     512 x 1,024 x 4   vmem
    256 x 512 x 8    4.16 / 1.21 / 0.386 / 0.069     1,024 x 512 x 2   5.77 / 1.774 / - / -
    256 x 1,024 x 4  3.71 / 1.16 / - / -             1,024 x 1,024 x 2 3.39 / 1.063 / - / -

The key tile's width decides it here too, and the heads a step now matter:
with the accumulator half as wide, FOUR heads a step are 21% faster than
two (at 256 | 256 two and four were level), so a step computes
``LANES_A_STEP`` = 512 value lanes whatever the head's width — 2 heads of
256, 4 of 128; eight do not fit the fast memory.  Inside a traced prefill
the call takes 3.85 / 1.11 ms a layer at 4,096 / 2,048 tokens (4.87 / 1.39
at two heads a step): 0.344 / 0.086 TFLOP of causal work at the published
widths at 89 / 77 TFLOP/s, 157 / 151 G (query, key) pairs a second as
computed — the per-pair vector work bounds it, not the matmuls.  A
1,024-wide key tile is 9-11% faster still at 4,096 tokens and 2-4% at
2,048, and is NOT taken: it is 0.35 ms of a layer's 24 ms there, and
``TILE`` is also the grain of the run lengths the kernel takes (1,536 and
2,560 are no whole number of 1,024).

Why ``MIN_TILES``: XLA's blocked body costs less a pair the shorter the run
(its float32 score blocks are small), the kernel the same at every length.
One whole prefill through XLA's body against the kernel (four heads a
step), ms on the device, PR 56:

    tokens            512     1,024   1,536   2,048   3,072   4,096
    LongCat, XLA's    25.14   44.10   66.15   89.70           258.52
    LongCat, kernel   26.06   43.80   65.17   87.35           193.94
    JoyAI, XLA's      24.60   47.01   67.70   98.53   168.35
    JoyAI, kernel     26.87   46.19   67.87   96.22   161.13

(LongCat-Flash at 4 of 28 layers, 8 attentions of 64 heads; JoyAI-LLM-Flash's
40 of 32.)  At one tile the kernel loses 4-9% of the prefill, at two and
three the bodies are level (-1.7 ... +0.3%), from four tiles the kernel wins
(2.3-2.6% at 2,048, 4.3% at 3,072, 25% at 4,096): the rule starts at four, so
JoyAI's cell (512 and 1,536 tokens) keeps the programs it had.

Off the chip the kernel runs in Pallas interpret mode (``_interpret`` of
``ops/flash_attention.py``, as its kernels do), so the tests run the very
kernel.
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

NEG_INF = -1e30
LANES = 128
#: queries a tile, and keys a tile.  2,560 (the reference comparison's
#: prompt), 4,096 and 8,192 are whole numbers of it; it computes 1.125 /
#: 1.0625 times the visible pairs at 4,096 / 8,192 (the module's text has
#: the sweep)
TILE = 512
#: value lanes a grid step computes, one head after another, on one fetch of
#: the mask's tile — a step's output block and accumulator are (TILE, 512)
#: whatever the value head's width: 2 heads of 256 (22.00 / 20.41 / 20.43 ms a
#: call at 1 / 2 / 4), 4 of 128 (the module's text, PR 56)
LANES_A_STEP = 512
#: the shortest run the kernel takes, in tiles: under it XLA's blocked body is
#: as fast or faster (the module's text, PR 56)
MIN_TILES = 4


def _fits(run_len: int, v_head_dim: int) -> bool:
    """What the kernel can compute: whole tiles of tokens, value heads of
    whole lane tiles."""
    return run_len % TILE == 0 and v_head_dim % LANES == 0


def implementation(run_len: int, qk_head_dim: int, v_head_dim: int) -> str:
    """Which body a run of ``run_len`` tokens with heads of ``qk_head_dim``
    (keys) / ``v_head_dim`` (values) traces: ``"flash"`` — the kernel — for a
    whole number of tiles, at least ``MIN_TILES`` of them, and value heads of
    whole lane tiles, else ``"blocked"``.  The key head may be any width: the
    kernel is given it with zeros up to whole lane tiles (``lanes_behind``).
    The rule reads the run's LENGTH because the kernel costs the same a
    (query, key) pair at every length and XLA's body less the shorter the
    run: under 2,048 tokens the blocked body wins (the module's text)."""
    if _fits(run_len, v_head_dim) and run_len >= MIN_TILES * TILE:
        return "flash"
    return "blocked"


def lanes_behind(qk_head_dim: int) -> int:
    """Zeros the kernel wants behind each key (and query) head: up to the
    next whole lane tile (192 -> 64, 256 -> 0)."""
    return -qk_head_dim % LANES


def _live_pairs(run_len: int):
    """(query tile, key tile) of every pair that holds a visible key: a
    query tile's pairs in a run, keys ascending up to its own tile."""
    pairs = [(i, j) for i in range(run_len // TILE) for j in range(i + 1)]
    return np.asarray(pairs, np.int32).T


def pairs_computed(run_len: int) -> int:
    """(query, key) pairs the kernel computes scores for, a head: the live
    tiles, whole."""
    return _live_pairs(run_len).shape[1] * TILE * TILE


def _kernel(qt_ref, kt_ref, q_ref, k_ref, v_ref, *rest, scale, heads, masked):
    """Grid (head groups, live pairs).  q, k (TILE, heads x Dqk), v (TILE,
    heads x Dv), mask (TILE, TILE) int8 where ``masked``, out (TILE, heads x
    Dv); scratch m, l (heads, TILE, 1) and acc (heads, TILE, Dv) float32."""
    mask_ref = rest[0] if masked else None
    o_ref, m_ref, l_ref, acc_ref = rest[masked:]
    i = pl.program_id(1)
    qt, kt = qt_ref[i], kt_ref[i]
    tile = q_ref.shape[0]
    dqk, dv = q_ref.shape[1] // heads, v_ref.shape[1] // heads

    @pl.when(kt == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if masked:
        keep = mask_ref[...].astype(jnp.int32) != 0
    else:
        q_pos = qt * tile + lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
        k_pos = kt * tile + lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
        keep = q_pos >= k_pos
    for g in range(heads):
        v = v_ref[:, g * dv:(g + 1) * dv]
        s = lax.dot_general(
            q_ref[:, g * dqk:(g + 1) * dqk], k_ref[:, g * dqk:(g + 1) * dqk],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        ) * scale                                               # (TILE, TILE)
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_ref[g]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_ref[g] = m_new
        l_ref[g] = l_ref[g] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[g] = acc_ref[g] * corr + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kt == qt)
    def _finish():
        for g in range(heads):
            o_ref[:, g * dv:(g + 1) * dv] = (acc_ref[g] / l_ref[g]).astype(o_ref.dtype)


def latent_prefill_attention(q, k, v, mask=None, *, scale: float):
    """The flash body.  q, k (Sq, H, Dqk), v (Sq, H, Dv), mask (Sq, Sq)
    int8 (non-zero: query t attends to key s; inside the causal triangle,
    at least one key a query) or None for causal -> (Sq, H, Dv) in ``v``'s
    dtype.  Sq is a whole number of ``TILE`` (any number: ``MIN_TILES`` is
    ``implementation``'s to hold), Dv of 128 lanes; q and k that
    are no whole lane tiles wide are padded here (``lanes_behind``; the model
    hands them over padded, in the write that assembles them), and ``scale``
    is the caller's, of the head as published."""
    Sq, H, Dqk = q.shape
    Dv = v.shape[-1]
    if (not _fits(Sq, Dv) or k.shape != q.shape
            or v.shape[:2] != (Sq, H)
            or (mask is not None and mask.shape != (Sq, Sq))):
        raise ValueError(
            f"the prefill kernel wants whole tiles of {TILE} and value heads of "
            f"whole 128-lane tiles: q {q.shape}, k {k.shape}, v {v.shape}, mask "
            f"{None if mask is None else mask.shape}"
        )
    behind = lanes_behind(Dqk)
    if behind:  # zeros add nothing to a score
        q, k = (jnp.pad(x, ((0, 0), (0, 0), (0, behind))) for x in (q, k))
        Dqk += behind
    heads = max(n for n in range(1, max(1, LANES_A_STEP // Dv) + 1) if H % n == 0)
    pairs = _live_pairs(Sq)

    def spec(width, side):  # a (query: 0, key: 1) tile of an (Sq, H x width) array
        return pl.BlockSpec((TILE, heads * width), lambda h, i, *tiles: (tiles[side][i], h))

    in_specs = [spec(Dqk, 0), spec(Dqk, 1), spec(Dv, 1)]
    operands = [q.reshape(Sq, H * Dqk), k.reshape(Sq, H * Dqk), v.reshape(Sq, H * Dv)]
    if mask is not None:
        in_specs.append(pl.BlockSpec((TILE, TILE), lambda h, i, qt, kt: (qt[i], kt[i])))
        operands.append(mask.astype(jnp.int8))
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, heads=heads, masked=mask is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(H // heads, pairs.shape[1]),
            in_specs=in_specs,
            out_specs=spec(Dv, 0),
            scratch_shapes=[
                pltpu.VMEM((heads, TILE, 1), jnp.float32),
                pltpu.VMEM((heads, TILE, 1), jnp.float32),
                pltpu.VMEM((heads, TILE, Dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((Sq, H * Dv), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=_interpret(),
        name="latent_prefill",
    )(jnp.asarray(pairs[0]), jnp.asarray(pairs[1]), *operands)
    return out.reshape(Sq, H, Dv)
