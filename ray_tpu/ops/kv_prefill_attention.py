"""A K/V layer kind's prefill attention: grouped queries, keys wider than
values, a window and a sink, in one flash forward kernel.

    out[t, h] = sum_s p[t, s] v[s, kv(h)]
    p[t, s]   = exp(x[t, s]) / (exp(sink[h]) + sum_s' exp(x[t, s']))      (no sink: the plain softmax)
    x[t, s]   = q[t, h] . k[s, kv(h)] / sqrt(Dk)  over the keys  t - window < s <= t  (no window: s <= t)

``q`` (S, H, Dk), ``k`` (S, KV, Dk) and ``v`` (S, KV, Dv) are ONE row's run
from position 0 (``models/llama.py:_kind_attention``: the run's own tokens are
all the keys there are); query head h reads KV head ``h // (H / KV)``, and
nothing is repeated over the group.  ``sink`` (H,) float32 is a learned
number a query head that joins the softmax's denominator and carries no
value (MiMo-V2-Flash's window layers).  Scores and softmax in float32,
probabilities cast to the values' dtype before they meet them, as every
attention body of the model casts them.

Two bodies, chosen by the heads' shape in ONE place (``implementation``):

* ``flash`` — the Pallas kernel ``kv_prefill`` (``kv_prefill.N`` on the
  trace's op line), so nothing of size heads x queries x keys is ever in
  HBM.  q, k, v and the output stay (S, heads x D) as the projections leave
  them — a head is a 128-lane-aligned column block — and a step's query
  heads all read ONE KV head's column block, whose index the grid's head
  axis gives.  The run is padded up to whole tiles (a padded key lies behind
  every real query) and a key head that is no whole number of lane tiles
  with zeros up to one (192 -> 256: zeros add nothing to a score).  What a
  grid step does is decided by ``window``, in ``_flash``, because the two
  kinds of layer need opposite things:

  - no window (``_causal_kernel``): ``ops/latent_prefill_attention.py``'s
    schedule.  Grid (query heads / heads a step, live tile pairs): a tile
    of ``TILE`` queries against the tiles of ``TILE_KEYS`` keys that hold
    one of its visible keys, the pairs a list made at trace time, blocked
    online softmax with running max / sum / accumulator in float32 scratch.
    The sink starts a query's running maximum, and its running sum at
    ``exp(0)``.
  - a window (``_window_kernel``): grid (query heads / heads a step, query
    tiles), and a step computes its ``WINDOW_TILE`` queries' WHOLE band:
    the tile's own keys and the ``BLOCK``-key blocks before it that hold a
    visible key (one, for a window up to 129), a sub-tile of ``BLOCK``
    queries — every head of the KV group stacked in rows — against the
    blocks of ITS band, a plain one-pass softmax, the output written once.
    Nothing is carried between steps: no scratch, no rescale.  The pairs
    computed stay the band's blocks — two a sub-tile for a window of 128,
    one for the run's first — so a window layer's cost grows with S, not S
    squared.

* ``dense`` — plain XLA, an (S, S) score a head: value heads that are no
  whole lane tiles (tier-1's toy widths).

What the shapes were measured against (a v5e, PR 62; bfloat16, ms ONE call,
the kernel's own device time in a profiler trace; "parent" is the one body
this module had before: a grid step a (query tile, key tile) pair, the
online softmax for both kinds, a window layer in tiles of 128 x 128).  A
window layer, 64 heads on 8, 192 | 128, window 128 with a sink, at 12,288 /
2,048 tokens (queries a step x heads a step; "apart": a product a head
instead of the heads stacked in rows):

    parent (128 x 128 x 8)   5.211 / 0.851
    128 x 4   3.944 / 0.653   apart 3.425 / 0.572     128 x 8   3.411 / 0.563   apart 3.174 / 0.531
    256 x 4   3.584 / 0.599   apart 3.220 / 0.541     256 x 8   2.934 / 0.488   apart 2.990 / 0.502
    512 x 4   3.426 / 0.573   apart 3.123 / 0.526     512 x 8 **2.711 / 0.451** apart 2.901 / 0.489

A layer without a window, MiMo-V2-Flash's 64 heads on 4, 192 | 128, at
12,288 / 2,048 tokens, then Solar-Open2's 64 heads on 8, 128 | 128, at
16,384 / 4,096 (queries x keys x heads a step):

    parent (512 x 512 x 2)   40.15 / 1.406    62.89 / 4.400
    512 x 512 x 4            31.28 / -        60.17 / 4.220
    512 x 1,024 x 2        **26.83 / 1.094    35.10 / 2.710**
    512 x 1,024 x 4          26.44 / 1.084    34.48 / 2.660
    512 x 2,048 x 2          28.34 / 1.374    36.21 / 3.120
    256 x 1,024 x 4          28.89 / 1.168    38.22 / 2.916
    1,024 x 1,024 x 2        25.39 / 1.050    33.20 / 2.571
    512 x 1,024 x 2, a tile wholly under the diagonal skipping the mask   26.83 / 1.094   35.10 / 2.710
    the same, every tile masked                                           27.11 / 1.098   36.05 / 2.757

The key tile's width decides it, as in ``latent_prefill``: every key tile
rescales a (queries, Dv) accumulator and reduces a row maximum and a row
sum over lanes, a head, whatever its width; 1,024 keys hide that where 512
do not, 2,048 compute too much above the diagonal.  A body of its own for
the tiles wholly under the diagonal (no iotas, compare, select) is worth
1.0% / 2.7% and was NOT kept; nor were four heads a step (1.5-1.8%) or
1,024 queries a tile (5%, and a run would be padded up to 1,024).

``pairs_computed`` is what the chosen body computes scores for, a head, and
what ``attn_keys``' run slots of keys read count (``LLMEngine.
cache_counters``).

Off the chip the kernel runs in Pallas interpret mode (``_interpret`` of
``ops/flash_attention.py``, as its kernels do), so the tests run the very
kernel.
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

NEG_INF = -1e30
#: a layer without a window: queries a tile, keys a tile, and query heads a
#: grid step at most (the module's text has the sweep)
TILE = 512
TILE_KEYS = 1024
HEADS_A_STEP = 2
#: a window layer: keys a block of the band and queries a sub-tile that is
#: scored against its own band, queries a grid step at most, and query heads
#: a step at most
BLOCK = 128
WINDOW_TILE = 512
WINDOW_HEADS_A_STEP = 8


def implementation(qk_head_dim: int, v_head_dim: int) -> str:
    """Which body a run with heads of ``qk_head_dim`` (keys) / ``v_head_dim``
    (values) traces: ``"flash"`` — the kernel — for value heads of whole lane
    tiles, else ``"dense"``."""
    return "flash" if v_head_dim % 128 == 0 else "dense"


def _back(window: int) -> int:
    """Key blocks before a query sub-tile's own that hold one of its keys."""
    return -(-(window - 1) // BLOCK)


def tiles(group: int, window: int = 0, run_len: int | None = None):
    """(queries, keys, query heads) a grid step of the kernel is traced
    with, where ``group`` query heads read each KV head.  Without a window:
    a tile of queries against a tile of keys.  With one: a step's queries
    (a run of ``run_len`` tokens shorter than ``WINDOW_TILE``: its own
    blocks), in sub-tiles of ``BLOCK``, each against the keys of its own
    band (its block and the ``_back`` before it)."""
    most = WINDOW_HEADS_A_STEP if window else HEADS_A_STEP
    heads = max(n for n in range(1, most + 1) if group % n == 0)
    if not window:
        return TILE, TILE_KEYS, heads
    queries = WINDOW_TILE if run_len is None else min(WINDOW_TILE, -(-run_len // BLOCK) * BLOCK)
    return queries, (_back(window) + 1) * BLOCK, heads


def _live_pairs(run_len: int):
    """(query tile, key tile) of every pair of a layer without a window that
    holds a visible key: a query tile's pairs in a run, keys ascending from
    the first up to the tile that holds the query tile's last key."""
    pairs = [(i, j) for i in range(-(-run_len // TILE))
             for j in range(((i + 1) * TILE - 1) // TILE_KEYS + 1)]
    return np.asarray(pairs, np.int32).T


def pairs_computed(run_len: int, qk_head_dim: int, v_head_dim: int,
                   window: int = 0) -> int:
    """(query, key) pairs the chosen body computes scores for, a head: the
    live tiles, whole; a window layer's sub-tiles of ``BLOCK`` queries that
    hold a token, each against the blocks of its band at or after position
    0; or the dense body's square."""
    if implementation(qk_head_dim, v_head_dim) != "flash":
        return run_len * run_len
    if not window:
        return _live_pairs(run_len).shape[1] * TILE * TILE_KEYS
    back = _back(window)
    return sum(min(i, back) + 1 for i in range(-(-run_len // BLOCK))) * BLOCK * BLOCK


def _scores(q, k, scale):
    return lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale


def _mix(p, v):
    return lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _causal_kernel(qt_ref, kt_ref, *rest, scale, heads, sunk):
    """A layer without a window.  Grid (head groups, live pairs).  q (TILE,
    heads x Dk), k (TILE_KEYS, Dk) and v (TILE_KEYS, Dv) of the ONE KV head
    the step's query heads read, sink (heads, 1, 1) float32 where ``sunk``,
    out (TILE, heads x Dv); scratch m, l (heads, TILE, 1) and acc (heads,
    TILE, Dv) float32, carried over a query tile's key tiles."""
    sink_ref = rest[3] if sunk else None
    q_ref, k_ref, v_ref = rest[:3]
    o_ref, m_ref, l_ref, acc_ref = rest[3 + sunk:]
    i = pl.program_id(1)
    qt, kt = qt_ref[i], kt_ref[i]
    tq, tk = q_ref.shape[0], k_ref.shape[0]
    dqk, dv = k_ref.shape[1], v_ref.shape[1]

    @pl.when(kt == 0)
    def _init():
        if sunk:
            m_ref[...] = jnp.broadcast_to(sink_ref[...], m_ref.shape)
            l_ref[...] = jnp.ones_like(l_ref)
        else:
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = qt * tq + lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
    k_pos = kt * tk + lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
    keep = q_pos >= k_pos
    k, v = k_ref[...], v_ref[...]
    for g in range(heads):
        s = _scores(q_ref[:, g * dqk:(g + 1) * dqk], k, scale)         # (tq, tk)
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_ref[g]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_ref[g] = m_new
        l_ref[g] = l_ref[g] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[g] = acc_ref[g] * corr + _mix(p, v)

    @pl.when((kt + 1) * tk >= (qt + 1) * tq)
    def _finish():
        for g in range(heads):
            o_ref[:, g * dv:(g + 1) * dv] = (acc_ref[g] / l_ref[g]).astype(o_ref.dtype)


def _window_kernel(q_ref, *rest, scale, heads, window, live, sunk):
    """A window layer.  Grid (head groups, query tiles): a step computes its
    queries' whole band, so nothing is carried between steps.  q (Q, heads x
    Dk); the ``back`` key blocks before the tile, k (BLOCK, Dk) each (block 0
    again where there is none: the first tile leaves them out), and the
    tile's own k (Q, Dk); v likewise (Dv); sink (heads, 1, 1) float32 where
    ``sunk``; out (Q, heads x Dv).  A sub-tile of BLOCK queries, the step's
    heads stacked in rows, meets the ``back`` + 1 blocks of its band in one
    pass: every sub-tile's band lies the same way against it, so one mask
    serves them all."""
    back = _back(window)
    k_before, (k_ref, *rest) = rest[:back], rest[back:]
    v_before, (v_ref, *rest) = rest[:back], rest[back:]
    sink_ref = rest[0] if sunk else None
    o_ref = rest[-1]
    t = pl.program_id(1)
    blocks = q_ref.shape[0] // BLOCK
    dqk, dv = k_ref.shape[1], v_ref.shape[1]
    rows, cols = heads * BLOCK, (back + 1) * BLOCK
    # query - key, in positions: the band's first block lies ``back`` before
    apart = (back * BLOCK + lax.broadcasted_iota(jnp.int32, (rows, cols), 0) % BLOCK
             - lax.broadcasted_iota(jnp.int32, (rows, cols), 1))
    keep = (apart >= 0) & (apart < window)

    sink = None
    if sunk:
        sink = jnp.concatenate(
            [jnp.broadcast_to(sink_ref[g], (BLOCK, 1)) for g in range(heads)])

    def sub_tile(j, before):
        """Sub-tile j against the last ``before`` of the blocks before the
        tile and the tile's own up to its own block."""
        at = slice(j * BLOCK, (j + 1) * BLOCK)
        q = jnp.concatenate([q_ref[at, g * dqk:(g + 1) * dqk] for g in range(heads)])
        own = slice(max(j - back, 0) * BLOCK, (j + 1) * BLOCK)
        keys = [(b[...], c[...]) for b, c in zip(k_before[back - before:], v_before[back - before:])]
        keys.append((k_ref[own, :], v_ref[own, :]))
        col, s = cols - sum(k.shape[0] for k, _v in keys), []  # the band ends at the right edge
        for k, _v in keys:
            s.append(jnp.where(keep[:, col:col + k.shape[0]], _scores(q, k, scale), NEG_INF))
            col += k.shape[0]
        top = functools.reduce(jnp.maximum, [x.max(axis=1, keepdims=True) for x in s])
        if sunk:
            top = jnp.maximum(top, sink)
        p = [jnp.exp(x - top) for x in s]
        under = sum(x.sum(axis=1, keepdims=True) for x in p)
        if sunk:
            under = under + jnp.exp(sink - top)
        out = sum(_mix(x, v) for x, (_k, v) in zip(p, keys)) / under
        for g in range(heads):
            o_ref[at, g * dv:(g + 1) * dv] = out[g * BLOCK:(g + 1) * BLOCK].astype(o_ref.dtype)

    for j in range(blocks):
        held = t * blocks + j < live                 # the sub-tile holds a token
        outside = max(back - j, 0)                   # blocks of its band before the tile
        # the run's first tiles have fewer blocks before them than that
        early = -(-outside // blocks)
        for first in range(early):
            pl.when(held & (t == first))(functools.partial(sub_tile, j, first * blocks))
        pl.when(held & (t >= early))(functools.partial(sub_tile, j, outside))


def _flash(q, k, v, window: int, sink):
    S, H, Dk = q.shape
    KV, Dv = v.shape[1:]
    G = H // KV
    tq, tk, heads = tiles(G, window, S)
    if window:
        tk = tq                     # keys a fetched tile: the step's own, not a band's
    # whole tiles of tokens, whole lane tiles of a key head: zeros behind
    Dp = -(-Dk // 128) * 128
    q = jnp.pad(q, ((0, -S % tq), (0, 0), (0, Dp - Dk))).reshape(-1, H * Dp)
    k = jnp.pad(k, ((0, -S % tk), (0, 0), (0, Dp - Dk))).reshape(-1, KV * Dp)
    v = jnp.pad(v, ((0, -S % tk), (0, 0), (0, 0))).reshape(-1, KV * Dv)
    sunk = sink is not None
    sinks = [sink.astype(jnp.float32).reshape(H, 1, 1)] if sunk else []
    sink_spec = [pl.BlockSpec((heads, 1, 1), lambda h, i, *_: (h, 0, 0))] if sunk else []

    def kv_head(h):                                  # the one the group's heads read
        return (h * heads) // G

    if window:
        back, blocks = _back(window), tq // BLOCK

        def before(width, b):   # the b-th of the blocks before the tile (none: block 0)
            return pl.BlockSpec(
                (BLOCK, width),
                lambda h, t: (jnp.maximum(t * blocks - back + b, 0), kv_head(h)))

        def own(width):
            return pl.BlockSpec((tq, width), lambda h, t: (t, kv_head(h)))

        kernel = functools.partial(
            _window_kernel, scale=1.0 / math.sqrt(Dk), heads=heads, window=window,
            live=-(-S // BLOCK), sunk=sunk)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(H // heads, q.shape[0] // tq),
            in_specs=[
                pl.BlockSpec((tq, heads * Dp), lambda h, t: (t, h)),
                *(before(Dp, b) for b in range(back)), own(Dp),
                *(before(Dv, b) for b in range(back)), own(Dv),
                *sink_spec,
            ],
            out_specs=pl.BlockSpec((tq, heads * Dv), lambda h, t: (t, h)),
        )
        operands = [q, *[k] * (back + 1), *[v] * (back + 1), *sinks]
        semantics = ("parallel", "parallel")
    else:
        pairs = _live_pairs(S)

        def mine(width):        # a query head group's tile of queries
            return pl.BlockSpec((tq, heads * width), lambda h, i, qt, kt: (qt[i], h))

        def shared(width):      # the key tile of the one KV head the group reads
            return pl.BlockSpec((tk, width), lambda h, i, qt, kt: (kt[i], kv_head(h)))

        kernel = functools.partial(
            _causal_kernel, scale=1.0 / math.sqrt(Dk), heads=heads, sunk=sunk)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(H // heads, pairs.shape[1]),
            in_specs=[mine(Dp), shared(Dp), shared(Dv), *sink_spec],
            out_specs=mine(Dv),
            scratch_shapes=[
                pltpu.VMEM((heads, tq, 1), jnp.float32),
                pltpu.VMEM((heads, tq, 1), jnp.float32),
                pltpu.VMEM((heads, tq, Dv), jnp.float32),
            ],
        )
        operands = [jnp.asarray(pairs[0]), jnp.asarray(pairs[1]), q, k, v, *sinks]
        semantics = ("parallel", "arbitrary")
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((q.shape[0], H * Dv), v.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=_interpret(),
        name="kv_prefill",
    )(*operands)
    return out[:S].reshape(S, H, Dv)


def _dense(q, k, v, window: int, sink):
    S, H, Dk = q.shape
    KV = k.shape[1]
    q = q.reshape(S, KV, H // KV, Dk)
    scores = jnp.einsum(
        "qkgd,tkd->kgqt", q, k, preferred_element_type=jnp.float32
    ) / math.sqrt(Dk)
    t = jnp.arange(S)
    keep = t[:, None] >= t[None, :]
    if window:
        keep = keep & (t[:, None] - t[None, :] < window)
    scores = jnp.where(keep, scores, NEG_INF)
    top = scores.max(-1, keepdims=True)
    under = 0.0
    if sink is not None:
        sink = sink.astype(jnp.float32).reshape(KV, H // KV, 1, 1)
        top = jnp.maximum(top, sink)
        under = jnp.exp(sink - top)
    probs = jnp.exp(scores - top)
    probs = (probs / (probs.sum(-1, keepdims=True) + under)).astype(v.dtype)
    return jnp.einsum("kgqt,tkd->qkgd", probs, v).reshape(S, H, -1)


def attention(q, k, v, *, window: int = 0, sink=None):
    """q (S, H, Dk), k (S, KV, Dk), v (S, KV, Dv) of one row's run from
    position 0; ``window`` > 0: a query sees its own key and the ``window -
    1`` before it; ``sink`` (H,) float32 or None -> (S, H, Dv) in ``v``'s
    dtype."""
    S, H, Dk = q.shape
    if k.shape[0] != S or k.shape[2] != Dk or v.shape[:2] != k.shape[:2] or H % k.shape[1]:
        raise ValueError(f"q {q.shape}, k {k.shape}, v {v.shape} are no one run's")
    body = _flash if implementation(Dk, v.shape[2]) == "flash" else _dense
    return body(q, k, v, window, sink)
