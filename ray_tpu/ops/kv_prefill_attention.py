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
  trace's op line), ``ops/latent_prefill_attention.py``'s schedule: a tile of
  queries against the tiles of keys that hold one of its visible keys,
  blocked online softmax with running max / sum / accumulator in float32
  scratch, so nothing of size heads x queries x keys is ever in HBM.  The
  grid is (query heads / heads a step, live tile pairs); the pairs are a list
  made at trace time, so a tile outside the band costs neither a fetch nor a
  grid step: a window layer's cost grows with S, not S squared.  q, k, v and
  the output stay (S, heads x D) as the projections leave them — a head is a
  128-lane-aligned column block — and a step's query heads all read ONE KV
  head's column block, whose index the grid's head axis gives.  The run is
  padded up to whole tiles (a padded key lies behind every real query) and a
  key head that is no whole number of lane tiles with zeros up to one (192
  -> 256: zeros add nothing to a score).  A full layer takes tiles of
  ``TILE`` (512 x 512, two heads a step: that module's sweep); a window
  layer tiles of ``WINDOW_TILE`` = 128 and every head of a KV group a step,
  because a query sees 128 keys: at 512 the band's tiles would hold eight
  times the pairs inside it, at 128 two.  The sink starts a query's running
  maximum, and its running sum at ``exp(0)``.
* ``dense`` — plain XLA, an (S, S) score a head: value heads that are no
  whole lane tiles (tier-1's toy widths).

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
#: queries and keys a tile of a layer without a window, and query heads a
#: grid step (``ops/latent_prefill_attention.py``'s sweep)
TILE = 512
HEADS_A_STEP = 2
#: queries and keys a tile of a window layer, and query heads a step at most
WINDOW_TILE = 128
WINDOW_HEADS_A_STEP = 8


def implementation(qk_head_dim: int, v_head_dim: int) -> str:
    """Which body a run with heads of ``qk_head_dim`` (keys) / ``v_head_dim``
    (values) traces: ``"flash"`` — the kernel — for value heads of whole lane
    tiles, else ``"dense"``."""
    return "flash" if v_head_dim % 128 == 0 else "dense"


def _tile(window: int) -> int:
    return WINDOW_TILE if window else TILE


def _live_pairs(tiles: int, back: int):
    """(query tile, key tile) of every pair that holds a visible key: a
    query tile's pairs in a run, keys ascending from ``back`` tiles before
    its own (from the first, without a window) up to its own."""
    pairs = [(i, j) for i in range(tiles)
             for j in range(0 if back < 0 else max(0, i - back), i + 1)]
    return np.asarray(pairs, np.int32).T


def _back(window: int, tile: int) -> int:
    """Key tiles before a query tile's own that hold one of its keys (-1:
    all of them)."""
    return -(-(window - 1) // tile) if window else -1


def pairs_computed(run_len: int, qk_head_dim: int, v_head_dim: int,
                   window: int = 0) -> int:
    """(query, key) pairs the chosen body computes scores for, a head: the
    live tiles, whole, or the dense body's square."""
    if implementation(qk_head_dim, v_head_dim) != "flash":
        return run_len * run_len
    tile = _tile(window)
    tiles = -(-run_len // tile)
    return _live_pairs(tiles, _back(window, tile)).shape[1] * tile * tile


def _kernel(qt_ref, kt_ref, *rest, scale, heads, window, back, sunk):
    """Grid (head groups, live pairs).  q (tile, heads x Dk), k (tile, Dk)
    and v (tile, Dv) of the ONE KV head the step's query heads read, sink
    (heads, 1, 1) float32 where ``sunk``, out (tile, heads x Dv); scratch m,
    l (heads, tile, 1) and acc (heads, tile, Dv) float32."""
    sink_ref = rest[3] if sunk else None
    q_ref, k_ref, v_ref = rest[:3]
    o_ref, m_ref, l_ref, acc_ref = rest[3 + sunk:]
    i = pl.program_id(1)
    qt, kt = qt_ref[i], kt_ref[i]
    tile = q_ref.shape[0]
    dqk, dv = k_ref.shape[1], v_ref.shape[1]

    @pl.when(kt == (0 if back < 0 else jnp.maximum(qt - back, 0)))
    def _init():
        if sunk:
            m_ref[...] = jnp.broadcast_to(sink_ref[...], m_ref.shape)
            l_ref[...] = jnp.ones_like(l_ref)
        else:
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = qt * tile + lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
    k_pos = kt * tile + lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
    keep = q_pos >= k_pos
    if window:
        keep = keep & (q_pos - k_pos < window)
    k, v = k_ref[...], v_ref[...]
    for g in range(heads):
        s = lax.dot_general(
            q_ref[:, g * dqk:(g + 1) * dqk], k,
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        ) * scale                                               # (tile, tile)
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


def _flash(q, k, v, window: int, sink):
    S, H, Dk = q.shape
    KV, Dv = v.shape[1:]
    G = H // KV
    tile = _tile(window)
    heads = max(n for n in range(1, (WINDOW_HEADS_A_STEP if window else HEADS_A_STEP) + 1)
                if G % n == 0)
    # whole tiles of tokens, whole lane tiles of a key head: zeros behind
    Sp, Dp = -(-S // tile) * tile, -(-Dk // 128) * 128
    pad = ((0, Sp - S), (0, 0), (0, Dp - Dk))
    q, k = jnp.pad(q, pad), jnp.pad(k, pad)
    v = jnp.pad(v, ((0, Sp - S), (0, 0), (0, 0)))
    back = _back(window, tile)
    pairs = _live_pairs(Sp // tile, back)

    def mine(width, side):  # a query head group's (query: 0, key: 1) tile
        return pl.BlockSpec((tile, heads * width), lambda h, i, *tiles: (tiles[side][i], h))

    def shared(width):      # the key tile of the one KV head the group reads
        return pl.BlockSpec(
            (tile, width), lambda h, i, qt, kt: (kt[i], (h * heads) // G))

    in_specs = [mine(Dp, 0), shared(Dp), shared(Dv)]
    operands = [q.reshape(Sp, H * Dp), k.reshape(Sp, KV * Dp), v.reshape(Sp, KV * Dv)]
    if sink is not None:
        in_specs.append(pl.BlockSpec((heads, 1, 1), lambda h, i, *_: (h, 0, 0)))
        operands.append(sink.astype(jnp.float32).reshape(H, 1, 1))
    out = pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / math.sqrt(Dk), heads=heads,
                          window=window, back=back, sunk=sink is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(H // heads, pairs.shape[1]),
            in_specs=in_specs,
            out_specs=mine(Dv, 0),
            scratch_shapes=[
                pltpu.VMEM((heads, tile, 1), jnp.float32),
                pltpu.VMEM((heads, tile, 1), jnp.float32),
                pltpu.VMEM((heads, tile, Dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((Sp, H * Dv), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=_interpret(),
        name="kv_prefill",
    )(jnp.asarray(pairs[0]), jnp.asarray(pairs[1]), *operands)
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
