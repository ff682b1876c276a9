"""The gated delta rule (Yang et al., "Gated Delta Networks",
arXiv:2412.06464): a linear-attention layer's fixed-size recurrent state
in place of a list of per-token keys and values.

Per head, state ``S`` (d_k, d_v) float32, token t with query ``q`` and key
``k`` (d_k; normed and scaled by the caller), value ``v`` (d_v), decay
``alpha`` in (0, 1] and write strength ``beta``::

    S' = alpha S            o = S_t^T q
    S_t = S' + k (x) (beta (v - S'^T k))

``step`` is that update for ONE token of every row, ``scan`` the CHUNKED
form for a run of tokens (section 3 of the paper; the algorithm of
``transformers``' ``torch_chunk_gated_delta_rule``): inside a chunk of
``chunk`` tokens the rule's triangular system is solved once, with matrix
products, and between chunks the state is carried — S / chunk sequential
steps of matmuls for a prompt, not S of outer products.

Both take the decay as ``log_alpha`` = log(alpha) <= 0: a chunk's
cumulative decay is a SUM there, where the product of 64 alphas underflows
float32 (a head whose A is 16 decays by e^-20 a token).  Everything is
float32 with the products at ``Precision.HIGHEST`` (the chip's default for
float32 operands is one bfloat16 pass, which would round the state).

THE DECAY A KEY CHANNEL (Kimi Delta Attention: Kimi Linear,
arXiv:2510.26692; Solar-Open2's ``kda_*`` layers): every entry point takes
``log_alpha`` with a trailing d_k axis — (B, H, d_k) a token, (B, S, H, d_k)
a run — and then ``S' = Diag(alpha) S``: ``S'^T x = S^T (alpha * x)``, so
``step`` contracts the old state with ``alpha k`` and ``alpha q``; the kernel
``kda_step`` (``_kernel_channels``) transposes alpha with k and q and
multiplies down the sublanes; ``scan`` becomes ``_scan_channels``, whose
docstring has the algebra.  Its stability argument: the scalar gate's
``e^(G_i - G_j)`` is one number a (token, token) pair and is formed from the
DIFFERENCE of two sums; with a decay a channel the same factor sits inside
the sum over d_k, and taken apart as ``(q e^G_i) . (k e^-G_j)`` its second
factor is e^1280 at the end of a 64-token chunk of a channel that decays by
e^-20 a token.  So no exponent above zero is formed: between sub-chunks of
16 tokens both factors are taken against the cumulative decay at the last
token before the later sub-chunk (both exponents <= 0, because the sum
falls), inside a sub-chunk the difference is formed a channel before the
exponential.  The scalar entry points keep their signatures and their
bodies: a decay constant over the channels gives their results to 1e-5
(``tests/test_solar_open2_ops.py``), and nothing broadcasts a scalar gate to
d_k channels.

``scan`` has two bodies, chosen from the operands' SHAPE in ONE place
(``scan_implementation``): the Pallas kernel ``kda_chunk`` (``_scan_kernel``)
where the decay is a key channel's, a head's d_k and d_v are whole 128-lane
tiles, the chunk is 64 tokens and the heads are whole groups of eight —
Solar-Open2's served prefills — and plain ``jax.numpy`` everywhere else (the
scalar gate at any width: Olmo-Hybrid's 96 x 192 heads are no whole tile;
tier-1's toy widths; the kernel's reference in the tests).  THE KERNEL keeps a
chunk in fast memory: grid (row, group of 8 heads, chunk), the chunk axis last
and sequential; a step takes the chunk's q, k, v, decay (64 tokens x 8 heads x
128, as the mixer makes them: (R, S, H, d) blocks, a head's 64 rows picked out
of the (token, head) sublanes by strided loads) and beta, and for a head at a
time — two heads' chains side by side in a loop's trip, for the scheduler to
interleave — forms the running sum of the decays (six shifted adds down the
sublanes), the pair scores (``_scan_channels``'s algebra and stability argument
unchanged: between sub-chunks of 16 both factors against the reference point on
the MXU, only for the sub-chunk rows that have something before them; inside a
sub-chunk the difference a channel before the exponential on the VPU / EUP, a
column of pairs at a time, the sum over d_k along the lanes, the rows of a
sublane tile at or under the diagonal only), solves the unit-lower-triangular
system by forward substitution (``_substitute``: the earlier sub-chunks' rows
through one product, inside a sub-chunk a row at a time on the VPU: no series,
no inverse is formed), takes the three (d_k, d_v) products against the carried
state and writes o.  The state (128 x 128 float32 a head) is the kernel's second
output, whose block does not move along the chunk axis: set from ``state0`` at a
group's first chunk, carried in fast memory, written out after its last.  HBM
sees q, k, v, the decay and beta in and o out, once.  Every product is float32
at ``Precision.HIGHEST`` (Mosaic's ``contract_precision<fp32>``), every
exponent <= 0, no floor, no clamp.  ``_chunk_state_step`` and ``_substitute``
know nothing of the channels: the scalar gate's pair scores (one product and
one decay a pair) can be put under them as they are.

What was swept for ``kda_chunk`` (a v5e, PR 60; the served segment alone, 1 row
x 1,024 tokens x 64 heads x (128, 128), microseconds a (token, layer) — the
parent's ``jax.numpy`` body 6.58 alone, 5.67 in the prefill program):

  the solve (8 heads a step, 2 a trip)                                us
    nilpotent series, ten 64 x 64 x 64 products at six passes         2.54
      (the same with the series alone taken out: 1.20 — more than half the
      kernel; with the sub-chunks' exponentials taken out as well: 1.19)
    forward substitution by 16-row blocks, the diagonal blocks a row at a
      time on the VPU (this body; 1.42 in the prefill program)        1.53
  where the exponentials went: the 64 columns of pairs a (head, chunk)
    inside the sub-chunks (VPU / EUP / the lane sums) cost nothing that
    shows: 1.53 with them, 1.56 without — they run under the MXU's products
  heads a trip of the loop, 1 / 2 / 4                                1.68 1.53 1.44
    (1.2 / 2.6 / 4.0 s of Mosaic compile a kernel, six kernels a set-up:
    2 is kept, ``setup_s`` is judged)
  heads a grid step, 8 / 16 (both without the strided loads)         1.41 1.42
  operand layout: (R, S, H, d) blocks as the mixer makes q, k, v and the
    decay, a head's rows by strided loads: 0.11 of the 1.53 (1.41 with the
    loads left out); (R, H, S, d) would take four transposes of 33.5 MB a
    segment first, 0.33 at the chip's 819 GB/s at the least: not built.
    In the prefill program nothing stands beside the kernel under
    ``kda_scan`` but beta's (1024, 64) regrouping: no copy of an operand.
  what is left (by taking parts out): the three state products 0.53, the
  operands' DMA, the strided loads, the running sum and the factors 0.42, the
  cross-sub-chunk products 0.10, the substitution ~0.4.

The one-token update of a cache's layer
(``step_layer``) has two bodies, chosen from the operand's SHAPE in ONE place
(``implementation``):

* ``in_place`` — the Pallas kernel ``gated_delta_step`` (the custom call
  shows as ``gated_delta_step.N`` on a trace's op line).  The cache keeps
  every linear layer's states in ONE leaf, ``packed``: (layers, rows, d_k,
  heads x d_v), a row's heads side by side, so that at Olmo-Hybrid's 96 x 30
  x 192 every (8, 128) tile is whole (a (96, 192) matrix a head lies in 256
  lanes: a third more bytes held and moved).  The kernel takes the WHOLE
  leaf, the layer by scalar prefetch, and gives the leaf back
  (``input_output_aliases``): a block of (rows a step, d_k, columns of some
  heads) comes into fast memory once, both contractions over d_k are taken
  of it on the VPU (float32 multiplies and adds: exact float32, nothing on
  the MXU), ``alpha S + k (x) delta`` goes back out, and nothing else of the
  leaf moves.  ``step``'s algebra, the sum over d_k in another order.
* ``xla`` — ``step`` on the layer's rows, sliced out and written back:
  states that are no whole tiles (tier-1's toy widths: four heads of 16 are
  half a lane tile).

What was swept (a v5e, PR 48; the cell's shape, 32 rows x 30 heads x (96,
192), 12 layers stacked: 141.5 MB a layer read once and written once, 0.173
ms at the chip's 819 GB/s):

  alone, a loop over the 12 layers in one program (calls 1-3; ms a layer):
    XLA, (layers, rows, H, d_k, d_v): slice, ``step``, write back     0.495-0.500
    whole-array ``jnp`` on (d_k, 384) a head pair, k and q handed over
      TRANSPOSED (d_k, H) and broadcast along the lanes; columns of
      2 / 6 / 10 / 30 heads a step, one row                         .427 .314 .437 .423
    the same, k and q to their lanes by a one-hot product on the MXU
      (three bfloat16 pieces a value; as run, XLA had folded the pieces
      into one and the state was off by 2e-5), 2 / 6 / 10 / 30 heads .463 .323 .314 .316
    eight sublanes at a time into two accumulators, k stashed for the
      second pass (this body), 6 / 10 heads a step, one row           .321 .309
    the same with the sublane tiles a ``fori_loop``                   .722 .689
    rows a ``fori_loop`` inside a step of 8 rows, 2 / 6 / 10 heads    .325 .310 .301
    a step that only copies its block, 10 heads x 1 row / 6 x 8       .307 .303
  A copy is as slow as the rule: alone, the pipeline's two DMAs a step set
  the pace, not the arithmetic — and less so inside the model's step, so
  the rest was read there.
  in the decode program, by scope from a trace of one step (calls 3-5; ms
  a layer = ``gdn_step`` / 12; the parent's XLA body 0.414):
    heads x rows a step     2x8   2x32  6x4   6x8   10x1  10x2  10x4  10x8  10x16
    ms a layer              .249  .246  .225  .226  .224  .224  .222  .220  .222
  Runs of 12 KB (a head pair's 384 columns) cost a tenth against 36-60 KB;
  past that neither the block (1.5-15 MB) nor the rows a step matter, so the
  smallest footprint that is no slower is kept: 10 heads x 4 rows, 2.9 MB a
  block, 12 MB of fast memory in four buffers (``STEP_BYTES``,
  ``ROWS_A_STEP``).  30 heads x 8 rows (71 MB) does not fit.  The rows of a
  step are a loop whose body is one row's head pairs, unrolled (static lane
  slices): 1.0 s of compile a call where a whole row's 15 pairs a grid step
  took 3.1 s and the rows unrolled as well 1.5 s more a program; the
  sublane tiles of a pair are a ``fori_loop`` that is unrolled when LOWERED,
  not in Python: the same kernel, and the decode program's trace takes 2.3 s
  of set-up where twelve copies of the body a pair took 5.6 (the parent's
  1.3; ``setup_s`` is judged).  What the
  kernel's operands look like decided more than its blocks: handed k and q
  transposed, or the gates with the rows in front, XLA passed the layout up
  to the convolution's tail and carried ``gdn_conv`` through the loop in
  another layout — copied in and out of every step, 2.6 ms of a 21 ms step —
  so they go in as the layer makes them and the kernel transposes k and q
  itself (two 128 x 128 transposes a row).

Off the chip the kernel runs in Pallas interpret mode (``_interpret`` of
``ops/flash_attention.py``), so the tests run the very kernel.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.flash_attention import _interpret

#: tokens of a chunk: the paper's, ``fla``'s and ``transformers``' 64
CHUNK = 64
_EXACT = lax.Precision.HIGHEST


def step(q, k, v, log_alpha, beta, state):
    """One token of every row.  q, k: (B, H, d_k); v: (B, H, d_v);
    log_alpha, beta: (B, H); state: (B, H, d_k, d_v) float32.  Returns
    (o (B, H, d_v) float32, the new state).

    The state is read twice and written once: both contractions are
    taken of the OLD state in one pass (``S_t^T q = alpha S^T q + (k . q)
    delta``: the rule's own algebra, nothing approximated), the update is
    the second.

    ``log_alpha`` (B, H, d_k): the decay is PER KEY CHANNEL (``S' = Diag(alpha)
    S``, Kimi Delta Attention): ``S'^T x = S^T (alpha * x)``, so the two
    contractions are taken of the old state against ``alpha k`` and ``alpha
    q``, still in one pass."""
    q, k, v, beta = (x.astype(jnp.float32) for x in (q, k, v, beta))
    if log_alpha.ndim == k.ndim:
        alpha = jnp.exp(log_alpha.astype(jnp.float32))              # (B, H, d_k)
        seen_k = (state * (alpha * k)[..., None]).sum(-2)            # S'^T k
        seen_q = (state * (alpha * q)[..., None]).sum(-2)            # S'^T q
        delta = beta[..., None] * (v - seen_k)
        o = seen_q + (k * q).sum(-1, keepdims=True) * delta
        return o, alpha[..., None] * state + k[..., None] * delta[..., None, :]
    alpha = jnp.exp(log_alpha.astype(jnp.float32))[..., None]       # (B, H, 1)
    seen_k = (state * k[..., None]).sum(-2)                          # S^T k
    seen_q = (state * q[..., None]).sum(-2)                          # S^T q
    delta = beta[..., None] * (v - alpha * seen_k)                   # (B, H, d_v)
    o = alpha * seen_q + (k * q).sum(-1, keepdims=True) * delta
    state = alpha[..., None] * state + k[..., None] * delta[..., None, :]
    return o, state


def packed(state):
    """(..., H, d_k, d_v) -> (..., d_k, H d_v): a row's heads side by side,
    the layout the cache keeps and ``step_layer`` updates."""
    *lead, H, d_k, d_v = state.shape
    return jnp.moveaxis(state, -3, -2).reshape(*lead, d_k, H * d_v)


def unpacked(state, heads: int):
    """``packed``'s inverse: (..., d_k, H d_v) -> (..., H, d_k, d_v)."""
    *lead, d_k, columns = state.shape
    return jnp.moveaxis(state.reshape(*lead, d_k, heads, columns // heads), -2, -3)


def step_layer(q, k, v, log_alpha, beta, states, layer):
    """``step`` on layer ``layer`` (a traced scalar) of ``states`` (L, B,
    d_k, H d_v) float32, every layer's rows ``packed``.  Returns (o (B, H,
    d_v) float32, ``states`` with that layer's rows updated)."""
    B, H, d_k = k.shape
    if implementation(B, H, d_k, v.shape[-1]) == "in_place":
        return step_in_place(q, k, v, log_alpha, beta, states, layer)
    state = unpacked(lax.dynamic_index_in_dim(states, layer, 0, keepdims=False), H)
    o, state = step(q, k, v, log_alpha, beta, state)
    return o, lax.dynamic_update_index_in_dim(states, packed(state), layer, 0)


def _group(d_v: int) -> int:
    """Heads whose columns side by side are whole 128-lane tiles."""
    return 128 // math.gcd(d_v, 128)


def implementation(rows: int, heads: int, d_k: int, d_v: int) -> str:
    """Which body the one-token update of (rows, heads) states of (d_k, d_v)
    traces: ``"in_place"`` — the kernel — where a row's state (d_k, heads x
    d_v) is whole (8, 128) tiles (d_k whole sublane tiles inside the one
    transpose a row, the heads whole groups of lane tiles) and the rows are
    whole sublane tiles of the gates, or fewer than one; else ``"xla"``."""
    if (d_k % 8 == 0 and d_k <= 128 and heads % _group(d_v) == 0
            and (rows % 8 == 0 or rows < 8)):
        return "in_place"
    return "xla"


#: rows of one grid step's block of the state (where the rows are whole
#: sublane tiles), and the bytes of that block at most: in and out, two
#: buffers each, lie in fast memory at once
ROWS_A_STEP = 4
STEP_BYTES = 3 << 20
VMEM_LIMIT_BYTES = 24 << 20


def _heads_a_step(heads: int, d_k: int, d_v: int) -> int:
    """Heads whose columns are one grid step's block: the most whole groups
    that divide ``heads`` and fit ``STEP_BYTES`` at ``ROWS_A_STEP`` rows."""
    g = _group(d_v)
    fit = [n for n in range(g, min(heads, 128) + 1, g)
           if heads % n == 0 and ROWS_A_STEP * 4 * d_k * n * d_v <= STEP_BYTES]
    return max(fit, default=g)


def _kernel(layer_ref, kq_ref, gates_ref, s_ref, o_ref, out_ref, kt_ref, kb_ref, *, d_v):
    """One grid step: ``rows`` rows' columns of ``heads`` heads.  kq (rows,
    2, heads padded to whole sublane tiles, 128): k and q as the layer hands
    them over, a head a sublane, d_k along the lanes; gates (4, 8 rows, C):
    alpha, beta, v and k . q at every column of their head, a row a sublane;
    s, out (rows, d_k, C); o (8 rows, C).  Scratch: kt (2, 128, 128), k and
    q with d_k DOWN the sublanes (one transpose a row), kb (d_k, W) a
    group's k at the lanes of its heads.

    Inside a row, a GROUP of heads at a time (W = whole lane tiles: a head
    pair, 384 lanes, at d_v 192), static slices all: the first pass takes
    both contractions of the old state, eight sublanes at a time, into two
    (8, W) accumulators; the second writes ``alpha S + k (x) delta``."""
    del layer_ref
    rows, d_k, C = s_ref.shape
    # the block of gates and o holds this step's rows from ``base`` on
    base = (pl.program_id(1) * rows) % o_ref.shape[0]
    g = _group(d_v)
    W = g * d_v
    lane = lax.broadcasted_iota(jnp.int32, (8, 128), 1)
    f32 = jnp.float32

    def a_row(r, carry):
        for i in range(2):
            x = kq_ref[r, i]
            x = jnp.concatenate([x, jnp.zeros((128 - x.shape[0], 128), f32)], 0)
            kt_ref[i] = x.T
        for p in range(C // W):
            cols = pl.ds(p * W, W)

            def at_its_lanes(i, sl):  # k or q, sublanes ``sl``, at its head's columns: (8, W)
                heads = [
                    jnp.broadcast_to(kt_ref[i, sl, p * g + h:p * g + h + 1], (8, 128))
                    for h in range(g)
                ]
                tiles = []
                for t in range(W // 128):  # the heads that own lanes of tile t
                    first, last = t * 128 // d_v, (t * 128 + 127) // d_v
                    tile = heads[first]
                    for h in range(first + 1, last + 1):
                        tile = jnp.where(lane < h * d_v - t * 128, tile, heads[h])
                    tiles.append(tile)
                return jnp.concatenate(tiles, 1)

            alpha, beta, v, kq = (gates_ref[i, pl.ds(base + r, 1), cols] for i in range(4))

            def contract(n, acc):  # sublanes n 8 .. of both contractions
                sl = pl.ds(pl.multiple_of(n * 8, 8), 8)
                S, K = s_ref[r, sl, cols], at_its_lanes(0, sl)
                kb_ref[sl, :] = K
                return acc[0] + S * K, acc[1] + S * at_its_lanes(1, sl)

            # traced once, unrolled when lowered: static slices in the kernel
            # (a loop the chip runs is 2.2 times slower), a twelfth of the
            # equations in the program's trace
            acc_k, acc_q = lax.fori_loop(
                0, d_k // 8, contract, (jnp.zeros((8, W), f32),) * 2, unroll=True)
            seen_k = jnp.sum(acc_k, axis=0, keepdims=True)           # S^T k
            seen_q = jnp.sum(acc_q, axis=0, keepdims=True)           # S^T q
            delta = beta * (v - alpha * seen_k)                      # (1, W)
            o_ref[pl.ds(base + r, 1), cols] = alpha * seen_q + kq * delta
            alpha8, delta8 = jnp.broadcast_to(alpha, (8, W)), jnp.broadcast_to(delta, (8, W))

            def write(n, carry):
                sl = pl.ds(pl.multiple_of(n * 8, 8), 8)
                out_ref[r, sl, cols] = alpha8 * s_ref[r, sl, cols] + kb_ref[sl, :] * delta8
                return carry

            lax.fori_loop(0, d_k // 8, write, 0, unroll=True)
        return carry

    lax.fori_loop(0, rows, a_row, 0)


def step_in_place(q, k, v, log_alpha, beta, states, layer):
    """The kernel: ``step`` on layer ``layer`` () int32 of ``states`` (L, B,
    d_k, H d_v) float32, which is the output's buffer
    (``input_output_aliases``): the layer's blocks move, nothing else.  q,
    k: (B, H, d_k); v: (B, H, d_v); log_alpha, beta: (B, H) — or log_alpha
    (B, H, d_k), a decay a key channel: the kernel ``kda_step``
    (``_kernel_channels``).  Returns (o (B, H, d_v) float32, ``states``)."""
    B, H, d_k = k.shape
    d_v = v.shape[-1]
    if implementation(B, H, d_k, d_v) != "in_place" or states.shape[1:] != (B, d_k, H * d_v):
        raise ValueError(
            f"the one-pass update wants a row's state in whole (8, 128) tiles, "
            f"packed (layers, rows, d_k, heads x d_v): q {q.shape}, v {v.shape}, "
            f"states {states.shape}"
        )
    if log_alpha.ndim == k.ndim:
        return _step_in_place_channels(q, k, v, log_alpha, beta, states, layer)
    hb = _heads_a_step(H, d_k, d_v)
    nb, C = H // hb, hb * d_v
    rb, gr = (ROWS_A_STEP, 8) if B % 8 == 0 else (1, B)
    f32 = jnp.float32
    q, k, v, beta = (x.astype(f32) for x in (q, k, v, beta))
    alpha = jnp.exp(log_alpha.astype(f32))
    # a row a sublane, as the layer makes ``v``: with the rows in front XLA
    # hands that layout on to what made ``v``, and the loop's carried
    # ``gdn_conv`` was copied in and out of every step (compile-only, PR 48)
    gates = jnp.stack([
        jnp.repeat(alpha, d_v, axis=-1), jnp.repeat(beta, d_v, axis=-1),
        v.reshape(B, H * d_v), jnp.repeat((k * q).sum(-1), d_v, axis=-1),
    ])                                                               # (4, B, H d_v)
    # k and q as they are made too, a head a row: the kernel transposes
    hbp = -(-hb // 8) * 8
    kq = jnp.stack([k, q], 1).reshape(B, 2, nb, hb, d_k).swapaxes(1, 2)
    kq = jnp.pad(kq, ((0, 0),) * 3 + ((0, hbp - hb), (0, 128 - d_k)))   # (B, nb, 2, hbp, 128)
    # columns outermost, rows innermost: a block of gates and of o serves
    # the ``gr / rb`` steps that follow one another
    state = pl.BlockSpec((None, rb, d_k, C), lambda j, b, layer: (layer[0], b, 0, j))
    o, states = pl.pallas_call(
        functools.partial(_kernel, d_v=d_v),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb, B // rb),
            in_specs=[
                pl.BlockSpec((rb, None, 2, hbp, 128), lambda j, b, layer: (b, j, 0, 0, 0)),
                pl.BlockSpec((4, gr, C), lambda j, b, layer: (0, b * rb // gr, j)),
                state,
            ],
            out_specs=[pl.BlockSpec((gr, C), lambda j, b, layer: (b * rb // gr, j)), state],
            scratch_shapes=[
                pltpu.VMEM((2, 128, 128), f32),
                pltpu.VMEM((d_k, _group(d_v) * d_v), f32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, H * d_v), f32),
            jax.ShapeDtypeStruct(states.shape, f32),
        ],
        input_output_aliases={3: 1},  # operands count the scalar-prefetch one
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=_interpret(),
        name="gated_delta_step",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), kq, gates, states)
    return o.reshape(B, H, d_v), states


def _head_lanes(kt_ref, i, sl, p, d_v):
    """Operand ``i`` of ``kt_ref`` (d_k down the sublanes, a head a lane),
    sublanes ``sl``, at the columns of group ``p``'s heads: (8, W)."""
    g = _group(d_v)
    W = g * d_v
    lane = lax.broadcasted_iota(jnp.int32, (8, 128), 1)
    heads = [jnp.broadcast_to(kt_ref[i, sl, p * g + h:p * g + h + 1], (8, 128))
             for h in range(g)]
    tiles = []
    for t in range(W // 128):  # the heads that own lanes of tile t
        first, last = t * 128 // d_v, (t * 128 + 127) // d_v
        tile = heads[first]
        for h in range(first + 1, last + 1):
            tile = jnp.where(lane < h * d_v - t * 128, tile, heads[h])
        tiles.append(tile)
    return jnp.concatenate(tiles, 1)


def _kernel_channels(layer_ref, kqa_ref, gates_ref, s_ref, o_ref, out_ref,
                     kt_ref, kb_ref, ab_ref, *, d_v):
    """``_kernel`` with a decay a key channel.  kqa (rows, 3, heads padded,
    128): k, q and alpha (heads, d_k) as the layer makes them; gates (3, 8
    rows, C): beta, v and k . q at every column of their head; scratch kt (3,
    128, 128) the three transposed, kb / ab (d_k, W) a group's k and alpha at
    the lanes of its heads.  The first pass takes ``S^T (alpha k)`` and ``S^T
    (alpha q)`` of the old state, the second writes ``alpha S + k (x) delta``
    with alpha down the sublanes: the block still comes in once and goes out
    once."""
    del layer_ref
    rows, d_k, C = s_ref.shape
    base = (pl.program_id(1) * rows) % o_ref.shape[0]
    W = _group(d_v) * d_v
    f32 = jnp.float32

    def a_row(r, carry):
        for i in range(3):
            x = kqa_ref[r, i]
            x = jnp.concatenate([x, jnp.zeros((128 - x.shape[0], 128), f32)], 0)
            kt_ref[i] = x.T
        for p in range(C // W):
            cols = pl.ds(p * W, W)
            # the row's gates out of their whole (8, W) tile by a mask: at one
            # lane tile a head (d_v 128) a one-sublane load at a traced index
            # does not lower ("dynamic load with unaligned indices")
            mine = lax.broadcasted_iota(jnp.int32, (o_ref.shape[0], W), 0) == base + r
            beta, v, kq = (
                jnp.sum(jnp.where(mine, gates_ref[i, :, cols], 0.0), axis=0, keepdims=True)
                for i in range(3))

            def contract(n, acc):  # sublanes n 8 .. of both contractions
                sl = pl.ds(pl.multiple_of(n * 8, 8), 8)
                K, A = _head_lanes(kt_ref, 0, sl, p, d_v), _head_lanes(kt_ref, 2, sl, p, d_v)
                kb_ref[sl, :] = K
                ab_ref[sl, :] = A
                decayed = s_ref[r, sl, cols] * A
                return (acc[0] + decayed * K,
                        acc[1] + decayed * _head_lanes(kt_ref, 1, sl, p, d_v))

            acc_k, acc_q = lax.fori_loop(
                0, d_k // 8, contract, (jnp.zeros((8, W), f32),) * 2, unroll=True)
            seen_k = jnp.sum(acc_k, axis=0, keepdims=True)           # S'^T k
            seen_q = jnp.sum(acc_q, axis=0, keepdims=True)           # S'^T q
            delta = beta * (v - seen_k)                              # (1, W)
            o_ref[:, cols] = jnp.where(mine, seen_q + kq * delta, o_ref[:, cols])
            delta8 = jnp.broadcast_to(delta, (8, W))

            def write(n, carry):
                sl = pl.ds(pl.multiple_of(n * 8, 8), 8)
                out_ref[r, sl, cols] = (
                    ab_ref[sl, :] * s_ref[r, sl, cols] + kb_ref[sl, :] * delta8)
                return carry

            lax.fori_loop(0, d_k // 8, write, 0, unroll=True)
        return carry

    lax.fori_loop(0, rows, a_row, 0)


def _step_in_place_channels(q, k, v, log_alpha, beta, states, layer):
    """``step_in_place`` for ``log_alpha`` (B, H, d_k): the same grid and the
    same blocks of the state under the kernel ``kda_step``; alpha rides with
    k and q (a head a sublane, d_k along the lanes) and is transposed with
    them."""
    B, H, d_k = k.shape
    d_v = v.shape[-1]
    hb = _heads_a_step(H, d_k, d_v)
    nb, C = H // hb, hb * d_v
    rb, gr = (ROWS_A_STEP, 8) if B % 8 == 0 else (1, B)
    f32 = jnp.float32
    q, k, v, beta = (x.astype(f32) for x in (q, k, v, beta))
    alpha = jnp.exp(log_alpha.astype(f32))
    gates = jnp.stack([
        jnp.repeat(beta, d_v, axis=-1), v.reshape(B, H * d_v),
        jnp.repeat((k * q).sum(-1), d_v, axis=-1),
    ])                                                               # (3, B, H d_v)
    hbp = -(-hb // 8) * 8
    kqa = jnp.stack([k, q, alpha], 1).reshape(B, 3, nb, hb, d_k).swapaxes(1, 2)
    kqa = jnp.pad(kqa, ((0, 0),) * 3 + ((0, hbp - hb), (0, 128 - d_k)))  # (B, nb, 3, hbp, 128)
    state = pl.BlockSpec((None, rb, d_k, C), lambda j, b, layer: (layer[0], b, 0, j))
    W = _group(d_v) * d_v
    o, states = pl.pallas_call(
        functools.partial(_kernel_channels, d_v=d_v),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb, B // rb),
            in_specs=[
                pl.BlockSpec((rb, None, 3, hbp, 128), lambda j, b, layer: (b, j, 0, 0, 0)),
                pl.BlockSpec((3, gr, C), lambda j, b, layer: (0, b * rb // gr, j)),
                state,
            ],
            out_specs=[pl.BlockSpec((gr, C), lambda j, b, layer: (b * rb // gr, j)), state],
            scratch_shapes=[
                pltpu.VMEM((3, 128, 128), f32),
                pltpu.VMEM((d_k, W), f32),
                pltpu.VMEM((d_k, W), f32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, H * d_v), f32),
            jax.ShapeDtypeStruct(states.shape, f32),
        ],
        input_output_aliases={3: 1},  # operands count the scalar-prefetch one
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=_interpret(),
        name="kda_step",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), kqa, gates, states)
    return o.reshape(B, H, d_v), states


def scan(q, k, v, log_alpha, beta, state0, valid=None, chunk: int = CHUNK):
    """A run of S tokens of every row, from ``state0``.  q, k: (B, S, H,
    d_k); v: (B, S, H, d_v); log_alpha, beta: (B, S, H); state0: (B, H,
    d_k, d_v); valid: (B, S) bool, the run's real tokens (None: all).
    Returns (o (B, S, H, d_v) float32, the final state float32).

    A position that is not ``valid`` is the identity (alpha 1, beta 0):
    it leaves the state as it found it, and its own output is not to be
    read.  S is padded up to whole chunks with such positions, so the
    final state is that of the real tokens whatever S is.

    ``log_alpha`` (B, S, H, d_k), a decay a key channel: the kernel
    ``kda_chunk`` or ``_scan_channels``, as ``scan_implementation`` reads the
    shapes."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    channels = log_alpha.ndim == k.ndim
    if scan_implementation(H, dk, dv, chunk, channels) == "kernel":
        return _scan_kernel(q, k, v, log_alpha, beta, state0, valid)
    if channels:
        return _scan_channels(q, k, v, log_alpha, beta, state0, valid, chunk)
    pad = -S % chunk
    real = jnp.ones((B, S), bool) if valid is None else valid
    real = jnp.pad(real, ((0, 0), (0, pad)))[..., None]              # (B, S', 1)

    def chunked(x):  # (B, S or S', H, ...) -> (B, H, N, chunk, ...) float32
        x = x.astype(jnp.float32)
        x = jnp.pad(x, ((0, 0), (0, S + pad - x.shape[1])) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape(B, -1, chunk, *x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    g = chunked(_masked(log_alpha, real, pad))
    b = chunked(_masked(beta, real, pad))
    q, k, v = chunked(q), chunked(k), chunked(v)                     # (B, H, N, C, d)
    g = jnp.cumsum(g, axis=-1)                                       # (B, H, N, C)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # decay from token j to token i of a chunk, i >= j; masked BEFORE the
    # exponential: above the diagonal the difference is positive
    decay = jnp.exp(jnp.where(lower, g[..., :, None] - g[..., None, :], -jnp.inf))
    k_beta, v_beta = k * b[..., None], v * b[..., None]

    def mm(eq, x, y):
        return jnp.einsum(eq, x, y, precision=_EXACT)

    # the rule inside a chunk: (I + A) u = beta (v - decayed S0^T k), A
    # strictly lower; its inverse by the finite series of a nilpotent
    # matrix, sum (-A)^n = prod (I + (-A)^(2^j)): log2(chunk) squarings
    a = -jnp.where(jnp.tril(lower, -1), mm("bhnid,bhnjd->bhnij", k_beta, k) * decay, 0.0)
    eye = jnp.eye(chunk, dtype=jnp.float32)
    solve, power = eye + a, a
    for _ in range(max(0, (chunk - 1).bit_length() - 1)):
        power = mm("bhnij,bhnjk->bhnik", power, power)
        solve = mm("bhnij,bhnjk->bhnik", solve, eye + power)
    u = mm("bhnij,bhnjd->bhnid", solve, v_beta)                      # (B, H, N, C, d_v)
    w = mm("bhnij,bhnjd->bhnid", solve, k_beta * jnp.exp(g)[..., None])
    within = mm("bhnid,bhnjd->bhnij", q, k) * decay                  # i >= j
    q_in = q * jnp.exp(g)[..., None]                                 # against the chunk's S0
    k_out = k * jnp.exp(g[..., -1:] - g)[..., None]                  # into the chunk's end
    total = jnp.exp(g[..., -1])[..., None, None]                     # (B, H, N, 1, 1)

    def one(state, c):
        u_c, w_c, within_c, q_c, k_c, total_c = c
        new = u_c - mm("bhid,bhdv->bhiv", w_c, state)
        o = mm("bhid,bhdv->bhiv", q_c, state) + mm("bhij,bhjv->bhiv", within_c, new)
        return total_c * state + mm("bhid,bhiv->bhdv", k_c, new), o

    over_chunks = tuple(jnp.moveaxis(x, 2, 0) for x in (u, w, within, q_in, k_out, total))
    state, o = lax.scan(one, state0.astype(jnp.float32), over_chunks)
    o = jnp.moveaxis(o, 0, 2).reshape(B, H, -1, dv)[:, :, :S]        # (B, H, S, d_v)
    return jnp.moveaxis(o, 1, 2), state


#: tokens of a sub-chunk of the per-channel rule (``fla``'s ``chunk_kda``: 16)
SUB_CHUNK = 16


def _scan_channels(q, k, v, log_alpha, beta, state0, valid=None, chunk: int = CHUNK):
    """``scan`` with a decay a key channel (Kimi Delta Attention,
    arXiv:2510.26692): log_alpha (B, S, H, d_k), the rest as ``scan``'s.

    The algebra is ``scan``'s with ``Diag(alpha)`` in ``alpha``'s place: with
    G_i the decays' running sum inside a chunk (a vector over d_k), ``(I + A)
    new = beta v - (beta k * e^G) S0``, ``A_ij = beta_i sum_d k_id k_jd
    e^(G_id - G_jd)`` (i > j), ``o_i = (q_i * e^G_i) S0 + sum_{j<=i} (sum_d
    q_id k_jd e^(G_id - G_jd)) new_j``, ``S_C = Diag(e^G_C) S0 + sum_j (k_j *
    e^(G_C - G_j)) new_j^T``.  What the scalar gate never needed: ``e^(G_i -
    G_j)`` no longer leaves the sum over d, and its two factors ``e^G_i`` and
    ``e^-G_j`` cannot be taken apart — a channel that decays by e^-20 a token
    has ``e^-G`` = e^1280 at a chunk's end, past float32.  So every exponent
    that is formed is <= 0:

    * between SUB-CHUNKS of ``SUB_CHUNK`` tokens (i in sub-chunk I, j in an
      earlier one) both factors are taken against a reference point between
      them, R_I = G at the last token before I: ``e^(G_i - R_I)`` and ``e^(R_I
      - G_j)``, each in (0, 1] because G falls.  One matrix product a row of
      sub-chunks, the same operations as the scalar form.  A factor that
      underflows to 0 stands for a product smaller still;
    * inside a sub-chunk the difference ``G_i - G_j`` is formed per channel
      BEFORE the exponential (masked to -inf above the diagonal) and summed
      over d: (SUB_CHUNK, SUB_CHUNK, d_k) a sub-chunk, a quarter of a chunk's
      pairs.

    The triangular system is solved as in ``scan`` (the nilpotent series);
    beta up to 2 (negative eigenvalues) changes nothing of it.  Everything
    float32, products at HIGHEST."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    pad = -S % chunk
    sub = SUB_CHUNK if chunk % SUB_CHUNK == 0 else chunk
    nI = chunk // sub
    real = jnp.ones((B, S), bool) if valid is None else valid
    real = jnp.pad(real, ((0, 0), (0, pad)))                         # (B, S')

    def chunked(x):  # (B, S or S', H, ...) -> (B, H, N, chunk, ...) float32
        x = x.astype(jnp.float32)
        x = jnp.pad(x, ((0, 0), (0, S + pad - x.shape[1])) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape(B, -1, chunk, *x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    g = jnp.pad(log_alpha.astype(jnp.float32), ((0, 0), (0, pad), (0, 0), (0, 0)))
    g = chunked(jnp.where(real[..., None, None], g, 0.0))             # (B, H, N, C, d_k)
    b = chunked(_masked(beta, real[..., None], pad))                  # (B, H, N, C)
    q, k, v = chunked(q), chunked(k), chunked(v)
    g = jnp.cumsum(g, axis=-2)
    N = g.shape[2]

    def mm(eq, x, y):
        return jnp.einsum(eq, x, y, precision=_EXACT)

    qk = jnp.stack([q, k])                                           # (2, B, H, N, C, d_k)
    lead = (B, H, N, nI)
    g_sub = g.reshape(*lead, sub, dk)
    # inside a sub-chunk: the difference first, then the exponential
    inside = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
    decay = jnp.exp(jnp.where(inside, g_sub[..., :, None, :] - g_sub[..., None, :, :], -jnp.inf))
    k_sub = k.reshape(*lead, sub, dk)
    diag = (qk.reshape(2, *lead, sub, 1, dk) * (k_sub[..., None, :, :] * decay)).sum(-1)
    pairs = jnp.einsum("xbhnIij,IJ->xbhnIiJj", diag, jnp.eye(nI, dtype=jnp.float32))
    if nI > 1:
        # between sub-chunks: both sides against R_I, G at the last token before I
        ref = jnp.concatenate(
            [jnp.zeros((B, H, N, 1, dk), jnp.float32), g_sub[..., :-1, -1, :]], axis=-2)
        left = qk.reshape(2, *lead, sub, dk) * jnp.exp(g_sub - ref[..., None, :])
        before = (jnp.arange(chunk)[None, :] < (jnp.arange(nI) * sub)[:, None])[..., None]
        right = k[..., None, :, :] * jnp.exp(
            jnp.where(before, ref[..., None, :] - g[..., None, :, :], -jnp.inf))
        pairs = pairs + mm("xbhnIid,bhnIjd->xbhnIij", left, right).reshape(pairs.shape)
    within, kk = pairs.reshape(2, B, H, N, chunk, chunk)             # i >= j, decayed
    k_beta, v_beta = k * b[..., None], v * b[..., None]
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    a = -jnp.where(strict, kk * b[..., None], 0.0)
    eye = jnp.eye(chunk, dtype=jnp.float32)
    solve, power = eye + a, a
    for _ in range(max(0, (chunk - 1).bit_length() - 1)):
        power = mm("bhnij,bhnjk->bhnik", power, power)
        solve = mm("bhnij,bhnjk->bhnik", solve, eye + power)
    u = mm("bhnij,bhnjd->bhnid", solve, v_beta)                      # (B, H, N, C, d_v)
    w = mm("bhnij,bhnjd->bhnid", solve, k_beta * jnp.exp(g))
    q_in = q * jnp.exp(g)                                            # against the chunk's S0
    k_out = k * jnp.exp(g[..., -1:, :] - g)                          # into the chunk's end
    total = jnp.exp(g[..., -1, :])[..., None]                        # (B, H, N, d_k, 1)

    def one(state, c):
        u_c, w_c, within_c, q_c, k_c, total_c = c
        new = u_c - mm("bhid,bhdv->bhiv", w_c, state)
        o = mm("bhid,bhdv->bhiv", q_c, state) + mm("bhij,bhjv->bhiv", within_c, new)
        return total_c * state + mm("bhid,bhiv->bhdv", k_c, new), o

    over_chunks = tuple(jnp.moveaxis(x, 2, 0) for x in (u, w, within, q_in, k_out, total))
    state, o = lax.scan(one, state0.astype(jnp.float32), over_chunks)
    o = jnp.moveaxis(o, 0, 2).reshape(B, H, -1, dv)[:, :, :S]        # (B, H, S, d_v)
    return jnp.moveaxis(o, 1, 2), state


#: heads of one grid step of ``kda_chunk``: a sublane tile of the (tokens,
#: heads, d) blocks the operands come in as
HEADS_A_STEP = 8
#: heads whose chains one trip of the kernel's loop over a step's heads holds
#: side by side (the scheduler's to interleave)
HEADS_A_TRIP = 2
SCAN_VMEM_LIMIT_BYTES = 32 << 20


def scan_implementation(heads: int, d_k: int, d_v: int, chunk: int, channels: bool) -> str:
    """Which body a run of the chunked rule traces: ``"kernel"`` — ``kda_chunk``
    — where the decay is a key channel's (``channels``), a head's d_k and d_v
    are whole 128-lane tiles, the chunk is the kernel's 64 tokens and the
    heads are whole groups of ``HEADS_A_STEP``; else ``"xla"``, the
    ``jax.numpy`` bodies."""
    if (channels and d_k % 128 == 0 and d_v % 128 == 0 and chunk == CHUNK
            and heads % HEADS_A_STEP == 0):
        return "kernel"
    return "xla"


def _mm(x, y, contract=((1,), (0,))):
    """A float32 product on the MXU, exact (six bfloat16 passes)."""
    return lax.dot_general(x, y, (contract, ((), ())), precision=_EXACT,
                           preferred_element_type=jnp.float32)


def _substitute(a, rhs, sub):
    """``(I + A)^-1 rhs`` for ``a = -A`` strictly lower triangular (C, C), rhs
    (C, W): forward substitution by blocks of ``sub`` rows.  What the earlier
    blocks' rows add to a block's right side is one product on the MXU; inside
    a block a row is final once the rows before it are, and is then taken off
    the rows under it — its column of ``a`` along the lanes (a one-hot sum), the
    row along the sublanes, float32 multiplies and adds on the VPU.  Exact: no
    series, no inverse is formed."""
    C, W = rhs.shape
    lane = lax.broadcasted_iota(jnp.int32, (8, C), 1)
    solved = []
    for r0 in range(0, C, sub):
        a_I, x = a[r0:r0 + sub], rhs[r0:r0 + sub]
        if r0:
            done = jnp.concatenate(solved + [jnp.zeros((C - r0, W), jnp.float32)], axis=0)
            x = x + _mm(a_I, done)
        tiles = [x[t:t + 8] for t in range(0, sub, 8)]
        for j in range(sub - 1):
            t0, at = divmod(j, 8)
            x_j = jnp.broadcast_to(tiles[t0][at:at + 1], (8, W))
            for t in range(t0 + (at == 7), len(tiles)):
                column = jnp.sum(jnp.where(lane == r0 + j, a_I[8 * t:8 * t + 8], 0.0),
                                 axis=1, keepdims=True)
                tiles[t] = tiles[t] + column * x_j
        solved += tiles
    return jnp.concatenate(solved, axis=0)


def _chunk_state_step(within, a, v_beta, k_in, q_in, k_out, total, state, sub):
    """What every chunked delta rule does once its pair scores stand, one head
    and one chunk of C tokens (the scalar gate's scores can be put under it as
    they are): ``within`` (C, C) the decayed q k^T, i >= j; ``a`` (C, C) minus
    the decayed beta k k^T, i > j; ``v_beta`` (C, d_v); ``k_in`` (C, d_k) the
    decayed beta k against the chunk's first state, ``q_in`` q likewise;
    ``k_out`` k decayed to the chunk's end; ``total`` (d_k, 1) the chunk's
    whole decay; ``state`` (d_k, d_v).  Returns (o (C, d_v), the next
    state)."""
    C, d_v = v_beta.shape
    rhs = jnp.concatenate([v_beta, k_in], axis=1)                    # (C, d_v + d_k)
    uw = _substitute(a, rhs, sub)
    seen = _mm(jnp.concatenate([uw[:, d_v:], q_in], axis=0), state)  # (2 C, d_v)
    new = uw[:, :d_v] - seen[:C]
    o = seen[C:] + _mm(within, new)
    return o, total * state + _mm(k_out, new, ((0,), (0,)))


def _kda_chunk_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s0_ref, o_ref, s_ref,
                      qs, ks, vs, gs, bs, os_, *, sub):
    """One grid step of ``kda_chunk``: one chunk of C tokens of ``hb`` heads
    of one row.  q, k, g: (C, hb, d_k) and v, o: (C, hb, d_v) as the mixer
    makes them; b (C, hb) beta; s0, s: (hb, d_k, d_v), the group's state —
    ``s`` is the output's block, which stays in fast memory over the chunk
    axis (its index does not move): set from ``s0`` at the first chunk,
    carried, written out after the last.  Scratch (hb, C, d): the operands a
    head in front, beta along the lanes, and o."""
    C, hb, d_k = q_ref.shape
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    # a head's 64 rows out of the (token, head) sublanes: strided loads, static
    for h in range(hb):
        for src, dst in ((q_ref, qs), (k_ref, ks), (v_ref, vs), (g_ref, gs)):
            dst[h] = src[:, h, :]
        bs[h] = jnp.broadcast_to(b_ref[:, h:h + 1], (C, 128))

    row = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    token = lax.broadcasted_iota(jnp.int32, (C, d_k), 0)
    lane = lax.broadcasted_iota(jnp.int32, (8, C), 1)
    diag = (lax.broadcasted_iota(jnp.int32, (d_k, d_k), 0)
            == lax.broadcasted_iota(jnp.int32, (d_k, d_k), 1))

    def a_head(h):
        # the decays' running sum down the chunk: log2(C) shifted adds
        G = gs[h]
        s = 1
        while s < C:
            G = G + jnp.where(token >= s, pltpu.roll(G, s, 0), 0.0)
            s *= 2
        gs[h] = G
        q, k, beta = qs[h], ks[h], bs[h]
        blocks = []
        for I in range(C // sub):
            r0 = I * sub
            rows = slice(r0, r0 + sub)
            G_I, q_I, k_I = G[rows], q[rows], k[rows]
            # inside the sub-chunk: the difference a channel BEFORE the
            # exponential, a pair's sum over d_k along the lanes; 8 rows (a
            # sublane tile) at a time, those at or under the diagonal
            tiles = sub // 8
            acc = [[jnp.zeros((8, C), f32) for _ in range(tiles)] for _ in range(2)]
            for j in range(sub):
                g_j = gs[h, pl.ds(r0 + j, 1), :]
                k_j = ks[h, pl.ds(r0 + j, 1), :]
                for t in range(j // 8, tiles):
                    part = slice(8 * t, 8 * t + 8)
                    # above the diagonal (i < j, masked below) the difference
                    # is positive: held at 0, no exponent above zero
                    decayed = k_j * jnp.exp(jnp.minimum(G_I[part] - g_j, 0.0))
                    for x, x_I in enumerate((q_I, k_I)):
                        pair = jnp.sum(x_I[part] * decayed, axis=1, keepdims=True)
                        acc[x][t] = jnp.where(lane == r0 + j, pair, acc[x][t])
            inside = jnp.concatenate(
                [jnp.concatenate(acc[x], axis=0) for x in range(2)], axis=0)  # (2 sub, C)
            if I:
                # between sub-chunks: both factors against R_I, G at the last
                # token before I (both exponents <= 0, because G falls)
                ref = gs[h, pl.ds(r0 - 1, 1), :]
                left = jnp.exp(G_I - ref)
                right = k[:r0] * jnp.exp(ref - G[:r0])
                right = jnp.concatenate([right, jnp.zeros((C - r0, d_k), f32)], axis=0)
                inside = inside + _mm(
                    jnp.concatenate([q_I * left, k_I * left], axis=0), right, ((1,), (1,)))
            blocks.append(inside)
        within = jnp.concatenate([b[:sub] for b in blocks], axis=0)          # (C, C)
        kk = jnp.concatenate([b[sub:] for b in blocks], axis=0)
        within = jnp.where(row >= col, within, 0.0)
        a = -jnp.where(row > col, kk * beta[:, :C], 0.0)
        decay = jnp.exp(G)
        last = gs[h, pl.ds(C - 1, 1), :]                                      # (1, d_k)
        # the chunk's whole decay down the sublanes: the row, on a diagonal,
        # summed along the lanes
        total = jnp.sum(jnp.where(diag, jnp.exp(last), 0.0), axis=1, keepdims=True)
        o, state = _chunk_state_step(
            within, a, vs[h] * _lanes(beta, vs.shape[-1]),
            k * _lanes(beta, d_k) * decay, q * decay, k * jnp.exp(last - G), total, s_ref[h], sub)
        os_[h] = o
        s_ref[h] = state

    def trip(n, carry):
        for i in range(HEADS_A_TRIP):
            a_head(n * HEADS_A_TRIP + i)
        return carry

    lax.fori_loop(0, hb // HEADS_A_TRIP, trip, 0)
    for h in range(hb):
        o_ref[:, h, :] = os_[h]


def _lanes(beta, width):
    """beta (C, 128), a token's along all its lanes, as wide as ``width``."""
    return beta if width == 128 else jnp.concatenate([beta] * (width // 128), axis=1)


@jax.jit  # traced once a process, not once a layer and program: set-up is judged
def _scan_kernel(q, k, v, log_alpha, beta, state0, valid=None):
    """``_scan_channels`` as the kernel ``kda_chunk`` (module docstring): q,
    k, log_alpha (B, S, H, d_k), v (B, S, H, d_v), beta (B, S, H), state0 (B,
    H, d_k, d_v), as the mixer makes them.  The grid is (row, group of
    ``HEADS_A_STEP`` heads, chunk), the chunk axis last and sequential."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    if scan_implementation(H, dk, dv, CHUNK, log_alpha.ndim == 4) != "kernel":
        raise ValueError(
            f"kda_chunk wants a decay a channel, whole lane tiles a head and whole "
            f"groups of {HEADS_A_STEP} heads: q {q.shape}, v {v.shape}, "
            f"log_alpha {log_alpha.shape}")
    f32 = jnp.float32
    C, hb = CHUNK, HEADS_A_STEP
    pad = -S % C
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, log_alpha, beta))
    if valid is not None:
        # a position that is not valid is the identity: alpha 1, beta 0
        g = jnp.where(valid[..., None, None], g, 0.0)
        beta = jnp.where(valid[..., None], beta, 0.0)
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)) for x in (q, k, v, g, beta))
    N, groups = (S + pad) // C, H // hb
    # beta a group's heads side by side: (C, hb) blocks whose last axis is whole
    beta = jnp.moveaxis(beta.reshape(B, S + pad, groups, hb), 2, 1)

    def tokens(d):
        return pl.BlockSpec((None, C, hb, d), lambda b, j, c: (b, c, j, 0))

    state = pl.BlockSpec((None, hb, dk, dv), lambda b, j, c: (b, j, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_kda_chunk_kernel, sub=SUB_CHUNK),
        grid=(B, groups, N),
        in_specs=[tokens(dk), tokens(dk), tokens(dv), tokens(dk),
                  pl.BlockSpec((None, None, C, hb), lambda b, j, c: (b, j, c, 0)), state],
        out_specs=[tokens(dv), state],
        scratch_shapes=[pltpu.VMEM((hb, C, d), f32) for d in (dk, dk, dv, dk, 128, dv)],
        out_shape=[
            jax.ShapeDtypeStruct((B, S + pad, H, dv), f32),
            jax.ShapeDtypeStruct((B, H, dk, dv), f32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=SCAN_VMEM_LIMIT_BYTES,
        ),
        interpret=_interpret(),
        name="kda_chunk",
    )(q, k, v, g, beta, state0.astype(f32))
    return o[:, :S], state


def _masked(x, real, pad):
    """(B, S, H) padded to whole chunks, zero where no real token is."""
    return jnp.where(real, jnp.pad(x.astype(jnp.float32), ((0, 0), (0, pad), (0, 0))), 0.0)


def recurrent(q, k, v, log_alpha, beta, state0):
    """``scan``'s result by ``step`` token after token: the rule as it is
    written, for tests and references; no serving path calls it."""
    def one(state, t):
        o, state = step(*t, state)
        return state, o

    over_tokens = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, log_alpha, beta))
    state, o = lax.scan(one, state0.astype(jnp.float32), over_tokens)
    return jnp.moveaxis(o, 0, 1), state
