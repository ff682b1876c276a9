"""Flash attention for TPU: two Pallas kernels, `flash_fwd` and `flash_bwd`.

Causal multi-head attention that never materializes the (S, S) score
matrix: a tile of queries meets the tiles of keys at or before it with an
online softmax.  bfloat16 (or float32) operands as the caller hands them,
float32 scores, softmax statistics and accumulation; `dp = do·vᵀ` from the
operands as they arrive (a product of two bfloat16 numbers is exact in
float32: one MXU pass where an upcast to float32 took several).

Layout: q, k, v, o and their gradients are (B, S, H x D) as the
projections leave them (`flash_attention` reshapes a (B, S, H, D)
caller's; `models/gpt2.py` folds the weights' head axes, so its
projections are plain matmuls onto this layout and XLA puts no copy
beside a kernel).  A grid step works on a GROUP of heads that is a
lane-aligned column block (`_heads_a_step`: two 64-wide heads a 128-lane
tile, one head of a whole number of tiles), so no copy moves a half-empty
tile and nothing 64 wide is padded to 128 in HBM.  An odd head count
leaves the last group half outside the array: that head's lanes hold
whatever lay there, its results go nowhere (a write outside an array is
dropped, its `lse` row is cut off), and no head reads another's lanes.
`lse` is (B, groups, heads a group, S) float32 inside, (B, H, S) outside.

Grid: (B, groups, query tiles), the tiles of one (batch row, group) in
order.  A sequence's keys and values stay WHOLE in fast memory — (S, 128)
blocks whose index does not change over the tiles, so they are fetched
once — and a step walks the key tiles 0..i of its query tile i in a loop
(`lax.fori_loop`, the diagonal's tile after it with the mask): a tile
past the diagonal costs neither a fetch nor a loop turn, and no tile pair
costs a grid step.  Both kernels compute the scores TRANSPOSED, (keys,
queries): `lse`, the running maximum and sum and `delta` are then ROWS,
reduced along sublanes (plain vector maxima and sums) and broadcast along
them, where (queries, keys) scores want a cross-lane reduction and a
lane broadcast per 8 queries and tile pair — the dearest thing in the
forward kernel before (the sweep below).

* `flash_fwd`: sᵀ = k·qᵀ, p, the accumulator (D, queries) = vᵀ·p in the
  loop's carry (no scratch), transposed once at the tile's end; `o` and
  `lse` written once a tile.  The scale is folded into q once a tile
  where that is exact (a power of two), else it multiplies the scores.
* `flash_bwd`: ONE recomputation of p a tile pair gives all three
  gradients — 5 matmuls and one exponential pass where a dq and a dk/dv
  kernel take 7 and 2: dv += p·do, dp = v·doᵀ, ds = p (dp − delta), dk +=
  ds·q, dq += dsᵀ·k (the one product that contracts its left operand's
  rows).  dq is a tile's own (the loop's carry); dk, dv accumulate over
  the query tiles in (heads, S, D) float32 scratch and are written at the
  last.  delta = rowsum(do ⊙ o) is made in the kernel, once a tile (left
  to XLA it decided the layout `do` was made in, and the kernels waited
  for a copy of it).

What is resident grows with S — 1 KB a token forward, 3 KB backward at
two 64-wide heads — so `vmem_limit_bytes` is reckoned from the shapes and
a sequence that cannot fit (past ~16k tokens backward) is refused with
the way out (`ops/ring_attention.py`).  (o, lse) carry checkpoint names
(`RESIDUAL_NAMES`), so a rematted caller keeps them and does not run the
forward kernel twice.

The sweep (a v5e, PR 50, four chip calls; ms ONE call by the profiler's
op line, parent and variants in one process; operands bfloat16 at the two
training cells' shapes B x H x S x D = 8 x 16 x 1,024 x 64 | 8 x 25 x
1,024 x 64, and Olmo-Hybrid's prefill 1 x 30 x 2,048 x 128, forward only):

                                      forward        dq + dk/dv or one backward
    parent (PR 35): (B, H, nq, nk)    1.203 | 1.925  0.600 + 0.825 | 0.986 + 1.366   Olmo 0.995
      grid, 512 x 512, one head
    a grid step a LIVE tile pair (scalar-prefetch list), float32 scratch, head
    pairs, one-pass dp, (queries, keys) scores; query tile x key tile:
      128 x 128                       2.420 | 3.937  1.805 + 1.682 | 3.088 + 2.855
      256 x 256                       1.455 | 2.366  0.783 + 0.866 | 1.289 + 1.417
      512 x 512                       1.013 | 1.647  0.652 + 0.678 | 1.052 + 1.088   Olmo 0.682
      256 x 1,024                     0.845 | 1.374  0.808 + 0.914 | 1.313 + 1.478
      1,024 x 1,024 (all of S x S)    0.768 | 1.248  0.741 + 0.813 | 1.182 + 1.291   Olmo 0.481
    the same, a walked tile's dead sub-tiles cut away by static bodies:
      256 x 1,024                     0.722 | 1.173  0.779 + 0.632 | 1.261 + 1.022
      512 x 1,024                     0.720 | 1.169  0.761 + 0.659 | 1.226 + 1.059
    keys and values resident, a loop over key tiles, (queries, keys) forward,
    ONE backward kernel; tile:
      128                             1.450 | 2.355  1.702 | 2.745
      256                             0.911 | 1.479  1.053 | 1.692
      512                             0.622 | 1.010  0.890 | 1.427
      1,024 forward (all of S x S)    0.540 | 0.875
      256, four heads a step          0.838 | 1.487  0.933 | 1.641
    the same with the forward's scores transposed:
      256                             0.688 | 1.118
    **512                             0.390 | 0.633  0.890 | 1.427**                 Olmo 0.372
      1,024                           0.377 | 0.611
      512, four heads a step          0.346 | 0.612  0.852 | 1.491

What it says.  Skipping dead tiles, float32 scratch and head pairs alone
bought 20%: with (queries, keys) scores a tile pair costs 5 ns a query
row whatever its width (the row maximum's and sum's cross-lane
reductions, the column broadcasts), so ONE 1,024 x 1,024 tile that
computes twice the visible scores beat every schedule that skipped the
dead half, and static bodies for the dead sub-tiles bought 6%.  Rows in
place of columns took the forward from 0.62 to 0.39 ms at the same tile;
one recomputation in place of two took the backward from 1.33 to 0.89.
Past 512 nothing is left forward (3% for a third more scores); a 1,024
backward tile was not tried (512 compiles in 9 s, 256 in 2.4).  Four heads a step help medium's forward (11%) and lose at 25
heads, whose last group would be three quarters empty.  In all: 2.63 →
1.28 ms a layer at medium's shape, 4.28 → 2.06 at XL's, Olmo-Hybrid's
2,048-token forward 0.995 → 0.372; the forward does its 25.8 GFLOP of
live tiles at 66 TFLOP/s (20.7 before), the backward 64.4 at 72, of the
98 a 64-wide contraction leaves of the chip's 197.

Role-equivalent to the reference's fused GPU attention paths (those
delegate to torch/cutlass; the MXU/VMEM design here is original).  Off
the chip the kernels run in Pallas interpret mode (`_interpret`): tests
exercise the same code on CPU, and nothing switches to the dense einsum.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
#: fast memory a grid step may take beside what is resident: its tiles and
#: a tile pair's float32 scores (the compiler's own limit for a kernel)
TILE_VMEM_BYTES = 16 << 20
#: a v5e core's fast memory
VMEM_BYTES = 128 << 20
_NT = (((1,), (1,)), ((), ()))  # a · bᵀ
_NN = (((1,), (0,)), ((), ()))  # a · b
_TN = (((0,), (0,)), ((), ()))  # aᵀ · b


def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _visible(blk):
    """The diagonal tile's mask over (keys, queries): a query sees the keys
    at or before it."""
    return (lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
            >= lax.broadcasted_iota(jnp.int32, (blk, blk), 0))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, fold, heads):
    """Query tile i against the key tiles 0..i.  q, o (blk, heads x D); k,
    v (S, heads x D); lse (heads, blk).  Scores transposed (keys,
    queries): the running maximum and sum are rows, reduced along
    sublanes and broadcast along them, and the accumulator is (D,
    queries) until the tile's end."""
    i = pl.program_id(2)
    blk = q_ref.shape[0]
    D = q_ref.shape[1] // heads
    qs = q_ref[...] * scale if fold else q_ref[...]
    keep = _visible(blk)

    def pair(j, state, masked):
        rows = pl.ds(pl.multiple_of(j * blk, blk), blk)
        out = []
        for h in range(heads):
            cols = slice(h * D, (h + 1) * D)
            m_prev, l_prev, acc = state[h]
            s = _dot(k_ref[rows, cols], qs[:, cols], _NT)  # (keys, queries)
            if not fold:
                s = s * scale
            if masked:
                s = jnp.where(keep, s, NEG_INF)
            m = jnp.maximum(m_prev, s.max(axis=0, keepdims=True))
            p = jnp.exp(s - m)
            corr = jnp.exp(m_prev - m)
            l = l_prev * corr + p.sum(axis=0, keepdims=True)
            v = v_ref[rows, cols]
            out.append((m, l, acc * corr + _dot(v, p.astype(v.dtype), _TN)))
        return tuple(out)

    state = tuple(
        (jnp.full((1, blk), NEG_INF, jnp.float32), jnp.zeros((1, blk), jnp.float32),
         jnp.zeros((D, blk), jnp.float32))
        for _ in range(heads))
    state = lax.fori_loop(0, i, lambda j, st: pair(j, st, False), state)
    state = pair(i, state, True)
    for h in range(heads):
        m, l, acc = state[h]  # l >= 1: the row's largest score adds exp(0)
        o_ref[:, h * D:(h + 1) * D] = (acc / l).T.astype(o_ref.dtype)
        lse_ref[h:h + 1, :] = m + jnp.log(l)


def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, scale, fold, heads):
    """Query tile i against the key tiles 0..i, one recomputation of p a
    pair: 5 matmuls and one exponential pass.  q, o, do, dq (blk, heads x
    D); k, v, dk, dv (S, heads x D); lse (heads, blk); the accumulators
    (heads, S, D) float32.  Scores transposed (keys, queries): dv = pT.do
    and dk = dsT.q are plain matmuls, `lse` and `delta` broadcast as the
    rows they are, and dq = ds.k alone contracts the left operand's
    rows."""
    i = pl.program_id(2)
    blk = q_ref.shape[0]
    D = q_ref.shape[1] // heads

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    qs = q_ref[...] * scale if fold else q_ref[...]
    do_o = do_ref[...].astype(jnp.float32) * o_ref[...].astype(jnp.float32)
    delta = [do_o[:, h * D:(h + 1) * D].sum(axis=1)[None, :] for h in range(heads)]
    keep = _visible(blk)

    def pair(j, dq, masked):
        rows = pl.ds(pl.multiple_of(j * blk, blk), blk)
        out = []
        for h in range(heads):
            cols = slice(h * D, (h + 1) * D)
            q, do, k = qs[:, cols], do_ref[:, cols], k_ref[rows, cols]
            s = _dot(k, q, _NT)  # (keys, queries)
            if not fold:
                s = s * scale
            if masked:
                s = jnp.where(keep, s, NEG_INF)
            p = jnp.exp(s - lse_ref[h:h + 1, :])
            dv_acc[h, rows, :] += _dot(p.astype(do.dtype), do, _NN)
            dp = _dot(v_ref[rows, cols], do, _NT)
            ds = (p * (dp - delta[h])).astype(q.dtype)
            dk_acc[h, rows, :] += _dot(ds, q, _NN)
            out.append(dq[h] + _dot(ds, k, _TN))
        return tuple(out)

    dq = tuple(jnp.zeros((blk, D), jnp.float32) for _ in range(heads))
    dq = lax.fori_loop(0, i, lambda j, dq: pair(j, dq, False), dq)
    dq = pair(i, dq, True)
    for h in range(heads):
        dq_ref[:, h * D:(h + 1) * D] = (dq[h] * scale).astype(dq_ref.dtype)

    @pl.when(i == pl.num_programs(2) - 1)
    def _finish():
        for h in range(heads):
            cols = slice(h * D, (h + 1) * D)
            # a folded scale came in with q: dk has it already
            dk_ref[:, cols] = (dk_acc[h] * (1.0 if fold else scale)).astype(dk_ref.dtype)
            dv_ref[:, cols] = dv_acc[h].astype(dv_ref.dtype)


def _heads_a_step(H, D):
    """Heads a grid step works on: as many as fill a 128-lane tile (two
    of 64), one where a head is whole tiles already; heads of any other
    width, or fewer heads than fill a tile, go as one group as wide as
    the array (a block may always be that)."""
    if D % 128 == 0:
        return 1
    g = 128 // D if 128 % D == 0 else H
    return min(g, H)


def _block_size(S):
    """Queries a tile, which is keys a tile: the largest of 512, 256, 128
    that divides the length (the module's text has the sweep)."""
    if S % 128 != 0:
        raise ValueError(
            f"flash_attention requires seq len divisible by 128, got {S}; "
            "use the dense attention path for ragged lengths"
        )
    return 512 if S % 512 == 0 else (256 if S % 256 == 0 else 128)


def _interpret():
    """Interpret the kernels when there is no TPU to compile them for —
    the CPU-test convenience.  On a chip they compile (a
    ``tpu_custom_call`` in the step's text is the proof)."""
    return jax.devices()[0].platform != "tpu"


def _out_struct(shape, dtype, like):
    """A kernel output that varies over the mesh axes ``like`` varies
    over: under ``sharded_flash_attention``'s shard_map every
    pallas_call output must say so (jax's check_vma)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _call(kernel, name, like, heads, blk, operands, in_specs, out_specs,
          out_shape, accumulators=0):
    """One of the two kernels over (B, head groups, query tiles); the
    tiles of one (batch row, group) run in order, so its keys and values
    are fetched once and its dk, dv written once."""
    B, S, HD = like.shape
    D = HD // heads
    g = _heads_a_step(heads, D)
    W = g * D
    specs = {
        "tile": pl.BlockSpec((None, blk, W), lambda b, j, i: (b, i, j)),
        "whole": pl.BlockSpec((None, S, W), lambda b, j, i: (b, 0, j)),
        "rows": pl.BlockSpec((None, None, g, blk), lambda b, j, i: (b, j, 0, i)),
    }
    # what stays in fast memory: the whole-sequence blocks in two buffers
    # each and the float32 accumulators; the tiles and a pair's scores on top
    # (a head's 64 lanes of an accumulator are padded to a tile's 128)
    resident = S * (
        2 * W * like.dtype.itemsize * (in_specs + out_specs).count("whole")
        + 4 * g * pl.cdiv(D, 128) * 128 * accumulators)
    if resident + TILE_VMEM_BYTES > VMEM_BYTES:
        raise ValueError(
            f"flash_attention keeps a sequence's keys and values in fast memory: "
            f"{S} tokens take {resident >> 20} MiB of {VMEM_BYTES >> 20}; shard "
            "the sequence (ops/ring_attention.py)"
        )
    return pl.pallas_call(
        functools.partial(kernel, heads=g),
        grid=(B, pl.cdiv(heads, g), S // blk),
        in_specs=[specs[x] for x in in_specs],
        out_specs=[specs[x] for x in out_specs],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((g, S, D), jnp.float32)] * accumulators,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=resident + TILE_VMEM_BYTES,
        ),
        interpret=_interpret(),
        name=name,
    )(*operands)


def _fold(scale):
    """A power of two multiplies q exactly, in any float type."""
    return math.frexp(scale)[0] == 0.5


def _grouped_rows(B, S, heads, D):
    """Shape of `lse` as the kernels hold it: (B, groups, heads a group,
    S), the last group filled up where the heads are odd."""
    g = _heads_a_step(heads, D)
    return (B, pl.cdiv(heads, g), g, S)


def _fwd(q, k, v, heads, scale):
    """q, k, v (B, S, H x D) -> o like q, lse (B, H, S) float32."""
    B, S, HD = q.shape
    rows = _grouped_rows(B, S, heads, HD // heads)
    o, lse = _call(
        functools.partial(_fwd_kernel, scale=scale, fold=_fold(scale)),
        "flash_fwd", q, heads, _block_size(S), (q, k, v),
        ["tile", "whole", "whole"], ["tile", "rows"],
        [_out_struct(q.shape, q.dtype, q), _out_struct(rows, jnp.float32, q)],
    )
    return o, lse.reshape(B, -1, S)[:, :heads]


def _bwd(q, k, v, o, lse, do, heads, scale):
    """q, k, v, o, do (B, S, H x D); lse (B, H, S) -> dq, dk, dv."""
    B, S, HD = q.shape
    rows = _grouped_rows(B, S, heads, HD // heads)
    lse = jnp.pad(lse, ((0, 0), (0, rows[1] * rows[2] - heads), (0, 0)))
    return _call(
        functools.partial(_bwd_kernel, scale=scale, fold=_fold(scale)),
        "flash_bwd", q, heads, _block_size(S),
        (q, k, v, o, do, lse.reshape(rows)),
        ["tile", "whole", "whole", "tile", "tile", "rows"],
        ["tile", "whole", "whole"],
        [_out_struct(x.shape, x.dtype, x) for x in (q, k, v)],
        accumulators=2,  # dk, dv
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, heads, scale):
    return _fwd(q, k, v, heads, scale)[0]


def _flash_fwd(q, k, v, heads, scale):
    o, lse = _named_residuals(*_fwd(q, k, v, heads, scale))
    return o, (q, k, v, o, lse)


def _flash_bwd(heads, scale, res, do):
    return _bwd(*res, do, heads, scale)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, scale: float | None = None):
    """Causal flash attention.  q, k, v: (B, S, H, D) → (B, S, H, D), the
    kernels' own layout but for the heads' axis folded into the lanes."""
    B, S, H, D = q.shape
    o = _flash(
        q.reshape(B, S, H * D), k.reshape(B, S, H * D), v.reshape(B, S, H * D),
        H, scale or 1.0 / math.sqrt(D),
    )
    return o.reshape(B, S, H, D)


def sharded_flash_attention(q, k, v, head_dim: int, scale: float | None = None):
    """Flash attention on the kernels' own layout, q, k, v (B, S, H x D) →
    (B, S, H x D), per shard under an active mesh.

    pallas_call is a custom call XLA cannot auto-partition, so under pjit
    with a live mesh we shard_map over (batch → data axes, heads → tp) and
    run the kernels on the local block (whole heads: H divides by tp).
    Sequence stays unsharded — sp sharding belongs to ring attention
    (ops/ring_attention.py).
    """
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.mesh import DATA_AXES, TP_AXIS

    def local(q, k, v):
        return _flash(q, k, v, q.shape[-1] // head_dim,
                      scale or 1.0 / math.sqrt(head_dim))

    mesh = None
    try:
        ambient = jax.sharding.get_mesh()
        if ambient is not None and not getattr(ambient, "empty", False):
            mesh = ambient
    except Exception:
        pass
    if mesh is None:
        from ray_tpu.parallel.mesh import current_mesh

        mesh = current_mesh()
    if mesh is None:
        return local(q, k, v)
    spec = P(DATA_AXES, None, TP_AXIS)
    # (the interpreter evaluates a kernel's body equation by equation on the
    # shard's blocks, and jax's check refuses the body's constants beside
    # them: they vary over no mesh axis)
    return jax.shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=not _interpret(),
    )(q, k, v)


# Below every pallas_call and their callers, its import too: a kernel's
# serialized body holds its callers' source lines, and the persistent
# compile cache keys on it (PERF.md section 5, (7)).

#: What `jax.checkpoint_policies.save_only_these_names` has to be given
#: for the backward pass of a rematted caller to reuse the forward
#: kernel's outputs (models/gpt2.py); outside such a policy the names
#: are identities.
RESIDUAL_NAMES = ("flash_o", "flash_lse")


def _named_residuals(o, lse):
    """The forward kernel's (o, lse) under `RESIDUAL_NAMES`.  What a
    policy keeps is the named value as it lies: `o` (B, S, H x D) in
    whole 128-lane rows, so a layer scan that stacks it pads nothing.
    The primal output comes from the named values, so nothing of a
    recompute needs the kernel."""
    from jax.ad_checkpoint import checkpoint_name

    return (checkpoint_name(o, RESIDUAL_NAMES[0]),
            checkpoint_name(lse, RESIDUAL_NAMES[1]))
