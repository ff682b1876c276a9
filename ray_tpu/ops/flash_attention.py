"""Flash attention for TPU as a Pallas kernel.

Causal multi-head attention that never materializes the (S, S) score
matrix: queries are processed in blocks against KV blocks with an online
log-sum-exp softmax, so per-core live memory is O(block² + block·D) VMEM
and HBM traffic is O(S·D) instead of O(S²).  This is the single biggest
HBM-bandwidth lever for transformer training on TPU — the dense einsum
path writes + rereads ~400 MB of f32 scores per layer for (B=8, H=12,
S=1024) while this kernel writes only the (B, H, S) log-sum-exp.

Layout: the kernels take q, k, v as (B, H, S, D) (`flash_attention`
transposes for a (B, S, H, D) caller).  Grids are (B, H, nq, nk), kv
innermost ((B, H, nk, nq) for dk/dv); a block is one head's (blk, D).

Backward follows the flash-attention-2 recipe: save (o, lse), compute
delta = rowsum(do ⊙ o), then one kernel accumulates dq over KV blocks
and another accumulates (dk, dv) over Q blocks, recomputing p = exp(s −
lse) on the fly.  (o, lse) carry checkpoint names (`RESIDUAL_NAMES`), so
a rematted caller can keep them and not run the forward kernel twice.

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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, blk_q, blk_k):
    """Grid (B, H, nq, nk); kv innermost.  Accumulators live in the o/lse
    output blocks (revisited across the nk dimension) — m and l are packed
    into lse_ref's two rows until the final kv step collapses them."""
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        lse_ref[0, 0, 0, :] = jnp.full((blk_q,), NEG_INF, jnp.float32)  # m
        lse_ref[0, 0, 1, :] = jnp.zeros((blk_q,), jnp.float32)  # l

    # Causal: kv block ki overlaps q block qi iff ki*blk_k <= qi*blk_q + blk_q - 1.
    @pl.when(ki * blk_k < (qi + 1) * blk_q)
    def _step():
        q = q_ref[0, 0, :, :]  # (blk_q, D)
        k = k_ref[0, 0, :, :]  # (blk_k, D)
        v = v_ref[0, 0, :, :]
        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # (blk_q, blk_k)
        q_pos = qi * blk_q + jax.lax.broadcasted_iota(
            jnp.int32, (blk_q, blk_k), 0
        )
        k_pos = ki * blk_k + jax.lax.broadcasted_iota(
            jnp.int32, (blk_q, blk_k), 1
        )
        mask = q_pos >= k_pos
        s = jnp.where(mask, s, NEG_INF)

        m_prev = lse_ref[0, 0, 0, :]
        l_prev = lse_ref[0, 0, 1, :]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        lse_ref[0, 0, 0, :] = m_new
        lse_ref[0, 0, 1, :] = l_prev * corr + p.sum(axis=1)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        o_ref[0, 0, :, :] = (
            o_ref[0, 0, :, :] * corr[:, None] + pv
        ).astype(o_ref.dtype)

    @pl.when(ki == nk - 1)
    def _finish():
        m = lse_ref[0, 0, 0, :]
        l = jnp.maximum(lse_ref[0, 0, 1, :], 1e-30)
        o_ref[0, 0, :, :] = (o_ref[0, 0, :, :] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0, 0, :] = m + jnp.log(l)


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *, scale, blk_q, blk_k
):
    """Grid (B, H, nq, nk): accumulate dq for one q block over kv blocks."""
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    @pl.when(ki * blk_k < (qi + 1) * blk_q)
    def _step():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :].astype(jnp.float32)
        lse = lse_ref[0, 0, 0, :]  # (blk_q,)
        delta = delta_ref[0, 0, 0, :]
        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )
        q_pos = qi * blk_q + jax.lax.broadcasted_iota(
            jnp.int32, (blk_q, blk_k), 0
        )
        k_pos = ki * blk_k + jax.lax.broadcasted_iota(
            jnp.int32, (blk_q, blk_k), 1
        )
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[:, None]) * scale
        dq_ref[0, 0, :, :] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    *, scale, blk_q, blk_k,
):
    """Grid (B, H, nk, nq): accumulate dk, dv for one kv block over q blocks."""
    ki, qi = pl.program_id(2), pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    @pl.when((qi + 1) * blk_q > ki * blk_k)
    def _step():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :].astype(jnp.float32)
        lse = lse_ref[0, 0, 0, :]
        delta = delta_ref[0, 0, 0, :]
        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )
        q_pos = qi * blk_q + jax.lax.broadcasted_iota(
            jnp.int32, (blk_q, blk_k), 0
        )
        k_pos = ki * blk_k + jax.lax.broadcasted_iota(
            jnp.int32, (blk_q, blk_k), 1
        )
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])  # (blk_q, blk_k)
        # dv += p^T @ do
        dv_ref[0, 0, :, :] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0, 0, :, :],
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(dv_ref.dtype)
        dp = jax.lax.dot_general(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - delta[:, None]) * scale).astype(q.dtype)
        # dk += ds^T @ q
        dk_ref[0, 0, :, :] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(dk_ref.dtype)


def _block_sizes(S):
    if S % 128 != 0:
        raise ValueError(
            f"flash_attention requires seq len divisible by 128, got {S}; "
            "use the dense attention path for ragged lengths"
        )
    blk = 512 if S % 512 == 0 else (256 if S % 256 == 0 else 128)
    blk = min(blk, S)
    return blk, blk


def _interpret():
    """Interpret the kernels when there is no TPU to compile them for —
    the CPU-test convenience.  On a chip they compile (a
    ``tpu_custom_call`` in the step's text is the proof)."""
    return jax.devices()[0].platform != "tpu"


def _out_struct(shape, dtype, like):
    """A kernel output that varies over the mesh axes ``like`` varies
    over: under ``sharded_flash_attention_bhsd``'s shard_map every
    pallas_call output must say so (jax's check_vma)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _fwd(q, k, v, scale):
    """q, k, v: (B, H, S, D)."""
    B, H, S, D = q.shape
    blk_q, blk_k = _block_sizes(S)
    nq, nk = S // blk_q, S // blk_k
    grid = (B, H, nq, nk)
    qspec = pl.BlockSpec((1, 1, blk_q, D), lambda b, h, i, j: (b, h, i, 0))
    kspec = pl.BlockSpec((1, 1, blk_k, D), lambda b, h, i, j: (b, h, j, 0))
    o, lse2 = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, blk_q=blk_q, blk_k=blk_k),
        grid=grid,
        in_specs=[qspec, kspec, kspec],
        out_specs=[
            pl.BlockSpec((1, 1, blk_q, D), lambda b, h, i, j: (b, h, i, 0)),
            # rows: [m; l] during accumulation, [lse; l] after finish
            pl.BlockSpec((1, 1, 2, blk_q), lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_shape=[
            _out_struct((B, H, S, D), q.dtype, q),
            _out_struct((B, H, 2, S), jnp.float32, q),
        ],
        interpret=_interpret(),
        name="flash_fwd",
    )(q, k, v)
    return o, lse2[:, :, 0, :]


def _bwd(q, k, v, o, lse, do, scale):
    """All tensors (B, H, S, D); lse (B, H, S)."""
    B, H, S, D = q.shape
    blk_q, blk_k = _block_sizes(S)
    nq, nk = S // blk_q, S // blk_k
    delta = jnp.einsum(
        "bhsd,bhsd->bhs", do.astype(jnp.float32), o.astype(jnp.float32)
    )
    lse4 = lse[:, :, None, :]  # (B, H, 1, S)
    delta4 = delta[:, :, None, :]
    qspec = pl.BlockSpec((1, 1, blk_q, D), lambda b, h, i, j: (b, h, i, 0))
    kspec = pl.BlockSpec((1, 1, blk_k, D), lambda b, h, i, j: (b, h, j, 0))
    rspec = pl.BlockSpec((1, 1, 1, blk_q), lambda b, h, i, j: (b, h, 0, i))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, blk_q=blk_q, blk_k=blk_k),
        grid=(B, H, nq, nk),
        in_specs=[qspec, kspec, kspec, qspec, rspec, rspec],
        out_specs=pl.BlockSpec(
            (1, 1, blk_q, D), lambda b, h, i, j: (b, h, i, 0)
        ),
        out_shape=_out_struct((B, H, S, D), q.dtype, q),
        interpret=_interpret(),
        name="flash_dq",
    )(q, k, v, do, lse4, delta4)
    # For the dkv pass the grid iterates (kv, q): index maps swap i/j roles.
    qspec2 = pl.BlockSpec((1, 1, blk_q, D), lambda b, h, i, j: (b, h, j, 0))
    kspec2 = pl.BlockSpec((1, 1, blk_k, D), lambda b, h, i, j: (b, h, i, 0))
    rspec2 = pl.BlockSpec((1, 1, 1, blk_q), lambda b, h, i, j: (b, h, 0, j))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, blk_q=blk_q, blk_k=blk_k),
        grid=(B, H, nk, nq),
        in_specs=[qspec2, kspec2, kspec2, qspec2, rspec2, rspec2],
        out_specs=[
            pl.BlockSpec((1, 1, blk_k, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, blk_k, D), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            _out_struct((B, H, S, D), k.dtype, k),
            _out_struct((B, H, S, D), v.dtype, v),
        ],
        interpret=_interpret(),
        name="flash_dkv",
    )(q, k, v, do, lse4, delta4)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def flash_attention_bhsd(q, k, v, scale: float | None = None):
    """Causal flash attention, (B, H, S, D) layout (kernel-native)."""
    o, _ = _fwd(q, k, v, scale or 1.0 / math.sqrt(q.shape[-1]))
    return o


def _flash_fwd(q, k, v, scale):
    s = scale or 1.0 / math.sqrt(q.shape[-1])
    o, lse = _named_residuals(*_fwd(q, k, v, s))
    return o, (q, k, v, o, lse)


def _flash_bwd(scale, res, do):
    q, k, v, o, lse = res
    s = scale or 1.0 / math.sqrt(q.shape[-1])
    return _bwd(q, k, v, o, lse, do, s)


flash_attention_bhsd.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, scale: float | None = None):
    """Causal flash attention.  q, k, v: (B, S, H, D) → (B, S, H, D).

    Thin layout adapter over :func:`flash_attention_bhsd`; the transposes
    fuse into neighboring ops under jit.  Models that can emit
    (B, H, S, D) directly should call the bhsd variant.
    """
    o = flash_attention_bhsd(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        scale,
    )
    return o.transpose(0, 2, 1, 3)


def sharded_flash_attention_bhsd(q, k, v, scale: float | None = None):
    """Flash attention that runs per-shard under an active mesh.

    pallas_call is a custom call XLA cannot auto-partition, so under pjit
    with a live mesh we shard_map over (batch → data axes, heads → tp) and
    run the kernel on the local block.  Sequence stays unsharded — sp
    sharding belongs to ring attention (ops/ring_attention.py).
    """
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.mesh import DATA_AXES, TP_AXIS

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
        return flash_attention_bhsd(q, k, v, scale)
    spec = P(DATA_AXES, TP_AXIS, None, None)
    fn = jax.shard_map(
        functools.partial(flash_attention_bhsd, scale=scale),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)


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
    policy keeps is the named value as it lies: `o` is named in rows of
    128 lanes, because a layer scan that stacks a 64-wide minor
    dimension pads it to the tile's 128 and keeps twice the bytes
    (1.26 GB more a chip in GPT-2 XL's step) for a step 0.4% slower
    on one v5e and 0.2% faster on four (PERF.md section 6, PR 35).
    The primal output comes from the named values, so nothing of a
    recompute needs the kernel."""
    from jax.ad_checkpoint import checkpoint_name

    B, H, S, D = o.shape
    if D % 128:  # S is a multiple of 128 (_block_sizes), so S * D is
        o = o.reshape(B, H, S * D // 128, 128)
    o = checkpoint_name(o, RESIDUAL_NAMES[0]).reshape(B, H, S, D)
    return o, checkpoint_name(lse, RESIDUAL_NAMES[1])
