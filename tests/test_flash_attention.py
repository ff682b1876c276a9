"""Flash attention (pallas) vs the dense einsum reference.

On CPU the kernel runs in pallas interpret mode, so these tests verify
the exact same kernel code the TPU executes (ray has no attention kernels
to mirror — this is TPU-first surface; the numerics oracle is
ops/attention.py's dense path).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import flash_attention as fa
from ray_tpu.ops.attention import dense_attention
from ray_tpu.ops.flash_attention import flash_attention


def _qkv(B=1, S=256, H=2, D=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    return [jax.random.normal(k, (B, S, H, D), dtype) for k in ks]


class TestFlashForward:
    @pytest.mark.parametrize("S", [128, 256])
    def test_matches_dense(self, S):
        q, k, v = _qkv(S=S)
        o_flash = flash_attention(q, k, v)
        o_dense = dense_attention(q, k, v)
        np.testing.assert_allclose(
            np.asarray(o_flash), np.asarray(o_dense), atol=2e-5, rtol=2e-5
        )

    def test_causality(self):
        """Changing future keys/values must not change earlier outputs."""
        q, k, v = _qkv(S=128)
        o1 = flash_attention(q, k, v)
        k2 = k.at[:, 64:].set(0.0)
        v2 = v.at[:, 64:].set(9.0)
        o2 = flash_attention(q, k2, v2)
        np.testing.assert_allclose(
            np.asarray(o1[:, :64]), np.asarray(o2[:, :64]), atol=1e-6
        )
        assert not np.allclose(np.asarray(o1[:, 64:]), np.asarray(o2[:, 64:]))

    def test_multi_block(self):
        """S spanning several kv blocks exercises the online-softmax merge."""
        q, k, v = _qkv(S=512, seed=3)
        np.testing.assert_allclose(
            np.asarray(flash_attention(q, k, v)),
            np.asarray(dense_attention(q, k, v)),
            atol=2e-5,
            rtol=2e-5,
        )


class TestFlashBackward:
    def test_grads_match_dense(self):
        q, k, v = _qkv(S=256, seed=1)

        def loss_flash(q, k, v):
            return (flash_attention(q, k, v) ** 2).sum()

        def loss_dense(q, k, v):
            return (dense_attention(q, k, v) ** 2).sum()

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4
            )

    def test_value_and_grad_jit(self):
        q, k, v = _qkv(S=128, seed=2)
        f = jax.jit(
            jax.value_and_grad(lambda q: flash_attention(q, k, v).sum())
        )
        val, grad = f(q)
        assert np.isfinite(float(val))
        assert np.isfinite(np.asarray(grad)).all()


class TestFlashInModel:
    def test_gpt2_flash_loss_matches_dense(self):
        from ray_tpu.models import gpt2

        cfg_d = gpt2.GPTConfig.tiny(attention_impl="dense", dtype=jnp.float32)
        cfg_f = gpt2.GPTConfig.tiny(attention_impl="flash", dtype=jnp.float32)
        params = gpt2.init(jax.random.key(0), cfg_d)
        tokens = jax.random.randint(
            jax.random.key(1), (2, 65), 0, cfg_d.vocab_size, jnp.int32
        )
        l_d = gpt2.loss_fn(params, {"tokens": tokens}, cfg_d)
        l_f = gpt2.loss_fn(params, {"tokens": tokens}, cfg_f)
        assert abs(float(l_d) - float(l_f)) < 1e-3


class TestFlashUnderMesh:
    """sharded_flash_attention shard_maps the kernels over the data and tp
    axes whenever a mesh is live — every mesh-built train step takes this
    path, a one-device mesh included."""

    @pytest.mark.parametrize("shape", [{"dp": 1}, {"dp": 1, "fsdp": 2, "tp": 2}])
    def test_output_and_grads_match_the_unsharded_kernel(self, shape):
        from ray_tpu.ops.flash_attention import sharded_flash_attention
        from ray_tpu.parallel import mesh as mesh_mod

        cfg = mesh_mod.MeshConfig(**shape)
        n = cfg.dp * cfg.fsdp * cfg.tp
        q, k, v = _qkv(B=2, S=128, H=2, seed=5)

        def loss(fn):
            return lambda q, k, v: (fn(q, k, v) ** 2).sum()

        def sharded(q, k, v):  # the kernels' own layout, heads in the lanes
            B, S, H, D = q.shape
            folded = (x.reshape(B, S, H * D) for x in (q, k, v))
            return sharded_flash_attention(*folded, D).reshape(q.shape)

        mesh = mesh_mod.make_mesh(cfg, devices=jax.devices()[:n])
        try:
            with mesh_mod.use(mesh):
                o = jax.jit(sharded)(q, k, v)
                g = jax.jit(jax.grad(loss(sharded), argnums=(0, 1, 2)))(q, k, v)
        finally:
            mesh_mod.set_current_mesh(None)
        o_ref = flash_attention(q, k, v)
        g_ref = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=1e-6)
        for a, b in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


# ---- the schedule: tiles, head groups, precisions ---------------------------


@pytest.fixture
def tile(monkeypatch):
    """Call it with a size: what `_block_size` answers from then on (queries
    a tile, which is keys a tile), whatever the length."""

    def force(blk):
        monkeypatch.setattr(fa, "_block_size", lambda S: min(blk, S))
        jax.clear_caches()  # the custom-vjp's rules are traced once a shape

    yield force
    jax.clear_caches()


def _weighted(fn, w):
    return lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * w).sum()


def _o_and_grads(fn, q, k, v, w):
    return (fn(q, k, v), *jax.grad(_weighted(fn, w), argnums=(0, 1, 2))(q, k, v))


def _reference(q, k, v, w):
    """Dense float32 attention of the operands as they are rounded: output,
    the three gradients of sum(o * w), and the log-sum-exp (B, H, S)."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    S, D = q.shape[1], q.shape[3]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    s = jnp.where(np.tril(np.ones((S, S), bool)), s, -np.inf)
    return (*_o_and_grads(dense_attention, q, k, v, w),
            jax.nn.logsumexp(s, axis=-1))


def _rms(a, b):
    return float(jnp.sqrt(jnp.mean((a.astype(jnp.float32) - b) ** 2)))


#: every tile `_block_size` can answer at some length
RULE_TILES = sorted({fa._block_size(S) for S in range(128, 4097, 128)})


class TestSchedule:
    """Forward, the three gradients and `lse` against the dense float32
    reference.  bfloat16 limits are a few roundings of the outputs (the
    reference is computed from the same rounded operands); float32 ones
    the accumulation order's."""

    LIMITS = {jnp.float32: 2e-5, jnp.bfloat16: 3e-2}

    def _check(self, B, S, H, D, dtype, seed=0):
        q, k, v = _qkv(B=B, S=S, H=H, D=D, dtype=dtype, seed=seed)
        w = jax.random.normal(jax.random.key(seed + 100), q.shape, jnp.float32)
        *want, lse_want = _reference(q, k, v, w)
        got = _o_and_grads(flash_attention, q, k, v, w)
        limit = self.LIMITS[dtype]
        for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
            assert a.dtype == dtype and a.shape == b.shape
            err = float(jnp.abs(a.astype(jnp.float32) - b).max())
            assert err < limit * max(1.0, float(jnp.abs(b).max())), (name, err)
        _, lse = fa._fwd(*(x.reshape(B, S, H * D) for x in (q, k, v)), H, D ** -0.5)
        assert lse.shape == (B, H, S) and lse.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_want), atol=1e-4, rtol=1e-5)

    @pytest.mark.parametrize("blk", RULE_TILES)
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_every_tile_the_rule_can_pick_at_1024(self, tile, blk, dtype):
        # a first tile (the diagonal's pair alone), tiles with whole pairs
        # below the diagonal, and the last
        tile(blk)
        self._check(1, 1024, 2, 64, dtype)

    @pytest.mark.parametrize("blk", [128, 256, 512, 1024])
    def test_any_tile_that_divides_the_length(self, tile, blk):
        """The walk, the mask and the accumulators' slices hold for any tile
        size, though the rule picks few."""
        tile(blk)
        self._check(2, 1024, 3, 64, jnp.float32, seed=7)

    @pytest.mark.parametrize("B,H", [(1, 25), (3, 5)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_an_odd_head_count_leaves_half_a_group_outside(self, B, H, dtype):
        assert fa._heads_a_step(H, 64) == 2
        self._check(B, 256, H, 64, dtype, seed=H)

    @pytest.mark.parametrize("H,D,heads_a_step", [
        (2, 128, 1), (3, 128, 1), (4, 64, 2), (1, 64, 1), (4, 32, 4), (6, 32, 4),
        (2, 256, 1), (3, 48, 3),
    ])
    def test_head_widths_and_their_groups(self, H, D, heads_a_step):
        assert fa._heads_a_step(H, D) == heads_a_step
        self._check(2, 256, H, D, jnp.bfloat16, seed=D)

    def test_a_scale_that_is_no_power_of_two_multiplies_the_scores(self):
        """1/sqrt(128) rounds q if folded into it; the kernels fold only
        what is exact."""
        assert fa._fold(0.125) and fa._fold(2.0) and not fa._fold(128 ** -0.5)
        self._check(1, 256, 2, 128, jnp.float32, seed=3)


#: rms error of the PARENT's kernels (PR 35's text, bfloat16 accumulators in
#: the output blocks) against the dense float32 reference at the shape and
#: seed below, in Pallas interpret mode: o, dq, dk, dv
PARENT_RMS = (3.1958e-04, 3.3001e-04, 3.6313e-04, 4.0871e-04)


def test_bfloat16_results_are_at_least_as_close_as_the_parents():
    """float32 accumulators in place of bfloat16 output blocks, and `dp` from
    one pass over bfloat16 operands (their products are exact in float32): every
    output and gradient is at least as close to the float32 reference as the
    kernels of PR 35 were."""
    ks = jax.random.split(jax.random.key(50), 4)
    q, k, v, w = [jax.random.normal(kk, (2, 1024, 2, 64), jnp.float32) for kk in ks]
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    *want, _ = _reference(q, k, v, w)
    got = _o_and_grads(flash_attention, q, k, v, w)
    for name, a, b, limit in zip(("o", "dq", "dk", "dv"), got, want, PARENT_RMS):
        assert _rms(a, b) <= limit, (name, _rms(a, b), limit)


def _kernel_dots(jaxpr, inside=None, found=None):
    """{kernel name: [(lhs dtype, rhs dtype, out dtype) of each dot_general
    in its body]} of every pallas_call under ``jaxpr``."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and inside:
            found.setdefault(inside, []).append(
                tuple(x.aval.dtype.name for x in (*eqn.invars, *eqn.outvars)))
        name = inside
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernel_dots(sub, name, found)
    return found


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_backward_multiplies_the_operands_as_they_arrive(dtype):
    """`dp = do . v^T` from bfloat16 `do` and `v` is one MXU pass with exact
    products (no float32 copy of either feeds a matmul); float32 operands
    keep float32 products.  Accumulation is float32 in both."""
    q, k, v = _qkv(S=128, dtype=jnp.dtype(dtype))
    grad = jax.grad(
        lambda q, k, v: flash_attention(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))
    dots = _kernel_dots(jax.make_jaxpr(grad)(q, k, v).jaxpr)
    # a pair's body is traced twice — bare in the loop over the tiles below
    # the diagonal, masked for the tile on it — and in it each of a step's
    # two heads in turn: 2 matmuls a head forward, 5 backward
    assert {name: len(d) for name, d in dots.items()} == {
        "flash_fwd": 2 * 2 * 2, "flash_bwd": 2 * 2 * 5}
    for name, found in dots.items():
        assert set(found) == {(dtype, dtype, "float32")}, (name, found)
