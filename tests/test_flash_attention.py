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
    """sharded_flash_attention_bhsd shard_maps the kernel over the data
    and tp axes whenever a mesh is live — every mesh-built train step
    takes this path, a one-device mesh included."""

    @pytest.mark.parametrize("shape", [{"dp": 1}, {"dp": 1, "fsdp": 2, "tp": 2}])
    def test_output_and_grads_match_the_unsharded_kernel(self, shape):
        from ray_tpu.ops.flash_attention import (
            flash_attention_bhsd,
            sharded_flash_attention_bhsd,
        )
        from ray_tpu.parallel import mesh as mesh_mod

        cfg = mesh_mod.MeshConfig(**shape)
        n = cfg.dp * cfg.fsdp * cfg.tp
        q, k, v = [
            x.transpose(0, 2, 1, 3) for x in _qkv(B=2, S=128, H=2, seed=5)
        ]

        def loss(fn):
            return lambda q, k, v: (fn(q, k, v) ** 2).sum()

        mesh = mesh_mod.make_mesh(cfg, devices=jax.devices()[:n])
        try:
            with mesh_mod.use(mesh):
                o = jax.jit(sharded_flash_attention_bhsd)(q, k, v)
                g = jax.jit(jax.grad(
                    loss(sharded_flash_attention_bhsd), argnums=(0, 1, 2)
                ))(q, k, v)
        finally:
            mesh_mod.set_current_mesh(None)
        o_ref = flash_attention_bhsd(q, k, v)
        g_ref = jax.grad(loss(flash_attention_bhsd), argnums=(0, 1, 2))(
            q, k, v
        )
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=1e-6)
        for a, b in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
