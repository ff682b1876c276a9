"""A rematted GPT-2 block keeps the flash forward kernel's (o, lse) and
runs that kernel once a layer: `gpt2._features_aux`'s checkpoint policy
with `flash_attention.RESIDUAL_NAMES`.

Three things are held, single device and on a 4-device `fsdp=4` mesh of
CPU devices (kernels in interpret mode): the gradient's structure (one
forward and one backward kernel a layer body), its values (the
policy-less `jax.checkpoint`'s bit for bit) and what is kept (the
stacks of `o` as the kernel writes it, (B, S, H x D) in whole 128-lane
rows, and of `lse`, and nothing else of the block).  The compiled
programs at the cells' sizes are in tests/test_chip_compile.py.
"""

import math
import re
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models import gpt2, pp
from ray_tpu.ops import flash_attention as fa
from ray_tpu.parallel import mesh as mesh_mod
from ray_tpu.parallel import spmd

L, B, S, H, D = 3, 4, 128, 2, 64
CONFIG = gpt2.GPTConfig(
    vocab_size=256, max_seq_len=S, num_layers=L, num_heads=H,
    embed_dim=H * D, attention_impl="flash", remat=True,
)
KERNELS = ("flash_fwd", "flash_bwd")


def _loss(params, batch):
    return gpt2.loss_fn(params, batch, CONFIG)


@pytest.fixture(params=["one_device", "fsdp4"])
def placed(request):
    """(params, batch) as a trainer holds them, under the mesh its step
    runs in — none, or four CPU devices with `fsdp=4`."""
    tokens = np.random.default_rng(35).integers(0, 256, (B, S + 1), np.int32)
    if request.param == "one_device":
        yield gpt2.init(jax.random.key(0), CONFIG), {"tokens": jnp.asarray(tokens)}
        return
    mesh = mesh_mod.make_mesh(
        mesh_mod.MeshConfig(dp=1, fsdp=4), devices=jax.devices()[:4]
    )
    try:
        state = spmd.sharded_init(
            mesh, lambda rng: gpt2.init(rng, CONFIG), jax.random.key(0),
            gpt2.param_logical_axes(CONFIG), optax.identity(),
        )
        with mesh_mod.use(mesh):
            yield state.params, spmd.shard_batch(mesh, {"tokens": tokens})
    finally:
        mesh_mod.set_current_mesh(None)


@pytest.fixture
def without_the_policy(monkeypatch):
    """Call it, and what is traced from then on is `jax.checkpoint(_block)`
    as it was: nothing of the block kept."""

    def drop():
        monkeypatch.setattr(
            jax.checkpoint_policies, "save_only_these_names",
            lambda *names: jax.checkpoint_policies.nothing_saveable,
        )
        jax.clear_caches()  # no trace made under the policy answers

    return drop


def _kernel_counts(params, batch):
    text = str(jax.make_jaxpr(jax.grad(_loss))(params, batch))
    return {k: len(re.findall(rf"name={k}\b", text)) for k in KERNELS}


def _residuals(params, batch):
    """Shape and dtype of everything the forward pass hands the backward
    pass: the leaves of the vjp function."""
    vjp = jax.eval_shape(lambda p: jax.vjp(lambda p: _loss(p, batch), p)[1], params)
    return Counter((x.shape, x.dtype.name) for x in jax.tree.leaves(vjp))


def test_the_gradient_runs_each_kernel_once_a_layer(placed, without_the_policy):
    # the layer scan is rolled: one body forward, one backward
    assert _kernel_counts(*placed) == {"flash_fwd": 1, "flash_bwd": 1}
    without_the_policy()
    assert _kernel_counts(*placed)["flash_fwd"] == 2, (
        "the comparison is no longer with a block that recomputes its "
        "forward kernel"
    )


def test_loss_and_gradients_equal_the_policy_less_checkpoints(
    placed, without_the_policy
):
    got = jax.jit(jax.value_and_grad(_loss))(*placed)
    without_the_policy()
    want = jax.jit(jax.value_and_grad(_loss))(*placed)
    assert float(got[0]) == float(want[0])
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(got[1]), jax.tree.leaves(want[1])
    ):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=jax.tree_util.keystr(path)
        )
    assert all(np.abs(np.asarray(x)).max() > 0 for x in jax.tree.leaves(got[1]))


def test_only_o_in_rows_of_128_lanes_and_lse_are_kept(placed, without_the_policy):
    kept = _residuals(*placed)
    without_the_policy()
    before = _residuals(*placed)
    added = kept - before
    assert not before - kept
    # `o` as the kernels read and write it: the heads folded into the
    # lanes, so the scan's stack has no 64-wide minor dimension to pad
    assert (H * D) % 128 == 0
    assert added == Counter({
        ((L, B, S, H * D), "bfloat16"): 1,
        ((L, B, H, S), "float32"): 1,
    })
    assert sum(
        math.prod(shape) * jnp.dtype(dtype).itemsize * n
        for (shape, dtype), n in added.items()
    ) == L * (B * H * S * D * 2 + B * H * S * 4)


def test_callers_without_a_policy_get_the_values_they_got(monkeypatch):
    """`flash_attention()` and the pipeline's stage function
    (`models/pp.py` calls `gpt2._block` under no policy): a name is an
    identity there, output and gradients."""
    q, k, v = (
        jax.random.normal(key, (2, S, H, D), jnp.bfloat16)
        for key in jax.random.split(jax.random.key(1), 3)
    )
    stage = pp.gpt2_partition(CONFIG).stage_fn
    blocks = gpt2.init(jax.random.key(0), CONFIG)["blocks"]
    h = jax.random.normal(jax.random.key(2), (2, S, H * D), jnp.bfloat16)

    def values():
        # fresh functions every time: jit would answer a function it has
        # traced before from its cache, whatever was patched since
        def attend(q, k, v):
            return fa.flash_attention(q, k, v)

        def through(blocks, h):
            return stage(blocks, h)

        def total(f):
            return lambda *a: f(*a).astype(jnp.float32).sum()

        return jax.tree.leaves((
            jax.jit(attend)(q, k, v),
            jax.jit(jax.grad(total(attend), argnums=(0, 1, 2)))(q, k, v),
            jax.jit(through)(blocks, h),
            jax.jit(jax.grad(total(through), argnums=(0, 1)))(blocks, h),
        ))

    def names_traced():
        grad = jax.grad(lambda q, k, v: fa.flash_attention(q, k, v).sum())
        return "name=flash_o" in str(jax.make_jaxpr(grad)(q, k, v))

    got = values()
    assert names_traced()
    monkeypatch.setattr(fa, "_named_residuals", lambda o, lse: (o, lse))
    # the custom-vjp's forward rule is traced once a shape and kept
    jax.clear_caches()
    assert not names_traced()
    for a, b in zip(got, values()):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
