"""The main path's kernels, compiled for the chip without the chip.

libtpu's compiler is installed wherever the tests run, and it compiles
for a TPU that is described, not attached (`topologies.get_topology_desc`).
That catches what Pallas interpret mode cannot — a block the Mosaic
compiler refuses for its tiling, a kernel that wants more VMEM than it
may have — at about a second per kernel and no chip time.  A compile
that passes is not a chip run: nothing executes here.

The shapes are the two head layouts the repo trains and serves at
width: GPT-2-124M at B=8 x S=1024 (12 heads x 64) and the Llama family
at one 2048-token sequence (32 heads x 128).
"""

import os

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops import flash_attention as fa

# the compiler otherwise logs under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

HEAD_SHAPES = {
    "gpt2_124m": (8, 12, 1024, 64),
    "llama_7b": (1, 32, 2048, 128),
}


@pytest.fixture(scope="module")
def v5e_chip():
    """One device of a described v5e 2x2, or skip."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu / unknown topology
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_not_interpreted(monkeypatch):
    """Steer the kernel's mode switch the way a chip would (jax.devices()
    here is the CPU), and keep these compiles out of the persistent
    cache: an entry written for a described chip cannot be read back
    without one, and the next run would warn about it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.setattr(fa, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _loss(q, k, v):
    return fa.flash_attention_bhsd(q, k, v).astype(jnp.float32).sum()


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("shape_name", sorted(HEAD_SHAPES))
def test_flash_kernel_compiles_for_v5e(
    v5e_chip, compiled_not_interpreted, shape_name, direction
):
    x = jax.ShapeDtypeStruct(
        HEAD_SHAPES[shape_name], jnp.bfloat16, sharding=v5e_chip
    )
    if direction == "forward":
        fn, n_kernels = fa.flash_attention_bhsd, 1
    else:
        # forward (for the residuals) + the dq kernel + the dk/dv kernel
        fn, n_kernels = jax.grad(_loss, argnums=(0, 1, 2)), 3
    text = jax.jit(fn).lower(x, x, x).compile().as_text()
    assert text.count("tpu_custom_call") == n_kernels, (
        f"{shape_name} {direction}: expected {n_kernels} compiled Pallas "
        f"kernel(s) in the program"
    )
