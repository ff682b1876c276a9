"""The block SDAR-30B-A3B shares with Qwen3-MoE, where it needs no served
run: one chip's share of the expert layer under the renormalised softmax
router, and the two kinds of ``qk_norm`` with ``head_dim`` a field, each
against its plain reference."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import sdar
from ray_tpu.models import llama

from test_llama_block_diffusion import config, prompt, spec, weights


def test_eight_shares_of_the_expert_layer_add_up_to_the_uncut_reference():
    """One chip's share (``experts_held`` 1 of 8 at ``expert_offset`` r)
    computes its own expert's part under the full router, renormalised over
    the chosen experts wherever they live: the eight parts add up to the
    reference's whole layer."""
    cfg = config(mask_block=1)
    params = weights(cfg, seed=5)
    blocks = params["blocks"]
    h = jax.random.normal(jax.random.key(2), (1, 6, cfg.embed_dim), jnp.float32)
    total = 0.0
    for rank in range(8):
        share = dataclasses.replace(cfg, experts_held=1, expert_offset=rank)
        p = {k: v[0] for k, v in blocks.items() if k not in llama._EXPERT_TENSORS}
        p.update({k: blocks[k][:1, rank:rank + 1] for k in llama._EXPERT_TENSORS}, layer=0)
        y, routing = llama._ffn(h, p, share)
        assert int(routing["rows"].sum()) == int((routing["experts"] == rank).sum())
        total = total + y
    layer = {k: v[0] for k, v in blocks.items()}
    with jax.default_matmul_precision("highest"):
        whole, chosen, _ = sdar._experts(h[0], layer, spec(cfg))
    np.testing.assert_allclose(np.asarray(total[0]), np.asarray(whole), atol=2e-5)
    assert np.asarray(chosen).shape == (6, 2)


@pytest.mark.parametrize("kind", ["head", True], ids=["per_head", "whole_width"])
def test_each_qk_norm_against_its_reference(kind):
    """The no-cache forward with heads of 32 on a 64-wide model (``head_dim``
    is a field: ``embed_dim // num_heads`` would be 16): the per-head norm
    against ``reference/sdar.py`` (block 1: causal), the whole-width one
    against ``reference/olmoe.py``."""
    cfg = config(mask_block=1, qk_norm=kind, num_kv_heads=4 if kind is True else 2)
    assert cfg.head_dim == 32 != cfg.embed_dim // cfg.num_heads
    params = weights(cfg, seed=9)
    width = cfg.head_dim if kind == "head" else cfg.num_heads * cfg.head_dim
    assert params["blocks"]["q_norm"].shape == (cfg.num_layers, width)
    tokens = jnp.asarray(prompt(10, 4), jnp.int32)
    got = llama.forward(params, tokens[None], cfg)[0]
    if kind == "head":
        want, _ = sdar.forward(params, tokens, spec(cfg, block=1))
    else:
        from chipbench.reference import olmoe

        want, _ = olmoe.forward(params, tokens, cfg.rope_theta, cfg.rms_eps, 2,
                                positions=list(range(10)), renormalise=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-4, rtol=1e-4)
    assert llama.num_params(cfg) == sum(a.size for a in jax.tree.leaves(params))
    # attention's share of the FLOPs goes by heads x head_dim, not the width
    wide = dataclasses.replace(cfg, head_dim=64)
    assert (llama.flops_per_token(wide, 16) - 6.0 * (llama.num_params(wide) - 64 * 64)
            == 2 * (llama.flops_per_token(cfg, 16) - 6.0 * (llama.num_params(cfg) - 64 * 64)))
