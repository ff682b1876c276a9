"""``llama._ffn`` where not every (token, choice) row has a group
(``experts_held``, ``zero_experts``): only the held rows are gathered, a
block of R at a time, and land in their tokens' sums — against a plain
float32 reference that applies every held expert to every token, for
routings that fill one block, overflow it, hold nothing, mix identity
experts in, or are a decode step's few rows; the gradient; and the counter
the cache carries (``moe_rows_gathered_total``)."""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops.grouped_matmul import ROW_TILE

#: GLM-5's / JoyAI's expert layer at toy widths: sigmoid router with a
#: selection bias, a shared expert, experts 4..8 of 16 held here
SIGMOID = dict(num_layers=1, num_experts=16, experts_per_token=4, expert_dim=32,
               shared_expert_dim=32, router_scoring="sigmoid", router_norm_topk=True,
               router_scale=2.5, experts_held=4, expert_offset=4)


def layer(cfg, seed=0, bias=None):
    """One layer's parameters as ``_ffn`` takes them (the expert tensors
    stacked over the stack's one layer), weights four times ``init``'s so
    that the experts' terms are decisive, and the router's selection bias."""
    blocks = llama.init(jax.random.key(seed), cfg)["blocks"]
    blocks = jax.tree.map(lambda a: a * 4 if a.ndim > 2 else a, blocks)
    p = {k: v[0] for k, v in blocks.items()}
    p.update({k: blocks[k] for k in llama._EXPERT_TENSORS}, layer=jnp.int32(0))
    if bias is not None:
        p["router_bias"] = jnp.asarray(bias, jnp.float32)
    return p


def reference(h, p, cfg):
    """The layer in plain float32: every held expert applied to EVERY token
    (N, X, E), and a token's sum over its choices read out of that — no
    sort, no gather, no block."""
    c = cfg
    x = h.reshape(-1, c.embed_dim)
    weight, expert = llama._route(x, p, c)                     # (N, K) each
    with jax.default_matmul_precision("highest"):
        gate = jnp.einsum("ne,xem->nxm", x, p["w_gate"][0])
        up = jnp.einsum("ne,xem->nxm", x, p["w_up"][0])
        every = jnp.einsum("nxm,xme->nxe", jax.nn.silu(gate) * up, p["w_down"][0])
        held = expert - c.expert_offset
        here = (held >= 0) & (held < c.experts_here) & (expert < c.num_experts)
        picked = jnp.take_along_axis(
            every, jnp.clip(held, 0, c.experts_here - 1)[:, :, None], axis=1)
        y = (jnp.where(here, weight, 0.0)[:, :, None] * picked).sum(1)
        if c.zero_experts:
            y = y + jnp.where(expert >= c.num_experts, weight, 0.0).sum(-1)[:, None] * x
        if c.shared_expert_dim:
            y = y + (jax.nn.silu(x @ p["ws_gate"]) * (x @ p["ws_up"])) @ p["ws_down"]
    return y.reshape(h.shape), here.sum()


def held_bias(cfg, value):
    held = np.arange(cfg.router_outputs) - cfg.expert_offset
    return np.where((held >= 0) & (held < cfg.experts_here), value, 0.0)


#: name -> (config, tokens, the selection bias on the held experts, row
#: tiles gathered as a function of the held rows)
CASES = {
    # 512 tokens x 4 = 2,048 rows, about a quarter held: one block of 1,024
    "typical": (LlamaConfig.tiny(**SIGMOID), (2, 256), None, lambda held: 8),
    # the bias puts all four choices of every token on the four held
    # experts: 2,048 held rows, two full blocks
    "every_choice_held": (LlamaConfig.tiny(**SIGMOID), (2, 256), 10.0, lambda held: 16),
    # ... and away from them: no block runs, the shared expert alone
    "no_choice_held": (LlamaConfig.tiny(**SIGMOID), (2, 256), -10.0, lambda held: 0),
    # LongCat-Flash's router: 4 identity experts behind 8 real ones, of
    # which experts 2..6 are held; 3 x 400 = 1,200 rows, two blocks of 1,024
    "identity_experts": (LlamaConfig.tiny_shortcut(num_layers=1, experts_held=4, expert_offset=2),
                         (1, 400), None, lambda held: 8 * -(-held // 1024)),
    # a decode step's shape: 4 x 4 = 16 rows, one block of one row tile
    "decode_step": (LlamaConfig.tiny(**SIGMOID), (4, 1), None, lambda held: 1),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_held_rows_alone_give_the_layer(name):
    cfg, shape, bias, tiles = CASES[name]
    p = layer(cfg, bias=None if bias is None else held_bias(cfg, bias))
    h = jax.random.normal(jax.random.key(7), (*shape, cfg.embed_dim), jnp.float32)
    y, routing = jax.jit(lambda h, p: llama._ffn(h, p, cfg))(h, p)
    want, held = reference(h, p, cfg)
    held = int(held)
    np.testing.assert_allclose(y, want, rtol=0, atol=2e-5)
    assert float(jnp.std(want)) > 0.05
    rows = shape[0] * shape[1] * cfg.experts_per_token
    assert int(routing["rows"].sum()) == held
    assert int(routing["tiles"]) == tiles(held)
    if name == "every_choice_held":
        assert held == rows > llama._held_block(rows, cfg)       # a block is no capacity
    if name == "no_choice_held":
        assert held == 0 and float(jnp.abs(y).max()) > 0.05
    if name == "identity_experts":
        assert 0 < int(routing["zero"]) < rows and 0 < held < rows


def test_the_block_is_read_from_the_calls_shape_and_the_configs_share():
    glm = LlamaConfig.tiny(**dict(SIGMOID, num_experts=256, experts_held=16, experts_per_token=8))
    longcat = LlamaConfig.tiny_shortcut(num_experts=512, zero_experts=256, experts_held=16,
                                        experts_per_token=12)
    # a 2,048-token chunk: one and a half times a uniform router's share
    assert llama._held_block(2048 * 8, glm) == 1536 == 1.5 * 2048 * 8 * 16 / 256
    assert llama._held_block(2048 * 12, longcat) == 1024 > 1.5 * 2048 * 12 * 16 / 768
    # a decode step's rows fit one block, in whole row tiles
    assert llama._held_block(64 * 12, longcat) == 768 and llama._held_block(32 * 8, glm) == 256
    assert llama._held_block(16, glm) == ROW_TILE
    # every row of a call is never more than one block
    assert llama._held_block(1000, dataclasses.replace(glm, experts_held=255)) == 1024


def test_the_gradient_is_the_references():
    cfg, shape, _, _ = CASES["identity_experts"]
    p = layer(cfg)
    h = jax.random.normal(jax.random.key(3), (*shape, cfg.embed_dim), jnp.float32)
    probe = jax.random.normal(jax.random.key(4), h.shape, jnp.float32)
    names = ("w_gate", "w_down", "w_router")

    def through(f):
        def scalar(h, weights):
            return (f(h, {**p, **weights}, cfg)[0] * probe).sum()
        return jax.jit(jax.grad(scalar, argnums=(0, 1)))(h, {k: p[k] for k in names})

    mine, want = through(llama._ffn), through(reference)
    for got, ref in zip(jax.tree.leaves(mine), jax.tree.leaves(want)):
        assert float(jnp.abs(ref).max()) > 1e-3
        np.testing.assert_allclose(got, ref, rtol=0, atol=2e-4 * float(jnp.abs(ref).max()))


def test_the_cache_counts_the_rows_gathered_and_the_held_pairs_as_before():
    """A seeded run of the engine on a held-experts config: ``cache_counters``
    reports blocks x R as ``moe_rows_gathered_total``, and the held pairs are
    the router's choices that fell on held experts, counted one by one."""
    from ray_tpu.serve.llm import LlamaDeployment

    cfg = LlamaConfig.tiny(**dict(SIGMOID, num_layers=2), num_kv_heads=4)
    replica = LlamaDeployment.func_or_class(config=cfg, max_slots=3, max_len=48, seed=0)
    engine = replica.engine
    prompts = [[3, 7, 11, 2], [5, 1, 9, 13, 17, 8]]

    async def one(prompt):
        return [t async for t in engine.stream(prompt, max_new_tokens=6)]

    async def run():
        await asyncio.gather(*(one(p) for p in prompts))
        return await replica.stats()

    stats = asyncio.run(run())
    calls = stats["moe_layer_steps_total"]
    assert calls == cfg.num_layers * (2 + (stats["rows_stepped_total"] - 10) // 3)
    # every call here is one block of one row tile (at most 6 x 4 rows)
    assert stats["moe_rows_gathered_total"] == calls * ROW_TILE
    assert stats["moe_routed_pairs_total"] == (
        stats["rows_stepped_total"] * cfg.num_layers * cfg.experts_per_token)
    assert stats["moe_held_pairs_total"] == int(np.asarray(stats["moe_expert_tokens"]).sum())
    assert 0 < stats["moe_held_pairs_total"] < stats["moe_routed_pairs_total"]
    # a prompt through the model alone: the held pairs are the router's
    # choices that fell on held experts, and the call is counted beside its tiles
    _, cache, chose = llama.choices_cached(
        engine.params, jnp.asarray([prompts[1]], jnp.int32), llama.init_cache(cfg, 1, 48),
        jnp.int32(0), None, cfg)
    experts = np.asarray(chose["experts"]) - cfg.expert_offset        # (layers, 1, 6, k)
    here = ((experts >= 0) & (experts < cfg.experts_here)).reshape(cfg.num_layers, -1)
    assert np.asarray(cache["moe_expert_tokens"]).sum(-1).tolist() == here.sum(-1).tolist()
    assert np.asarray(cache["moe_layer_steps"]).tolist() == [[1, 1]] * cfg.num_layers
