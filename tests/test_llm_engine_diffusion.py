"""``LLMEngine`` where the deployment generates by diffusion over blocks
(``diffusion_block=4``): a step gives a row none of its block's tokens or
all of them, the rows' blocks stay on the device, a row is retired by what
has been delivered — and a request still gets exactly ``max_new_tokens``
ids, in order, one stream item each, with one step in flight ahead.  And the
rule's threshold branch, on a vocabulary small enough to fire it."""

import asyncio
import dataclasses

import jax
import numpy as np
import pytest

from chipbench.reference import sdar
from ray_tpu.models import block_diffusion as bd
from ray_tpu.serve.llm import LLMEngine

from test_llama_block_diffusion import (BLOCK, MASK, SLOTS, T, config, prompt,
                                        run_rows, weights)

BUDGETS = [1, 5, 8, 0, 12, 3]
PROMPTS = [8, 9, 11, 2, 8, 11]  # few lengths: a prefill compiles once a length
MAX_LEN = 32


def served(params, cfg, **kw):
    eng = LLMEngine(params, cfg, max_slots=SLOTS - 1, max_len=MAX_LEN,
                    diffusion_block=BLOCK, temperature=T, **kw)

    async def one(i, n):
        return [t async for t in eng.stream(prompt(PROMPTS[i], i), n)]

    async def all_of_them():
        return await asyncio.gather(*[one(i, n) for i, n in enumerate(BUDGETS)])

    return asyncio.run(all_of_them()), eng


@pytest.fixture(scope="module")
def model():
    cfg = config(mask_block=1)  # the deployment's option puts the model under the block mask
    return cfg, weights(cfg)


@pytest.fixture(scope="module")
def streamed(model):
    cfg, params = model
    return served(params, cfg, seed=3)


def test_streams_hold_their_budget_in_order(streamed):
    """``max_new_tokens`` of 1, 5, 8 (and 0, 12, 3), prompts of 2 to 11
    tokens with every leftover, six requests on three slots: exactly the
    budget each, never the MASK id; the counters add up."""
    first, eng = streamed
    assert [len(o) for o in first] == BUDGETS
    assert all(0 <= t < MASK for o in first for t in o)
    assert eng.config.mask_block == BLOCK and eng.stateful and not eng.speculative
    assert eng.diffusion_tokens_emitted_total == sum(BUDGETS)
    blocks = eng.diffusion_commit_forwards_total
    assert blocks >= sum(-(-b // BLOCK) for b in BUDGETS)
    assert eng.diffusion_forwards_total > 4 * blocks - 8
    assert eng.diffusion_tokens_unmasked_total <= BLOCK * blocks + BLOCK * 3
    assert eng.diffusion_threshold_transfers_total == 0
    assert eng.diffusion_wasted_row_steps_total > 0 and eng.kv_keys_visible_step > 0
    # a cache of 32 slots takes XLA's body: every row's slab whole, every step
    assert eng.kv_keys_read_step == (
        eng.decode_steps_total * (SLOTS - 1) * MAX_LEN * eng.config.num_layers)
    assert eng.kv_keys_read_step > eng.kv_keys_visible_step
    assert eng.admitted_total == len(BUDGETS) and eng.slots == [None] * (SLOTS - 1)
    assert eng.steps_launched_ahead_total > 0


def test_a_request_gets_the_blocks_the_programs_give_it(model, streamed):
    """Requests 0-2 through the engine, among others: the ids of the two
    programs driven by hand for those requests with the same key (a
    candidate's draw hangs on seed, request, position and pass only: not on
    who shares the step, nor on how the host paced it)."""
    _cfg, params = model
    got, eng = streamed
    rows, _, _ = run_rows(
        eng.config, params, eng._step_options["settings"], jax.random.key(3),
        [prompt(PROMPTS[i], i) for i in range(3)], 16, budgets=BUDGETS[:3],
        requests=[0, 1, 2])
    assert [r["emitted"] for r in rows] == got[:3]


@pytest.mark.parametrize("kw, why", [
    (dict(speculative_tokens=1), "speculative"),
    (dict(max_len=30), "whole number of blocks"),
    (dict(latent=True), "K/V-cache"),
    (dict(denoising_steps=3), "whole multiple"),
])
def test_refusals(model, kw, why):
    cfg, params = model
    kw = dict(kw)
    if kw.pop("latent", False):
        cfg = dataclasses.replace(
            cfg, kv_lora_rank=16, q_lora_rank=16, qk_nope_head_dim=8,
            qk_rope_head_dim=8, v_head_dim=8)
    if "speculative_tokens" in kw:
        cfg = dataclasses.replace(cfg, mtp_layers=1)
    with pytest.raises(ValueError, match=why):
        LLMEngine(params, cfg, **{"max_slots": 2, "max_len": 32, "diffusion_block": BLOCK, **kw})


def test_the_threshold_branch_fires_on_a_small_peaked_vocabulary():
    """A 16-id vocabulary with scaled logits: candidates over 0.9 are
    transferred together, a block takes fewer than four passes, and the
    reference's rule agrees pass by pass."""
    cfg = config(vocab_size=16)
    params = weights(cfg, seed=3, scale=8.0)
    params["lm_head"] = params["lm_head"] * 6.0
    settings = bd.Settings(block=BLOCK, denoising_steps=4, threshold=0.9, mask_id=15)
    key = jax.random.key(11)
    prompts = [np.random.default_rng(i).integers(0, 15, 8).tolist() for i in range(3)]
    rows, _states, _ = run_rows(cfg, params, settings, key, prompts, 10)
    fired = together = 0
    for r, row in enumerate(rows):
        for p in row["passes"]:
            if not p["live"] or p["committed"]:
                continue
            masked = p["block"] == 15
            want, high = sdar.transfers(p["conf"], masked, 0.9, 1)
            assert want.tolist() == p["transfer"].tolist()
            assert p["by_threshold"] == (int(want.sum()) if high else 0)
            fired += high
            together += int(want.sum()) > 1
        blocks = sum(p["committed"] for p in row["passes"])
        refining = sum(p["live"] and not p["committed"] for p in row["passes"])
        assert blocks >= 1 and refining <= 4 * (blocks + 1)
    assert fired and together  # both branches ran: the other is the run above
