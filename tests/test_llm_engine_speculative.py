"""``LLMEngine`` where the deployment drafts (``speculative_tokens=1``) and
where it samples (``temperature`` > 0): a step yields one or two tokens a
row, the rows' positions stay on the device, a row is retired by what has
been delivered — and a request still gets exactly ``max_new_tokens`` ids,
in order, one stream item each, with one step in flight ahead."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama, mtp
from ray_tpu.serve.llm import LLMEngine

from test_llama_mtp import tiny, weights

BUDGETS = [1, 2, 3, 4, 7, 8, 15, 0, 5, 33]
MAX_LEN = 40


def prompt(i):
    return np.random.default_rng(i).integers(0, 128, 1 + i % 6).tolist()


def served(params, cfg, budgets=BUDGETS, **kw):
    eng = LLMEngine(params, cfg, max_slots=3, max_len=MAX_LEN, **kw)

    async def one(i, n):
        return [t async for t in eng.stream(prompt(i), n)]

    async def all_of_them():
        return await asyncio.gather(*[one(i, n) for i, n in enumerate(budgets)])

    return asyncio.run(all_of_them()), eng


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    return cfg, weights(cfg, sharp=1.0), weights(cfg)


def test_greedy_drafting_streams_exactly_the_greedy_ids(model):
    """Budgets 0, 1, 2, odd and even, prompts of 1 to 6 tokens, ten
    requests on three slots (so rows end in different steps and slots are
    taken again after a late retirement): every request gets exactly its
    budget, and the ids of greedy decoding without drafting."""
    cfg, params, _ = model
    plain, _ = served(params, cfg)
    drafted, eng = served(params, cfg, speculative_tokens=1)
    assert [len(o) for o in drafted] == BUDGETS
    assert drafted == plain
    assert eng.spec_drafted_total > 0 and eng.spec_wasted_row_steps_total > 0
    # tokens from decode steps: every request's but its first, which the prefill gave
    assert eng.spec_tokens_emitted_total == sum(max(0, b - 1) for b in BUDGETS)
    assert eng.admitted_total == len(BUDGETS) and eng.slots == [None] * 3


@pytest.mark.parametrize("speculative", [0, 1], ids=["plain", "drafting"])
def test_sampled_streams_hold_their_budget_and_repeat(model, speculative):
    """Temperature 1: exactly the budget, ids of the vocabulary, and the
    same ids again from another engine with the same seed (a token's draw
    hangs on seed, request and position); other ids with another seed."""
    cfg, _, params = model
    kw = dict(speculative_tokens=speculative, temperature=1.0)
    first, eng = served(params, cfg, seed=3, **kw)
    again, _ = served(params, cfg, seed=3, **kw)
    other, _ = served(params, cfg, seed=4, **kw)
    assert [len(o) for o in first] == BUDGETS
    assert all(0 <= t < cfg.vocab_size for o in first for t in o)
    assert first == again and first != other
    if speculative:
        assert 0 < eng.spec_accepted_total < eng.spec_drafted_total
        assert eng.spec_tokens_emitted_total == sum(max(0, b - 1) for b in BUDGETS)


def test_a_draw_does_not_hang_on_who_shares_the_step(model):
    """The same request (number 0) alone and among others: the same ids."""
    cfg, _, params = model
    kw = dict(speculative_tokens=1, temperature=1.0, seed=9)
    alone, _ = served(params, cfg, budgets=[12], **kw)
    among, _ = served(params, cfg, budgets=[12, 9, 7, 5], **kw)
    assert alone[0] == among[0]


@pytest.mark.parametrize("temperature", [0.0, 1.0])
def test_one_step_stays_in_flight_ahead(model, temperature):
    cfg, _, params = model
    _, eng = served(params, cfg, budgets=[30, 30, 30], speculative_tokens=1,
                    temperature=temperature)
    assert eng.decode_steps_total >= 15
    # all but the first step after the admissions were launched while the
    # one before was still in flight
    assert eng.steps_launched_ahead_total >= eng.decode_steps_total - 2
    # two token rows a slot a step, and the prompts
    assert eng.rows_stepped_total == 2 * 3 * eng.decode_steps_total + sum(
        len(prompt(i)) for i in range(3))


@pytest.mark.parametrize("speculative", [0, 1], ids=["plain", "drafting"])
def test_a_request_may_fill_the_cache_to_its_last_position(model, speculative):
    """prompt + budget == max_len: served whole; one more is refused."""
    cfg, _, params = model
    eng = LLMEngine(params, cfg, max_slots=2, max_len=MAX_LEN,
                    speculative_tokens=speculative, temperature=1.0)

    async def one(n):
        return [t async for t in eng.stream(prompt(5), n)]

    async def go():
        whole = await one(MAX_LEN - 6)
        with pytest.raises(ValueError, match="exceeds"):
            await one(MAX_LEN - 5)
        # the slot that ran to the cache's end serves the next request
        return whole, await one(4)

    whole, after = asyncio.run(go())
    assert len(whole) == MAX_LEN - 6 and len(after) == 4


def test_stats_count_the_modules_layer_and_the_drafts(model):
    from ray_tpu.serve.llm import LlamaDeployment

    cfg, _, params = model
    dep = LlamaDeployment.func_or_class(
        config=cfg, weights_loader=lambda: params, max_slots=2, max_len=32,
        speculative_tokens=1, temperature=1.0, seed=1)

    async def go():
        got = await dep.generate_all([5, 6, 7], 9)
        return got, await dep.stats()

    got, stats = asyncio.run(go())
    assert len(got) == 9
    row = 128 * 4                                       # 24 + 8 -> 128 lanes, float32
    assert stats["cache_bytes"]["ckv"] == 4 * 2 * 32 * row   # 3 layers + the module
    assert stats["spec_tokens_emitted_total"] == 8
    assert stats["spec_drafted_total"] >= stats["spec_accepted_total"] >= 0
    # the drafting versions of the two programs (the jit caches are the process's)
    assert min(stats["programs"].values()) >= 1
    assert mtp.decode_step_rowwise._cache_size() == stats["programs"]["decode_step_rowwise"]
    assert stats["mla_keys_visible_step"] > 0 and stats["mla_keys_read_step"] > 0
    assert stats["max_slots"] == 2 and stats["max_len"] == 32
    # every expert layer's rows, the module's among them, were routed
    tokens = np.asarray(stats["moe_expert_tokens"])
    assert tokens.shape == (3, 4) and (tokens.sum(-1) > 0).all()
