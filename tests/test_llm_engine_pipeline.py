"""The decode engine keeps one step in flight ahead of the one whose
tokens it delivers (``serve/llm.py:LLMEngine``): step k+1 is fed step
k's argmax on the device and launched before step k's tokens come down,
and only an admission drains the pipeline.  Held here off the chip: the
streams are what each prompt gives alone, the order of launches and
deliveries is the pipelined one, the counters say so, and a step that
fails at its sync fails its streams once.
"""

import asyncio
import collections
import functools
import time

import numpy as np
import pytest

from ray_tpu.serve.traffic.config import RequestShedError, set_request_deadline

CONFIGS = {
    "dense": {},
    "window": dict(sliding_window=6),
    "expert": dict(num_kv_heads=4, mlp_dim=0, num_experts=8,
                   experts_per_token=2, expert_dim=48, qk_norm=True),
}


@functools.lru_cache(maxsize=None)
def _model(kind):
    import jax

    from ray_tpu.models import llama

    config = llama.LlamaConfig.tiny(**CONFIGS[kind])
    return config, llama.init(jax.random.key(0), config)


@functools.lru_cache(maxsize=None)
def _alone(kind, prompt, new):
    """The greedy tokens of ``prompt`` by itself (``llama.generate_kv``)."""
    import jax.numpy as jnp

    from ray_tpu.models import llama

    config, params = _model(kind)
    out = llama.generate_kv(
        params, jnp.asarray([prompt], jnp.int32), config, max_new_tokens=new,
    )
    return np.asarray(out[0, len(prompt):]).tolist()


def _engine(kind="dense", **kwargs):
    from ray_tpu.serve.llm import LLMEngine

    config, params = _model(kind)
    return LLMEngine(params, config, **kwargs)


class R:
    """One request of a scenario.  ``after=(j, n)``: sent when request j
    has received its n-th token, so with the step behind that token in
    flight.  ``deadline``: seconds from the send, as the traffic plane
    would have set it.  ``expect``: an exception type, else the tokens
    of the prompt alone."""

    def __init__(self, prompt, new, after=None, deadline=None, expect=None):
        self.prompt, self.new = tuple(prompt), new
        self.after, self.deadline, self.expect = after, deadline, expect


async def _serve(engine, requests):
    """Every request through ``engine.stream``, each in a task of its
    own: (tokens, error) per request."""
    got = [[] for _ in requests]
    errors = [None] * len(requests)
    reached = collections.defaultdict(asyncio.Event)

    async def one(k, r):
        if r.after is not None:
            await reached[r.after].wait()
        if r.deadline is not None:
            set_request_deadline(time.monotonic() + r.deadline)
        try:
            async for tok in engine.stream(list(r.prompt), r.new):
                got[k].append(tok)
                reached[(k, len(got[k]))].set()
        except Exception as e:  # noqa: BLE001 — the scenario expects it
            errors[k] = e

    await asyncio.wait_for(
        asyncio.gather(*(one(k, r) for k, r in enumerate(requests))), 120
    )
    return got, errors


SCENARIOS = {
    "staggered_concurrent_requests": ("dense", dict(max_slots=3, max_len=64), [
        R([3, 7, 11, 2], 9),
        R([5, 1, 9, 13, 17, 8], 6, after=(0, 2)),
        R([4, 4, 6], 7, after=(1, 1)),
    ]),
    "more_requests_than_slots": ("dense", dict(max_slots=2, max_len=64), [
        R([3, 7, 11, 2], 5), R([5, 1, 9, 13, 17, 8], 7), R([4, 4, 6], 3),
        R([9, 8, 7, 6, 5], 6), R([2, 3], 4),
    ]),
    "a_request_arrives_while_a_step_is_in_flight": (
        "dense", dict(max_slots=2, max_len=64), [
            R([3, 7, 11, 2], 8),
            R([5, 1, 9, 13, 17, 8], 5, after=(0, 3)),
            R([4, 4, 6], 4, after=(1, 2)),
        ]),
    "max_new_tokens_of_0_1_and_2": ("dense", dict(max_slots=2, max_len=64), [
        R([3, 7, 11, 2], 0), R([5, 1, 9, 13, 17, 8], 1), R([4, 4, 6], 2),
        R([9, 8, 7, 6, 5], 6), R([2, 3], 1, after=(3, 2)),
        R([2, 3, 4], 2, after=(3, 3)),
    ]),
    "a_rejected_prompt": ("dense", dict(max_slots=2, max_len=16), [
        R([3, 7, 11, 2], 6),
        R(list(range(1, 13)), 8, after=(0, 2), expect=ValueError),
        R([4, 4, 6], 5, after=(0, 3)),
    ]),
    "a_shed_request": ("dense", dict(max_slots=1, max_len=64), [
        R([3, 7, 11, 2], 6),
        R([5, 1, 9, 13, 17, 8], 5, after=(0, 1), deadline=-1.0, expect=RequestShedError),
        R([4, 4, 6], 4, after=(0, 2), deadline=600.0),
    ]),
    "a_sliding_window_config": (
        "window", dict(max_slots=2, max_len=64, max_prompt_len=4), [
            R([3, 7, 11, 2], 30), R([5, 1, 9], 12, after=(0, 4)),
            R([8, 8], 9, after=(1, 3)),
        ]),
    "an_expert_config": ("expert", dict(max_slots=3, max_len=48), [
        R([3, 7, 11, 2], 10), R([5, 1, 9, 13, 17, 8], 8, after=(0, 2)),
        R([4, 4, 6], 6, after=(1, 5)), R([9, 8, 7, 6, 5], 5, after=(2, 1)),
    ]),
}


@pytest.mark.parametrize("name", list(SCENARIOS))
def test_each_stream_gets_the_tokens_of_its_prompt_alone(name):
    kind, kwargs, requests = SCENARIOS[name]
    engine = _engine(kind, **kwargs)
    got, errors = asyncio.run(_serve(engine, requests))
    for k, r in enumerate(requests):
        if r.expect is not None:
            assert isinstance(errors[k], r.expect), (k, errors[k])
            assert got[k] == []
        else:
            assert errors[k] is None, (k, errors[k])
            assert got[k] == _alone(kind, r.prompt, r.new), k
    # nothing is left behind: no step in flight, no slot taken, and no
    # step was launched for nothing
    assert not engine._flying and not any(engine.slots)
    served = [r for r in requests if r.expect is None and r.new > 1]
    assert engine.decode_steps_total <= sum(r.new - 1 for r in served)
    if served:
        assert engine.decode_steps_total >= max(r.new - 1 for r in served)


class _Logged:
    """``models.llama`` with the two engine programs' calls logged."""

    def __init__(self, module, log):
        self._module, self._log = module, log

    def __getattr__(self, name):
        return getattr(self._module, name)

    def decode_step_rowwise(self, *args):
        self._log.append(("launch",))
        return self._module.decode_step_rowwise(*args)

    def prefill_into_slot(self, *args):
        self._log.append(("prefill",))
        return self._module.prefill_into_slot(*args)


def _logged_run(monkeypatch, requests, **kwargs):
    """The engine's launches, prefills and ``queue.put``s in the order
    they happened, and the scenario's results."""
    log = []
    engine = _engine(**kwargs)
    engine._llama = _Logged(engine._llama, log)
    put = asyncio.Queue.put

    async def logging_put(self, item):
        log.append(("put", id(self), item))
        return await put(self, item)

    monkeypatch.setattr(asyncio.Queue, "put", logging_put)
    got, errors = asyncio.run(_serve(engine, requests))
    monkeypatch.undo()
    assert errors == [None] * len(requests)
    return log, got, engine


def _by_step(log):
    """Log positions by decode step (1-based): ``launch[s]``, and
    ``puts[s]``, the positions at which step s's tokens were put.  A
    queue's first token is its prefill's (``first[q]``); its n-th belongs
    to the (n-1)-th step launched after that prefill was, for a row
    decodes in every step from its admission to its end."""
    launch, puts, first, seen = {}, collections.defaultdict(list), {}, {}
    launched_at_prefill, launches, prefills = {}, 0, []
    for at, event in enumerate(log):
        if event[0] == "launch":
            launches += 1
            launch[launches] = at
        elif event[0] == "prefill":
            prefills.append((at, launches))
        elif isinstance(event[2], int):
            q = event[1]
            if q not in seen:
                seen[q] = 1
                first[q] = at
                # the newest prefill before this put is this queue's
                launched_at_prefill[q] = [n for p, n in prefills if p < at][-1]
            else:
                seen[q] += 1
                puts[launched_at_prefill[q] + seen[q] - 1].append(at)
    return launch, dict(puts), first, prefills


def test_the_next_step_is_launched_before_this_ones_tokens_are_delivered(
        monkeypatch):
    requests = [
        R([3, 7, 11, 2], 9),
        R([5, 1, 9, 13, 17, 8], 8, after=(0, 3)),
        R([4, 4, 6], 5, after=(1, 2)),       # waits for the first's slot
    ]
    log, got, engine = _logged_run(
        monkeypatch, requests, max_slots=2, max_len=64)
    for r, toks in zip(requests, got):
        assert toks == _alone("dense", r.prompt, r.new)
    launch, puts, first, prefills = _by_step(log)
    steps = sorted(launch)
    assert steps == list(range(1, engine.decode_steps_total + 1))
    assert sorted(puts) == steps  # every step delivered something
    ahead = 0
    for s in steps[:-1]:
        admitted = [p for p, _ in prefills if launch[s] < p < launch[s + 1]]
        if not admitted:
            # a non-admitting iteration: call s+1 comes before any token
            # of step s
            assert launch[s + 1] < min(puts[s]), s
            ahead += 1
        else:
            # an admission drains: the prefill goes behind step s, step
            # s's tokens are delivered, then the prefill's first token
            firsts = [at for at in first.values() if launch[s] < at < launch[s + 1]]
            assert admitted[0] < min(puts[s]), s
            assert max(puts[s]) < min(firsts), s
    assert len(prefills) == 3 and ahead == engine.steps_launched_ahead_total
    assert ahead == len(steps) - 3  # all but the first step after a prefill


def test_stats_counts_the_steps_launched_ahead():
    """One slot, a scripted run: A restarts the engine from idle, B is
    admitted behind A's last step (one drain), then the engine parks and
    C restarts it."""
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.serve.llm import LlamaDeployment

    replica = LlamaDeployment.func_or_class(max_slots=1, max_len=32, seed=0)
    engine = replica.engine

    async def run():
        a, b = await asyncio.gather(
            _serve(engine, [R([3, 7, 11, 2], 4)]),
            _serve(engine, [R([5, 1, 9], 3)]),
        )
        warm = llama.decode_step_rowwise._cache_size()
        c = await _serve(engine, [R([4, 4, 6], 5)])
        return a, b, c, warm, await replica.stats()

    a, b, c, warm, stats = asyncio.run(run())
    assert [len(x[0][0]) for x in (a, b, c)] == [4, 3, 5]
    drains, restarts = 1, 2
    assert stats["decode_steps_total"] == 3 + 2 + 4
    assert stats["steps_launched_ahead_total"] == (
        stats["decode_steps_total"] - drains - restarts)
    assert stats["admitted_total"] == 3
    assert stats["rows_stepped_total"] == 4 + 3 + 3 + 9 * 1
    # fed the last step's argmax or an array from the host (as the
    # benchmark's reference check feeds it), it is one decode program
    _, engine.cache = llama.decode_step_rowwise(
        engine.params, jnp.asarray(np.zeros((1,), np.int32)), engine.cache,
        jnp.asarray(np.zeros((1,), np.int32)), engine.config,
    )
    assert llama.decode_step_rowwise._cache_size() == warm


class _FailsAtSync:
    def __array__(self, *args, **kwargs):
        raise RuntimeError("the device lost step 3")


def test_a_step_that_raises_at_its_sync_fails_its_streams_once(monkeypatch):
    engine = _engine(max_slots=2, max_len=64)
    launch = engine._launch
    failed = collections.Counter()
    put = asyncio.Queue.put

    async def counting_put(self, item):
        if isinstance(item, Exception):
            failed[id(self)] += 1
        return await put(self, item)

    monkeypatch.setattr(asyncio.Queue, "put", counting_put)

    async def failing_launch(life, active):
        await launch(life, active)
        if engine.decode_steps_total == 3:
            engine._flying[-1].tokens = _FailsAtSync()

    engine._launch = failing_launch
    requests = [R([3, 7, 11, 2], 9), R([5, 1, 9, 13, 17, 8], 6)]

    async def run():
        first = await _serve(engine, requests)
        engine._launch = launch
        return first, await _serve(engine, [R([4, 4, 6], 5)])

    (got, errors), (after, after_errors) = asyncio.run(run())
    # both streams were live at step 3: tokens of the prefill and of
    # steps 1 and 2 (step 2 was delivered after step 3 was launched),
    # then the error, once each
    for k, r in enumerate(requests):
        assert isinstance(errors[k], RuntimeError) and "step 3" in str(errors[k])
        assert got[k] == _alone("dense", r.prompt, r.new)[:3]
    assert sorted(failed.values()) == [1, 1]
    assert not engine._flying and not any(engine.slots)
    assert after_errors == [None]
    assert after[0] == _alone("dense", (4, 4, 6), 5)
