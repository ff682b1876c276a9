"""Serve LLM path: dynamic batching, multiplexing, and the
continuous-batching decode replica (SURVEY §7 config 5).

Mirrors ray: serve/batching.py:456 (@serve.batch), serve/api.py:607
(multiplexing), and the vLLM-on-ray LLM-replica pattern: N concurrent
streaming clients share one slot batch; replica death mid-stream raises
and recovery serves fresh requests.
"""

import threading
import time

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


class TestServeBatch:
    def test_concurrent_calls_batch_together(self, cluster):
        @serve.deployment
        class Batcher:
            def __init__(self):
                self.batch_sizes = []

            @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.2)
            async def pred(self, items):
                self.batch_sizes.append(len(items))
                return [x * 2 for x in items]

            async def __call__(self, x):
                return await self.pred(x)

            async def sizes(self):
                return self.batch_sizes

        h = serve.run(Batcher.bind(), name="batch_app", route_prefix=None)
        resps = [h.remote(i) for i in range(8)]
        vals = sorted(r.result(timeout_s=60) for r in resps)
        assert vals == [i * 2 for i in range(8)]
        sizes = h.options(method_name="sizes").remote().result(timeout_s=30)
        assert max(sizes) > 1, f"no batching happened: {sizes}"
        serve.delete("batch_app")

    def test_batch_error_propagates_to_all(self, cluster):
        @serve.deployment
        class Bad:
            @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.1)
            async def pred(self, items):
                raise RuntimeError("batch exploded")

            async def __call__(self, x):
                return await self.pred(x)

        h = serve.run(Bad.bind(), name="badbatch_app", route_prefix=None)
        resps = [h.remote(i) for i in range(3)]
        for r in resps:
            with pytest.raises(Exception, match="batch exploded"):
                r.result(timeout_s=60)
        serve.delete("badbatch_app")


class TestMultiplexing:
    def test_model_id_routes_and_caches(self, cluster):
        @serve.deployment
        class Mux:
            def __init__(self):
                self.loads = []

            @serve.multiplexed(max_num_models_per_replica=2)
            async def get_model(self, model_id: str):
                self.loads.append(model_id)
                return f"model::{model_id}"

            async def __call__(self, x):
                model = await self.get_model()
                return (model, serve.get_multiplexed_model_id(), x)

            async def loads_seen(self):
                return self.loads

        h = serve.run(Mux.bind(), name="mux_app", route_prefix=None)
        r1 = h.options(multiplexed_model_id="a").remote(1).result(timeout_s=60)
        assert r1 == ("model::a", "a", 1)
        r2 = h.options(multiplexed_model_id="a").remote(2).result(timeout_s=60)
        assert r2 == ("model::a", "a", 2)
        h.options(multiplexed_model_id="b").remote(3).result(timeout_s=60)
        h.options(multiplexed_model_id="c").remote(4).result(timeout_s=60)
        # "a" loaded once despite two calls; "c" evicted the LRU entry
        loads = h.options(method_name="loads_seen").remote().result(
            timeout_s=30
        )
        assert loads.count("a") == 1
        assert loads == ["a", "b", "c"], loads
        serve.delete("mux_app")


class TestLLMServing:
    def test_concurrent_streaming_clients(self, cluster):
        from ray_tpu.serve.llm import LlamaDeployment

        h = serve.run(
            LlamaDeployment.options(name="llm").bind(
                max_slots=4, max_len=64
            ),
            name="llm_app", route_prefix=None,
        )
        prompts = [[3, 7, 11], [5, 1, 4, 9], [2, 2, 2]]
        results = [None] * len(prompts)
        errors = []

        def client(i):
            try:
                gen = h.options(
                    method_name="generate", stream=True
                ).remote(prompts[i], max_new_tokens=6)
                toks = list(gen)
                results[i] = toks
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [
            threading.Thread(target=client, args=(i,))
            for i in range(len(prompts))
        ]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        elapsed = time.monotonic() - t0
        assert not errors, errors
        for toks in results:
            assert toks is not None and len(toks) == 6
            assert all(isinstance(t, int) for t in toks)
        # continuous batching: 3 concurrent 6-token streams should take
        # far less than 3x a single stream (shared decode steps); this is
        # a generous sanity bound, not a perf benchmark
        assert elapsed < 120, elapsed

        # determinism: same prompt again gives the same greedy tokens
        again = list(
            h.options(method_name="generate", stream=True).remote(
                prompts[0], max_new_tokens=6
            )
        )
        assert again == results[0]
        serve.delete("llm_app")

    def test_replica_death_failover(self, cluster):
        import os as _os

        from ray_tpu.serve.llm import LlamaDeployment

        class CrashableLlama(LlamaDeployment.func_or_class):
            async def crash(self):
                _os._exit(1)

        dep = serve.deployment(CrashableLlama).options(name="llm2")
        h = serve.run(
            dep.bind(max_slots=2, max_len=128),
            name="llm2_app", route_prefix=None,
        )
        gen = h.options(method_name="generate", stream=True).remote(
            [1, 2, 3], max_new_tokens=64
        )
        first = next(gen)
        assert isinstance(first, int)
        # kill the replica from inside, mid-stream (fire and forget)
        h.options(method_name="crash").remote()
        # the stream must surface the death rather than hang
        with pytest.raises(Exception):
            for _ in range(128):
                next(gen)
            raise AssertionError("stream survived a dead replica")
        # the controller restarts the replica; a NEW request succeeds
        deadline = time.monotonic() + 120
        out = None
        while time.monotonic() < deadline:
            try:
                out = list(
                    h.options(method_name="generate", stream=True).remote(
                        [4, 5], max_new_tokens=3
                    )
                )
                break
            except Exception:
                time.sleep(2)
        assert out is not None and len(out) == 3
        serve.delete("llm2_app")


class TestHTTPStreaming:
    def test_llm_tokens_stream_over_http_ndjson(self, cluster):
        import json as _json
        import urllib.request

        from ray_tpu.serve.llm import LlamaDeployment

        serve.run(
            LlamaDeployment.options(name="llmh").bind(
                max_slots=2, max_len=48
            ),
            name="llmh_app", route_prefix="/llm", http_port=0,
        )
        from ray_tpu.serve import api as serve_api

        port = ray_tpu.get(
            serve_api._proxy_handle.start.remote(), timeout=60
        )
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/llm?method=generate&stream=1",
            data=_json.dumps(
                {"prompt": [1, 2, 3], "max_new_tokens": 5}
            ).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=120) as r:
            assert r.headers["Content-Type"].startswith(
                "application/x-ndjson"
            )
            lines = [ln for ln in r.read().decode().splitlines() if ln]
        toks = [_json.loads(ln) for ln in lines]
        assert len(toks) == 5
        assert all(isinstance(t, int) for t in toks)
        serve.delete("llmh_app")


class TestRollingCacheEngine:
    def test_windowed_engine_uses_small_cache_and_matches_dense(self):
        """A sliding-window model with a prompt cap serves through a
        ROLLING cache (window + max_prompt - 1 slots) and must emit the
        same greedy tokens as full dense recompute, decoding far past
        the cache length (the Mistral KV-memory win, live in serving)."""
        import asyncio

        import jax
        import numpy as np

        from ray_tpu.models import llama
        from ray_tpu.serve.llm import LLMEngine

        cfg = llama.LlamaConfig.tiny(sliding_window=6)
        params = llama.init(jax.random.key(0), cfg)
        engine = LLMEngine(
            params, cfg, max_slots=2, max_len=64, max_prompt_len=4
        )
        assert engine.cache_len == 9  # 6 + 4 - 1 << 64
        assert engine.cache["k"].shape[2] == 9

        prompt = [3, 7, 11, 2]

        async def run():
            toks = []
            async for t in engine.stream(prompt, max_new_tokens=30):
                toks.append(t)
            return toks

        got = asyncio.run(run())
        assert len(got) == 30
        import jax.numpy as jnp

        ref = llama.generate(
            params, jnp.asarray([prompt], jnp.int32), cfg,
            max_new_tokens=30,
        )
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(ref[0, len(prompt):])
        )

    def test_prompt_cap_enforced(self):
        import asyncio

        import jax

        from ray_tpu.models import llama
        from ray_tpu.serve.llm import LLMEngine

        cfg = llama.LlamaConfig.tiny(sliding_window=6)
        params = llama.init(jax.random.key(0), cfg)
        engine = LLMEngine(
            params, cfg, max_slots=1, max_len=64, max_prompt_len=4
        )

        async def run():
            with pytest.raises(ValueError, match="prompt cap"):
                async for _ in engine.stream([1] * 8, max_new_tokens=2):
                    pass

        asyncio.run(run())


class TestExpertEngine:
    def test_streams_through_an_expert_config_and_stats_reads_the_counters(self):
        """A tiny OLMoE-shaped config (8 experts, top-2, QK-norm) through
        the same LLMEngine: the greedy tokens are the full recompute's,
        and ``stats()`` reports the routing counters the cache carries —
        every row of every decode step is counted, also the idle slot's."""
        import asyncio

        import jax
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.models import llama
        from ray_tpu.serve.llm import LlamaDeployment
        from ray_tpu.util import metrics

        cfg = llama.LlamaConfig.tiny(
            num_kv_heads=4, mlp_dim=0, num_experts=8, experts_per_token=2,
            expert_dim=48, qk_norm=True,
        )
        replica = LlamaDeployment.func_or_class(
            config=cfg, max_slots=3, max_len=48, seed=0
        )
        engine = replica.engine
        prompts = [[3, 7, 11, 2], [5, 1, 9, 13, 17, 8]]

        async def one(prompt):
            return [t async for t in engine.stream(prompt, max_new_tokens=10)]

        async def run():
            before = await replica.stats()
            got = await asyncio.gather(*(one(p) for p in prompts))
            return before, got, await replica.stats()

        before, got, stats = asyncio.run(run())
        for prompt, toks in zip(prompts, got):
            ref = llama.generate(
                engine.params, jnp.asarray([prompt], jnp.int32), cfg,
                max_new_tokens=10,
            )
            np.testing.assert_array_equal(
                np.asarray(toks), np.asarray(ref[0, len(prompt):])
            )
        assert before["moe_layer_steps_total"] == 0
        assert before["grouped_matmul"] == stats["grouped_matmul"] == "ragged_dot"
        tokens = np.asarray(stats["moe_expert_tokens"])
        assert tokens.shape == (cfg.num_layers, cfg.num_experts)
        # two prefills and the decode steps, each over ALL three slots
        rows = stats["rows_stepped_total"]
        assert rows == 4 + 6 + 3 * ((rows - 10) // 3) and rows >= 10 + 3 * 9
        assert tokens.sum() == rows * cfg.num_layers * cfg.experts_per_token
        layer_steps = stats["moe_layer_steps_total"]
        assert layer_steps == cfg.num_layers * (2 + (rows - 10) // 3)
        assert 2 * layer_steps <= stats["moe_experts_touched_total"] <= 8 * layer_steps
        gauges = {
            m["name"]: list(m["series"].values())[0]
            for m in metrics.registry_snapshot()
            if m["name"].startswith("llm_moe_")
        }
        assert gauges["llm_moe_experts_touched_mean"] == pytest.approx(
            stats["moe_experts_touched_total"] / layer_steps)
        assert gauges["llm_moe_expert_load_max_over_mean"] == pytest.approx(
            tokens.max() / tokens.mean())

    def test_a_dense_config_reports_no_expert_keys(self):
        import asyncio

        from ray_tpu.serve.llm import LlamaDeployment

        replica = LlamaDeployment.func_or_class(max_slots=2, max_len=32)
        stats = asyncio.run(replica.stats())
        assert not [k for k in stats if k.startswith("moe_")]
        assert "grouped_matmul" not in stats and stats["rows_stepped_total"] == 0
