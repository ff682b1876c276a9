"""Llama model family tests: shapes, causality, GQA, sharding, HF parity.

Mirrors the gpt2 test coverage (tests/test_parallel.py) for the second
LM family, plus a transformers weight-conversion parity check like
tests/test_hf_interop.py.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from ray_tpu.models import llama
from ray_tpu.parallel import spmd
from ray_tpu.parallel.mesh import MeshConfig, make_mesh


class TestLlamaModel:
    def test_forward_shapes_and_loss(self):
        cfg = llama.LlamaConfig.tiny()
        params = llama.init(jax.random.key(0), cfg)
        toks = jax.random.randint(
            jax.random.key(1), (2, 17), 0, cfg.vocab_size
        )
        logits = llama.forward(params, toks[:, :-1], cfg)
        assert logits.shape == (2, 16, cfg.vocab_size)
        loss = llama.loss_fn(params, {"tokens": toks}, cfg)
        assert abs(float(loss) - np.log(cfg.vocab_size)) < 0.5

    def test_causality(self):
        cfg = llama.LlamaConfig.tiny()
        params = llama.init(jax.random.key(0), cfg)
        t1 = jnp.zeros((1, 16), jnp.int32)
        t2 = t1.at[0, 10].set(5)
        l1 = llama.forward(params, t1, cfg)
        l2 = llama.forward(params, t2, cfg)
        np.testing.assert_allclose(
            np.asarray(l1[0, :10]), np.asarray(l2[0, :10]), atol=1e-4
        )
        assert not np.allclose(np.asarray(l1[0, 10:]), np.asarray(l2[0, 10:]))

    def test_gqa_equals_mha_when_kv_repeated(self):
        """num_kv_heads=H with duplicated KV weights must equal GQA with
        shared heads — validates the repeat wiring."""
        cfg_gqa = llama.LlamaConfig.tiny(num_heads=4, num_kv_heads=2)
        params = llama.init(jax.random.key(0), cfg_gqa)
        cfg_mha = dataclasses.replace(cfg_gqa, num_kv_heads=4)
        p2 = jax.tree.map(lambda x: x, params)
        p2["blocks"]["wk"] = jnp.repeat(params["blocks"]["wk"], 2, axis=2)
        p2["blocks"]["wv"] = jnp.repeat(params["blocks"]["wv"], 2, axis=2)
        toks = jax.random.randint(jax.random.key(3), (1, 12), 0, 256)
        a = llama.forward(params, toks, cfg_gqa)
        b = llama.forward(p2, toks, cfg_mha)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)

    def test_chunked_xent_matches_dense(self):
        cfg = llama.LlamaConfig.tiny()
        cfg_chunk = dataclasses.replace(cfg, xent_chunk=16)
        params = llama.init(jax.random.key(0), cfg)
        toks = jax.random.randint(jax.random.key(1), (2, 65), 0, 256)
        l1 = float(llama.loss_fn(params, {"tokens": toks}, cfg))
        l2 = float(llama.loss_fn(params, {"tokens": toks}, cfg_chunk))
        assert abs(l1 - l2) < 1e-4

    def test_tiny_overfit(self):
        """A few adam steps on one batch must drop the loss sharply."""
        cfg = llama.LlamaConfig.tiny()
        params = llama.init(jax.random.key(0), cfg)
        opt = optax.adam(1e-2)
        opt_state = opt.init(params)
        toks = jax.random.randint(jax.random.key(1), (4, 33), 0, 256)

        @jax.jit
        def step(params, opt_state):
            loss, grads = jax.value_and_grad(llama.loss_fn)(
                params, {"tokens": toks}, cfg
            )
            updates, opt_state = opt.update(grads, opt_state)
            return optax.apply_updates(params, updates), opt_state, loss

        losses = []
        for _ in range(25):
            params, opt_state, loss = step(params, opt_state)
            losses.append(float(loss))
        assert losses[-1] < losses[0] - 1.0, losses[::8]


class TestSlidingWindow:
    """Mistral-style sliding-window attention (llama sliding_window)."""

    def test_window_geq_seq_equals_full_causal(self):
        cfg = llama.LlamaConfig.tiny()
        cfg_w = dataclasses.replace(cfg, sliding_window=64)  # > seq
        params = llama.init(jax.random.key(0), cfg)
        toks = jax.random.randint(jax.random.key(1), (2, 16), 0, 256)
        np.testing.assert_allclose(
            np.asarray(llama.forward(params, toks, cfg)),
            np.asarray(llama.forward(params, toks, cfg_w)),
            atol=1e-5,
        )

    def test_window_bounds_receptive_field_one_layer(self):
        """With ONE layer and window w, position t is independent of
        tokens at positions <= t - w (multi-layer stacks widen the
        field by w per layer, like Mistral)."""
        cfg = llama.LlamaConfig.tiny(num_layers=1, sliding_window=4)
        params = llama.init(jax.random.key(0), cfg)
        t1 = jnp.zeros((1, 16), jnp.int32)
        t2 = t1.at[0, 2].set(9)  # perturb position 2
        l1 = llama.forward(params, t1, cfg)
        l2 = llama.forward(params, t2, cfg)
        # positions >= 2 + 4 never see position 2
        np.testing.assert_allclose(
            np.asarray(l1[0, 6:]), np.asarray(l2[0, 6:]), atol=1e-4
        )
        # but positions inside the window do
        assert not np.allclose(
            np.asarray(l1[0, 2:6]), np.asarray(l2[0, 2:6])
        )

    def test_cached_decode_matches_dense_with_window(self):
        """The KV-cache prefill + rowwise decode must agree with the
        dense windowed forward token-for-token."""
        cfg = llama.LlamaConfig.tiny(sliding_window=5)
        params = llama.init(jax.random.key(0), cfg)
        toks = jax.random.randint(jax.random.key(7), (1, 9), 0, 256)
        dense_last = llama.forward(params, toks, cfg)[:, -1, :]
        cache = llama.init_cache(cfg, 1, 16)
        cached_last, cache = llama.forward_cached(
            params, toks, cache, jnp.int32(0), cfg
        )
        np.testing.assert_allclose(
            np.asarray(cached_last), np.asarray(dense_last),
            atol=2e-4, rtol=2e-4,
        )
        # one rowwise decode step vs dense recompute of the longer seq
        nxt = jnp.argmax(cached_last, axis=-1).astype(jnp.int32)
        pos = jnp.full((1,), 9, jnp.int32)
        step_logits, cache = llama.decode_step_rowwise(
            params, nxt, cache, pos, cfg
        )
        longer = jnp.concatenate([toks, nxt[:, None]], axis=1)
        dense_step = llama.forward(params, longer, cfg)[:, -1, :]
        np.testing.assert_allclose(
            np.asarray(step_logits), np.asarray(dense_step),
            atol=2e-4, rtol=2e-4,
        )

    def test_rolling_cache_wraps_and_matches_dense(self):
        """A cache SMALLER than the decoded sequence (the Mistral
        memory win) must still match dense logits step for step — the
        rolling slots wrap and old positions get overwritten."""
        cfg = llama.LlamaConfig.tiny(sliding_window=4)
        params = llama.init(jax.random.key(0), cfg)
        toks = jax.random.randint(jax.random.key(8), (1, 3), 0, 256)
        T = llama.rolling_cache_len(cfg, prefill_chunk=3)  # 4 + 3 - 1
        assert T == 6
        cache = llama.init_cache(cfg, 1, T)
        logits, cache = llama.forward_cached(
            params, toks, cache, jnp.int32(0), cfg
        )
        seq = toks
        for step in range(10):  # total 13 positions >> T=6: wraps twice
            np.testing.assert_allclose(
                np.asarray(logits),
                np.asarray(llama.forward(params, seq, cfg)[:, -1, :]),
                atol=3e-4, rtol=3e-4,
                err_msg=f"diverged at decode step {step}",
            )
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
            pos = jnp.full((1,), seq.shape[1] - 1, jnp.int32)
            logits, cache = llama.decode_step_rowwise(
                params, nxt, cache, pos, cfg
            )

    def test_prefill_chunk_exceeding_cache_raises(self):
        cfg = llama.LlamaConfig.tiny(sliding_window=4)
        params = llama.init(jax.random.key(0), cfg)
        toks = jnp.zeros((1, 8), jnp.int32)
        cache = llama.init_cache(cfg, 1, 6)  # chunk 8 > T 6
        with pytest.raises(AssertionError, match="prefill chunk"):
            llama.forward_cached(params, toks, cache, jnp.int32(0), cfg)

    def test_generate_kv_with_window_larger_than_sequence(self):
        """The common config (window >> decoded length) must serve
        through the cached fast path — the cache never wraps, so no
        rolling constraint applies — and agree with full recompute."""
        cfg = llama.LlamaConfig.tiny(sliding_window=64)
        params = llama.init(jax.random.key(0), cfg)
        prompt = jax.random.randint(jax.random.key(9), (1, 8), 0, 256)
        cached = llama.generate_kv(params, prompt, cfg, max_new_tokens=4)
        full = llama.generate(params, prompt, cfg, max_new_tokens=4)
        np.testing.assert_array_equal(np.asarray(cached), np.asarray(full))

    def test_mistral_preset_shape(self):
        cfg = llama.LlamaConfig.mistral_7b()
        assert cfg.sliding_window == 4096 and cfg.num_kv_heads == 8
        assert cfg.mlp_dim == 14336 and cfg.max_seq_len == 32768


class TestLlamaSharded:
    def test_sharded_train_step(self):
        mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
        cfg = llama.LlamaConfig.tiny()
        opt = optax.adamw(1e-2)
        state = spmd.sharded_init(
            mesh,
            lambda r: llama.init(r, cfg),
            jax.random.key(0),
            llama.param_logical_axes(cfg),
            opt,
        )
        assert state.params["tok_embed"].sharding.spec == P("tp", "fsdp")
        step = spmd.compile_train_step(
            lambda p, b: llama.loss_fn(p, b, cfg), opt
        )
        toks = jax.random.randint(jax.random.key(1), (8, 33), 0, 256)
        batch = spmd.shard_batch(mesh, {"tokens": toks})
        with jax.set_mesh(mesh):
            losses = []
            for _ in range(10):
                state, metrics = step(state, batch)
                losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0] - 0.5, losses


class TestLlamaHF:
    @pytest.fixture(scope="class")
    def tiny_pair(self):
        transformers = pytest.importorskip("transformers")
        hf_cfg = transformers.LlamaConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=64,
            rms_norm_eps=1e-5, tie_word_embeddings=False,
            attention_dropout=0.0,
        )
        model = transformers.LlamaForCausalLM(hf_cfg).eval()
        from ray_tpu.models.hf import llama_params_from_hf

        params, config = llama_params_from_hf(
            model, dtype=jnp.float32, remat=False,
        )
        return model, params, config

    def test_config_mapping(self, tiny_pair):
        _, params, config = tiny_pair
        assert config.num_kv_heads == 2 and config.q_per_kv == 2
        assert params["blocks"]["wq"].shape == (2, 32, 4, 8)
        assert params["blocks"]["wk"].shape == (2, 32, 2, 8)

    def test_logit_parity(self, tiny_pair):
        torch = pytest.importorskip("torch")
        model, params, config = tiny_pair
        rng = np.random.default_rng(0)
        tokens = rng.integers(0, 128, size=(2, 13), dtype=np.int64)
        with torch.no_grad():
            hf_logits = model(torch.from_numpy(tokens)).logits.numpy()
        ours = np.asarray(
            llama.forward(params, jnp.asarray(tokens, jnp.int32), config),
            np.float32,
        )
        np.testing.assert_allclose(ours, hf_logits, atol=2e-3, rtol=2e-3)


class TestQwen3MoeHF:
    """The block SDAR-30B-A3B shares with Qwen3-MoE, against transformers'
    own: ``head_dim`` off ``hidden_size // heads``, per-head ``q_norm`` /
    ``k_norm``, softmax top-k renormalised (``norm_topk_prob``)."""

    def test_logit_parity(self):
        transformers = pytest.importorskip("transformers")
        torch = pytest.importorskip("torch")
        if not hasattr(transformers, "Qwen3MoeForCausalLM"):
            pytest.skip("this transformers has no Qwen3-MoE")
        torch.manual_seed(0)
        hf_cfg = transformers.Qwen3MoeConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=24, num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, num_experts=8, num_experts_per_tok=2,
            norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=[],
            max_position_embeddings=64, rms_norm_eps=1e-6, rope_theta=1e6,
            tie_word_embeddings=False, attention_dropout=0.0,
            use_sliding_window=False, router_aux_loss_coef=0.0,
        )
        model = transformers.Qwen3MoeForCausalLM(hf_cfg).eval()
        with torch.no_grad():  # norms off one, so that they are seen
            for name, p in model.named_parameters():
                if "norm" in name:
                    p.add_(0.2 * torch.randn_like(p))
        from ray_tpu.models.hf import llama_params_from_hf

        params, config = llama_params_from_hf(model, dtype=jnp.float32, remat=False)
        assert config.head_dim == 16 != config.embed_dim // config.num_heads
        assert config.qk_norm == "head" and config.router_norm_topk
        assert params["blocks"]["q_norm"].shape == (2, 16)
        assert params["blocks"]["w_gate"].shape == (2, 8, 32, 24)
        tokens = np.random.default_rng(0).integers(0, 128, size=(2, 13), dtype=np.int64)
        with torch.no_grad():
            hf_logits = model(torch.from_numpy(tokens)).logits.numpy()
        ours = np.asarray(
            llama.forward(params, jnp.asarray(tokens, jnp.int32), config), np.float32)
        np.testing.assert_allclose(ours, hf_logits, atol=2e-3, rtol=2e-3)


class TestLlamaServe:
    def test_llama_inference_replica(self):
        """SURVEY §7 config-5 shape: a Serve replica hosting the LM,
        scoring and generating behind the handle API."""
        import ray_tpu
        from ray_tpu import serve

        ray_tpu.init(num_cpus=4, num_tpus=0)
        try:
            @serve.deployment(num_replicas=1)
            class LlamaReplica:
                def __init__(self):
                    self.cfg = llama.LlamaConfig.tiny()
                    self.params = llama.init(jax.random.key(0), self.cfg)

                def __call__(self, token_ids=None, new_tokens=4):
                    toks = jnp.asarray([token_ids], jnp.int32)
                    out = llama.generate(
                        self.params, toks, self.cfg,
                        max_new_tokens=int(new_tokens),
                    )
                    return {"tokens": np.asarray(out[0]).tolist()}

            handle = serve.run(LlamaReplica.bind(), name="llm",
                               route_prefix="/llm")
            resp = handle.remote(token_ids=[1, 2, 3], new_tokens=4).result(
                timeout_s=300
            )
            assert len(resp["tokens"]) == 7
            assert all(0 <= t < 256 for t in resp["tokens"])
            serve.shutdown()
        finally:
            ray_tpu.shutdown()


class TestKVCacheDecode:
    def test_kv_decode_matches_full_recompute(self):
        """generate_kv (O(1)/token cached step) must emit exactly the
        same greedy tokens as generate (full recompute)."""
        cfg = llama.LlamaConfig.tiny()
        params = llama.init(jax.random.key(0), cfg)
        prompt = jax.random.randint(jax.random.key(5), (2, 7), 0, 256)
        full = llama.generate(params, prompt, cfg, max_new_tokens=12)
        cached = llama.generate_kv(params, prompt, cfg, max_new_tokens=12)
        np.testing.assert_array_equal(np.asarray(full), np.asarray(cached))

    def test_cached_forward_matches_dense_logits(self):
        """Prefill through the cache path must reproduce the dense
        forward's last-position logits."""
        cfg = llama.LlamaConfig.tiny()
        params = llama.init(jax.random.key(0), cfg)
        toks = jax.random.randint(jax.random.key(6), (1, 9), 0, 256)
        dense_last = llama.forward(params, toks, cfg)[:, -1, :]
        cache = llama.init_cache(cfg, 1, 16)
        cached_last, _ = llama.forward_cached(
            params, toks, cache, jnp.int32(0), cfg
        )
        np.testing.assert_allclose(
            np.asarray(cached_last), np.asarray(dense_last),
            atol=2e-4, rtol=2e-4,
        )

    def test_gqa_cache_shapes(self):
        cfg = llama.LlamaConfig.tiny(num_heads=4, num_kv_heads=2)
        cache = llama.init_cache(cfg, 3, 32)
        assert cache["k"].shape == (2, 3, 32, 2 * 16)

    def test_generate_kv_decodes_with_the_engines_programs(self):
        """generate_kv has no decode program of its own: on a shape no
        other test uses it compiles one ``prefill_into_slot`` and one
        ``decode_step_rowwise``, the two the serving engine runs."""
        cfg = llama.LlamaConfig.tiny(vocab_size=300, num_layers=1)
        params = llama.init(jax.random.key(0), cfg)
        prompt = jax.random.randint(jax.random.key(2), (3, 5), 0, 300)
        before = (llama.prefill_into_slot._cache_size(),
                  llama.decode_step_rowwise._cache_size())
        cached = llama.generate_kv(params, prompt, cfg, max_new_tokens=3)
        assert (llama.prefill_into_slot._cache_size(),
                llama.decode_step_rowwise._cache_size()) == (
            before[0] + 1, before[1] + 1)
        full = llama.generate(params, prompt, cfg, max_new_tokens=3)
        np.testing.assert_array_equal(np.asarray(full), np.asarray(cached))


class TestRowwiseDecode:
    """The continuous batcher's step: rows at different positions in one
    cache, against the dense forward of each row's own sequence."""

    T, STEPS = 12, 4
    # row -> prompt length; row 0 starts decoding at pos 0 with nothing
    # prefilled, row 2 ends at pos T - 1, row 3 idles (token 0, pos 0)
    PROMPTS = {1: 3, 2: 8}

    def _setup(self, kv_heads, window):
        cfg = llama.LlamaConfig.tiny(
            num_heads=4, num_kv_heads=kv_heads, sliding_window=window
        )
        params = llama.init(jax.random.key(0), cfg)
        seqs = jax.random.randint(jax.random.key(11), (3, self.T), 0, 256)
        # causal: position p of the padded row is the row's own prefix
        dense = np.asarray(llama.forward(params, seqs, cfg))
        cache = llama.init_cache(cfg, 4, self.T)
        for row, n in self.PROMPTS.items():
            logits, cache = llama.prefill_into_slot(
                params, seqs[row:row + 1, :n], cache, jnp.int32(row), cfg
            )
            np.testing.assert_allclose(
                np.asarray(logits[0]), dense[row, n - 1], atol=3e-4, rtol=3e-4
            )
        return cfg, params, np.asarray(seqs), dense, cache

    @pytest.mark.parametrize("window", [0, 5])
    @pytest.mark.parametrize("kv_heads", [4, 2, 1])  # G = 1, 2, 4
    def test_rows_match_dense_forward(self, kv_heads, window):
        cfg, params, seqs, dense, cache = self._setup(kv_heads, window)
        start = np.array([0, 3, 8, 0])
        for step in range(self.STEPS):
            pos = start + np.array([step, step, step, 0])
            tokens = np.append(seqs[np.arange(3), pos[:3]], 0)
            logits, cache = llama.decode_step_rowwise(
                params, jnp.asarray(tokens, jnp.int32), cache,
                jnp.asarray(pos, jnp.int32), cfg,
            )
            for row in range(3):
                np.testing.assert_allclose(
                    np.asarray(logits[row]), dense[row, pos[row]],
                    atol=3e-4, rtol=3e-4,
                    err_msg=f"row {row} at pos {pos[row]} (step {step})",
                )
        assert pos[2] == self.T - 1

    def test_rows_do_not_leak(self):
        """Whatever another row's cache holds, a row's logits are the
        same to the bit."""
        cfg, params, seqs, _, cache = self._setup(2, 0)
        noise = {
            n: a.at[:, jnp.array([0, 2, 3])].set(
                jax.random.normal(jax.random.key(i), a[:, :3].shape, a.dtype)
            )
            for i, (n, a) in enumerate(cache.items())
        }
        tokens = jnp.asarray([5, seqs[1, 3], 7, 0], jnp.int32)
        pos = jnp.asarray([6, 3, 11, 0], jnp.int32)
        clean, _ = llama.decode_step_rowwise(params, tokens, cache, pos, cfg)
        noisy, _ = llama.decode_step_rowwise(params, tokens, noise, pos, cfg)
        np.testing.assert_array_equal(
            np.asarray(clean[1]), np.asarray(noisy[1])
        )
        assert not np.array_equal(np.asarray(clean[0]), np.asarray(noisy[0]))

    @pytest.mark.parametrize("window", [0, 5])
    @pytest.mark.parametrize("kv_heads", [4, 2, 1])
    def test_entry_points_agree_and_touch_only_their_rows(self, kv_heads, window):
        """One prompt through ``forward_cached`` into a 1-row cache and
        through ``prefill_into_slot`` into row 2 of a 4-row cache full
        of noise, then one ``decode_step_rowwise`` on each: the same
        logits and K/V, and every other row as it was, to the bit."""
        cfg = llama.LlamaConfig.tiny(
            num_heads=4, num_kv_heads=kv_heads, sliding_window=window
        )
        params = llama.init(jax.random.key(0), cfg)
        n, row = 6, 2
        prompt = jax.random.randint(jax.random.key(12), (1, n), 0, 256)
        one = llama.init_cache(cfg, 1, self.T)
        noise = {
            name: np.asarray(
                jax.random.normal(jax.random.key(i), a.shape, a.dtype))
            for i, (name, a) in enumerate(
                llama.init_cache(cfg, 4, self.T).items())
        }
        others = [0, 1, 3]

        def same_rows(four, one, upto, before, others_from):
            for name in ("k", "v"):
                got, want = np.asarray(four[name]), np.asarray(one[name])
                np.testing.assert_allclose(
                    got[:, row, :upto], want[:, 0, :upto], atol=1e-5, rtol=1e-5)
                # the row's own tail and the other rows: never written
                np.testing.assert_array_equal(
                    got[:, row, upto:], before[name][:, row, upto:])
                np.testing.assert_array_equal(
                    got[:, others, others_from:],
                    before[name][:, others, others_from:])

        want, one = llama.forward_cached(params, prompt, one, jnp.int32(0), cfg)
        got, four = llama.prefill_into_slot(
            params, prompt, {k: jnp.asarray(v) for k, v in noise.items()},
            jnp.int32(row), cfg,
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)
        same_rows(four, one, n, noise, 0)

        tok = jnp.argmax(want, axis=-1).astype(jnp.int32)
        want, one = llama.decode_step_rowwise(
            params, tok, one, jnp.full((1,), n, jnp.int32), cfg)
        prefilled = {k: np.asarray(v) for k, v in four.items()}
        # the other rows decode token 0 at position 0: into their slot 0
        got, four = llama.decode_step_rowwise(
            params, jnp.zeros((4,), jnp.int32).at[row].set(tok[0]), four,
            jnp.zeros((4,), jnp.int32).at[row].set(n), cfg,
        )
        np.testing.assert_allclose(
            np.asarray(got[row]), np.asarray(want[0]), atol=1e-5, rtol=1e-5)
        same_rows(four, one, n + 1, prefilled, 1)


def _expert_config(**kw):
    """Tiny OLMoE-shaped config: MHA with QK-norm, 8 experts, top-2."""
    defaults = dict(
        num_kv_heads=4, mlp_dim=0, num_experts=8, experts_per_token=2,
        expert_dim=48, qk_norm=True,
    )
    defaults.update(kw)
    return llama.LlamaConfig.tiny(**defaults)


class TestGroupedMatmul:
    """ops/grouped_matmul.py against a loop over rows."""

    @pytest.mark.parametrize("sizes", [
        [3, 0, 5, 1, 0, 7],      # ragged, with empty groups
        [0, 0, 16, 0, 0, 0],     # every row in one group
        [1, 1, 1, 1, 1, 1],      # single-row groups; rows past the sum
        [0, 0, 0, 0, 0, 0],      # nothing routed at all
    ])
    def test_rows_times_their_groups_matrix(self, sizes):
        from ray_tpu.ops.grouped_matmul import grouped_matmul, implementation

        assert implementation() == "ragged_dot"  # no TPU here
        rows, k, n = 16, 12, 20
        lhs = jax.random.normal(jax.random.key(0), (rows, k))
        rhs = jax.random.normal(jax.random.key(1), (len(sizes), k, n))
        got = np.asarray(grouped_matmul(lhs, rhs, jnp.asarray(sizes)))
        group = np.repeat(np.arange(len(sizes)), sizes)
        for r, g in enumerate(group):  # rows past sum(sizes) are undefined
            np.testing.assert_allclose(
                got[r], np.asarray(lhs[r]) @ np.asarray(rhs[g]),
                atol=1e-5, rtol=1e-5,
            )
        assert got.shape == (rows, n)

    def test_tiles_fit_the_two_shapes_the_replica_runs(self):
        from ray_tpu.ops import grouped_matmul as gm

        for k, n in ((2048, 1024), (1024, 2048), (64, 48), (4096, 14336)):
            tm, tk, tn = gm.tile_for(k, n)
            assert (tm, tk) == (128, k) and (tn == n or tn % 128 == 0)
            # two bf16 tiles of rhs in flight: half the kernel's 16 MiB
            assert 2 * tk * tn * 2 <= 8 * 2**20
        assert gm.tile_for(2048, 1024) == (128, 2048, 1024)  # a whole expert
        assert gm.tile_for(1024, 2048) == (128, 1024, 2048)


class TestExpertLayer:
    def test_dense_config_is_todays_program(self):
        """num_experts = 0 and qk_norm False: the same parameters, the
        same cache and no routing operation in any traced program."""
        cfg = llama.LlamaConfig.tiny()
        params = llama.init(jax.random.key(0), cfg)
        assert set(params["blocks"]) == {
            "attn_norm", "wq", "wk", "wv", "wo", "mlp_norm",
            "w_gate", "w_up", "w_down",
        }
        assert params["blocks"]["w_gate"].shape == (2, 64, 160)
        assert set(llama.param_logical_axes(cfg)["blocks"]) == set(params["blocks"])
        cache = llama.init_cache(cfg, 2, 16)
        assert set(cache) == {"k", "v"}
        rows = jnp.zeros((2,), jnp.int32)
        for text in (
            str(jax.make_jaxpr(
                lambda p, c: llama.decode_step_rowwise(p, rows, c, rows, cfg)
            )(params, cache)),
            str(jax.make_jaxpr(
                lambda p, c: llama.prefill_into_slot(
                    p, jnp.zeros((1, 8), jnp.int32), c, jnp.int32(0), cfg)
            )(params, cache)),
            str(jax.make_jaxpr(
                lambda p: llama.loss_fn(p, {"tokens": jnp.zeros((1, 9), jnp.int32)}, cfg)
            )(params)),
        ):
            for op in ("top_k", " sort[", "argsort", "ragged_dot", "moe_"):
                assert op not in text, op
        logits, new = llama.decode_step_rowwise(params, rows, cache, rows, cfg)
        assert set(new) == {"k", "v"}

    def test_expert_parameters_and_axes(self):
        cfg = _expert_config()
        params = llama.init(jax.random.key(0), cfg)
        b = params["blocks"]
        assert b["w_router"].shape == (2, 64, 8)
        assert b["w_gate"].shape == b["w_up"].shape == (2, 8, 64, 48)
        assert b["w_down"].shape == (2, 8, 48, 64)
        assert b["q_norm"].shape == b["k_norm"].shape == (2, 64)
        axes = llama.param_logical_axes(cfg)["blocks"]
        assert set(axes) == set(b)
        for name in ("w_gate", "w_up", "w_down"):
            assert axes[name][1] == "expert" and len(axes[name]) == b[name].ndim
        assert llama.num_params(cfg) == sum(
            a.size for a in jax.tree.leaves(params))

    def test_no_token_is_dropped_when_every_token_picks_the_same_expert(self):
        """A router that sends every token to experts 5 and 2: a layer
        with a capacity would drop most of them.  Here expert 5 computes
        every token, and the output is the plain weighted sum."""
        cfg = _expert_config(num_layers=1)
        params = llama.init(jax.random.key(0), cfg)
        router = np.zeros((1, 64, 8), np.float32)
        router[0, 0, 5], router[0, 0, 2] = 3.0, 2.0
        params["blocks"]["w_router"] = jnp.asarray(router)
        h = jax.random.normal(jax.random.key(3), (2, 6, 64))
        h = h.at[..., 0].set(jnp.abs(h[..., 0]) + 0.5)  # logit 5 > logit 2 > 0
        # as a layer loop hands them over: the layer's own slice of every
        # leaf but the three expert tensors, which stay stacked
        p = {k: v if k in llama._EXPERT_TENSORS else v[0]
             for k, v in params["blocks"].items()}
        y, routing = llama._ffn(h, dict(p, layer=jnp.int32(0)), cfg)
        np.testing.assert_array_equal(
            np.asarray(routing["rows"]), [0, 0, 12, 0, 0, 12, 0, 0])
        assert np.asarray(routing["experts"]).tolist() == [[[5, 2]] * 6] * 2
        probs = np.asarray(jax.nn.softmax(h.reshape(12, 64) @ router[0], -1))
        want = np.zeros((12, 64), np.float32)
        x = np.asarray(h.reshape(12, 64))
        for e in (5, 2):
            g = x @ np.asarray(p["w_gate"][0, e])
            act = g / (1 + np.exp(-g)) * (x @ np.asarray(p["w_up"][0, e]))
            want += probs[:, e:e + 1] * (act @ np.asarray(p["w_down"][0, e]))
        np.testing.assert_allclose(
            np.asarray(y.reshape(12, 64)), want, atol=1e-5, rtol=1e-4)

    def test_counters_after_n_steps_equal_a_numpy_recount(self):
        """The cache's running totals against the choices the no-cache
        forward makes for the same tokens, counted with numpy.  Rows the
        caller treats as idle (token 0 at position 0) are counted too."""
        cfg = _expert_config()
        params = llama.init(jax.random.key(0), cfg)
        params["blocks"]["w_router"] = params["blocks"]["w_router"] * 20
        seq = np.asarray(jax.random.randint(jax.random.key(4), (9,), 0, 256))
        slots, steps = 3, 4
        cache = llama.init_cache(cfg, slots, 16)
        assert cache["moe_expert_tokens"].shape == (2, 8)
        _, cache = llama.prefill_into_slot(
            params, jnp.asarray(seq[None, :5]), cache, jnp.int32(1), cfg)
        for i in range(steps):
            tokens = np.zeros((slots,), np.int32)
            pos = np.zeros((slots,), np.int32)
            tokens[1], pos[1] = seq[5 + i], 5 + i
            _, cache = llama.decode_step_rowwise(
                params, jnp.asarray(tokens), cache, jnp.asarray(pos), cfg)
        # row 1 saw seq[:9]; rows 0 and 2 saw token 0 at position 0, 4 times
        chose = np.asarray(llama.expert_choices(params, jnp.asarray(seq[None]), cfg))
        idle = np.asarray(llama.expert_choices(params, jnp.zeros((1, 1), jnp.int32), cfg))
        want = np.zeros((2, 8), np.int64)
        touched = np.zeros((2,), np.int64)
        for layer in range(2):
            calls = [chose[layer, 0, :5].ravel()] + [
                np.concatenate([chose[layer, 0, 5 + i], idle[layer, 0, 0], idle[layer, 0, 0]])
                for i in range(steps)
            ]
            for call in calls:
                count = np.bincount(call, minlength=8)
                want[layer] += count
                touched[layer] += (count > 0).sum()
        np.testing.assert_array_equal(np.asarray(cache["moe_expert_tokens"]), want)
        np.testing.assert_array_equal(np.asarray(cache["moe_experts_touched"]), touched)
        np.testing.assert_array_equal(np.asarray(cache["moe_layer_steps"]), [1 + steps] * 2)
        # nothing dropped: k rows per token per layer
        assert want.sum() == 2 * 2 * (5 + steps * slots)

    def test_generate_kv_runs_an_expert_config(self):
        cfg = _expert_config()
        params = llama.init(jax.random.key(0), cfg)
        prompt = jnp.asarray([[3, 7, 11, 2]], jnp.int32)
        kv = llama.generate_kv(params, prompt, cfg, max_new_tokens=5)
        full = llama.generate(params, prompt, cfg, max_new_tokens=5)
        np.testing.assert_array_equal(np.asarray(kv), np.asarray(full))

    def test_expert_loss_has_gradients_for_every_expert_tensor(self):
        cfg = _expert_config()
        params = llama.init(jax.random.key(0), cfg)
        toks = jax.random.randint(jax.random.key(1), (2, 17), 0, cfg.vocab_size)
        loss, grads = jax.value_and_grad(llama.loss_fn)(params, {"tokens": toks}, cfg)
        assert abs(float(loss) - np.log(cfg.vocab_size)) < 0.5
        for name in ("w_router", "w_gate", "w_up", "w_down", "q_norm", "k_norm"):
            assert float(jnp.abs(grads["blocks"][name]).max()) > 0, name
