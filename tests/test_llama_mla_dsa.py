"""GLM-5's block through ``models/llama.py`` at small sizes on the CPU,
seeded weights: latent (MLA) cache + sparse-attention indexer + sigmoid
router beside a shared expert + one chip's share of the experts, against
the plain float32 reference ``chipbench/reference/glm_dsa.py`` (on the
chip ``chipbench/jobs/serve_dsa.py`` makes the same comparison at the
published widths)."""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.jobs import serve_dsa, serve_mtp
from chipbench.reference import errors
from chipbench.reference import glm_dsa as ref
from chipbench.reference.llama import FLOAT32_TOLERANCE
from ray_tpu.models import llama
from ray_tpu.ops import latent_decode_attention, topk_mask

TOPK = 8
_STEP_PROGRAMS = (llama.choices_cached, llama.decode_step_rowwise)


@pytest.fixture
def body(request, monkeypatch):
    """``"gathered"``: the decode step as these tiny caches trace it;
    ``"streamed"``: the kernel in blocks of 8 keys, which takes them.
    The block size is read when a program is traced, so what was traced
    under another one is forgotten, before and after."""
    if request.param == "streamed":
        monkeypatch.setattr(latent_decode_attention, "BLOCK_KEYS", 8)
    for program in _STEP_PROGRAMS:
        program.clear_cache()
    yield request.param
    for program in _STEP_PROGRAMS:
        program.clear_cache()


both_bodies = pytest.mark.parametrize("body", ["gathered", "streamed"], indirect=True)


def tiny(**kw):
    """3 layers (1 dense + 2 expert), 4 heads of nope 12 | rope 8 | v 16,
    latent 24, query latent 32, indexer 4 heads x 16 picking 8 keys, 16
    experts of which 4 are held (from 4), top-4, a shared expert."""
    d = dict(
        vocab_size=128, max_seq_len=128, num_layers=3, num_heads=4, num_kv_heads=4,
        embed_dim=64, mlp_dim=96, dtype=jnp.float32, remat=False, rope_theta=1e4,
        q_lora_rank=32, kv_lora_rank=24, qk_nope_head_dim=12, qk_rope_head_dim=8,
        v_head_dim=16, index_n_heads=4, index_head_dim=16, index_topk=TOPK,
        first_dense_layers=1, num_experts=16, experts_per_token=4, expert_dim=32,
        shared_expert_dim=32, router_scoring="sigmoid", router_norm_topk=True,
        router_scale=2.5, experts_held=4, expert_offset=4,
    )
    d.update(kw)
    return llama.LlamaConfig(**d)


def weights(cfg, seed=0):
    """Seeded weights with the norms' scales and the indexer's bias away
    from their neutral values (at 1 / 0 a missing norm or bias would
    still pass)."""
    params = llama.init(jax.random.key(seed), cfg)
    for stack in ("dense_blocks", "blocks"):
        b = params.get(stack)
        if b is None:
            continue
        for i, name in enumerate(("q_a_norm", "kv_a_norm", "ik_norm", "ik_bias")):
            noise = 0.3 * jax.random.normal(jax.random.key(i + 1), b[name].shape)
            b[name] = (b[name] + noise).astype(b[name].dtype)
    return params


def prompt(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, n).tolist()


@pytest.mark.parametrize("length,block,kw,body", [
    (3 * TOPK, 128, {}, "gathered"),          # one block of queries
    (3 * TOPK, 8, {}, "gathered"),            # three whole blocks
    (21, 8, {}, "gathered"),                  # the last block padded
    (32, 8, {}, "gathered"),                  # four causal groups of one block
    (3 * TOPK, 8, {"first_dense_layers": 2}, "gathered"),  # two dense blocks lead
    (3 * TOPK, 8, {"first_dense_layers": 0}, "gathered"),  # expert blocks only
    (TOPK - 2, 128, {}, "gathered"),          # never more keys than may be seen
    # the decode steps through the kernel: the cache's 40 keys are 5 blocks
    (3 * TOPK, 128, {}, "streamed"),          # more visible than chosen, 4 blocks in
    (21, 8, {}, "streamed"),                  # the steps cross into the 4th block
    (TOPK - 2, 128, {}, "streamed"),          # fewer, exactly, one more than index_topk
], indirect=["body"])
def test_prefill_then_decode_through_the_cache_is_the_reference(
        monkeypatch, length, block, kw, body):
    """Logits, selected sets and chosen experts of ``prefill_into_slot``
    and three ``decode_step_rowwise`` steps in a three-row cache equal the
    reference's full forward at a context of 3 x ``index_topk``."""
    assert latent_decode_attention.implementation(40) == body
    monkeypatch.setattr(llama, "_QUERY_BLOCK", block)
    cfg = tiny(**kw)
    params = weights(cfg)
    cache = llama.init_cache(cfg, 3, 40)
    seq = prompt(cfg, length)
    logits, cache, chose = llama.choices_cached(
        params, jnp.asarray([seq], jnp.int32), cache, jnp.int32(1), None, cfg)
    system, steps = [logits[0]], []
    prefill = chose
    for _ in range(3):
        seq.append(int(jnp.argmax(system[-1])))
        tokens, pos = np.zeros(3, np.int32), np.zeros(3, np.int32)
        tokens[1], pos[1] = seq[-1], len(seq) - 1
        logits, cache, chose = llama.choices_cached(
            params, jnp.asarray(tokens), cache, None, jnp.asarray(pos), cfg)
        system.append(logits[1])
        steps.append(chose)
    want, info = ref.forward(
        params, jnp.asarray(seq, jnp.int32), serve_dsa.spec_of(cfg),
        positions=list(range(length - 1, length + 3)), head_rows=64)
    err = errors(jnp.stack(system), want)
    assert err["rms"] < FLOAT32_TOLERANCE["rms"] and err["max"] < FLOAT32_TOLERANCE["max"]
    sets = np.asarray(info["selected"])                       # (L, S, S)
    assert np.array_equal(np.asarray(prefill["selected"])[:, 0], sets[:, :length, :length])
    experts = np.sort(np.asarray(info["experts"]), -1)        # (Le, S, k)
    assert np.array_equal(
        np.sort(np.asarray(prefill["experts"])[:, 0], -1), experts[:, :length])
    for i, chose in enumerate(steps):
        t = length + i
        row = np.asarray(chose["selected"])[:, 1, 0]          # (L, T)
        assert np.array_equal(row[:, :t + 1], sets[:, t, :t + 1]) and not row[:, t + 1:].any()
        assert row.sum(-1).tolist() == [min(TOPK, t + 1)] * cfg.num_layers
        assert np.array_equal(np.sort(np.asarray(chose["experts"])[:, 1, 0], -1), experts[:, t])
    # the engine's two programs are the same step without the choices
    cache2 = llama.init_cache(cfg, 3, 40)
    plain, cache2 = llama.prefill_into_slot(
        params, jnp.asarray([seq[:length]], jnp.int32), cache2, jnp.int32(1), cfg)
    np.testing.assert_allclose(plain[0], system[0], rtol=0, atol=1e-6)


@both_bodies
def test_absorbed_decode_attention_equals_the_expanded_form(body):
    """A decode step (queries carried into the latent space, values
    applied to the mix of latents) gives the logits of a prefill one
    token longer (keys and values expanded), at every length around
    ``index_topk``."""
    assert latent_decode_attention.implementation(32) == body
    cfg = tiny()
    params = weights(cfg)
    seq = prompt(cfg, 2 * TOPK + 1, seed=3)
    for n in (TOPK - 1, TOPK, TOPK + 1, 2 * TOPK):
        cache = llama.init_cache(cfg, 2, 32)
        _, cache = llama.prefill_into_slot(
            params, jnp.asarray([seq[:n]], jnp.int32), cache, jnp.int32(0), cfg)
        step, _ = llama.decode_step_rowwise(
            params, jnp.asarray([seq[n], 0], jnp.int32), cache,
            jnp.asarray([n, 0], jnp.int32), cfg)
        whole, _ = llama.prefill_into_slot(
            params, jnp.asarray([seq[:n + 1]], jnp.int32),
            llama.init_cache(cfg, 2, 32), jnp.int32(1), cfg)
        np.testing.assert_allclose(step[0], whole[0], rtol=0, atol=2e-6)


INF = float("inf")


@pytest.mark.parametrize("scores,k,want", [
    # more visible keys than k: exactly k, the largest
    ([[5., 1., 4., 3., 2., -INF]], 3, [[1, 0, 1, 1, 0, 0]]),
    # a tie AT the k-th value goes to the lower position
    ([[2., 7., 2., 2., 9., 2.]], 3, [[1, 1, 0, 0, 1, 0]]),
    ([[2., 2., 2., 2., 2., 2.]], 4, [[1, 1, 1, 1, 0, 0]]),
    # a tie above the margin changes nothing
    ([[9., 9., 1., 5., 0., 3.]], 3, [[1, 1, 0, 1, 0, 0]]),
    # fewer visible keys than k: every visible key, no other
    ([[4., 3., -INF, -INF, -INF, -INF]], 3, [[1, 1, 0, 0, 0, 0]]),
    # exactly k visible
    ([[4., 3., 1., -INF, -INF, -INF]], 3, [[1, 1, 1, 0, 0, 0]]),
    # a margin of one float32 step still separates
    ([[1., np.nextafter(np.float32(1), np.float32(2)), 1., 0.]], 1, [[0, 1, 0, 0]]),
    # no more keys than k at all: the mask is the visible keys
    ([[1., -INF, 2.]], 8, [[1, 0, 1]]),
])
@pytest.mark.parametrize("body", ["sorted", "counted"])
def test_selection_is_exact(scores, k, want, body):
    if body == "sorted":    # as the model traces a run this small
        assert topk_mask.implementation(1, len(scores[0]), k) == "sorted"
        got = llama._select_mask(jnp.asarray(scores, jnp.float32), k)
    else:   # the kernel: the row eight times over whole lane tiles of unseen keys
        wide = np.full((8, 128), -INF, np.float32)
        wide[:, :len(scores[0])] = scores
        got = topk_mask.counted_mask(jnp.asarray(wide), k)[:1, :len(scores[0])]
    assert np.array_equal(np.asarray(got), np.asarray(want, bool))
    # the decode step's ``lax.top_k`` orders ties the same way
    s = jnp.asarray(scores, jnp.float32)
    if s.shape[-1] > k:
        _, idx = jax.lax.top_k(s, k)
        picked = np.zeros(s.shape, bool)
        picked[0, np.asarray(idx)[0]] = True
        picked &= np.asarray(s) > -INF
        assert np.array_equal(picked, np.asarray(want, bool))


@pytest.mark.parametrize("chips,held,spec_of,kw", [
    (16, 2, serve_dsa.spec_of, {}),
    # JoyAI-LLM-Flash's cut: 32 chips share a layer, no indexer beside it
    (32, 2, serve_mtp.spec_of, dict(index_topk=0, index_n_heads=0, index_head_dim=0)),
], ids=["glm5_16_chips", "joyai_32_chips"])
def test_the_shares_of_an_expert_layer_add_up_to_the_whole_layer(chips, held, spec_of, kw):
    """``chips`` chips, ``held`` of the experts each: the routed parts the
    shares compute, with the shared expert (which every chip computes
    alike) counted once, add up to the uncut reference's layer."""
    X = chips * held
    cfg = tiny(num_experts=X, experts_held=0, expert_offset=0, first_dense_layers=0, **kw)
    params = weights(cfg, seed=2) if not kw else llama.init(jax.random.key(2), cfg)
    p = {k: v[1] for k, v in params["blocks"].items()}          # one layer, whole
    h = jax.random.normal(jax.random.key(9), (2, 24, cfg.embed_dim), jnp.float32)
    spec = spec_of(cfg)
    with jax.default_matmul_precision("highest"):
        whole, chosen, _ = ref._experts(h.reshape(-1, cfg.embed_dim), p, spec)
        shared = ref._swiglu(h.reshape(-1, cfg.embed_dim),
                             p["ws_gate"], p["ws_up"], p["ws_down"])
    total, rows = 0.0, []
    for rank in range(chips):
        share = dataclasses.replace(cfg, experts_held=held, expert_offset=held * rank)
        mine = dict(p, layer=jnp.int32(0), **{
            k: p[k][None, held * rank:held * (rank + 1)]
            for k in ("w_gate", "w_up", "w_down")})
        y, routing = llama._ffn(h, mine, share)
        total = total + (y.reshape(-1, cfg.embed_dim) - shared)
        rows.append(np.asarray(routing["rows"]))
        # a share's reference is the reference given the same held set
        with jax.default_matmul_precision("highest"):
            part, _, _ = ref._experts(
                h.reshape(-1, cfg.embed_dim),
                {**p, **{k: mine[k][0] for k in ("w_gate", "w_up", "w_down")}},
                spec_of(share))
        np.testing.assert_allclose(y.reshape(-1, cfg.embed_dim), part, rtol=0, atol=2e-6)
    np.testing.assert_allclose(total + shared, whole, rtol=0, atol=5e-6)
    # every routed assignment was computed by exactly one share
    assert np.concatenate(rows).sum() == 2 * 24 * cfg.experts_per_token
    assert np.array_equal(
        np.concatenate(rows), np.bincount(np.asarray(chosen).ravel(), minlength=X))


def test_rows_whose_experts_all_live_elsewhere_get_the_shared_expert_alone():
    cfg = tiny(num_experts=64, experts_held=8, expert_offset=56, first_dense_layers=0)
    params = weights(cfg, seed=4)
    p = dict({k: v[0] for k, v in params["blocks"].items()}, layer=jnp.int32(0),
             **{k: params["blocks"][k][:1] for k in ("w_gate", "w_up", "w_down")})
    h = jax.random.normal(jax.random.key(1), (1, 32, cfg.embed_dim), jnp.float32)
    y, routing = llama._ffn(h, p, cfg)
    elsewhere = ~((np.asarray(routing["experts"]) >= 56).any(-1))[0]
    assert elsewhere.any() and not elsewhere.all()
    shared = llama._swiglu(h, p["ws_gate"], p["ws_up"], p["ws_down"], cfg)
    np.testing.assert_allclose(y[0][elsewhere], shared[0][elsewhere], rtol=0, atol=1e-7)
    assert np.abs(np.asarray(y - shared)[0][~elsewhere]).max() > 1e-5
    assert np.isfinite(np.asarray(y)).all()


def test_long_runs_go_through_the_expert_layer_in_chunks(monkeypatch):
    cfg = tiny(first_dense_layers=0)
    params = weights(cfg)
    p = dict({k: v[0] for k, v in params["blocks"].items()}, layer=jnp.int32(0),
             **{k: params["blocks"][k][:1] for k in ("w_gate", "w_up", "w_down")})
    h = jax.random.normal(jax.random.key(1), (1, 32, cfg.embed_dim), jnp.float32)
    y, routing = llama._ffn(h, p, cfg)
    monkeypatch.setattr(llama, "_FFN_CHUNK", 8)
    yc, rc = llama._ffn_in_chunks(h, p, cfg)
    np.testing.assert_allclose(yc, y, rtol=0, atol=1e-6)
    assert np.array_equal(rc["rows"], routing["rows"])
    assert np.array_equal(rc["experts"], routing["experts"])


def test_wide_counters_do_not_overflow():
    total = jnp.zeros((3, 2), jnp.int32)
    for _ in range(5):
        total = llama._add_wide(total, jnp.asarray([2_000_000_000, 7, 1 << 20], jnp.int32))
    assert [llama.wide_total(total[i]) for i in range(3)] == [10_000_000_000, 35, 5 << 20]
    assert llama.wide_total(total) == 10_000_000_000 + 35 + (5 << 20)


@both_bodies
def test_the_cache_holds_latent_rows_index_keys_and_counters(body):
    cfg = tiny()
    cache = llama.init_cache(cfg, 3, 40)
    assert cache["ckv"].shape == (3, 3, 40, 128)    # 24 + 8 values in a 128-lane row
    assert cache["ik"].shape == (3, 3, 40, 16)
    assert "k" not in cache and "v" not in cache
    assert cache["moe_expert_tokens"].shape == (2, 4)   # expert layers x experts HELD
    seq = prompt(cfg, 24)
    _, cache = llama.prefill_into_slot(
        weights(cfg), jnp.asarray([seq], jnp.int32), cache, jnp.int32(2), cfg)
    keys = np.asarray(cache["dsa_keys"])
    visible = 24 * 25 // 2
    selected = sum(min(TOPK, t + 1) for t in range(24))
    for layer in range(3):
        assert llama.wide_total(keys[layer, 0, 0]) == visible
        assert llama.wide_total(keys[layer, 1, 0]) == selected
    assert not keys[:, :, 1].any()
    assert not np.asarray(cache["ckv"])[:, :2].any() and not np.asarray(cache["ckv"])[:, 2, 24:].any()
    assert not np.asarray(cache["ckv"])[..., 32:].any()     # the row's padding stays zero
    _, cache = llama.decode_step_rowwise(
        weights(cfg), jnp.asarray([1, 2, 3], jnp.int32), cache,
        jnp.asarray([0, 0, 24], jnp.int32), cfg)
    keys = np.asarray(cache["dsa_keys"])
    assert llama.wide_total(keys[0, 0, 1]) == 1 + 1 + 25
    assert llama.wide_total(keys[0, 1, 1]) == 1 + 1 + TOPK
    # latent rows read: the chosen ones, or the blocks of 8 up to each ``pos``
    assert llama.wide_total(keys[0, 2, 1]) == (
        1 + 1 + TOPK if body == "gathered" else 8 + 8 + 32)
    # a run: the pairs its attention computed scores for (one causal group)
    assert [llama.wide_total(keys[layer, 2, 0]) for layer in range(3)] == [24 * 24] * 3


def test_the_no_cache_forward_refuses_a_latent_config():
    cfg = tiny()
    with pytest.raises(NotImplementedError):
        llama.forward(weights(cfg), jnp.zeros((1, 8), jnp.int32), cfg)
    with pytest.raises(NotImplementedError):
        llama.forward_cached(weights(cfg), jnp.zeros((2, 4), jnp.int32),
                             llama.init_cache(cfg, 2, 16), 0, cfg)


def glm5(**kw):
    """The benchmark's configuration ``glm-5-ep16-l6`` as ISSUE 30 cuts it."""
    d = dict(
        vocab_size=19360, num_layers=6, num_heads=64, num_kv_heads=64, embed_dim=6144,
        mlp_dim=12288, q_lora_rank=2048, kv_lora_rank=512, qk_nope_head_dim=192,
        qk_rope_head_dim=64, v_head_dim=256, index_n_heads=32, index_head_dim=128,
        index_topk=2048, first_dense_layers=1, num_experts=256, experts_per_token=8,
        expert_dim=2048, shared_expert_dim=2048, router_scoring="sigmoid",
        router_norm_topk=True, router_scale=2.5, experts_held=16,
    )
    d.update(kw)
    return llama.LlamaConfig(**d)


def test_num_params_and_flops_count_the_new_layers():
    cfg = glm5()
    attention = (6144 * 2048 + 2048 + 2048 * 64 * 256 + 6144 * 576 + 512
                 + 512 * 64 * 448 + 64 * 256 * 6144)
    indexer = 2048 * 32 * 128 + 6144 * 128 + 2 * 128 + 6144 * 32
    assert attention == pytest.approx(165.0e6, rel=0.002)
    assert indexer == pytest.approx(9.4e6, rel=0.01)
    norms = 2 * 6144
    dense = attention + indexer + norms + 3 * 6144 * 12288
    expert = (attention + indexer + norms + 6144 * 256 + 256
              + 17 * 3 * 6144 * 2048)                       # 16 held + the shared one
    assert dense == pytest.approx(400.9e6, rel=0.002)
    assert expert == pytest.approx(817.7e6, rel=0.002)
    total = dense + 5 * expert + 2 * 19360 * 6144 + 6144
    assert llama.num_params(cfg) == total
    assert total == pytest.approx(4.73e9, rel=0.002)
    # 6N + attention over the keys a query may see, not over the context;
    # the indexer over the context
    assert llama.flops_per_token(cfg, 8192) == pytest.approx(
        6.0 * (total - 19360 * 6144)
        + 6 * 6 * (64 * (256 + 256) * 2048 + 32 * 128 * 8192), rel=1e-9)
    # the configurations the benchmark had count as they did
    for old in (llama.LlamaConfig.tiny(),
                llama.LlamaConfig.tiny(num_experts=8, experts_per_token=2, expert_dim=32)):
        n = llama.num_params(old) - old.vocab_size * old.embed_dim
        assert llama.flops_per_token(old, 64) == 6.0 * n + 12 * old.num_layers * old.embed_dim * 64


@pytest.mark.parametrize("body", ["streamed"], indirect=True)
def test_whole_tiles_take_the_counted_selection_and_give_the_references_sets(
        monkeypatch, body):
    """A 128-token prompt (one block of 128 queries over 128 keys) and three
    decode steps in an 8-row cache of 256 keys: every selection of the model
    goes through the kernel, and logits and selected sets are the
    reference's."""
    rows, cache_len, length = 8, 256, 128
    assert topk_mask.implementation(length, length, TOPK) == "counted"
    assert topk_mask.implementation(rows, cache_len, TOPK) == "counted"
    traced = []
    real = topk_mask.counted_mask
    monkeypatch.setattr(topk_mask, "counted_mask", lambda scores, k: (
        traced.append(scores.shape), real(scores, k))[1])
    monkeypatch.setattr(topk_mask, "sorted_mask", None)     # never asked for
    cfg = tiny(max_seq_len=cache_len)
    params = weights(cfg)
    cache = llama.init_cache(cfg, rows, cache_len)
    seq = prompt(cfg, length, seed=5)
    logits, cache, prefill = llama.choices_cached(
        params, jnp.asarray([seq], jnp.int32), cache, jnp.int32(1), None, cfg)
    system, steps = [logits[0]], []
    for _ in range(3):
        seq.append(int(jnp.argmax(system[-1])))
        tokens, pos = np.zeros(rows, np.int32), np.zeros(rows, np.int32)
        tokens[1], pos[1] = seq[-1], len(seq) - 1
        logits, cache, chose = llama.choices_cached(
            params, jnp.asarray(tokens), cache, None, jnp.asarray(pos), cfg)
        system.append(logits[1])
        steps.append(chose)
    assert set(traced) == {(length, length), (rows, cache_len)}
    want, info = ref.forward(
        params, jnp.asarray(seq, jnp.int32), serve_dsa.spec_of(cfg),
        positions=list(range(length - 1, length + 3)), head_rows=64)
    err = errors(jnp.stack(system), want)
    assert err["rms"] < FLOAT32_TOLERANCE["rms"] and err["max"] < FLOAT32_TOLERANCE["max"]
    sets = np.asarray(info["selected"])                       # (L, S, S)
    assert np.array_equal(np.asarray(prefill["selected"])[:, 0], sets[:, :length, :length])
    for i, chose in enumerate(steps):
        t = length + i
        picked = np.asarray(chose["selected"])[:, :, 0]       # (L, rows, T)
        assert np.array_equal(picked[:, 1, :t + 1], sets[:, t, :t + 1])
        assert picked.sum(-1).tolist() == [[1, TOPK] + [1] * (rows - 2)] * cfg.num_layers


@both_bodies
def test_a_deployment_streams_the_references_greedy_tokens_and_reports_its_cache(body):
    """A ``LlamaDeployment`` on the tiny configuration: two concurrent
    requests through the engine get, token for token, the argmax of the
    reference's logits over their own context; ``stats()`` reports the
    cache by entry and the keys seen, selected and read."""
    from ray_tpu.serve.llm import LlamaDeployment
    from ray_tpu.util import metrics

    cfg = tiny()
    replica = LlamaDeployment.func_or_class(config=cfg, max_slots=3, max_len=48, seed=0)
    engine = replica.engine
    prompts = [prompt(cfg, 12, seed=1), prompt(cfg, 19, seed=2)]

    async def one(p):
        return [t async for t in engine.stream(p, max_new_tokens=9)]

    async def run():
        before = await replica.stats()
        got = await asyncio.gather(*(one(p) for p in prompts))
        return before, got, await replica.stats()

    before, got, stats = asyncio.run(run())
    for p, toks in zip(prompts, got):
        seq = p + toks
        want, _ = ref.forward(
            engine.params, jnp.asarray(seq, jnp.int32), serve_dsa.spec_of(cfg),
            positions=list(range(len(p) - 1, len(seq) - 1)), head_rows=64)
        want = np.asarray(want)
        for i, tok in enumerate(toks):   # the streamed token has the reference's top logit
            assert want[i, tok] >= want[i].max() - 1e-5, (i, tok, int(want[i].argmax()))
    assert stats["programs"]["decode_step_rowwise"] >= 1
    assert set(stats["cache_bytes"]) == {
        "ckv", "ik", "dsa_keys", "moe_expert_tokens", "moe_experts_touched", "moe_layer_steps"}
    assert stats["cache_bytes"]["ckv"] == 3 * 3 * 48 * 128 * 4
    assert stats["cache_bytes"]["ik"] == 3 * 3 * 48 * 16 * 4
    assert before["dsa_visible_run"] == before["dsa_selected_step"] == 0
    runs = sum(n * (n + 1) // 2 for n in (12, 19)) * cfg.num_layers
    assert stats["dsa_visible_run"] == runs
    assert stats["dsa_selected_run"] == cfg.num_layers * sum(
        min(TOPK, t + 1) for n in (12, 19) for t in range(n))
    assert stats["dsa_visible_step"] > stats["dsa_selected_step"] > 0
    assert before["dsa_read_step"] == before["dsa_read_run"] == 0
    # three rows are no whole sublane tile: the sorted selection
    assert before["topk_mask"] == stats["topk_mask"] == "sorted"
    # XLA's body at these lengths: one causal group, every (query, key) pair
    assert stats["dsa_read_run"] == cfg.num_layers * (12 * 12 + 19 * 19)
    if body == "gathered":      # the chosen rows and no other
        assert stats["dsa_read_step"] == stats["dsa_selected_step"]
    else:                       # whole blocks of 8: each (layer, row) reads 0..7 past ``pos``
        over = stats["dsa_read_step"] - stats["dsa_visible_step"]
        assert stats["dsa_read_step"] % 8 == 0
        assert 0 < over <= 7 * cfg.num_layers * stats["rows_stepped_total"]
    tokens = np.asarray(stats["moe_expert_tokens"])
    assert tokens.shape == (2, 4)
    gauges = {m["name"]: list(m["series"].values())[0]
              for m in metrics.registry_snapshot() if m["name"].startswith("llm_")}
    seen = stats["dsa_visible_run"] + stats["dsa_visible_step"]
    assert gauges["llm_dsa_selected_share"] == pytest.approx(
        (stats["dsa_selected_run"] + stats["dsa_selected_step"]) / seen)
    assert gauges["llm_moe_held_assignment_share"] == pytest.approx(
        tokens.sum() / (stats["rows_stepped_total"] * 2 * cfg.experts_per_token))


def test_a_kv_config_reports_its_cache_by_entry_and_no_dsa_keys():
    from ray_tpu.serve.llm import LlamaDeployment

    replica = LlamaDeployment.func_or_class(max_slots=2, max_len=32)
    stats = asyncio.run(replica.stats())
    cfg = replica.config
    one = cfg.num_layers * 2 * 32 * cfg.num_kv_heads * cfg.head_dim * 4
    assert stats["cache_bytes"] == {"k": one, "v": one}
    assert not any(k.startswith("dsa_") for k in stats)


def test_the_comparison_refuses_a_program_without_the_mechanism_or_the_precision():
    """What decides the cell's ``correct`` (``serve_dsa.system_run`` /
    ``against_reference``), at a small size: the honest program agrees on
    logits, sets and experts; with its selection switched off (every key
    attended to) the sets have the wrong size and the logits are far; with
    its weights cut to float8's three bits of mantissa the sets and the
    experts swap and the logits move a hundred thousand times further
    than float32 rounds.  And the reference with ITS selection off is not
    the program either."""
    cfg = tiny()
    params = weights(cfg)
    seq = prompt(cfg, 3 * TOPK, seed=5)

    def run(system_params, system_cfg):
        _, out = serve_dsa.system_run(
            system_params, system_cfg, llama.init_cache(cfg, 3, 40), 3, seq)
        return out, serve_dsa.against_reference(params, cfg, out)

    out, honest = run(params, cfg)
    assert honest["err"]["max"] < FLOAT32_TOLERANCE["max"]
    assert honest["sets_equal"] == honest["set_overlap"] == 1.0 and honest["set_size_ok"]
    assert honest["swap_rate"] == 0.0 and honest["swapped_margin_max"] == 0.0
    assert honest["twin_logits_differing"] == 0
    assert out["experts"].shape == (2, 3 * TOPK + 2, cfg.experts_per_token)
    free = serve_dsa.against_reference(params, cfg, out, given=False)
    assert free["err"]["max"] < FLOAT32_TOLERANCE["max"]     # same choices: same result
    _, everything = run(params, dataclasses.replace(cfg, index_topk=40))
    assert not everything["set_size_ok"] and everything["sets_equal"] == 0.0
    assert everything["err"]["rms"] > 1000 * honest["err"]["rms"]
    _, cut = run(serve_dsa.cut_mantissa(params), cfg)
    assert cut["set_size_ok"] and cut["sets_equal"] < 0.8 and cut["set_overlap"] < 0.99
    assert cut["swap_rate"] > 0.05
    assert cut["err"]["rms"] > 1e5 * honest["err"]["rms"]
    no_selection, _ = ref.forward(
        params, jnp.asarray(out["seq"], jnp.int32),
        serve_dsa.spec_of(cfg, attend_all=True),
        positions=list(range(3 * TOPK - 1, 3 * TOPK + 2)), head_rows=64)
    assert errors(out["logits"], no_selection)["rms"] > 1000 * honest["err"]["rms"]


def test_the_selection_bias_is_balanced_like_a_trained_ones():
    """``serve_dsa.balance_router`` moves each expert layer's selection
    bias against the experts' loads, as the training of a ``noaux_tc``
    router does, and nothing else: on prompts it has not seen the loads
    are more even than under the drawn bias."""
    cfg = tiny(first_dense_layers=0)
    drawn = weights(cfg, seed=3)
    length = 512

    def unevenness(params):
        cache, load = llama.init_cache(cfg, 1, length), 0
        for i in range(4):
            _, cache, chose = llama.choices_cached(
                params, jnp.asarray([prompt(cfg, length, seed=100 + i)], jnp.int32),
                cache, jnp.int32(0), None, cfg)
            experts = np.asarray(chose["experts"])[:, 0].reshape(cfg.num_layers, -1)
            load = load + np.stack([np.bincount(e, minlength=cfg.num_experts) for e in experts])
        return float(np.std(load / load.mean()))

    before = unevenness(drawn)
    balanced, _ = serve_dsa.balance_router(
        drawn, cfg, 7, llama.init_cache(cfg, 1, length), length)
    assert unevenness(balanced) < 0.3 * before
    same = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)), drawn, balanced)
    moved = same["blocks"].pop("router_bias")
    assert not moved and all(jax.tree.leaves(same))
    # from the seed: the same seed gives the same weights
    again, _ = serve_dsa.balance_router(
        drawn, cfg, 7, llama.init_cache(cfg, 1, length), length)
    assert jnp.array_equal(again["blocks"]["router_bias"], balanced["blocks"]["router_bias"])


def test_a_router_that_overturns_clear_calls_is_refused():
    """The logits are compared under the system's own expert choices and
    cannot see a router that chooses wrongly; the choices are compared for
    themselves.  A system whose selection bias favours ONE expert swaps
    few (layer, token) pairs — under any limit on their share that lets
    rounding's close calls through — but swaps them where the reference's
    margin was clear: ``swapped_margin_max`` refuses it."""
    cfg = tiny()
    params = weights(cfg)
    seq = prompt(cfg, 3 * TOPK, seed=5)
    faulty = dict(params, blocks=dict(
        params["blocks"],
        router_bias=params["blocks"]["router_bias"].at[:, cfg.expert_offset].add(0.02)))
    _, out = serve_dsa.system_run(faulty, cfg, llama.init_cache(cfg, 3, 40), 3, seq)
    got = serve_dsa.against_reference(params, cfg, out)
    assert got["err"]["max"] < FLOAT32_TOLERANCE["max"]      # the arithmetic is right
    assert 0.0 < got["swap_rate"] < 0.3 and got["twin_logits_differing"] == 0
    assert got["swapped_margin_max"] > 2 * got["margin_p50"] > 0.0
    limits = {"rms": 1, "max": 1, "set_overlap_min": 0, "sets_equal_min": 0, "swap_rate_max": 0.3}
    assert serve_dsa.passes(got, {**limits, "swapped_margin_max": 1.0})
    assert not serve_dsa.passes(got, {**limits, "swapped_margin_max": got["margin_p50"]})


@pytest.mark.parametrize("program", ["prefill_into_slot", "decode_step_rowwise"])
def test_the_logits_compared_are_the_served_programs(monkeypatch, program):
    """``system_run`` takes its logits from ``prefill_into_slot`` and
    ``decode_step_rowwise`` themselves — the executables the engine
    serves with — and the choices-returning program only lends the
    choices: a served program that computes something else moves the
    compared logits, and is told from the program the choices came from."""
    cfg = tiny()
    params = weights(cfg)
    seq = prompt(cfg, 3 * TOPK, seed=5)
    _, honest = serve_dsa.system_run(params, cfg, llama.init_cache(cfg, 3, 40), 3, seq)
    served = getattr(llama, program)

    def faulty(*args):
        logits, cache = served(*args)
        return logits + 0.5, cache

    monkeypatch.setattr(llama, program, faulty)
    _, out = serve_dsa.system_run(params, cfg, llama.init_cache(cfg, 3, 40), 3, seq)
    moved = np.asarray(out["logits"] - honest["logits"])
    first = program == "prefill_into_slot"
    # greedy tokens are unmoved by a constant, so the other calls agree
    assert out["seq"] == honest["seq"]
    assert np.allclose(moved[:1], 0.5 if first else 0.0)
    assert np.allclose(moved[1:], 0.0 if first else 0.5)
    assert out["twin_logits_differing"] == moved.astype(bool).sum() > 0
    got = serve_dsa.against_reference(params, cfg, out)
    assert got["err"]["max"] > 100 * FLOAT32_TOLERANCE["max"]
    assert not serve_dsa.passes(
        {**got, "err": {"rms": 0.0, "max": 0.0}},
        {"rms": 1, "max": 1, "set_overlap_min": 0, "sets_equal_min": 0, "swap_rate_max": 1,
         "swapped_margin_max": 1})
