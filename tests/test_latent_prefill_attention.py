"""A latent config's prefill attention (``ops/latent_prefill_attention.py``):
the Pallas kernel, in interpret mode, against XLA's blocked body; which body
a run takes (a key head of any width: zeros behind it up to whole lane
tiles); and ``models/llama.py``'s run through either — the same selection,
the same logits with and without ``collect``, the counters, the body's name
in ``stats()``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.ops import latent_prefill_attention as lpa

TOPK = 8
_RUN_PROGRAMS = (llama.choices_cached, llama.prefill_into_slot)


def xla_body(q, k, v, mask, scale):
    """``_latent_attention``'s blocked body over one block of all queries."""
    att = jnp.einsum("qhd,shd->hqs", q, k, preferred_element_type=jnp.float32) * scale
    att = jnp.where(mask[None], att, -1e30)
    probs = jax.nn.softmax(att, axis=-1).astype(v.dtype)
    return jnp.einsum("hqs,shv->qhv", probs, v)


def selection(run_len: int, tile: int, seed: int):
    """(Sq, Sq) bool as ``_select_mask`` makes it, exactly ``TOPK`` keys a
    query that sees more, from scores with TIES at the k-th value (drawn
    from four levels), and the last query of the second tile given scores
    that put all its keys in its own tile: none inside the first."""
    t = np.arange(run_len)
    scores = np.random.default_rng(seed).integers(0, 4, (run_len, run_len)).astype(np.float32)
    lone = 2 * tile - 1
    scores[lone, :tile] = -1.0
    scores = jnp.where(t[None, :] <= t[:, None], jnp.asarray(scores), -jnp.inf)
    hit = np.asarray(llama._select_mask(scores, TOPK))
    assert not hit[lone, :tile].any() and hit[lone].sum() == TOPK
    assert (hit.sum(-1) == np.minimum(t + 1, TOPK)).all()
    return hit


@pytest.fixture
def tile(monkeypatch):
    """Tiles of 128 in the kernel's own tests (the chip's are 512)."""
    monkeypatch.setattr(lpa, "TILE", 128)
    return 128


@pytest.mark.parametrize("tiles", [2, 3])
@pytest.mark.parametrize("masked", [True, False], ids=["selection", "causal"])
@pytest.mark.parametrize("heads", [2, 3, 5], ids=["two_a_step", "three_a_step", "one_a_step"])
@pytest.mark.parametrize("Dqk", [256, 192], ids=["keys256", "keys192"])
def test_the_kernel_is_xlas_body(tile, tiles, masked, heads, Dqk):
    """Tiles of 128 at 2 and 3 a side (3 and 6 live pairs), heads of 256 |
    128 and of 192 | 128 (a key head of no whole lane tiles: zeros behind it
    up to 256), as many a grid step as divide the heads and make at most 512
    value lanes (of 128: up to four): with the selection as the mask operand,
    and causal without one."""
    Dv = 128
    Sq = tiles * tile
    ks = jax.random.split(jax.random.key(tiles), 3)
    q = jax.random.normal(ks[0], (Sq, heads, Dqk), jnp.float32)
    k = jax.random.normal(ks[1], (Sq, heads, Dqk), jnp.float32)
    v = jax.random.normal(ks[2], (Sq, heads, Dv), jnp.float32)
    hit = selection(Sq, tile, seed=tiles) if masked else np.tril(np.ones((Sq, Sq), bool))
    got = lpa.latent_prefill_attention(
        q, k, v, jnp.asarray(hit, jnp.int8) if masked else None, scale=0.0625)
    want = xla_body(q, k, v, jnp.asarray(hit), 0.0625)
    assert got.shape == (Sq, heads, Dv) and got.dtype == v.dtype
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)


def test_the_kernel_casts_probabilities_to_the_values_dtype(tile):
    """bfloat16 in, bfloat16 out, within a bfloat16 step of XLA's body."""
    Sq = 2 * tile
    ks = jax.random.split(jax.random.key(7), 3)
    q, k, v = (jax.random.normal(kk, (Sq, 2, 128), jnp.bfloat16) for kk in ks)
    hit = selection(Sq, tile, seed=7)
    got = lpa.latent_prefill_attention(q, k, v, jnp.asarray(hit, jnp.int8), scale=0.09)
    want = xla_body(q, k, v, jnp.asarray(hit), 0.09)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        got.astype(np.float32), want.astype(np.float32), rtol=0, atol=0.03)


def test_the_kernel_refuses_what_implementation_would_not_send(tile):
    x = jnp.zeros((256, 2, 128), jnp.float32)
    with pytest.raises(ValueError, match="whole tiles"):
        lpa.latent_prefill_attention(x[:192], x[:192], x[:192], scale=1.0)
    with pytest.raises(ValueError, match="128-lane"):     # a 64-wide value head
        lpa.latent_prefill_attention(x, x, x[..., :64], scale=1.0)
    with pytest.raises(ValueError, match="128-lane"):     # keys of another width than queries
        lpa.latent_prefill_attention(x[..., :96], x, x, scale=1.0)
    with pytest.raises(ValueError, match="mask"):
        lpa.latent_prefill_attention(x, x, x, jnp.ones((256, 128), jnp.int8), scale=1.0)


@pytest.mark.parametrize("run_len,qk,v,want", [
    (2560, 256, 256, "flash"),      # the reference comparison's prompt (GLM-5)
    (4096, 256, 256, "flash"),      # the cell's two prompt lengths
    (8192, 256, 256, "flash"),
    (2048, 256, 256, "flash"),      # four tiles: the shortest run it takes
    (2048, 192, 128, "flash"),      # LongCat's two prompt lengths: a key head
    (4096, 192, 128, "flash"),      # of 192 goes padded to 256
    (512, 128, 128, "blocked"),     # one tile: under four XLA's body is faster
    (512, 192, 128, "blocked"),     # JoyAI's two prompt lengths
    (1536, 192, 128, "blocked"),
    (4000, 192, 128, "blocked"),
    (4000, 256, 256, "blocked"),    # a ragged length
    (24, 20, 16, "blocked"),        # tier-1's tiny runs
    (256, 256, 256, "blocked"),     # less than a tile
    (4096, 256, 64, "blocked"),     # a value head of half a lane tile
])
def test_the_body_goes_by_the_runs_shape(run_len, qk, v, want):
    assert lpa.implementation(run_len, qk, v) == want


@pytest.mark.parametrize("qk,behind", [(192, 64), (256, 0), (128, 0), (96, 32), (20, 108)])
def test_zeros_fill_a_key_head_up_to_whole_lane_tiles(qk, behind):
    assert lpa.lanes_behind(qk) == behind


@pytest.mark.parametrize("run_len,tiles", [(512, 1), (2560, 15), (4096, 36), (8192, 136)])
def test_pairs_computed_are_the_live_tiles(run_len, tiles):
    assert lpa.pairs_computed(run_len) == tiles * 512 * 512
    qt, kt = lpa._live_pairs(run_len)
    assert (kt <= qt).all() and len(set(zip(qt.tolist(), kt.tolist()))) == tiles
    # each query tile's pairs in a run, keys ascending from 0
    assert (np.diff(qt) >= 0).all() and (kt[np.r_[0, 1 + np.flatnonzero(np.diff(qt))]] == 0).all()


def wide(**kw):
    """``test_llama_mla_dsa.tiny`` with two heads of whole lane tiles (nope
    96 | rope 32 = 128, values 128): 3 layers (1 dense + 2 expert), latent
    24, indexer 4 heads x 16 picking 8 keys."""
    d = dict(
        vocab_size=128, max_seq_len=512, num_layers=3, num_heads=2, num_kv_heads=2,
        embed_dim=64, mlp_dim=96, dtype=jnp.float32, remat=False, rope_theta=1e4,
        q_lora_rank=32, kv_lora_rank=24, qk_nope_head_dim=96, qk_rope_head_dim=32,
        v_head_dim=128, index_n_heads=4, index_head_dim=16, index_topk=TOPK,
        first_dense_layers=1, num_experts=16, experts_per_token=4, expert_dim=32,
        shared_expert_dim=32, router_scoring="sigmoid", router_norm_topk=True,
        router_scale=2.5, experts_held=4, expert_offset=4,
    )
    d.update(kw)
    return llama.LlamaConfig(**d)


@pytest.fixture
def body(request, monkeypatch):
    """``"flash"``: tiles of 32, so a run of 256 is eight a side;
    ``"blocked"``: no length is a whole number of tiles.  The tile is read
    when a program is traced, so what was traced under another one is
    forgotten, before and after."""
    monkeypatch.setattr(lpa, "TILE", 32 if request.param == "flash" else 1 << 20)
    monkeypatch.setattr(llama, "_QUERY_BLOCK", 16)
    for program in _RUN_PROGRAMS:
        program.clear_cache()
    yield request.param
    for program in _RUN_PROGRAMS:
        program.clear_cache()


both_bodies = pytest.mark.parametrize("body", ["flash", "blocked"], indirect=True)
RUN = 256


def run_of(cfg, collect: bool):
    params = llama.init(jax.random.key(3), cfg)
    tokens = jnp.asarray(
        [np.random.default_rng(5).integers(0, cfg.vocab_size, RUN)], jnp.int32)
    cache = llama.init_cache(cfg, 2, RUN)
    if collect:
        return llama.choices_cached(params, tokens, cache, jnp.int32(1), None, cfg)
    return llama.prefill_into_slot(params, tokens, cache, jnp.int32(1), cfg)


@both_bodies
def test_a_run_with_its_choices_kept_is_the_served_run_bit_for_bit(body):
    """``choices_cached`` takes the body ``prefill_into_slot`` takes: equal
    logits and cache bit for bit, and ``selected`` is exactly ``index_topk``
    keys a query that sees more, inside the causal triangle."""
    cfg = wide()
    assert lpa.implementation(RUN, 128, cfg.v_head_dim) == body
    logits, cache = run_of(cfg, collect=False)
    twin, twin_cache, chose = run_of(cfg, collect=True)
    assert np.array_equal(np.asarray(logits), np.asarray(twin))
    for name in cache:
        assert np.array_equal(np.asarray(cache[name]), np.asarray(twin_cache[name])), name
    hit = np.asarray(chose["selected"])
    assert hit.shape == (cfg.num_layers, 1, RUN, RUN) and hit.dtype == bool
    assert not np.triu(hit[:, 0], 1).any()
    assert (hit[:, 0].sum(-1) == np.minimum(np.arange(RUN) + 1, TOPK)).all()


NO_INDEXER = dict(index_topk=0, index_n_heads=0, index_head_dim=0)


@pytest.mark.parametrize("how", [
    {}, dict(qk_nope_head_dim=64), dict(qk_nope_head_dim=64, **NO_INDEXER),
], ids=["keys128", "keys96", "keys96_causal"])
def test_both_bodies_select_the_same_keys_and_give_the_same_logits(how, monkeypatch):
    """Also for a key head of nope 64 | rope 32 = 96 over values of 128
    (LongCat's and JoyAI's 128 | 64 = 192 at toy width): the kernel on heads
    padded to 128 lanes, XLA's body on the 96 as they are."""
    cfg = wide(**how)
    outs = {}
    for which, tile in (("flash", 64), ("blocked", 1 << 20)):
        monkeypatch.setattr(lpa, "TILE", tile)
        for program in _RUN_PROGRAMS:
            program.clear_cache()
        assert lpa.implementation(
            RUN, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, cfg.v_head_dim) == which
        logits, cache, chose = run_of(cfg, collect=True)
        outs[which] = (np.asarray(logits), np.asarray(cache["ckv"]), np.asarray(chose["selected"]))
    for program in _RUN_PROGRAMS:
        program.clear_cache()
    assert np.array_equal(outs["flash"][2], outs["blocked"][2])
    np.testing.assert_allclose(outs["flash"][0], outs["blocked"][0], rtol=0, atol=2e-5)
    np.testing.assert_allclose(outs["flash"][1], outs["blocked"][1], rtol=0, atol=2e-5)


@both_bodies
def test_a_run_counts_keys_visible_selected_and_read(body):
    """``dsa_keys``' run slot: visible and selected as before this kernel,
    read = the pairs the body computed scores for — whole live tiles (36 of
    32 x 32 at eight a side) under the kernel, the four causal groups'
    rectangles (64 queries x 64 / 128 / 192 / 256 keys) in XLA's body."""
    cfg = wide()
    _, cache = run_of(cfg, collect=False)
    keys = np.asarray(cache["dsa_keys"])
    read = 36 * 32 * 32 if body == "flash" else 64 * (64 + 128 + 192 + 256)
    for layer in range(cfg.num_layers):
        assert llama.wide_total(keys[layer, 0, 0]) == RUN * (RUN + 1) // 2
        assert llama.wide_total(keys[layer, 1, 0]) == sum(min(TOPK, t + 1) for t in range(RUN))
        assert llama.wide_total(keys[layer, 2, 0]) == read
    assert not keys[:, :, 1].any()                          # no step was taken


@both_bodies
@pytest.mark.parametrize("nope", [96, 64], ids=["keys128", "keys96"])
def test_a_run_without_an_indexer_attends_causally(body, nope, monkeypatch):
    """``index_topk`` unset: the kernel is given NO mask operand (an indexer's
    run hands it the (Sq, Sq) int8 selection), and ``collect`` hands back the
    triangle with the served program's logits.  A key head of nope 64 | rope
    32 = 96 (LongCat's and JoyAI's 128 | 64 = 192 at toy width) reaches the
    kernel as 128 lanes, the zeros written by the model."""
    masks, widths, scales = [], [], []
    kernel = lpa.latent_prefill_attention

    def spy(q, k, v, mask=None, **kw):
        masks.append(mask if mask is None else (mask.shape, mask.dtype))
        widths.append((q.shape[-1], k.shape[-1], v.shape[-1]))
        scales.append(kw["scale"])
        return kernel(q, k, v, mask, **kw)

    monkeypatch.setattr(lpa, "latent_prefill_attention", spy)
    cfg = wide(qk_nope_head_dim=nope, **NO_INDEXER)
    logits, cache = run_of(cfg, collect=False)
    twin, _, chose = run_of(cfg, collect=True)
    assert np.array_equal(np.asarray(logits), np.asarray(twin))
    assert np.array_equal(
        np.asarray(chose["selected"])[:, 0],
        np.broadcast_to(np.tril(np.ones((RUN, RUN), bool)), (3, RUN, RUN)))
    assert "dsa_keys" not in cache
    # traced once a parameter stack (dense blocks, expert blocks) a program
    assert masks == ([None] * 4 if body == "flash" else [])
    assert widths == [(128, 128, 128)] * len(masks)
    assert scales == [pytest.approx((nope + 32) ** -0.5)] * len(masks)   # the head's as published
    run_of(wide(), collect=False)
    assert masks[4:] == ([((RUN, RUN), jnp.int8)] * 2 if body == "flash" else [])


@pytest.mark.parametrize("how,want", [
    (dict(NO_INDEXER, qk_nope_head_dim=64), "flash"),   # keys of 96: padded to 128
    (NO_INDEXER, "flash"),                              # keys of 128
    ({}, "flash"),                                      # an indexer's config says it too
    (dict(NO_INDEXER, v_head_dim=64), "blocked"),       # a value head of half a lane tile
], ids=["keys96", "keys128", "indexer", "values64"])
def test_stats_name_the_body_a_whole_tile_run_takes(how, want):
    """``stats()["latent_prefill_attention"]`` of every latent config: what
    ``implementation`` says for the shortest run the kernel takes at the
    config's head widths."""
    import asyncio

    from ray_tpu.serve.llm import LlamaDeployment

    cfg = wide(max_seq_len=64, **how)
    params = llama.init(jax.random.key(3), cfg)
    dep = LlamaDeployment.func_or_class(
        config=cfg, weights_loader=lambda: params, max_slots=2, max_len=32)
    stats = asyncio.run(dep.stats())
    assert stats["latent_prefill_attention"] == want == lpa.implementation(
        lpa.MIN_TILES * lpa.TILE, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim,
        cfg.v_head_dim)
