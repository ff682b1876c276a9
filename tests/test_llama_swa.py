"""A decoder whose attention layers are of two KINDS — full attention, and a
sliding window with a learned sink, each with its own KV heads, rotary base
and cache — over held experts (MiMo-V2-Flash through ``LlamaConfig``), on the
CPU at toy widths with seeded float32 weights: the cached path (prefill, then
decode through the cache past two turns of the rolling slots, rows of
different lengths side by side) against the float32 reference's full forward;
each piece of the mathematics told apart by leaving it out of the reference;
the 16 chips' shares of an expert layer adding up to the layer; the cache
tree and its counters; the published preset; the kernels in interpret mode
against XLA's bodies; the engine's tokens and its refusals in words."""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.jobs.serve_swa import spec_of
from chipbench.reference import errors, within
from chipbench.reference import mimo_v2_flash as reference
from chipbench.reference.llama import FLOAT32_TOLERANCE
from ray_tpu.models import llama
from ray_tpu.models.llama import FULL, LINEAR, SLIDING, AttentionKind, LlamaConfig
from ray_tpu.ops import kv_decode_attention as kda
from ray_tpu.ops import kv_prefill_attention as kpa
from ray_tpu.serve.llm import LlamaDeployment, LLMEngine

SLOTS, MAX_LEN, WINDOW = 3, 64, 8
#: shorter than the window, longer than two of them, and in between
PROMPTS = (5, 20, 11)
#: past two turns of the 8 rolling slots
STEPS = 20


def with_sinks(params, cfg, seed=5):
    """Sinks near ln(window): a fifth to a half of a window query's mass."""
    sink = jnp.log(float(cfg.sliding.window)) - jax.random.uniform(
        jax.random.key(seed), params["swa_blocks"]["sink"].shape, maxval=1.4)
    return dict(params, swa_blocks=dict(params["swa_blocks"], sink=sink))


@pytest.fixture(scope="module")
def cfg():
    return LlamaConfig.tiny_swa()


@pytest.fixture(scope="module")
def params(cfg):
    return with_sinks(llama.init(jax.random.key(0), cfg), cfg)


@pytest.fixture(scope="module")
def served(cfg, params):
    """The three prompts prefilled into the three slots, then ``STEPS`` greedy
    steps of all rows at once: (per row its tokens, its logits (1 + STEPS, V),
    the cache)."""
    rng = np.random.default_rng(0)
    cache = llama.init_cache(cfg, SLOTS, MAX_LEN)
    seqs = [rng.integers(0, cfg.vocab_size, n).tolist() for n in PROMPTS]
    logits = []
    for r, seq in enumerate(seqs):
        out, cache = llama.prefill_into_slot(
            params, jnp.asarray([seq], jnp.int32), cache, jnp.int32(r), cfg)
        logits.append([out[0]])
    for _ in range(STEPS):
        for r, seq in enumerate(seqs):
            seq.append(int(jnp.argmax(logits[r][-1])))
        out, cache = llama.decode_step_rowwise(
            params, jnp.asarray([s[-1] for s in seqs], jnp.int32), cache,
            jnp.asarray([len(s) - 1 for s in seqs], jnp.int32), cfg)
        for r in range(SLOTS):
            logits[r].append(out[r])
    return seqs, [jnp.stack(row) for row in logits], cache


def against(params, cfg, served, **bent):
    """rms / max of the served rows' logits against the reference's full
    forward of the same tokens (every token of a row was fed: its logits are
    those at its last 1 + STEPS positions)."""
    seqs, logits, _ = served
    want = []
    for n, seq in zip(PROMPTS, seqs):
        hidden, _ = reference.forward(params, jnp.asarray(seq, jnp.int32), spec_of(cfg, **bent))
        want.append(reference.logits(params, hidden[n - 1:]))
    return errors(jnp.concatenate(logits), jnp.concatenate(want))


# ---- the cached path against the reference ----------------------------------

@pytest.mark.limit(170)
def test_prefill_and_decode_through_the_cache_are_the_references_forward(cfg, params, served):
    """Prompts shorter and longer than the window, rows of different lengths
    in one step, 20 steps: the 8 rolling slots turn more than twice."""
    seqs, logits, _ = served
    assert [len(s) for s in seqs] == [n + STEPS for n in PROMPTS]
    assert all(out.shape == (STEPS + 1, cfg.vocab_size) for out in logits)
    err = against(params, cfg, served)
    assert err["max"] < FLOAT32_TOLERANCE["max"] / 100, err


@pytest.mark.limit(170)
@pytest.mark.parametrize("piece, bent", [
    ("the softmax in bfloat16", dict(softmax_dtype="bfloat16")),
    ("no sink", dict(sink=False)),
    ("no 0.707 on the values", dict(value_scale=1.0)),
    ("the window layers' rotary base on the full layers", dict(rope_theta=1e4)),
    ("the full layers' rotary base on the window layers", dict(swa_rope_theta=5e6)),
    ("a window of 7", dict(window=WINDOW - 1)),
    ("a window of 9", dict(window=WINDOW + 1)),
    ("all 12 values of a head rotated", dict(rotary_dim=12)),
])
def test_each_piece_left_out_of_the_reference_is_told_apart(cfg, params, served, piece, bent):
    """The honest comparison passes float32's limits a hundred times over;
    with one piece bent the same comparison fails them."""
    err = against(params, cfg, served, **bent)
    assert not within(err, FLOAT32_TOLERANCE), (piece, err)
    assert err["max"] > 2 * FLOAT32_TOLERANCE["max"], (piece, err)


def test_the_cache_holds_a_pair_a_kind_and_counts_what_each_saw(cfg, served):
    _, _, cache = served
    full, window = cfg.kv_layers, cfg.sliding_layers
    assert (full, window) == (3, 4)
    # full layers: 1 KV head of 12 | 8 at every position; window layers: 2 KV
    # heads in 8 rolling slots a row, whatever max_len is
    assert cache["k"].shape == (full, SLOTS, MAX_LEN, 12)
    assert cache["v"].shape == (full, SLOTS, MAX_LEN, 8)
    assert cache["swa_k"].shape == (window, SLOTS, WINDOW, 2 * 12)
    assert cache["swa_v"].shape == (window, SLOTS, WINDOW, 2 * 8)
    assert set(cache) == {"k", "v", "swa_k", "swa_v", "attn_keys", "moe_expert_tokens",
                          "moe_experts_touched", "moe_layer_steps"}
    keys = np.asarray(cache["attn_keys"])   # (full|window, visible|read, run|step, 2)
    count = lambda kind, what, when: llama.wide_total(keys[kind, what, when])  # noqa: E731
    # the prefills: pairs inside the mask, and pairs the dense body scored
    assert count(0, 0, 0) == full * sum(n * (n + 1) // 2 for n in PROMPTS)
    band = lambda n: n * min(n, WINDOW) - min(n, WINDOW) * (min(n, WINDOW) - 1) // 2  # noqa: E731
    assert count(1, 0, 0) == window * sum(band(n) for n in PROMPTS)
    assert count(0, 1, 0) == full * sum(n * n for n in PROMPTS)
    assert count(1, 1, 0) == window * sum(n * n for n in PROMPTS)
    # the steps: a row at position p sees p + 1 keys, a window layer at most 8
    pos = [[n + i for n in PROMPTS] for i in range(STEPS)]
    assert count(0, 0, 1) == full * sum(p + 1 for step in pos for p in step)
    assert count(1, 0, 1) == window * sum(min(p + 1, WINDOW) for step in pos for p in step)
    # ... and XLA's body reads the slab whole: every slot of every row
    assert count(0, 1, 1) == full * STEPS * SLOTS * MAX_LEN
    assert count(1, 1, 1) == window * STEPS * SLOTS * WINDOW


def test_a_window_layers_slots_hold_the_rows_last_keys(cfg, params):
    """A prompt of 20 leaves positions 12-19 in slots 4-7 and 0-3; a step at
    position 20 overwrites slot 4 and nothing else."""
    cache = llama.init_cache(cfg, 2, 32)
    seq = np.random.default_rng(3).integers(0, cfg.vocab_size, 20)
    _, cache = llama.prefill_into_slot(
        params, jnp.asarray([seq], jnp.int32), cache, jnp.int32(1), cfg)
    before = np.asarray(cache["swa_k"])
    assert not before[:, 0].any() and before[:, 1].any(-1).all()
    _, cache = llama.decode_step_rowwise(
        params, jnp.asarray([0, 7], jnp.int32), cache, jnp.asarray([0, 20], jnp.int32), cfg)
    after = np.asarray(cache["swa_k"])
    changed = (after[:, 1] != before[:, 1]).any(-1)              # (window layers, slots)
    assert (changed == (np.arange(WINDOW) == 20 % WINDOW)).all()


# ---- one chip's share of the experts ----------------------------------------

def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    cfg = LlamaConfig.tiny_swa(num_experts=16, experts_per_token=4)
    whole = llama.init(jax.random.key(2), cfg)["blocks"]
    layer = {k: v[0] for k, v in whole.items() if k not in llama._EXPERT_TENSORS}
    h = jax.random.normal(jax.random.key(3), (2, 9, cfg.embed_dim))
    full = dict(layer, layer=0, **{k: whole[k] for k in llama._EXPERT_TENSORS})
    want, routing = llama._ffn(h, full, cfg)
    total, rows = 0.0, 0
    for chip in range(16):
        share = dataclasses.replace(cfg, experts_held=1, expert_offset=chip)
        mine = dict(layer, layer=0, **{
            k: whole[k][:, chip:chip + 1] for k in llama._EXPERT_TENSORS})
        part, got = llama._ffn(h, mine, share)
        assert (np.asarray(got["experts"]) == np.asarray(routing["experts"])).all()
        total, rows = total + part, rows + int(got["rows"].sum())
    assert rows == 2 * 9 * 4                                     # every routed pair, once
    assert float(jnp.abs(total - want).max()) < 1e-6


# ---- the published shape -----------------------------------------------------

def test_the_published_preset_holds_the_pattern_and_counts_to_the_parameter():
    c = LlamaConfig.mimo_v2_flash()
    full = [i for i, kind in enumerate(c.layer_types) if kind == FULL]
    assert full == [0, 5, 11, 17, 23, 29, 35, 41, 47] and c.sliding_layers == 39
    assert (c.num_heads, c.num_kv_heads, c.head_dim, c.value_dim, c.rotary_dim) == (
        64, 4, 192, 128, 64)
    assert c.sliding == AttentionKind(num_kv_heads=8, rope_theta=1e4, window=128, sink=True)
    assert (c.rope_theta, c.value_scale, c.first_dense_layers, c.expert_layers) == (
        5e6, 0.707, 1, 47)
    assert (c.num_experts, c.experts_per_token, c.expert_dim, c.mlp_dim) == (
        256, 8, 2048, 16384)
    experts = 47 * 256 * 25_165_824
    attention = 9 * 89_128_960 + 39 * 94_371_840
    small = 48 * 2 * 4096 + 4096 + 39 * 64 + 47 * 256             # norms, sinks, biases
    assert llama.num_params(c) == (experts + attention + 201_326_592 + 47 * 1_048_576
                                   + 2 * 152_576 * 4096 + small) == 308_778_780_864
    # the loop runs the first six layers once, then seven times (5 window, 1 full)
    segments = llama._segments(tuple((n, k) for n, _i, k, _c, _x in llama._layer_order(c)))
    assert [(len(period), repeats) for period, repeats in segments] == [(6, 1), (6, 7)]
    assert [k for _n, k in segments[1][0]] == [SLIDING] * 5 + [FULL]


def test_what_does_not_go_together_is_refused_by_name():
    with pytest.raises(ValueError, match="one goes with the other"):
        LlamaConfig.tiny(layer_types=(FULL, SLIDING), num_layers=2)
    with pytest.raises(NotImplementedError, match="no experts, leading dense blocks or window"):
        LlamaConfig.tiny_swa(layer_types=(FULL, LINEAR, SLIDING, FULL, LINEAR, SLIDING, FULL))
    with pytest.raises(NotImplementedError, match="written for the K/V path of a model with"):
        LlamaConfig.tiny(v_head_dim=8)
    cfg = LlamaConfig.tiny_swa()
    params = jax.eval_shape(lambda: llama.init(jax.random.key(0), cfg))
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="window layers beside full ones"):
        llama.forward(params, tokens, cfg)
    with pytest.raises(NotImplementedError, match="window layers beside full ones"):
        llama.loss_fn(params, {"tokens": tokens}, cfg)
    cache = jax.eval_shape(lambda: llama.init_cache(cfg, 1, 32))
    with pytest.raises(NotImplementedError, match="a rolling cache has already overwritten"):
        jax.eval_shape(lambda p, c: llama.forward_cached(p, tokens, c, 4, cfg), params, cache)
    with pytest.raises(NotImplementedError, match="mask_block with a sliding window"):
        llama._cache_mask(jnp.zeros((1, 4), jnp.int32), 16, window=8, block=4)


# ---- the kernels, interpreted, against XLA's bodies --------------------------

def prefill_operands(run, heads, kv_heads, sunk, seed):
    """One run's q, k (192-wide), v (128-wide) and, where ``sunk``, a sink a
    query head that holds a fair share of a query's mass."""
    k = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(k[0], (run, heads, 192))
    kk = jax.random.normal(k[1], (run, kv_heads, 192))
    v = jax.random.normal(k[2], (run, kv_heads, 128))
    return q, kk, v, (jax.random.normal(k[3], (heads,)) + 3.0 if sunk else None)


@pytest.fixture
def scored(monkeypatch):
    """(rows x keys) of every product of scores the interpreted kernel RUNS
    (a body under a ``pl.when`` that does not hold adds nothing)."""
    seen = []
    scores = kpa._scores

    def counted(q, k, scale):
        jax.debug.callback(lambda: seen.append(q.shape[0] * k.shape[0]))
        return scores(q, k, scale)

    monkeypatch.setattr(kpa, "_scores", counted)
    return seen


@pytest.mark.limit(170)
@pytest.mark.parametrize("window, sunk", [(0, False), (128, True), (100, True), (128, False)])
def test_the_prefill_kernel_is_the_dense_body(window, sunk):
    """Grouped queries (4 heads on 2), 192 | 128, a ragged run of 300."""
    q, kk, v, sink = prefill_operands(300, 4, 2, sunk, window + sunk)
    assert kpa.implementation(192, 128) == "flash" and kpa.implementation(12, 8) == "dense"
    got = kpa.attention(q, kk, v, window=window, sink=sink)
    want = kpa._dense(q, kk, v, window, sink)
    assert got.shape == (300, 4, 128)
    assert float(jnp.abs(got - want).max()) < 5e-6
    # a window: 3 sub-tiles of 128 queries, two blocks of 128 keys each but
    # the first; none: one tile of 512 queries against one of 1,024 keys
    pairs = (2 * 3 - 1) * 128 * 128 if window else 512 * 1024
    assert kpa.pairs_computed(300, 192, 128, window) == pairs
    assert kpa.pairs_computed(300, 12, 8, window) == 300 * 300


@pytest.mark.limit(60)
@pytest.mark.parametrize("heads, kv_heads", [(4, 2), (8, 1)])
@pytest.mark.parametrize("run, tile", [(90, 512), (256, 128), (300, 256)])
@pytest.mark.parametrize("window, sunk", [(128, True), (100, False), (1, True), (300, False)])
def test_a_window_layers_band_in_one_step_is_the_dense_body(
        monkeypatch, scored, window, sunk, run, tile, heads, kv_heads):
    """A run under one block, one of whole tiles and a ragged one whose last
    step holds a sub-tile without a token; a step's queries one sub-tile (the
    blocks before it another step's), two, and the whole run; windows of a
    block, under one, of one key and of three blocks; every head of a KV
    group stacked in a step's rows."""
    monkeypatch.setattr(kpa, "WINDOW_TILE", tile)
    q, kk, v, sink = prefill_operands(run, heads, kv_heads, sunk, window + run + heads)
    got = kpa.attention(q, kk, v, window=window, sink=sink)
    jax.effects_barrier()
    assert got.shape == (run, heads, 128)
    assert float(jnp.abs(got - kpa._dense(q, kk, v, window, sink)).max()) < 5e-6
    # a sub-tile that holds a token meets its own block and the blocks of
    # its band before it that there are: a block for a window of 1
    blocks, back = -(-run // 128), -(-(window - 1) // 128)
    pairs = sum(min(i, back) + 1 for i in range(blocks)) * 128 * 128
    assert kpa.pairs_computed(run, 192, 128, window) == pairs
    assert sum(scored) == pairs * heads
    assert kpa.tiles(heads // kv_heads, window, run) == (
        min(tile, blocks * 128), (back + 1) * 128, heads // kv_heads)


@pytest.mark.limit(60)
@pytest.mark.parametrize("heads, kv_heads, sunk", [(4, 2, False), (8, 1, True), (4, 2, True)])
def test_a_full_layers_tiles_carry_the_softmax_up_to_the_diagonal(
        monkeypatch, scored, heads, kv_heads, sunk):
    """Tiles of 128 queries against 256 keys over a run of 600: a tile
    wholly under the diagonal, one the diagonal crosses in its first half,
    one in its second, and a padded tail of queries and of keys."""
    monkeypatch.setattr(kpa, "TILE", 128)
    monkeypatch.setattr(kpa, "TILE_KEYS", 256)
    q, kk, v, sink = prefill_operands(600, heads, kv_heads, sunk, heads + sunk)
    got = kpa.attention(q, kk, v, sink=sink)
    jax.effects_barrier()
    assert float(jnp.abs(got - kpa._dense(q, kk, v, 0, sink)).max()) < 5e-6
    pairs = sum(i // 2 + 1 for i in range(5)) * 128 * 256
    assert kpa.pairs_computed(600, 192, 128) == pairs
    assert sum(scored) == pairs * heads
    assert kpa.tiles(heads // kv_heads) == (128, 256, 2)


@pytest.mark.limit(170)
@pytest.mark.parametrize("cache_len, sunk", [(256, False), (128, True)])
def test_the_decode_kernel_is_the_slab_body(cache_len, sunk):
    """Key heads that start off a lane tile's edge (2 x 192) over 128-wide
    values; rows at their first key, their last, and in between; a window
    layer's one block of slots with its sink."""
    R, H, KV, D, Dv = 3, 4, 2, 192, 128
    k = jax.random.split(jax.random.key(cache_len), 4)
    cache_k = jax.random.normal(k[0], (2, R, cache_len, KV * D))
    cache_v = jax.random.normal(k[1], (2, R, cache_len, KV * Dv))
    q = jax.random.normal(k[2], (R, 1, H, D))
    sink = jax.random.normal(k[3], (H,)) + 3.0 if sunk else None
    visible = jnp.asarray([[0], [cache_len - 1], [77]], jnp.int32)
    assert kda.implementation(cache_len, D, kv_heads=KV, v_head_dim=Dv) == "streamed"
    assert kda.implementation(cache_len, D) == "slab"            # the model-wide rule
    assert kda.implementation(64, D, kv_heads=KV, v_head_dim=Dv) == "slab"
    got = kda.kv_decode_attention(q, cache_k, cache_v, jnp.int32(1), visible, sink=sink)
    mask = jnp.arange(cache_len) <= visible[:, :, None]
    want = llama._grouped_attention(
        q, cache_k[1], cache_v[1], mask, LlamaConfig.tiny(), KV, sink)
    assert got.shape == (R, 1, H, Dv)
    assert float(jnp.abs(got - want).max()) < 5e-6


# ---- the engine ---------------------------------------------------------------

REQUESTS = [(9, 12), (21, 5), (13, 19), (7, 1), (30, 8), (5, 0), (16, 11)]


def prompt(n, seed):
    return np.random.default_rng([seed, n]).integers(0, 256, n).tolist()


@pytest.fixture(scope="module")
def replica(cfg, params):
    return LlamaDeployment.func_or_class(
        config=cfg, weights_loader=lambda: params, max_slots=SLOTS, max_len=MAX_LEN)


@pytest.fixture(scope="module")
def streamed(replica):
    engine = replica.engine

    async def one(i):
        n, budget = REQUESTS[i]
        return [t async for t in engine.stream(prompt(n, i), budget)]

    async def run():
        got = await asyncio.gather(*(one(i) for i in range(len(REQUESTS))))
        return got, await replica.stats()

    return asyncio.run(run())


@pytest.mark.limit(170)
@pytest.mark.parametrize("i", range(len(REQUESTS)))
def test_a_request_gets_its_own_rows_tokens_whatever_its_slot_held(replica, streamed, i):
    """Seven requests through three slots: a slot's window slots still hold
    its last request's keys when the next one is prefilled."""
    n, budget = REQUESTS[i]
    want = llama.generate_kv(replica.engine.params, jnp.asarray([prompt(n, i)], jnp.int32),
                             replica.config, max_new_tokens=budget)
    assert streamed[0][i] == np.asarray(want[0, n:]).tolist()
    assert len(streamed[0][i]) == budget


def test_stats_carry_the_kinds_counters(replica, streamed, cfg):
    _, stats = streamed
    steps = stats["decode_steps_total"]
    assert steps > 0 and "kv_keys_read_step" not in stats
    # XLA's slab at toy widths: every slot of every row, a layer and step
    assert stats["full_keys_read_step"] == steps * SLOTS * MAX_LEN * cfg.kv_layers
    assert stats["swa_keys_read_step"] == steps * SLOTS * WINDOW * cfg.sliding_layers
    assert 0 < stats["full_keys_visible_step"] < stats["full_keys_read_step"]
    assert 0 < stats["swa_keys_visible_step"] <= stats["swa_keys_read_step"]
    admitted = [n for n, budget in REQUESTS if budget > 0]
    assert stats["full_pairs_visible_run"] == cfg.kv_layers * sum(
        n * (n + 1) // 2 for n in admitted)
    assert stats["swa_pairs_read_run"] == cfg.sliding_layers * sum(n * n for n in admitted)
    assert stats["swa_pairs_visible_run"] < stats["full_pairs_visible_run"]
    assert stats["kv_prefill_attention"] == "dense"
    # what the kernel would take at these heads: 4 on 1 a tile pair of two, 4
    # on 2 both of a group against a band of two blocks
    assert stats["kv_prefill_tiles"] == {"full": (512, 1024, 2), "swa": (512, 256, 2)}
    assert stats["kv_decode_attention"] == {"full": "slab", "swa": "slab"}
    assert stats["cache_bytes"]["swa_k"] == cfg.sliding_layers * SLOTS * WINDOW * 2 * 12 * 4
    assert stats["cache_bytes"]["k"] == cfg.kv_layers * SLOTS * MAX_LEN * 12 * 4


@pytest.mark.parametrize("kw, why", [
    (dict(speculative_tokens=1),
     "speculative_tokens does not go with .*window layers.*8 rolling slots.*rejected draft"),
    (dict(diffusion_block=4),
     "diffusion_block does not go with .*window layers.*block mask with a sliding window"),
])
def test_the_engine_refuses_what_a_rolling_cache_cannot_take_back(cfg, params, kw, why):
    with pytest.raises(ValueError, match=why):
        LLMEngine(params, cfg, max_slots=2, max_len=32, **kw)
