"""JoyAI-LLM-Flash's block through ``models/llama.py`` and its
multi-token-prediction module drafting through ``models/mtp.py``, at small
sizes on the CPU, seeded weights: latent attention over every visible key
(no indexer), a verify run of two tokens a row, the module's forward, the
acceptance rule and what a rejected draft leaves behind — against the plain
float32 reference ``chipbench/reference/joyai_mtp.py`` (on the chip
``chipbench/jobs/serve_mtp.py`` makes the same comparison at the published
widths, with the same functions)."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.jobs import serve_mtp
from chipbench.reference import joyai_mtp as ref
from chipbench.reference.llama import FLOAT32_TOLERANCE
from ray_tpu.models import llama, mtp
from ray_tpu.ops import latent_decode_attention
from ray_tpu.serve.llm import LLMEngine

_PROGRAMS = (mtp.decode_step_rowwise, mtp.prefill_into_slot,
             llama.decode_step_rowwise)


@pytest.fixture
def body(request, monkeypatch):
    """``"dense"``: the step as these tiny caches trace it (plain XLA over
    the slab); ``"streamed"``: the kernel in blocks of 8 keys.  The block
    size is read when a program is traced, so what was traced under
    another one is forgotten, before and after."""
    if request.param == "streamed":
        monkeypatch.setattr(latent_decode_attention, "BLOCK_KEYS", 8)
    for program in _PROGRAMS:
        program.clear_cache()
    yield request.param
    for program in _PROGRAMS:
        program.clear_cache()


both_bodies = pytest.mark.parametrize("body", ["dense", "streamed"], indirect=True)


def tiny(**kw):
    """3 layers (1 dense + 2 expert) and the module, 4 heads of nope 12 |
    rope 8 | v 16, latent 24, query latent 32, no indexer, 16 experts of
    which 4 are held (from 4), top-4, a shared expert."""
    d = dict(
        vocab_size=128, max_seq_len=128, num_layers=3, num_heads=4, num_kv_heads=4,
        embed_dim=64, mlp_dim=96, dtype=jnp.float32, remat=False, rope_theta=1e4,
        q_lora_rank=32, kv_lora_rank=24, qk_nope_head_dim=12, qk_rope_head_dim=8,
        v_head_dim=16, first_dense_layers=1, num_experts=16, experts_per_token=4,
        expert_dim=32, shared_expert_dim=32, router_scoring="sigmoid",
        router_norm_topk=True, router_scale=2.5, experts_held=4, expert_offset=4,
        mtp_layers=1,
    )
    d.update(kw)
    return llama.LlamaConfig(**d)


def weights(cfg, seed=0, sharp=10.0):
    """Seeded weights with the norms' scales away from 1 (at 1 a missing
    norm would still pass) and the output head ``sharp`` times as large:
    at N(0, 0.02) and 64 wide both heads give near-uniform distributions
    and every draft is accepted; sharpened, the two disagree and both
    outcomes occur."""
    params = llama.init(jax.random.key(seed), cfg)

    def off(tree, names):
        for i, name in enumerate(names):
            noise = 0.3 * jax.random.normal(jax.random.key(i + 1), tree[name].shape)
            tree[name] = (tree[name] + noise).astype(tree[name].dtype)

    for stack in (params["dense_blocks"], params["blocks"], params["mtp"]["block"]):
        off(stack, ("q_a_norm", "kv_a_norm"))
    off(params["mtp"], ("enorm", "hnorm", "head_norm"))
    params["lm_head"] = params["lm_head"] * sharp
    return params


def engine(cfg, params, **kw):
    kw = dict(dict(max_slots=4, max_len=48, speculative_tokens=1, temperature=1.0,
                   seed=5), **kw)
    return LLMEngine(params, cfg, **kw)


def compared(cfg, params, lens=(9, 17), steps=8, **kw):
    eng = engine(cfg, params, **kw)
    out = serve_mtp.system_run(eng, 3, list(lens), steps)
    return eng, out, serve_mtp.against_reference(
        params, cfg, eng._key, eng.temperature, out)


# ---- (1) the main model and (2) the module, through the cache ----------------

@both_bodies
def test_prefill_then_speculative_steps_through_the_cache_are_the_reference(body):
    """Both verify positions of accepted drafts, the first of rejected
    ones and the positions reached after a rejection, against the
    reference's full forward over what was finally emitted; the module's
    draft logits against the reference's module forward."""
    cfg = tiny()
    eng, out, got = compared(cfg, weights(cfg))
    assert got["accepted"] >= 3 and got["rejected"] >= 3, got
    # float32 both sides: the bound of the other float32 comparisons
    assert got["err"]["rms"] <= FLOAT32_TOLERANCE["rms"], got
    assert got["err"]["max"] <= FLOAT32_TOLERANCE["max"] * 4, got
    assert got["draft_err"]["rms"] <= FLOAT32_TOLERANCE["rms"], got
    assert got["draft_err"]["max"] <= FLOAT32_TOLERANCE["max"] * 4, got
    assert got["swap_rate"] == 0.0
    # a position behind a rejected draft was verified again, a step later
    row = out["rows"][0]
    after_rejection = [b["pos"] for a, b in zip(row["steps"], row["steps"][1:])
                       if not a["accepted"]]
    assert after_rejection and all(
        b["pos"] == a["pos"] + 1 + a["accepted"]
        for a, b in zip(row["steps"], row["steps"][1:]))
    assert serve_mtp.passes(got, dict(
        FLOAT32_TOLERANCE, draft_rms=1e-4, draft_max=1e-3, swap_rate_max=0.0,
        swapped_margin_max=0.0, max=1e-3))


def test_the_comparison_refuses_a_wrong_module_or_a_lower_precision():
    cfg = tiny()
    params = weights(cfg)
    tol = dict(rms=1e-4, max=1e-3, draft_rms=1e-4, draft_max=1e-3,
               swap_rate_max=0.3, swapped_margin_max=0.05)
    assert serve_mtp.passes(compared(cfg, params)[2], tol)
    # the hidden state and the embedding swapped inside the concatenation
    E = cfg.embed_dim
    swapped = dict(params, mtp=dict(params["mtp"], eh_proj=jnp.concatenate(
        [params["mtp"]["eh_proj"][E:], params["mtp"]["eh_proj"][:E]])))
    eng = engine(cfg, swapped)
    out = serve_mtp.system_run(eng, 3, [9, 17], 8)
    got = serve_mtp.against_reference(params, cfg, eng._key, 1.0, out)
    assert got["draft_err"]["max"] > 0.1 and got["err"]["max"] < 1e-3
    assert not serve_mtp.passes(got, tol)
    # bfloat16 weights, activations and cache against these limits
    low = tiny(dtype=jnp.bfloat16)
    got = compared(low, jax.tree.map(lambda a: a.astype(jnp.bfloat16), params))[2]
    assert got["err"]["rms"] > 1e-3 and not serve_mtp.passes(got, tol)


@both_bodies
def test_greedy_drafting_gives_the_tokens_of_greedy_decoding(body):
    """Temperature 0: the draft is accepted where it is the main model's
    argmax, so drafting on and off emit the same ids."""
    cfg = tiny()
    params = weights(cfg, sharp=1.0)
    prompts = [np.random.default_rng(i).integers(0, 128, 5 + i).tolist() for i in range(4)]
    want = np.asarray(llama.generate_kv(
        params, jnp.asarray([p[:5] for p in prompts]), cfg, max_new_tokens=12))[:, 5:]
    cache, state = llama.init_cache(cfg, 4, 48), mtp.init_state(cfg, 4)
    key = jax.random.key(0)
    got = []
    for b, p in enumerate(prompts):
        first, cache, state, _ = mtp.prefill_into_slot(
            params, jnp.asarray([p[:5]], jnp.int32), cache, jnp.int32(b), state, key,
            jnp.int32(b), jnp.int32(12), cfg, 0.0)
        got.append([int(first)])
    for _ in range(12):
        outs, state, cache, _ = mtp.decode_step_rowwise(params, state, cache, key, cfg, 0.0)
        for b, o in enumerate(np.asarray(outs)):
            got[b] += o[:o[2]].tolist()
    assert np.array_equal(np.asarray(got), want)
    assert np.asarray(state["left"]).tolist() == [0] * 4


# ---- (3) the acceptance rule -------------------------------------------------

def test_the_rule_replays_exactly_in_the_reference():
    cfg = tiny()
    _, out, got = compared(cfg, weights(cfg), steps=10)
    assert got["replay_mismatches"] == 0
    assert got["accepted"] + got["rejected"] == 20
    # and on given logits and keys, without a model
    key = jax.random.key(11)
    p_logits = jax.random.normal(jax.random.key(1), (12, 2, 40))
    q_logits = jax.random.normal(jax.random.key(2), (12, 40))
    request, position = jnp.arange(12) + 7, jnp.arange(12) * 5 + 3
    draft = llama._pick_token(
        q_logits, llama.draw_keys(key, request, position, llama.DRAW_DRAFT), temperature=0.7)
    accepted, tokens = mtp.accept(
        p_logits, q_logits, draft, key, request, position, temperature=0.7)
    for r in range(12):
        drafted = ref.draft(q_logits[r], key, int(request[r]), int(position[r]), 0.7)
        ok, ids = ref.accept(p_logits[r], q_logits[r], drafted, key, int(request[r]),
                             int(position[r]), 0.7)
        assert (drafted, ok) == (int(draft[r]), bool(accepted[r]))
        assert ids[:1 + ok] == np.asarray(tokens[r])[:1 + ok].tolist()
    assert 0 < int(accepted.sum()) < 12


def _exact_distributions(cfg, params, prompt):
    """P(t_{S+1}) and P(t_{S+2}) of the main model at temperature 1 after
    ``prompt``, summed over what came before, by enumeration."""
    V, spec = cfg.vocab_size, serve_mtp.spec_of(cfg)

    def nxt(tokens):
        hidden, _ = ref.forward(params, jnp.asarray(tokens, jnp.int32), spec)
        return np.asarray(jax.nn.softmax(ref.logits(params, hidden[-1:])[0]), np.float64)

    first = nxt(prompt)
    second = np.stack([nxt(prompt + [a]) for a in range(V)])          # [a, b]
    third = np.stack([[nxt(prompt + [a, b]) for b in range(V)] for a in range(V)])
    p2 = first @ second
    p3 = np.einsum("a,ab,abc->c", first, second, third)
    return p2, p3


@pytest.mark.parametrize("drafting", [1, 0], ids=["drafting", "plain"])
def test_emitted_ids_are_distributed_as_the_main_models(drafting):
    """A 6-id vocabulary, 1,536 requests of one prompt: the second and
    third generated ids against the main model's exact marginals, by
    chi-square at level 0.001 (5 degrees of freedom: 20.52) — the second is
    an accepted draft or the residual's draw, the third the token behind an
    accepted draft or the next step's.  Drafting on and off."""
    cfg = tiny(vocab_size=6, num_layers=2)
    params = weights(cfg, seed=3, sharp=5.0)
    prompt = [1, 4, 2, 0, 5]
    p2, p3 = _exact_distributions(cfg, params, prompt)
    assert p2.min() > 0.02 and p2.max() < 0.6      # neither flat nor one-hot
    B, rounds = 32, 48
    key = jax.random.key(17)
    toks = jnp.asarray([prompt], jnp.int32)
    counts2, counts3, accepted = np.zeros(6), np.zeros(6), 0
    for k in range(rounds):
        request = np.arange(B) + k * B
        cache = llama.init_cache(cfg, B, 16)
        if drafting:
            state = mtp.init_state(cfg, B)
            for b in range(B):
                _, cache, state, _ = mtp.prefill_into_slot(
                    params, toks, cache, jnp.int32(b), state, key,
                    jnp.int32(request[b]), jnp.int32(4), cfg, 1.0)
            seqs = [[] for _ in range(B)]
            for _ in range(2):
                outs, state, cache, _ = mtp.decode_step_rowwise(
                    params, state, cache, key, cfg, 1.0)
                outs = np.asarray(outs)
                accepted += outs[:, 3].sum()
                for b in range(B):
                    seqs[b] += outs[b, :outs[b, 2]].tolist()
        else:
            rows = []
            for b in range(B):
                logits, cache = llama.prefill_into_slot(params, toks, cache, jnp.int32(b), cfg)
                rows.append(logits)
            pos = np.full((B,), len(prompt), np.int32)
            tok = llama.sample_rows(jnp.concatenate(rows), key, jnp.asarray(request),
                                    jnp.asarray(pos), temperature=1.0)
            seqs = [[] for _ in range(B)]
            for i in range(2):
                logits, cache = llama.decode_step_rowwise(
                    params, tok, cache, jnp.asarray(pos + i), cfg)
                tok = llama.sample_rows(logits, key, jnp.asarray(request),
                                        jnp.asarray(pos + i + 1), temperature=1.0)
                for b, t in enumerate(np.asarray(tok)):
                    seqs[b].append(int(t))
        for s in seqs:
            counts2[s[0]] += 1
            counts3[s[1]] += 1
    n = B * rounds
    if drafting:
        assert 0.2 < accepted / n / 2 < 0.9       # both outcomes, often
    for counts, p in ((counts2, p2), (counts3, p3)):
        chi2 = float((((counts - n * p) ** 2) / (n * p)).sum())
        assert chi2 < 20.52, (chi2, counts, n * p)


# ---- (5) a rejected draft leaves nothing readable ----------------------------

@both_bodies
def test_what_lies_behind_pos_is_never_read(body):
    """After a step in which drafts were rejected: every cache row at or
    behind a row's new ``pos`` in the main layers (a rejected draft's among
    them), and from two before it in the module's, set to 1e3 — the next
    step's logits do not move by a bit.  (Finite: a key behind ``pos``
    inside a block that is read is weighted by zero, not skipped.)"""
    cfg = tiny()
    params = weights(cfg)
    eng = engine(cfg, params)
    serve_mtp.system_run(eng, 3, [9, 17, 5, 12], 3)
    state, cache, key = eng._spec, eng.cache, eng._key
    state = dict(state, left=jnp.full((4,), 9, jnp.int32))      # live again
    outs, state, cache, _ = mtp.decode_step_rowwise(params, state, cache, key, cfg, 1.0)
    assert 0 < int(np.asarray(outs)[:, 3].sum()) < 4           # some rejected

    def copy(tree):
        return jax.tree.map(jnp.copy, tree)

    pos = np.asarray(state["pos"])
    t = np.arange(cache["ckv"].shape[2])
    behind = t[None, :] >= pos[:, None]                               # (B, T)
    module_behind = t[None, :] >= pos[:, None] - 2
    mask = np.concatenate([np.repeat(behind[None], cfg.num_layers, 0),
                           module_behind[None]])[..., None]
    dirty = dict(cache, ckv=jnp.where(mask, 1e3, cache["ckv"]))
    assert float(jnp.abs(dirty["ckv"] - cache["ckv"]).max()) > 100
    _, _, _, clean_detail = mtp.decode_step_rowwise(
        params, copy(state), copy(cache), key, cfg, 1.0)
    _, _, _, dirty_detail = mtp.decode_step_rowwise(
        params, copy(state), dirty, key, cfg, 1.0)
    for name in ("p_logits", "q_logits", "draft"):
        assert np.array_equal(clean_detail[name], dirty_detail[name]), name


# ---- the cache, the counters, the sizes --------------------------------------

def test_the_cache_holds_one_kind_of_state_and_the_modules_layer():
    cfg = tiny()
    cache = llama.init_cache(cfg, 4, 48)
    assert set(cache) == {"ckv", "mla_keys", "moe_expert_tokens",
                          "moe_experts_touched", "moe_layer_steps"}
    assert cache["ckv"].shape == (4, 4, 48, 128)          # 3 layers + the module
    assert cache["moe_expert_tokens"].shape == (3, 4)     # 2 expert layers + it
    params = llama.init(jax.random.key(0), cfg)
    assert "w_iq" not in params["blocks"] and "ik_norm" not in params["dense_blocks"]
    assert set(params["mtp"]) == {"enorm", "hnorm", "head_norm", "eh_proj", "block"}
    assert params["mtp"]["eh_proj"].shape == (128, 64)
    # GLM-5's tree is what it was: the indexer's tensors, no module
    glm = llama.init(jax.random.key(0), tiny(
        index_n_heads=4, index_head_dim=16, index_topk=8, mtp_layers=0))
    assert "w_iq" in glm["blocks"] and "mtp" not in glm


@both_bodies
def test_steps_count_the_rows_visible_once_a_row(body):
    cfg = tiny()
    eng = engine(cfg, weights(cfg))
    serve_mtp.system_run(eng, 3, [9, 17], 1)
    keys = np.asarray(eng.cache["mla_keys"])
    visible = [llama.wide_total(keys[l, 0]) for l in range(4)]
    read = [llama.wide_total(keys[l, 1]) for l in range(4)]
    # rows at 9 and 17 (their last queries at 10 and 18 see 11 and 19 keys)
    # and two idle rows at 1; the module's pairs end two positions earlier
    assert visible[:3] == [11 + 19 + 3 + 3] * 3 and visible[3] == 9 + 17 + 1 + 1
    # whole blocks of 8 up to the last query's; the dense body the whole slab
    assert read[:3] == [16 + 24 + 8 + 8 if body == "streamed" else 4 * 48] * 3
    assert read[3] == (16 + 24 + 8 + 8 if body == "streamed" else 4 * 48)
    steps = np.asarray(eng.cache["moe_layer_steps"])      # (calls, row tiles gathered)
    assert steps[:, 0].tolist() == [3, 3, 3]              # 2 prefills + 1 step each
    assert steps[:, 1].tolist() == [3, 3, 3]              # each one block of one tile


def test_num_params_and_flops_count_the_module_and_no_indexer():
    cfg = tiny()
    without = tiny(mtp_layers=0)
    E = cfg.embed_dim
    block = llama.num_params(tiny(num_layers=2, first_dense_layers=0, mtp_layers=0)) - (
        llama.num_params(tiny(num_layers=1, first_dense_layers=0, mtp_layers=0)))
    assert llama.num_params(cfg) - llama.num_params(without) == block + 2 * E * E + 3 * E
    # attention over every visible key in all four cache layers, no indexer's
    per_key = cfg.num_heads * (12 + 8 + 16)
    n = llama.num_params(cfg) - cfg.vocab_size * E
    assert llama.flops_per_token(cfg, 100) == 6.0 * n + 6 * 4 * per_key * 100


def test_drafting_needs_a_module_and_one_token():
    cfg = tiny(mtp_layers=0)
    params = llama.init(jax.random.key(0), cfg)
    with pytest.raises(ValueError, match="multi-token-prediction"):
        LLMEngine(params, cfg, speculative_tokens=1)
    with pytest.raises(ValueError, match="0 or 1"):
        LLMEngine(params, tiny(), speculative_tokens=2)
    with pytest.raises(NotImplementedError, match="without an indexer"):
        llama.init(jax.random.key(0), tiny(index_topk=8, index_n_heads=4, index_head_dim=16))


# ---- (7) without drafting and at temperature 0: today's programs -------------

#: sha256 of the lowered text of the two engine programs at the commit
#: before the module (PR 31, 36cdf3b), as jax 0.9.0 prints it (another
#: jax words the same program otherwise: the cases skip there).  Refresh them
#: from the parent commit of a PR that means to change the step, never to
#: make this pass.  ``kv`` and ``moe``: PR 37's, which changed the K/V cache's
#: stored shape to (L, B, T, KV x D) — the four programs' text changed with it
#: (their tiny caches keep XLA's attention body); ``glm``'s step is still
#: PR 31's, its prefill PR 40's, which means to change it: a run now counts
#: the (query, key) pairs it computed scores for into ``dsa_keys`` (the tiny
#: run keeps XLA's attention body)
_PINNED_JAX = "0.9.0"
_LOWERED = {
    "kv": ("25631a6d7e31deaf", "6d314943030ee47b"),
    "moe": ("a2af198d06292884", "54cadcea029f716f"),
    # a held-experts config: re-pinned on PR 54, whose ``_ffn`` gathers the
    # held rows alone (``kv`` and ``moe``, whose rows all have a group, held)
    "glm": ("ea9a1c5877b495dd", "d112bb76b60fa495"),
}


def _config(name):
    if name == "kv":
        return llama.LlamaConfig.tiny()
    if name == "moe":
        return llama.LlamaConfig.tiny(num_experts=8, experts_per_token=2,
                                      expert_dim=32, mlp_dim=0)
    return tiny(index_n_heads=4, index_head_dim=16, index_topk=8, mtp_layers=0)


@pytest.mark.parametrize("program", [0, 1], ids=["decode_step_rowwise", "prefill_into_slot"])
@pytest.mark.parametrize("name", sorted(_LOWERED))
def test_the_one_token_programs_lower_to_what_they_were(name, program):
    if jax.__version__ != _PINNED_JAX:
        pytest.skip(f"the hashes are of jax {_PINNED_JAX}'s text, this is {jax.__version__}")
    cfg = _config(name)
    params = jax.eval_shape(lambda: llama.init(jax.random.key(0), cfg))
    cache = jax.eval_shape(lambda: llama.init_cache(cfg, 4, 64))
    rows = jax.ShapeDtypeStruct((4,), jnp.int32)
    if program == 0:
        text = llama.decode_step_rowwise.lower(params, rows, cache, rows, cfg).as_text()
    else:
        text = llama.prefill_into_slot.lower(
            params, jax.ShapeDtypeStruct((1, 16), jnp.int32), cache,
            jax.ShapeDtypeStruct((), jnp.int32), cfg).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == _LOWERED[name][program]


def test_an_engine_without_the_options_calls_only_the_one_token_programs(monkeypatch):
    import asyncio

    cfg = tiny()
    params = llama.init(jax.random.key(0), cfg)

    def refuse(*a, **k):
        raise AssertionError("a drafting or sampling program was called")

    for name in ("decode_step_rowwise", "prefill_into_slot"):
        monkeypatch.setattr(mtp, name, refuse)
    monkeypatch.setattr(llama, "sample_rows", refuse)
    eng = LLMEngine(params, cfg, max_slots=2, max_len=32)
    assert eng._key is None and eng._spec is None and eng._programs is llama

    async def one():
        return [t async for t in eng.stream([3, 1, 4, 1, 5], 6)]

    got = asyncio.run(one())
    want = np.asarray(llama.generate_kv(
        params, jnp.asarray([[3, 1, 4, 1, 5]]), cfg, max_new_tokens=6))[0, 5:]
    assert got == want.tolist()
