"""Generation by diffusion over blocks (``models/block_diffusion.py`` over
``llama._cached_step`` under the block mask) against the plain float32
reference (``chipbench/reference/sdar.py``), at a small size on the CPU:
logits, not tokens.  Every compared program is jitted once and shared
through module-scoped fixtures."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import sdar
from ray_tpu.models import block_diffusion as bd
from ray_tpu.models import llama

MASK = 63
BLOCK = 4
T = 1.0
SLOTS, MAX_LEN = 4, 32
#: prompts that leave 0, 1 and 3 tokens over a whole number of blocks
PROMPT_LENS = (8, 9, 11)
#: tokens each asks for: the third's budget is met after two blocks
BUDGETS = (12, 12, 5)
STEPS = 11


def config(**kw):
    base = dict(
        vocab_size=MASK + 1, num_layers=2, num_heads=4, num_kv_heads=2,
        embed_dim=64, head_dim=32, mlp_dim=0, num_experts=8, experts_per_token=2,
        expert_dim=32, qk_norm="head", router_norm_topk=True, rope_theta=1e6,
        rms_eps=1e-6, mask_block=BLOCK,
    )
    base.update(kw)
    return llama.LlamaConfig.tiny(**base)


def weights(cfg, seed=0, scale=8.0):
    """Seeded weights, the matrices scaled up so that logits and routing are
    not flat, the norms' scales off one."""
    params = llama.init(jax.random.key(seed), cfg)
    key = jax.random.key(seed + 1)

    def shaped(path, a):
        if a.ndim > 1 and "norm" not in jax.tree_util.keystr(path):
            return a * scale
        return 1.0 + 0.3 * jax.random.normal(
            jax.random.fold_in(key, hash(jax.tree_util.keystr(path)) % 1000), a.shape)

    return jax.tree_util.tree_map_with_path(shaped, params)


def spec(cfg, block=BLOCK):
    return sdar.Spec(cfg.rope_theta, cfg.rms_eps, cfg.experts_per_token, block,
                     cfg.expert_offset)


def prompt(n, seed):
    return np.random.default_rng(seed).integers(0, MASK, n).tolist()


def run_rows(cfg, params, settings, key, prompts, steps, budgets=BUDGETS,
             requests=(100, 101, 102)):
    """Prefill ``prompts`` into rows 0.. and step the whole batch; the last
    row of the batch stays empty.  -> (rows: per prompt {"done": tokens
    committed, "passes": [...]}, every step's state, cache)."""
    cache = llama.init_cache(cfg, SLOTS, MAX_LEN)
    state = bd.init_state(cfg, SLOTS, settings)
    rows = []
    for r, p in enumerate(prompts):
        first, cache, state, _ = bd.prefill_into_slot(
            params, jnp.asarray([p], jnp.int32), cache, jnp.int32(r), state, key,
            jnp.int32(requests[r]), jnp.int32(budgets[r]), cfg, T, settings)
        assert int(first) == -1
        rows.append({"prompt": p, "done": list(p[:len(p) // BLOCK * BLOCK]),
                     "emitted": [], "passes": []})
    states = [jax.tree.map(np.asarray, state)]
    for _ in range(steps):
        outs, state, cache, detail = bd.decode_step_rowwise(
            params, state, cache, key, cfg, T, settings)
        outs, detail = np.asarray(outs), jax.tree.map(np.asarray, detail)
        states.append(jax.tree.map(np.asarray, state))
        for r, row in enumerate(rows):
            live, committed = bool(outs[r, BLOCK + 1]), bool(outs[r, BLOCK + 2])
            row["passes"].append({
                "live": live, "committed": committed, "before": list(row["done"]),
                "ids": outs[r, :outs[r, BLOCK]].tolist(),
                "by_threshold": int(outs[r, BLOCK + 4]),
                **{k: detail[k][r] for k in ("pos", "passes", "block", "logits", "x0",
                                             "conf", "transfer")},
            })
            row["emitted"] += outs[r, :outs[r, BLOCK]].tolist()
            if committed:
                row["done"] += detail["block"][r].tolist()
    return rows, states, cache


@pytest.fixture(scope="module")
def served():
    cfg = config()
    params = weights(cfg)
    settings = bd.Settings(block=BLOCK, denoising_steps=4, threshold=0.9, mask_id=MASK)
    key = jax.random.key(7)
    prompts = [prompt(n, i) for i, n in enumerate(PROMPT_LENS)]
    rows, states, cache = run_rows(cfg, params, settings, key, prompts, STEPS)
    return {"cfg": cfg, "params": params, "settings": settings, "key": key,
            "rows": rows, "states": states, "cache": cache}


@pytest.mark.parametrize("row", range(3), ids=[f"leftover{n % BLOCK}" for n in PROMPT_LENS])
def test_every_pass_against_the_reference(served, row):
    """Prefill (P % 4 of 0, 1, 3) and diffusion steps through the cache:
    every pass's logits of the block equal the reference's full forward over
    [committed so far ; the block as it stood], and the reference's rule on
    those logits and keys gives the program's candidates, transfers and next
    block."""
    s, r = served, served["rows"][row]
    leftover = PROMPT_LENS[row] % BLOCK
    assert r["passes"][0]["block"].tolist() == (
        r["prompt"][len(r["prompt"]) - leftover:] + [MASK] * (BLOCK - leftover))
    live = [p for p in r["passes"] if p["live"]]
    assert len(live) >= 7 and sum(p["committed"] for p in live) >= 2
    for i, p in enumerate(live):
        before, block = p["before"], p["block"].tolist()
        assert p["pos"] == len(before)
        ref, _ = sdar.forward(s["params"], jnp.asarray(before + block), spec(s["cfg"]),
                              rows=list(range(len(before), len(before) + BLOCK)))
        np.testing.assert_allclose(p["logits"], np.asarray(ref), atol=3e-4, rtol=1e-4)
        nxt = live[i + 1]["block"].tolist() if i + 1 < len(live) else None
        if p["committed"]:
            assert MASK not in block
            assert nxt is None or nxt == [MASK] * BLOCK or not live[i + 1]["live"]
            continue
        masked = np.asarray(block) == MASK
        conf = np.zeros((BLOCK,), np.float32)
        for j in np.flatnonzero(masked):
            x0, conf[j] = sdar.candidate(p["logits"][j], s["key"], 100 + row,
                                         int(p["pos"]) + int(j), int(p["passes"]), T, MASK)
            assert x0 == p["x0"][j] and x0 != MASK
            assert conf[j] == pytest.approx(p["conf"][j], rel=1e-4)
        transfer, _high = sdar.transfers(conf, masked, 0.9, 1)
        assert transfer.tolist() == p["transfer"].tolist() and transfer.sum() == 1
        if nxt is not None:
            assert nxt == np.where(transfer, p["x0"], p["block"]).tolist()
    # what was delivered: the committed blocks in order, less the prompt's part
    n = len(r["prompt"])
    assert r["emitted"] == r["done"][n:][:BUDGETS[row]]
    assert len(r["emitted"]) == BUDGETS[row] or live[-1] is r["passes"][-1]


def test_rows_in_different_phases_share_a_step(served):
    """One batch, one program: in some step one row commits while another
    refines, and the rows' blocks are at different passes."""
    rows = served["rows"]
    mixed = [
        {(p["committed"], int(p["passes"])) for p in step if p["live"]}
        for step in zip(*(r["passes"] for r in rows))
    ]
    assert any(len({c for c, _ in kinds}) == 2 for kinds in mixed)
    assert any(len({n for _, n in kinds}) >= 2 for kinds in mixed)


def test_a_row_with_nothing_left_is_stepped_and_unchanged(served):
    """The empty slot (row 3) from the start, and a row whose budget is met
    from then on: every step leaves their state as it was."""
    states = served["states"]
    for before, after in zip(states, states[1:]):
        idle = before["left"] == 0
        assert idle[3]
        for k in before:
            np.testing.assert_array_equal(before[k][idle], after[k][idle])
    assert states[-1]["left"][2] == 0 and states[-3]["left"][2] == 0  # its budget was met


def test_the_cache_after_a_commit_is_a_fresh_prefill_of_the_final_sequence(served):
    """The K/V a later block reads are those of the block's FINAL tokens:
    after its commits a row's cache rows equal what one prefill of [prompt's
    whole blocks ; the committed blocks] writes."""
    s, r = served, served["rows"][0]
    done = r["done"]
    assert len(done) >= PROMPT_LENS[0] + 2 * BLOCK
    fresh = llama.init_cache(s["cfg"], SLOTS, MAX_LEN)
    _, fresh, _, _ = bd.prefill_into_slot(
        s["params"], jnp.asarray([done], jnp.int32), fresh, jnp.int32(0),
        bd.init_state(s["cfg"], SLOTS, s["settings"]), s["key"], jnp.int32(0),
        jnp.int32(4), s["cfg"], T, s["settings"])
    for k in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(s["cache"][k][:, 0, :len(done)]),
            np.asarray(fresh[k][:, 0, :len(done)]), atol=2e-5)


def test_a_step_that_skips_the_commit_fails_the_comparison(served):
    """Advance a row past a block whose last MASK has just gone WITHOUT the
    commit forward (the cache then holds the K/V of the last refining pass,
    of a block with a MASK in it): the next block's logits are off the
    reference's by far more than any pass of the honest run."""
    s = served
    cfg, params, settings, key = s["cfg"], s["params"], s["settings"], s["key"]
    cache = llama.init_cache(cfg, SLOTS, MAX_LEN)
    state = bd.init_state(cfg, SLOTS, settings)
    p = s["rows"][0]["prompt"]
    _, cache, state, _ = bd.prefill_into_slot(
        params, jnp.asarray([p], jnp.int32), cache, jnp.int32(0), state, key,
        jnp.int32(100), jnp.int32(12), cfg, T, settings)
    for _ in range(4):  # the four refining passes of the first block
        _, state, cache, _ = bd.decode_step_rowwise(params, state, cache, key, cfg, T, settings)
    block = np.asarray(state["block"][0]).tolist()
    assert MASK not in block and block == s["rows"][0]["done"][len(p):len(p) + BLOCK]
    skipped = dict(
        state, pos=state["pos"].at[0].add(BLOCK),
        block=state["block"].at[0].set(MASK), passes=state["passes"].at[0].set(0))
    _, _, _, detail = bd.decode_step_rowwise(params, skipped, cache, key, cfg, T, settings)
    ref, _ = sdar.forward(params, jnp.asarray(p + block + [MASK] * BLOCK), spec(cfg),
                          rows=list(range(len(p) + BLOCK, len(p) + 2 * BLOCK)))
    off = float(jnp.max(jnp.abs(detail["logits"][0] - ref)))
    assert off > 100 * 3e-4, off
