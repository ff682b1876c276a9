"""Job kind ``serve_scmoe``: the ``serve_moe`` job for a decoder of
shortcut-connected double layers — two latent attentions, two dense
SwiGLUs and one expert layer across them, whose router also chooses
identity ("zero-compute") experts — served as one chip's share of an
expert-parallel layer (LongCat-Flash through ``LlamaConfig``).

Same path — ``serve.run`` of a decode replica, requests through the
deployment handle's streaming path, ``LLMEngine`` on the chip — same load,
same stamps, same facts keys: ``run`` IS ``serve_moe.run`` with what that
file hard-wires exchanged, as ``jobs/serve_dsa.py`` and ``jobs/serve_mtp.py``
do it and with their helpers where they fit (``check_prompt``, ``_InTurn``).
The comparison that decides ``correct`` (``ScmoeReplica.check_reference``,
``system_run``, ``against_reference``, ``passes``) is made on the chip, at
the served widths, in the engine's own cache and on what the two served
executables (``llama.prefill_into_slot`` / ``llama.decode_step_rowwise``)
produce: one check row a prompt length of the traffic, prefilled and then
decoded ``check_steps`` steps,

(i)   their logits at the prompt's last position and at every step against
      the float32 reference's full forward (``chipbench/reference/
      longcat_flash.py``) of the same tokens, GIVEN the system's choices;
(ii)  the routing choice by choice: the share of (layer, token) pairs whose
      chosen set is not the reference's own, and the largest reference
      margin among those;
(iii) the choices being the served programs' as far as a second
      executable can say: the choices-returning twin (``llama.
      choices_cached``) gives their logits within rounding, and the two
      pairs of programs count the same held and identity choices but for
      a handful of near ties (``system_run`` says why no more can be
      asked);
(iv)  both identity and held real experts among the checked tokens'
      choices;
(v)   no held pair and no identity choice uncomputed: the counters the
      twin carried through the check (rows each held expert computed,
      identity choices) are exactly the counts of the choices it handed
      back, and (``_window``: ``moe_dropped``) none is over in the window.

The module asks the program for its fields when it is IMPORTED, which
``run.py`` does before it starts a cluster: a program without them (a
commit from before the block) fails there, at once, and no chip is leased.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
from unittest import mock

from chipbench import loadgen, scmoe_cost, scmoe_trace
from chipbench.jobs import serve_dsa, serve_moe
from chipbench.jobs.serve_llm import BenchReplica
from ray_tpu.models.llama import LlamaConfig

SCMOE_FIELDS = (
    "block_form", "zero_experts", "mla_scale_q_lora", "mla_scale_kv_lora",
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "router_scale", "experts_held", "expert_offset",
)
_missing = set(SCMOE_FIELDS) - {f.name for f in dataclasses.fields(LlamaConfig)}
if _missing:
    raise RuntimeError(
        f"this program's LlamaConfig has no {sorted(_missing)}: it cannot run "
        "a configuration of shortcut-connected double layers"
    )

#: ids of the prompts the router's bias is balanced on
BALANCE_PROMPT_LEN = 2048
#: d ln(an output's load) / d (its bias) for SOFTMAX scores, reckoned: an
#: output is chosen when its score passes the 12th of 768, 0.0115 (numpy, the
#: router's N(0, 0.02) matrix on unit-RMS inputs: logits N(0, 1.57)); a
#: bias b moves that bar by b / 0.0115 in ln(score), 1 / 1.57 of it in
#: standard deviations, where the normal tail's hazard is 2.5: 2.5 / 1.57 /
#: 0.0115.  ``serve_dsa.BALANCE_SLOPE`` (29) is the sigmoid's, whose scores
#: are a hundred times these.  A slope off by two only makes the iteration
#: slower
BALANCE_SLOPE = 140.0

REHEARSAL_MODEL = {
    "hidden_size": 64, "ffn_hidden_size": 96, "expert_ffn_hidden_size": 32,
    "num_layers": 2, "num_attention_heads": 4, "vocab_size": 512,
    "q_lora_rank": 32, "kv_lora_rank": 24, "qk_nope_head_dim": 12,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 4,
    "n_routed_experts_published": 16, "zero_expert_num": 8, "moe_topk": 4,
}


def scmoe_config(cfg: dict):
    """The configuration file's keys -> the program's LlamaConfig.
    ``n_routed_experts`` is how many experts are HELD here, from
    ``expert_offset``; the router's real experts are
    ``n_routed_experts_published`` and ``zero_expert_num`` identity experts
    stand behind them."""
    import jax.numpy as jnp

    if cfg["attention_method"] != "MLA" or cfg["zero_expert_type"] != "identity":
        raise RuntimeError("the program runs latent attention and identity experts only")
    return LlamaConfig(
        vocab_size=cfg["vocab_size"], max_seq_len=cfg["max_position_embeddings"],
        num_layers=cfg["num_layers"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_attention_heads"], embed_dim=cfg["hidden_size"],
        mlp_dim=cfg["ffn_hidden_size"], rope_theta=float(cfg["rope_theta"]),
        rms_eps=cfg["rms_norm_eps"], dtype=getattr(jnp, cfg["dtype"]),
        param_dtype=getattr(jnp, cfg["param_dtype"]), sliding_window=0,
        tie_embeddings=cfg["tie_word_embeddings"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        mla_scale_q_lora=cfg["mla_scale_q_lora"],
        mla_scale_kv_lora=cfg["mla_scale_kv_lora"],
        block_form="shortcut", num_experts=cfg["n_routed_experts_published"],
        zero_experts=cfg["zero_expert_num"], experts_per_token=cfg["moe_topk"],
        expert_dim=cfg["expert_ffn_hidden_size"], router_scoring="softmax",
        router_norm_topk=False, router_scale=float(cfg["routed_scaling_factor"]),
        experts_held=cfg["n_routed_experts"], expert_offset=cfg["expert_offset"],
    )


def spec_of(config):
    """What the reference needs beside the parameter tree."""
    from chipbench.reference import longcat_flash

    return longcat_flash.Spec(
        float(config.rope_theta), float(config.rms_eps), config.qk_rope_head_dim,
        config.experts_per_token, float(config.router_scale), config.num_experts,
        config.expert_offset, config.mla_scale_q_lora, config.mla_scale_kv_lora,
    )


def model_facts(cfg: dict) -> dict:
    """The configuration's numbers as the readers' ``facts["model"]``, with
    the two keys ``chipbench/mla_cost.py`` counts a latent cache's layers
    by (``mla_attn_hbm_roofline_share``'s reader): the cache layers — two a
    layer here — and no multi-token-prediction module."""
    model = {k: v for k, v in cfg.items()
             if isinstance(v, (int, float)) and not isinstance(v, bool)}
    return dict(model, num_hidden_layers=scmoe_cost.cache_layers(cfg),
                num_nextn_predict_layers=0)


def balance_router(params, config, seed: int, cache, prompt_len: int):
    """``serve_dsa.balance_router``'s procedure for a SOFTMAX router over
    ``router_outputs`` outputs, identity experts among them: the selection
    bias, which starts at zero as the published buffer does, moved to where
    every output is chosen equally often (a trained checkpoint's are in
    balance; here a third of the choices then fall on identity experts and
    a token meets 8 real experts of its 12 on average, the published
    operating point).  Each iteration runs a fresh seeded prompt through
    slot 0 of ``cache`` with ``llama.choices_cached``, counts every
    output's load in every layer and moves the bias by ``gain /
    BALANCE_SLOPE x ln(even load / load)``.  Returns (params, cache)."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama

    rng = np.random.default_rng([seed % (2**63), 29])
    stored = params["blocks"]["router_bias"]
    bias = np.array(stored, np.float32)                       # (L, outputs)
    outputs = config.router_outputs
    even = prompt_len * config.experts_per_token / outputs
    loads = []
    for gain in [g for n, g in serve_dsa.BALANCE_SCHEDULE for _ in range(n)]:
        prompt = rng.integers(0, config.vocab_size, (1, prompt_len))
        _, cache, chose = llama.choices_cached(
            params, jnp.asarray(prompt, jnp.int32), cache, jnp.int32(0), None, config
        )
        experts = np.asarray(chose["experts"])[:, 0].reshape(bias.shape[0], -1)
        load = np.stack([np.bincount(e, minlength=outputs) for e in experts])
        loads.append(load)
        bias += gain / BALANCE_SLOPE * np.log(even / (load + 1.0))
        params = dict(params, blocks=dict(
            params["blocks"], router_bias=jnp.asarray(bias, stored.dtype)))
    first, last = loads[0] / even, np.mean(loads[-4:], axis=0) / even
    print(f"[serve_scmoe] router bias balanced on {len(loads)} prompts of {prompt_len}: "
          f"moved by sd {bias.std():.5f}, at most {np.abs(bias).max():.5f}; load / even "
          f"sd {first.std():.3f} -> {last.std():.3f} (last four prompts' mean), "
          f"identity share {loads[0][:, config.num_experts:].sum() / loads[0].sum():.3f} "
          f"-> {loads[-1][:, config.num_experts:].sum() / loads[-1].sum():.3f}", flush=True)
    return params, cache


def make_weights(cfg: dict, seed: int, rehearse: bool):
    """``weights_loader``: as ``serve_mtp.make_weights``: one jitted
    ``llama.init`` on the device in the type that is served, then the
    selection bias balanced (``balance_router``, in a scratch cache of one
    row)."""
    import jax

    from ray_tpu.models import llama
    from ray_tpu.util import compile_cache

    compile_cache.configure()
    dev = jax.devices()[0]
    if not rehearse and dev.platform != "tpu":
        raise RuntimeError(
            f"the replica was leased a TPU chip but jax came up on platform "
            f"{dev.platform!r}; there is no CPU fallback"
        )
    config = scmoe_config(cfg)
    params = jax.block_until_ready(
        jax.jit(functools.partial(llama.init, config=config))(jax.random.key(seed % (2**31)))
    )
    length, prompt_len = (256, 24) if rehearse else (
        cfg["serving"]["max_len"], BALANCE_PROMPT_LEN)
    params, _ = balance_router(
        params, config, seed, llama.init_cache(config, 1, length), prompt_len
    )
    return jax.block_until_ready(params)


def _counters(cache) -> dict:
    import numpy as np

    return {k: np.asarray(cache[k]).astype(np.int64)
            for k in ("moe_expert_tokens", "moe_zero_choices")}


def _rows(prefill, step, params, config, cache, max_slots: int, prompts, steps: int,
          then=None):
    """``prompts[r]`` into slot r by ``prefill``, then ``steps`` calls of
    ``step`` over the whole batch, row r fed ``then[r]``'s next token or,
    without it, the argmax of its logits before; the other rows step a
    token 0 at position 0, as the engine's empty slots do.  -> (cache, the
    rows' tokens, per row its logits (1 + steps, V), per call what the
    program returned beside logits and cache)."""
    import jax.numpy as jnp
    import numpy as np

    seqs, logits, calls = [list(p) for p in prompts], [[] for _ in prompts], []
    for r, seq in enumerate(seqs):
        out, cache, *rest = prefill(
            params, jnp.asarray([seq], jnp.int32), cache, jnp.int32(r), config)
        logits[r].append(out[0])
        calls.append(rest)
    for i in range(steps):
        tokens = np.zeros((max_slots,), np.int32)
        pos = np.zeros((max_slots,), np.int32)
        for r, seq in enumerate(seqs):
            seq.append(then[r][i] if then else int(jnp.argmax(logits[r][-1])))
            tokens[r], pos[r] = seq[-1], len(seq) - 1
        out, cache, *rest = step(params, jnp.asarray(tokens), cache, jnp.asarray(pos), config)
        for r in range(len(seqs)):
            logits[r].append(out[r])
        calls.append(rest)
    return cache, seqs, [jnp.stack(row) for row in logits], calls


def system_run(params, config, cache, max_slots: int, prompts, steps: int):
    """The check rows through ``llama.prefill_into_slot`` and ``steps``
    greedy ``llama.decode_step_rowwise`` steps in ``cache`` (slots 0, 1,
    ..): THE TWO EXECUTABLES THE ENGINE SERVES WITH, in the cache it then
    serves from; their logits are what is compared.  Those programs hand
    back no choices, so the same tokens then go through the same slots once
    more by ``llama.choices_cached`` — the same ``_cached_step`` compiled
    with its choices as further outputs — for the router's choices.  That
    twin is ANOTHER executable: the compiler fuses it in its own way, and
    its logits are the served programs' only up to bfloat16 rounding (bit
    for bit in some builds of this model, off by 0.04-0.08 of a logit in
    others: my chip runs, PR 49, calls 2 and 3), so a handful of near-tie
    choices may differ between the two.  Three readings hold the loan to
    account: ``twin_err``, the twin's logits against the served ones (rms
    and max over std, as ``reference.errors``); the counters the cache
    carries, read before, between and after — ``twin_pairs_miscounted``,
    how far the rows the held experts computed and the identity choices
    counted in the twin's calls are from the counts of the choices it
    handed back (every row of every call, the empty slots' too: exact, or
    the program did not compute what it chose), and ``served_pairs_off``,
    how far the served programs' counts are from the twin's (the choices
    that differ between the two, as far as they fall on held or identity
    experts).  Returns (cache, {"rows": [per check row
    {"prompt", "seq": prompt + the greedy tokens, "logits": (1 + steps, V),
    "experts": (L, len(seq), k) the router outputs every token chose}],
    ...})."""
    import jax.numpy as jnp
    import numpy as np

    from chipbench.reference import errors
    from ray_tpu.models import llama

    before = _counters(cache)
    cache, seqs, served, _ = _rows(
        llama.prefill_into_slot, llama.decode_step_rowwise,
        params, config, cache, max_slots, prompts, steps,
    )
    between = _counters(cache)
    cache, _, twin, calls = _rows(
        lambda p, t, c, slot, cfg: llama.choices_cached(p, t, c, slot, None, cfg),
        lambda p, t, c, pos, cfg: llama.choices_cached(p, t, c, None, pos, cfg),
        params, config, cache, max_slots, prompts, steps,
        then=[seq[len(p):] for seq, p in zip(seqs, prompts)],
    )
    after = _counters(cache)
    chose = [np.asarray(rest[0]["experts"]) for rest in calls]      # (L, R, Sq, k) a call
    n = len(prompts)
    experts = [np.concatenate([chose[r][:, 0]] + [c[:, r] for c in chose[n:]], axis=1)
               for r in range(n)]
    # what the twin's calls routed, by layer: every row of every call
    routed = np.concatenate([c.reshape(c.shape[0], -1) for c in chose], axis=1)
    held = routed - config.expert_offset
    want_tokens = np.stack([
        np.bincount(row[(row >= 0) & (row < config.experts_here)],
                    minlength=config.experts_here) for row in held])
    want_zero = (routed >= config.num_experts).sum(-1)
    miscounted = (
        np.abs(after["moe_expert_tokens"] - between["moe_expert_tokens"] - want_tokens).sum()
        + np.abs(after["moe_zero_choices"] - between["moe_zero_choices"] - want_zero).sum()
    )
    off = sum(np.abs((between[k] - before[k]) - (after[k] - between[k])).sum() for k in before)
    return cache, {
        "rows": [{"prompt": list(p), "seq": seq, "logits": out, "experts": e}
                 for p, seq, out, e in zip(prompts, seqs, served, experts)],
        "twin_pairs_miscounted": int(miscounted),
        "served_pairs_off": int(off),
        "twin_err": errors(jnp.stack(twin), jnp.stack(served)),
    }


def against_reference(params, config, out: dict, given: bool = True) -> dict:
    """What ``system_run`` produced against the float32 reference's full
    forward of the same tokens — ``given`` the system's choices
    (``reference/longcat_flash.py`` says why; False: free-running).
    {"err": rms and max of |logits - reference| / std over every checked
    position of every row, "swap_rate": share of (layer, token) pairs that
    chose another set of router outputs than the reference did at that
    layer, "swapped_margin_max": the largest margin (12th minus 13th
    selection score, in the reference's float32) among those pairs,
    "identity_choices" / "held_choices": how many of the checked tokens'
    choices fell on identity experts / on experts held here, ...}."""
    import jax.numpy as jnp
    import numpy as np

    from chipbench.reference import errors
    from chipbench.reference import longcat_flash as reference

    spec = spec_of(config)
    got, want, swapped, margins = [], [], [], []
    identity = held = 0
    for row in out["rows"]:
        first = len(row["prompt"]) - 1
        hidden, info = reference.forward(
            params, jnp.asarray(row["seq"], jnp.int32), spec,
            jnp.asarray(row["experts"]) if given else None)
        want.append(np.asarray(reference.logits(params, hidden[first:])))
        got.append(np.asarray(row["logits"], np.float32))
        swapped.append((np.sort(row["experts"], -1)
                        != np.sort(np.asarray(info["experts"]), -1)).any(-1).ravel())
        margins.append(np.asarray(info["expert_margin"]).ravel())
        here = row["experts"] - config.expert_offset
        identity += int((row["experts"] >= config.num_experts).sum())
        held += int(((here >= 0) & (here < config.experts_here)).sum())
    swapped, margins = np.concatenate(swapped), np.concatenate(margins)
    return {
        "err": errors(np.concatenate(got), np.concatenate(want)),
        "swap_rate": float(swapped.mean()),
        "swapped_margin_max": float(margins[swapped].max()) if swapped.any() else 0.0,
        "margin_p50": float(np.median(margins)),
        "identity_choices": identity, "held_choices": held,
        "twin_pairs_miscounted": out["twin_pairs_miscounted"],
        "served_pairs_off": out["served_pairs_off"],
        "twin_err": out["twin_err"],
    }


def passes(got: dict, tolerance: dict) -> bool:
    """The comparison that decides ``correct``: the served programs' logits
    within rms and max; the routers' choices (the logits are compared under
    the system's own, so they are held to account apart) within the share
    swapped and the largest margin overturned; both identity and held real
    experts among the checked choices; every held pair and identity choice
    the twin chose computed as counted, exactly; and the choices being the
    served programs' as far as a second executable can say (its logits
    theirs within ``twin_rms`` / ``twin_max``, their counters its own but
    for ``served_pairs_off_max`` pairs).  The limits and the readings they
    lie between: the configuration's file."""
    from chipbench.reference import within

    return bool(
        within(got["err"], tolerance)
        and got["swap_rate"] <= tolerance["swap_rate_max"]
        and got["swapped_margin_max"] <= tolerance["swapped_margin_max"]
        and min(got["identity_choices"], got["held_choices"]) >= 1
        and got["twin_pairs_miscounted"] == 0
        and got["served_pairs_off"] <= tolerance["served_pairs_off_max"]
        and within(got["twin_err"], {"rms": tolerance["twin_rms"], "max": tolerance["twin_max"]})
    )


def compare(params, config, cache, max_slots: int, seed: int, prompt_lens, steps: int):
    prompts = [serve_dsa.check_prompt(config, seed + r, n) for r, n in enumerate(prompt_lens)]
    cache, out = system_run(params, config, cache, max_slots, prompts, steps)
    return cache, against_reference(params, config, out)


class ScmoeReplica(BenchReplica):
    """``BenchReplica`` compared with the LongCat-Flash reference."""

    def check_reference(self, seed: int, tolerance: dict) -> dict:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.models import llama

        eng, cfg = self.engine, self.config
        live = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use")
        lens, steps = tolerance["check_prompt_lens"], int(tolerance["check_steps"])
        eng.cache, got = compare(
            eng.params, cfg, eng.cache, eng.max_slots, seed, lens, steps)
        # the decode program again, for its temporaries and its text (the
        # jitted call above keeps no handle on its executable)
        tokens = jnp.zeros((eng.max_slots,), jnp.int32)
        decode = llama.decode_step_rowwise.lower(
            eng.params, tokens, eng.cache, tokens, cfg).compile()
        temp = decode.memory_analysis().temp_size_in_bytes
        if tolerance.get("scope_file"):
            # a traced run: which instruction of which version of which
            # program runs under which scope (``scmoe_trace``)
            versions = {
                "decode_step_rowwise": [scmoe_trace.version(decode.as_text())],
                "prefill_into_slot": [
                    scmoe_trace.version(llama.prefill_into_slot.lower(
                        eng.params, jnp.zeros((1, n), jnp.int32), eng.cache,
                        jnp.int32(0), cfg,
                    ).compile().as_text())
                    for n in tolerance["scope_prompt_lens"]
                ],
            }
            with open(tolerance["scope_file"], "w") as f:
                json.dump(versions, f)
        print(f"[serve_scmoe] reference check at {lens} + {steps} steps: {got}", flush=True)
        return {**got, "tol": tolerance, "ok": passes(got, tolerance),
                "live_bytes": live, "decode_temp_bytes": temp,
                "cache_bytes": {k: int(np.prod(v.shape)) * v.dtype.itemsize
                                for k, v in eng.cache.items()}}


def _window(before: dict, after: dict, config) -> dict:
    """The window's counters (``stats()`` after it minus ``stats()`` after
    warm-up) as the readers' facts: ``serve_dsa._window``'s expert keys for
    the experts HELD here, the identity experts' (``moe_zero_choices``: the
    (token, choice) pairs that fell on them, and their share of all the
    pairs the router made), and the latent rows visible and read over all
    cache layers.  ``moe_dropped`` is what no-drop routing keeps at 0: pairs
    counted on held and identity experts beyond what was routed."""
    import numpy as np

    tokens = np.asarray(after["moe_expert_tokens"]) - np.asarray(before["moe_expert_tokens"])
    delta = {k: after[k] - before[k] for k in after
             if k.startswith("mla_") or (k.startswith("moe_") and k.endswith("_total"))}
    steps, touched = delta["moe_layer_steps_total"], delta["moe_experts_touched_total"]
    if steps <= 0 or tokens.sum() <= 0:
        raise RuntimeError("the expert layer counted no layer-step in the window")
    routed, zero = delta["moe_routed_pairs_total"], delta["moe_zero_choices_total"]
    return {
        "moe_layer_steps": int(steps),
        "moe_assignments": int(tokens.sum()),
        "moe_dropped": int(max(0, tokens.sum() + zero - routed)),
        "moe_experts_touched_mean": touched / steps,
        "moe_rows_per_layer_step_mean": float(tokens.sum()) / steps,
        "moe_expert_load_max_over_mean": float(tokens.max() / tokens.mean()),
        "moe_routed_assignments": int(routed),
        "moe_held_assignment_share": 100.0 * float(tokens.sum()) / routed,
        "moe_zero_choices": int(zero),
        "moe_zero_choice_share": 100.0 * zero / routed,
        "moe_real_choices_per_token_mean": config.experts_per_token * (1.0 - zero / routed),
        "decode_steps_in_window": int(after["decode_steps_total"] - before["decode_steps_total"]),
        "prefills_in_window": int(after["admitted_total"] - before["admitted_total"]),
        "mla_keys_visible_step": delta["mla_keys_visible_step"],
        "mla_keys_read_step": delta["mla_keys_read_step"],
    }


def _exchanged() -> dict:
    """What ``run`` puts in place of ``serve_moe``'s own while its ``run``
    runs."""
    return {"moe_config": scmoe_config, "MoeReplica": ScmoeReplica,
            "make_weights": make_weights, "_moe_window": _window,
            "REHEARSAL_MODEL": REHEARSAL_MODEL,
            "loadgen": serve_dsa._InTurn(loadgen)}


def run(ctx: dict) -> dict:
    """``serve_moe.run`` with its hard-wired parts exchanged; then, for a
    traced run, the double layer's parts' device time."""
    tolerance = dict(ctx["config"]["reference_tolerance"])
    lens = [16, 32] if ctx["rehearse"] else loadgen.prompt_lengths(ctx["traffic"])
    tolerance["check_prompt_lens"] = lens
    if ctx["rehearse"]:
        tolerance["check_steps"] = 4
    if ctx["trace"]:
        tolerance.update(
            scope_file=os.path.join(ctx["trace_dir"], scmoe_trace.SCOPE_FILE),
            scope_prompt_lens=lens,
        )
    config = dict(ctx["config"], reference_tolerance=tolerance)
    with mock.patch.multiple(serve_moe, **_exchanged()):
        job = serve_moe.run(dict(ctx, config=config))
    job["facts"]["model"] = model_facts(ctx["config"])
    if ctx["trace"] and os.path.isdir(ctx["trace_dir"]):
        job["facts"].update(scmoe_trace.facts(ctx["trace_dir"]))
    return job
