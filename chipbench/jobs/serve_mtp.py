"""Job kind ``serve_mtp``: the ``serve_moe`` job for a latent-attention
decoder served with its multi-token-prediction (MTP) module drafting one
token a step (JoyAI-LLM-Flash through ``LlamaConfig``, one chip's share of
an expert-parallel layer).

Same path — ``serve.run`` of a decode replica, requests through the
deployment handle's streaming path, ``LLMEngine`` on the chip — same load,
same stamps, same facts keys: ``run`` IS ``serve_moe.run`` with what that
file hard-wires exchanged, as ``jobs/serve_dsa.py`` does it and with its
helpers where they fit (``balance_router``, ``check_prompt``, ``_InTurn``).
What differs is that a step yields a data-dependent number of tokens a row,
so the comparison that decides ``correct`` (``MtpReplica.check_reference``,
``against_reference``, ``passes``) covers, in the engine's own cache and
with the two executables the window drives (``models/mtp.py:
prefill_into_slot`` / ``decode_step_rowwise``, which hand back what they
decided from):

(i)   the main model's logits at every verified position — both positions
      of an accepted draft, the first of a rejected one, and so the
      positions reached AFTER a rejection, whose cache row a rejected draft
      had written — against the float32 reference's full forward over the
      sequence that was finally emitted;
(ii)  the module's draft logits against the reference's module forward;
(iii) the reference's acceptance rule replayed on the program's own logits
      and keys gives the program's draft, accepted flag and ids exactly;
(iv)  both outcomes occurred among the checked (row, step)s;
(v)   the routing counters show no held (token, expert) pair uncomputed
      (``_window``: ``moe_dropped``).

The module asks the program for its fields when it is IMPORTED, which
``run.py`` does before it starts a cluster: a program without them (a
commit from before the module) fails there, at once, and no chip is leased.
"""

from __future__ import annotations

import dataclasses
import functools
import inspect
import json
import os
from unittest import mock

from chipbench import loadgen, mtp_trace
from chipbench.jobs import serve_dsa, serve_moe
from chipbench.jobs.serve_llm import BenchReplica
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.serve.llm import LLMEngine

MTP_FIELDS = (
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "index_topk", "first_dense_layers", "shared_expert_dim",
    "router_scoring", "router_norm_topk", "router_scale", "experts_held",
    "expert_offset", "mtp_layers",
)
_missing = set(MTP_FIELDS) - {f.name for f in dataclasses.fields(LlamaConfig)}
_missing |= {"speculative_tokens", "temperature", "seed"} - set(
    inspect.signature(LLMEngine.__init__).parameters)
if _missing:
    raise RuntimeError(
        f"this program has no {sorted(_missing)}: it cannot serve a model "
        "with its multi-token-prediction module drafting"
    )

#: the check rows' requests, far from the numbers the engine deals out
CHECK_REQUEST = 1 << 30
#: ids of the prompts the router's bias is balanced on
BALANCE_PROMPT_LEN = 2048

REHEARSAL_MODEL = {
    "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "vocab_size": 512,
    "q_lora_rank": 32, "kv_lora_rank": 24, "qk_nope_head_dim": 12,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "n_routed_experts_published": 16,
    "num_experts_per_tok": 4,
}


def joyai_config(cfg: dict):
    """The configuration file's keys -> the program's LlamaConfig.
    ``n_routed_experts`` is how many experts are HELD here, from
    ``expert_offset``; the router's width is ``n_routed_experts_published``."""
    import jax.numpy as jnp

    if cfg["n_group"] != 1 or cfg["topk_group"] != 1 or cfg["scoring_func"] != "sigmoid":
        raise RuntimeError("the program routes sigmoid scores without group limits only")
    if cfg["rope_scaling"] is not None or not cfg["rope_interleave"]:
        raise RuntimeError("the program turns interleaved pairs without scaling only")
    return LlamaConfig(
        vocab_size=cfg["vocab_size"], max_seq_len=cfg["max_position_embeddings"],
        num_layers=cfg["num_hidden_layers"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_attention_heads"], embed_dim=cfg["hidden_size"],
        mlp_dim=cfg["intermediate_size"], rope_theta=float(cfg["rope_theta"]),
        rms_eps=cfg["rms_norm_eps"], dtype=getattr(jnp, cfg["dtype"]),
        param_dtype=getattr(jnp, cfg["param_dtype"]), sliding_window=0,
        tie_embeddings=cfg["tie_word_embeddings"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        index_topk=0, first_dense_layers=cfg["first_k_dense_replace"],
        num_experts=cfg["n_routed_experts_published"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_dim=cfg["moe_intermediate_size"],
        shared_expert_dim=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        router_scoring="sigmoid", router_norm_topk=cfg["norm_topk_prob"],
        router_scale=float(cfg["routed_scaling_factor"]),
        experts_held=cfg["n_routed_experts"], expert_offset=cfg["expert_offset"],
        mtp_layers=cfg["num_nextn_predict_layers"],
    )


def spec_of(config):
    """What the reference needs beside the parameter tree."""
    from chipbench.reference import joyai_mtp

    return joyai_mtp.Spec(
        float(config.rope_theta), float(config.rms_eps), config.qk_rope_head_dim,
        config.experts_per_token, config.router_norm_topk,
        float(config.router_scale), config.expert_offset,
    )


def make_weights(cfg: dict, seed: int, rehearse: bool):
    """``weights_loader``: as ``serve_dsa.make_weights``: one jitted
    ``llama.init`` on the device in the type that is served, then the main
    model's selection bias balanced by ``serve_dsa.balance_router`` (in a
    scratch cache of one row)."""
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
    config = joyai_config(cfg)
    params = jax.block_until_ready(
        jax.jit(functools.partial(llama.init, config=config))(jax.random.key(seed % (2**31)))
    )
    length, prompt_len = (256, 24) if rehearse else (
        cfg["serving"]["max_len"], BALANCE_PROMPT_LEN)
    params, _ = serve_dsa.balance_router(
        params, config, seed, llama.init_cache(config, 1, length), prompt_len
    )
    return jax.block_until_ready(params)


def system_run(engine, seed: int, prompt_lens, steps: int) -> dict:
    """One check prompt of each of ``prompt_lens`` into the engine's cache
    rows 0, 1, .. by ``mtp.prefill_into_slot`` and ``steps`` calls of
    ``mtp.decode_step_rowwise`` over the whole batch — THE TWO EXECUTABLES
    THE ENGINE SERVES WITH, in the cache and on the rows' state it then
    serves from.  -> {"rows": [per check row {"request", "prompt", "seq":
    prompt + every emitted token, "experts": (expert layers, N, k) and
    "module_experts": (M, k) what each position / pair chose, "steps":
    [{"pos", "draft", "accepted", "count", "tokens", "p_logits" (2, V),
    "q_logits" (V,)}]}]}."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import mtp

    eng, cfg, T = engine, engine.config, engine.temperature
    cache, state = eng.cache, eng._spec
    rows = []
    for r, n in enumerate(prompt_lens):
        prompt = serve_dsa.check_prompt(cfg, seed + r, n)
        first, cache, state, detail = mtp.prefill_into_slot(
            eng.params, jnp.asarray([prompt], jnp.int32), cache, jnp.int32(r), state,
            eng._key, jnp.int32(CHECK_REQUEST + r), jnp.int32(2 * steps + 2), cfg, T,
        )
        rows.append({
            "request": CHECK_REQUEST + r, "prompt": prompt,
            "seq": prompt + [int(first)], "steps": [],
            "experts": list(np.asarray(detail["experts"])[:, 0].swapaxes(0, 1)),
            "module_experts": list(np.asarray(detail["module_experts"])[0]),
        })
    for _ in range(steps):
        outs, state, cache, detail = mtp.decode_step_rowwise(
            eng.params, state, cache, eng._key, cfg, T
        )
        # the check rows' part of it; ``experts`` has the layers in front
        part = {k: np.asarray(v[:, :len(rows)] if k == "experts" else v[:len(rows)])
                for k, v in detail.items()}
        outs = np.asarray(outs[:len(rows)])
        for r, row in enumerate(rows):
            n, count, accepted = int(part["pos"][r]), int(outs[r, 2]), bool(outs[r, 3])
            if n != len(row["seq"]) - 1:
                raise RuntimeError(f"row {r} is at {n}, its tokens end at {len(row['seq']) - 1}")
            # entry i of a list: position i's (pair i's) choices, the newest
            # in place of what a rejected draft or an earlier pass left there
            row["experts"][n:] = [part["experts"][:, r, j] for j in range(1 + accepted)]
            first = max(n - 2, 0)
            row["module_experts"][first:] = list(part["module_experts"][r, first - (n - 2):])
            row["steps"].append({
                "pos": n, "draft": int(part["draft"][r]), "accepted": accepted,
                "count": count, "tokens": outs[r, :count].tolist(),
                "p_logits": part["p_logits"][r], "q_logits": part["q_logits"][r],
            })
            row["seq"] += outs[r, :count].tolist()
    # the check rows are empty slots again for the engine
    eng.cache, eng._spec = cache, dict(state, left=jnp.zeros_like(state["left"]))
    return {"rows": rows}


def against_reference(params, config, key, temperature: float, out: dict) -> dict:
    """What ``system_run`` recorded against the float32 reference, the
    reference GIVEN the system's expert choices (``reference/joyai_mtp.py``
    says why).  {"err": rms and max of |main logits - reference| / std over
    every verified position, "draft_err": the same of the module's logits,
    "replay_mismatches": (row, step)s at which the reference's acceptance
    rule on the program's logits and keys gives another draft, flag or id,
    "accepted" / "rejected": how many of each among the checked (row,
    step)s, "swap_rate" / "swapped_margin_max" / "margin_p50": as
    ``serve_dsa.against_reference``, over the main model's and the module's
    expert layers}."""
    import jax.numpy as jnp
    import numpy as np

    from chipbench.reference import errors
    from chipbench.reference import joyai_mtp as reference

    spec = spec_of(config)
    got_main, want_main, got_draft, want_draft = [], [], [], []
    swapped, margins, mismatches, accepted = [], [], 0, 0
    for row in out["rows"]:
        last = row["steps"][-1]
        through = last["pos"] + 1 + last["accepted"]   # tokens the main model took
        tokens = jnp.asarray(row["seq"][:through], jnp.int32)
        experts = np.stack(row["experts"][:through], axis=1)          # (Le, N, k)
        hidden, info = reference.forward(params, tokens, spec, jnp.asarray(experts))
        pairs = last["pos"]                                           # pairs 0 .. pos - 1
        module_experts = np.stack(row["module_experts"][:pairs])
        y, module_info = reference.module_forward(
            params, hidden, tokens[:pairs + 1], spec, jnp.asarray(module_experts))
        at = [s["pos"] + j for s in row["steps"] for j in range(1 + s["accepted"])]
        want_main.append(np.asarray(reference.logits(params, hidden[jnp.asarray(at)])))
        got_main.append(np.stack(
            [s["p_logits"][j] for s in row["steps"] for j in range(1 + s["accepted"])]))
        at = [s["pos"] - 1 for s in row["steps"]]
        want_draft.append(np.asarray(reference.logits(params, y[jnp.asarray(at)])))
        got_draft.append(np.stack([s["q_logits"] for s in row["steps"]]))
        for mine, theirs in ((experts, info), (module_experts[None], {
                k: v[None] for k, v in module_info.items()})):
            swapped.append((np.sort(mine, -1)
                            != np.sort(np.asarray(theirs["experts"]), -1)).any(-1).ravel())
            margins.append(np.asarray(theirs["expert_margin"]).ravel())
        for s in row["steps"]:
            accepted += s["accepted"]
            if temperature <= 0.0:
                continue
            drafted = reference.draft(
                s["q_logits"], key, row["request"], s["pos"] + 1, temperature)
            ok, ids = reference.accept(
                s["p_logits"], s["q_logits"], drafted, key, row["request"],
                s["pos"] + 1, temperature)
            mismatches += (
                drafted != s["draft"] or ok != s["accepted"]
                or ids[:1 + ok][:s["count"]] != s["tokens"]
            )
    swapped, margins = np.concatenate(swapped), np.concatenate(margins)
    checked = sum(len(row["steps"]) for row in out["rows"])
    return {
        "err": errors(np.concatenate(got_main), np.concatenate(want_main)),
        "draft_err": errors(np.concatenate(got_draft), np.concatenate(want_draft)),
        "replay_mismatches": int(mismatches),
        "accepted": int(accepted), "rejected": int(checked - accepted),
        "swap_rate": float(swapped.mean()),
        "swapped_margin_max": float(margins[swapped].max()) if swapped.any() else 0.0,
        "margin_p50": float(np.median(margins)),
    }


def passes(got: dict, tolerance: dict) -> bool:
    """The comparison that decides ``correct``: the main model's logits
    within rms and max, the module's within theirs, the replay exact, both
    outcomes of a draft seen (``outcomes_min`` times each, 1 unless the
    tolerance says otherwise), and the routers' choices (the logits are
    compared under the system's own, so they are held to account apart)
    within the share swapped and the largest margin overturned (the limits
    and the readings they lie between: PERF.md section 4)."""
    from chipbench.reference import within

    return bool(
        within(got["err"], tolerance)
        and within(got["draft_err"],
                   {"rms": tolerance["draft_rms"], "max": tolerance["draft_max"]})
        and got["replay_mismatches"] == 0
        and min(got["accepted"], got["rejected"]) >= tolerance.get("outcomes_min", 1)
        and got["swap_rate"] <= tolerance["swap_rate_max"]
        and got["swapped_margin_max"] <= tolerance["swapped_margin_max"]
    )


class MtpReplica(BenchReplica):
    """``BenchReplica`` that drafts, compared with the JoyAI reference."""

    def __init__(self, config=None, weights_loader=None, max_slots: int = 4,
                 max_len: int = 256):
        # the deployment's sampling options are the configuration file's,
        # which ``serve_moe.run`` hands to the weights' loader alone
        cfg, seed, _rehearse = weights_loader.args
        super().__init__(
            config=config, weights_loader=weights_loader, max_slots=max_slots,
            max_len=max_len, seed=seed % (2**31),
            speculative_tokens=cfg["serving"]["speculative_tokens"],
            temperature=cfg["serving"]["temperature"],
        )

    def check_reference(self, seed: int, tolerance: dict) -> dict:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.models import mtp

        eng, cfg = self.engine, self.config
        live = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use")
        lens = tolerance["check_prompt_lens"]
        out = system_run(eng, seed, lens, int(tolerance["check_steps"]))
        got = against_reference(eng.params, cfg, eng._key, eng.temperature, out)
        # the decode program again, for its temporaries and its text (the
        # jitted call above keeps no handle on its executable)
        decode = mtp.decode_step_rowwise.lower(
            eng.params, eng._spec, eng.cache, eng._key, cfg, eng.temperature
        ).compile()
        temp = decode.memory_analysis().temp_size_in_bytes
        if tolerance.get("scope_file"):
            versions = {
                "decode_step_rowwise": [mtp_trace.version(decode.as_text())],
                "prefill_into_slot": [
                    mtp_trace.version(mtp.prefill_into_slot.lower(
                        eng.params, jnp.zeros((1, n), jnp.int32), eng.cache,
                        jnp.int32(0), eng._spec, eng._key, jnp.int32(0), jnp.int32(2),
                        cfg, eng.temperature,
                    ).compile().as_text())
                    for n in tolerance["scope_prompt_lens"]
                ],
            }
            with open(tolerance["scope_file"], "w") as f:
                json.dump(versions, f)
        print(f"[serve_mtp] reference check at {lens} + {tolerance['check_steps']} "
              f"steps: {got}", flush=True)
        return {**got, "tol": tolerance, "ok": passes(got, tolerance),
                "live_bytes": live, "decode_temp_bytes": temp,
                "cache_bytes": {k: int(np.prod(v.shape)) * v.dtype.itemsize
                                for k, v in eng.cache.items()}}


def _window(before: dict, after: dict, config) -> dict:
    """The window's counters (``stats()`` after it minus ``stats()`` after
    warm-up) as the readers' facts: ``serve_dsa._window``'s expert keys
    (``moe_dropped`` among them), and the speculative steps': drafts,
    acceptances, tokens, wasted row-steps, latent rows visible and read."""
    import numpy as np

    tokens = np.asarray(after["moe_expert_tokens"]) - np.asarray(before["moe_expert_tokens"])
    steps = after["moe_layer_steps_total"] - before["moe_layer_steps_total"]
    touched = after["moe_experts_touched_total"] - before["moe_experts_touched_total"]
    if steps <= 0 or tokens.sum() <= 0:
        raise RuntimeError("the expert layer counted no layer-step in the window")
    rows = after["rows_stepped_total"] - before["rows_stepped_total"]
    routed = rows * tokens.shape[0] * config.experts_per_token
    delta = {k: after[k] - before[k] for k in after if k.startswith(("spec_", "mla_"))}
    if not delta.get("spec_drafted_total"):
        raise RuntimeError("no speculative step drafted for a live row in the window")
    decode_steps = after["decode_steps_total"] - before["decode_steps_total"]
    return {
        "moe_layer_steps": int(steps),
        "moe_assignments": int(tokens.sum()),
        "moe_dropped": int(max(0, tokens.sum() - routed)),
        "moe_experts_touched_mean": touched / steps,
        "moe_rows_per_layer_step_mean": float(tokens.sum()) / steps,
        "moe_expert_load_max_over_mean": float(tokens.max() / tokens.mean()),
        "moe_routed_assignments": int(routed),
        "moe_held_assignment_share": 100.0 * float(tokens.sum()) / routed,
        "decode_steps_in_window": int(decode_steps),
        **delta,
        "spec_acceptance_rate": 100.0 * delta["spec_accepted_total"]
        / delta["spec_drafted_total"],
        "spec_tokens_per_row_step_mean": delta["spec_tokens_emitted_total"]
        / delta["spec_drafted_total"],
    }


def _exchanged() -> dict:
    """What ``run`` puts in place of ``serve_moe``'s own while its ``run``
    runs."""
    return {"moe_config": joyai_config, "MoeReplica": MtpReplica,
            "make_weights": make_weights, "_moe_window": _window,
            "REHEARSAL_MODEL": REHEARSAL_MODEL,
            "loadgen": serve_dsa._InTurn(loadgen)}


def run(ctx: dict) -> dict:
    """``serve_moe.run`` with its hard-wired parts exchanged; then, for a
    traced run, the module's and the attention's device time."""
    tolerance = dict(ctx["config"]["reference_tolerance"])
    lens = [16, 32] if ctx["rehearse"] else loadgen.prompt_lengths(ctx["traffic"])
    tolerance["check_prompt_lens"] = lens
    if ctx["rehearse"]:
        # a toy model's two heads agree: every draft is accepted
        tolerance.update(check_steps=4, outcomes_min=0)
    if ctx["trace"]:
        tolerance.update(
            scope_file=os.path.join(ctx["trace_dir"], mtp_trace.SCOPE_FILE),
            scope_prompt_lens=lens,
        )
    config = dict(ctx["config"], reference_tolerance=tolerance)
    with mock.patch.multiple(serve_moe, **_exchanged()):
        job = serve_moe.run(dict(ctx, config=config))
    job["facts"]["model"] = {
        k: v for k, v in ctx["config"].items() if isinstance(v, (int, float))}
    if ctx["trace"] and os.path.isdir(ctx["trace_dir"]):
        job["facts"].update(mtp_trace.facts(ctx["trace_dir"]))
    return job
