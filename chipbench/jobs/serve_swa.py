"""Job kind ``serve_swa``: the ``serve_moe`` job for a decoder whose attention
layers are of two KINDS — full attention, and a sliding window of 128 keys
with a learned sink — each with its own K/V shape and its own cache, over
sigmoid-routed experts of which this chip holds a share (MiMo-V2-Flash
through ``LlamaConfig``).

Same path — ``serve.run`` of a decode replica, requests through the
deployment handle's streaming path, ``LLMEngine`` on the chip — same load,
same stamps, same facts keys: ``run`` IS ``serve_moe.run`` with what that
file hard-wires exchanged, as ``jobs/serve_dsa.py``, ``jobs/serve_mtp.py`` and
``jobs/serve_scmoe.py`` do it and with their helpers where they fit
(``check_prompt``, ``_InTurn``, ``_rows``).  The comparison that decides
``correct`` (``SwaReplica.check_reference``, ``system_run``,
``against_reference``, ``passes``) is made on the chip, at the served widths,
in the engine's own cache and on what the two served executables
(``llama.prefill_into_slot`` / ``llama.decode_step_rowwise``) produce: one
check row a prompt length of the traffic (2,048 and 12,288 ids: both past
two turns of the 128 rolling slots), prefilled and then decoded
``check_steps`` steps through the cache,

(i)   their logits at the prompt's last position and at every step against
      the float32 reference's full forward (``chipbench/reference/
      mimo_v2_flash.py``) of the same tokens, GIVEN the system's choices;
(ii)  the routing choice by choice: the share of (layer, token) pairs whose
      chosen set is not the reference's own, and the largest reference
      margin among those;
(iii) the choices being the served programs' as far as a second executable
      can say (``jobs/serve_scmoe.py:system_run`` says why no more can be
      asked): the choices-returning twin's logits theirs within rounding,
      and the two pairs of programs counting the same held choices but for
      a handful of near ties;
(iv)  held experts among the checked tokens' choices;
(v)   no held pair uncomputed: the counter the twin carried through the
      check is exactly the count of the choices it handed back, and
      (``_window``: ``moe_dropped``) none is over in the window.

The module asks the program for its fields when it is IMPORTED, which
``run.py`` does before it starts a cluster: a program without them (a commit
from before attention kinds) fails there, at once, and no chip is leased.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
from unittest import mock

from chipbench import loadgen, swa_trace
from chipbench.jobs import serve_dsa, serve_moe, serve_scmoe
from chipbench.jobs.serve_llm import BenchReplica
from ray_tpu.models import llama
from ray_tpu.models.llama import LlamaConfig

SWA_FIELDS = (
    "sliding", "rotary_dim", "value_scale", "v_head_dim", "head_dim", "layer_types",
    "first_dense_layers", "router_scoring", "router_norm_topk", "experts_held",
    "expert_offset",
)
_missing = set(SWA_FIELDS) - {f.name for f in dataclasses.fields(LlamaConfig)}
_missing |= {n for n in ("AttentionKind", "SLIDING", "FULL") if not hasattr(llama, n)}
if _missing:
    raise RuntimeError(
        f"this program's models/llama.py has no {sorted(_missing)}: it cannot run "
        "a configuration whose attention layers are of two kinds"
    )

#: ids of the prompts the router's bias is balanced on
BALANCE_PROMPT_LEN = 2048

REHEARSAL_MODEL = {
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 1, "swa_num_key_value_heads": 2,
    "head_dim": 12, "v_head_dim": 8, "sliding_window": 8, "vocab_size": 512,
    "n_routed_experts": 4, "n_routed_experts_published": 16, "num_experts_per_tok": 2,
}


def swa_config(cfg: dict):
    """The configuration file's keys -> the program's LlamaConfig.  The
    layers are the published layers ``layers_kept`` names, each of the kind
    ``hybrid_layer_pattern`` gives it (0 full, 1 window) and dense or expert
    by ``moe_layer_freq``; ``n_routed_experts`` is how many experts are HELD
    here, from ``expert_offset``, of the ``n_routed_experts_published`` the
    router routes over."""
    import jax.numpy as jnp

    kept = cfg["layers_kept"]
    dense = [i for i in kept if not cfg["moe_layer_freq"][i]]
    if (len(kept) != cfg["num_hidden_layers"] or dense != kept[:len(dense)]
            or cfg["scoring_func"] != "sigmoid" or cfg["n_shared_experts"]
            or cfg["n_group"] != 1 or cfg["add_full_attention_sink_bias"]):
        raise RuntimeError(
            "the program runs num_hidden_layers kept layers, the dense ones "
            "leading, a sigmoid router of one group, no shared expert and no "
            "sink on the full layers")
    return LlamaConfig(
        vocab_size=cfg["vocab_size"], max_seq_len=cfg["max_position_embeddings"],
        num_layers=len(kept), num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], embed_dim=cfg["hidden_size"],
        mlp_dim=cfg["intermediate_size"], rope_theta=float(cfg["rope_theta"]),
        rms_eps=cfg["layernorm_epsilon"], dtype=getattr(jnp, cfg["dtype"]),
        param_dtype=getattr(jnp, cfg["param_dtype"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        head_dim=cfg["head_dim"], v_head_dim=cfg["v_head_dim"],
        rotary_dim=int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
        value_scale=float(cfg["attention_value_scale"]),
        layer_types=tuple(
            llama.SLIDING if cfg["hybrid_layer_pattern"][i] else llama.FULL for i in kept),
        sliding=llama.AttentionKind(
            num_kv_heads=cfg["swa_num_key_value_heads"],
            rope_theta=float(cfg["swa_rope_theta"]), window=cfg["sliding_window"],
            sink=bool(cfg["add_swa_attention_sink_bias"])),
        first_dense_layers=len(dense), num_experts=cfg["n_routed_experts_published"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_dim=cfg["moe_intermediate_size"], router_scoring="sigmoid",
        router_norm_topk=bool(cfg["norm_topk_prob"]),
        router_scale=float(cfg["routed_scaling_factor"] or 1.0),
        experts_held=cfg["n_routed_experts"], expert_offset=cfg["expert_offset"],
    )


def spec_of(config, **bent):
    """What the reference needs beside the parameter tree; ``bent``: one of
    its numbers another than the configuration's (a left-out piece)."""
    from chipbench.reference import mimo_v2_flash

    spec = mimo_v2_flash.Spec(
        tuple(config.layer_types), config.first_dense_layers,
        float(config.rope_theta), float(config.sliding.rope_theta),
        config.sliding.window, config.rotary_dim, float(config.value_scale),
        float(config.rms_eps), config.experts_per_token, config.expert_offset,
        bool(config.sliding.sink),
    )
    return spec._replace(**bent)


def model_facts(cfg: dict) -> dict:
    """The configuration's numbers and its kept layers' lists as the readers'
    ``facts["model"]`` (``chipbench/swa_cost.py`` counts from them)."""
    keep = ("layers_kept", "hybrid_layer_pattern", "moe_layer_freq",
            "add_swa_attention_sink_bias")
    return {k: v for k, v in cfg.items()
            if k in keep or (isinstance(v, (int, float)) and not isinstance(v, bool))}


def expert_stacks(config) -> list:
    """[(the tree's stack, the layer's index in it)] of the expert layers, in
    layer order: the order of every (expert layers, ..) array the program
    hands back."""
    seen, out = {}, []
    for i, kind in enumerate(config.layer_types):
        name = "swa_blocks" if kind == llama.SLIDING else "blocks"
        if i >= config.first_dense_layers:
            out.append((name, seen.get(name, 0)))
            seen[name] = seen.get(name, 0) + 1
    return out


def draw_sinks(params, config, seed: int):
    """``params`` with every window layer's sinks drawn so that a sink takes
    20-50% of a window query's mass: at seeded weights the scores lie near 0,
    so 128 visible keys weigh 128 and a sink b weighs e^b: b uniform between
    ln(window / 4) and ln(window).  A sink near 0 (as ``llama.init`` leaves
    it) would weigh 1 in 129, and a left-out sink would hide inside the
    comparison's tolerance."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    stack = params["swa_blocks"]
    w = config.sliding.window
    sink = jax.random.uniform(
        jax.random.fold_in(jax.random.key(seed % (2**31)), 55), stack["sink"].shape,
        jnp.float32, np.log(w / 4.0), np.log(float(w)))
    return dict(params, swa_blocks=dict(stack, sink=sink.astype(stack["sink"].dtype)))


def balance_router(params, config, seed: int, cache, prompt_len: int):
    """``serve_dsa.balance_router``'s procedure (its schedule and its slope:
    a sigmoid router of the same draw) over the expert layers of BOTH
    stacks: the selection bias moved to where every expert is chosen equally
    often, as a trained checkpoint's are.  Returns (params, cache)."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng([seed % (2**63), 29])
    stacks = expert_stacks(config)
    bias = {name: np.array(params[name]["router_bias"], np.float32)
            for name in dict(stacks)}
    even = prompt_len * config.experts_per_token / config.num_experts
    loads = []
    for gain in [g for n, g in serve_dsa.BALANCE_SCHEDULE for _ in range(n)]:
        prompt = rng.integers(0, config.vocab_size, (1, prompt_len))
        _, cache, chose = llama.choices_cached(
            params, jnp.asarray(prompt, jnp.int32), cache, jnp.int32(0), None, config
        )
        experts = np.asarray(chose["experts"])[:, 0].reshape(len(stacks), -1)
        load = np.stack([np.bincount(e, minlength=config.num_experts) for e in experts])
        loads.append(load)
        for (name, at), row in zip(stacks, load):
            bias[name][at] += gain / serve_dsa.BALANCE_SLOPE * np.log(even / (row + 1.0))
        params = dict(params, **{
            name: dict(params[name], router_bias=jnp.asarray(
                b, params[name]["router_bias"].dtype)) for name, b in bias.items()})
    first, last = loads[0] / even, np.mean(loads[-4:], axis=0) / even
    print(f"[serve_swa] router bias balanced on {len(loads)} prompts of {prompt_len}: "
          f"load / even sd {first.std():.3f} -> {last.std():.3f} (last four prompts' "
          f"mean), at most {np.abs(np.concatenate(list(bias.values()))).max():.4f}",
          flush=True)
    return params, cache


def make_weights(cfg: dict, seed: int, rehearse: bool):
    """``weights_loader``: as ``serve_scmoe.make_weights``: one jitted
    ``llama.init`` on the device in the type that is served, then the sinks
    drawn (``draw_sinks``) and the selection bias balanced
    (``balance_router``, in a scratch cache of one row)."""
    import jax

    from ray_tpu.util import compile_cache

    compile_cache.configure()
    dev = jax.devices()[0]
    if not rehearse and dev.platform != "tpu":
        raise RuntimeError(
            f"the replica was leased a TPU chip but jax came up on platform "
            f"{dev.platform!r}; there is no CPU fallback"
        )
    config = swa_config(cfg)
    params = jax.block_until_ready(
        jax.jit(functools.partial(llama.init, config=config))(jax.random.key(seed % (2**31)))
    )
    params = draw_sinks(params, config, seed)
    length, prompt_len = (256, 24) if rehearse else (
        cfg["serving"]["max_len"], BALANCE_PROMPT_LEN)
    params, _ = balance_router(
        params, config, seed, llama.init_cache(config, 1, length), prompt_len
    )
    return jax.block_until_ready(params)


def system_run(params, config, cache, max_slots: int, prompts, steps: int):
    """``serve_scmoe.system_run`` for experts without identity experts among
    them: the check rows through ``llama.prefill_into_slot`` and ``steps``
    greedy ``llama.decode_step_rowwise`` steps in ``cache`` (slots 0, 1, ..)
    — THE TWO EXECUTABLES THE ENGINE SERVES WITH, in the cache it then serves
    from; their logits are what is compared — then the same tokens through
    the same slots once more by ``llama.choices_cached`` for the router's
    choices, with that file's three readings of how far the twin may be
    trusted (``twin_err``, ``twin_pairs_miscounted``, ``served_pairs_off``).
    Returns (cache, {"rows": [per check row {"prompt", "seq", "logits": (1 +
    steps, V), "experts": (expert layers, len(seq), k)}], ...})."""
    import jax.numpy as jnp
    import numpy as np

    from chipbench.reference import errors

    def counted(c):
        return np.asarray(c["moe_expert_tokens"]).astype(np.int64)

    before = counted(cache)
    cache, seqs, served, _ = serve_scmoe._rows(
        llama.prefill_into_slot, llama.decode_step_rowwise,
        params, config, cache, max_slots, prompts, steps,
    )
    between = counted(cache)
    cache, _, twin, calls = serve_scmoe._rows(
        lambda p, t, c, slot, cfg: llama.choices_cached(p, t, c, slot, None, cfg),
        lambda p, t, c, pos, cfg: llama.choices_cached(p, t, c, None, pos, cfg),
        params, config, cache, max_slots, prompts, steps,
        then=[seq[len(p):] for seq, p in zip(seqs, prompts)],
    )
    after = counted(cache)
    chose = [np.asarray(rest[0]["experts"]) for rest in calls]      # (Le, R, Sq, k) a call
    n = len(prompts)
    experts = [np.concatenate([chose[r][:, 0]] + [c[:, r] for c in chose[n:]], axis=1)
               for r in range(n)]
    # what the twin's calls routed, by layer: every row of every call
    routed = np.concatenate([c.reshape(c.shape[0], -1) for c in chose], axis=1)
    held = routed - config.expert_offset
    want = np.stack([
        np.bincount(row[(row >= 0) & (row < config.experts_here)],
                    minlength=config.experts_here) for row in held])
    return cache, {
        "rows": [{"prompt": list(p), "seq": seq, "logits": out, "experts": e}
                 for p, seq, out, e in zip(prompts, seqs, served, experts)],
        "twin_pairs_miscounted": int(np.abs(after - between - want).sum()),
        "served_pairs_off": int(np.abs((between - before) - (after - between)).sum()),
        "twin_err": errors(jnp.stack(twin), jnp.stack(served)),
    }


def against_reference(params, config, out: dict, given: bool = True, **bent) -> dict:
    """What ``system_run`` produced against the float32 reference's full
    forward of the same tokens — ``given`` the system's choices (False:
    free-running); ``bent``: the reference with one of its numbers another
    (``spec_of``).  {"err": rms and max of |logits - reference| / std over
    every checked position of every row, "swap_rate": share of (expert
    layer, token) pairs that chose another set of experts than the reference
    did, "swapped_margin_max": the largest margin (8th minus 9th selection
    score, in the reference's float32) among those, "held_choices": how many
    of the checked tokens' choices fell on experts held here, ...}."""
    import jax.numpy as jnp
    import numpy as np

    from chipbench.reference import errors
    from chipbench.reference import mimo_v2_flash as reference

    spec = spec_of(config, **bent)
    got, want, swapped, margins = [], [], [], []
    held = 0
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
        held += int(((here >= 0) & (here < config.experts_here)).sum())
    swapped, margins = np.concatenate(swapped), np.concatenate(margins)
    return {
        "err": errors(np.concatenate(got), np.concatenate(want)),
        "swap_rate": float(swapped.mean()),
        "swapped_margin_max": float(margins[swapped].max()) if swapped.any() else 0.0,
        "margin_p50": float(np.median(margins)),
        "held_choices": held,
        "twin_pairs_miscounted": out["twin_pairs_miscounted"],
        "served_pairs_off": out["served_pairs_off"],
        "twin_err": out["twin_err"],
    }


def passes(got: dict, tolerance: dict) -> bool:
    """The comparison that decides ``correct``: the served programs' logits
    within rms and max; the routers' choices (the logits are compared under
    the system's own, so they are held to account apart) within the share
    swapped and the largest margin overturned; held experts among the
    checked choices; every held pair the twin chose computed as counted,
    exactly; and the choices being the served programs' as far as a second
    executable can say.  The limits and the readings they lie between: the
    configuration's file."""
    from chipbench.reference import within

    return bool(
        within(got["err"], tolerance)
        and got["swap_rate"] <= tolerance["swap_rate_max"]
        and got["swapped_margin_max"] <= tolerance["swapped_margin_max"]
        and got["held_choices"] >= 1
        and got["twin_pairs_miscounted"] == 0
        and got["served_pairs_off"] <= tolerance["served_pairs_off_max"]
        and within(got["twin_err"], {"rms": tolerance["twin_rms"], "max": tolerance["twin_max"]})
    )


def compare(params, config, cache, max_slots: int, seed: int, prompt_lens, steps: int):
    prompts = [serve_dsa.check_prompt(config, seed + r, n) for r, n in enumerate(prompt_lens)]
    cache, out = system_run(params, config, cache, max_slots, prompts, steps)
    return cache, against_reference(params, config, out)


class SwaReplica(BenchReplica):
    """``BenchReplica`` compared with the MiMo-V2-Flash reference."""

    def check_reference(self, seed: int, tolerance: dict) -> dict:
        import jax
        import jax.numpy as jnp
        import numpy as np

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
            # program runs under which scope (``swa_trace``)
            versions = {
                "decode_step_rowwise": [swa_trace.version(decode.as_text())],
                "prefill_into_slot": [
                    swa_trace.version(llama.prefill_into_slot.lower(
                        eng.params, jnp.zeros((1, n), jnp.int32), eng.cache,
                        jnp.int32(0), cfg,
                    ).compile().as_text())
                    for n in tolerance["scope_prompt_lens"]
                ],
            }
            with open(tolerance["scope_file"], "w") as f:
                json.dump(versions, f)
        print(f"[serve_swa] reference check at {lens} + {steps} steps: {got}", flush=True)
        return {**got, "tol": tolerance, "ok": passes(got, tolerance),
                "live_bytes": live, "decode_temp_bytes": temp,
                "cache_bytes": {k: int(np.prod(v.shape)) * v.dtype.itemsize
                                for k, v in eng.cache.items()}}


#: the window's counters of the two attention kinds, as ``stats()`` names them
KIND_COUNTERS = tuple(
    f"{kind}_{what}" for kind in ("full", "swa")
    for what in ("keys_visible_step", "keys_read_step", "pairs_visible_run", "pairs_read_run"))


def _window(before: dict, after: dict, config) -> dict:
    """The window's counters (``stats()`` after it minus ``stats()`` after
    warm-up) as the readers' facts: ``serve_dsa._window``'s expert keys for
    the experts HELD here, and per attention kind the keys the decode steps'
    rows could see and the keys fetched for them, the (query, key) pairs
    inside the prefills' masks and the pairs they computed scores for, all
    summed over the kind's layers.  ``moe_dropped`` is what no-drop routing
    keeps at 0: pairs counted on held experts beyond what was routed."""
    import numpy as np

    tokens = np.asarray(after["moe_expert_tokens"]) - np.asarray(before["moe_expert_tokens"])
    delta = {k: after[k] - before[k] for k in after
             if k in KIND_COUNTERS or (k.startswith("moe_") and k.endswith("_total"))}
    steps, touched = delta["moe_layer_steps_total"], delta["moe_experts_touched_total"]
    if steps <= 0 or tokens.sum() <= 0:
        raise RuntimeError("the expert layer counted no layer-step in the window")
    routed = delta["moe_routed_pairs_total"]
    return {
        "moe_layer_steps": int(steps),
        "moe_assignments": int(tokens.sum()),
        "moe_dropped": int(max(0, tokens.sum() - routed)),
        "moe_experts_touched_mean": touched / steps,
        "moe_rows_per_layer_step_mean": float(tokens.sum()) / steps,
        "moe_expert_load_max_over_mean": float(tokens.max() / tokens.mean()),
        "moe_routed_assignments": int(routed),
        "moe_held_assignment_share": 100.0 * float(tokens.sum()) / routed,
        "decode_steps_in_window": int(after["decode_steps_total"] - before["decode_steps_total"]),
        "prefills_in_window": int(after["admitted_total"] - before["admitted_total"]),
        **{k: delta[k] for k in KIND_COUNTERS},
        "kv_decode_attention": after["kv_decode_attention"],
        "kv_prefill_attention": after["kv_prefill_attention"],
    }


def _exchanged() -> dict:
    """What ``run`` puts in place of ``serve_moe``'s own while its ``run``
    runs."""
    return {"moe_config": swa_config, "MoeReplica": SwaReplica,
            "make_weights": make_weights, "_moe_window": _window,
            "REHEARSAL_MODEL": REHEARSAL_MODEL,
            "loadgen": serve_dsa._InTurn(loadgen)}


def run(ctx: dict) -> dict:
    """``serve_moe.run`` with its hard-wired parts exchanged; then, for a
    traced run, the two attention kinds' device time for the cell's six
    readers (``swa_trace.share``) and the two served programs' medians."""
    tolerance = dict(ctx["config"]["reference_tolerance"])
    lens = [16, 32] if ctx["rehearse"] else loadgen.prompt_lengths(ctx["traffic"])
    tolerance["check_prompt_lens"] = lens
    if ctx["rehearse"]:
        tolerance["check_steps"] = 4
    if ctx["trace"]:
        tolerance.update(
            scope_file=os.path.join(ctx["trace_dir"], swa_trace.SCOPE_FILE),
            scope_prompt_lens=lens,
        )
    config = dict(ctx["config"], reference_tolerance=tolerance)
    with mock.patch.multiple(serve_moe, **_exchanged()):
        job = serve_moe.run(dict(ctx, config=config))
    model = dict(ctx["config"], **(REHEARSAL_MODEL if ctx["rehearse"] else {}))
    job["facts"]["model"] = model_facts(model)
    if ctx["trace"] and os.path.isdir(ctx["trace_dir"]):
        job["facts"].update(swa_trace.facts(ctx["trace_dir"]))
        job["facts"].update(swa_trace.traced_program_ms(ctx["trace_dir"]))
    return job
