"""Job kind ``serve_diffusion``: the ``serve_moe`` job for an expert decoder
that generates by DIFFUSION OVER BLOCKS (SDAR-30B-A3B-Chat through
``LlamaConfig``, one chip's share of an expert-parallel layer).

Same path — ``serve.run`` of a decode replica, requests through the
deployment handle's streaming path, ``LLMEngine`` on the chip — same load,
same stamps, same facts keys: ``run`` IS ``serve_moe.run`` with what that
file hard-wires exchanged, as ``jobs/serve_dsa.py`` and ``jobs/serve_mtp.py``
do it and with their helpers where they fit (``check_prompt``, ``_InTurn``).
What differs is that a step refines a block of tokens in place and a row
gets none of them until the block is committed, so the comparison that
decides ``correct`` (``DiffusionReplica.check_reference``,
``against_reference``, ``passes``) covers, in the engine's own cache and with
the two executables the window drives (``models/block_diffusion.py:
prefill_into_slot`` / ``decode_step_rowwise``, which hand back what they
decided from):

(i)   every pass's logits of the block — refining passes and commits alike —
      against the float32 reference's full forward over [the sequence
      committed so far ; the block as it stood] under the block mask: that
      holds the K/V every later block reads to the blocks' FINAL tokens (a
      step that skipped the commit would leave the last refining pass's K/V,
      of a block with a MASK in it, and the next block's logits would be off)
      and the prefill's to the prompt's;
(ii)  the reference's rule replayed on the program's own logits and keys
      gives the program's candidates, transfers and next block exactly;
(iii) the ids delivered are the blocks as committed, in order;
(iv)  a commit happened for every check row (``commits_min`` of them);
(v)   the routing counters show no held (token, expert) pair uncomputed
      (``_window``: ``moe_dropped``), and no id delivered is the MASK row's
      (``_Slice.request_failed``).

The module asks the program for its fields when it is IMPORTED, which
``run.py`` does before it starts a cluster: a program without them (a
commit from before block diffusion) fails there, at once, and no chip is
leased.
"""

from __future__ import annotations

import dataclasses
import functools
import inspect
import json
import os
from unittest import mock

from chipbench import diffusion_trace, loadgen
from chipbench.jobs import serve_dsa, serve_moe
from chipbench.jobs.serve_llm import BenchReplica
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.serve.llm import LLMEngine

_missing = {"head_dim", "mask_block", "router_norm_topk", "experts_held"} - {
    f.name for f in dataclasses.fields(LlamaConfig)}
_missing |= {"diffusion_block", "denoising_steps", "confidence_threshold"} - set(
    inspect.signature(LLMEngine.__init__).parameters)
if _missing:
    raise RuntimeError(
        f"this program has no {sorted(_missing)}: it cannot serve a model that "
        "generates by diffusion over blocks"
    )

#: the check rows' requests, far from the numbers the engine deals out
CHECK_REQUEST = 1 << 30

REHEARSAL_MODEL = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 32, "num_hidden_layers": 2, "vocab_size": 512,
    "moe_intermediate_size": 32, "num_experts": 4, "num_experts_published": 16,
    "num_experts_per_tok": 4,
}


def sdar_config(cfg: dict):
    """The configuration file's keys -> the program's LlamaConfig.
    ``num_experts`` is how many experts are HELD here, from ``expert_offset``;
    the router's width is ``num_experts_published``."""
    import jax.numpy as jnp

    if cfg["mlp_only_layers"] or cfg["decoder_sparse_step"] != 1:
        raise RuntimeError("the program has an expert layer in every block or in none")
    if cfg["rope_scaling"] is not None or cfg["use_sliding_window"] or cfg["attention_bias"]:
        raise RuntimeError("the program runs plain rotary, full attention, no bias")
    return LlamaConfig(
        vocab_size=cfg["vocab_size"], max_seq_len=cfg["max_position_embeddings"],
        num_layers=cfg["num_hidden_layers"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], embed_dim=cfg["hidden_size"],
        head_dim=cfg["head_dim"], mlp_dim=0, rope_theta=float(cfg["rope_theta"]),
        rms_eps=cfg["rms_norm_eps"], dtype=getattr(jnp, cfg["dtype"]),
        param_dtype=getattr(jnp, cfg["param_dtype"]), sliding_window=0,
        tie_embeddings=cfg["tie_word_embeddings"], qk_norm="head",
        num_experts=cfg["num_experts_published"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_dim=cfg["moe_intermediate_size"],
        router_scoring="softmax", router_norm_topk=cfg["norm_topk_prob"],
        experts_held=cfg["num_experts"], expert_offset=cfg["expert_offset"],
    )


def spec_of(config, block: int):
    """What the reference needs beside the parameter tree."""
    from chipbench.reference import sdar

    return sdar.Spec(float(config.rope_theta), float(config.rms_eps),
                     config.experts_per_token, block, config.expert_offset)


def make_weights(cfg: dict, seed: int, rehearse: bool):
    """``weights_loader``: as ``serve_moe.make_weights``, one jitted
    ``llama.init`` on the device in the type that is served.  The softmax
    router has no selection bias to balance: N(0, 0.02) weights route near
    uniformly."""
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
    params = jax.jit(functools.partial(llama.init, config=sdar_config(cfg)))(
        jax.random.key(seed % (2**31))
    )
    return jax.block_until_ready(params)


def system_run(engine, seed: int, prompt_lens, steps: int) -> dict:
    """One check prompt of each of ``prompt_lens`` into the engine's cache
    rows 0, 1, .. by ``block_diffusion.prefill_into_slot`` and ``steps``
    calls of ``block_diffusion.decode_step_rowwise`` over the whole batch —
    THE TWO EXECUTABLES THE ENGINE SERVES WITH, in the cache and on the rows'
    state it then serves from.  -> {"rows": [per check row {"request",
    "prompt", "skip": the prompt's tokens in the first block, "emitted":
    every id the steps' ``outs`` delivered, "passes": [{"pos", "passes",
    "block" (Bk,), "logits" (Bk, V), "x0", "conf", "transfer" (Bk,),
    "experts" (L, Bk, k), "committed": bool, "ids": what ``outs`` delivered}],
    "prompt_experts": (L, whole, k)}]}."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import block_diffusion

    eng, cfg, T = engine, engine.config, engine.temperature
    settings = eng._step_options["settings"]
    Bk = settings.block
    cache, state = eng.cache, eng._spec
    rows = []
    for r, n in enumerate(prompt_lens):
        # ids from the rows the traffic draws from: never the MASK row
        prompt = np.random.default_rng([(seed + r) % (2**63), 11]).integers(
            0, settings.mask_id, n).tolist()
        _, cache, state, detail = block_diffusion.prefill_into_slot(
            eng.params, jnp.asarray([prompt], jnp.int32), cache, jnp.int32(r), state,
            eng._key, jnp.int32(CHECK_REQUEST + r), jnp.int32(Bk * steps), cfg, T,
            settings,
        )
        rows.append({
            "request": CHECK_REQUEST + r, "prompt": prompt, "skip": n % Bk,
            "emitted": [], "passes": [],
            "prompt_experts": np.asarray(detail["experts"])[:, 0],
        })
    for _ in range(steps):
        outs, state, cache, detail = block_diffusion.decode_step_rowwise(
            eng.params, state, cache, eng._key, cfg, T, settings
        )
        # the check rows' part of it; ``experts`` has the layers in front
        part = {k: np.asarray(v[:, :len(rows)] if k == "experts" else v[:len(rows)])
                for k, v in detail.items()}
        outs = np.asarray(outs[:len(rows)])
        for r, row in enumerate(rows):
            ids = outs[r, :outs[r, Bk]].tolist()
            row["emitted"] += ids
            row["passes"].append({
                "committed": bool(outs[r, Bk + 2]), "ids": ids,
                **{k: part[k][r] for k in ("pos", "passes", "block", "logits", "x0",
                                           "conf", "transfer")},
                "experts": part["experts"][:, r],
            })
    # the check rows are empty slots again for the engine
    eng.cache, eng._spec = cache, dict(state, left=jnp.zeros_like(state["left"]))
    return {"rows": rows}


def against_reference(params, config, key, temperature: float, settings,
                      out: dict) -> dict:
    """What ``system_run`` recorded against the float32 reference, the
    reference GIVEN the system's expert choices (``reference/sdar.py`` says
    why).  {"err": rms and max of |logits - reference| / std over every pass's
    block, "replay_mismatches": (row, pass)s at which the reference's rule on
    the program's own logits and keys gives another candidate, transfer or
    next block, "delivery_mismatches": rows whose delivered ids are not their
    committed blocks in order, "commits_min": the fewest commits a check row
    had, "threshold_passes": passes that transferred by the threshold,
    "swap_rate" / "swapped_margin_max" / "margin_p50": as
    ``serve_dsa.against_reference``, over every position of each row's last
    forward}."""
    import jax.numpy as jnp
    import numpy as np

    from chipbench.reference import errors
    from chipbench.reference import sdar as reference

    Bk, mask = settings.block, settings.mask_id
    per_pass = Bk // settings.denoising_steps
    spec = spec_of(config, Bk)
    got, want, swapped, margins = [], [], [], []
    replay = delivery = by_threshold = 0
    commits = []
    for row in out["rows"]:
        whole = len(row["prompt"]) // Bk * Bk
        done = list(row["prompt"][:whole])              # committed so far
        chose = row["prompt_experts"]                   # (L, len(done), k)
        skip, blocks = row["skip"], []
        for i, step in enumerate(row["passes"]):
            block = step["block"].tolist()
            if step["pos"] != len(done):
                raise RuntimeError(f"a block at {step['pos']} behind {len(done)} tokens")
            experts = np.concatenate([chose, step["experts"]], axis=1)
            ref, info = reference.forward(
                params, jnp.asarray(done + block, jnp.int32), spec, experts,
                rows=list(range(len(done), len(done) + Bk)),
            )
            got.append(step["logits"])
            want.append(np.asarray(ref))
            nxt = row["passes"][i + 1]["block"].tolist() if i + 1 < len(row["passes"]) else None
            if step["committed"]:
                done, chose = done + block, experts
                blocks.append(block[skip:])
                skip = 0
                replay += mask in block or (nxt is not None and nxt != [mask] * Bk)
                continue
            masked = np.asarray(block) == mask
            conf = np.zeros((Bk,), np.float32)
            ok = True
            for j in np.flatnonzero(masked):
                x0, conf[j] = reference.candidate(
                    step["logits"][j], key, row["request"], step["pos"] + int(j),
                    int(step["passes"]), temperature, mask)
                ok &= x0 == step["x0"][j]
            transfer, high = reference.transfers(conf, masked, settings.threshold, per_pass)
            by_threshold += high
            ok &= bool((transfer == step["transfer"]).all())
            if nxt is not None:
                ok &= nxt == np.where(transfer, step["x0"], step["block"]).tolist()
            replay += not ok
        commits.append(len(blocks))
        delivery += row["emitted"] != [t for b in blocks for t in b]
        # the row's last forward saw every position it has
        swapped.append((np.sort(experts, -1)
                        != np.sort(np.asarray(info["experts"]), -1)).any(-1).ravel())
        margins.append(np.asarray(info["expert_margin"]).ravel())
    swapped, margins = np.concatenate(swapped), np.concatenate(margins)
    return {
        "err": errors(np.concatenate(got), np.concatenate(want)),
        "replay_mismatches": int(replay), "delivery_mismatches": int(delivery),
        "commits_min": int(min(commits)), "threshold_passes": int(by_threshold),
        "swap_rate": float(swapped.mean()),
        "swapped_margin_max": float(margins[swapped].max()) if swapped.any() else 0.0,
        "margin_p50": float(np.median(margins)),
    }


def passes(got: dict, tolerance: dict) -> bool:
    """The comparison that decides ``correct``: every pass's logits within
    rms and max, the rule's replay and the delivery exact, a commit for every
    check row, and the routers' choices (the logits are compared under the
    system's own, so they are held to account apart) within the share swapped
    and the largest margin overturned (the limits and the readings they lie
    between: PERF.md section 4)."""
    from chipbench.reference import within

    return bool(
        within(got["err"], tolerance)
        and got["replay_mismatches"] == 0 and got["delivery_mismatches"] == 0
        and got["commits_min"] >= tolerance.get("commits_min", 1)
        and got["swap_rate"] <= tolerance["swap_rate_max"]
        and got["swapped_margin_max"] <= tolerance["swapped_margin_max"]
    )


class DiffusionReplica(BenchReplica):
    """``BenchReplica`` that generates by block diffusion, compared with the
    SDAR reference."""

    def __init__(self, config=None, weights_loader=None, max_slots: int = 4,
                 max_len: int = 256):
        # the deployment's generation settings are the configuration file's,
        # which ``serve_moe.run`` hands to the weights' loader alone
        cfg, seed, _rehearse = weights_loader.args
        serving = cfg["serving"]
        super().__init__(
            config=config, weights_loader=weights_loader, max_slots=max_slots,
            max_len=max_len, seed=seed % (2**31),
            diffusion_block=serving["diffusion_block"],
            denoising_steps=serving["denoising_steps"],
            confidence_threshold=serving["confidence_threshold"],
            temperature=serving["temperature"],
        )

    def check_reference(self, seed: int, tolerance: dict) -> dict:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.models import block_diffusion

        eng, cfg = self.engine, self.engine.config
        settings = eng._step_options["settings"]
        live = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use")
        lens = tolerance["check_prompt_lens"]
        out = system_run(eng, seed, lens, int(tolerance["check_steps"]))
        got = against_reference(eng.params, cfg, eng._key, eng.temperature, settings, out)
        # the decode program again, for its temporaries and its text (the
        # jitted call above keeps no handle on its executable)
        decode = block_diffusion.decode_step_rowwise.lower(
            eng.params, eng._spec, eng.cache, eng._key, cfg, eng.temperature, settings
        ).compile()
        temp = decode.memory_analysis().temp_size_in_bytes
        if tolerance.get("scope_file"):
            versions = {
                "decode_step_rowwise": [diffusion_trace.version(decode.as_text())],
                "prefill_into_slot": [
                    diffusion_trace.version(block_diffusion.prefill_into_slot.lower(
                        eng.params, jnp.zeros((1, n), jnp.int32), eng.cache,
                        jnp.int32(0), eng._spec, eng._key, jnp.int32(0), jnp.int32(2),
                        cfg, eng.temperature, settings,
                    ).compile().as_text())
                    for n in tolerance["scope_prompt_lens"]
                ],
            }
            with open(tolerance["scope_file"], "w") as f:
                json.dump(versions, f)
        print(f"[serve_diffusion] reference check at {lens} + {tolerance['check_steps']} "
              f"steps: {got}", flush=True)
        return {**got, "tol": tolerance, "ok": passes(got, tolerance),
                "live_bytes": live, "decode_temp_bytes": temp,
                "cache_bytes": {k: int(np.prod(v.shape)) * v.dtype.itemsize
                                for k, v in eng.cache.items()}}


def _window(before: dict, after: dict, config) -> dict:
    """The window's counters (``stats()`` after it minus ``stats()`` after
    warm-up) as the readers' facts: ``serve_dsa._window``'s expert keys
    (``moe_dropped`` among them), and the diffusion steps': forwards of live
    rows, commits, tokens unmasked and emitted, wasted row-steps, keys
    visible."""
    import numpy as np

    tokens = np.asarray(after["moe_expert_tokens"]) - np.asarray(before["moe_expert_tokens"])
    steps = after["moe_layer_steps_total"] - before["moe_layer_steps_total"]
    touched = after["moe_experts_touched_total"] - before["moe_experts_touched_total"]
    if steps <= 0 or tokens.sum() <= 0:
        raise RuntimeError("the expert layer counted no layer-step in the window")
    rows = after["rows_stepped_total"] - before["rows_stepped_total"]
    routed = rows * tokens.shape[0] * config.experts_per_token
    delta = {k: after[k] - before[k] for k in after if k.startswith(("diffusion_", "kv_"))}
    forwards = delta.get("diffusion_forwards_total")
    if not forwards:
        raise RuntimeError("no diffusion step ran a live row in the window")
    return {
        "moe_layer_steps": int(steps),
        "moe_assignments": int(tokens.sum()),
        "moe_dropped": int(max(0, tokens.sum() - routed)),
        "moe_experts_touched_mean": touched / steps,
        "moe_rows_per_layer_step_mean": float(tokens.sum()) / steps,
        "moe_expert_load_max_over_mean": float(tokens.max() / tokens.mean()),
        "moe_routed_assignments": int(routed),
        "moe_held_assignment_share": 100.0 * float(tokens.sum()) / routed,
        "decode_steps_in_window": int(
            after["decode_steps_total"] - before["decode_steps_total"]),
        **delta,
        "diff_tokens_per_row_forward_mean": delta["diffusion_tokens_emitted_total"] / forwards,
        "diff_commit_forward_share": delta["diffusion_commit_forwards_total"] / forwards,
        "diff_threshold_transfer_share": delta["diffusion_threshold_transfers_total"]
        / max(1, delta["diffusion_tokens_unmasked_total"]),
    }


class _Slice(serve_dsa._InTurn):
    """``serve_dsa._InTurn`` (prompt lengths in turn by request, first sends
    in client order, the requests that got a token inside the window
    measured) over the vocabulary's rows the traffic may use: every row but
    the last, which stands for the MASK token — never in a prompt, and a
    failure in an answer."""

    def prompt_tokens(self, req, vocab_size):
        return super().prompt_tokens(req, vocab_size - 1)

    def request_failed(self, o, vocab_size, cut_ok=False):
        return self._module.request_failed(o, vocab_size - 1, cut_ok=cut_ok)


def _exchanged() -> dict:
    """What ``run`` puts in place of ``serve_moe``'s own while its ``run``
    runs."""
    return {"moe_config": sdar_config, "MoeReplica": DiffusionReplica,
            "make_weights": make_weights, "_moe_window": _window,
            "REHEARSAL_MODEL": REHEARSAL_MODEL, "loadgen": _Slice(loadgen)}


def run(ctx: dict) -> dict:
    """``serve_moe.run`` with its hard-wired parts exchanged; then, for a
    traced run, the block attention's device time."""
    tolerance = dict(ctx["config"]["reference_tolerance"])
    lens = loadgen.prompt_lengths(ctx["traffic"])
    block = ctx["config"]["serving"]["diffusion_block"]
    if ctx["rehearse"]:
        lens = [16, 32]
        tolerance.update(check_steps=2 * block + 2)
    # one prompt of each length of the mix, and one that leaves a leftover
    tolerance["check_prompt_lens"] = lens + [max(lens) - block // 2]
    # a block is committed by the forward after its last refining pass
    tolerance["commits_min"] = int(tolerance["check_steps"]) // (
        ctx["config"]["serving"]["denoising_steps"] + 1)
    if ctx["trace"]:
        tolerance.update(
            scope_file=os.path.join(ctx["trace_dir"], diffusion_trace.SCOPE_FILE),
            scope_prompt_lens=lens,
        )
    config = dict(ctx["config"], reference_tolerance=tolerance)
    with mock.patch.multiple(serve_moe, **_exchanged()):
        job = serve_moe.run(dict(ctx, config=config))
    job["facts"]["model"] = {
        k: v for k, v in ctx["config"].items() if isinstance(v, (int, float))}
    job["facts"]["diffusion_block"] = block
    if ctx["trace"] and os.path.isdir(ctx["trace_dir"]):
        job["facts"].update(diffusion_trace.facts(ctx["trace_dir"]))
    return job
