"""Job kind ``serve_kda``: the ``serve_moe`` job for a decoder whose layers
are Kimi delta attention — a recurrent state a head whose decay is a vector
over the key channels — three to one with gated full attention without
rotation, every layer over sigmoid-routed experts of which this chip holds a
share, and a shared expert (Solar-Open2 through ``LlamaConfig``).

Same path — ``serve.run`` of a decode replica, requests through the
deployment handle's streaming path, ``LLMEngine`` on the chip — same load,
same stamps, same facts keys: ``run`` IS ``serve_moe.run`` with what that
file hard-wires exchanged, as ``jobs/serve_swa.py`` does it and with its
helpers where they fit (``system_run``: the check rows through the two served
executables and their choices-returning twin, here from cleared states;
``serve_dsa.check_prompt``, ``_InTurn``).  The comparison that decides ``correct`` (``KdaReplica.
check_reference``, ``against_reference``, ``passes``) is made on the chip, at
the served widths, in the engine's own cache and on what the two served
executables (``llama.prefill_into_slot`` / ``llama.decode_step_rowwise``)
produce: one check row a prompt length of the traffic (4,096 and 16,384 ids:
2 and 8 segments of the chunked rule, the state carried between them),
prefilled and then decoded ``check_steps`` steps through the cache,

(i)   their logits at the prompt's last position and at every step against
      the float32 reference's full forward, the recurrence token by token
      (``chipbench/reference/solar_open2.py``), GIVEN the system's choices;
(ii)  the routing choice by choice: the share of (layer, token) pairs whose
      chosen set is not the reference's own, and the largest reference
      margin among those;
(iii) the twin's logits the served programs' within rounding, its counted
      pairs exactly the choices it handed back, and held experts among them
      (``serve_swa.system_run``'s three readings);
(iv)  the recurrent state held in float32 between steps (``state_low_bits``,
      as ``jobs/serve_hybrid.py`` reads it: the logits cannot show a state
      that passed through bfloat16 under the bfloat16 activations around it).

The module asks the program for its fields when it is IMPORTED, which
``run.py`` does before it starts a cluster: a program without them (a commit
from before the per-channel rule) fails there, at once, and no chip is leased.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
from unittest import mock

from chipbench import kda_trace, loadgen
from chipbench.jobs import serve_dsa, serve_moe, serve_swa
from chipbench.jobs.serve_llm import BenchReplica
from ray_tpu.models import hf, llama
from ray_tpu.models.llama import LlamaConfig

KDA_FIELDS = ("linear_kind", "linear_gate_rank", "linear_segment", "attn_output_gate",
              "layer_types", "shared_expert_dim", "experts_held", "expert_offset")
_missing = set(KDA_FIELDS) - {f.name for f in dataclasses.fields(LlamaConfig)}
_missing |= {n for n in ("solar_open2_fields",) if not hasattr(hf, n)}
if _missing:
    raise RuntimeError(
        f"this program has no {sorted(_missing)} (models/llama.py, models/hf.py): it "
        "cannot run a configuration with Kimi-delta-attention layers"
    )

REHEARSAL_MODEL = {
    "hidden_size": 64, "moe_intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 4,
                           "num_kv_heads": None},
    "n_routed_experts": 4, "n_routed_experts_published": 8, "num_experts_per_tok": 2,
}


def kda_config(cfg: dict):
    """The configuration file's keys -> the program's LlamaConfig, through
    the program's own reading of the published keys (``hf.
    solar_open2_fields``).  The layers are the first ``num_hidden_layers``;
    ``n_routed_experts`` is how many experts are HELD here, from
    ``expert_offset``, of the ``n_routed_experts_published`` the router
    routes over."""
    import jax.numpy as jnp

    fields = hf.solar_open2_fields(
        dict(cfg, n_routed_experts=cfg["n_routed_experts_published"]))
    serving = cfg["serving"]
    return LlamaConfig(
        vocab_size=cfg["vocab_size"], max_seq_len=cfg["max_position_embeddings"],
        num_layers=cfg["num_hidden_layers"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], embed_dim=cfg["hidden_size"],
        mlp_dim=cfg["intermediate_size"], rms_eps=cfg["rms_norm_eps"],
        dtype=getattr(jnp, cfg["dtype"]), param_dtype=getattr(jnp, cfg["param_dtype"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        experts_held=cfg["n_routed_experts"], expert_offset=cfg["expert_offset"],
        linear_chunk=serving["linear_chunk"], linear_segment=serving["linear_segment"],
        **fields,
    )


def spec_of(config, **bent):
    """What the reference needs beside the parameter tree; ``bent``: one of
    its pieces left out or another than the configuration's."""
    from chipbench.reference import solar_open2

    return solar_open2.Spec(
        tuple(config.layer_types), float(config.rms_eps), config.experts_per_token,
        config.expert_offset, bool(config.linear_neg_eigval),
        bool(config.attn_output_gate))._replace(**bent)


def model_facts(cfg: dict) -> dict:
    """The configuration's numbers and its two groups as the readers'
    ``facts["model"]`` (``chipbench/kda_cost.py`` counts from them)."""
    keep = ("gqa_layers", "linear_attn_config")
    return {k: v for k, v in cfg.items()
            if k in keep or (isinstance(v, (int, float)) and not isinstance(v, bool))}


def make_weights(cfg: dict, seed: int, rehearse: bool):
    """``weights_loader``: one jitted ``llama.init`` on the device in the
    type that is served.  The router's selection bias stays as it is drawn
    (N(0, 0.01)): the configuration's ``assumed`` says so."""
    import jax

    from ray_tpu.util import compile_cache

    compile_cache.configure()
    dev = jax.devices()[0]
    if not rehearse and dev.platform != "tpu":
        raise RuntimeError(
            f"the replica was leased a TPU chip but jax came up on platform "
            f"{dev.platform!r}; there is no CPU fallback"
        )
    return jax.block_until_ready(
        jax.jit(functools.partial(llama.init, config=kda_config(cfg)))(
            jax.random.key(seed % (2**31))))


def state_low_bits(cache, rows: int) -> float:
    """Of the first ``rows`` rows' recurrent state: the share of non-zero
    values whose float32 word has a bit set below bfloat16's sixteen — 1 -
    2**-16 of a state held in float32, none of one that passed through
    bfloat16."""
    import jax
    import jax.numpy as jnp

    state = cache["gdn_state"][:, :rows]
    low = (jax.lax.bitcast_convert_type(state, jnp.uint32) & 0xFFFF) != 0
    return float(low.sum() / jnp.maximum(1, (state != 0).sum()))


def against_reference(params, config, out: dict, given: bool = True, **bent) -> dict:
    """``serve_swa.against_reference`` with this model's reference: what
    ``serve_swa.system_run`` produced against the float32 reference's full
    forward of the same tokens — ``given`` the system's choices; ``bent``:
    the reference with one piece left out (``spec_of``)."""
    import jax.numpy as jnp
    import numpy as np

    from chipbench.reference import errors
    from chipbench.reference import solar_open2 as reference

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
        "state_low_bits": out["state_low_bits"],
    }


def passes(got: dict, tolerance: dict) -> bool:
    """The comparison that decides ``correct``: ``serve_swa.passes``'s
    limits, and the recurrent state held in float32 between steps."""
    return bool(serve_swa.passes(got, tolerance)
                and got["state_low_bits"] >= tolerance["state_low_bits_min"])


def system_run(params, config, cache, max_slots: int, prompts, steps: int):
    """``serve_swa.system_run`` — the check rows through the two served
    executables, then the same tokens once more through their
    choices-returning twin — with every row's recurrent state and convolution
    tail cleared before each of the two passes.  An EMPTY slot steps a token
    0 at position 0 like every row, and here it has a state that remembers:
    left as the first pass leaves it, the empty rows would route otherwise in
    the second, and the two pairs of programs would count other held pairs
    (``served_pairs_off``) though they agree on every checked row.  A check
    row's own state is written from zero by its prefill either way.  Adds
    ``state_low_bits`` of the check rows as the twin's last step left them."""
    import jax.numpy as jnp

    from chipbench.jobs import serve_scmoe

    rows = serve_scmoe._rows

    def from_rest(prefill, step, params, config, cache, *rest, **kw):
        cache = dict(cache, gdn_state=jnp.zeros_like(cache["gdn_state"]),
                     gdn_conv=jnp.zeros_like(cache["gdn_conv"]))
        return rows(prefill, step, params, config, cache, *rest, **kw)

    with mock.patch.object(serve_scmoe, "_rows", from_rest):
        cache, out = serve_swa.system_run(params, config, cache, max_slots, prompts, steps)
    out["state_low_bits"] = state_low_bits(cache, len(prompts))
    return cache, out


def compare(params, config, cache, max_slots: int, seed: int, prompt_lens, steps: int):
    prompts = [serve_dsa.check_prompt(config, seed + r, n) for r, n in enumerate(prompt_lens)]
    cache, out = system_run(params, config, cache, max_slots, prompts, steps)
    return cache, against_reference(params, config, out)


class KdaReplica(BenchReplica):
    """``BenchReplica`` compared with the Solar-Open2 reference."""

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
            wrote = {"prompt_lens": tolerance["scope_prompt_lens"], "versions": {
                "decode_step_rowwise": [kda_trace.version(decode.as_text())],
                "prefill_into_slot": [
                    kda_trace.version(llama.prefill_into_slot.lower(
                        eng.params, jnp.zeros((1, n), jnp.int32), eng.cache,
                        jnp.int32(0), cfg,
                    ).compile().as_text())
                    for n in tolerance["scope_prompt_lens"]
                ],
            }}
            with open(tolerance["scope_file"], "w") as f:
                json.dump(wrote, f)
        print(f"[serve_kda] reference check at {lens} + {steps} steps: {got}", flush=True)
        return {**got, "tol": tolerance, "ok": passes(got, tolerance),
                "live_bytes": live, "decode_temp_bytes": temp,
                "cache_bytes": {k: int(np.prod(v.shape)) * v.dtype.itemsize
                                for k, v in eng.cache.items()}}


#: the window's counters of the recurrent layers and of the full layers' keys
STATE_COUNTERS = ("gdn_rows_stepped", "gdn_tokens_scanned", "gdn_tokens_padded",
                  "gdn_state_bytes_step", "kv_keys_visible_step", "kv_keys_read_step")


def _window(before: dict, after: dict, config) -> dict:
    """The window's counters (``stats()`` after it minus ``stats()`` after
    warm-up) as the readers' facts: ``serve_swa._window``'s expert keys for
    the experts HELD here, the recurrent layers' one-token updates, scanned
    tokens and state bytes, and the keys the full layers' rows could see."""
    import numpy as np

    tokens = np.asarray(after["moe_expert_tokens"]) - np.asarray(before["moe_expert_tokens"])
    delta = {k: after[k] - before[k] for k in after
             if k in STATE_COUNTERS or (k.startswith("moe_") and k.endswith("_total"))}
    steps, touched = delta["moe_layer_steps_total"], delta["moe_experts_touched_total"]
    decode_steps = int(after["decode_steps_total"] - before["decode_steps_total"])
    if steps <= 0 or tokens.sum() <= 0 or decode_steps <= 0 or delta["gdn_tokens_scanned"] <= 0:
        raise RuntimeError("no expert layer-step, no decode step or no prefill of a "
                           f"recurrent layer was counted in the window: {delta}")
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
        "decode_steps_in_window": decode_steps,
        "prefills_in_window": int(after["admitted_total"] - before["admitted_total"]),
        **{k: delta[k] for k in STATE_COUNTERS},
        "gated_delta_step": after["gated_delta_step"],
    }


def _exchanged() -> dict:
    """What ``run`` puts in place of ``serve_moe``'s own while its ``run``
    runs."""
    return {"moe_config": kda_config, "MoeReplica": KdaReplica,
            "make_weights": make_weights, "_moe_window": _window,
            "REHEARSAL_MODEL": REHEARSAL_MODEL,
            "loadgen": serve_dsa._InTurn(loadgen)}


def run(ctx: dict) -> dict:
    """``serve_moe.run`` with its hard-wired parts exchanged; then, for a
    traced run, the recurrent layers' device time by scope and the two
    programs' executions (``kda_trace.facts``)."""
    tolerance = dict(ctx["config"]["reference_tolerance"])
    serving = dict(ctx["config"]["serving"])
    lens = loadgen.prompt_lengths(ctx["traffic"])
    if ctx["rehearse"]:
        lens = [16, 32]
        tolerance["check_steps"] = 4
        serving.update(linear_chunk=4, linear_segment=8)
    tolerance["check_prompt_lens"] = lens
    if ctx["trace"]:
        tolerance.update(
            scope_file=os.path.join(ctx["trace_dir"], kda_trace.SCOPE_FILE),
            scope_prompt_lens=lens,
        )
    config = dict(ctx["config"], reference_tolerance=tolerance, serving=serving)
    with mock.patch.multiple(serve_moe, **_exchanged()):
        job = serve_moe.run(dict(ctx, config=config))
    model = dict(config, **(REHEARSAL_MODEL if ctx["rehearse"] else {}))
    job["facts"]["model"] = model_facts(model)
    job["facts"]["linear_chunk"] = serving["linear_chunk"]
    if ctx["trace"] and os.path.isdir(ctx["trace_dir"]):
        job["facts"].update(kda_trace.facts(ctx["trace_dir"]))
    return job
