"""Job kind ``serve_dsa``: the ``serve_moe`` job for a decoder with a
latent (MLA) cache, a sparse-attention indexer and one chip's share of
an expert-parallel layer (GLM-5 through ``LlamaConfig``).

Same path — ``serve.run`` of a decode replica, requests through the
deployment handle's streaming path, ``LLMEngine`` on the chip — same
load, same stamps, same facts keys: ``run`` IS ``serve_moe.run``, called
with what that file hard-wires exchanged (``_exchanged``: the
configuration's keys -> ``dsa_config``, the replica class and its
reference -> ``DsaReplica`` and ``chipbench/reference/glm_dsa.py``, the
weights, the window's counters -> ``_window``, the rehearsal's toy
model, the load generator -> ``_InTurn``), so every reader written for
that job reads this one.  What it adds afterwards: the
device time of the sparse-attention path in a traced run, read from the
trace's own operation metadata (``chipbench/dsa_trace.py``) while the
trace directory still exists.

The module asks the program for its fields when it is IMPORTED, which
``run.py`` does before it starts a cluster: a program without them (a
commit from before the latent cache) fails there, at once, and no chip
is leased.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import threading
import time
from unittest import mock

from chipbench import dsa_trace, loadgen
from chipbench.jobs import serve_moe
from chipbench.jobs.serve_llm import CHECK_DECODE_STEPS, BenchReplica
from ray_tpu.models.llama import LlamaConfig

DSA_FIELDS = (
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "index_n_heads", "index_head_dim", "index_topk",
    "first_dense_layers", "shared_expert_dim", "router_scoring",
    "router_norm_topk", "router_scale", "experts_held", "expert_offset",
)
_missing = set(DSA_FIELDS) - {f.name for f in dataclasses.fields(LlamaConfig)}
if _missing:
    raise RuntimeError(
        f"this program's LlamaConfig has no {sorted(_missing)}: it cannot run "
        "a latent-attention configuration"
    )

#: 1.25 x ``index_topk``: the last fifth of the prompt's queries and both
#: decode steps see more keys than they may attend to, so selection is real
CHECK_PROMPT_LEN = 2560

REHEARSAL_MODEL = {
    "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "vocab_size": 512,
    "q_lora_rank": 32, "kv_lora_rank": 24, "qk_nope_head_dim": 12,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "index_n_heads": 4,
    "index_head_dim": 16, "index_topk": 8, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "n_routed_experts_published": 16,
    "num_experts_per_tok": 4, "check_prompt_len": 24,
}


def dsa_config(cfg: dict):
    """The configuration file's keys -> the program's LlamaConfig.
    ``n_routed_experts`` is how many experts are HELD here, from
    ``expert_offset``; the router's width is ``n_routed_experts_published``."""
    import jax.numpy as jnp

    if cfg["n_group"] != 1 or cfg["topk_group"] != 1 or cfg["scoring_func"] != "sigmoid":
        raise RuntimeError("the program routes sigmoid scores without group limits only")
    return LlamaConfig(
        vocab_size=cfg["vocab_size"], max_seq_len=cfg["max_position_embeddings"],
        num_layers=cfg["num_hidden_layers"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_attention_heads"], embed_dim=cfg["hidden_size"],
        mlp_dim=cfg["intermediate_size"],
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        rms_eps=cfg["rms_norm_eps"], dtype=getattr(jnp, cfg["dtype"]),
        param_dtype=getattr(jnp, cfg["param_dtype"]), sliding_window=0,
        tie_embeddings=cfg["tie_word_embeddings"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        index_n_heads=cfg["index_n_heads"], index_head_dim=cfg["index_head_dim"],
        index_topk=cfg["index_topk"],
        first_dense_layers=cfg["first_k_dense_replace"],
        num_experts=cfg["n_routed_experts_published"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_dim=cfg["moe_intermediate_size"],
        shared_expert_dim=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        router_scoring="sigmoid", router_norm_topk=cfg["norm_topk_prob"],
        router_scale=float(cfg["routed_scaling_factor"]),
        experts_held=cfg["n_routed_experts"], expert_offset=cfg["expert_offset"],
    )


def spec_of(config, **kw):
    """What the reference needs beside the parameter tree."""
    from chipbench.reference import glm_dsa

    return glm_dsa.Spec(
        float(config.rope_theta), float(config.rms_eps), config.qk_rope_head_dim,
        config.index_topk, config.experts_per_token, config.router_norm_topk,
        float(config.router_scale), config.expert_offset, **kw,
    )


def make_weights(cfg: dict, seed: int, rehearse: bool):
    """``weights_loader``: as ``serve_moe.make_weights``, one jitted
    ``llama.init`` on the device in the type that is served; then the
    router's selection bias balanced (``balance_router``)."""
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
    config = dsa_config(cfg)
    params = jax.block_until_ready(
        jax.jit(functools.partial(llama.init, config=config))(jax.random.key(seed % (2**31)))
    )
    # in a scratch cache of the served shape and at the reference check's
    # prompt length: the one compile of ``choices_cached`` serves both
    slots, length, prompt_len = (
        (4, 256, REHEARSAL_MODEL["check_prompt_len"]) if rehearse else
        (cfg["serving"]["max_slots"], cfg["serving"]["max_len"], CHECK_PROMPT_LEN)
    )
    params, _ = balance_router(
        params, config, seed, llama.init_cache(config, slots, length), prompt_len
    )
    return jax.block_until_ready(params)


#: how the router's selection bias is balanced: (iterations, gain) in
#: turn, a fresh seeded prompt each iteration
BALANCE_SCHEDULE = ((10, 0.4), (14, 0.15))
#: d ln(an expert's load) / d (its bias), reckoned: the score is the
#: sigmoid of a N(0, 1.57) logit (0.02 x sqrt(6144)), chosen when among
#: the top 1/32; a slope off by two only makes the iteration slower
BALANCE_SLOPE = 29.0


def balance_router(params, config, seed: int, cache, prompt_len: int):
    """``params`` with each expert layer's ``router_bias`` moved to
    where every expert is chosen equally often, which is what the bias
    is FOR (DeepSeek-V3's auxiliary-loss-free balancing: the bias enters
    the choice only, and training moves it against each expert's load):
    a trained checkpoint's experts are in balance, a drawn bias leaves
    each (layer, expert)'s load +-30% off, and which 80 of the 1,280
    this chip holds then decides how many expert matrices a decode step
    fetches — 1% of the step between one seed and the next (PERF.md
    section 6).  Each iteration runs a fresh seeded prompt of
    ``prompt_len`` ids through slot 0 of ``cache`` with
    ``llama.choices_cached``, counts every expert's load in every layer
    and moves the bias by ``gain / BALANCE_SLOPE x ln(even load / load)``.
    Returns (params, cache)."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama

    rng = np.random.default_rng([seed % (2**63), 29])
    stored = params["blocks"]["router_bias"]
    bias = np.array(stored, np.float32)                       # (Le, X)
    even = prompt_len * config.experts_per_token / config.num_experts
    for gain in [g for n, g in BALANCE_SCHEDULE for _ in range(n)]:
        prompt = rng.integers(0, config.vocab_size, (1, prompt_len))
        _, cache, chose = llama.choices_cached(
            params, jnp.asarray(prompt, jnp.int32), cache, jnp.int32(0), None, config
        )
        experts = np.asarray(chose["experts"])[:, 0].reshape(bias.shape[0], -1)
        load = np.stack([np.bincount(e, minlength=config.num_experts) for e in experts])
        bias += gain / BALANCE_SLOPE * np.log(even / (load + 1.0))
        params = dict(params, blocks=dict(
            params["blocks"], router_bias=jnp.asarray(bias, stored.dtype)))
    moved = bias - np.asarray(stored, np.float32)
    print(f"[serve_dsa] router bias balanced on {sum(n for n, _ in BALANCE_SCHEDULE)} prompts "
          f"of {prompt_len}: moved by sd {moved.std():.4f}, at most {np.abs(moved).max():.4f}",
          flush=True)
    return params, cache


def check_prompt(config, seed: int, prompt_len: int) -> list:
    import numpy as np

    return np.random.default_rng([seed % (2**63), 11]).integers(
        0, config.vocab_size, prompt_len
    ).tolist()


def _slot0(prefill, step, params, config, cache, max_slots: int, prompt: list, then=()):
    """``prompt`` into slot 0 by ``prefill``, then ``CHECK_DECODE_STEPS``
    calls of ``step`` on row 0, each fed ``then``'s next token or, without
    it, the argmax of the logits before.  -> (cache, the tokens, per call
    what the program returned beside the cache: logits (V,), choices)."""
    import jax.numpy as jnp
    import numpy as np

    seq = list(prompt)
    logits, cache, *rest = prefill(
        params, jnp.asarray([seq], jnp.int32), cache, jnp.int32(0), config
    )
    outs = [(logits[0], *rest)]
    for i in range(CHECK_DECODE_STEPS):
        seq.append(then[i] if then else int(jnp.argmax(outs[-1][0])))
        tokens = np.zeros((max_slots,), np.int32)
        pos = np.zeros((max_slots,), np.int32)
        tokens[0], pos[0] = seq[-1], len(seq) - 1
        logits, cache, *rest = step(
            params, jnp.asarray(tokens), cache, jnp.asarray(pos), config
        )
        outs.append((logits[0], *rest))
    return cache, seq, outs


def system_run(params, config, cache, max_slots: int, prompt: list):
    """``prompt`` through ``llama.prefill_into_slot`` and
    ``CHECK_DECODE_STEPS`` greedy ``llama.decode_step_rowwise`` steps in
    ``cache`` (slot 0): THE TWO EXECUTABLES THE ENGINE SERVES WITH, in the
    cache it then serves from; their logits are what is compared.  Those
    programs hand back no choices, so the same tokens then go through
    slot 0 once more by ``llama.choices_cached`` — the same
    ``_cached_step`` compiled with its choices as further outputs — for
    the selected sets and the experts, and ``twin_logits_differing``
    counts the logits at which that program's differ from the served
    ones' by a single bit: the choices are the served programs' as far as
    that is 0, which ``passes`` demands.  Returns (cache, {"seq": prompt
    + the greedy tokens, "logits": (1 + steps, V), "selected": [(L, S, S)
    of the prompt, (L, 1, S + i) of each step], "experts": (expert
    layers, S + steps, k) the experts every token chose, ...})."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama

    cache, seq, served = _slot0(
        llama.prefill_into_slot, llama.decode_step_rowwise,
        params, config, cache, max_slots, prompt,
    )
    cache, _, twin = _slot0(
        lambda p, t, c, slot, cfg: llama.choices_cached(p, t, c, slot, None, cfg),
        lambda p, t, c, pos, cfg: llama.choices_cached(p, t, c, None, pos, cfg),
        params, config, cache, max_slots, prompt, then=seq[len(prompt):],
    )
    logits = jnp.stack([o[0] for o in served])
    delta = jnp.abs(jnp.stack([o[0] for o in twin]) - logits)
    chose = [o[1] for o in twin]
    selected = [np.asarray(chose[0]["selected"])[:, 0]] + [
        np.asarray(c["selected"])[:, 0, :, :len(prompt) + 1 + i]
        for i, c in enumerate(chose[1:])
    ]
    return cache, {
        "seq": seq, "logits": logits, "selected": selected,
        "experts": np.concatenate([np.asarray(c["experts"])[:, 0] for c in chose], axis=1),
        "twin_logits_differing": int((delta > 0).sum()),
        "twin_logits_delta_max": float(delta.max()),
    }


def against_reference(params, config, out: dict, given: bool = True) -> dict:
    """What ``system_run`` produced against the float32 reference's full
    forward of the same tokens — ``given`` the system's expert choices
    (``reference/glm_dsa.py`` says why; False: free-running).  {"err": rms and max of |logits -
    reference| / std, "sets_equal": share of (layer, query) pairs whose
    selected set IS the reference's, "set_overlap": mean share of the
    reference's set the system's holds — both over the queries that see
    more than ``index_topk`` keys, where selection is real,
    "set_size_ok": every such query attended to exactly ``index_topk``
    keys and every other to every visible key, "swap_rate": share of
    (expert layer, token) pairs that chose another expert set than the
    reference did at that layer, "swapped_margin_max": the largest
    margin (8th minus 9th selection score, in the reference's float32)
    among those pairs — how decisive a choice the system overturned,
    ...}."""
    import jax.numpy as jnp
    import numpy as np

    from chipbench.reference import errors
    from chipbench.reference import glm_dsa as reference

    seq = out["seq"]
    S, prompt_len = len(seq), len(seq) - CHECK_DECODE_STEPS
    ref, info = reference.forward(
        params, jnp.asarray(seq, jnp.int32), spec_of(config),
        positions=list(range(prompt_len - 1, S)),
        experts=jnp.asarray(out["experts"]) if given else None,
    )
    want = np.asarray(info["selected"])                            # (L, S, S)
    got = np.zeros_like(want)
    got[:, :prompt_len, :prompt_len] = out["selected"][0]
    for i, rows in enumerate(out["selected"][1:]):
        got[:, prompt_len + i, :rows.shape[-1]] = rows[:, 0]
    real = np.arange(S) >= config.index_topk          # queries that must choose
    if not real.any():
        raise RuntimeError(f"a check prompt of {prompt_len} tokens never selects")
    hit = (got & want)[:, real].sum(-1)
    size = got[:, real].sum(-1)
    swapped = (
        np.sort(out["experts"], -1) != np.sort(np.asarray(info["experts"]), -1)
    ).any(-1)
    e_margin = np.asarray(info["expert_margin"])
    return {
        "err": errors(out["logits"], ref),
        "sets_equal": float((got == want)[:, real].all(-1).mean()),
        "set_overlap": float((hit / config.index_topk).mean()),
        "set_overlap_min": float((hit / config.index_topk).min()),
        "set_size_ok": bool((size == config.index_topk).all())
        and bool((got[:, ~real] == want[:, ~real]).all()),
        "select_margin_p50": float(np.median(np.asarray(info["select_margin"])[:, real])),
        "swap_rate": float(swapped.mean()),
        "swapped_margin_max": float(e_margin[swapped].max()) if swapped.any() else 0.0,
        "margin_p50": float(np.median(e_margin)),
        "twin_logits_differing": out["twin_logits_differing"],
        "twin_logits_delta_max": out["twin_logits_delta_max"],
    }


def compare(params, config, cache, max_slots: int, seed: int, prompt_len: int):
    cache, out = system_run(
        params, config, cache, max_slots, check_prompt(config, seed, prompt_len)
    )
    return cache, against_reference(params, config, out)


def cut_mantissa(params, bits: int = 3):
    """``params`` with every value's mantissa rounded to ``bits`` bits
    (float8 e4m3's three): the nearest precision below bfloat16's seven,
    for the readings that show the comparison refuses it.  By bit
    arithmetic: a cast to float8 and back is a no-op to the chip's
    compiler, which removes such a round trip."""
    import jax
    import jax.numpy as jnp

    def cut(a):
        if a.dtype == jnp.bfloat16:
            word, have = jnp.uint16, 7
        elif a.dtype == jnp.float32:
            word, have = jnp.uint32, 23
        else:
            return a
        drop = have - bits
        raw = jax.lax.bitcast_convert_type(a, word)
        raw = (raw + word(1 << (drop - 1))) & word(~((1 << drop) - 1) & (2 ** (8 * raw.dtype.itemsize) - 1))
        return jax.lax.bitcast_convert_type(raw, a.dtype)

    return jax.tree.map(cut, params)


def passes(got: dict, tolerance: dict) -> bool:
    """The comparison that decides ``correct``: the served programs'
    logits within rms and max, AND the choices: each selected set exactly
    ``index_topk`` keys (every visible key before that), the sets' mean
    overlap with the reference's and the share equal to it at or over the
    configuration's floors, the share of (expert layer, token) pairs that
    chose another expert set at or under its limit and none of them where
    the reference's margin was over its limit (a router may lose a close
    call to rounding, not a clear one), AND the choices being the served
    programs': the choices-returning program's logits theirs bit for bit
    (the limits and the readings they lie between:
    ``chipbench/reference/glm_dsa.py``)."""
    from chipbench.reference import within

    return bool(
        within(got["err"], tolerance) and got["set_size_ok"]
        and got["set_overlap"] >= tolerance["set_overlap_min"]
        and got["sets_equal"] >= tolerance["sets_equal_min"]
        and got["swap_rate"] <= tolerance["swap_rate_max"]
        and got["swapped_margin_max"] <= tolerance["swapped_margin_max"]
        and got["twin_logits_differing"] == 0
    )


class DsaReplica(BenchReplica):
    """``BenchReplica`` compared with the GLM-5 reference."""

    def check_reference(self, seed: int, tolerance: dict) -> dict:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.models import llama

        eng, cfg = self.engine, self.config
        live = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use")
        prompt_len = min(CHECK_PROMPT_LEN, eng.max_len - CHECK_DECODE_STEPS - 1,
                         int(tolerance.get("check_prompt_len", CHECK_PROMPT_LEN)))
        eng.cache, got = compare(
            eng.params, cfg, eng.cache, eng.max_slots, seed, prompt_len
        )
        # the decode program again, for its temporaries and its text (the
        # jitted call above keeps no handle on its executable)
        tokens = jnp.zeros((eng.max_slots,), jnp.int32)
        decode = llama.decode_step_rowwise.lower(
            eng.params, tokens, eng.cache, tokens, cfg
        ).compile()
        temp = decode.memory_analysis().temp_size_in_bytes
        if tolerance.get("scope_file"):
            # a traced run: which instruction of which version of which
            # program runs under which scope (``dsa_trace``), from the
            # programs' own text
            versions = {
                "decode_step_rowwise": [dsa_trace.version(decode.as_text())],
                "prefill_into_slot": [
                    dsa_trace.version(llama.prefill_into_slot.lower(
                        eng.params, jnp.zeros((1, n), jnp.int32), eng.cache,
                        jnp.int32(0), cfg,
                    ).compile().as_text())
                    for n in tolerance["scope_prompt_lens"]
                ],
            }
            with open(tolerance["scope_file"], "w") as f:
                json.dump(versions, f)
        print(f"[serve_dsa] reference check at {prompt_len} + {CHECK_DECODE_STEPS} "
              f"tokens: {got}", flush=True)
        return {**got, "tol": tolerance, "ok": passes(got, tolerance),
                "live_bytes": live, "decode_temp_bytes": temp,
                "cache_bytes": {k: int(np.prod(v.shape)) * v.dtype.itemsize
                                for k, v in eng.cache.items()}}


def _window(before: dict, after: dict, config) -> dict:
    """The window's counters (``stats()`` after it minus ``stats()``
    after warm-up) as the readers' facts: ``serve_moe._moe_window``'s keys
    for the experts HELD here, and the sparse-attention path's.

    ``moe_dropped`` is what no-drop routing keeps at 0 among the held
    experts.  Every token row the engine gave the model routes
    ``experts_per_token`` assignments in every expert layer; which of them
    fall on held experts is the router's business, so what can be held to
    account is that the experts computed exactly the rows the router
    counted for them — which the program's one counter is — and that no
    layer-step counted more than was routed."""
    import numpy as np

    from ray_tpu.models.llama import wide_total  # noqa: F401 — fails on the parent

    tokens = np.asarray(after["moe_expert_tokens"]) - np.asarray(before["moe_expert_tokens"])
    steps = after["moe_layer_steps_total"] - before["moe_layer_steps_total"]
    touched = after["moe_experts_touched_total"] - before["moe_experts_touched_total"]
    if steps <= 0 or tokens.sum() <= 0:
        raise RuntimeError("the expert layer counted no layer-step in the window")
    rows = after["rows_stepped_total"] - before["rows_stepped_total"]
    routed = rows * tokens.shape[0] * config.experts_per_token
    dsa = {k: after[k] - before[k] for k in after if k.startswith("dsa_")}
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
        **dsa,
        "dsa_selected_share_mean": 100.0 * (
            dsa["dsa_selected_step"] + dsa["dsa_selected_run"]
        ) / max(1, dsa["dsa_visible_step"] + dsa["dsa_visible_run"]),
        "dsa_layers": config.num_layers,
        "dsa_index_key_bytes": config.index_head_dim * 2,
        "dsa_latent_row_bytes": (config.kv_lora_rank + config.qk_rope_head_dim) * 2,
    }


class _InTurn:
    """``loadgen`` with the prompt lengths of a ``cycle`` mix IN TURN by
    request: request i gets ``values[i % len(values)]`` whatever the seed
    (``loadgen.schedule`` deals the lengths out by a permutation of the
    seed, which is harmless where prefills of all lengths cost 15 ms and
    here, where an 8,192-token prefill stalls the replica 1.15 s, 2.9% of
    a window, and a 4,096-token one 0.26 s, would make the work inside a
    window differ between seeds by one or two long prefills).  The
    seed keeps choosing every token id.

    And with the requests that count as MEASURED those that received a
    token inside the window, whenever they were sent.  ``loadgen.
    summarize`` measures a closed loop's requests SENT inside the
    window; here an answer of 2,048 tokens takes a minute and 32 earlier
    requests queue before any new one, so no request sent inside a 40 s
    window is served inside it, while every token of the window belongs
    to a request sent before it.  ``serve_tokens_per_s`` is the same
    count either way (every token that arrived inside the window, of
    any request); ``attempted`` / ``failed`` hold the requests the
    window's tokens belong to (and any that errored) to the same rules,
    as far as each got.

    And with the clients' first requests sent in client order
    (``prompt_tokens``)."""

    #: seconds between the first requests of consecutive clients
    FIRST_SEND_GAP_S = 0.01

    def __init__(self, module):
        self._module = module
        self._first_send = None
        self._lock = threading.Lock()

    def __getattr__(self, name):
        return getattr(self._module, name)

    def prompt_tokens(self, req, vocab_size):
        """The prompt, handed over when it is this request's turn to be
        sent: the clients' FIRST requests leave 10 ms apart in client
        order.  All 64 are sent at once by 64 racing threads otherwise,
        the order in which they reach the replica decides which 32 get a
        slot and in which order the 4,096- and 8,192-token prefills
        alternate from then on, and runs of one seed differed by a long
        prefill inside the window (2.0% spread over six runs where the
        bound asks for 1%; my chip run, PR 30, call 6)."""
        tokens = self._module.prompt_tokens(req, vocab_size)
        if req.client is not None and req.index == req.client:
            with self._lock:
                if self._first_send is None:
                    self._first_send = time.perf_counter()
            due = self._first_send + self.FIRST_SEND_GAP_S * req.client
            time.sleep(max(0.0, due - time.perf_counter()))
        return tokens

    def summarize(self, outcomes, seconds, open_loop):
        out = self._module.summarize(outcomes, seconds, open_loop)
        if open_loop:
            return out
        served = [o for o in outcomes
                  if o.error or any(0.0 <= t < seconds for t in o.token_s)]
        out["measured"] = served
        out["ttft_ms"] = [(o.token_s[0] - o.sent_s) * 1e3 for o in served if o.token_s]
        out["itl_ms"] = [
            (b - a) * 1e3 for o in served
            for a, b in zip(o.token_s, o.token_s[1:]) if 0.0 <= b < seconds
        ]
        return out

    def schedule(self, traffic, seed, seconds, max_len):
        reqs = self._module.schedule(traffic, seed, seconds, max_len)
        spec = traffic["prompt_len"]
        if spec["kind"] != "cycle":
            return reqs
        values = [int(v) for v in spec["values"]]
        return [
            dataclasses.replace(
                r, prompt_len=values[r.index % len(values)],
                new_tokens=min(r.new_tokens, max_len - values[r.index % len(values)]),
            )
            for r in reqs
        ]


def _exchanged() -> dict:
    """What ``run`` puts in place of ``serve_moe``'s own while its ``run``
    runs (which takes none of them as an argument: PERF.md section 7)."""
    return {"moe_config": dsa_config, "MoeReplica": DsaReplica,
            "make_weights": make_weights, "_moe_window": _window,
            "REHEARSAL_MODEL": REHEARSAL_MODEL, "loadgen": _InTurn(loadgen)}


# a ``serve_moe`` or ``loadgen`` that has lost one of the names is refused
# here, at import like a program without the fields, not served with its own
_lost = [n for n in _exchanged() if not hasattr(serve_moe, n)] + [
    "loadgen." + n for n in ("schedule", "summarize", "prompt_tokens")
    if not hasattr(loadgen, n)
]
if _lost:
    raise RuntimeError(
        f"jobs/serve_dsa.py exchanges {_lost} inside serve_moe.run, and "
        "jobs/serve_moe.py (or loadgen.py) no longer has them"
    )


def run(ctx: dict) -> dict:
    """``serve_moe.run`` with its hard-wired parts exchanged; then, for a
    traced run, the sparse-attention path's device time."""
    tolerance = dict(ctx["config"]["reference_tolerance"])
    if ctx["rehearse"]:
        tolerance["check_prompt_len"] = REHEARSAL_MODEL["check_prompt_len"]
    if ctx["trace"]:
        lens = [16, 32] if ctx["rehearse"] else loadgen.prompt_lengths(ctx["traffic"])
        tolerance.update(
            scope_file=os.path.join(ctx["trace_dir"], dsa_trace.SCOPE_FILE),
            scope_prompt_lens=lens,
        )
    ctx = dict(ctx, config=dict(ctx["config"], reference_tolerance=tolerance))
    with mock.patch.multiple(serve_moe, **_exchanged()):
        job = serve_moe.run(ctx)
    if ctx["trace"] and os.path.isdir(ctx["trace_dir"]):
        job["facts"].update(dsa_trace.facts(ctx["trace_dir"]))
    return job
