"""Job kind ``serve_moe``: the ``serve_llm`` job for a decoder whose
feed-forward is a sparse expert layer (OLMoE through ``LlamaConfig``).

Same path — ``serve.run`` of a decode replica, requests through the
deployment handle's streaming path, ``LLMEngine`` on the chip — same
load, same stamps and the same facts keys as ``jobs/serve_llm.py``,
whose helpers and ``BenchReplica`` it reuses, so every reader written
for that job reads this one.  What differs is what ``serve_llm.py``
hard-wires: the configuration's keys (``moe_config``), the reference
the replica is compared with (``chipbench/reference/olmoe.py``) and the
routing counters the replica's ``stats()`` carries, which become the
facts ``moe_*`` that the four ``.moe`` readers take.

The module asks the program for its expert fields when it is IMPORTED,
which ``run.py`` does before it starts a cluster: a program without
them (a commit from before the expert layer) fails there, at once, and
no chip is leased.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time

from chipbench import loadgen
from chipbench.jobs.serve_llm import (CHECK_DECODE_STEPS, CHECK_PROMPT_LEN,
                                      REPLICA_START_TIMEOUT_S, BenchReplica,
                                      _consume, _longest_gap)
from ray_tpu import serve
from ray_tpu.models.llama import LlamaConfig

EXPERT_FIELDS = ("num_experts", "experts_per_token", "expert_dim", "qk_norm")
_missing = set(EXPERT_FIELDS) - {f.name for f in dataclasses.fields(LlamaConfig)}
if _missing:
    raise RuntimeError(
        f"this program's LlamaConfig has no {sorted(_missing)}: it cannot run "
        "an expert configuration"
    )

REHEARSAL_MODEL = {
    "hidden_size": 64, "intermediate_size": 48, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_hidden_layers": 2, "vocab_size": 512,
    "num_experts": 8, "num_experts_per_tok": 2,
}


def moe_config(cfg: dict):
    """The configuration file's keys -> the program's LlamaConfig:
    ``intermediate_size`` is the width of ONE expert, there is no dense
    MLP, and OLMoE norms q and k (the file's ``assumed``)."""
    import jax.numpy as jnp

    return LlamaConfig(
        vocab_size=cfg["vocab_size"], max_seq_len=cfg["max_position_embeddings"],
        num_layers=cfg["num_hidden_layers"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], embed_dim=cfg["hidden_size"],
        mlp_dim=0, rope_theta=float(cfg["rope_theta"]),
        rms_eps=cfg["rms_norm_eps"], dtype=getattr(jnp, cfg["dtype"]),
        param_dtype=getattr(jnp, cfg["param_dtype"]), sliding_window=0,
        tie_embeddings=cfg["tie_word_embeddings"],
        num_experts=cfg["num_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_dim=cfg["intermediate_size"], qk_norm=True,
    )


def make_weights(cfg: dict, seed: int, rehearse: bool):
    """``weights_loader``: as ``serve_llm.make_weights``, one jitted
    ``llama.init`` on the device in the type that is served."""
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
    params = jax.jit(functools.partial(llama.init, config=moe_config(cfg)))(
        jax.random.key(seed % (2**31))
    )
    return jax.block_until_ready(params)


class MoeReplica(BenchReplica):
    """``BenchReplica`` compared with the OLMoE reference."""

    def check_reference(self, seed: int, tolerance: dict) -> dict:
        """As ``BenchReplica.check_reference`` (one prompt through
        ``prefill_into_slot`` and two ``decode_step_rowwise`` steps in
        the engine's own cache, logits against the float32 full
        forward), and the routing beside it: the share of (layer,
        token) pairs at which the program's no-cache forward in the
        served precision chose another set of experts than the float32
        reference, and the largest reference margin (k-th minus
        (k+1)-th router probability) among those pairs."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from chipbench.reference import errors, within
        from chipbench.reference import olmoe as reference
        from ray_tpu.models import llama

        eng, cfg = self.engine, self.config
        live = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use")
        seq = np.random.default_rng([seed % (2**63), 11]).integers(
            0, cfg.vocab_size, CHECK_PROMPT_LEN
        ).tolist()
        logits, eng.cache = llama.prefill_into_slot(
            eng.params, jnp.asarray([seq], jnp.int32), eng.cache, jnp.int32(0), cfg
        )
        system = [logits[0]]
        for _ in range(CHECK_DECODE_STEPS):
            seq.append(int(jnp.argmax(system[-1])))
            tokens = np.zeros((eng.max_slots,), np.int32)
            pos = np.zeros((eng.max_slots,), np.int32)
            tokens[0], pos[0] = seq[-1], len(seq) - 1
            logits, eng.cache = llama.decode_step_rowwise(
                eng.params, jnp.asarray(tokens), eng.cache, jnp.asarray(pos), cfg
            )
            system.append(logits[0])
        first = CHECK_PROMPT_LEN - 1
        ref, routing = reference.forward(
            eng.params, jnp.asarray(seq, jnp.int32), cfg.rope_theta, cfg.rms_eps,
            cfg.experts_per_token,
            positions=list(range(first, first + 1 + CHECK_DECODE_STEPS)),
        )
        err = errors(jnp.stack(system), ref)
        chose = np.sort(np.asarray(jax.jit(llama.expert_choices, static_argnums=2)(
            eng.params, jnp.asarray([seq], jnp.int32), cfg
        ))[:, 0], axis=-1)                                     # (L, S, k)
        swapped = (chose != np.sort(np.asarray(routing["experts"]), axis=-1)).any(-1)
        margin = np.asarray(routing["margin"])
        temp = llama.decode_step_rowwise.lower(
            eng.params, jnp.asarray(tokens), eng.cache, jnp.asarray(pos), cfg
        ).compile().memory_analysis().temp_size_in_bytes
        return {"err": err, "tol": tolerance, "ok": within(err, tolerance),
                "live_bytes": live, "decode_temp_bytes": temp,
                "swap_rate": float(swapped.mean()),
                "swapped_margin_max": float(margin[swapped].max()) if swapped.any() else 0.0,
                "margin_p50": float(np.median(margin))}


def _moe_window(before: dict, after: dict, config) -> dict:
    """The routing counters of the measured window (``stats()`` after it
    minus ``stats()`` after warm-up) as the ``.moe`` readers' facts.
    ``moe_dropped`` is what no-drop routing keeps at 0: the token rows
    the engine gave the model in the window (a prompt's length per
    prefill, ``max_slots`` per decode step) times layers times experts
    per token, minus the rows the experts computed."""
    import numpy as np

    tokens = np.asarray(after["moe_expert_tokens"]) - np.asarray(before["moe_expert_tokens"])
    steps = after["moe_layer_steps_total"] - before["moe_layer_steps_total"]
    touched = after["moe_experts_touched_total"] - before["moe_experts_touched_total"]
    if steps <= 0 or tokens.sum() <= 0:
        raise RuntimeError("the expert layer counted no layer-step in the window")
    rows = after["rows_stepped_total"] - before["rows_stepped_total"]
    return {
        "moe_layer_steps": int(steps),
        "moe_assignments": int(tokens.sum()),
        "moe_dropped": int(
            rows * config.num_layers * config.experts_per_token - tokens.sum()
        ),
        "moe_experts_touched_mean": touched / steps,
        "moe_rows_per_layer_step_mean": float(tokens.sum()) / steps,
        "moe_expert_load_max_over_mean": float(tokens.max() / tokens.mean()),
    }


def run(ctx: dict) -> dict:
    """``serve_llm.run`` with the three things it hard-wires exchanged
    (the body below follows it line for line; a ``benchmark`` PR that
    may edit that file can make the two one)."""
    cell, cfg, traffic = ctx["cell"], dict(ctx["config"]), dict(ctx["traffic"])
    serving = dict(cfg["serving"])
    seconds = float(ctx["seconds"])
    if ctx["rehearse"]:
        cfg.update(REHEARSAL_MODEL)
        serving.update(max_slots=4, max_len=256)
        traffic.update(clients=8, prompt_len={"kind": "cycle", "values": [16, 32]},
                       new_tokens={"kind": "fixed", "value": 12},
                       stagger={"step": 2, "over": 4})
        traffic.update(ramp_s=1, trace_at_s=0.5, trace_for_s=1.0, requests_per_client=400)
    if traffic["loop"] != "closed":
        raise RuntimeError("the serve_moe job runs closed-loop mixes only")
    log = lambda msg: print(  # noqa: E731
        f"[serve_moe +{time.time() - ctx['t_process_start']:.1f}s] {msg}", flush=True)
    vocab = cfg["vocab_size"]
    config = moe_config(cfg)
    app = serve.deployment(MoeReplica, name="chipbench_llm").options(
        ray_actor_options={"num_tpus": 1}
    ).bind(
        config=config,
        weights_loader=functools.partial(make_weights, cfg, ctx["seed"], ctx["rehearse"]),
        max_slots=serving["max_slots"], max_len=serving["max_len"],
    )
    handle = serve.run(app, name="chipbench", route_prefix=None)

    def call(method, *args, timeout_s=120.0):
        return handle.options(method_name=method).remote(*args).result(timeout_s=timeout_s)

    stats = call("stats", timeout_s=REPLICA_START_TIMEOUT_S)
    log("replica up: " + str({k: v for k, v in stats.items() if k != "moe_expert_tokens"}))
    if not ctx["rehearse"] and (stats["platform"] != "tpu" or stats["device_count"] != 1):
        raise RuntimeError(
            f"the replica reports {stats['device_count']} device(s) of platform "
            f"{stats['platform']!r}; the cell asks for 1 TPU chip"
        )
    if not ctx["rehearse"] and stats["grouped_matmul"] != "pallas_gmm":
        raise RuntimeError(
            f"the replica's grouped matmul is {stats['grouped_matmul']!r} on a "
            "TPU; there is no fallback"
        )
    check = call("check_reference", ctx["seed"], cfg["reference_tolerance"],
                 timeout_s=600.0)
    log(f"reference: |system - float32 reference| / std = {check['err']} "
        f"(tolerance {check['tol']}); routing: {check['swap_rate']:.4%} of (layer, "
        f"token) pairs chose another expert set than the reference, largest "
        f"reference margin among them {check['swapped_margin_max']:.3e} (median "
        f"margin {check['margin_p50']:.3e}); live bytes {check['live_bytes']}, "
        f"decode temporaries {check['decode_temp_bytes']}")

    reqs = loadgen.schedule(traffic, ctx["seed"], seconds, serving["max_len"])
    for n in loadgen.prompt_lengths(traffic):
        warm = loadgen.Request(-1, None, None, n, 2, n)
        got = list(handle.options(method_name="generate", stream=True).remote(
            loadgen.prompt_tokens(warm, vocab), max_new_tokens=2
        ))
        if len(got) != 2:
            raise RuntimeError(f"warm-up of prompt length {n} returned {got}")
    before = call("stats")
    log(f"warm: programs {before['programs']}, compiles {before['compiles']}")

    # ---- ramp + the measured window -----------------------------------
    ramp = float(traffic["ramp_s"])
    clock0 = time.perf_counter() + ramp  # offset 0 = start of the window
    t_window = time.time() + ramp
    outcomes, threads, cancel = [], [], threading.Event()
    stop_sending = threading.Event()

    def client(mine):
        for req in mine:
            if stop_sending.is_set():
                return
            _consume(handle, req, vocab, clock0, outcomes, cancel)

    for c in range(traffic["clients"]):
        mine = [r for r in reqs if r.client == c]
        threads.append(threading.Thread(target=client, args=(mine,), daemon=True))
    for t in threads:
        t.start()

    traced = {}
    if ctx["trace"]:
        time.sleep(max(0.0, clock0 + traffic["trace_at_s"] - time.perf_counter()))
        call("trace_start", ctx["trace_dir"])
        traced["t1"] = time.perf_counter() - clock0
        time.sleep(traffic["trace_for_s"])
        traced["t2"] = time.perf_counter() - clock0
        traced["host_s"] = call("trace_stop", timeout_s=300.0)
        traced["t3"] = time.perf_counter() - clock0
    time.sleep(max(0.0, clock0 + seconds - time.perf_counter()))
    stop_sending.set()
    after = call("stats")
    # closed loop: the clients' work is cut where the window ends
    cancel.set()
    deadline = time.perf_counter() + 1.0 + float(traffic["drain_s"])
    for t in list(threads):
        t.join(max(0.0, deadline - time.perf_counter()))
    final = call("stats")
    log(f"after the window: compiles {after['compiles']}, admitted "
        f"{final['admitted_total']}, shed {final['shed_total']}, peak bytes "
        f"{final['peak_bytes_in_use']}")

    summary = loadgen.summarize(list(outcomes), seconds, False)
    measured = summary["measured"]
    failures = [
        f for f in (loadgen.request_failed(o, vocab, cut_ok=True)
                    for o in measured) if f
    ]
    for f in failures[:5]:
        log(f"failed request: {f}")
    for what in ("ttft_ms", "itl_ms"):
        xs = summary[what]
        if xs:
            log(f"{what}: n={len(xs)} " + " ".join(
                f"p{q}={loadgen.percentile(xs, q):.1f}" for q in (50, 90, 95, 99, 100)))
    log(_longest_gap(list(outcomes)))
    if not summary["ttft_ms"] or not summary["itl_ms"]:
        raise RuntimeError("no request of the window produced a token")
    moe = _moe_window(before, after, config)
    log(f"expert layer over the window: {moe}")
    if moe["moe_dropped"]:
        log("the experts computed another number of rows than were routed: "
            "the run is not correct")
    facts = {
        "ttft_ms": summary["ttft_ms"], "itl_ms": summary["itl_ms"],
        "lag_ms": summary["lag_ms"], "max_slots": serving["max_slots"],
        "compiles_in_window": after["compiles"]["count"] - before["compiles"]["count"],
        "programs_before": before["programs"], "programs_after": after["programs"],
        "reference_err_rms": check["err"]["rms"],
        "reference_err_max": check["err"]["max"],
        "reference_swap_rate": check["swap_rate"],
        "reference_swapped_margin_max": check["swapped_margin_max"],
        "moe_embed": config.embed_dim, "moe_expert_dim": config.expert_dim,
        "moe_itemsize": 2 if cfg["dtype"] == "bfloat16" else 4,
        **moe,
    }
    if traced:
        facts["trace_host_s"] = traced["host_s"]
        facts["tokens_while_traced"] = sum(
            1 for o in list(outcomes) for t in o.token_s[1:]
            if traced["t1"] <= t < traced["t2"]
        )
        facts["traced_client_s"] = traced["t2"] - traced["t1"]
        # how long stop_trace() held the replica (no guard reads it here)
        facts["trace_stop_s"] = traced["t3"] - traced["t2"]
    serve.delete("chipbench")
    serve.shutdown()
    return {
        "device": {
            "platform": stats["platform"], "kind": stats["device_kind"],
            "count": stats["device_count"],
            "memory_peak_bytes": (
                max(final["peak_bytes_in_use"],
                    check["live_bytes"] + check["decode_temp_bytes"])
                if final["peak_bytes_in_use"] else None
            ),
        },
        "setup_s": t_window - ctx["t_process_start"],
        "attempted": len(measured),
        "failed": len(failures),
        "correct": bool(check["ok"] and moe["moe_dropped"] == 0),
        "end_to_end": {
            "serve_tokens_per_s": summary["tokens_per_s"],
            "itl_p95_ms": loadgen.percentile(summary["itl_ms"], 95),
        },
        "facts": facts,
    }
