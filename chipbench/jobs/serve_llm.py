"""Job kind ``serve_llm``: ``serve.run`` of a decode replica, requests
through the deployment handle's streaming path, ``LLMEngine`` on the
chip.

The parent generates load and stamps tokens (``chipbench/loadgen.py``
says what to send); it never opens a jax backend.  The replica holds
the chip, so the three things only the chip's holder can do are
methods of the replica's class: take a profiler trace of itself,
compare itself with the float32 reference, and count its compiles
(that one is ``LlamaDeployment.stats`` already).
"""

from __future__ import annotations

import functools
import threading
import time

from chipbench import contract, loadgen
from ray_tpu import serve
from ray_tpu.serve.llm import LlamaDeployment

REHEARSAL_MODEL = {
    "hidden_size": 64, "intermediate_size": 160, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 512,
}
REPLICA_START_TIMEOUT_S = 900.0
CHECK_PROMPT_LEN = 128
CHECK_DECODE_STEPS = 2


def llama_config(cfg: dict):
    """The configuration file's keys -> the program's LlamaConfig."""
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    return LlamaConfig(
        vocab_size=cfg["vocab_size"], max_seq_len=cfg["max_position_embeddings"],
        num_layers=cfg["num_hidden_layers"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], embed_dim=cfg["hidden_size"],
        mlp_dim=cfg["intermediate_size"], rope_theta=float(cfg["rope_theta"]),
        rms_eps=cfg["rms_norm_eps"], dtype=getattr(jnp, cfg["dtype"]),
        param_dtype=getattr(jnp, cfg["param_dtype"]), sliding_window=0,
        tie_embeddings=cfg["tie_word_embeddings"],
    )


def make_weights(cfg: dict, seed: int, rehearse: bool):
    """``weights_loader``: runs in the replica.  One jitted program
    makes every weight on the device, in the type it is served in
    (``llama.init`` run eagerly is a compile per tensor: 88 s, PR 21)."""
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
    config = llama_config(cfg)
    params = jax.jit(functools.partial(llama.init, config=config))(
        jax.random.key(seed % (2**31))
    )
    return jax.block_until_ready(params)


class BenchReplica(LlamaDeployment.func_or_class):
    """``LlamaDeployment`` plus what only the chip's holder can do."""

    def trace_start(self, trace_dir: str) -> bool:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        self._trace_t0 = time.perf_counter()
        return True

    def trace_stop(self) -> float:
        import jax

        host_s = time.perf_counter() - self._trace_t0
        jax.profiler.stop_trace()
        return host_s

    def check_reference(self, seed: int, tolerance: dict) -> dict:
        """One prompt through ``prefill_into_slot`` and two
        ``decode_step_rowwise`` steps, in the engine's own cache (slot
        0, before any traffic), against the float32 full forward of
        ``chipbench/reference/llama.py``: logits, not tokens."""
        import jax.numpy as jnp
        import numpy as np

        from chipbench.reference import errors, within
        from chipbench.reference import llama as reference
        from ray_tpu.models import llama

        import jax

        eng, cfg = self.engine, self.config
        # The allocator's peak counts live buffers only (PERF.md section
        # 6, PR 23); what the chip holds while decoding is the weights and
        # cache live now plus the decode program's own temporaries.
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
        ref = reference.forward(
            eng.params, jnp.asarray(seq, jnp.int32), cfg.rope_theta, cfg.rms_eps,
            positions=list(range(first, first + 1 + CHECK_DECODE_STEPS)),
        )
        err = errors(jnp.stack(system), ref)
        temp = llama.decode_step_rowwise.lower(
            eng.params, jnp.asarray(tokens), eng.cache, jnp.asarray(pos), cfg
        ).compile().memory_analysis().temp_size_in_bytes
        return {"err": err, "tol": tolerance, "ok": within(err, tolerance),
                "live_bytes": live, "decode_temp_bytes": temp}


def _longest_gap(outcomes) -> str:
    """Where the longest silence of any stream lay and how many of the
    streams alive across it shared it: one slow request, or the replica
    (or the whole machine) stopped.  For the log; PERF.md section 6."""
    gaps = [(b - a, a, b) for o in outcomes for a, b in zip(o.token_s, o.token_s[1:])]
    if not gaps:
        return "no token gap"
    length, a, b = max(gaps)
    live = [o for o in outcomes
            if o.token_s and o.token_s[0] <= a and o.token_s[-1] >= b]
    silent = sum(1 for o in live if not any(a + 0.01 < t < b - 0.01 for t in o.token_s))
    return (f"longest token gap {length:.3f} s, from {a:+.3f} s of the window; "
            f"{silent} of {len(live)} streams alive across it had no token in it")


def _consume(handle, req, vocab, clock0, out, cancel):
    """Send one request and stamp every token as it arrives."""
    prompt = loadgen.prompt_tokens(req, vocab)
    o = loadgen.Outcome(req, time.perf_counter() - clock0, [], [])
    out.append(o)
    gen = None
    try:
        gen = handle.options(method_name="generate", stream=True).remote(
            prompt, max_new_tokens=req.new_tokens
        )
        for tok in gen:
            o.token_s.append(time.perf_counter() - clock0)
            o.tokens.append(tok)
            if cancel.is_set():
                gen.cancel()
                return
        o.finished = True
    except Exception as e:  # noqa: BLE001 — counted as a failed request
        o.error = f"{type(e).__name__}: {(str(e).splitlines() or [''])[0]}"


def run(ctx: dict) -> dict:
    cell, cfg, traffic = ctx["cell"], dict(ctx["config"]), dict(ctx["traffic"])
    serving = dict(cfg["serving"])
    seconds = float(ctx["seconds"])
    if ctx["rehearse"]:
        cfg.update(REHEARSAL_MODEL)
        serving.update(max_slots=4, max_len=256)
        if traffic["loop"] == "closed":
            traffic.update(clients=8, prompt_len={"kind": "cycle", "values": [16, 32]},
                           new_tokens={"kind": "fixed", "value": 12},
                           stagger={"step": 2, "over": 4})
        else:
            traffic.update(prompt_len={**traffic["prompt_len"], "median": 24,
                                       "buckets": [16, 32, 48]},
                           new_tokens={"kind": "uniform_int", "low": 4, "high": 12})
        traffic.update(ramp_s=1, trace_at_s=0.5, trace_for_s=1.0, requests_per_client=400)
    log = lambda msg: print(  # noqa: E731
        f"[serve_llm +{time.time() - ctx['t_process_start']:.1f}s] {msg}", flush=True)
    vocab = cfg["vocab_size"]
    app = serve.deployment(BenchReplica, name="chipbench_llm").options(
        ray_actor_options={"num_tpus": 1}
    ).bind(
        config=llama_config(cfg),
        weights_loader=functools.partial(make_weights, cfg, ctx["seed"], ctx["rehearse"]),
        max_slots=serving["max_slots"], max_len=serving["max_len"],
    )
    handle = serve.run(app, name="chipbench", route_prefix=None)

    def call(method, *args, timeout_s=120.0):
        return handle.options(method_name=method).remote(*args).result(timeout_s=timeout_s)

    stats = call("stats", timeout_s=REPLICA_START_TIMEOUT_S)
    log(f"replica up: {stats}")
    if not ctx["rehearse"] and (stats["platform"] != "tpu" or stats["device_count"] != 1):
        raise RuntimeError(
            f"the replica reports {stats['device_count']} device(s) of platform "
            f"{stats['platform']!r}; the cell asks for 1 TPU chip"
        )
    check = call("check_reference", ctx["seed"], cfg["reference_tolerance"],
                 timeout_s=600.0)
    log(f"reference: |system - float32 reference| / std = {check['err']} "
        f"(tolerance {check['tol']}); live bytes {check['live_bytes']}, decode "
        f"temporaries {check['decode_temp_bytes']}")

    # warm every prompt length of the mix through the normal entry point
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

    if traffic["loop"] == "closed":
        for c in range(traffic["clients"]):
            mine = [r for r in reqs if r.client == c]
            threads.append(threading.Thread(target=client, args=(mine,), daemon=True))
        for t in threads:
            t.start()
    else:
        # one sleeping thread per request, all started before the first
        # is due: nothing is created or queued at the moment of sending
        def at_its_time(req):
            delay = clock0 + req.due_s - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            _consume(handle, req, vocab, clock0, outcomes, cancel)

        for req in reqs:
            threads.append(threading.Thread(target=at_its_time, args=(req,), daemon=True))
        for t in threads:
            t.start()

    traced = {}
    if ctx["trace"]:
        time.sleep(max(0.0, clock0 + traffic["trace_at_s"] - time.perf_counter()))
        call("trace_start", ctx["trace_dir"])
        traced["t1"] = time.perf_counter() - clock0
        time.sleep(traffic["trace_for_s"])
        traced["t2"] = time.perf_counter() - clock0
        # stop_trace() holds the replica from here until it returns
        traced["host_s"] = call("trace_stop", timeout_s=300.0)
        traced["t3"] = time.perf_counter() - clock0
    time.sleep(max(0.0, clock0 + seconds - time.perf_counter()))
    stop_sending.set()
    after = call("stats")
    # Open loop: a request due in the window has drain_s more to finish,
    # and one that has not is a failure.  Closed loop (drain_s 0): the
    # clients' work is cut where the window ends; what a stream had
    # delivered by then is checked as far as it got.
    deadline = time.perf_counter() + float(traffic["drain_s"])
    for t in list(threads):
        t.join(max(0.0, deadline - time.perf_counter()))
    cancel.set()
    deadline = time.perf_counter() + 1.0
    for t in list(threads):
        t.join(max(0.0, deadline - time.perf_counter()))
    final = call("stats")
    log(f"after the window: compiles {after['compiles']}, admitted "
        f"{final['admitted_total']}, shed {final['shed_total']}, peak bytes "
        f"{final['peak_bytes_in_use']}")

    open_loop = traffic["loop"] == "open"
    summary = loadgen.summarize(
        list(outcomes), seconds, open_loop,
        frozen=(traced["t2"], traced["t3"]) if traced else None)
    measured = summary["measured"]
    failures = [
        f for f in (loadgen.request_failed(o, vocab, cut_ok=not open_loop)
                    for o in measured) if f
    ]
    never_sent = 0
    if open_loop:  # due in the window but never sent: the generator fell behind
        sent = {o.request.index for o in outcomes}
        never_sent = sum(
            1 for r in reqs if 0.0 <= r.due_s < seconds and r.index not in sent
        )
        failures += ["never sent"] * never_sent
    for f in failures[:5]:
        log(f"failed request: {f}")
    for o in measured:
        if open_loop and o.sent_s - o.request.due_s > 0.1:
            log(f"late: request {o.request.index} due {o.request.due_s:.3f} sent "
                f"{o.sent_s:.3f} first token {o.token_s[:1]}")
    for what in ("ttft_ms", "itl_ms", "lag_ms"):
        xs = summary[what]
        if xs:
            log(f"{what}: n={len(xs)} " + " ".join(
                f"p{q}={loadgen.percentile(xs, q):.1f}" for q in (50, 90, 95, 99, 100)))
    log(_longest_gap(list(outcomes)))
    if traced:
        log(f"stop_trace() held the replica {traced['t3'] - traced['t2']:.1f} s from "
            f"{traced['t2']:+.1f} s of the window; {summary['ttft_left_out']} "
            f"request(s) that started in it (or {loadgen.FROZEN_LEAD_S:g} s before) "
            "are left out of ttft_ms")
    if not summary["ttft_ms"] or not summary["itl_ms"]:
        raise RuntimeError("no request of the window produced a token")
    log("itl_ms: " + ", ".join(
        f"{loadgen.share_over_median(summary['itl_ms'], k):.3f}% of the gaps over "
        f"{k:g} x their median" for k in (loadgen.STALLED_GAP_FACTOR, 3.0)))
    # A mix may state how long half of its requests may wait for their
    # first token.  It is a guard on ``correct``, not a judged metric:
    # a median holds when a process stops for seconds, a tail does not.
    # In a traced run it is held against the requests the profiler's
    # stop did not freeze (``loadgen.summarize``).
    broken = loadgen.ttft_guard(summary["ttft_ms"], traffic.get("ttft_p50_limit_ms"))
    if broken:
        log(f"{broken}: the run is not correct")
    facts = {
        "ttft_ms": summary["ttft_ms"], "itl_ms": summary["itl_ms"],
        "lag_ms": summary["lag_ms"], "max_slots": serving["max_slots"],
        "compiles_in_window": after["compiles"]["count"] - before["compiles"]["count"],
        "programs_before": before["programs"], "programs_after": after["programs"],
        "reference_err_rms": check["err"]["rms"],
        "reference_err_max": check["err"]["max"],
    }
    if traced:
        facts["trace_host_s"] = traced["host_s"]
        facts["tokens_while_traced"] = sum(
            1 for o in list(outcomes) for t in o.token_s[1:]
            if traced["t1"] <= t < traced["t2"]
        )
        facts["traced_client_s"] = traced["t2"] - traced["t1"]
        facts["trace_stop_s"] = traced["t3"] - traced["t2"]
        facts["ttft_left_out"] = summary["ttft_left_out"]
    end_to_end = {
        "serve_tokens_per_s": summary["tokens_per_s"],
        "itl_p95_ms": loadgen.percentile(summary["itl_ms"], 95),
    }
    # only where the cell is judged on it: it refuses a window of under
    # 1,000 gaps, which the other cells need not have
    if "itl_tail_mean_ms" in contract.declared_metrics(
            contract.load_benchmark(), cell["name"], 0):
        end_to_end["itl_tail_mean_ms"] = loadgen.interquantile_mean(
            summary["itl_ms"], *loadgen.TAIL_MEAN_RANGE)
        log(f"itl_tail_mean_ms: {end_to_end['itl_tail_mean_ms']:.4f}, the mean of the "
            f"gaps from the {loadgen.TAIL_MEAN_RANGE[0]}th percentile to under the "
            f"{loadgen.TAIL_MEAN_RANGE[1]}th")
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
        "attempted": len(measured) + never_sent,
        "failed": len(failures),
        "correct": bool(check["ok"] and not broken),
        "end_to_end": end_to_end,
        "facts": facts,
    }
