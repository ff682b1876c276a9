"""Job kind ``train_spmd``: one ``JaxTrainer`` worker holding the cell's
chips as one mesh, a GPT-2 step program, a timed window of steps.

``run`` is the parent's side (no jax backend is ever opened there);
``loop`` runs in the leased worker, which holds the chip and therefore
also takes the profiler trace and makes the float32 comparison.
"""

from __future__ import annotations

import math
import os
import time

REHEARSAL_MODEL = {"n_layer": 2, "n_embd": 64, "n_head": 4, "vocab_size": 512}
REHEARSAL_SEQ = 128


def gpt_config(cfg: dict, seq_len: int):
    """The configuration file's keys -> the program's GPTConfig."""
    import jax.numpy as jnp

    from ray_tpu.models.gpt2 import GPTConfig

    return GPTConfig(
        vocab_size=cfg["vocab_size"], max_seq_len=cfg["n_positions"],
        num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
        embed_dim=cfg["n_embd"], mlp_ratio=4,
        dtype=getattr(jnp, cfg["dtype"]),
        param_dtype=getattr(jnp, cfg["param_dtype"]),
        **cfg["program_config"],
    )


def host_batch(seed: int, step: int, batch: int, seq_len: int, vocab: int):
    """The step's tokens, drawn on the host: (batch, seq_len + 1) int32."""
    import numpy as np

    return np.random.default_rng([seed % (2**63), step]).integers(
        0, vocab, (batch, seq_len + 1), dtype=np.int32
    )


def loop(config: dict) -> None:
    """``train_loop_per_worker``.  Everything the parent needs travels
    in ``train.report``; the last report has ``kind == "result"``."""
    import jax
    import optax

    from chipbench.reference import errors, within
    from chipbench.reference import gpt2 as reference
    from ray_tpu import train
    from ray_tpu.models import gpt2
    from ray_tpu.parallel import mesh as mesh_mod
    from ray_tpu.parallel import spmd
    from ray_tpu.util import compile_cache

    cache_dir = compile_cache.configure()
    compiles = compile_cache.CompileLog()
    devices = jax.devices()  # opens the leased chips
    dev = devices[0]
    if not config["rehearse"]:
        if dev.platform != "tpu":
            raise RuntimeError(
                f"the worker was leased {config['chips']} TPU chip(s) but jax "
                f"came up on platform {dev.platform!r}; there is no CPU fallback"
            )
        if len(devices) != config["chips"]:
            raise RuntimeError(
                f"the cell asks for {config['chips']} chip(s), the worker sees "
                f"{len(devices)}"
            )
    devices = devices[: config["chips"]]
    cfg, traffic, seed = config["model"], config["traffic"], config["seed"]
    B, S = traffic["batch"], traffic["seq_len"]
    model = gpt_config(cfg, S)
    mesh = mesh_mod.make_mesh(mesh_mod.MeshConfig(**traffic["mesh"]), devices=devices)
    opt = cfg["optimizer"]
    optimizer = optax.adamw(opt["learning_rate"], weight_decay=opt["weight_decay"])
    state = spmd.sharded_init(
        mesh, lambda rng: gpt2.init(rng, model), jax.random.key(seed % (2**31)),
        gpt2.param_logical_axes(model), optimizer,
    )
    step = spmd.compile_train_step(lambda p, b: gpt2.loss_fn(p, b, model), optimizer)
    log = lambda msg: print(  # noqa: E731
        f"[train_spmd +{time.time() - config['t_process_start']:.1f}s] {msg}", flush=True)
    log(f"chips open: {len(devices)} x {dev.device_kind}")

    def place(i):
        return spmd.shard_batch(
            mesh, {"tokens": host_batch(seed, i, B, S, model.vocab_size)}
        )

    with mesh_mod.use(mesh):
        def one_step(i, state):
            # the callable compile_train_step returns, as a user runs it
            state, metrics = step(state, place(i))
            return state, metrics["loss"]

        n = 0
        for _ in range(traffic["warmup_steps"]):
            state, loss = one_step(n, state)
            jax.block_until_ready(loss)
            n += 1
        before = compiles.snapshot()
        log(f"warm after {n} steps; compiles so far {before}; cache {cache_dir}")

        # ---- the measured window --------------------------------------
        t_window = time.time()
        t0 = time.perf_counter()
        t_end = t0 + config["seconds"]
        step_ms, losses = [], []
        tracing, trace_first, trace_host_s = None, None, None
        while True:
            k = len(step_ms)
            if config["trace"] and tracing is None and k == traffic["trace_skip_steps"]:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(config["trace_dir"], profiler_options=opts)
                tracing, trace_first = time.perf_counter(), k
            ts = time.perf_counter()
            state, loss = one_step(n, state)
            loss = float(jax.block_until_ready(loss))
            step_ms.append((time.perf_counter() - ts) * 1e3)
            losses.append(loss)
            n += 1
            if tracing is not None and trace_host_s is None and (
                len(step_ms) - trace_first >= traffic["trace_steps"]
            ):
                trace_host_s = time.perf_counter() - tracing
                jax.profiler.stop_trace()
            if len(step_ms) % traffic["report_every"] == 0:
                train.report({"kind": "progress", "step": n, "loss": loss})
            if time.perf_counter() >= t_end:
                break
        elapsed = time.perf_counter() - t0
        after = compiles.snapshot()
        if config["trace"] and trace_host_s is None:
            if tracing is not None:
                jax.profiler.stop_trace()
            raise RuntimeError(
                f"the window ended after {len(step_ms)} steps, before "
                f"{traffic['trace_skip_steps']} + {traffic['trace_steps']} traced "
                "steps were done"
            )
        # The allocator's peak counts live buffers only, not what a
        # program holds while it runs (PERF.md section 6, PR 23): the
        # chip's peak is the live state plus the step's temporaries, which
        # only the compiler tells.  The same step is lowered by hand to
        # ask it, after the window and after the compile count is taken;
        # the executable comes from the persistent cache.
        temp_bytes = step.lower(state, place(0)).compile(
        ).memory_analysis().temp_size_in_bytes
        peak = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
        in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
        log(f"window: {len(step_ms)} steps in {elapsed:.3f} s (longest "
            f"{max(step_ms):.0f} ms); allocator peak "
            f"{peak}; in use {in_use}; step temporaries {temp_bytes}; compiles "
            f"with the memory analysis {compiles.snapshot()}")
        peak = [max(p, u + temp_bytes) for p, u in zip(peak, in_use) if p and u]

        # ---- correctness, outside the window --------------------------
        n_seq = traffic["check_sequences"]
        toks = host_batch(seed, 10**9, n_seq, S - 1, model.vocab_size)
        sys_logits = jax.jit(lambda p, t: gpt2.forward(p, t, model))(
            state.params, spmd.shard_batch(mesh, toks)
        )[0]
        ref_logits = reference.forward(state.params, toks[0], model.num_heads)
        err = errors(sys_logits, ref_logits)
        ref_ok = within(err, cfg["reference_tolerance"])
        log(f"reference: |system - float32 reference| / std = {err} (tolerance "
            f"{cfg['reference_tolerance']}) on one {S}-token sequence")
    finite = all(math.isfinite(x) for x in losses)
    head = sum(losses[:10]) / len(losses[:10])
    tail = sum(losses[-10:]) / len(losses[-10:])
    train.report({
        "kind": "result",
        "device": {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(peak) if peak else None,
        },
        "t_window": t_window, "elapsed_s": elapsed, "steps": len(step_ms),
        "step_ms": step_ms, "tokens_per_step": B * S,
        "nonfinite_steps": sum(not math.isfinite(x) for x in losses),
        "loss_first10": head, "loss_last10": tail,
        "reference_err": err,
        "correct": bool(finite and tail <= head and ref_ok),
        "compiles_before": before, "compiles_after": after,
        "trace_host_s": trace_host_s, "pid": os.getpid(),
    })


def run(ctx: dict) -> dict:
    """Parent side: start the trainer, wait, turn the worker's result
    into the job result the harness reads."""
    from chipbench import flops
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    cell, cfg, traffic = ctx["cell"], dict(ctx["config"]), dict(ctx["traffic"])
    if ctx["rehearse"]:
        cfg.update(REHEARSAL_MODEL)
        traffic.update(seq_len=REHEARSAL_SEQ, batch=max(2, cell["chips"] * 2),
                       trace_skip_steps=1, trace_steps=2)
    result = JaxTrainer(
        loop,
        train_loop_config={
            "model": cfg, "traffic": traffic, "seed": ctx["seed"],
            "seconds": ctx["seconds"], "trace": ctx["trace"],
            "trace_dir": ctx["trace_dir"], "chips": cell["chips"],
            "rehearse": ctx["rehearse"],
            "t_process_start": ctx["t_process_start"],
        },
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=True, tpus_per_worker=cell["chips"]
        ),
        run_config=RunConfig(name="chipbench_" + cell["name"],
                             storage_path=ctx["storage_dir"]),
    ).fit()
    if result.error is not None:
        raise RuntimeError(f"the trainer failed: {result.error}")
    res = next(
        (r for r in reversed(result.metrics_dataframe or []) if r.get("kind") == "result"),
        None,
    )
    if res is None:
        raise RuntimeError("the training loop ended without its result report")
    tokens_per_s_per_chip = (
        res["steps"] * res["tokens_per_step"] / res["elapsed_s"] / cell["chips"]
    )
    return {
        "device": res["device"],
        "setup_s": res["t_window"] - ctx["t_process_start"],
        "attempted": res["steps"],
        "failed": res["nonfinite_steps"],
        "correct": res["correct"],
        "end_to_end": {"train_tokens_per_s_per_chip": tokens_per_s_per_chip},
        "facts": {
            "step_ms": res["step_ms"],
            "tokens_per_s_per_chip": tokens_per_s_per_chip,
            "tokens_per_step": res["tokens_per_step"],
            "flops_per_token": flops.gpt2_train_flops_per_token(cfg, traffic["seq_len"]),
            "compiles_in_window": res["compiles_after"]["count"] - res["compiles_before"]["count"],
            "reference_err_rms": res["reference_err"]["rms"],
            "reference_err_max": res["reference_err"]["max"],
            "loss_first10": res["loss_first10"], "loss_last10": res["loss_last10"],
            "trace_host_s": res["trace_host_s"],
        },
    }
