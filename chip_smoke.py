#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

Drives ray_tpu's two main paths once each, through the entry points a
user calls, on a real TPU, and checks what comes out:

    python chip_smoke.py             # one chip: train, then serve
    python chip_smoke.py --chips 4   # four chips: mesh and leases only
    python chip_smoke.py --chips 4 --pipeline   # four chips: pipeline only

One chip (what the driver runs), in one cluster:

- *train*: ``JaxTrainer`` (one worker, one leased chip) lays GPT-2-124M
  out at its published size with ``spmd.sharded_init`` and takes 5 AdamW
  steps of ``spmd.compile_train_step`` at B=8 x S=1024 with the Pallas
  flash-attention kernel.
- *serve*: ``serve.run(LlamaDeployment...)`` (one replica, one leased
  chip) at Mistral-7B widths, depth cut to what one chip holds; four
  streaming requests through the deployment handle, then one of them
  again, alone.

Four chips (``--chips 4``; nothing of the above), what exists only
across chips (``--chips 4 --pipeline`` runs, instead, two steps of the
MPMD pipeline with a chip per stage actor):

- *mesh*: one worker holding four chips runs the same GPT-2 loop on an
  fsdp=2 x tp=2 mesh and again on one of its chips, from the same seed.
- *leases*: four one-chip actors alive at once, then two two-chip ones,
  each opening exactly its own chips.

This process never opens a jax backend: a chip belongs to one process
at a time, and every process that touches one here is a worker the
raylet leased it to.  Nothing sets JAX_PLATFORMS; chips are found by
``ray_tpu.init()``'s own detection.  Without an accelerator, or if a
leased worker comes up on the host, the script fails: there is no CPU
mode.  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
on success and ``{"ok": false, ...}`` (exit code != 0) otherwise.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import shutil
import signal
import subprocess
import threading
import time
import traceback

#: hard stop for the whole run, compilation included (the driver allows
#: 1200 s on one chip; the builder gives the four-chip call more)
DEADLINE_S = {1: 1080.0, 4: 1700.0}

#: what a leased worker must come up on, and what proves in the compiled
#: step's text that the Pallas kernel ran compiled
PLATFORM, KERNEL_MARKER = "tpu", "tpu_custom_call"

#: GPT-2-124M as published (GPTConfig's defaults: 12 layers, 12 heads,
#: 768 wide, vocab 50,304, bf16 compute) with the flash kernel, no remat
GPT2_MODEL = {"attention_impl": "flash", "remat": False}
GPT2_BATCH, GPT2_SEQ, GPT2_STEPS = 8, 1024, 5
#: the pipeline's stage programs run outside any mesh, on the model's
#: plain attention path
PIPELINE_MODEL = {"remat": False}
#: |loss on the fsdp x tp mesh - loss on one chip| per step.  Same seed,
#: same batch, bf16 matmuls whose reduction order differs with the
#: sharding; the loss is ~10.8, so this is about half a percent.
MESH_LOSS_TOL = 0.05

#: Mistral-7B's published widths (LlamaConfig.mistral_7b) with the depth
#: cut from 32 layers to what one 16 GB chip holds beside its cache
SERVE_MODEL = {"num_layers": 8}
SERVE_SLOTS, SERVE_MAX_LEN = 8, 1024
SERVE_PROMPT_LENS = (128, 512, 128, 512)
SERVE_NEW_TOKENS = 16
REPLICA_START_TIMEOUT_S = 420.0
REQUEST_TIMEOUT_S = 300.0

#: one line per run: what the previous run in this checkout compiled in,
#: so a second run in one chip-tool command can print both
RUN_LOG = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "chiprun_out", "chip_smoke.jsonl"
)


class SmokeFailure(Exception):
    """A check of this script did not hold."""


class WrongPlatform(SmokeFailure):
    """A leased worker came up on something other than the chip.  Ends
    the run at once: every later phase would build its model there."""


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# what runs in leased workers
# ---------------------------------------------------------------------------


def _gpt2_loop(config):
    """JaxTrainer loop: GPT-2-124M at its published size, AdamW, one
    fixed seeded batch, on each mesh of ``config["runs"]`` in turn.
    Everything the parent prints or checks travels in train.report."""
    import os
    import time
    import warnings

    import jax
    import numpy as np
    import optax

    from __graft_entry__ import _assert_no_involuntary_remat
    from ray_tpu import train
    from ray_tpu.models import gpt2
    from ray_tpu.parallel import mesh as mesh_mod
    from ray_tpu.parallel import spmd
    from ray_tpu.util import compile_cache

    devices = jax.devices()  # opens the leased chips
    dev = devices[0]
    train.report({
        "kind": "devices", "platform": dev.platform,
        "device_kind": dev.device_kind, "device_count": len(devices),
        "pid": os.getpid(),
        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        "cache_dir": compile_cache.configure(),
    })
    if dev.platform != config["platform"]:
        raise RuntimeError(
            f"worker was leased {config['chips']} TPU chip(s) but jax came "
            f"up on platform {dev.platform!r}"
        )
    if len(devices) != config["chips"]:
        raise RuntimeError(
            f"worker was leased {config['chips']} chip(s) but sees "
            f"{len(devices)} devices"
        )

    seed = config["seed"]
    model = gpt2.GPTConfig.gpt2_124m(**config["model"])
    tokens = np.random.default_rng(seed).integers(
        0, model.vocab_size, (config["batch"], config["seq"] + 1),
        dtype=np.int32,
    )
    for run in config["runs"]:
        devs = devices[: run["n_devices"]]
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            mesh = mesh_mod.make_mesh(
                mesh_mod.MeshConfig(**run["mesh"]), devices=devs
            )
        optimizer = optax.adamw(3e-4, weight_decay=0.1)
        state = spmd.sharded_init(
            mesh,
            lambda rng: gpt2.init(rng, model),
            jax.random.key(seed),
            gpt2.param_logical_axes(model),
            optimizer,
        )
        jax.block_until_ready(state)
        state_bytes = sum(a.nbytes for a in jax.tree.leaves(state))
        held = [(d.memory_stats() or {}).get("bytes_in_use") for d in devs]
        with mesh_mod.use(mesh):
            batch = spmd.shard_batch(mesh, {"tokens": tokens})
            step = spmd.compile_train_step(
                lambda p, b: gpt2.loss_fn(p, b, model), optimizer
            )
            t0 = time.perf_counter()
            with _assert_no_involuntary_remat():
                compiled = step.lower(state, batch).compile()
            compile_s = time.perf_counter() - t0
            n_kernels = compiled.as_text().count(config["kernel_marker"])
            train.report({
                "kind": "compiled", "run": run["name"],
                "mesh": mesh_mod.MeshConfig(**run["mesh"]).describe(),
                "compile_seconds": compile_s,
                "kernel_calls": n_kernels,
                "mesh_warnings": [str(w.message) for w in caught],
                "state_bytes": state_bytes,
                "bytes_in_use_after_init": held,
            })
            if n_kernels == 0:
                raise RuntimeError(
                    f"no {config['kernel_marker']} in the compiled step: the "
                    "flash kernel was interpreted or gave way to the dense "
                    "path"
                )
            for i in range(config["steps"]):
                t0 = time.perf_counter()
                state, metrics = compiled(state, batch)
                loss = float(jax.block_until_ready(metrics["loss"]))
                train.report({
                    "kind": "step", "run": run["name"], "step": i,
                    "loss": loss, "seconds": time.perf_counter() - t0,
                })
        train.report({
            "kind": "memory", "run": run["name"],
            "peak_bytes_in_use": [
                (d.memory_stats() or {}).get("peak_bytes_in_use")
                for d in devs
            ],
        })
        mesh_mod.set_current_mesh(None)
        del state, batch, compiled, metrics


class _LeaseProbe:
    """Actor body for the leases phase: open the chips this process was
    leased, say which they are, and run one jitted bf16 matmul."""

    def probe(self):
        import os

        import jax
        import jax.numpy as jnp

        import numpy as np
        from jax.sharding import Mesh, PartitionSpec as P

        devices = jax.devices()
        x = jnp.ones((2048, 2048), jnp.bfloat16)
        sums = []
        for d in devices:
            y = jax.jit(lambda a: a @ a)(jax.device_put(x, d))
            sums.append(float(jax.block_until_ready(y)[0, 0]))
        # and one sum across the leased chips: they must be able to
        # reach each other, not only exist
        across = jax.jit(jax.shard_map(
            lambda v: jax.lax.psum(v, "i"),
            mesh=Mesh(np.array(devices), ("i",)),
            in_specs=P("i"), out_specs=P(),
        ))(jnp.ones(len(devices)))
        fds = set()
        for fd in os.listdir("/proc/self/fd"):
            try:
                target = os.readlink(f"/proc/self/fd/{fd}")
            except OSError:
                continue
            if target.startswith(("/dev/accel", "/dev/vfio/")):
                fds.add(target)
        return {
            "pid": os.getpid(),
            "platform": devices[0].platform,
            "n_devices": len(devices),
            "leased_chips": os.environ.get("TPU_VISIBLE_CHIPS", ""),
            "open_device_files": sorted(fds - {"/dev/vfio/vfio"}),
            "matmul_ok": all(s == 2048.0 for s in sums),
            "sum_across_chips": float(across[0]),
        }


# ---------------------------------------------------------------------------
# phases (parent side)
# ---------------------------------------------------------------------------


def _fit_gpt2(chips: int, runs: list, seed: int, name: str) -> list:
    """Run _gpt2_loop under JaxTrainer on one worker holding ``chips``
    chips; print every report; return them."""
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    result = JaxTrainer(
        _gpt2_loop,
        train_loop_config={
            "chips": chips, "runs": runs, "seed": seed,
            "platform": PLATFORM, "kernel_marker": KERNEL_MARKER,
            "model": GPT2_MODEL, "batch": GPT2_BATCH, "seq": GPT2_SEQ,
            "steps": GPT2_STEPS,
        },
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=True, tpus_per_worker=chips
        ),
        run_config=RunConfig(name=name),
    ).fit()
    reports = result.metrics_dataframe or []
    for r in reports:
        r = {k: v for k, v in r.items() if k != "_timestamp"}
        kind = r.pop("kind", "?")
        if kind == "step":
            say(f"{name}: [{r['run']}] step {r['step']} loss "
                f"{r['loss']:.4f} wall {r['seconds']:.3f} s")
        else:
            say(f"{name}: {kind} {json.dumps(r)}")
    if result.error is not None:
        platform = next(
            (r["platform"] for r in reports if r.get("kind") == "devices"),
            None,
        )
        kind = WrongPlatform if platform not in (None, PLATFORM) else SmokeFailure
        raise kind(
            f"trainer failed (worker platform: {platform}): {result.error}"
        )
    return reports


def _check_losses(reports: list, run: str) -> list:
    losses = [
        r["loss"] for r in reports
        if r.get("kind") == "step" and r["run"] == run
    ]
    if len(losses) != GPT2_STEPS:
        raise SmokeFailure(f"{run}: {len(losses)} steps reported: {losses}")
    if not all(math.isfinite(x) for x in losses):
        raise SmokeFailure(f"{run}: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise SmokeFailure(f"{run}: loss did not fall: {losses}")
    return losses


def _device_of(reports: list) -> dict:
    d = next(r for r in reports if r.get("kind") == "devices")
    return {
        "platform": d["platform"], "kind": d["device_kind"],
        "count": d["device_count"],
    }


def _compile_seconds(reports: list) -> dict:
    return {
        r["run"]: round(r["compile_seconds"], 3)
        for r in reports if r.get("kind") == "compiled"
    }


def phase_train(seed: int, summary: dict) -> dict:
    say(f"train: GPT-2-124M 12L/12H/768 vocab 50304 bf16 flash no-remat, "
        f"AdamW, B={GPT2_BATCH} x S={GPT2_SEQ}, {GPT2_STEPS} steps, "
        f"one worker x one chip")
    runs = [{"name": "one-chip", "mesh": {"dp": 1}, "n_devices": 1}]
    reports = _fit_gpt2(1, runs, seed, "train")
    losses = _check_losses(reports, "one-chip")
    summary["train_compile_seconds"] = _compile_seconds(reports)
    say(f"train: ok, losses {[round(x, 4) for x in losses]}")
    return _device_of(reports)


def phase_serve(seed: int, summary: dict) -> None:
    import jax.numpy as jnp  # a dtype for the config; opens no backend
    import numpy as np

    from ray_tpu import serve
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.llm import LlamaDeployment

    full = LlamaConfig.mistral_7b()
    cfg = LlamaConfig.mistral_7b(param_dtype=jnp.bfloat16, **SERVE_MODEL)
    per_layer = (
        2 * cfg.embed_dim * cfg.num_heads * cfg.head_dim
        + 2 * cfg.embed_dim * cfg.num_kv_heads * cfg.head_dim
        + 3 * cfg.embed_dim * cfg.mlp_dim + 2 * cfg.embed_dim
    )
    n_params = (
        cfg.num_layers * per_layer + 2 * cfg.vocab_size * cfg.embed_dim
        + cfg.embed_dim
    )
    say(f"serve: Mistral-7B widths (hidden {cfg.embed_dim}, "
        f"{cfg.num_heads}Q/{cfg.num_kv_heads}KV x {cfg.head_dim}, MLP "
        f"{cfg.mlp_dim}, vocab {cfg.vocab_size}, window "
        f"{cfg.sliding_window}), bf16 weights from seed {seed}; CUT: depth "
        f"{full.num_layers} -> {cfg.num_layers} layers "
        f"({n_params / 1e9:.2f} B params, {2 * n_params / 2**30:.1f} GiB), "
        f"max_slots={SERVE_SLOTS}, max_len={SERVE_MAX_LEN}")
    app = LlamaDeployment.options(
        ray_actor_options={"num_tpus": 1}
    ).bind(
        config=cfg, max_slots=SERVE_SLOTS, max_len=SERVE_MAX_LEN, seed=seed
    )
    handle = serve.run(app, name="chip_smoke_llm", route_prefix=None)
    t0 = time.monotonic()
    stats = handle.options(method_name="stats").remote().result(
        timeout_s=REPLICA_START_TIMEOUT_S
    )
    say(f"serve: replica up in {time.monotonic() - t0:.1f} s: "
        f"{json.dumps(stats)}")
    if stats["platform"] != PLATFORM:
        raise WrongPlatform(
            f"replica was leased a TPU chip but reports platform "
            f"{stats['platform']!r}"
        )

    rng = np.random.default_rng(seed)
    prompts = [
        rng.integers(0, cfg.vocab_size, n).tolist() for n in SERVE_PROMPT_LENS
    ]

    def generate(prompt, out, i):
        t0 = time.monotonic()
        first = None
        toks = []
        try:
            for tok in handle.options(
                method_name="generate", stream=True
            ).remote(prompt, max_new_tokens=SERVE_NEW_TOKENS):
                if first is None:
                    first = time.monotonic() - t0
                toks.append(tok)
            out[i] = (toks, first, time.monotonic() - t0)
        except Exception as e:  # noqa: BLE001 — re-raised by the caller
            out[i] = e

    def run_together(batch):
        out = [None] * len(batch)
        threads = [
            threading.Thread(target=generate, args=(p, out, i), daemon=True)
            for i, p in enumerate(batch)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(REQUEST_TIMEOUT_S)
        for i, r in enumerate(out):
            if r is None:
                raise SmokeFailure(f"request {i} did not finish in "
                                   f"{REQUEST_TIMEOUT_S:.0f} s")
            if isinstance(r, Exception):
                raise SmokeFailure(f"request {i} failed: {r!r}")
        return out

    together = run_together(prompts)
    for i, (toks, first, total) in enumerate(together):
        say(f"serve: request {i} prompt {len(prompts[i])} tokens -> {toks} "
            f"(first token {first:.2f} s, all {total:.2f} s)")
        if len(toks) != SERVE_NEW_TOKENS or not all(
            isinstance(t, int) and 0 <= t < cfg.vocab_size for t in toks
        ):
            raise SmokeFailure(
                f"request {i}: want {SERVE_NEW_TOKENS} in-vocabulary "
                f"tokens, got {toks}"
            )
    after = handle.options(method_name="stats").remote().result(timeout_s=60)
    say(f"serve: compiles while serving: "
        f"{after['compiles']['count'] - stats['compiles']['count']} in "
        f"{after['compiles']['seconds'] - stats['compiles']['seconds']:.2f} s "
        f"(replica start: {stats['compiles']['count']} in "
        f"{stats['compiles']['seconds']:.2f} s); jitted programs "
        f"{after['programs']}, persistent-cache hits "
        f"{after['compiles']['cache_hits']}, peak bytes "
        f"{after['peak_bytes_in_use']}")
    alone = run_together(prompts[1:2])[0][0]
    if alone != together[1][0]:
        raise SmokeFailure(
            f"prompt 1 alone gave {alone}, in the batch {together[1][0]}"
        )
    say("serve: ok, prompt 1 sent again alone returned the same "
        f"{SERVE_NEW_TOKENS} tokens")
    summary["serve_compiles"] = after["compiles"]
    serve.delete("chip_smoke_llm")
    serve.shutdown()


def phase_mesh(seed: int, summary: dict) -> dict:
    say(f"mesh: the GPT-2-124M loop on fsdp=2 x tp=2 over four chips of "
        f"one worker (global batch {GPT2_BATCH}), then on one of its "
        f"chips from the same seed")
    runs = [
        {"name": "fsdp2xtp2", "mesh": {"dp": 1, "fsdp": 2, "tp": 2},
         "n_devices": 4},
        {"name": "one-chip", "mesh": {"dp": 1}, "n_devices": 1},
    ]
    reports = _fit_gpt2(4, runs, seed, "mesh")
    sharded = _check_losses(reports, "fsdp2xtp2")
    single = _check_losses(reports, "one-chip")
    worst = max(abs(a - b) for a, b in zip(sharded, single))
    say(f"mesh: max |loss difference| {worst:.5f} (tolerance "
        f"{MESH_LOSS_TOL}, bf16)")
    if worst > MESH_LOSS_TOL:
        raise SmokeFailure(
            f"mesh losses {sharded} differ from one-chip losses {single} "
            f"by {worst} > {MESH_LOSS_TOL}"
        )
    comp = next(
        r for r in reports
        if r.get("kind") == "compiled" and r["run"] == "fsdp2xtp2"
    )
    if comp["mesh_warnings"]:
        raise SmokeFailure(f"mesh construction warned: {comp['mesh_warnings']}")
    held, total = comp["bytes_in_use_after_init"], comp["state_bytes"]
    # parameters and optimizer state on all four chips, none holding the lot
    if None in held or min(held) < 0.5 * max(held) or max(held) > 0.5 * total:
        raise SmokeFailure(
            f"state of {total} bytes is not spread over the four chips: "
            f"bytes in use per device {held}"
        )
    say(f"mesh: ok, {total} state bytes held as {held} per device")
    summary["mesh_compile_seconds"] = _compile_seconds(reports)
    return _device_of(reports)


def phase_leases(per_actor: int, n_actors: int, summary: dict) -> None:
    """``n_actors`` actors of ``per_actor`` chips each, alive at once:
    each opens exactly its own chips, together they hold all four."""
    import ray_tpu

    name = f"leases {n_actors}x{per_actor}"
    say(f"{name}: {n_actors} actors x num_tpus={per_actor}, alive at once")
    probe_cls = ray_tpu.remote(_LeaseProbe)
    actors = [
        probe_cls.options(num_tpus=per_actor).remote()
        for _ in range(n_actors)
    ]
    got, errors = [], []
    try:
        refs = [a.probe.remote() for a in actors]
        for i, ref in enumerate(refs):
            try:
                got.append(ray_tpu.get(ref, timeout=300))
            except Exception as e:  # noqa: BLE001 — all reported below
                errors.append(f"actor {i}: {(str(e).splitlines() or [''])[0]}")
    finally:
        for a in actors:
            ray_tpu.kill(a)
    for g in got:
        say(f"{name}: {json.dumps(g)}")
    if errors:
        raise SmokeFailure("; ".join(errors))
    chips, files = [], []
    for g in got:
        if g["platform"] != PLATFORM or g["n_devices"] != per_actor:
            raise SmokeFailure(
                f"actor leased {per_actor} chip(s) sees "
                f"{g['n_devices']} {g['platform']} device(s)"
            )
        if not g["matmul_ok"] or g["sum_across_chips"] != per_actor:
            raise SmokeFailure(f"wrong arithmetic on pid {g['pid']}: {g}")
        chips += g["leased_chips"].split(",")
        files += g["open_device_files"]
    if sorted(chips) != ["0", "1", "2", "3"]:
        raise SmokeFailure(
            f"leased chips {chips} are not a partition of the four"
        )
    if len(files) != len(set(files)):
        raise SmokeFailure(f"two actors opened one device file: {files}")
    say(f"{name}: ok")
    summary[name] = "ok"


def phase_pipeline(seed: int, summary: dict) -> dict:
    """Two steps of the MPMD pipeline: 2 stages x 2 lanes, every stage
    actor its own process on its own chip.  Not compared with the
    single-gang reference (that runs in one process, on the host)."""
    from ray_tpu.models import gpt2
    from ray_tpu.train.pipeline import (
        PipelineConfig,
        PipelineTrainer,
        synthetic_batches,
    )

    model = gpt2.GPTConfig.gpt2_124m(**PIPELINE_MODEL)
    pc = PipelineConfig(
        model_config=model, n_stages=2, dp=2, n_micro=4, micro_batch=2,
        seq_len=GPT2_SEQ, optimizer={"name": "adamw", "lr": 3e-4},
        seed=seed, name="chip_smoke_pp",
    )
    say(f"pipeline: GPT-2-124M cut in {pc.n_stages} stages x {pc.dp} lanes, "
        f"{pc.n_micro} micro-batches of {pc.micro_batch} x {pc.seq_len}, "
        f"p2p handoff, 2 steps, one chip per stage actor")
    trainer = PipelineTrainer(pc, bundle={"CPU": 1, "TPU": 1})
    try:
        trainer.start()
        for info in trainer.stage_info:
            say(f"pipeline: stage actor {json.dumps(info)}")
        losses = trainer.train(synthetic_batches(pc, 2))
    finally:
        trainer.shutdown()
    say(f"pipeline: losses {losses}")
    if any(i["platform"] != PLATFORM for i in trainer.stage_info):
        raise SmokeFailure("a stage actor is not on the chip")
    if sorted(i["tpu_chips"] for i in trainer.stage_info) != ["0", "1", "2", "3"]:
        raise SmokeFailure("stage actors do not hold one chip each")
    # random weights, random tokens: the loss starts at about ln(vocab)
    want = math.log(model.vocab_size)
    if not all(math.isfinite(x) and abs(x - want) < 1.0 for x in losses):
        raise SmokeFailure(f"losses {losses} are not near ln(vocab)={want:.2f}")
    say("pipeline: ok")
    summary["pipeline"] = "ok"
    return {
        "platform": PLATFORM, "kind": trainer.stage_info[0]["device_kind"],
        "count": len(trainer.stage_info),
    }


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def _descendants(root: int) -> list:
    children: dict = {}
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/stat") as f:
                ppid = int(f.read().rsplit(")", 1)[1].split()[1])
        except (OSError, ValueError, IndexError):
            continue
        children.setdefault(ppid, []).append(int(pid))
    out, todo = [], [root]
    while todo:
        for kid in children.get(todo.pop(), []):
            out.append(kid)
            todo.append(kid)
    return out


def _stop_everything(session_dir, keep_logs: bool) -> None:
    """Shut the cluster down and leave no process behind; on failure
    keep the session's logs where the chip tool brings them back."""
    started = _descendants(os.getpid())
    try:
        import ray_tpu

        if ray_tpu.is_initialized():
            t = threading.Thread(target=ray_tpu.shutdown, daemon=True)
            t.start()
            t.join(20)
    except Exception:  # noqa: BLE001 — the kill below is the backstop
        traceback.print_exc()
    for pid in started:
        try:
            os.kill(pid, signal.SIGKILL)
        except OSError:
            pass
    if keep_logs and session_dir and os.path.isdir(session_dir):
        dest = os.path.join(os.path.dirname(RUN_LOG), "chip_smoke_logs")
        shutil.rmtree(dest, ignore_errors=True)
        os.makedirs(dest, exist_ok=True)
        for path in glob.glob(os.path.join(session_dir, "*.log")):
            shutil.copy(path, dest)
            with open(path, errors="replace") as f:
                tail = f.read()[-3000:]
            print(f"---- {os.path.basename(path)} (tail) ----\n{tail}",
                  flush=True)


_FINISHING = threading.Lock()


def _finish(ok: bool, device, error, session_dir, summary: dict) -> None:
    """Stop everything, print the last line, exit.  Never returns, and
    runs once: the deadline thread and the main thread may both get here."""
    _FINISHING.acquire()
    _stop_everything(session_dir, keep_logs=not ok)
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        ok, error = False, (
            "the parent process initialised a jax backend "
            f"({error or 'all phases had passed'})"
        )
    summary.update(ok=ok, device=device, error=error)
    os.makedirs(os.path.dirname(RUN_LOG), exist_ok=True)
    with open(RUN_LOG, "a") as f:
        f.write(json.dumps(summary) + "\n")
    last = {"ok": True, "device": device} if ok else {
        "ok": False, "error": error, "device": device,
    }
    print(json.dumps(last), flush=True)
    os._exit(0 if ok else 1)


def _previous_run() -> dict:
    try:
        with open(RUN_LOG) as f:
            return json.loads(f.read().strip().splitlines()[-1])
    except (OSError, IndexError, ValueError):
        return {}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train and serve on one chip (default); "
                         "4: only the mesh and leases phases")
    ap.add_argument("--pipeline", action="store_true",
                    help="with --chips 4: instead, two steps of the MPMD "
                         "pipeline, 2 stages x 2 lanes on a chip each")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.pipeline and args.chips != 4:
        ap.error("--pipeline needs --chips 4")

    state = {"session_dir": None, "device": None, "phase": "start"}
    summary = {"chips": args.chips, "seed": args.seed, "started": time.time()}

    def on_deadline():
        _finish(False, state["device"],
                f"deadline of {DEADLINE_S[args.chips]:.0f} s passed in "
                f"phase {state['phase']}", state["session_dir"], summary)

    watchdog = threading.Timer(DEADLINE_S[args.chips], on_deadline)
    watchdog.daemon = True
    watchdog.start()
    try:
        gxx = shutil.which("g++")
        say("toolchain: " + (
            subprocess.run([gxx, "--version"], capture_output=True,
                           text=True).stdout.splitlines()[0]
            if gxx else "no g++ on PATH"
        ))
        prev = _previous_run()
        import ray_tpu
        from ray_tpu.util import compile_cache

        say(f"compile cache: "
            f"{os.environ.get(compile_cache.ENV_VAR) or compile_cache.default_dir()}"
            f" ({compile_cache.ENV_VAR} "
            f"{'set' if os.environ.get(compile_cache.ENV_VAR) else 'unset'})")
        state["phase"] = "init"
        info = ray_tpu.init()
        state["session_dir"] = info["session_dir"]
        total = ray_tpu.cluster_resources()
        n_tpu = int(total.get("TPU", 0))
        say(f"chips found: {n_tpu} by {info['tpu_detected_by']} "
            f"(cluster resources {json.dumps(total)})")
        if n_tpu < args.chips:
            raise SmokeFailure(
                f"need {args.chips} TPU chip(s), ray_tpu.init() found "
                f"{n_tpu} (detection: {info['tpu_detected_by']})"
            )
        if args.chips == 1:
            phases = [
                ("train", lambda: phase_train(args.seed, summary)),
                ("serve", lambda: phase_serve(args.seed, summary)),
            ]
        elif args.pipeline:
            phases = [
                ("pipeline", lambda: phase_pipeline(args.seed, summary)),
            ]
        else:
            phases = [
                ("mesh", lambda: phase_mesh(args.seed, summary)),
                ("leases 4x1", lambda: phase_leases(1, 4, summary)),
                ("leases 2x2", lambda: phase_leases(2, 2, summary)),
            ]
        # every phase runs, whatever became of the one before: a failed
        # run should say all that is wrong, not only the first thing
        failed = []
        for name, phase in phases:
            state["phase"] = name
            try:
                device = phase()
            except WrongPlatform:
                raise
            except Exception as e:  # noqa: BLE001 — reported, run fails
                traceback.print_exc()
                say(f"{name}: FAILED: {type(e).__name__}: {e}")
                failed.append(
                    f"{name}: {type(e).__name__}: "
                    + (str(e).splitlines() or [""])[0]
                )
            else:
                state["device"] = state["device"] or device
        if failed:
            raise SmokeFailure("; ".join(failed))
        for key in ("train_compile_seconds", "mesh_compile_seconds",
                    "serve_compiles"):
            if key in summary:
                say(f"{key}: this run {json.dumps(summary[key])}, previous "
                    f"run in this checkout {json.dumps(prev.get(key))}")
        state["phase"] = "done"
    except BaseException as e:  # noqa: BLE001 — every failure ends in _finish
        traceback.print_exc()
        watchdog.cancel()
        _finish(False, state["device"],
                f"{type(e).__name__}: " + (str(e).splitlines() or [""])[0],
                state["session_dir"], summary)
    watchdog.cancel()
    _finish(True, state["device"], None, state["session_dir"], summary)


if __name__ == "__main__":
    main()
