"""Job kind ``serve_loop``: the ``serve_llm`` job for a LOOPED decoder — the
whole stack of blocks run ``total_ut_steps`` times a token with shared
weights, the final norm behind every pass, K and V of every (pass, layer), a
four-norm sandwich block and an exit gate (Ouro-2.6B through ``LlamaConfig``).

Same path — ``serve.run`` of a decode replica, requests through the
deployment handle's streaming path, ``LLMEngine`` on the chip — same load,
same stamps, same facts keys: ``run`` IS ``serve_llm.run`` with what that
file hard-wires exchanged (``_exchanged``), as ``jobs/serve_hybrid.py`` does
it and with its ``_InTurn`` for the prompts' order.  What differs is what a
token costs (four streams of the weights, 192 cache layers) and what can go
wrong: a pass too few, a norm between the passes forgotten, the cache layer
of the wrong pass.  So the comparison that decides ``correct``
(``LoopReplica.check_reference``, ``system_run``, ``against_reference``,
``passes``) runs, in the engine's own cache and with the two executables the
window drives (``llama.prefill_into_slot`` / ``llama.decode_step_rowwise``):

(i)   a check prompt of every length of the mix (64 and 128 ids), each
      prefilled into a slot of its own (not the first ones), then
      ``check_steps`` greedy steps of the FULL batch, and every logits row
      of it — the prefill's and each step's, 9 a row — against the float32
      reference's full forward over [prompt; tokens so far]
      (``chipbench/reference/ouro.py``): a key of another pass's cache layer,
      a pass left out or a norm on the wrong side is an error of the order of
      the logits;
(ii)  the exit gate, which changes no logit at the published threshold:
      what the cache's ``loop_exit_mass`` gained over those calls — the sum
      over (row, call) of the exit mass before the last pass, idle rows too —
      against the reference's ``1 - p_T`` at the same positions (an idle row
      steps token 0 at position 0);
(iii) ``loop_passes``: exactly ``total_ut_steps`` a (row, call).

``serve_llm.run`` keeps no counters of the window, so the replica writes them
beside the run itself, as ``jobs/serve_hybrid.py``'s does: every ``stats()``
call after the reference check appends its counters to a file of the run's
own directory, and ``run`` reads the window as the difference of the call
after warm-up and the call at the window's end.

The module asks the program for its fields when it is IMPORTED, which
``run.py`` does before it starts a cluster: a program without them (a commit
from before the loop) fails there, at once, and no chip is leased.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
from unittest import mock

from chipbench import loadgen, loop_trace
from chipbench.jobs import serve_hybrid, serve_llm
from chipbench.jobs.serve_llm import BenchReplica
from ray_tpu.models import hf
from ray_tpu.models.llama import LlamaConfig

LOOP_FIELDS = ("loop_passes", "sandwich_norm", "early_exit_threshold")
_missing = set(LOOP_FIELDS) - {f.name for f in dataclasses.fields(LlamaConfig)}
_missing |= {n for n in ("ouro_fields",) if not hasattr(hf, n)}
if _missing:
    raise RuntimeError(
        f"this program has no {sorted(_missing)} (models/llama.py, models/hf.py): it "
        "cannot run a looped configuration"
    )

#: what ``_counted`` keeps of a ``stats()`` call
COUNTED = ("loop_", "kv_keys_", "decode_steps_total", "rows_stepped_total", "admitted_total")

REHEARSAL_MODEL = {
    "hidden_size": 64, "intermediate_size": 160, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 512, "total_ut_steps": 3,
}


def loop_config(cfg: dict):
    """The configuration file's keys -> the program's LlamaConfig, through
    the program's own reading of the published keys (``hf.ouro_fields``)."""
    import jax.numpy as jnp

    if cfg["model_type"] != "ouro" or set(cfg["layer_types"]) != {"full_attention"}:
        raise RuntimeError("the serve_loop job runs an Ouro configuration, every layer "
                           "full attention")
    return LlamaConfig(
        vocab_size=cfg["vocab_size"], max_seq_len=cfg["max_position_embeddings"],
        num_layers=cfg["num_hidden_layers"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], embed_dim=cfg["hidden_size"],
        head_dim=cfg["head_dim"], mlp_dim=cfg["intermediate_size"],
        rms_eps=cfg["rms_norm_eps"], dtype=getattr(jnp, cfg["dtype"]),
        param_dtype=getattr(jnp, cfg["param_dtype"]),
        tie_embeddings=cfg["tie_word_embeddings"], **hf.ouro_fields(cfg),
    )


def spec_of(config, **bent):
    """What the reference needs beside the parameter tree; ``bent``: one of
    its pieces left out."""
    from chipbench.reference import ouro

    return ouro.Spec(config.loop_passes, float(config.rope_theta),
                     float(config.rms_eps))._replace(**bent)


def model_facts(cfg: dict) -> dict:
    """The configuration's numbers as the readers' ``facts["model"]``
    (``chipbench/loop_cost.py`` counts from them)."""
    return {k: v for k, v in cfg.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


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
    return jax.block_until_ready(
        jax.jit(functools.partial(llama.init, config=loop_config(cfg)))(
            jax.random.key(seed % (2**31))))


def slot_of(r: int, slots: int) -> int:
    """The cache row check prompt ``r`` is served in: not the first ones,
    and not side by side (16 slots: 3, 8; the rehearsal's 4: 3, 0)."""
    return (5 * r + 3) % slots


def system_run(params, config, cache, slots: int, prompts, steps: int):
    """Each of ``prompts`` into its cache row (``slot_of``) by
    ``llama.prefill_into_slot`` and ``steps`` greedy calls of
    ``llama.decode_step_rowwise`` over all ``slots`` rows — THE TWO EXECUTABLES
    THE ENGINE SERVES WITH, in the cache it then serves from; the rows without
    a check prompt idle at token 0, position 0.  -> (cache, {"rows": [per
    check row {"prompt": its ids, "ids": they and the tokens fed behind them,
    "logits": (1 + steps, V) the prefill's row and each step's}],
    "loop_passes": [passes run, (row, call)s] and "loop_exit_mass": what the
    cache's two running totals gained over these calls, "calls": [(rows,
    idle rows) of each call]})."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama

    def totals(cache):
        return (np.asarray(cache["loop_passes"]).astype(np.int64),
                float(np.asarray(cache["loop_exit_mass"], np.float64)))

    before = totals(cache)
    rows, calls = [], []
    for r, prompt in enumerate(prompts):
        logits, cache = llama.prefill_into_slot(
            params, jnp.asarray([prompt], jnp.int32), cache,
            jnp.int32(slot_of(r, slots)), config)
        rows.append({"prompt": list(prompt), "ids": list(prompt), "logits": [logits[0]]})
        calls.append((1, 0))
    for _ in range(steps):
        tokens, pos = np.zeros((slots,), np.int32), np.zeros((slots,), np.int32)
        for r, row in enumerate(rows):
            row["ids"].append(int(jnp.argmax(row["logits"][-1])))
            tokens[slot_of(r, slots)] = row["ids"][-1]
            pos[slot_of(r, slots)] = len(row["ids"]) - 1
        logits, cache = llama.decode_step_rowwise(
            params, jnp.asarray(tokens), cache, jnp.asarray(pos), config)
        for r, row in enumerate(rows):
            row["logits"].append(logits[slot_of(r, slots)])
        calls.append((slots, slots - len(rows)))
    for row in rows:
        row["logits"] = np.asarray(jnp.stack(row["logits"]))
    after = totals(cache)
    return cache, {"rows": rows, "calls": calls,
                   "loop_passes": (after[0] - before[0]).tolist(),
                   "loop_exit_mass": after[1] - before[1]}


def against_reference(params, config, out: dict, shared_cache: bool = False, **bent) -> dict:
    """What ``system_run`` recorded against the float32 reference's full
    forward over each row's [prompt; tokens fed].  ``bent``: the reference
    with one piece left out (``spec_of``); ``shared_cache``: with ONE cache
    layer a parameter layer from each row's prompt on.  {"err": rms and max
    of |logits - reference| / std over every compared row, "exit_mass" the
    system's count and "exit_mass_reference" the reference's ``1 - p_T``
    summed over the same (row, call)s — the check rows' compared positions and
    the idle rows' token 0 at position 0 — "exit_mass_rel" their distance over
    the reference's, "loop_passes_off": the passes counted less ``passes`` a
    (row, call), which is 0."""
    import jax.numpy as jnp
    import numpy as np

    from chipbench.reference import errors
    from chipbench.reference import ouro as reference

    want, mass = [], 0.0
    for row in out["rows"]:
        n = row["logits"].shape[0]
        first = len(row["prompt"]) - 1
        spec = spec_of(config, **bent)
        if shared_cache:
            spec = spec._replace(shared_cache_from=len(row["prompt"]))
        logits, lam = reference.forward(
            params, jnp.asarray(row["ids"], jnp.int32), spec, list(range(first, first + n)))
        want.append(np.asarray(logits))
        mass += float((1.0 - reference.exit_distribution(lam)[-1]).sum())
    idle = sum(n for _rows, n in out["calls"])
    if idle:
        _, lam = reference.forward(params, jnp.zeros((1,), jnp.int32), spec_of(config, **bent), [0])
        mass += idle * float(1.0 - reference.exit_distribution(lam)[-1, 0])
    row_calls = sum(rows for rows, _n in out["calls"])
    return {
        "err": errors(np.concatenate([row["logits"] for row in out["rows"]]),
                      np.concatenate(want)),
        "exit_mass": out["loop_exit_mass"], "exit_mass_reference": mass,
        "exit_mass_rel": abs(out["loop_exit_mass"] - mass) / mass,
        "loop_passes_off": int(abs(out["loop_passes"][0] - config.loop_passes * row_calls)
                               + abs(out["loop_passes"][1] - row_calls)),
    }


def passes(got: dict, tolerance: dict) -> bool:
    """The comparison that decides ``correct``: every compared row's logits
    within rms and max, the gate's exit mass within its relative limit, and
    the passes counted exactly (the limits and the readings they lie
    between: the configuration file's ``reference_tolerance.why``)."""
    from chipbench.reference import within

    return bool(within(got["err"], tolerance)
                and got["exit_mass_rel"] <= tolerance["exit_mass_rel_max"]
                and got["loop_passes_off"] == 0)


def compare(params, config, cache, slots: int, seed: int, prompt_lens, steps: int):
    from chipbench.jobs.serve_dsa import check_prompt

    prompts = [check_prompt(config, seed + r, n) for r, n in enumerate(prompt_lens)]
    cache, out = system_run(params, config, cache, slots, prompts, steps)
    return cache, against_reference(params, config, out)


class LoopReplica(BenchReplica):
    """``BenchReplica`` compared with the Ouro reference, whose ``stats()``
    also keeps the counters of the run's window."""

    _counters_file = None

    async def stats(self) -> dict:
        out = await super().stats()
        if self._counters_file:
            with open(self._counters_file, "a") as f:
                f.write(json.dumps(_counted(out)) + "\n")
        return out

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
        idle = jnp.zeros((eng.max_slots,), jnp.int32)
        decode = llama.decode_step_rowwise.lower(
            eng.params, idle, eng.cache, idle, cfg).compile()
        temp = decode.memory_analysis().temp_size_in_bytes
        if tolerance.get("scope_file"):
            wrote = {"prompt_lens": tolerance["scope_prompt_lens"],
                     "passes": cfg.loop_passes, "versions": {
                "decode_step_rowwise": [loop_trace.version(decode.as_text())],
                "prefill_into_slot": [
                    loop_trace.version(llama.prefill_into_slot.lower(
                        eng.params, jnp.zeros((1, n), jnp.int32), eng.cache,
                        jnp.int32(0), cfg,
                    ).compile().as_text())
                    for n in tolerance["scope_prompt_lens"]
                ],
            }}
            with open(tolerance["scope_file"], "w") as f:
                json.dump(wrote, f)
        print(f"[serve_loop] reference check at {lens} + {steps} steps: {got}", flush=True)
        if tolerance.get("counters_file"):
            with open(tolerance["counters_file"], "w") as f:
                f.write(json.dumps({"check": got}) + "\n")
            self._counters_file = tolerance["counters_file"]
        return {**got, "tol": tolerance, "ok": passes(got, tolerance),
                "live_bytes": live, "decode_temp_bytes": temp,
                "cache_bytes": {k: int(np.prod(v.shape)) * v.dtype.itemsize
                                for k, v in eng.cache.items()}}


def _counted(stats: dict) -> dict:
    return {k: v for k, v in stats.items() if k.startswith(COUNTED)}


def _window(counters_file: str) -> dict:
    """The comparison's readings and the window's counters as the readers'
    facts: the file's first line is the check's, the ``stats()`` call at the
    window's end minus the call after warm-up the next two (``serve_llm.run``
    asks once more, after the drain)."""
    with open(counters_file) as f:
        (check, *calls) = [json.loads(line) for line in f]
    if len(calls) < 2:
        raise RuntimeError(f"the replica wrote down {len(calls)} stats() call(s), not "
                           "the one after warm-up and the one at the window's end")
    before, after = calls[0], calls[1]
    delta = {k: after[k] - before[k] for k in after}
    steps = delta.pop("decode_steps_total")
    if steps <= 0 or delta.get("loop_row_steps", 0) <= 0:
        raise RuntimeError(f"no decode step or no pass was counted in the window: {delta}")
    check = check["check"]
    return {"decode_steps_in_window": int(steps),
            "prefills_in_window": int(delta.pop("admitted_total")), **delta,
            "reference_exit_mass_rel": check["exit_mass_rel"],
            "reference_loop_passes_off": check["loop_passes_off"]}


def _exchanged() -> dict:
    """What ``run`` puts in place of ``serve_llm``'s own while its ``run``
    runs."""
    return {"llama_config": loop_config, "BenchReplica": LoopReplica,
            "make_weights": make_weights, "REHEARSAL_MODEL": REHEARSAL_MODEL,
            "loadgen": serve_hybrid._InTurn(loadgen)}


_lost = [n for n in _exchanged() if not hasattr(serve_llm, n)]
if _lost:
    raise RuntimeError(
        f"jobs/serve_loop.py exchanges {_lost} inside serve_llm.run, and "
        "jobs/serve_llm.py no longer has them"
    )


def run(ctx: dict) -> dict:
    """``serve_llm.run`` with its hard-wired parts exchanged; then the
    window's counters and, for a traced run, the loop's device time by scope
    and by pass."""
    if ctx["traffic"]["loop"] != "closed":
        raise RuntimeError("the serve_loop job runs closed-loop mixes only")
    tolerance = dict(ctx["config"]["reference_tolerance"])
    lens = loadgen.prompt_lengths(ctx["traffic"])
    if ctx["rehearse"]:
        lens = [16, 32]
        tolerance.update(check_steps=4)
    tolerance["check_prompt_lens"] = lens
    os.makedirs(ctx["storage_dir"], exist_ok=True)
    tolerance["counters_file"] = os.path.join(ctx["storage_dir"], "loop_counters.jsonl")
    if ctx["trace"]:
        tolerance.update(
            scope_file=os.path.join(ctx["trace_dir"], loop_trace.SCOPE_FILE),
            scope_prompt_lens=lens,
        )
    config = dict(ctx["config"], reference_tolerance=tolerance)
    with mock.patch.multiple(serve_llm, **_exchanged()):
        job = serve_llm.run(dict(ctx, config=config))
    model = dict(config, **REHEARSAL_MODEL) if ctx["rehearse"] else config
    job["facts"].update(_window(tolerance["counters_file"]))
    job["facts"]["model"] = model_facts(model)
    if ctx["trace"] and os.path.isdir(ctx["trace_dir"]):
        job["facts"].update(loop_trace.facts(ctx["trace_dir"]))
        split = job["facts"].get("loop_pass_decode_device_s_by_pass")
        if split:
            print(f"[serve_loop] decode device seconds under loop_pass, by pass: {split}",
                  flush=True)
    return job
