"""Job kind ``serve_hybrid``: the ``serve_llm`` job for a decoder whose
layers are of two mixer kinds — the gated delta rule, whose state is one
matrix a head and row, beside full attention over a K/V cache
(Olmo-Hybrid-7B through ``LlamaConfig``).

Same path — ``serve.run`` of a decode replica, requests through the
deployment handle's streaming path, ``LLMEngine`` on the chip — same load,
same stamps, same facts keys: ``run`` IS ``serve_llm.run`` with what that
file hard-wires exchanged (``_exchanged``), as ``jobs/serve_dsa.py`` does it
to ``serve_moe.run`` and with its ``_InTurn`` for the prompts' order.  What
differs is what a slot holds: a recurrent state that no length describes.
So the comparison that decides ``correct`` (``HybridReplica.
check_reference``, ``system_run``, ``against_reference``, ``passes``) runs,
in the engine's own cache and with the two executables the window drives
(``llama.prefill_into_slot`` / ``llama.decode_step_rowwise``):

(i)   check prompts of every length of the mix and one that is no multiple
      of the chunk (1,024; 2,048; 1,531), each prefilled into its own slot,
      then ``check_steps`` steps of the FULL batch, and every logits row of
      it — the prefill's and each step's — against the float32 reference's
      full forward over [prompt; tokens so far], the recurrence token by
      token (``chipbench/reference/olmo_hybrid.py``): a state that kept
      anything of the padding, a tail shifted wrongly or a K/V row of the
      wrong layer is an error of the order of the logits;
(ii)  the first check row is a slot that SERVED ANOTHER REQUEST FIRST — a
      prompt prefilled into it and stepped twice before the check prompt is
      admitted: a prefill that started from what the slot held would carry
      that request's state into this one's logits.

``serve_llm.run`` keeps no counters of the window, so the replica writes
them beside the run itself: every ``stats()`` call after the reference check
appends its counters to a file of the run's own directory (``_counted``),
and ``run`` reads the window as the difference of the call after warm-up and
the call at the window's end.

The module asks the program for its fields when it is IMPORTED, which
``run.py`` does before it starts a cluster: a program without them (a commit
from before the recurrent layers) fails there, at once, and no chip is
leased.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
from unittest import mock

from chipbench import gdn_trace, loadgen
from chipbench.jobs import serve_dsa, serve_llm
from chipbench.jobs.serve_llm import BenchReplica
from ray_tpu.models.llama import LlamaConfig

HYBRID_FIELDS = ("layer_types", "linear_num_heads", "linear_key_head_dim",
                 "linear_value_head_dim", "linear_conv_kernel", "linear_neg_eigval",
                 "linear_chunk", "post_norm")
_missing = set(HYBRID_FIELDS) - {f.name for f in dataclasses.fields(LlamaConfig)}
if _missing:
    raise RuntimeError(
        f"this program's LlamaConfig has no {sorted(_missing)}: it cannot run a "
        "configuration with linear-attention layers"
    )

#: the prompt no multiple of the chunk; the one served in the first check
#: row's slot before it, and the steps it is given there
RAGGED_PROMPT_LEN = 1531
EARLIER_STEPS = 2
#: what ``_counted`` keeps of a ``stats()`` call
COUNTED = ("gdn_", "kv_keys_", "decode_steps_total", "rows_stepped_total", "admitted_total")

REHEARSAL_MODEL = {
    "hidden_size": 64, "intermediate_size": 160, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_hidden_layers": 8, "vocab_size": 512,
    "linear_num_key_heads": 4, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 16,
}


def hybrid_config(cfg: dict):
    """The configuration file's keys -> the program's LlamaConfig.  The
    layers that are run are the first ``num_hidden_layers`` of
    ``layer_types``."""
    import jax.numpy as jnp

    if cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]:
        raise RuntimeError("the program runs as many key heads as value heads")
    if cfg["rope_parameters"]["rope_theta"] is not None or cfg["attention_bias"]:
        raise RuntimeError("the full layers are run without rotation and without bias")
    return LlamaConfig(
        vocab_size=cfg["vocab_size"], max_seq_len=cfg["max_position_embeddings"],
        num_layers=cfg["num_hidden_layers"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], embed_dim=cfg["hidden_size"],
        mlp_dim=cfg["intermediate_size"], rope_theta=None,
        rms_eps=cfg["rms_norm_eps"], dtype=getattr(jnp, cfg["dtype"]),
        param_dtype=getattr(jnp, cfg["param_dtype"]), sliding_window=0,
        tie_embeddings=cfg["tie_word_embeddings"], qk_norm=True, post_norm=True,
        layer_types=tuple(cfg["layer_types"][:cfg["num_hidden_layers"]]),
        linear_num_heads=cfg["linear_num_value_heads"],
        linear_key_head_dim=cfg["linear_key_head_dim"],
        linear_value_head_dim=cfg["linear_value_head_dim"],
        linear_conv_kernel=cfg["linear_conv_kernel_dim"],
        linear_neg_eigval=cfg["linear_allow_neg_eigval"],
        linear_chunk=cfg["serving"]["linear_chunk"],
    )


def spec_of(config):
    """What the reference needs beside the parameter tree."""
    from chipbench.reference import olmo_hybrid

    return olmo_hybrid.Spec(tuple(config.layer_types), float(config.rms_eps),
                            bool(config.linear_neg_eigval))


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
    params = jax.jit(functools.partial(llama.init, config=hybrid_config(cfg)))(
        jax.random.key(seed % (2**31))
    )
    return jax.block_until_ready(params)


def system_run(params, config, cache, slots: int, seed: int, prompt_lens, steps: int,
               between=None):
    """One check prompt of each of ``prompt_lens`` into cache rows 0, 1, ..
    by ``llama.prefill_into_slot`` and ``steps`` calls of
    ``llama.decode_step_rowwise`` over all ``slots`` rows — THE TWO
    EXECUTABLES THE ENGINE SERVES WITH, in the cache it then serves from.
    Row 0 has served another request first: a prompt of the first length
    and ``EARLIER_STEPS`` steps.  ``between(cache) -> cache``: applied
    after every call, for the readings that plant a fault there (the job
    plants none).  -> (cache, {"rows": [per check row {"ids": prompt and
    the tokens fed behind it, "logits": (1 + steps, V) the prefill's row
    and each step's}], "state_low_bits": of the check rows' recurrent state
    as the last step left it, the share of non-zero values whose float32
    word has a bit set below bfloat16's sixteen — 1 - 2**-16 of a state
    held in float32, none of one that passed through bfloat16})."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama

    keep = between or (lambda c: c)

    def prompt(r, n):
        return np.random.default_rng([(seed + r) % (2**63), 11]).integers(
            0, config.vocab_size, n).tolist()

    def step(cache, fed):  # {row: (token, position)}; the other rows idle at 0
        tokens, pos = np.zeros((slots,), np.int32), np.zeros((slots,), np.int32)
        for r, (t, p) in fed.items():
            tokens[r], pos[r] = t, p
        logits, cache = llama.decode_step_rowwise(
            params, jnp.asarray(tokens), cache, jnp.asarray(pos), config)
        return logits, keep(cache)

    def prefill(cache, r, ids):
        logits, cache = llama.prefill_into_slot(
            params, jnp.asarray([ids], jnp.int32), cache, jnp.int32(r), config)
        return logits[0], keep(cache)

    earlier = prompt(len(prompt_lens), prompt_lens[0])
    last, cache = prefill(cache, 0, earlier)
    for _ in range(EARLIER_STEPS):
        earlier.append(int(jnp.argmax(last)))
        logits, cache = step(cache, {0: (earlier[-1], len(earlier) - 1)})
        last = logits[0]
    rows = []
    for r, n in enumerate(prompt_lens):
        ids = prompt(r, n)
        first, cache = prefill(cache, r, ids)
        rows.append({"ids": ids, "logits": [first]})
    for _ in range(steps):
        for row in rows:
            row["ids"].append(int(jnp.argmax(row["logits"][-1])))
        logits, cache = step(
            cache, {r: (row["ids"][-1], len(row["ids"]) - 1) for r, row in enumerate(rows)})
        for r, row in enumerate(rows):
            row["logits"].append(logits[r])
    for row in rows:
        row["logits"] = np.asarray(jnp.stack(row["logits"]))
    state = cache["gdn_state"][:, :len(rows)]
    low = (jax.lax.bitcast_convert_type(state, jnp.uint32) & 0xFFFF) != 0
    held = float(low.sum() / jnp.maximum(1, (state != 0).sum()))
    return cache, {"rows": rows, "state_low_bits": held}


def against_reference(params, config, out: dict) -> dict:
    """What ``system_run`` recorded against the float32 reference's full
    forward over each row's [prompt; tokens fed], every sequence filled up
    with zeros to the longest (causal: what lies behind a position does not
    reach it; one compile serves the rows).  {"err": rms and max of |logits
    - reference| / std over every compared row, "state_low_bits": as
    ``system_run`` read it}."""
    import jax.numpy as jnp
    import numpy as np

    from chipbench.reference import errors
    from chipbench.reference import olmo_hybrid as reference

    spec, rows = spec_of(config), out["rows"]
    longest = max(len(row["ids"]) for row in rows)
    want = []
    for row in rows:
        n = row["logits"].shape[0]
        first = len(row["ids"]) - n          # the prompt's last position
        ids = row["ids"] + [0] * (longest - len(row["ids"]))
        want.append(np.asarray(reference.forward(
            params, jnp.asarray(ids, jnp.int32), spec, rows=list(range(first, first + n)))))
    err = errors(np.concatenate([row["logits"] for row in rows]), np.concatenate(want))
    return {"err": err, "state_low_bits": out["state_low_bits"]}


def passes(got: dict, tolerance: dict) -> bool:
    """The comparison that decides ``correct``: every compared row's logits
    within rms and max, and the recurrent state held in float32 between
    steps, as the configuration states it — which the logits cannot show:
    a state rounded to bfloat16 moves them less than the bfloat16
    activations around it do (the limits and the readings they lie between:
    the configuration file's ``reference_tolerance.why``)."""
    from chipbench.reference import within

    return bool(within(got["err"], tolerance)
                and got["state_low_bits"] >= tolerance["state_low_bits_min"])


class HybridReplica(BenchReplica):
    """``BenchReplica`` compared with the Olmo-Hybrid reference, whose
    ``stats()`` also keeps the counters of the run's window."""

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
        lens = tolerance["check_prompt_lens"]
        eng.cache, out = system_run(
            eng.params, cfg, eng.cache, eng.max_slots, seed, lens,
            int(tolerance["check_steps"]))
        got = against_reference(eng.params, cfg, out)
        # the decode program again, for its temporaries and its text (the
        # jitted call above keeps no handle on its executable)
        idle = jnp.zeros((eng.max_slots,), jnp.int32)
        decode = llama.decode_step_rowwise.lower(
            eng.params, idle, eng.cache, idle, cfg).compile()
        temp = decode.memory_analysis().temp_size_in_bytes
        if tolerance.get("scope_file"):
            wrote = {"prompt_lens": tolerance["scope_prompt_lens"], "versions": {
                "decode_step_rowwise": [gdn_trace.version(decode.as_text())],
                "prefill_into_slot": [
                    gdn_trace.version(llama.prefill_into_slot.lower(
                        eng.params, jnp.zeros((1, n), jnp.int32), eng.cache,
                        jnp.int32(0), cfg,
                    ).compile().as_text())
                    for n in tolerance["scope_prompt_lens"]
                ],
            }}
            with open(tolerance["scope_file"], "w") as f:
                json.dump(wrote, f)
        self._counters_file = tolerance.get("counters_file")
        print(f"[serve_hybrid] reference check at {lens} + {tolerance['check_steps']} "
              f"steps: {got}", flush=True)
        return {**got, "tol": tolerance, "ok": passes(got, tolerance),
                "live_bytes": live, "decode_temp_bytes": temp,
                "cache_bytes": {k: int(np.prod(v.shape)) * v.dtype.itemsize
                                for k, v in eng.cache.items()}}


def _counted(stats: dict) -> dict:
    return {k: v for k, v in stats.items() if k.startswith(COUNTED)}


def _window(counters_file: str) -> dict:
    """The window's counters as the readers' facts: the ``stats()`` call at
    the window's end minus the call after warm-up — the first two the
    replica wrote down (``serve_llm.run`` asks once more, after the drain)."""
    with open(counters_file) as f:
        calls = [json.loads(line) for line in f]
    if len(calls) < 2:
        raise RuntimeError(f"the replica wrote down {len(calls)} stats() call(s), not "
                           "the one after warm-up and the one at the window's end")
    before, after = calls[0], calls[1]
    delta = {k: after[k] - before[k] for k in after}
    steps = delta.pop("decode_steps_total")
    if steps <= 0 or delta["gdn_rows_stepped"] <= 0 or delta["gdn_tokens_scanned"] <= 0:
        raise RuntimeError(f"no decode step or no prefill ran a linear layer in the "
                           f"window: {delta}")
    return {"decode_steps_in_window": int(steps),
            "prefills_in_window": int(delta.pop("admitted_total")), **delta}


class _InTurn(serve_dsa._InTurn):
    """``serve_dsa._InTurn`` under ``serve_llm.run``, which names the
    seconds a traced run's profiler froze the replica: the requests that
    count are those that received a token inside the window, so none is
    left out for when it was sent."""

    def summarize(self, outcomes, seconds, open_loop, frozen=None):
        return super().summarize(outcomes, seconds, open_loop)


def _exchanged() -> dict:
    """What ``run`` puts in place of ``serve_llm``'s own while its ``run``
    runs."""
    return {"llama_config": hybrid_config, "BenchReplica": HybridReplica,
            "make_weights": make_weights, "REHEARSAL_MODEL": REHEARSAL_MODEL,
            "loadgen": _InTurn(loadgen)}


_lost = [n for n in _exchanged() if not hasattr(serve_llm, n)]
if _lost:
    raise RuntimeError(
        f"jobs/serve_hybrid.py exchanges {_lost} inside serve_llm.run, and "
        "jobs/serve_llm.py no longer has them"
    )


def run(ctx: dict) -> dict:
    """``serve_llm.run`` with its hard-wired parts exchanged; then the
    window's counters and, for a traced run, the recurrent layers' device
    time."""
    if ctx["traffic"]["loop"] != "closed":
        raise RuntimeError("the serve_hybrid job runs closed-loop mixes only")
    tolerance = dict(ctx["config"]["reference_tolerance"])
    lens = loadgen.prompt_lengths(ctx["traffic"])
    ragged = RAGGED_PROMPT_LEN
    chunk = ctx["config"]["serving"]["linear_chunk"]
    if ctx["rehearse"]:
        lens, ragged, chunk = [16, 32], 27, 4
        tolerance.update(check_steps=4)
    # one prompt of each length of the mix, and one that fills no whole chunk
    tolerance["check_prompt_lens"] = lens + [ragged]
    os.makedirs(ctx["storage_dir"], exist_ok=True)
    tolerance["counters_file"] = os.path.join(ctx["storage_dir"], "hybrid_counters.jsonl")
    if ctx["trace"]:
        tolerance.update(
            scope_file=os.path.join(ctx["trace_dir"], gdn_trace.SCOPE_FILE),
            scope_prompt_lens=lens,
        )
    config = dict(ctx["config"], reference_tolerance=tolerance,
                  serving=dict(ctx["config"]["serving"], linear_chunk=chunk))
    with mock.patch.multiple(serve_llm, **_exchanged()):
        job = serve_llm.run(dict(ctx, config=config))
    model = dict(config, **REHEARSAL_MODEL) if ctx["rehearse"] else config
    job["facts"].update(_window(tolerance["counters_file"]))
    job["facts"]["model"] = {
        k: v for k, v in model.items()
        if isinstance(v, (int, float)) or k == "layer_types"}
    job["facts"]["linear_chunk"] = chunk
    if ctx["trace"] and os.path.isdir(ctx["trace_dir"]):
        job["facts"].update(gdn_trace.facts(ctx["trace_dir"]))
    return job
