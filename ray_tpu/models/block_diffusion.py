"""Generation by diffusion over blocks (SDAR): the decode replica's two
programs for a deployment that refines a block of B tokens a row in place
and commits it (``LLMEngine(diffusion_block=B)``).

The model is a decoder run under a BLOCK-causal mask (``LlamaConfig.
mask_block`` = B: a query sees every key up to the end of its own block of
B positions) whose logits row i is the distribution of token i ITSELF, not
of the next one.  A MASK id stands where a token is not decided yet.  The
model card's loop, for one sequence::

    prefill   the first (P // B) * B prompt tokens in one forward, K/V kept;
              the P % B left over open the first block, unmasked
    a block   cur = [leftover prompt tokens ; MASK ...] at positions n .. n + B - 1
      repeat  if cur has no MASK: forward(cur) with its K/V KEPT (the commit);
                  emit the block; next block
              logits = forward(cur), K/V not kept
              x0_i ~ softmax(logits_i / T), c_i = that probability    (masked i)
              high = {masked i : c_i > threshold};  n_t = B / denoising_steps
              transfer = high if |high| >= n_t else the n_t masked i of largest c_i
              cur_i = x0_i for i in transfer

(``low_confidence_dynamic``; the MASK id's logit is left out of the draw and
of ``c``, so a transferred token is never MASK.)  At temperature 0 ``x0`` is
the ``argmax`` and ``c`` its probability at temperature 1.

WHAT A ROW CARRIES between steps, on the device (``init_state``): ``pos`` n,
the position of its block's first token (a multiple of B); ``block`` (B,),
the block as it stands; ``skip``, how many of its leading tokens are the
prompt's (the first block's leftover: never emitted); ``left``, tokens
still to emit; ``req``, the request's number, and ``passes``, the refining
passes this block has had (both for the draws' keys).  The host holds none
of it and waits for none of it to launch the next step.

ONE STEP (``decode_step_rowwise``; every row, fixed shapes, whatever phase
each row is in): ONE ``llama._cached_step`` of B tokens a row at positions n
.. n + B - 1, the block's K/V written into the row's own cache rows.  A row
whose block arrived without MASK has just been COMMITTED by this very
forward — its final tokens' K/V are what the cache now holds: emit them
(less ``skip``, at most ``left``), advance to the next block, all MASK.  Any
other live row: apply the rule above to its logits.  "K/V not kept" is
"written into the block's own cache rows and written anew by every later
pass, the commit last": those rows lie at or behind no other query's horizon
until the block is committed (a row's queries see its own slot only), as a
rejected draft's rows do in ``models/mtp.py`` — no copy, no rollback pass.
A row whose ``left`` is 0 (an empty slot, or a request whose budget is met)
is stepped like every other and its state stays as it was.

Draws: ``fold_in(llama.draw_keys(key, request, position, DRAW_UNMASK),
pass)``: a candidate's draw hangs on its request, its position and the pass
only.

Both programs hand back, last, a ``detail`` of what they decided from — the
step: ``pos`` (B,), ``passes`` (B,), the block as it stood ``block`` (B, Bk),
``logits`` (B, Bk, V) float32, the candidates ``x0`` and their ``conf``
(B, Bk), ``transfer`` (B, Bk) bool, and the experts every token chose
(``experts``: (expert layers, B, Bk, k)); the prefill: the ``experts`` of the
prompt's tokens.  The engine drops it; a comparison with a reference
(``chipbench/jobs/serve_diffusion.py``) reads it from the very executables
that serve.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import llama
from ray_tpu.models.llama import DRAW_UNMASK, LlamaConfig, Params

#: columns of a step's ``outs`` behind the block's ids: how many of them
#: count, and of this row-step: was the row live, did it commit a block,
#: tokens unmasked, of those by the threshold, keys its queries could see;
#: and, live or not, the last cache slot the row's attention was given
OUT_FIELDS = ("count", "live", "committed", "unmasked", "by_threshold", "visible",
              "last")


@dataclasses.dataclass(frozen=True)
class Settings:
    """The generation settings of a deployment (static: one compile)."""

    block: int = 4
    denoising_steps: int = 4
    threshold: float = 0.9
    mask_id: int = 0

    def __post_init__(self):
        if self.block < 2 or self.denoising_steps < 1 or self.block % self.denoising_steps:
            raise ValueError(
                f"a block of {self.block} tokens refined in {self.denoising_steps} "
                "steps: the block is at least 2 and a whole multiple of the steps"
            )


def init_state(config: LlamaConfig, batch_size: int, settings: Settings) -> Params:
    """What every cache row carries between steps; all rows empty
    (``left`` 0)."""
    B = batch_size
    state = {  # a buffer each: the steps donate them
        k: jnp.zeros((B,), jnp.int32) for k in ("pos", "skip", "left", "req", "passes")
    }
    state["block"] = jnp.full((B, settings.block), settings.mask_id, jnp.int32)
    return state


def candidates(logits, key, request, positions, passes, *, temperature, mask_id):
    """A candidate for every position of every block and its confidence.
    logits (B, Bk, V) float32; request, passes (B,); positions (B, Bk).  ->
    (x0 (B, Bk) int32, conf (B, Bk) float32: the probability x0 was drawn
    with).  The MASK id is in neither."""
    B, Bk, V = logits.shape
    logits = jnp.where(jnp.arange(V) == mask_id, -jnp.inf, logits)
    if temperature > 0.0:
        logits = logits / temperature
        keys = llama.draw_keys(
            key, jnp.repeat(request, Bk), positions.reshape(-1), DRAW_UNMASK
        )
        keys = jax.vmap(jax.random.fold_in)(keys, jnp.repeat(passes, Bk))
        x0 = jax.vmap(jax.random.categorical)(keys, logits.reshape(-1, V))
        x0 = x0.reshape(B, Bk).astype(jnp.int32)
    else:
        x0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    probs = jax.nn.softmax(logits, axis=-1)
    return x0, jnp.take_along_axis(probs, x0[..., None], axis=-1)[..., 0]


def transfers(conf, masked, settings: Settings):
    """The rule (``low_confidence_dynamic``).  conf, masked (B, Bk) -> (the
    positions unmasked by this pass (B, Bk) bool, by the threshold? (B,)
    bool).  Ties in confidence go to the lower position."""
    per_pass = settings.block // settings.denoising_steps
    high = masked & (conf > settings.threshold)
    _, best = lax.top_k(jnp.where(masked, conf, -1.0), per_pass)       # (B, per_pass)
    top = (best[:, :, None] == jnp.arange(settings.block)).any(1) & masked
    enough = high.sum(-1) >= per_pass
    return jnp.where(enough[:, None], high, top), enough


def _refine(params: Params, state: Params, cache: Params, key,
            config: LlamaConfig, temperature: float, settings: Settings):
    """One step -> (outs, state, cache, detail): ``outs`` (B, Bk + 7) int32:
    the ids a row emits first, then ``OUT_FIELDS``; ``detail``: what the
    step decided from (module docstring)."""
    c, s = config, settings
    Bk = s.block
    cur, n, skip, left = state["block"], state["pos"], state["skip"], state["left"]
    passes = state["passes"]
    masked = cur == s.mask_id
    live = left > 0
    commit = live & ~masked.any(-1)
    refine = live & ~commit
    with jax.named_scope("diff_forward"):
        hidden, cache, chose = llama._cached_step(
            params, cur, cache, None, n, c, collect=True, hidden=True
        )
        logits = llama._logits(params, hidden, c)
    with jax.named_scope("diff_unmask"):
        positions = n[:, None] + jnp.arange(Bk)
        x0, conf = candidates(
            logits, key, state["req"], positions, passes,
            temperature=temperature, mask_id=s.mask_id,
        )
        transfer, enough = transfers(conf, masked, s)
        transfer = transfer & refine[:, None]
        count = jnp.where(commit, jnp.minimum(Bk - skip, left), 0)
        left = left - count
        onward = commit & (left > 0)  # a row that is done keeps the state it had

        def moved(new, old, when):
            return jnp.where(when.reshape(-1, *(1,) * (old.ndim - 1)), new, old)

        state = {
            "pos": moved(n + Bk, n, onward),
            "block": moved(s.mask_id, jnp.where(transfer, x0, cur), onward),
            "skip": moved(0, skip, onward),
            "passes": moved(0, passes + refine, onward),
            "left": left,
            "req": state["req"],
        }
        ids = jnp.take_along_axis(cur, (jnp.arange(Bk) + skip[:, None]) % Bk, axis=1)
        unmasked = transfer.sum(-1, dtype=jnp.int32)
        outs = jnp.concatenate([ids, jnp.stack([
            count, live.astype(jnp.int32), commit.astype(jnp.int32), unmasked,
            jnp.where(enough, unmasked, 0), jnp.where(live, n + Bk, 0), n + Bk - 1,
        ], axis=1)], axis=1)
    return outs, state, cache, {
        "pos": n, "passes": passes, "block": cur, "logits": logits,
        "x0": x0, "conf": conf, "transfer": transfer,
        "experts": chose.get("experts"),
    }


@partial(jax.jit, static_argnames=("config", "temperature", "settings"),
         donate_argnames=("state", "cache"))
def decode_step_rowwise(params, state, cache, key, config: LlamaConfig,
                        temperature: float, settings: Settings):
    """One block-diffusion step for every row: the engine's decode program
    where it generates by diffusion (the module's docstring).  -> (outs (B,
    Bk + 7) int32, state, cache, detail)."""
    return _refine(params, state, cache, key, config, temperature, settings)


@partial(jax.jit, static_argnames=("config", "temperature", "settings"),
         donate_argnames=("state", "cache"))
def prefill_into_slot(params, tokens, cache, slot, state, key, request,
                      max_new, config: LlamaConfig, temperature: float,
                      settings: Settings):
    """Prefill ONE sequence into cache row ``slot``: the prompt's whole
    blocks through ``llama._cached_step`` under the block mask (none where
    the prompt is shorter than a block), what is left over into the row's
    first block, and the row's state set.  tokens (1, S).  -> (-1: a prefill
    emits no token, cache, state, detail).  ``key`` and ``temperature`` are
    the drafting prefill's, which draws; this one does not."""
    c, Bk = config, settings.block
    S = tokens.shape[1]
    whole = S // Bk * Bk
    detail = {}
    if whole:
        _, cache, chose = llama._cached_step(
            params, tokens[:, :whole], cache, slot, jnp.zeros((1,), jnp.int32), c,
            collect=True, hidden=True,
        )
        detail["experts"] = chose.get("experts")
    first = jnp.concatenate([
        tokens[0, whole:], jnp.full((Bk - (S - whole),), settings.mask_id, jnp.int32)
    ])
    new = {"pos": whole, "block": first, "skip": S - whole, "left": max_new,
           "req": request, "passes": 0}
    state = {k: v.at[slot].set(new[k]) for k, v in state.items()}
    return jnp.int32(-1), cache, state, detail
