"""Speculative decoding with a multi-token-prediction (MTP) module: the
decode replica's two programs for a deployment that drafts one token a
step (``LLMEngine(speculative_tokens=1)``).

The module (DeepSeek-V3's, depth 1; ``LlamaConfig.mtp_layers`` = 1) is one
more block of the main model's shape behind its last layer, with its own
cache layer (``config.num_layers``), the main model's embedding and output
head, and four parameters of its own (``params["mtp"]``).  For the pair
(main model's final-normed hidden state ``h_i`` at position i, the NEXT
token ``t_{i+1}``)::

    x_i     = eh_proj([enorm(Emb(t_{i+1})) ; hnorm(h_i)])
    y_i     = Block(x)_i        rotary position i, causal over ITS cache
    q_{i+2} = softmax(Head(head_norm(y_i)) / temperature)

``q_{i+2}`` drafts the token at position i + 2.

WHAT A ROW CARRIES between steps, on the device (``init_state``): ``pos``
n — tokens ``t_<=n`` are accepted and ``t_n`` has not been through the main
model; ``toks`` ``[t_{n-1}, t_n]`` and ``hid`` ``[h_{n-2}, h_{n-1}]``, the
module's last two pairs; ``left``, tokens still to emit; ``req``, the
request's number (for its draws).  The host holds none of it and waits for
none of it to launch the next step.

ONE STEP (``decode_step_rowwise``; every row, fixed shapes):

a. the module over pairs n - 2 and n - 1 of every row (after an accepted
   draft both are new; after a rejected one pair n - 2 is computed again,
   to the same cache row) -> ``q_{n+1}``, draft ``d ~ q_{n+1}``;
b. the main model over ``[t_n, d]`` at positions n, n + 1: ONE
   ``llama._cached_step`` of two tokens a row, both latent rows written,
   each query seeing the keys up to its own position -> ``p_{n+1}``,
   ``p_{n+2}`` and the hidden states ``h_n``, ``h_{n+1}``;
c. ``u < min(1, p_{n+1}(d) / q_{n+1}(d))``: emit ``d`` and ``t_{n+2} ~
   p_{n+2}``, advance 2; else emit ``t_{n+1} ~ norm(max(p_{n+1} - q_{n+1},
   0))``, advance 1.  Every emitted token is distributed as the main
   model's own (Leviathan et al. 2023; Chen et al. 2023).  At temperature 0
   all four distributions are one-hot: the draft is accepted where it is
   the main model's ``argmax``.

A rejected draft's cache rows (position n + 1 of the main layers) lie
behind the row's new ``pos`` and the next step writes them anew before any
query may see them: no copy, no rollback pass.  A row whose ``left`` is 0
(an empty slot, or a request whose budget is met while the host has not
heard yet) is stepped like every other and its state stays as it was: what
it writes lies in its own slot, at positions it already held.

Draws: ``llama.draw_keys(key, request, position, purpose)``.

Both programs hand back, last, a ``detail`` of what they decided from —
the step: ``pos`` (B,), ``draft`` (B,), the main model's ``p_logits`` (B, 2,
V) and the module's ``q_logits`` (B, V) in float32, the experts every token
chose in the main model's expert layers (``experts``: (expert layers, B, 2,
k)) and in the module's (``module_experts``: (B, 2, k)); the prefill: the
two ``experts`` of the prompt's tokens and pairs.  It is 6 MB a step that
the sampling has to materialise anyway; the engine drops it, and a
comparison with a reference (``chipbench/jobs/serve_mtp.py``) reads it
from the very executables that serve.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import llama
from ray_tpu.models.llama import (DRAW_ACCEPT, DRAW_DRAFT, DRAW_RESIDUAL,
                                  DRAW_TOKEN, LlamaConfig, Params)


def init_state(config: LlamaConfig, batch_size: int) -> Params:
    """What every cache row carries between speculative steps; all rows
    empty (``left`` 0)."""
    B = batch_size
    return {
        "pos": jnp.ones((B,), jnp.int32),
        "toks": jnp.zeros((B, 2), jnp.int32),
        "hid": jnp.zeros((B, 2, config.embed_dim), config.dtype),
        "left": jnp.zeros((B,), jnp.int32),
        "req": jnp.zeros((B,), jnp.int32),
    }


def module_step(params: Params, hidden, tokens, state: Params, slot, positions,
                config: LlamaConfig):
    """The module over each row's run of pairs.  hidden (R, Sq, E): the
    main model's final-normed ``h_i``; tokens (R, Sq): ``t_{i+1}``;
    positions (R, Sq): i; state: the cache's token state; slot as
    ``llama._block_step``'s.  -> (``head_norm(y)`` (R, Sq, E), state, aux:
    the block's, its ``experts`` (R, Sq, k) among them).  Its callers put it
    under the scope ``mtp_draft``."""
    c = config
    m = params["mtp"]
    emb = params["tok_embed"].astype(c.dtype)[tokens]
    x = jnp.concatenate([
        llama._rmsnorm(emb, m["enorm"], c.rms_eps),
        llama._rmsnorm(hidden.astype(c.dtype), m["hnorm"], c.rms_eps),
    ], axis=-1)
    x = jnp.einsum("rsd,de->rse", x, m["eh_proj"].astype(c.dtype))
    (stacked, _), whole = llama._layer_params(m["block"], c)
    p = dict(
        {k: v[0] for k, v in stacked.items()},
        layer=0, cache_layer=c.num_layers, **whole,
    )
    x, state, aux = llama._block_step(x, p, state, slot, positions, c, True)
    return llama._rmsnorm(x, m["head_norm"], c.rms_eps), state, aux


def _counted(cache: Params, state: Params, aux: Params, step: bool,
             config: LlamaConfig) -> Params:
    """``llama._with_counts`` of the module's one layer."""
    aux = {k: v[None] for k, v in aux.items() if k in ("expert_rows", "row_tiles", "mla_keys")}
    return llama._with_counts(cache, state, aux, step, first=config.num_layers)


def accept(p_logits, q_logits, draft, key, request, position, *, temperature):
    """The acceptance rule.  p_logits (B, 2, V): the main model's at the
    draft's position and behind it; q_logits (B, V): the module's at the
    draft's position; draft (B,); position (B,): the draft's.  -> (accepted
    (B,) bool, tokens (B, 2) int32: ``[the token at the draft's position,
    the one behind it]``, the second to be used where accepted)."""
    if temperature <= 0.0:
        top = jnp.argmax(p_logits, axis=-1).astype(jnp.int32)
        return draft == top[:, 0], top
    p = jax.nn.softmax(p_logits[:, 0] / temperature, axis=-1)
    q = jax.nn.softmax(q_logits / temperature, axis=-1)
    p_d = jnp.take_along_axis(p, draft[:, None], axis=-1)[:, 0]
    q_d = jnp.take_along_axis(q, draft[:, None], axis=-1)[:, 0]
    u = jax.vmap(jax.random.uniform)(
        llama.draw_keys(key, request, position, DRAW_ACCEPT)
    )
    accepted = u < jnp.minimum(1.0, p_d / q_d)
    other = jax.vmap(jax.random.categorical)(
        llama.draw_keys(key, request, position, DRAW_RESIDUAL),
        jnp.log(jnp.maximum(p - q, 0.0)),
    ).astype(jnp.int32)
    behind = llama._pick_token(
        p_logits[:, 1], llama.draw_keys(key, request, position + 1, DRAW_TOKEN),
        temperature=temperature,
    )
    return accepted, jnp.stack([jnp.where(accepted, draft, other), behind], axis=1)


def _speculate(params: Params, state: Params, cache: Params, key,
               config: LlamaConfig, temperature: float):
    """One speculative step -> (outs, state, cache, detail): ``outs`` (B,
    4) int32 ``[token, token, how many of them count (0, 1 or 2),
    drafted-and-accepted (0 or 1)]``; ``detail``: what the step decided
    from (module docstring)."""
    c = config
    n, req = state["pos"], state["req"]
    with jax.named_scope("mtp_draft"):
        pairs = jnp.maximum(n[:, None] + jnp.array([-2, -1]), 0)
        y, token_state, aux = module_step(
            params, state["hid"], state["toks"], {"ckv": cache["ckv"]}, None, pairs, c
        )
        cache = _counted(cache, token_state, aux, True, c)
        q_logits = llama._logits(params, y[:, 1], c)
        draft = llama._pick_token(
            q_logits, llama.draw_keys(key, req, n + 1, DRAW_DRAFT),
            temperature=temperature,
        )
    with jax.named_scope("spec_verify"):
        fed = jnp.stack([state["toks"][:, 1], draft], axis=1)
        hidden, cache, chose = llama._cached_step(
            params, fed, cache, None, n, c, collect=True, hidden=True
        )
        p_logits = llama._logits(params, hidden, c)
    with jax.named_scope("spec_accept"):
        accepted, tokens = accept(
            p_logits, q_logits, draft, key, req, n + 1, temperature=temperature
        )
        live = state["left"] > 0
        count = jnp.where(live, jnp.minimum(1 + accepted, state["left"]), 0)
        left = state["left"] - count
        go = left > 0  # a row that is done keeps the state it had

        def moved(new, old):
            return jnp.where(go.reshape(-1, *(1,) * (new.ndim - 1)), new, old)

        took = accepted[:, None]
        state = {
            "pos": moved(n + 1 + accepted, n),
            "toks": moved(
                jnp.where(took, tokens, jnp.stack([fed[:, 0], tokens[:, 0]], 1)),
                state["toks"],
            ),
            "hid": moved(
                jnp.where(
                    took[:, :, None], hidden,
                    jnp.stack([state["hid"][:, 1], hidden[:, 0]], 1),
                ).astype(c.dtype),
                state["hid"],
            ),
            "left": left,
            "req": req,
        }
        outs = jnp.concatenate(
            [tokens, count[:, None], (accepted & live)[:, None].astype(jnp.int32)],
            axis=1,
        )
    return outs, state, cache, {
        "pos": n, "draft": draft, "p_logits": p_logits, "q_logits": q_logits,
        "experts": chose.get("experts"), "module_experts": aux.get("experts"),
    }


@partial(jax.jit, static_argnames=("config", "temperature"),
         donate_argnames=("state", "cache"))
def decode_step_rowwise(params, state, cache, key, config: LlamaConfig,
                        temperature: float):
    """One speculative step for every row: the engine's decode program
    where it drafts (the module's docstring).  -> (outs (B, 4) int32,
    state, cache, detail)."""
    return _speculate(params, state, cache, key, config, temperature)


@partial(jax.jit, static_argnames=("config", "temperature"),
         donate_argnames=("state", "cache"))
def prefill_into_slot(params, tokens, cache, slot, state, key, request,
                      max_new, config: LlamaConfig, temperature: float):
    """Prefill ONE sequence into cache row ``slot`` of the main layers AND
    of the module's: the prompt through ``llama._cached_step``, the first
    new token drawn from its last logits, the module over the prompt's S
    pairs (``h_0..h_{S-1}`` with ``t_1..t_S``, the drawn one last), and the
    row's state set.  tokens (1, S).  -> (the first token () int32, cache,
    state, detail)."""
    c = config
    S = tokens.shape[1]
    hidden, cache, chose = llama._cached_step(
        params, tokens, cache, slot, jnp.zeros((1,), jnp.int32), c,
        collect=True, hidden=True,
    )
    request = jnp.reshape(request, (1,)).astype(jnp.int32)
    first = llama._pick_token(
        llama._logits(params, hidden[:, -1], c),
        llama.draw_keys(key, request, jnp.full((1,), S, jnp.int32), DRAW_TOKEN),
        temperature=temperature,
    )
    nxt = jnp.concatenate([tokens[:, 1:], first[:, None]], axis=1)
    with jax.named_scope("mtp_draft"):
        _, _, aux = module_step(
            params, hidden, nxt, {}, slot, jnp.arange(S)[None, :], c
        )
    ckv = lax.dynamic_update_slice(
        cache["ckv"], aux["ckv_rows"][None, None], (c.num_layers, slot, 0, 0)
    )
    cache = _counted(cache, {"ckv": ckv}, aux, False, c)
    last_two = [max(S - 2, 0), S - 1]
    state = {
        "pos": state["pos"].at[slot].set(S),
        "toks": state["toks"].at[slot].set(
            jnp.stack([tokens[0, -1] if S > 1 else first[0], first[0]])
        ),
        "hid": state["hid"].at[slot].set(hidden[0, jnp.array(last_two)].astype(c.dtype)),
        "left": state["left"].at[slot].set(max_new - 1),
        "req": state["req"].at[slot].set(request[0]),
    }
    return first[0], cache, state, {
        "experts": chose.get("experts"), "module_experts": aux.get("experts"),
    }
