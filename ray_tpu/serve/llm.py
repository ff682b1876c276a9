"""LLM serving: a continuous-batching decode replica over the Llama
KV-cache path.

Role-equivalent of ray: serve's LLM deployments (serve/llm, and the
vLLM-on-ray pattern): N concurrent streaming requests share ONE fixed
slot batch — new requests prefill into free cache rows while existing
rows keep decoding (continuous batching), every decode step is one fused
XLA call over all slots (`llama.decode_step_rowwise`, per-row
positions), and tokens stream back per request over the core
streaming-generator transport.

A step yields one token a row — or, where the deployment drafts
(``speculative_tokens=1``, a model with a multi-token-prediction module:
``models/mtp.py``), one or two: the module drafts a token, the main model
verifies it in the same step, and the row advances by two where the draft
is accepted.  Where it generates by diffusion over blocks
(``diffusion_block=4``, a model trained for it: ``models/
block_diffusion.py``) a step refines every row's block of four tokens in
place and a row gets none of them until its block is committed, then all
four.  Both are the ONE path of "a step with state on the device that
yields 0..k ids a row" (``_launch_stateful`` / ``_hand_out``).
``temperature`` 0 decodes greedily; above 0 every token is
drawn from the main model's softmax at that temperature, with draws that
hang on ``seed``, the request and the position only.  Whatever the
options, a request gets exactly ``max_new_tokens`` ids, in order, one
stream item each; drafting changes how many steps they take, not what
they are.

Wire-up::

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import LlamaDeployment

    app = LlamaDeployment.options(name="llm").bind(
        config=my_config, weights_ref=ray_tpu.put(params),
        max_slots=8, max_len=2048,
    )
    h = serve.run(app, name="llm_app")
    for tok in h.options(method_name="generate", stream=True).remote(
            prompt_ids, max_new_tokens=64):
        ...
"""

from __future__ import annotations

import asyncio
import collections
import contextlib
import heapq
import itertools
import time
from typing import Any, List, Optional

from ray_tpu import serve
from ray_tpu.accelerators import tpu
from ray_tpu.util import metrics, tracing

#: histogram boundaries in ms, 1 ms .. 62 s in steps of 1.15x: a median
#: read from the buckets by interpolation is within 5% of the samples'
_MS_LADDER = tuple(round(1.15 ** k, 4) for k in range(80))
#: always on, two observations per request, none per step
_QUEUE_WAIT_MS = metrics.Histogram(
    "llm_queue_wait_ms",
    "stream() pushed the request -> the slot admitter popped it",
    boundaries=_MS_LADDER, tag_keys=("outcome",),
)
_ENGINE_TTFT_MS = metrics.Histogram(
    "llm_engine_ttft_ms",
    "stream() pushed the request -> its first token was put on its queue",
    boundaries=_MS_LADDER,
)
#: expert configs only, set where ``cache_counters`` reads the counters
_MOE_TOUCHED_MEAN = metrics.Gauge(
    "llm_moe_experts_touched_mean",
    "experts with at least one row, mean over every layer-step so far",
)
_MOE_LOAD_MAX_OVER_MEAN = metrics.Gauge(
    "llm_moe_expert_load_max_over_mean",
    "rows of the busiest (layer, expert) over the mean of all, so far",
)
#: latent-attention configs only, set where ``cache_counters`` reads them
_DSA_SELECTED_SHARE = metrics.Gauge(
    "llm_dsa_selected_share",
    "keys attention was given over keys the indexer saw, every query so far",
)
_MOE_HELD_SHARE = metrics.Gauge(
    "llm_moe_held_assignment_share",
    "routed (token, expert) assignments that fell on an expert held here",
)
#: drafting deployments only, set where a step's tokens are delivered
_SPEC_ACCEPTANCE = metrics.Gauge(
    "llm_spec_acceptance_rate",
    "drafted tokens the main model accepted over tokens drafted, so far",
)
#: block-diffusion deployments only, set where a step's tokens are delivered
_DIFF_TOKENS_PER_FORWARD = metrics.Gauge(
    "llm_diffusion_tokens_per_row_forward",
    "tokens delivered over forwards of live rows (refining and commit), so far",
)
_OFF = contextlib.nullcontext()


def _part(parent: Optional[tracing.Span], name: str):
    """A child span of ``parent``, whatever the ambient context: a decode
    step's parts are recorded an iteration apart.  ``parent`` is None
    where tracing was off when the step began (asked once per step, so a
    step is recorded whole or not at all)."""
    if parent is None:
        return _OFF
    return tracing.Span(name, (parent.trace_id, parent.span_id), {})


class _Request:
    __slots__ = ("prompt", "max_new", "queue", "pushed", "trace_id", "number")

    def __init__(self, prompt, max_new, queue, trace_id, number):
        self.prompt = prompt
        self.max_new = max_new
        self.queue = queue          # per-request token queue
        self.pushed = time.monotonic()
        self.trace_id = trace_id    # of its llm.request span, if traced
        self.number = number        # in order of arrival: keys its draws


class _Slot:
    """What the host knows of a cache row when it launches a step.  The
    row's token is not here: it is row i of ``LLMEngine._tokens``, the
    last launched step's choice, which stays on the device.  Where the
    step keeps its state on the device (drafting, block diffusion), the
    row's position stays there too (a step advances it by a number only
    the device knows, ``LLMEngine._spec``), and the host counts what it has
    delivered: ``remaining`` is then tokens still to DELIVER."""

    __slots__ = ("queue", "pos", "remaining", "max_pos", "request", "pushed")

    def __init__(self, queue, pos, remaining, max_pos, request, pushed=None):
        self.queue = queue          # per-request token queue
        self.pos = pos              # position of the token the next step feeds
        self.remaining = remaining  # decode steps still to launch
        self.max_pos = max_pos
        self.request = request      # the request's number (its draws' key)
        # when the request was pushed, while it waits for its first token
        # (a block-diffusion prefill gives none); None once it has one
        self.pushed = pushed


class _Step:
    """A decode step from its launch to the delivery of its tokens."""

    __slots__ = ("tokens", "rows", "span", "loop")

    def __init__(self, tokens, rows, span, loop=None):
        # (max_slots,) int32 on the device, the step's choice a row; of a
        # stateful step (max_slots, k + extras): up to k ids, how many of
        # them count, and what the step's kind tallies (``_hand_out``)
        self.tokens = tokens
        # [(row, its _Slot, the request's last token?)]; of a stateful step
        # [(row, its _Slot)]: which token is the last is not known yet
        self.rows = rows
        self.span = span      # its llm.step span, if traced
        # a traced step of a looped config: the cache's loop totals as this
        # step left them, copied on the device (the next step takes the
        # cache itself), for the span (``LLMEngine._loop_totals``)
        self.loop = loop


_END = object()


class LLMEngine:
    """Slot-based continuous batcher: admit-prefill + shared decode step,
    with one decode step in flight ahead of the one being delivered.

    The host never needs a token to schedule the next step: step k+1 is
    fed step k's choice as it is, a device array (``_tokens``; a prefill's
    first token is merged into it on the device), a row's position is its
    last plus one, and a row ends by counts the host holds (no stop
    token).  Where the step keeps its state on the device (the deployment
    drafts, or generates by diffusion over blocks), a row's position and
    what it still has to emit are device arrays too (``_spec``: a step
    gives a row 0..k ids and only the device knows how many), the host
    learns how many tokens a step gave a row when it delivers them — one
    step late — and a row is retired by what has been DELIVERED: the step
    launched meanwhile steps that row once more, into its own slot, for
    nothing (``spec_`` / ``diffusion_wasted_row_steps_total``).  So the loop
    launches step k+1,
    then waits for step k's
    tokens, hands them out and yields to their consumers while the device
    computes.  Only an admission drains the pipeline: the prefill is
    launched behind the step in flight, that step's tokens are delivered,
    the prefill's first token is waited for, and the next step starts on
    an idle device — one long token gap per admission for every live
    row, not two.

    What a slot holds is the cache's business: K/V rows a free slot's
    next request overwrites position by position, and, where the config
    has linear-attention layers (``layer_types``), a recurrent state that
    no length describes — the admission's prefill writes it from zero,
    whatever the slot held (``llama._gated_delta_state``) — or, where it has
    window layers beside full ones (``sliding_attention``), a second K/V pair
    of ``window`` rolling slots a row, which the prefill fills with the
    prompt's last keys (``llama._kind_attention``) — or, where the model is
    LOOPED (``loop_passes``), K/V rows of every (pass, layer): a row-step still
    yields one token, the scheduler knows nothing of the passes, and a token
    costs ``cache_layers`` cache layers, not ``num_layers``.  Refused in words: with
    linear layers ``speculative_tokens``, ``diffusion_block`` and a
    model-wide ``sliding_window``; with window layers ``speculative_tokens``
    and ``diffusion_block`` (a block mask with a sliding window is not
    written: ``llama._cache_mask``)."""

    def __init__(self, params, config, *, max_slots: int = 4,
                 max_len: int = 256, max_prompt_len: Optional[int] = None,
                 speculative_tokens: int = 0, temperature: float = 0.0,
                 seed: int = 0, diffusion_block: int = 0,
                 denoising_steps: Optional[int] = None,
                 confidence_threshold: float = 0.9):
        import dataclasses

        import jax.numpy as jnp

        from ray_tpu.models import llama

        self._llama = llama
        self.params = params
        self.speculative = int(speculative_tokens)
        self.diffusion_block = int(diffusion_block)
        self.temperature = float(temperature or 0.0)
        if self.speculative not in (0, 1):
            raise ValueError("speculative_tokens is 0 or 1: one drafted token a step")
        def refuse(beside: str, refused: dict) -> None:
            for option, why in refused.items():
                if why:
                    raise ValueError(
                        f"{option} does not go with a config that has {beside}: {why}")

        if config.linear_layers:
            # a linear-attention layer's state is ONE matrix a row, not a
            # row a position: nothing of it can be taken back or rewritten
            refuse("linear-attention layers (layer_types)", {
                "speculative_tokens": self.speculative and (
                    "a rejected draft has already moved the row's recurrent "
                    "state, and there is no row of it to overwrite"),
                "diffusion_block": self.diffusion_block and (
                    "every refining pass runs the block's positions again, and "
                    "the recurrent state can take a position once"),
                "sliding_window": config.sliding_window and (
                    "a model-wide window's rolling cache re-addresses positions "
                    "(slot = position mod length) for every layer, and a "
                    "recurrent state has no positions to re-address"),
            })
        if config.sliding is not None:
            # a window layer keeps a row's last ``window`` keys and no others
            refuse("window layers (layer_types: sliding_attention), whose cache is "
                   f"{config.sliding.window} rolling slots a row", {
                "speculative_tokens": self.speculative and (
                    "a rejected draft's key has already overwritten the key "
                    "``window`` positions back, which the row still needs"),
                "diffusion_block": self.diffusion_block and (
                    "a block mask with a sliding window is not written "
                    "(llama._cache_mask), and every refining pass would write "
                    "the block's keys over keys the row still needs"),
            })
        if self.speculative and not config.mtp_layers:
            raise ValueError(
                "speculative_tokens=1 needs a model with a multi-token-prediction "
                "module to draft with (LlamaConfig.mtp_layers)"
            )
        if self.diffusion_block and (
            self.speculative or config.latent or config.sliding_window
            or max_len % self.diffusion_block
        ):
            raise ValueError(
                "diffusion_block goes with a K/V-cache model without a sliding "
                "window, without speculative_tokens, and with max_len a whole "
                f"number of blocks (max_len {max_len}, block {self.diffusion_block})"
            )
        # the two programs: the model's own, or a kind whose step keeps the
        # rows' state on the device and yields 0..k ids a row (drafting,
        # block diffusion): their state, the tokens a step brings a row (as
        # many ids as it may give it), and the programs' further static
        # arguments
        self._programs = llama
        self._spec = self._key = None
        self._step_ids = 1
        self._step_options: dict = {}
        if self.speculative:
            from ray_tpu.models import mtp

            self._programs = mtp
            self._spec = mtp.init_state(config, max_slots)
            self._step_ids = 2  # in the model and in the module
        elif self.diffusion_block:
            from ray_tpu.models import block_diffusion

            # the model is run under the block mask the deployment generates by
            config = dataclasses.replace(config, mask_block=self.diffusion_block)
            settings = block_diffusion.Settings(
                block=self.diffusion_block,
                denoising_steps=denoising_steps or self.diffusion_block,
                threshold=float(confidence_threshold),
                # the vocabulary's last row stands for the MASK token
                mask_id=config.vocab_size - 1,
            )
            self._programs = block_diffusion
            self._spec = block_diffusion.init_state(config, max_slots, settings)
            self._step_ids = self.diffusion_block
            self._step_options = {"settings": settings}
        self.config = config
        self.stateful = self._spec is not None
        if self.stateful or self.temperature > 0.0:
            import jax

            self._key = jax.random.key(seed)
        self.max_slots = max_slots
        self.max_len = max_len
        # Models with a MODEL-WIDE sliding window and an explicit prompt cap
        # get a ROLLING cache: window + max_prompt - 1 slots serve ANY decode
        # length up to max_len positions (the Mistral KV-memory win;
        # llama.rolling_cache_len).  Without the cap — or without such a
        # window — the cache holds every position, as before.  Where the
        # window is a layer KIND's (``config.sliding``), ``cache_len`` is the
        # FULL layers' and ``llama.init_cache`` sizes the window layers' own
        # K/V pair from the config: ``window`` rolling slots a row
        self.max_prompt_len = max_prompt_len or max_len
        if config.sliding_window and max_prompt_len:
            self.cache_len = min(
                max_len, llama.rolling_cache_len(config, max_prompt_len)
            )
        else:
            self.cache_len = max_len
        self.cache = llama.init_cache(config, max_slots, self.cache_len)
        # a looped config's traced steps copy the cache's two loop totals for
        # their spans (``_launch``): the copy is compiled here, at set-up
        self._loop_copy = None
        if config.loop_passes > 1:
            import jax

            self._loop_copy = jax.jit(lambda totals: jax.tree.map(jnp.copy, totals))
            self._loop_copy({k: self.cache[k] for k in ("loop_passes", "loop_exit_mass")})
        # held while a prefill or a decode step is launched on the
        # (donated) cache: whoever else wants to read it (cache_counters)
        # waits its turn, and then for the steps launched so far
        self._cache_lock = asyncio.Lock()
        # a slot is taken from a request's prefill to the LAUNCH of its
        # last step; that step's _Step delivers the last token
        self.slots: List[Optional[_Slot]] = [None] * max_slots
        # what the next decode step is fed, on the device
        self._tokens = jnp.zeros((max_slots,), jnp.int32)
        # launched and not yet delivered, oldest first: at most two, and
        # two only between a launch and the delivery that follows it
        self._flying: collections.deque = collections.deque()
        # slot admitter queue: EDF heap of (deadline, seq, _Request) —
        # requests with a traffic-plane SLO overtake deadline-less ones
        # (deadline=inf) at the free slot, and expired waiters are shed
        # before prefill
        self._pending: List[tuple] = []
        self._admit_seq = itertools.count()
        self._request_seq = itertools.count()
        self._runner: Optional[asyncio.Task] = None
        self._wake = asyncio.Event()
        # admitter counters (bench / tests)
        self.admitted_total = 0
        self.shed_total = 0
        # token rows the model was given: a prompt's length per prefill,
        # max_slots per decode step (inactive rows are computed too)
        self.rows_stepped_total = 0
        self.decode_steps_total = 0
        # of those, launched while the one before had not been synced
        self.steps_launched_ahead_total = 0
        # drafting deployments: live rows a step drafted for, drafts the
        # main model accepted, tokens delivered from decode steps, and rows
        # stepped once more after their budget was met (the host hears of
        # it a step late)
        self.spec_drafted_total = 0
        self.spec_accepted_total = 0
        self.spec_tokens_emitted_total = 0
        self.spec_wasted_row_steps_total = 0
        # block-diffusion deployments, by ``block_diffusion.OUT_FIELDS``:
        # forwards of live rows, of those commits, tokens unmasked, of those
        # by the threshold, blocks committed (= commit forwards), rows
        # stepped once more after their budget was met
        self.diffusion_forwards_total = 0
        self.diffusion_commit_forwards_total = 0
        self.diffusion_tokens_unmasked_total = 0
        self.diffusion_threshold_transfers_total = 0
        self.diffusion_tokens_emitted_total = 0
        self.diffusion_wasted_row_steps_total = 0
        # K/V configs, every-row steps, summed over layers: keys the live
        # rows' queries could see, and keys the step's attention FETCHED for
        # all rows, free ones too (whole blocks up to each row's last
        # visible key where the kernel runs, the whole slab where XLA's body
        # does: ``ops/kv_decode_attention.keys_read``).  From the positions
        # the host feeds the step, or from the stateful step's ``outs``
        self.kv_keys_visible_step = 0
        self.kv_keys_read_step = 0

    # -- client side -----------------------------------------------------
    async def stream(self, prompt: List[int], max_new_tokens: int = 16):
        """Async generator of generated token ids for one request.

        Captures the traffic plane's per-request deadline (when the
        request came through a TrafficConfig'd deployment) at submit
        time — the contextvar is only live in the submitting task — so
        the slot admitter can order prefill admissions EDF and shed
        requests whose SLO already lapsed in the replica's own queue.
        """
        from ray_tpu.serve.traffic.config import get_request_deadline

        if self._runner is None or self._runner.done():
            self._runner = asyncio.get_running_loop().create_task(
                self._run()
            )
        q: asyncio.Queue = asyncio.Queue()
        deadline = get_request_deadline()
        # the request's span is finished by hand: a `with` around the
        # yields below would write the context variable of whoever
        # drives this generator
        request = tracing.span(
            "llm.request", prompt_len=len(prompt),
            max_new_tokens=int(max_new_tokens),
        ) if tracing.enabled() else None
        heapq.heappush(self._pending, (
            deadline if deadline is not None else float("inf"),
            next(self._admit_seq),
            _Request(list(prompt), int(max_new_tokens), q,
                     request.trace_id if request else None,
                     next(self._request_seq)),
        ))
        self._wake.set()
        try:
            while True:
                tok = await q.get()
                if tok is _END:
                    return
                if isinstance(tok, Exception):
                    raise tok
                yield tok
        finally:
            if request is not None:
                request.finish()

    async def cache_counters(self) -> dict:
        """Every running total the donated cache carries, in ONE pass
        under the cache's lock (one device-to-host copy per counter,
        between two steps), as one flat dict.  An expert config:
        ``moe_expert_tokens`` (expert layers, experts held) rows each
        expert of each layer computed, ``moe_experts_touched_total`` and
        ``moe_layer_steps_total`` summed over the layers,
        ``moe_routed_pairs_total`` (token, choice) pairs the router made
        (token rows the model was given x expert layers x experts per
        token), ``moe_held_pairs_total`` of them on an expert held here
        and, where the router has identity experts (``zero_experts``),
        ``moe_zero_choices_total`` of them on those,
        ``moe_rows_gathered_total`` (token, choice) rows the expert layers
        gathered and handed the kernel: every routed pair where every
        expert is held, else blocks x R of ``llama._ffn`` — held over
        gathered is how full the blocks ran, gathered over routed what
        gathering the held rows alone saved; every row of a
        decode step routes, so rows of slots the engine holds no request
        in are counted too: these count what the kernel did, not what
        clients received.  A latent-attention config: keys the indexer
        saw (``dsa_visible_*``) and keys attention was given
        (``dsa_selected_*``), summed over every (layer, row, query),
        decode steps (``_step``) and prefills (``_run``) apart, and
        ``dsa_read_step``, latent rows the decode steps' attention
        fetched from the cache: the selected ones where they were
        gathered, every row of the blocks up to ``pos`` where they were
        streamed (``ops/latent_decode_attention.py``), and
        ``dsa_read_run``, the (query, key) pairs the prefills' attention
        computed scores for, a head: over ``dsa_visible_run`` the body's
        over-compute, which says which body the prefills took
        (``ops/latent_prefill_attention.py``), and ``topk_mask``, which
        body finds the indexer's top-k at the decode step's shape —
        ``counted`` (the kernel) or ``sorted`` — as
        ``ops/topk_mask.py:implementation`` reads it from (rows, cache
        length, ``index_topk``); a prefill's blocks ask it again by their
        own shapes.  Every latent config: ``latent_prefill_attention``, the
        body a run of at least ``MIN_TILES`` whole tiles takes at its head
        widths (``flash`` — the kernel — or ``blocked``:
        ``ops/latent_prefill_attention.py:implementation``; a shorter run
        or one that is no whole tiles keeps ``blocked``).
        Latent attention
        without an indexer: ``mla_keys_visible_step`` (keys a step's
        rows could see — a row's last query's, the others see prefixes —
        over every layer and row) and ``mla_keys_read_step`` (latent rows
        fetched for them: a row's blocks once for all its queries) — over
        both attentions of every layer where the block is the
        shortcut-connected double layer.  Sets the gauges of both.  A
        config with linear-attention layers:
        ``llama.GDN_COUNTS``, all over (row, linear layer) —
        ``gdn_rows_stepped`` one-token updates of decode steps,
        ``gdn_tokens_scanned`` / ``gdn_tokens_padded`` prompt tokens the
        prefills' chunked rule ran and the identity positions that filled
        their last chunks, ``gdn_state_bytes_step`` bytes of recurrent state
        the decode steps read and wrote — and ``gated_delta_step``, which
        body updates the state at the decode step's shape: ``in_place``
        (the kernel, one pass over the layer's rows where they lie) or
        ``xla``, as ``ops/gated_delta.py:implementation`` reads it from
        (rows, heads, d_k, d_v) — and ``gated_delta_scan``, which body a
        prefill's chunked rule traces: ``kernel`` (``kda_chunk``, a chunk in
        fast memory) or ``xla``, as ``ops/gated_delta.py:scan_implementation``
        reads it from (heads, d_k, d_v, chunk, a decay a channel or
        not).  A config with window layers beside full
        ones (``layer_types``: ``sliding_attention``), per kind (``full_`` /
        ``swa_``) and summed over the kind's layers, a head:
        ``*_keys_visible_step`` keys a decode step's rows could see (a window
        layer's at most ``window``) and ``*_keys_read_step`` keys fetched for
        them (whole blocks of 128 up to each row's last key under the kernel,
        the slab under XLA's body; a window layer's slots are one block);
        ``*_pairs_visible_run`` (query, key) pairs inside the prefills' masks —
        the causal triangle, the window's band — and ``*_pairs_read_run``
        pairs their attention computed scores for (whole live tiles under
        ``ops/kv_prefill_attention.py``'s kernel): read over visible is each
        body's over-compute; and which bodies the shapes chose,
        ``kv_prefill_attention`` (``flash`` | ``dense``), ``kv_prefill_tiles``
        ({``full``, ``swa``}: (queries, keys, query heads) a grid step of the
        prefill kernel takes at these heads: a tile pair of the online
        softmax without a window, a step's queries and the band a sub-tile
        of them meets with one, ``ops/kv_prefill_attention.py:tiles``) and
        ``kv_decode_attention`` ({``full``, ``swa``}: ``streamed`` |
        ``slab``).  These count on the device (``attn_keys`` rides the
        cache): ``kv_keys_*_step`` are not reported for such a config.  A
        looped config (``loop_passes`` > 1): ``_loop_totals``."""
        import numpy as np

        from ray_tpu.models.llama import wide_total
        from ray_tpu.ops import (
            gated_delta,
            grouped_matmul,
            latent_prefill_attention,
            topk_mask,
        )

        out = {}
        names = [k for k in self.cache
                 if k.startswith(("moe_", "dsa_", "mla_", "gdn_counts", "attn_keys",
                                  "loop_"))]
        if not names:
            return out
        async with self._cache_lock:
            host = {k: np.asarray(self.cache[k]) for k in names}
        if "moe_expert_tokens" in host:
            tokens = host["moe_expert_tokens"]
            touched = int(host["moe_experts_touched"].sum())
            # (expert layers,) calls; where not every row has a group
            # (expert layers, 2): the calls, the row tiles gathered
            calls = host["moe_layer_steps"].astype(np.int64)
            steps = int(calls.sum() if calls.ndim == 1 else calls[:, 0].sum())
            if steps:
                _MOE_TOUCHED_MEAN.set(touched / steps)
                _MOE_LOAD_MAX_OVER_MEAN.set(float(tokens.max() / tokens.mean()))
            # a module that is not drafting is given no row
            idle = 0 if self.speculative else self.config.mtp_layers
            routed = (self.rows_stepped_total * (tokens.shape[0] - idle)
                      * self.config.experts_per_token)
            if self.config.experts_held and routed:
                _MOE_HELD_SHARE.set(float(tokens.sum()) / routed)
            out.update({
                "grouped_matmul": grouped_matmul.implementation(),
                "moe_expert_tokens": tokens.tolist(),
                "moe_experts_touched_total": touched,
                "moe_layer_steps_total": steps,
                "moe_routed_pairs_total": int(routed),
                "moe_held_pairs_total": int(tokens.sum()),
                "moe_rows_gathered_total": int(routed) if calls.ndim == 1 else int(
                    calls[:, 1].sum()) * grouped_matmul.ROW_TILE,
            })
            if "moe_zero_choices" in host:
                out["moe_zero_choices_total"] = int(host["moe_zero_choices"].sum())
        if "dsa_keys" in host:
            keys = host["dsa_keys"]       # (L, visible|selected|read, run|step, 2)
            dsa = {
                f"dsa_{what}_{kind}": wide_total(keys[:, i, j])
                for i, what in enumerate(("visible", "selected", "read"))
                for j, kind in enumerate(("run", "step"))
            }
            seen = dsa["dsa_visible_run"] + dsa["dsa_visible_step"]
            if seen:
                _DSA_SELECTED_SHARE.set(
                    (dsa["dsa_selected_run"] + dsa["dsa_selected_step"]) / seen
                )
            out.update(dsa)
            # no ``dsa_`` name: those are running totals, differenced over a window
            out["topk_mask"] = topk_mask.implementation(
                self.max_slots, self.cache_len, self.config.index_topk
            )
        if "mla_keys" in host:
            keys = host["mla_keys"]       # (layers, visible|read, 2)
            out["mla_keys_visible_step"] = wide_total(keys[:, 0])
            out["mla_keys_read_step"] = wide_total(keys[:, 1])
        if self.config.latent:
            c = self.config
            # the body a long enough run of whole tiles takes at these head widths
            out["latent_prefill_attention"] = latent_prefill_attention.implementation(
                latent_prefill_attention.MIN_TILES * latent_prefill_attention.TILE,
                c.qk_nope_head_dim + c.qk_rope_head_dim, c.v_head_dim)
        if "attn_keys" in host:
            from ray_tpu.ops import kv_decode_attention, kv_prefill_attention

            c = self.config
            keys = host["attn_keys"]      # (full|window, visible|read, run|step, 2)
            for i, kind in enumerate(("full", "swa")):
                for j, what in enumerate(("visible", "read")):
                    out[f"{kind}_pairs_{what}_run"] = wide_total(keys[i, j, 0])
                    out[f"{kind}_keys_{what}_step"] = wide_total(keys[i, j, 1])
            # which bodies the two programs were traced with, by their shapes
            out["kv_prefill_attention"] = kv_prefill_attention.implementation(
                c.head_dim, c.value_dim)
            out["kv_prefill_tiles"] = {
                kind: kv_prefill_attention.tiles(c.num_heads // heads, window)
                for kind, heads, window in (
                    ("full", c.num_kv_heads, 0),
                    ("swa", c.sliding.num_kv_heads, c.sliding.window))
            }
            out["kv_decode_attention"] = {
                kind: kv_decode_attention.implementation(
                    length, c.head_dim, kv_heads=heads, v_head_dim=c.value_dim)
                for kind, length, heads in (
                    ("full", self.cache_len, c.num_kv_heads),
                    ("swa", c.sliding.window, c.sliding.num_kv_heads))
            }
        if "loop_passes" in host:
            out.update(self._loop_totals(host))
        if "gdn_counts" in host:
            from ray_tpu.models.llama import GDN_COUNTS

            out.update(
                (name, wide_total(host["gdn_counts"][i]))
                for i, name in enumerate(GDN_COUNTS)
            )
            c = self.config
            # no ``gdn_`` name: those are running totals, differenced over a window
            out["gated_delta_step"] = gated_delta.implementation(
                self.max_slots, c.linear_num_heads, c.linear_key_head_dim,
                c.linear_value_head_dim,
            )
            out["gated_delta_scan"] = gated_delta.scan_implementation(
                c.linear_num_heads, c.linear_key_head_dim, c.linear_value_head_dim,
                c.linear_chunk, c.linear_kind == "kda",
            )
        return out

    @staticmethod
    def _loop_totals(cache) -> dict:
        """A looped config's running totals as the cache carries them
        (``llama.init_cache``): ``loop_passes`` passes run, summed over every
        (row, call) of the two programs — ``loop_passes`` a row-step while
        every token runs every pass — ``loop_row_steps`` those (row, call)s,
        and ``loop_exit_mass`` the exit distribution's mass before the last
        pass, summed over them: the passes' share a threshold under 1 could
        save with these weights."""
        import numpy as np

        passes, row_steps = (int(x) for x in np.asarray(cache["loop_passes"]))
        return {"loop_passes": passes, "loop_row_steps": row_steps,
                "loop_exit_mass": float(np.asarray(cache["loop_exit_mass"]))}

    # -- engine loop -----------------------------------------------------
    async def _run(self):
        while True:
            try:
                await self._run_inner()
            except Exception as e:  # noqa: BLE001 — delivered to clients
                import logging

                import jax.numpy as jnp

                logging.getLogger(__name__).exception(
                    "LLM engine step failed; failing active requests"
                )
                # fail every active stream (a row is in its slot, in a
                # step in flight, or both) and drain pending admissions;
                # drop the steps in flight and their tokens with the
                # cache (a donated buffer may be stale after a mid-step
                # failure) and keep serving
                live = {id(s.queue): s.queue for s in self.slots if s}
                for step in self._flying:
                    live.update((id(r[1].queue), r[1].queue) for r in step.rows)
                while self._pending:
                    q = heapq.heappop(self._pending)[2].queue
                    live[id(q)] = q
                for q in live.values():
                    await q.put(e)
                    await q.put(_END)
                self.slots = [None] * self.max_slots
                self._flying.clear()
                self._tokens = jnp.zeros((self.max_slots,), jnp.int32)
                if self.stateful:
                    self._spec = self._programs.init_state(
                        self.config, self.max_slots, *self._step_options.values()
                    )
                self.cache = self._llama.init_cache(
                    self.config, self.max_slots, self.cache_len
                )

    async def _admit(self, life: Optional[tracing.Span]) -> int:
        """Admit pending requests into free slots (prefill), EDF: the
        earliest-deadline waiter takes the free cache row, and a waiter
        whose deadline lapsed in this queue is shed — prefill compute
        for a response the client already gave up on would only delay
        every live slot's next token.  Returns the requests prefilled.

        The first prefill is launched behind the decode step in flight,
        whose tokens are delivered before this waits for the prefill's
        own: the pipeline is empty from there on."""
        import jax.numpy as jnp

        llama = self._llama
        cfg = self.config
        prefilled = 0
        while self._pending and None in self.slots:
            deadline, _, req = heapq.heappop(self._pending)
            q, prompt, max_new = req.queue, req.prompt, req.max_new
            waited_ms = (time.monotonic() - req.pushed) * 1e3
            if deadline <= time.monotonic():
                from ray_tpu.serve.traffic.config import (
                    RequestShedError,
                )

                self.shed_total += 1
                _QUEUE_WAIT_MS.observe(waited_ms, {"outcome": "shed"})
                await q.put(RequestShedError(
                    "SLO budget exhausted before a decode slot "
                    "freed up"
                ))
                await q.put(_END)
                continue
            self.admitted_total += 1
            S0 = len(prompt)
            if max_new > 0 and (
                S0 + max_new > self.max_len
                or S0 > self.max_prompt_len
                or S0 == 0
            ):
                _QUEUE_WAIT_MS.observe(waited_ms, {"outcome": "rejected"})
                await q.put(ValueError(
                    f"prompt of {S0} tokens + {max_new} new exceeds "
                    f"max_len {self.max_len} (or prompt cap "
                    f"{self.max_prompt_len})"
                ))
                await q.put(_END)
                continue
            _QUEUE_WAIT_MS.observe(waited_ms, {"outcome": "admitted"})
            if max_new <= 0:  # exact budget: zero tokens requested
                await q.put(_END)
                continue
            slot = self.slots.index(None)
            with tracing.Span(
                "llm.prefill", (req.trace_id, tracing.current()[1]),
                {"slot": slot, "prompt_len": S0,
                 "rows_stalled": self.max_slots - self.slots.count(None)},
            ) if life is not None else _OFF:
                toks = jnp.asarray([prompt], jnp.int32)
                row = jnp.int32(slot)

                def _prefill():
                    if self.stateful:
                        return self._programs.prefill_into_slot(
                            self.params, toks, self.cache, row, self._spec,
                            self._key, jnp.int32(req.number),
                            jnp.int32(max_new), cfg, self.temperature,
                            **self._step_options,
                        )[:3]
                    logits, cache = llama.prefill_into_slot(
                        self.params, toks, self.cache, row, cfg,
                    )
                    if self.temperature > 0.0:
                        return llama.sample_rows(
                            logits, self._key, jnp.asarray([req.number]),
                            jnp.asarray([S0]), temperature=self.temperature,
                        )[0], cache
                    return jnp.argmax(logits[0]), cache

                async with self._cache_lock:
                    first, self.cache, *spec = await asyncio.to_thread(_prefill)
                    # a block-diffusion prefill is given the prompt's whole blocks
                    self.rows_stepped_total += (
                        S0 - S0 % self.diffusion_block if self.diffusion_block else S0
                    )
                if spec:  # the row's first token is in its state
                    self._spec, = spec
                elif max_new > 1:
                    self._tokens = llama.set_row(self._tokens, row, first)
                if self._flying:
                    await self._deliver()
                first = int(first)  # -1: this prefill gives no token (diffusion)
                if first >= 0:
                    await q.put(first)
            prefilled += 1
            if first >= 0:
                _ENGINE_TTFT_MS.observe((time.monotonic() - req.pushed) * 1e3)
                max_new -= 1
            if max_new <= 0:
                await q.put(_END)
                continue
            self.slots[slot] = _Slot(
                queue=q, pos=S0, remaining=max_new,
                max_pos=self.max_len - 1, request=req.number,
                pushed=None if first >= 0 else req.pushed,
            )
        return prefilled

    async def _launch(self, life: Optional[tracing.Span],
                      active: List[int]) -> None:
        """Call one fused decode step over ALL slots (inactive rows decode
        into their own rows harmlessly; the shape stays constant) on the
        tokens the last one left on the device, and put it in flight.
        Nothing here waits for the device."""
        import jax.numpy as jnp
        import numpy as np

        llama = self._llama
        cfg = self.config
        if self.stateful:
            await self._launch_stateful(life, active)
            return
        sampled = self.temperature > 0.0
        with _part(life, "llm.step.build"):
            pos = np.zeros((self.max_slots,), np.int32)
            request = np.zeros((self.max_slots,), np.int32)
            for i in active:
                pos[i] = self.slots[i].pos
                request[i] = self.slots[i].request
            if not (cfg.latent or cfg.sliding):  # those count on the device
                self._count_kv_keys(int(pos[active].sum()) + len(active), pos)
        with _part(life, "llm.step.dispatch") as dispatch:

            def _step(tokens=self._tokens):
                p = jnp.asarray(pos)
                # what is left of the dispatch after the hop to the
                # thread and the copy
                with _part(dispatch, "llm.step.launch"):
                    logits, cache = llama.decode_step_rowwise(
                        self.params, tokens, self.cache, p, cfg,
                    )
                    if sampled:
                        return llama.sample_rows(
                            logits, self._key, jnp.asarray(request), p + 1,
                            temperature=self.temperature,
                        ), cache
                    return jnp.argmax(logits, axis=-1), cache

            loop = None
            async with self._cache_lock:
                self._tokens, self.cache = await asyncio.to_thread(_step)
                self.rows_stepped_total += self.max_slots
                if life is not None and self._loop_copy is not None:
                    loop = self._loop_copy(
                        {k: self.cache[k] for k in ("loop_passes", "loop_exit_mass")})
            self.decode_steps_total += 1
            if self._flying:
                self.steps_launched_ahead_total += 1
            # a row's last step is known now, by counts: its slot is
            # free for the next prefill, which the device runs behind
            # this step
            rows = []
            for i in active:
                s = self.slots[i]
                s.pos += 1
                s.remaining -= 1
                last = s.remaining <= 0 or s.pos >= s.max_pos
                rows.append((i, s, last))
                if last:
                    self.slots[i] = None
            self._flying.append(_Step(self._tokens, rows, life, loop))

    def _count_kv_keys(self, visible: int, last) -> None:
        """One every-row step into ``kv_keys_visible_step`` (``visible``:
        keys its live rows could see) and ``kv_keys_read_step`` (``last``
        (max_slots,): EVERY row's last visible key as the step was given
        it; a free slot's is 0 in a one-token step)."""
        from ray_tpu.ops.kv_decode_attention import keys_read

        c = self.config
        read = keys_read(last, self.cache_len, c.head_dim, c.sliding_window)
        # the layers that keep K and V: all, or a hybrid config's full ones
        self.kv_keys_visible_step += visible * c.kv_layers
        self.kv_keys_read_step += int(read.sum()) * c.kv_layers

    async def _launch_stateful(self, life: Optional[tracing.Span],
                               active: List[int]) -> None:
        """``_launch`` where the step keeps the rows' state on the device
        and yields 0..k ids a row — a speculative step (``models/mtp.py``),
        a block-diffusion step (``models/block_diffusion.py``): one step
        over all slots on the state the last one left there (positions,
        tokens or blocks, what each row has left to emit).  The host builds
        no array and waits for nothing; its ``llm.step.build`` is the list
        of the rows it will hand tokens to (a step's life keeps its five
        parts: PERF.md section 3)."""
        with _part(life, "llm.step.build"):
            # which rows end with this step only its tokens will tell
            rows = [(i, self.slots[i]) for i in active]
        with _part(life, "llm.step.dispatch") as dispatch:

            def _step(state=self._spec):
                with _part(dispatch, "llm.step.launch"):
                    return self._programs.decode_step_rowwise(
                        self.params, state, self.cache, self._key,
                        self.config, self.temperature, **self._step_options,
                    )[:3]

            async with self._cache_lock:
                outs, self._spec, self.cache = await asyncio.to_thread(_step)
                self.rows_stepped_total += self._step_ids * self.max_slots
            self.decode_steps_total += 1
            if self._flying:
                self.steps_launched_ahead_total += 1
            if life is not None and self.speculative:
                life.attrs["drafted"] = len(active)
            self._flying.append(_Step(outs, rows, life))

    async def _hand_out(self, step: _Step, outs) -> None:
        """A stateful step's tokens to their queues: 0..k a row, in order,
        never one past the request's budget; a row whose budget is met is
        retired HERE, a step later than its last token was made.  ``outs``
        (max_slots, k + extras): k ids, how many of them count, and what
        the step's kind tallies (``_tally``)."""
        import numpy as np

        k = self._step_ids
        emitted = wasted = 0
        live = []
        for i, slot in step.rows:
            if slot.remaining <= 0:
                # retired when the step before was delivered; this one
                # had been launched by then
                wasted += 1
                continue
            live.append(i)
            n = min(int(outs[i, k]), slot.remaining)
            for tok in outs[i, :n]:
                await slot.queue.put(int(tok))
            if n and slot.pushed is not None:  # its prefill gave no token
                _ENGINE_TTFT_MS.observe((time.monotonic() - slot.pushed) * 1e3)
                slot.pushed = None
            slot.remaining -= n
            emitted += n
            if slot.remaining <= 0:
                await slot.queue.put(_END)
                if self.slots[i] is slot:
                    self.slots[i] = None
        extras = outs[np.asarray(live, np.int64), k + 1:].sum(0)
        if self.diffusion_block:
            # the live rows' visible keys; EVERY row's last visible key, a
            # retired row's stale one too
            fields = self._programs.OUT_FIELDS
            self._count_kv_keys(int(extras[fields.index("visible") - 1]),
                                outs[:, k + fields.index("last")])
        attrs = self._tally(emitted, wasted, extras, len(live))
        if step.span is not None:
            step.span.attrs.update(emitted=emitted, **attrs)

    def _tally(self, emitted: int, wasted: int, extras, rows: int) -> dict:
        """One delivered stateful step into the running totals of its kind;
        -> the step's span attributes.  ``extras``: the columns of ``outs``
        behind the count, summed over the ``rows`` rows the host still held
        a request in (drafting: accepted?; block diffusion:
        ``block_diffusion.OUT_FIELDS`` behind ``count``)."""
        if self.speculative:
            accepted = int(extras[0])
            self.spec_wasted_row_steps_total += wasted
            self.spec_drafted_total += rows
            self.spec_tokens_emitted_total += emitted
            self.spec_accepted_total += accepted
            if self.spec_drafted_total:
                _SPEC_ACCEPTANCE.set(self.spec_accepted_total / self.spec_drafted_total)
            return {"accepted": accepted}
        forwards, committed, unmasked, by_threshold = (int(x) for x in extras[:4])
        self.diffusion_wasted_row_steps_total += wasted
        self.diffusion_forwards_total += forwards
        self.diffusion_commit_forwards_total += committed
        self.diffusion_tokens_unmasked_total += unmasked
        self.diffusion_threshold_transfers_total += by_threshold
        self.diffusion_tokens_emitted_total += emitted
        if self.diffusion_forwards_total:
            _DIFF_TOKENS_PER_FORWARD.set(
                self.diffusion_tokens_emitted_total / self.diffusion_forwards_total
            )
        return {"committed": committed, "unmasked": unmasked}

    async def _deliver(self) -> None:
        """Wait for the oldest step in flight, put its tokens on their
        queues and give their consumers one pass of the loop."""
        import numpy as np

        step = self._flying[0]  # in flight until synced: _run fails its rows
        with _part(step.span, "llm.step.sync"):
            nxt = np.asarray(step.tokens)
        self._flying.popleft()
        with _part(step.span, "llm.step.deliver"):
            if self.stateful:
                await self._hand_out(step, nxt)
            else:
                for i, slot, last in step.rows:
                    await slot.queue.put(int(nxt[i]))
                    if last:
                        await slot.queue.put(_END)
        # consumers take their tokens and the transport sends them while
        # the device computes the step launched before this one's sync
        with _part(step.span, "llm.step.yield"):
            await asyncio.sleep(0)
        if step.span is not None:
            if step.loop is not None:
                step.span.attrs.update(self._loop_totals(step.loop))
            step.span.finish()

    async def _run_inner(self):
        while True:
            if not (self._pending or self._flying or any(self.slots)):
                # idle: park until a request arrives
                self._wake.clear()
                with tracing.root("llm.idle") if tracing.enabled() else _OFF:
                    await self._wake.wait()
                continue
            # one llm.step span is the life of one decode step, from the
            # admissions before its launch to the yield after its
            # delivery an iteration later: consecutive ones overlap, so
            # they are finished by hand (util/tracing.py; names and the
            # metric each is read by: PERF.md section 3)
            life = tracing.root(
                "llm.step", step=self.decode_steps_total + 1,
            ) if tracing.enabled() else None
            admitted = 0
            if self._pending and None in self.slots:
                with _part(life, "llm.step.admit"):
                    admitted = await self._admit(life)
            active = [i for i, s in enumerate(self.slots) if s is not None]
            # the step launched by the last iteration, unless an
            # admission has just delivered it
            ahead = bool(self._flying)
            if life is not None:
                life.attrs.update(
                    active=len(active), admitted=admitted, ahead=ahead,
                )
            if active:
                await self._launch(life, active)
            elif life is not None:
                life.finish()  # it admitted at most; there is no step
            if ahead:
                await self._deliver()


def _tree_bytes(tree) -> int:
    """Bytes the arrays of a pytree hold (no copy, no device sync)."""
    import jax

    return sum(getattr(x, "nbytes", 0) for x in jax.tree.leaves(tree))


@serve.deployment
class LlamaDeployment:
    """Decode replica: tiny-config by default, or real weights via a
    ``weights_ref`` (object-store ref) / ``weights_loader`` callable.
    ``temperature`` (0: greedy), ``speculative_tokens`` (0, or 1 with a
    model that has a multi-token-prediction module), ``diffusion_block``
    (0, or the block length of a model that generates by diffusion over
    blocks, with ``denoising_steps`` (default: the block's length) and
    ``confidence_threshold``; the vocabulary's last row stands for the MASK
    token: ``models/block_diffusion.py``) and ``seed`` (of
    the default weights and of every draw) are the deployment's: see
    ``LLMEngine``."""

    def __init__(self, config=None, weights_ref=None, weights_loader=None,
                 max_slots: int = 4, max_len: int = 256,
                 max_prompt_len: Optional[int] = None, seed: int = 0,
                 speculative_tokens: int = 0, temperature: float = 0.0,
                 diffusion_block: int = 0,
                 denoising_steps: Optional[int] = None,
                 confidence_threshold: float = 0.9):
        import jax

        from ray_tpu.models import llama
        from ray_tpu.util.compile_cache import CompileLog

        self._compiles = CompileLog()
        self.config = config or llama.LlamaConfig.tiny()
        tpu.open_leased_chips()
        with tracing.startup("llm.start.weights") as started:
            if weights_ref is not None:
                import ray_tpu

                params = ray_tpu.get(weights_ref)
            elif weights_loader is not None:
                params = weights_loader()
            else:
                params = llama.init(jax.random.key(seed), self.config)
            started.attrs["param_bytes"] = _tree_bytes(params)
        # cache allocation, the rows' state, the programs' adapters
        with tracing.startup("llm.start.engine") as started:
            self.engine = LLMEngine(
                params, self.config, max_slots=max_slots, max_len=max_len,
                max_prompt_len=max_prompt_len,
                speculative_tokens=speculative_tokens,
                temperature=temperature, seed=seed,
                diffusion_block=diffusion_block,
                denoising_steps=denoising_steps,
                confidence_threshold=confidence_threshold,
            )
            # the engine's: under the block mask where it generates by diffusion
            self.config = self.engine.config
            started.attrs["cache_bytes"] = _tree_bytes(self.engine.cache)

    async def stats(self) -> dict:
        """What this replica runs on and what it has cost so far: the
        jax device it holds, its XLA compiles (all of them, and how
        many versions of the two engine programs — prefill compiles
        once per distinct prompt length), peak device memory where the
        backend reports it, the admitter's counters, and the decode
        steps launched (``decode_steps_total``; of those,
        ``steps_launched_ahead_total`` while the one before was still
        in flight: all but the first after each admission or idle time).

        An expert config adds which grouped-matmul body its programs
        were traced with (``grouped_matmul``) and the routing counters
        the cache carries (``LLMEngine.cache_counters``; one device-to-
        host copy here, none in any step): ``moe_expert_tokens``,
        ``moe_experts_touched_total``, ``moe_layer_steps_total``,
        ``moe_routed_pairs_total``, ``moe_held_pairs_total``,
        ``moe_rows_gathered_total`` and, with identity experts,
        ``moe_zero_choices_total``.  The
        two gauges ``llm_moe_experts_touched_mean`` and
        ``llm_moe_expert_load_max_over_mean`` are set from them there
        (this class travels to its replica by value, so it names no
        metric object itself).  A latent-attention config adds
        ``dsa_visible_step`` / ``dsa_selected_step`` (decode steps) and
        ``dsa_visible_run`` / ``dsa_selected_run`` (prefills): keys its
        indexer saw and keys attention was given, over every (layer,
        row, query) of the replica's life, ``dsa_read_step`` (latent
        rows the decode steps fetched to attend to them: equal to
        ``dsa_selected_step`` where rows are gathered, the streamed
        blocks' rows otherwise), ``dsa_read_run`` ((query, key) pairs the
        prefills computed scores for: whole live tiles under the prefill
        kernel), ``topk_mask`` (which body finds the top-k at the decode
        step's shape: ``counted`` or ``sorted``), and the gauges
        ``llm_dsa_selected_share`` and (experts held here)
        ``llm_moe_held_assignment_share``.  Latent attention without an
        indexer: ``mla_keys_visible_step`` / ``mla_keys_read_step``; with or
        without one, ``latent_prefill_attention`` (which body a prefill of
        whole tiles, 2,048 tokens or more, takes: ``flash`` or ``blocked``).  A
        drafting deployment: ``spec_drafted_total`` (live rows its steps
        drafted for), ``spec_accepted_total``, ``spec_tokens_emitted_total``
        (tokens delivered from decode steps: 1 + accepted a live row-step,
        less what fell past a budget), ``spec_wasted_row_steps_total`` (rows
        stepped once more after their budget was met), and the gauge
        ``llm_spec_acceptance_rate``; ``programs`` then counts the drafting
        versions of the two programs (``models/mtp.py``).  A block-diffusion
        deployment: ``diffusion_forwards_total`` (forwards of live rows),
        ``diffusion_commit_forwards_total`` = ``diffusion_blocks_committed_
        total`` (of those, the ones that found a block without MASK and
        committed it), ``diffusion_tokens_unmasked_total`` (of those,
        ``diffusion_threshold_transfers_total`` by the confidence
        threshold), ``diffusion_tokens_emitted_total``, ``diffusion_wasted_
        row_steps_total``, and the gauge ``llm_diffusion_tokens_per_row_
        forward``; ``programs`` then counts ``models/block_diffusion.py``'s
        versions.  A K/V config (one-token or block-diffusion steps):
        ``kv_keys_visible_step`` (keys the live rows' queries could see,
        over layers, rows and steps: a row's block sees the same keys) and
        ``kv_keys_read_step`` (keys the steps' attention fetched, for every
        row, free slots too: whole blocks up to each row's last visible key
        where ``ops/kv_decode_attention.py``'s kernel runs, the whole slab
        where XLA's body does; read / visible is the over-read); over every
        (pass, layer) of a looped config (``loop_passes``: ``kv_layers`` is
        the cache's, 192 for Ouro-2.6B's 48), which adds ``loop_passes``,
        ``loop_row_steps`` and ``loop_exit_mass`` (``LLMEngine._loop_totals``;
        a traced step's ``llm.step`` span carries the three as that step
        left them); over the
        FULL layers only where the config has ``layer_types``, which adds
        the recurrent layers' ``gdn_rows_stepped``, ``gdn_tokens_scanned``,
        ``gdn_tokens_padded`` and ``gdn_state_bytes_step``
        (``LLMEngine.cache_counters``).  A config with window layers beside
        full ones reports, in their place, ``full_`` / ``swa_`` x
        ``keys_visible_step`` / ``keys_read_step`` / ``pairs_visible_run`` /
        ``pairs_read_run`` and the bodies chosen, ``kv_prefill_attention`` and
        ``kv_decode_attention`` (``LLMEngine.cache_counters``).
        ``cache_bytes``
        is what the cache holds, by entry (the module's layer is one of
        ``ckv``'s; a window-and-full config's ``k`` / ``v`` are its full
        layers' at ``max_len``, ``swa_k`` / ``swa_v`` its window layers' at
        ``window`` slots)."""
        import jax

        from ray_tpu.models import llama

        devices = jax.devices()
        dev = devices[0]
        mem = dev.memory_stats() or {}
        counters = await self.engine.cache_counters()
        programs = self.engine._programs  # llama's, or a stateful step's
        if self.engine.speculative:
            counters.update({
                k: getattr(self.engine, k) for k in (
                    "spec_drafted_total", "spec_accepted_total",
                    "spec_tokens_emitted_total", "spec_wasted_row_steps_total",
                )
            })
        if self.engine.diffusion_block:
            counters.update({
                k: getattr(self.engine, k) for k in (
                    "diffusion_forwards_total", "diffusion_commit_forwards_total",
                    "diffusion_tokens_unmasked_total",
                    "diffusion_threshold_transfers_total",
                    "diffusion_tokens_emitted_total",
                    "diffusion_wasted_row_steps_total",
                )
            })
            counters["diffusion_blocks_committed_total"] = (
                self.engine.diffusion_commit_forwards_total
            )
        if not (self.engine.config.latent or self.engine.speculative
                or self.engine.config.sliding):
            counters["kv_keys_visible_step"] = self.engine.kv_keys_visible_step
            counters["kv_keys_read_step"] = self.engine.kv_keys_read_step
        return {
            **counters,
            # what the cache holds, entry by entry (``k``/``v``, or a
            # latent config's ``ckv``/``ik``; the counters beside them)
            "cache_bytes": {
                k: int(v.size) * v.dtype.itemsize
                for k, v in self.engine.cache.items()
            },
            "max_slots": self.engine.max_slots,
            "max_len": self.engine.max_len,
            # "serial" | "shortcut": which block body the two programs run
            "block_form": self.engine.config.block_form,
            "platform": dev.platform,
            "device_kind": dev.device_kind,
            "device_count": len(devices),
            "compiles": self._compiles.snapshot(),
            "programs": {
                "prefill_into_slot": programs.prefill_into_slot._cache_size(),
                "decode_step_rowwise": programs.decode_step_rowwise._cache_size(),
            },
            "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
            "admitted_total": self.engine.admitted_total,
            "shed_total": self.engine.shed_total,
            "rows_stepped_total": self.engine.rows_stepped_total,
            "decode_steps_total": self.engine.decode_steps_total,
            "steps_launched_ahead_total":
                self.engine.steps_launched_ahead_total,
        }

    def update_weights(self, params) -> bool:
        """Swap the decode params in place — the serve weight-push path
        (`serve.weights.push_weights` fans new weights to every replica
        via one collective broadcast, optionally block-quantized).
        In-flight decodes pick the new params up at the next step the
        engine launches (one is in flight on the old ones meanwhile);
        the KV cache is content not weights, so it stays valid."""
        self.engine.params = params
        return True

    async def generate(self, prompt: List[int], max_new_tokens: int = 16):
        """Streaming generation (use handle.options(stream=True))."""
        async for tok in self.engine.stream(prompt, max_new_tokens):
            yield tok

    async def generate_all(self, prompt: List[int],
                           max_new_tokens: int = 16) -> List[int]:
        """Unary convenience: the full generated id list."""
        return [
            tok async for tok in self.engine.stream(prompt, max_new_tokens)
        ]
