"""Pipeline-parallel composition for the transformer families.

Cuts the scan-stacked GPT-2 / Llama blocks into `pp` stages.  The cut
itself — which params belong to a stage, what the per-stage step
functions are — is expressed ONCE, as a :class:`ModelPartition`, and
consumed by BOTH pipeline schedules:

- the in-program schedule here (`gpt2_pp_train_step` /
  `llama_pp_train_step`): stages run on the shared 6-axis mesh
  (parallel/mesh.py) driven by
  `parallel.pipeline.tailed_pipeline_train_step` — the embedding prelude
  runs replicated on every stage, each stage scans its slice of layers,
  activations `lax.ppermute` to the next stage per microbatch, and the
  final norm + lm head + cross-entropy evaluate on the last stage.  The
  whole schedule (fwd+bwd+update) is ONE compiled program — the
  TPU-native form of the reference's pipeline execution over
  actors/NCCL (ray: compiled DAG NCCL channels, python/ray/dag/) with
  the compiler deriving the backward pipeline through the permutes.

- the MPMD schedule (`ray_tpu.train.pipeline`): each stage is a
  long-lived actor gang, micro-batch activations/grads hand between
  stages as shm objects, and a 1F1B schedule drives the per-stage
  fwd/bwd programs built from the SAME partition
  (train/pipeline/partition.py) — so the two schedules can never drift
  on what a "stage" means.

Composable with the other axes: shard_map is manual over `pp` only
(partial-auto), so dp batch sharding and tp/fsdp parameter shardings
propagate through GSPMD as usual.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models import gpt2 as gpt2_mod
from ray_tpu.models import llama as llama_mod
from ray_tpu.parallel.mesh import PP_AXIS
from ray_tpu.parallel.pipeline import tailed_pipeline_train_step

Params = Any


# -- stage splitting ---------------------------------------------------------


def split_stacked(blocks: Params, n_stages: int) -> Params:
    """(L, ...) stacked layer params → (n_stages, L // n_stages, ...)."""

    def reshape(leaf):
        L = leaf.shape[0]
        if L % n_stages:
            raise ValueError(
                f"{L} layers not divisible into {n_stages} pipeline stages"
            )
        return leaf.reshape((n_stages, L // n_stages) + leaf.shape[1:])

    return jax.tree.map(reshape, blocks)


def merge_stacked(stages: Params) -> Params:
    """Inverse of split_stacked (for checkpoint export / parity tests)."""
    return jax.tree.map(
        lambda leaf: leaf.reshape((-1,) + leaf.shape[2:]), stages
    )


def pp_params_sharding(mesh: Mesh, pp_params: Params) -> Params:
    """NamedShardings: stages split over pp, tail replicated (tp/fsdp
    refinements can be layered on by passing these through the rule
    table first)."""
    return {
        "stages": jax.tree.map(
            lambda _: NamedSharding(mesh, P(PP_AXIS)), pp_params["stages"]
        ),
        "tail": jax.tree.map(
            lambda _: NamedSharding(mesh, P()), pp_params["tail"]
        ),
    }


# -- the reusable partition --------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModelPartition:
    """One model family's pipeline cut, schedule-agnostic.

    ``prelude(tail, tokens) -> h`` embeds a microbatch (runs on the
    FIRST stage under MPMD, replicated on every stage in-program);
    ``stage_fn(stage_blocks, h) -> h`` runs one stage's layer slice;
    ``loss_tail(tail, outs, targets) -> scalar`` evaluates final norm +
    head + cross-entropy on the LAST stage's outputs, where ``outs`` is
    ``(n_micro, mb, S, E)`` and ``targets`` ``(n_micro, mb, S)``.
    ``to_pp(params, n_stages)`` / ``from_pp(pp_params)`` cut and merge
    the parameter pytree ({"stages": stacked, "tail": rest});
    ``init(rng)`` builds the family's fresh full-model params (the
    partition carries ALL model-family knowledge, so registering a new
    family here is sufficient for train.pipeline to drive it).
    """

    name: str
    config: Any
    prelude: Callable[[Params, jax.Array], jax.Array]
    stage_fn: Callable[[Params, jax.Array], jax.Array]
    loss_tail: Callable[[Params, jax.Array, jax.Array], jax.Array]
    to_pp: Callable[[Params, int], Params]
    from_pp: Callable[[Params], Params]
    init: Callable[[Any], Params]

    def micro_loss(self, tail: Params, h: jax.Array,
                   targets: jax.Array) -> jax.Array:
        """Per-microbatch loss: ``loss_tail`` over a single microbatch
        (``h`` (mb, S, E), ``targets`` (mb, S)).  The mean over one
        leading micro-axis entry equals the per-micro mean, so both
        schedules share one loss definition."""
        return self.loss_tail(tail, h[None], targets[None])


# -- GPT-2 -------------------------------------------------------------------


def gpt2_to_pp(params: Params, n_stages: int) -> Params:
    tail = {k: v for k, v in params.items() if k != "blocks"}
    return {"stages": split_stacked(params["blocks"], n_stages),
            "tail": tail}


def gpt2_from_pp(pp_params: Params) -> Params:
    out = dict(pp_params["tail"])
    out["blocks"] = merge_stacked(pp_params["stages"])
    return out


def gpt2_partition(config) -> ModelPartition:
    """The GPT-2 pipeline cut: embedding prelude, scanned block slices,
    tied-head cross-entropy tail."""
    c = config

    def prelude(tail, tokens):
        S = tokens.shape[-1]
        wte = tail["wte"].astype(c.dtype)
        x = wte[tokens] + tail["wpe"].astype(c.dtype)[:S]
        return x

    def stage_fn(stage_blocks, h):
        def body(x, layer_params):
            x2, _aux = gpt2_mod._block(x, layer_params, c, None)
            return x2, None

        h2, _ = lax.scan(body, h, stage_blocks)
        return h2

    def loss_tail(tail, outs, targets):
        x = gpt2_mod._layernorm(outs, tail["lnf_scale"], tail["lnf_bias"])
        logits = jnp.einsum(
            "nbse,ve->nbsv", x, tail["wte"].astype(c.dtype),
            preferred_element_type=jnp.float32,
        )
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tl = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        return -(tl - lse).mean()

    return ModelPartition(
        name="gpt2", config=c, prelude=prelude, stage_fn=stage_fn,
        loss_tail=loss_tail, to_pp=gpt2_to_pp, from_pp=gpt2_from_pp,
        init=lambda rng: gpt2_mod.init(rng, c),
    )


def gpt2_pp_train_step(
    config, mesh: Mesh, optimizer, *, n_micro: int,
    _check_vma: bool = False,
):
    """Pipelined GPT-2 train step over the mesh's pp axis.

    step(pp_params, opt_state, tokens, targets) -> (pp_params, opt_state,
    loss); tokens/targets are (n_micro, mb, S) int32 microbatches.
    """
    p = gpt2_partition(config)
    return tailed_pipeline_train_step(
        p.stage_fn, p.prelude, p.loss_tail, optimizer, mesh,
        n_micro=n_micro, _check_vma=_check_vma,
    )


# -- Llama -------------------------------------------------------------------


def llama_to_pp(params: Params, n_stages: int) -> Params:
    tail = {k: v for k, v in params.items() if k != "blocks"}
    return {"stages": split_stacked(params["blocks"], n_stages),
            "tail": tail}


def llama_from_pp(pp_params: Params) -> Params:
    out = dict(pp_params["tail"])
    out["blocks"] = merge_stacked(pp_params["stages"])
    return out


def llama_partition(config) -> ModelPartition:
    """The Llama pipeline cut (GQA blocks, RMSNorm tail, tied or untied
    head)."""
    c = config

    def prelude(tail, tokens):
        emb = tail["tok_embed"].astype(c.dtype)
        return emb[tokens]

    def stage_fn(stage_blocks, h):
        B, S = h.shape[0], h.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))

        def body(x, layer_params):
            # (x, expert choices): the choices are None for the dense
            # configs this cut supports
            return llama_mod._block(x, layer_params, positions, c)

        h2, _ = lax.scan(body, h, stage_blocks)
        return h2

    def loss_tail(tail, outs, targets):
        x = llama_mod._rmsnorm(outs, tail["final_norm"], c.rms_eps)
        head = (
            tail["tok_embed"] if c.tie_embeddings else tail["lm_head"]
        ).astype(c.dtype)
        logits = jnp.einsum(
            "nbse,ve->nbsv", x, head, preferred_element_type=jnp.float32
        )
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tl = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        return -(tl - lse).mean()

    return ModelPartition(
        name="llama", config=c, prelude=prelude, stage_fn=stage_fn,
        loss_tail=loss_tail, to_pp=llama_to_pp, from_pp=llama_from_pp,
        init=lambda rng: llama_mod.init(rng, c),
    )


def llama_pp_train_step(
    config, mesh: Mesh, optimizer, *, n_micro: int,
    _check_vma: bool = False,
):
    """Pipelined Llama train step over the mesh's pp axis."""
    p = llama_partition(config)
    return tailed_pipeline_train_step(
        p.stage_fn, p.prelude, p.loss_tail, optimizer, mesh,
        n_micro=n_micro, _check_vma=_check_vma,
    )


# -- registry (train.pipeline resolves model families by name) ---------------

PARTITIONS: Dict[str, Callable[[Any], ModelPartition]] = {
    "gpt2": gpt2_partition,
    "llama": llama_partition,
}


def get_partition(model: str, config) -> ModelPartition:
    try:
        factory = PARTITIONS[model]
    except KeyError:
        raise ValueError(
            f"unknown pipeline model family {model!r} "
            f"(registered: {sorted(PARTITIONS)})"
        ) from None
    return factory(config)
