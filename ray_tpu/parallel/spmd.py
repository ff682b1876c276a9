"""Compile a sharded training step over a mesh.

This is where the reference's DDP/FSDP wrapper layer
(ray: python/ray/train/torch/train_loop_utils.py:158 `prepare_model`)
collapses to: params and optimizer state are laid out by the logical-axis
rule table, the whole step is one pjit'd program, and XLA inserts the
gradient reductions (all-reduce over dp, reduce-scatter over fsdp) and
per-layer all-gathers over ICI.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ray_tpu.parallel.mesh import DATA_AXES, SP_AXIS
from ray_tpu.parallel.sharding import (
    DEFAULT_RULES,
    Rules,
    logical_to_spec,
    tree_shardings,
)
from ray_tpu.util import tracing


class TrainState(NamedTuple):
    step: Any
    params: Any
    opt_state: Any


def batch_sharding(mesh: Mesh, *, shard_seq: bool = False) -> NamedSharding:
    """Input batch layout: batch dim over the data axes (plus dcn,
    outermost, on a multi-slice mesh), optionally seq over sp."""
    from ray_tpu.parallel.mesh import DCN_AXIS

    data_axes = tuple(DATA_AXES)
    if DCN_AXIS in mesh.axis_names:
        data_axes = (DCN_AXIS,) + data_axes
    if shard_seq:
        return NamedSharding(mesh, PartitionSpec(data_axes, SP_AXIS))
    return NamedSharding(mesh, PartitionSpec(data_axes))


def shard_batch(mesh: Mesh, batch, *, shard_seq: bool = False):
    """Place a host-side batch pytree onto the mesh, batch-dim sharded."""
    sh = batch_sharding(mesh, shard_seq=shard_seq)
    return jax.tree.map(lambda x: jax.device_put(x, sh), batch)


def _match_param_subtrees(
    state_shape, default_shardings, param_shardings, param_shape
):
    """Replace any opt-state subtree structurally identical to the param
    tree with the param shardings, so adam mu/nu (etc.) shard like their
    params; everything else keeps ``default_shardings`` (replicated).

    A subtree must match the param tree's structure AND its leaf shapes:
    structure alone misfires when params is a single bare array, because
    every leaf (e.g. adam's scalar step count) has the same leaf treedef.
    """
    param_struct = jax.tree.structure(param_shardings)
    param_leaf_shapes = [a.shape for a in jax.tree.leaves(param_shape)]

    def _shapes_match(node):
        leaves = jax.tree.leaves(node)
        return len(leaves) == len(param_leaf_shapes) and all(
            getattr(a, "shape", None) == s
            for a, s in zip(leaves, param_leaf_shapes)
        )

    def rec(shape_node, shard_node):
        try:
            if jax.tree.structure(shape_node) == param_struct and _shapes_match(
                shape_node
            ):
                return param_shardings
        except Exception:
            pass
        if hasattr(shape_node, "_fields"):
            return type(shape_node)(
                **{
                    f: rec(getattr(shape_node, f), getattr(shard_node, f))
                    for f in shape_node._fields
                }
            )
        if isinstance(shape_node, tuple):
            return tuple(rec(a, b) for a, b in zip(shape_node, shard_node))
        if isinstance(shape_node, list):
            return [rec(a, b) for a, b in zip(shape_node, shard_node)]
        if isinstance(shape_node, dict):
            return {k: rec(shape_node[k], shard_node[k]) for k in shape_node}
        return shard_node

    return rec(state_shape, default_shardings)


def _full_init(init_fn: Callable, optimizer: optax.GradientTransformation):
    """The one definition of how a fresh TrainState is built."""

    def go(rng):
        params = init_fn(rng)
        return TrainState(
            jnp.zeros((), jnp.int32), params, optimizer.init(params)
        )

    return go


def state_shardings(
    mesh: Mesh,
    init_fn: Callable,
    rng,
    param_logical,
    optimizer: optax.GradientTransformation,
    rules: Optional[Rules] = None,
) -> TrainState:
    """Compute the TrainState sharding tree without materializing anything."""
    param_shardings = tree_shardings(mesh, param_logical, rules)
    rep = NamedSharding(mesh, PartitionSpec())

    state_shape = jax.eval_shape(_full_init(init_fn, optimizer), rng)
    opt_shardings = jax.tree.map(lambda _: rep, state_shape.opt_state)
    opt_shardings = _match_param_subtrees(
        state_shape.opt_state, opt_shardings, param_shardings,
        state_shape.params,
    )
    return TrainState(rep, param_shardings, opt_shardings)


def sharded_init(
    mesh: Mesh,
    init_fn: Callable,
    rng,
    param_logical,
    optimizer: Optional[optax.GradientTransformation] = None,
    rules: Optional[Rules] = None,
) -> TrainState:
    """Initialize params + optimizer state directly into their shardings.

    Runs init under jit with out_shardings so each device materializes
    only its own parameter shards — a large model on 256 chips never
    exists unsharded anywhere.
    """
    optimizer = optimizer or optax.identity()
    with tracing.startup(
        "train.start.state",
        mesh=",".join(f"{k}={v}" for k, v in mesh.shape.items()),
    ) as started:
        state = _sharded_init(
            mesh, init_fn, rng, param_logical, optimizer, rules
        )
        started.attrs["param_bytes"] = sum(
            x.nbytes for x in jax.tree.leaves(state.params)
        )
        return state


def _sharded_init(mesh, init_fn, rng, param_logical, optimizer,
                  rules) -> TrainState:
    # pre-check divisibility so a mismatch (e.g. num_experts=6 on ep=4)
    # surfaces as a clear error naming the param and axis, not a GSPMD
    # partitioning failure deep inside jit
    shapes = jax.eval_shape(init_fn, rng)

    def check(path, leaf, logical):
        # tree_map_with_path walks BOTH trees together, so a structure
        # mismatch between init_fn's output and param_logical raises a
        # tree error naming the spot instead of silently mispairing
        spec = logical_to_spec(logical, rules)
        for dim, axis in zip(leaf.shape, spec):
            if axis is None:
                continue
            axes = axis if isinstance(axis, tuple) else (axis,)
            n = 1
            for a in axes:
                n *= mesh.shape[a]
            if n > 1 and dim % n:
                name = jax.tree_util.keystr(path)
                raise ValueError(
                    f"param {name} dim of size {dim} (logical axes "
                    f"{logical}) is not divisible by mesh axis "
                    f"{axis} of size {n}; adjust the model config or "
                    "the mesh shape"
                )
        return leaf

    jax.tree_util.tree_map_with_path(check, shapes, param_logical)
    out_shardings = state_shardings(
        mesh, init_fn, rng, param_logical, optimizer, rules
    )
    with jax.set_mesh(mesh):
        state = jax.jit(
            _full_init(init_fn, optimizer), out_shardings=out_shardings
        )(rng)
    # the span ends with the state on the devices, not with its dispatch
    return jax.block_until_ready(state)


def compile_train_step(
    loss_fn: Callable[[Any, Any], jax.Array],
    optimizer: optax.GradientTransformation,
    *,
    donate: bool = True,
):
    """Build `step(state, batch) -> (state, metrics)`.

    Shardings are carried by the arrays themselves (see sharded_init /
    shard_batch): jit propagates them, and the gradient cross-shard
    reductions are emitted by XLA because the loss is batch-sharded
    while params are dp-replicated / fsdp-sharded.
    """

    def step(state: TrainState, batch):
        # scope names are metadata for the profiler's op line
        with jax.named_scope("fwd_bwd"):
            loss, grads = jax.value_and_grad(loss_fn)(state.params, batch)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(
                grads, state.opt_state, state.params
            )
            params = optax.apply_updates(state.params, updates)
        gnorm = optax.global_norm(grads)
        return (
            TrainState(state.step + 1, params, opt_state),
            {"loss": loss, "grad_norm": gnorm},
        )

    return jax.jit(step, donate_argnums=(0,) if donate else ())
