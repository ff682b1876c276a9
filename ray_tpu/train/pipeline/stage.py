"""The long-lived pipeline stage actor.

One process per (stage, dp-lane).  Holds the stage's parameter slice,
optimizer state, the 1F1B activation stash, and per-step grad
accumulators; executes forward/backward micro-ops in the queue order
the driver enqueued (sync actors run per-caller calls in admission
order, so the actor queue IS the 1F1B schedule for this stage).

Preemption survival contract (the reason this is an actor and not a
task): every micro-op is EXACTLY-ONCE under migration —

- a per-step ledger caches each completed op's reply keyed by
  (kind, step, micro); a call retried after a migration (lost reply,
  or a call in flight when the node died) returns the cached value
  without re-applying its state effects;
- ``__rt_checkpoint__`` captures params + optimizer state + the grad
  accumulators + the stash + the ledger, so the drain plane
  (PR 9) migrates the stage MID-STEP with its in-flight microbatches
  intact — the restored actor continues the step, it does not restart
  it;
- dp>1 stages are ranks of a util.collective group registered at
  configure time; the drain plane's proactive reform re-forms the
  group around the migrated member BEFORE the old node dies.

Micro-batch handoff (spec["handoff"]):

- ``"p2p"`` (default): adjacent stages of one dp lane are ranks of a
  per-lane collective group and stream activations/grads directly over
  persistent channels (util/collective/channel.py) — the driver's
  calls carry no data, only control; ops self-synchronize by fetching
  ``seq = step·n_micro + micro`` (the ledger key extended to the wire),
  and async sends overlap the next micro-op's compute.  Channel
  outboxes ride the checkpoint, so a migrated member re-offers its
  in-flight payloads into the re-formed group.
- ``"driver"``: PR 13's path — every activation an ObjectRef through
  the driver (kept for A/B benching and as the fallback).

Everything crossing the process boundary is numpy (bit-exact buffers);
jit re-ingests on entry.
"""

from __future__ import annotations

import os
import socket
from typing import Any, Dict, Optional

import numpy as np

import ray_tpu
from ray_tpu.train.pipeline.partition import (
    StagePrograms,
    flatten_grads,
    get_partition,
    to_numpy,
    to_wire,
    unflatten_grads,
)


@ray_tpu.remote
class PipelineStageActor:
    """One pipeline stage lane (rank ``lane`` of the stage's dp group)."""

    def __init__(self):
        self._spec: Optional[dict] = None
        self._progs: Optional[StagePrograms] = None
        self._blocks = None
        self._tail = None
        self._opt_blocks = None
        self._opt_tail = None
        self._acc_blocks = None
        self._acc_tail = None
        self._stash: Dict[int, Any] = {}
        self._ledger: Dict[tuple, Any] = {}
        self._losses: Dict[int, Dict[int, Any]] = {}
        self._executed = 0
        self._deduped = 0
        self._ch: Dict[str, Any] = {}
        self._p2p = False

    # -- topology discovery (WorkerGroup rank assignment) ----------------
    def node_info(self) -> dict:
        from ray_tpu.train.worker_group import actor_node_info

        return actor_node_info()

    def set_env(self, env: Dict[str, str]) -> bool:
        os.environ.update(env)
        return True

    # -- lifecycle -------------------------------------------------------
    def configure(self, spec: dict, blocks, tail=None) -> dict:
        """Install the stage: build programs, adopt the param slice,
        init optimizer state, and (dp > 1) join the stage's collective
        group under rank ``lane``.

        spec keys: model, model_config, n_stages, stage_idx, n_micro,
        dp, lane, optimizer, scale, group_name, collective_backend,
        collective_options (optional dict: wire_dtype / algorithm /
        chunk_bytes for the dp grad allreduce — default None keeps the
        bit-exact fp32 ring), handoff, lane_group (p2p channel group).
        """
        self._build(spec)
        self._blocks = blocks
        self._opt_blocks = to_numpy(self._progs.init_opt(blocks))
        if self._progs.is_first or self._progs.is_last:
            if tail is None:
                raise ValueError(
                    "first/last pipeline stages need the tail params"
                )
            self._tail = tail
            self._opt_tail = to_numpy(self._progs.init_opt(tail))
        if spec.get("handoff", "driver") == "p2p" and spec["n_stages"] > 1:
            from ray_tpu.util import collective as col

            # every actor joins its LANE group before its dp group: the
            # two group families partition the actors two ways, and one
            # consistent join order keeps the concurrent configure()
            # rendezvous rounds cycle-free.  No options: activations
            # must cross the wire bit-exact (quantization is a dp
            # grad-allreduce concern, never a channel one).
            col.init_collective_group(
                spec["n_stages"], spec["stage_idx"],
                backend=spec.get("collective_backend", "rpc"),
                group_name=spec["lane_group"],
            )
            self._open_channels()
        if spec["dp"] > 1:
            from ray_tpu.util import collective as col

            # group options (not per-op args) so the wire format rides
            # the rendezvous records: a drain-migration reform restores
            # the exact same data path without re-plumbing anything
            col.init_collective_group(
                spec["dp"], spec["lane"],
                backend=spec.get("collective_backend", "rpc"),
                group_name=spec["group_name"],
                options=spec.get("collective_options"),
            )
        import jax

        return {
            "pid": os.getpid(), "host": socket.gethostname(),
            "stage": spec["stage_idx"], "lane": spec["lane"],
            # what this stage's programs run on, and which chips the
            # raylet leased it ("" on a CPU lease)
            "platform": jax.devices()[0].platform,
            "device_kind": jax.devices()[0].device_kind,
            "tpu_chips": os.environ.get("TPU_VISIBLE_CHIPS", ""),
        }

    def _build(self, spec: dict) -> None:
        part = get_partition(spec["model"], spec["model_config"])
        self._progs = StagePrograms(
            part, spec["n_stages"], spec["stage_idx"], spec["optimizer"],
            spec["scale"],
        )
        self._spec = spec

    # -- p2p channels ------------------------------------------------------
    def _open_channels(self) -> None:
        """Open this stage's persistent channel ends on the lane group
        (group-lazy: only registers endpoints + reform listeners, so
        the restore path may call it BEFORE the group re-join)."""
        from ray_tpu.train.pipeline import schedule as sched
        from ray_tpu.util.collective.channel import (
            ChannelReceiver,
            ChannelSender,
        )

        spec = self._spec
        s, S, M = spec["stage_idx"], spec["n_stages"], spec["n_micro"]
        g = spec["lane_group"]
        depth = sched.inflight_micros(s, S, M)
        self._ch = {}
        if s > 0:
            self._ch["fwd_in"] = ChannelReceiver(g, "F", s - 1)
            self._ch["grad_out"] = ChannelSender(g, "B", s - 1,
                                                 window=depth)
        if s < S - 1:
            self._ch["fwd_out"] = ChannelSender(g, "F", s + 1,
                                                window=depth)
            self._ch["grad_in"] = ChannelReceiver(g, "B", s + 1)
        if s in (0, S - 1):
            # the edge stages exchange their raw tail-grad sums at
            # apply time over a dedicated "T" stream (seq = step) —
            # the last driver-mediated data ref gone from the step
            peer = S - 1 if s == 0 else 0
            self._ch["tail_out"] = ChannelSender(g, "T", peer)
            self._ch["tail_in"] = ChannelReceiver(g, "T", peer)
        self._p2p = True

    def _seq(self, step: int, micro: int) -> int:
        # the exactly-once ledger key, extended to the wire: pure in
        # (step, micro), so a migrated retry re-fetches/re-posts the
        # SAME stream position and dedupes identically
        return step * self._spec["n_micro"] + micro

    def _reap_sends(self) -> None:
        """Surface terminal async-send failures on the next micro-op
        (the overlap engine completes transfers in the background;
        nothing else would ever observe a late error)."""
        for ch in self._ch.values():
            reap = getattr(ch, "reap", None)
            if reap is not None:
                reap()

    # -- exactly-once ledger ---------------------------------------------
    def _cached(self, key):
        if key in self._ledger:
            self._deduped += 1
            return True, self._ledger[key]
        return False, None

    # -- micro-ops ---------------------------------------------------------
    def forward(self, step: int, micro: int, payload=None, targets=None):
        """First stage: payload = tokens (mb, S) int32, returns h.
        Mid stage: payload = h from the previous stage, returns h.
        Last stage: payload = h, targets = (mb, S); fused
        forward+loss+backward-begin — returns the grad flowing DOWN to
        the previous stage (the per-micro loss is kept here; the driver
        reads the step mean once via step_loss).

        p2p handoff: non-first stages ignore ``payload`` and fetch
        ``seq`` off the lane channel; the output is POSTED downstream
        (async — the transfer overlaps the next op's compute) and the
        driver gets a tiny control ack instead of the array."""
        key = ("F", step, micro)
        hit, val = self._cached(key)
        if hit:
            return val
        p = self._progs
        seq = None
        if self._p2p:
            seq = self._seq(step, micro)
            self._reap_sends()
            if not p.is_first:
                # fetch BEFORE counting the execution: an op that dies
                # waiting on the wire did no work to dedupe
                payload = self._ch["fwd_in"].fetch(seq)
        self._executed += 1
        if p.is_last:
            loss, (gb, gt, gh) = p.fwd_loss(
                self._blocks, self._tail, payload, targets
            )
            self._accumulate(gb, gt)
            self._losses.setdefault(step, {})[micro] = np.float32(loss)
            out = to_numpy(gh)
            if self._p2p:
                self._ch["grad_out"].post(seq, to_wire(out))
                out = True
        else:
            if p.is_first:
                h = p.fwd(self._blocks, self._tail, payload)
            else:
                h = p.fwd(self._blocks, payload)
            self._stash[micro] = payload
            out = to_numpy(h)
            if self._p2p:
                self._ch["fwd_out"].post(seq, to_wire(out))
                out = True
        self._ledger[key] = out
        return out

    def backward(self, step: int, micro: int, g_out=None):
        """Recompute-from-stash backward for first/mid stages; returns
        the grad for the stage below (True on the first stage — token
        grads stop here).  p2p handoff: ``g_out`` is fetched off the
        lane channel and the produced grad posted downstream."""
        key = ("B", step, micro)
        hit, val = self._cached(key)
        if hit:
            return val
        p = self._progs
        if p.is_last:
            raise RuntimeError(
                "last-stage backward is fused into forward; the driver "
                "must not submit B ops to the last stage"
            )
        seq = None
        if self._p2p:
            seq = self._seq(step, micro)
            self._reap_sends()
            g_out = self._ch["grad_in"].fetch(seq)
        self._executed += 1
        h_in = self._stash.pop(micro)
        if p.is_first:
            gb, gt = p.bwd(self._blocks, self._tail, h_in, g_out)
            self._accumulate(gb, gt)
            out = True
        else:
            gb, gh = p.bwd(self._blocks, h_in, g_out)
            self._accumulate(gb, None)
            out = to_numpy(gh)
            if self._p2p:
                self._ch["grad_out"].post(seq, to_wire(out))
                out = True
        self._ledger[key] = out
        return out

    def run_ops(self, step: int, ops, tokens=None, targets=None) -> bool:
        """ONE control RPC per stage per step (p2p): execute this
        stage's whole 1F1B op list in admission order; activations and
        grads move on the lane channels, so the call carries only the
        edge stages' token/target slices — (n_micro, lane_mb, seq_len)
        — and returns a single ack.  Every micro-op still ledgers
        individually, so a batch retried after a migration re-executes
        only the ops actually lost."""
        for kind, m in ops:
            if kind == "F":
                self.forward(
                    step, m,
                    tokens[m] if tokens is not None else None,
                    targets[m] if targets is not None else None,
                )
            else:
                self.backward(step, m)
        return True

    def _accumulate(self, g_blocks, g_tail):
        p = self._progs
        self._acc_blocks = (
            to_numpy(g_blocks) if self._acc_blocks is None
            else to_numpy(p.tree_add(self._acc_blocks, g_blocks))
        )
        if g_tail is not None:
            self._acc_tail = (
                to_numpy(g_tail) if self._acc_tail is None
                else to_numpy(p.tree_add(self._acc_tail, g_tail))
            )

    # -- step end ---------------------------------------------------------
    def tail_grads(self, step: int):
        """This side's RAW accumulated tail-grad sum (first and last
        stages exchange these; see partition module docstring)."""
        key = ("TG", step)
        hit, val = self._cached(key)
        if hit:
            return val
        out = to_numpy(self._acc_tail)
        self._ledger[key] = out
        return out

    def apply_gradients(self, step: int, other_tail_grads=None) -> bool:
        """Allreduce (dp > 1) + scale + optimizer update; clears the
        step's accumulators and expires ledger entries of PAST steps
        (the current step's stay — a lost apply reply must dedupe)."""
        key = ("A", step)
        hit, val = self._cached(key)
        if hit:
            return val
        p = self._progs
        self._executed += 1
        g_blocks = self._acc_blocks
        g_tail = None
        if p.is_first or p.is_last:
            if (self._p2p and other_tail_grads is None
                    and "tail_in" in self._ch):
                other_tail_grads = self._exchange_tail(step)
            # canonical operand order (first_side, last_side): both tail
            # copies compute the identical sum bitwise
            own, other = self._acc_tail, other_tail_grads
            first_side = own if p.is_first else other
            last_side = other if p.is_first else own
            g_tail = to_numpy(p.tree_add(first_side, last_side))
        if self._spec["dp"] > 1:
            g_blocks, g_tail = self._allreduce(g_blocks, g_tail)
        g_blocks = p.tree_scale(g_blocks)
        self._blocks, self._opt_blocks = map(to_numpy, p.apply(
            self._blocks, self._opt_blocks, g_blocks
        ))
        if g_tail is not None:
            g_tail = p.tree_scale(g_tail)
            self._tail, self._opt_tail = map(to_numpy, p.apply(
                self._tail, self._opt_tail, g_tail
            ))
        self._acc_blocks = None
        self._acc_tail = None
        self._stash.clear()
        if self._p2p:
            # PAST steps only (seq < step·M): the CURRENT step's
            # payloads stay re-deliverable until the NEXT apply proves
            # every cross-stage fetch of this step completed — the
            # driver finishes step k (all acks) before submitting k+1,
            # so by the apply of k+1 step k is certainly consumed
            base = step * self._spec["n_micro"]
            for ch in self._ch.values():
                # the "T" stream counts in steps, not micro seqs — its
                # current entry must likewise outlive THIS apply (the
                # peer edge stage may still be fetching it)
                ch.purge_below(step if ch.stream == "T" else base)
        self._ledger = {
            k: v for k, v in self._ledger.items() if k[1] >= step
        }
        self._losses = {s: v for s, v in self._losses.items() if s >= step}
        self._ledger[key] = True
        return True

    def _exchange_tail(self, step: int):
        """Edge-stage tail-grad swap over the lane "T" stream: post the
        own RAW sum (flattened to one f32 vector), fetch the peer's,
        unflatten against the local tree (both edges hold the same tail
        structure).  seq = step — pure, so a migrated retry re-posts
        and re-fetches the identical position and dedupes on the wire
        exactly like the micro-op streams."""
        self._ch["tail_out"].post(
            step, to_wire(flatten_grads(to_numpy(self._acc_tail)))
        )
        peer_flat = self._ch["tail_in"].fetch(step)
        return unflatten_grads(to_numpy(self._acc_tail), peer_flat)

    def _allreduce(self, g_blocks, g_tail):
        """Grad allreduce over the stage group, riding out a migration
        window: between a peer's old worker dying and the proactive
        reform completing, the group is transiently poisoned (or
        mid-reform, i.e. locally uninitialized).  The op mutates no
        actor state, so retrying against the re-formed group is exact —
        both sides re-enter with their checkpoint-intact accumulators.
        A peer that is REALLY gone keeps the group poisoned past the
        budget and the error surfaces as before."""
        import time as _time

        from ray_tpu.common.config import cfg
        from ray_tpu.util import collective as col
        from ray_tpu.util.collective.types import CollectiveError

        group = self._spec["group_name"]
        deadline = _time.monotonic() + float(
            self._spec.get("allreduce_retry_timeout_s")
            or cfg.collective_rendezvous_timeout_s
        )
        # ONE op per apply: blocks (and tail, when this stage holds one)
        # concatenated into a single f32 vector — one ring pass, and no
        # partially-reduced multi-op state to reason about under retry
        flat_b = flatten_grads(g_blocks)
        if g_tail is not None:
            flat = np.concatenate([flat_b, flatten_grads(g_tail)])
        else:
            flat = flat_b
        while True:
            try:
                summed = col.allreduce(flat, group_name=group)
                break
            except CollectiveError:
                if _time.monotonic() >= deadline:
                    raise
                _time.sleep(0.5)
        out_blocks = unflatten_grads(g_blocks, summed[:flat_b.size])
        out_tail = (
            unflatten_grads(g_tail, summed[flat_b.size:])
            if g_tail is not None else None
        )
        return out_blocks, out_tail

    def step_loss(self, step: int) -> float:
        """Mean per-micro loss of this lane for ``step`` (last stage)."""
        per = self._losses.get(step)
        if per is None:
            raise RuntimeError(f"no losses recorded for step {step}")
        vals = np.array(
            [per[m] for m in sorted(per)], dtype=np.float32
        )
        return float(np.float32(vals.sum() / np.float32(len(vals))))

    # -- introspection ----------------------------------------------------
    def get_params(self):
        return {"blocks": self._blocks, "tail": self._tail}

    def group_rank(self):
        from ray_tpu.util import collective as col

        return col.get_rank(self._spec["group_name"])

    def counters(self) -> dict:
        from ray_tpu.common import faults, serialization as ser
        from ray_tpu.core.runtime import get_runtime

        return {
            "pid": os.getpid(),
            "executed": self._executed,
            "deduped": self._deduped,
            "copy_trace": dict(ser.COPY_TRACE),
            "slab_hits": get_runtime().store.stats().get("slab_hits", 0),
            # RT_FAULTS firings in THIS worker process — chaos tests arm
            # plans via the env var and can only read the trace through
            # the actor (faults.trace() is per-process state)
            "fault_trace": faults.trace(),
        }

    # -- migration hooks (PR 9 drain plane) -------------------------------
    def __rt_checkpoint__(self):
        return {
            "spec": self._spec,
            "blocks": self._blocks,
            "tail": self._tail,
            "opt_blocks": self._opt_blocks,
            "opt_tail": self._opt_tail,
            "acc_blocks": self._acc_blocks,
            "acc_tail": self._acc_tail,
            "stash": dict(self._stash),
            "ledger": dict(self._ledger),
            "losses": {s: dict(v) for s, v in self._losses.items()},
            "executed": self._executed,
            "deduped": self._deduped,
            # unpurged channel payloads: the restored twin re-offers
            # these into the re-formed lane group (acked sends may have
            # died unconsumed in a co-migrating peer's mailbox)
            "send_outbox": {
                name: ch.outbox_state()
                for name, ch in self._ch.items()
                if hasattr(ch, "outbox_state")
            },
        }

    def __rt_restore__(self, state):
        self._build(state["spec"])
        self._blocks = state["blocks"]
        self._tail = state["tail"]
        self._opt_blocks = state["opt_blocks"]
        self._opt_tail = state["opt_tail"]
        self._acc_blocks = state["acc_blocks"]
        self._acc_tail = state["acc_tail"]
        self._stash = state["stash"]
        self._ledger = state["ledger"]
        self._losses = state["losses"]
        self._executed = state["executed"]
        self._deduped = state["deduped"]
        spec = state["spec"]
        if spec.get("handoff") == "p2p" and spec["n_stages"] > 1:
            # endpoints + reform listeners only — the lane-group
            # re-join runs AFTER this hook (worker_main's
            # _rejoin_collective_group), and its install fires the
            # listeners, which re-offer the restored outboxes
            self._open_channels()
            for name, st in (state.get("send_outbox") or {}).items():
                ch = self._ch.get(name)
                if ch is not None and hasattr(ch, "restore_outbox"):
                    ch.restore_outbox(st)
