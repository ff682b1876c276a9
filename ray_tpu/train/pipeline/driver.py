"""MPMD pipeline driver: stage gangs + the 1F1B dispatch loop.

``PipelineTrainer`` places each stage as a gang of ``dp`` long-lived
actors (one ``train.worker_group.WorkerGroup`` per stage — atomic
placement-group reservation, node-aware lane ranks) and drives the
1F1B schedule over the batched task plane.  Two data planes
(``PipelineConfig.handoff``):

- ``"p2p"`` (default): adjacent stages stream activations/grads over
  persistent per-lane channels (util/collective/channel.py) and the
  driver ships NO data per micro-op — ONE ``run_ops`` control RPC per
  stage per step carries the stage's whole 1F1B op list (stages
  self-synchronize on channel seq arrival), the edge stages swap tail
  grads over the lane "T" stream, and stage compute overlaps the
  in-flight transfers (async channel sends).  Driver RPCs per step
  collapse from O(micro-ops) to O(stages).
- ``"driver"``: PR 13's path — every micro-op call's activation/grad
  inputs arrive as ObjectRefs, riding the data plane's vectored put
  path (small activations on the inline slab, large ones
  worker-stored in the shm arena and pulled by the consuming stage).

``LocalPipelineRunner`` executes the SAME per-stage programs (same
partition, same accumulation order, same optimizer math) sequentially
in one process — the bit-exact single-gang reference the parity tests
and the bench compare against.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import ray_tpu
from ray_tpu.train.backend_executor import TrainWorkerGroupError
from ray_tpu.train.pipeline import schedule as sched
from ray_tpu.train.pipeline.partition import (
    StagePrograms,
    get_partition,
    to_numpy,
)
from ray_tpu.train.pipeline.stage import PipelineStageActor


@dataclasses.dataclass
class PipelineConfig:
    """One MPMD pipeline run's shape."""

    model_config: Any
    model: str = "gpt2"
    n_stages: int = 2
    n_micro: int = 4
    micro_batch: int = 2       # rows per microbatch (global, split over dp)
    seq_len: int = 32
    dp: int = 1                # lanes per stage (ranks of the stage group)
    optimizer: Dict[str, Any] = dataclasses.field(
        default_factory=lambda: {"name": "sgd", "lr": 0.1}
    )
    seed: int = 0
    name: str = "pipeline"
    collective_backend: str = "rpc"
    # Collectives v2 data path for the dp grad allreduce: e.g.
    # {"wire_dtype": "int8"} block-quantizes the concatenated grad
    # vector (~4x fewer wire bytes per apply), {"algorithm": "auto"}
    # enables size-based ring/rd selection.  None (default) keeps the
    # fp32 ring bit-for-bit — the dp parity pin depends on it.
    collective_options: Optional[Dict[str, Any]] = None
    # in-flight micro-ops ride retries across a stage migration
    max_task_retries: int = 8
    get_timeout_s: float = 600.0
    # micro-batch handoff plane: "p2p" streams activations over
    # persistent stage-to-stage channels; "driver" ships ObjectRefs
    # through the driver per micro-op (see module docstring)
    handoff: str = "p2p"

    def __post_init__(self):
        if self.micro_batch % self.dp:
            raise ValueError(
                f"micro_batch {self.micro_batch} must divide over "
                f"dp {self.dp}"
            )
        if self.handoff not in ("p2p", "driver"):
            raise ValueError(
                f"handoff must be 'p2p' or 'driver', got "
                f"{self.handoff!r}"
            )

    @property
    def scale(self) -> float:
        return 1.0 / float(self.n_micro * self.dp)

    @property
    def lane_mb(self) -> int:
        return self.micro_batch // self.dp

    def tokens_per_step(self) -> int:
        return self.n_micro * self.micro_batch * self.seq_len

    def stage_spec(self, stage_idx: int, lane: int) -> dict:
        return {
            "model": self.model,
            "model_config": self.model_config,
            "n_stages": self.n_stages,
            "stage_idx": stage_idx,
            "n_micro": self.n_micro,
            "dp": self.dp,
            "lane": lane,
            "optimizer": dict(self.optimizer),
            "scale": self.scale,
            "group_name": f"{self.name}:stage{stage_idx}",
            "collective_backend": self.collective_backend,
            "collective_options": self.collective_options,
            "handoff": self.handoff,
            "lane_group": f"{self.name}:lane{lane}:pp",
        }


def synthetic_batches(config: PipelineConfig, steps: int,
                      seed: Optional[int] = None
                      ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Deterministic token batches shared by the cluster run, the local
    reference, and the bench: (tokens, targets) each
    (n_micro, micro_batch, seq_len) int32."""
    rng = np.random.default_rng(config.seed if seed is None else seed)
    vocab = config.model_config.vocab_size
    out = []
    for _ in range(steps):
        toks = rng.integers(
            0, vocab,
            (config.n_micro, config.micro_batch, config.seq_len + 1),
            dtype=np.int32,
        )
        out.append((toks[..., :-1], toks[..., 1:]))
    return out


def _init_pp_params_here(config: PipelineConfig):
    """Model init + stage cut in THIS process (numpy trees, ready to
    ship).  All family knowledge comes from the partition registry, so a
    new family registered in models.pp.PARTITIONS just works here."""
    import jax

    part = get_partition(config.model, config.model_config)
    params = part.init(jax.random.key(config.seed))
    return to_numpy(part.to_pp(params, config.n_stages))


def init_pp_params(config: PipelineConfig):
    """Model init + stage cut for a cluster run, computed in a CPU-leased
    task and fetched as numpy trees.  Never in the calling process: a
    driver that opened a jax backend here would be the first process on
    the accelerator, and the stage actors it starts next could not open
    their chips."""
    task = ray_tpu.remote(num_cpus=0)(_init_pp_params_here)
    return ray_tpu.get(task.remote(config), timeout=config.get_timeout_s)


class PipelineTrainer:
    """Drives a 1F1B MPMD pipeline over stage actor gangs.

    Default placement: one WorkerGroup (placement group) of ``dp``
    actors per stage.  Tests that need exact node control (chaos
    placement) pass ``stage_actor_options`` — a [stage][lane] matrix of
    ``.options()`` dicts — and actors are created directly instead.
    """

    def __init__(self, config: PipelineConfig, *,
                 bundle: Optional[Dict[str, float]] = None,
                 placement_strategy: str = "PACK",
                 stage_actor_options: Optional[List[List[dict]]] = None):
        self.config = config
        self.bundle = bundle or {"CPU": 1}
        self.placement_strategy = placement_strategy
        self.stage_actor_options = stage_actor_options
        self.actors: List[List[Any]] = []   # [stage][lane]
        self.worker_groups: List[Any] = []
        self.step = 0
        self.losses: List[float] = []
        # each stage actor's configure() reply, [stage][lane] flattened:
        # pid, host, jax platform, leased chips
        self.stage_info: List[dict] = []

    # -- lifecycle -------------------------------------------------------
    def start(self) -> None:
        cfg = self.config
        actor_opts = {
            "max_task_retries": cfg.max_task_retries,
            # crash recovery is the trainer-level gang-restart policy;
            # drain MIGRATION (the preemption path) consumes no budget
            "max_restarts": 0,
        }
        if self.stage_actor_options is not None:
            for s in range(cfg.n_stages):
                lanes = []
                for r in range(cfg.dp):
                    opts = dict(actor_opts)
                    opts.update(self.stage_actor_options[s][r])
                    lanes.append(PipelineStageActor.options(**opts).remote())
                self.actors.append(lanes)
        else:
            from ray_tpu.train.worker_group import WorkerGroup

            for s in range(cfg.n_stages):
                wg = WorkerGroup(
                    cfg.dp, dict(self.bundle),
                    placement_strategy=self.placement_strategy,
                    actor_cls=PipelineStageActor,
                    actor_options=actor_opts,
                )
                self.worker_groups.append(wg)
                # lane = gang rank (node-grouped, deterministic)
                self.actors.append(
                    [w.actor for w in sorted(wg.workers,
                                             key=lambda w: w.rank)]
                )
        pp = init_pp_params(cfg)
        import jax

        refs = []
        for s in range(cfg.n_stages):
            blocks = jax.tree.map(lambda a, _s=s: a[_s], pp["stages"])
            tail = (
                pp["tail"] if s in (0, cfg.n_stages - 1) else None
            )
            for r in range(cfg.dp):
                refs.append(self.actors[s][r].configure.remote(
                    cfg.stage_spec(s, r), blocks, tail
                ))
        try:
            self.stage_info = ray_tpu.get(refs, timeout=cfg.get_timeout_s)
        except Exception as e:
            raise TrainWorkerGroupError(
                f"pipeline stage configure failed: {e}"
            ) from e

    def shutdown(self) -> None:
        for wg in self.worker_groups:
            try:
                wg.shutdown()
            except Exception:
                pass
        if not self.worker_groups:
            for lanes in self.actors:
                for a in lanes:
                    try:
                        ray_tpu.kill(a)
                    except Exception:
                        pass
        self.actors = []
        self.worker_groups = []

    # -- the 1F1B dispatch loop -------------------------------------------
    def run_step(self, tokens: np.ndarray, targets: np.ndarray) -> float:
        """One training step: submit the full 1F1B graph, block on the
        applies, return the global mean loss.

        tokens/targets: (n_micro, micro_batch, seq_len) int32; lane r
        takes the contiguous row slice [r·lane_mb, (r+1)·lane_mb).
        """
        cfg = self.config
        S, M, dp, step = cfg.n_stages, cfg.n_micro, cfg.dp, self.step
        mb = cfg.lane_mb
        A = self.actors
        sink = []  # refs gathered only to surface errors
        if cfg.handoff == "p2p":
            # pure control plane, O(1) RPCs per stage per step: ONE
            # run_ops call ships a stage's whole 1F1B op list (plus the
            # edge stages' token/target slices); the stages move every
            # activation, grad, and tail-grad between themselves on the
            # lane channels, self-synchronizing on seq arrival, and
            # each reply is a tiny ack
            for s in range(S):
                ops = sched.stage_ops(s, S, M)
                for r in range(dp):
                    rows = slice(r * mb, (r + 1) * mb)
                    sink.append(A[s][r].run_ops.remote(
                        step, ops,
                        tokens[:, rows] if s == 0 else None,
                        targets[:, rows] if s == S - 1 else None,
                    ))
            applies = [
                A[s][r].apply_gradients.remote(step)
                for r in range(dp) for s in range(S)
            ]
        else:
            h: Dict[Tuple[int, int, int], Any] = {}   # (s, m, r) -> ref
            g: Dict[Tuple[int, int, int], Any] = {}
            for s, kind, m in sched.submission_order(S, M):
                for r in range(dp):
                    rows = slice(r * mb, (r + 1) * mb)
                    if kind == "F":
                        if s == 0:
                            ref = A[0][r].forward.remote(
                                step, m, tokens[m, rows]
                            )
                            h[(0, m, r)] = ref
                        elif s == S - 1:
                            ref = A[s][r].forward.remote(
                                step, m, h[(s - 1, m, r)],
                                targets[m, rows]
                            )
                            g[(s, m, r)] = ref   # fused: F returns grad
                        else:
                            ref = A[s][r].forward.remote(
                                step, m, h[(s - 1, m, r)]
                            )
                            h[(s, m, r)] = ref
                    else:
                        ref = A[s][r].backward.remote(
                            step, m, g[(s + 1, m, r)]
                        )
                        if s == 0:
                            sink.append(ref)
                        else:
                            g[(s, m, r)] = ref
            tg_first = [A[0][r].tail_grads.remote(step) for r in range(dp)]
            tg_last = [
                A[S - 1][r].tail_grads.remote(step) for r in range(dp)
            ]
            applies = []
            for r in range(dp):
                applies.append(
                    A[0][r].apply_gradients.remote(step, tg_last[r])
                )
                applies.append(
                    A[S - 1][r].apply_gradients.remote(step, tg_first[r])
                )
                for s in range(1, S - 1):
                    applies.append(A[s][r].apply_gradients.remote(step))
        loss_refs = [A[S - 1][r].step_loss.remote(step) for r in range(dp)]
        try:
            ray_tpu.get(sink + applies, timeout=cfg.get_timeout_s)
            lane_losses = ray_tpu.get(loss_refs, timeout=cfg.get_timeout_s)
        except Exception as e:
            raise TrainWorkerGroupError(
                f"pipeline step {step} failed: {e}"
            ) from e
        loss = float(
            np.float32(np.sum(np.float32(lane_losses), dtype=np.float32)
                       / np.float32(dp))
        )
        self.step += 1
        self.losses.append(loss)
        return loss

    def train(self, batches) -> List[float]:
        return [self.run_step(x, y) for x, y in batches]

    # -- introspection ----------------------------------------------------
    def gather_params(self):
        """Merged full-model params pulled from lane 0 of every stage."""
        import jax

        cfg = self.config
        per = ray_tpu.get(
            [self.actors[s][0].get_params.remote()
             for s in range(cfg.n_stages)],
            timeout=cfg.get_timeout_s,
        )
        stages = jax.tree.map(
            lambda *leaves: np.stack(leaves),
            *[p["blocks"] for p in per],
        )
        part = get_partition(cfg.model, cfg.model_config)
        return to_numpy(part.from_pp(
            {"stages": stages, "tail": per[0]["tail"]}
        ))

    def counters(self) -> List[List[dict]]:
        cfg = self.config
        return [
            ray_tpu.get(
                [a.counters.remote() for a in lanes],
                timeout=cfg.get_timeout_s,
            )
            for lanes in self.actors
        ]

    def ideal_micro_ops(self, steps: int) -> int:
        """Micro-op executions per lane actor set for ``steps`` clean
        steps: F+B per micro per non-last stage, fused F per micro on
        the last, one apply per stage — times dp lanes."""
        cfg = self.config
        per_step = (
            (2 * (cfg.n_stages - 1) + 1) * cfg.n_micro + cfg.n_stages
        )
        return per_step * cfg.dp * steps


class LocalPipelineRunner:
    """The single-gang reference: same partition, same per-stage
    programs, same micro order, same optimizer math — in one process.

    dp lanes are simulated sequentially; lane grad sums use the same
    canonical operand order as the 2-rank ring (elementwise a+b), so
    for dp ≤ 2 the cluster run matches this runner bit-for-bit.
    """

    def __init__(self, config: PipelineConfig):
        self.config = config
        part = get_partition(config.model, config.model_config)
        self.progs = [
            StagePrograms(part, config.n_stages, s, config.optimizer,
                          config.scale)
            for s in range(config.n_stages)
        ]
        pp = _init_pp_params_here(config)
        import jax

        self.blocks = [
            jax.tree.map(lambda a, _s=s: a[_s], pp["stages"])
            for s in range(config.n_stages)
        ]
        self.tails = {
            0: pp["tail"],
            config.n_stages - 1: to_numpy(
                jax.tree.map(np.copy, pp["tail"])
            ),
        }
        self.opt_blocks = [
            to_numpy(self.progs[s].init_opt(self.blocks[s]))
            for s in range(config.n_stages)
        ]
        self.opt_tails = {
            s: to_numpy(self.progs[s].init_opt(t))
            for s, t in self.tails.items()
        }
        self.losses: List[float] = []

    def run_step(self, tokens: np.ndarray, targets: np.ndarray) -> float:
        cfg = self.config
        S, M, dp, mb = cfg.n_stages, cfg.n_micro, cfg.dp, cfg.lane_mb
        P = self.progs
        acc_b: List[List[Any]] = [[None] * S for _ in range(dp)]
        acc_t: List[Dict[int, Any]] = [
            {0: None, S - 1: None} for _ in range(dp)
        ]
        lane_loss: List[List[np.float32]] = [[] for _ in range(dp)]

        def add(s, lane, g_blocks, g_tail=None):
            acc_b[lane][s] = (
                to_numpy(g_blocks) if acc_b[lane][s] is None
                else to_numpy(P[s].tree_add(acc_b[lane][s], g_blocks))
            )
            if g_tail is not None:
                acc_t[lane][s] = (
                    to_numpy(g_tail) if acc_t[lane][s] is None
                    else to_numpy(P[s].tree_add(acc_t[lane][s], g_tail))
                )

        for m in range(M):
            for lane in range(dp):
                rows = slice(lane * mb, (lane + 1) * mb)
                toks, tgt = tokens[m, rows], targets[m, rows]
                stash = {0: toks}
                h = to_numpy(P[0].fwd(self.blocks[0], self.tails[0], toks))
                for s in range(1, S - 1):
                    stash[s] = h
                    h = to_numpy(P[s].fwd(self.blocks[s], h))
                loss, (gb, gt, gh) = P[S - 1].fwd_loss(
                    self.blocks[S - 1], self.tails[S - 1], h, tgt
                )
                lane_loss[lane].append(np.float32(loss))
                add(S - 1, lane, gb, gt)
                gdown = to_numpy(gh)
                for s in range(S - 2, 0, -1):
                    gb, gh = P[s].bwd(self.blocks[s], stash[s], gdown)
                    add(s, lane, gb)
                    gdown = to_numpy(gh)
                gb, gt = P[0].bwd(
                    self.blocks[0], self.tails[0], stash[0], gdown
                )
                add(0, lane, gb, gt)

        # lane reduction: elementwise sum in lane order (== the 2-rank
        # ring's a+b); dp == 1 skips it, matching the cluster path
        for s in range(S):
            g = acc_b[0][s]
            for lane in range(1, dp):
                g = to_numpy(P[s].tree_add(g, acc_b[lane][s]))
            g = P[s].tree_scale(g)
            self.blocks[s], self.opt_blocks[s] = map(to_numpy, P[s].apply(
                self.blocks[s], self.opt_blocks[s], g
            ))
        # tail: canonical (first_side, last_side) then lane reduction
        for s in (0, S - 1):
            gt = to_numpy(P[s].tree_add(acc_t[0][0], acc_t[0][S - 1]))
            for lane in range(1, dp):
                gt = to_numpy(P[s].tree_add(
                    gt,
                    P[s].tree_add(acc_t[lane][0], acc_t[lane][S - 1]),
                ))
            gt = P[s].tree_scale(gt)
            self.tails[s], self.opt_tails[s] = map(to_numpy, P[s].apply(
                self.tails[s], self.opt_tails[s], gt
            ))
        lane_means = [
            float(np.float32(
                np.array(l, dtype=np.float32).sum()
                / np.float32(len(l))
            ))
            for l in lane_loss
        ]
        loss = float(
            np.float32(np.sum(np.float32(lane_means), dtype=np.float32)
                       / np.float32(dp))
        )
        self.losses.append(loss)
        return loss

    def train(self, batches) -> List[float]:
        return [self.run_step(x, y) for x, y in batches]

    def gather_params(self):
        import jax

        cfg = self.config
        part = get_partition(cfg.model, cfg.model_config)
        stages = jax.tree.map(
            lambda *leaves: np.stack(leaves), *self.blocks
        )
        return to_numpy(part.from_pp(
            {"stages": stages, "tail": self.tails[0]}
        ))
