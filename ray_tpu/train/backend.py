"""Training backends: per-framework process-group setup on the worker gang.

Role-equivalent of ray: python/ray/train/backend.py:32,16 (Backend/
BackendConfig) and train/torch/config.py:153,112 (_TorchBackend.on_start →
dist.init_process_group).  The TPU-native backend wires
`jax.distributed.initialize` instead of NCCL: worker 0 of node 0 is the
coordinator, every worker learns (coordinator_address, num_processes,
process_id), and from there all numeric collectives live INSIDE compiled
XLA programs over ICI — no runtime collective library.
"""

from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Dict, List

from ray_tpu.accelerators import tpu

if TYPE_CHECKING:
    from ray_tpu.train.worker_group import WorkerGroup


@dataclasses.dataclass
class BackendConfig:
    @property
    def backend_cls(self):
        return Backend


class Backend:
    """Hooks around the worker gang's lifecycle."""

    def on_start(self, worker_group: "WorkerGroup", backend_config: BackendConfig):
        pass

    def on_training_start(
        self, worker_group: "WorkerGroup", backend_config: BackendConfig
    ):
        pass

    def on_shutdown(self, worker_group: "WorkerGroup", backend_config: BackendConfig):
        pass


@dataclasses.dataclass
class JaxConfig(BackendConfig):
    """Configuration of the jax.distributed bootstrap.

    ``coordinator_port``: port the rank-0 process binds for the
    distributed service; 0 (default) asks the coordinator worker for a
    free port at gang start — re-picked on every gang (re)start, so
    restarts never trip over TIME_WAIT and concurrent gangs on one host
    never collide.  ``init_distributed``: call
    `jax.distributed.initialize` on each worker at training start (True
    for real multi-host SPMD; False leaves single-process jax, used by
    single-worker runs and CPU tests).
    """

    coordinator_port: int = 0
    init_distributed: bool = False

    @property
    def backend_cls(self):
        return JaxBackend


def _jax_distributed_init(coordinator: str, num_processes: int, process_id: int):
    import jax

    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    tpu.open_leased_chips()
    # prove the gang actually formed — callers gate training on this
    return jax.process_count() == num_processes


def _find_free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


class JaxBackend(Backend):
    def __init__(self):
        self._resolved_port: int = 0

    def on_start(self, worker_group: "WorkerGroup", backend_config: JaxConfig):
        """Publish the SPMD bootstrap env to every worker.

        (ray: _TorchBackend picks MASTER_ADDR/PORT from worker 0 —
        train/torch/config.py:94-112; here worker 0 of node 0 hosts the
        jax coordinator.)  Resolved fresh per gang start: a restarted
        gang must not inherit a dead coordinator's port.
        """
        coord = worker_group.workers[0]
        port = backend_config.coordinator_port
        if not port:
            import ray_tpu

            port = ray_tpu.get(
                coord.actor.execute.remote(_find_free_port), timeout=60
            )
        self._resolved_port = port
        coordinator = f"{coord.ip}:{port}"
        envs: List[Dict[str, str]] = []
        for w in worker_group.workers:
            envs.append(
                {
                    "RT_COORDINATOR_ADDRESS": coordinator,
                    "RT_NUM_PROCESSES": str(len(worker_group.workers)),
                    "RT_PROCESS_ID": str(w.rank),
                    "RT_NODE_RANK": str(w.node_rank),
                }
            )
        worker_group.set_envs(envs)

    def on_training_start(
        self, worker_group: "WorkerGroup", backend_config: JaxConfig
    ):
        if not backend_config.init_distributed:
            return
        coord = worker_group.workers[0]
        coordinator = f"{coord.ip}:{self._resolved_port}"
        n = len(worker_group.workers)
        import ray_tpu

        ok = ray_tpu.get(
            [
                w.actor.execute.remote(
                    _jax_distributed_init, coordinator, n, w.rank
                )
                for w in worker_group.workers
            ],
            timeout=300,
        )
        if not all(ok):
            # surface as a gang failure so the trainer's teardown +
            # FailureConfig restart policy run (a bare RuntimeError would
            # escape fit()'s retry loop and leak the worker group)
            from ray_tpu.train.backend_executor import TrainWorkerGroupError

            raise TrainWorkerGroupError(
                f"jax.distributed gang formed with wrong process count "
                f"(expected {n}): {ok}"
            )
