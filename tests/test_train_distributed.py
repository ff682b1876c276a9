"""Real multi-process SPMD: `jax.distributed.initialize` through the
trainer gang.

Everything else in the suite exercises multi-device sharding inside ONE
process (virtual 8-device CPU mesh).  These tests run the actual
multi-HOST bootstrap path the way a TPU pod would use it — N separate
worker processes, `JaxConfig(init_distributed=True)`, a Gloo-backed
cross-process `psum` inside a jitted step — so the coordinator wiring,
process-id assignment, and gang restart are executed, not just compiled.
(reference analogue: python/ray/train/torch/config.py:94-112
_TorchBackend.on_start + its CI tests; jax replaces the torch process
group with jax.distributed + XLA collectives.)
"""

import os

import pytest

import ray_tpu
from ray_tpu import train
from ray_tpu.train import JaxConfig, JaxTrainer, RunConfig, ScalingConfig
from ray_tpu.train.config import FailureConfig


def _distributed_psum_loop(config):
    """Runs in each gang worker AFTER jax.distributed.initialize."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    ctx = train.get_context()
    n_proc = jax.process_count()
    assert n_proc == ctx.get_world_size(), (n_proc, ctx.get_world_size())
    assert jax.process_index() == ctx.get_world_rank()
    devs = np.array(jax.devices())
    # each process contributes its local devices to one dp axis
    mesh = Mesh(devs, ("dp",))

    # 1) pure collective: psum of (axis_index + 1) over every device in
    # the gang — crosses the process boundary via Gloo
    shard_map = jax.shard_map

    def contrib():
        return jax.lax.psum(
            jax.lax.axis_index("dp").astype(jnp.float32) + 1.0, "dp"
        )

    total = jax.jit(
        shard_map(contrib, mesh=mesh, in_specs=(), out_specs=P())
    )()
    d = len(devs)
    expected = d * (d + 1) / 2

    # 2) one REAL data-parallel train step: replicated params, data
    # sharded across the gang; XLA inserts the cross-process grad psum
    repl = NamedSharding(mesh, P())
    dp = NamedSharding(mesh, P("dp"))
    w = jax.device_put(jnp.zeros((4,), jnp.float32), repl)
    rows_per_dev = 2
    local = np.tile(
        np.arange(4, dtype=np.float32),
        (rows_per_dev * jax.local_device_count(), 1),
    )
    x = jax.make_array_from_process_local_data(
        dp, local, (rows_per_dev * d, 4)
    )
    y = jax.make_array_from_process_local_data(
        dp,
        np.full((rows_per_dev * jax.local_device_count(),), 14.0, np.float32),
        (rows_per_dev * d,),
    )

    def loss(w, x, y):
        return jnp.mean((x @ w - y) ** 2)

    @jax.jit
    def step(w, x, y):
        g = jax.grad(loss)(w, x, y)
        return w - 0.01 * g, loss(w, x, y)

    w, l0 = step(w, x, y)
    w, l1 = step(w, x, y)
    train.report(
        {
            "psum": float(np.asarray(total)),
            "expected_psum": expected,
            "loss0": float(l0),
            "loss1": float(l1),
            "w0": float(np.asarray(w)[0]),
            "process_count": n_proc,
        }
    )


@pytest.fixture
def dist_cluster():
    ray_tpu.init(num_cpus=4, num_tpus=0)
    yield ray_tpu
    ray_tpu.shutdown()


class TestDistributedGang:
    def test_two_process_psum_and_train_step(self, dist_cluster, tmp_path):
        trainer = JaxTrainer(
            _distributed_psum_loop,
            scaling_config=ScalingConfig(num_workers=2, use_tpu=False),
            backend_config=JaxConfig(init_distributed=True),
            run_config=RunConfig(
                name="dist_psum", storage_path=str(tmp_path)
            ),
        )
        result = trainer.fit()
        m = result.metrics
        assert m["process_count"] == 2
        assert m["psum"] == pytest.approx(m["expected_psum"])
        # the dp step actually descends, identically on every process
        # (rank-0 metrics are canonical; loss is a global mean)
        assert m["loss1"] < m["loss0"]

    def test_gang_restart_reinitializes_distributed(
        self, dist_cluster, tmp_path
    ):
        marker = str(tmp_path / "died_once")

        def loop(config):
            import jax

            assert jax.process_count() == 2
            ctx = train.get_context()
            if ctx.get_world_rank() == 1 and not os.path.exists(
                config["marker"]
            ):
                open(config["marker"], "w").close()
                os._exit(1)  # simulated worker crash mid-gang
            train.report({"round": 1, "procs": jax.process_count()})

        trainer = JaxTrainer(
            loop,
            train_loop_config={"marker": marker},
            scaling_config=ScalingConfig(num_workers=2, use_tpu=False),
            backend_config=JaxConfig(init_distributed=True),
            run_config=RunConfig(
                name="dist_restart",
                storage_path=str(tmp_path),
                failure_config=FailureConfig(max_failures=2),
            ),
        )
        result = trainer.fit()
        # the gang died once (rank 1), restarted in FRESH processes on a
        # FRESH coordinator port, and re-formed the 2-process group
        assert os.path.exists(marker)
        assert result.metrics["procs"] == 2
