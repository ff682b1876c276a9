"""The runtime's accelerator seam, as far as a CPU can show it.

What a chip run proves is in chip_smoke.py itself; these pin the parts
of the seam that must hold everywhere: no fallback from a TPU lease to
the host, what a chip lease puts in a worker's environment, where the
compile cache goes, and a driver process that never opens a jax backend.
"""

import json
import os
import subprocess
import sys
import time
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(**extra):
    env = {k: v for k, v in os.environ.items() if not k.startswith("RT_")}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env.update(extra)
    return env


def test_chip_smoke_refuses_a_leased_worker_on_the_host(tmp_path):
    """One fake chip, jax held to the CPU: the trainer's worker is
    leased a chip and comes up on the host.  chip_smoke.py must say so
    and fail, within seconds and before it builds any model — not run
    the model on the CPU."""
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=_env(RT_TPU_CHIPS_OVERRIDE="1"), cwd=tmp_path,
        capture_output=True, text=True, timeout=120,
    )
    elapsed = time.monotonic() - t0
    assert out.returncode != 0, out.stdout[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert "platform 'cpu'" in last["error"], last
    assert "compiled" not in out.stdout  # no step was built, let alone run
    assert elapsed < 60, elapsed


class TestCompileCacheHelper:
    @pytest.fixture
    def jax_cache_config(self):
        import jax

        was = jax.config.jax_compilation_cache_dir
        yield jax.config
        jax.config.update("jax_compilation_cache_dir", was)

    def test_placed_from_outside_nothing_is_set_in_code(
        self, tmp_path, monkeypatch, jax_cache_config
    ):
        from ray_tpu.util import compile_cache

        # jax reads the variable itself (at import); configure() must
        # leave the config alone whatever it holds
        before = jax_cache_config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.configure() == str(tmp_path)
        assert jax_cache_config.jax_compilation_cache_dir == before

    def test_default_is_the_checkout_whatever_the_process(
        self, monkeypatch, jax_cache_config
    ):
        from ray_tpu.util import compile_cache

        want = os.path.join(REPO, ".jax_cache")
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert compile_cache.configure() == want
        assert jax_cache_config.jax_compilation_cache_dir == want
        # and from another pid, another working directory
        env = _env()
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        out = subprocess.run(
            [sys.executable, "-c",
             "import os; from ray_tpu.util import compile_cache as c; "
             "print(os.getpid(), c.default_dir())"],
            env=env, cwd="/", capture_output=True, text=True, timeout=60,
            check=True,
        )
        pid, path = out.stdout.split()
        assert int(pid) != os.getpid() and path == want


class TestAccelEnvFor:
    """What a chip lease puts in the worker's environment on a four-chip
    host: a subset needs its shape beside the chip ids (else libtpu
    sizes the process for the whole host, and refuses a second one at
    its host-wide lock); the whole host needs nothing more."""

    def _raylet(self, tmp_path, monkeypatch, n_host=4):
        from ray_tpu.core.raylet import Raylet

        monkeypatch.delenv("RT_TPU_JAX_PLATFORM", raising=False)
        return Raylet(
            "127.0.0.1:1", resources={"CPU": 1, "TPU": n_host},
            session_dir=str(tmp_path),
        )

    @pytest.mark.parametrize("n_chips,bounds", [
        (1, "1,1,1"), (2, "1,2,1"), (4, None),
    ])
    def test_subset_sizes(self, tmp_path, monkeypatch, n_chips, bounds):
        r = self._raylet(tmp_path, monkeypatch)
        envs = [r._accel_env_for({"TPU": n_chips}) for _ in range(4 // n_chips)]
        leased = [e["TPU_VISIBLE_CHIPS"].split(",") for e in envs]
        assert all(len(c) == n_chips for c in leased)
        assert sorted(sum(leased, [])) == ["0", "1", "2", "3"]
        for e in envs:
            assert e["JAX_PLATFORMS"] == "tpu"
            # both of libtpu's names for each bound: the host's own
            # environment carries the older ones, sized for four chips
            for var in ("TPU_CHIPS_PER_PROCESS_BOUNDS",
                        "TPU_CHIPS_PER_HOST_BOUNDS"):
                assert e.get(var) == bounds
            for var in ("TPU_PROCESS_BOUNDS", "TPU_HOST_BOUNDS"):
                assert e.get(var) == (bounds and "1,1,1")
        with pytest.raises(Exception, match="exhausted"):
            r._accel_env_for({"TPU": n_chips})

    def test_two_chip_lease_takes_an_aligned_pair(self, tmp_path, monkeypatch):
        r = self._raylet(tmp_path, monkeypatch)
        assert r._accel_env_for({"TPU": 1})["TPU_VISIBLE_CHIPS"] == "0"
        # not "1,2": those sit on a diagonal of the 2x2
        assert r._accel_env_for({"TPU": 2})["TPU_VISIBLE_CHIPS"] == "2,3"
        # and the next single chip fills the broken pair
        assert r._accel_env_for({"TPU": 1})["TPU_VISIBLE_CHIPS"] == "1"

    def test_lease_waits_for_a_pair_not_for_two_chips(
        self, tmp_path, monkeypatch
    ):
        """Four one-chip workers are killed and a two-chip lease arrives
        while they exit: the chips come back one at a time, and with 0
        and 3 back there are two free chips but no pair.  (Seen on the
        four-chip host: "no aligned block of 2 among free chips [0, 3]".)"""
        import asyncio

        async def scenario():
            r = self._raylet(tmp_path, monkeypatch)
            r._tpu_chips_free = {0, 3}

            async def comes_back(chip, after):
                await asyncio.sleep(after)
                r._tpu_chips_free.add(chip)

            for chip, after in ((2, 0.05), (1, 0.1)):
                task = asyncio.ensure_future(comes_back(chip, after))
                r._retiring[task] = types.SimpleNamespace(tpu_chips=(chip,))
                task.add_done_callback(r._retiring.pop)
            await r._await_reclaimed_chips(2)
            return r._accel_env_for({"TPU": 2})["TPU_VISIBLE_CHIPS"]

        assert asyncio.run(scenario()) == "2,3"

    def test_a_shape_libtpu_cannot_open_is_refused(self, tmp_path, monkeypatch):
        r = self._raylet(tmp_path, monkeypatch)
        with pytest.raises(Exception, match="not a shape"):
            r._accel_env_for({"TPU": 3})
        assert len(r._tpu_chips_free) == 4  # nothing leaked

    def test_no_tpu_resource_is_a_cpu_lease(self, tmp_path, monkeypatch):
        r = self._raylet(tmp_path, monkeypatch)
        assert r._accel_env_for({"CPU": 1}) == {"JAX_PLATFORMS": "cpu"}


def test_worker_cannot_be_moved_off_an_initialised_backend():
    """_apply_jax_platform fails the lease, where it used to log a
    warning and carry on on whatever platform the worker had."""
    import jax

    from ray_tpu.core.worker_main import _apply_jax_platform

    jax.devices()  # this process now runs on the cpu, for good
    _apply_jax_platform({"JAX_PLATFORMS": "cpu"})
    with pytest.raises(RuntimeError, match="already initialised 'cpu'"):
        _apply_jax_platform({"JAX_PLATFORMS": "tpu"})
    with pytest.raises(RuntimeError, match="already initialised 'cpu'"):
        _apply_jax_platform({"JAX_PLATFORMS": "tpu,cpu"})
    assert jax.config.jax_platforms == "cpu"


DRIVER_ENTRY_POINTS = ["init", "JaxTrainer.fit", "serve.run", "init_pp_params"]

_DRIVER_SRC = """
import os
import ray_tpu
from jax._src import xla_bridge
from ray_tpu import serve, train
from ray_tpu.models import gpt2
from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
from ray_tpu.train.pipeline import PipelineConfig, init_pp_params


def after(step, ok):
    print("AFTER", step, "ok" if ok else "WRONG", "backends_initialised",
          xla_bridge.backends_are_initialized(), flush=True)


def loop(config):
    import jax.numpy as jnp
    train.report({"x": float(jnp.ones(3).sum()),
                  "cache": os.environ.get("JAX_COMPILATION_CACHE_DIR")})


@serve.deployment
class Echo:
    def __call__(self, x):
        return x


ray_tpu.init(num_cpus=4, num_tpus=0)
after("init", ray_tpu.is_initialized())
result = JaxTrainer(
    loop, scaling_config=ScalingConfig(num_workers=1),
    run_config=RunConfig(name="t", storage_path=os.environ["STORAGE"]),
).fit()
# the worker ran jax, and a cache directory placed from outside reached it
after("JaxTrainer.fit", result.error is None
      and result.metrics.get("x") == 3.0
      and result.metrics.get("cache") == os.environ["JAX_COMPILATION_CACHE_DIR"])
handle = serve.run(Echo.bind(), name="echo", route_prefix=None)
after("serve.run", handle.remote(7).result(timeout_s=60) == 7)
serve.shutdown()
pp = init_pp_params(PipelineConfig(model_config=gpt2.GPTConfig.tiny()))
after("init_pp_params", bool(pp["stages"]) and bool(pp["tail"]))
ray_tpu.shutdown()
"""


@pytest.fixture(scope="module")
def driver_run(tmp_path_factory):
    """One driver process that goes through every entry point the chip
    paths use, saying after each whether it has opened a jax backend."""
    tmp = tmp_path_factory.mktemp("driver")
    out = subprocess.run(
        [sys.executable, "-c", _DRIVER_SRC],
        env=_env(JAX_COMPILATION_CACHE_DIR=str(tmp / "jaxcache"),
                 STORAGE=str(tmp)),
        cwd=tmp, capture_output=True, text=True, timeout=300,
    )
    return out


@pytest.mark.parametrize("entry_point", DRIVER_ENTRY_POINTS)
def test_driver_never_initialises_a_jax_backend(driver_run, entry_point):
    """The chips belong to the workers: a driver that had opened a jax
    backend — in init, a trainer fit, serve.run or pipeline parameter
    init — would hold them, and the workers it starts next could not."""
    lines = [
        line for line in driver_run.stdout.splitlines()
        if line.startswith(f"AFTER {entry_point} ")
    ]
    assert lines, (driver_run.stdout[-2000:], driver_run.stderr[-3000:])
    assert lines[0].split()[2:] == ["ok", "backends_initialised", "False"], (
        lines[0], driver_run.stderr[-2000:]
    )
