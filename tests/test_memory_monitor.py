"""Memory monitor + OOM worker-killing tests.

Mirrors ray: python/ray/tests/test_memory_pressure.py on the fake-usage
override: flip a file to a pressure value, watch the raylet kill a
worker, and watch the core's retry machinery finish the task anyway.
"""

import os
import time

import pytest

import ray_tpu
from ray_tpu.common.config import cfg
from ray_tpu.core.memory_monitor import measure_usage_fraction


class TestMeasurement:
    def test_fake_file_override(self, tmp_path, monkeypatch):
        fake = tmp_path / "usage"
        fake.write_text("0.87")
        monkeypatch.setenv("RT_MEMORY_MONITOR_FAKE_USAGE_FILE", str(fake))
        cfg.reset()
        try:
            assert measure_usage_fraction() == pytest.approx(0.87)
            fake.write_text("bogus")
            assert measure_usage_fraction() == 0.0
        finally:
            monkeypatch.delenv("RT_MEMORY_MONITOR_FAKE_USAGE_FILE")
            cfg.reset()

    def test_real_measurement_sane(self):
        frac = measure_usage_fraction()
        assert 0.0 <= frac <= 1.5  # cgroup current can briefly exceed max


def test_a_worker_being_handed_to_a_lease_is_not_the_idle_victim():
    """Under pressure the monitor kills idle workers first.  A worker
    that had said ``worker_ready`` and was not yet bound to the lease
    that spawned it counted as one: killed there, it took the grant
    with it, and the retry of the very task the kill was to make room
    for failed (`lease failed with SchedulingError:`, 60 s later)."""
    import asyncio

    from ray_tpu.common.ids import WorkerID
    from ray_tpu.core import raylet as raylet_mod
    from ray_tpu.core.memory_monitor import MemoryMonitor

    class Proc:
        poll = staticmethod(lambda: None)

    class Conn:
        closed = False

        def __init__(self):
            self.peer_info = {}
            self.binding = asyncio.Event()
            self.bound = asyncio.Event()

        async def call(self, method, payload):
            assert method == "bind_env"
            self.binding.set()
            await self.bound.wait()
            return True

    r = raylet_mod.Raylet.__new__(raylet_mod.Raylet)
    r.draining = r._fencing = r._closing = False
    r._idle_by_env, r.workers = {}, {}

    def spawn(**kw):
        w = raylet_mod.WorkerEntry(worker_id=WorkerID.random(), proc=Proc())
        r.workers[w.worker_id] = w
        return w

    r._spawn_worker = spawn
    monitor = MemoryMonitor(r)

    async def scenario():
        lease = asyncio.ensure_future(r.rpc_lease_worker(
            None, {"lease_id": 1, "resources": {"CPU": 1}}))
        await asyncio.sleep(0)
        (w,) = r.workers.values()
        assert monitor.pick_victim() == (None, "")  # still starting
        conn = Conn()
        await r.rpc_worker_ready(
            conn, {"worker_id": w.worker_id.binary(), "address": "w:1"})
        await asyncio.wait_for(conn.binding.wait(), 5)
        # ready, its lease waits for bind_env: nobody's idle worker
        assert not w.idle and monitor.pick_victim() == (None, "")
        conn.bound.set()
        await asyncio.wait_for(lease, 5)
        assert monitor.pick_victim() == (w, "busy")
        await r.rpc_release_worker(None, {"worker_id": w.worker_id.binary()})
        assert monitor.pick_victim() == (w, "idle")

    asyncio.run(scenario())


@pytest.fixture(scope="module")
def oom_cluster(tmp_path_factory):
    fake = tmp_path_factory.mktemp("oom") / "usage"
    fake.write_text("0.0")
    os.environ["RT_MEMORY_MONITOR_FAKE_USAGE_FILE"] = str(fake)
    os.environ["RT_MEMORY_MONITOR_INTERVAL_S"] = "0.2"
    os.environ["RT_MEMORY_MONITOR_KILL_GRACE_S"] = "0.5"
    ray_tpu.init(num_cpus=2, num_tpus=0)
    yield fake
    ray_tpu.shutdown()
    for k in (
        "RT_MEMORY_MONITOR_FAKE_USAGE_FILE",
        "RT_MEMORY_MONITOR_INTERVAL_S",
        "RT_MEMORY_MONITOR_KILL_GRACE_S",
    ):
        os.environ.pop(k, None)


class TestOomKilling:
    def test_pressure_kills_worker_and_task_retries(self, oom_cluster,
                                                    tmp_path):
        fake = oom_cluster
        marker = str(tmp_path / "attempted")

        @ray_tpu.remote
        def hog(marker_path):
            # first attempt parks forever (the "leak"); the retry, after
            # the monitor killed attempt one, returns immediately
            if os.path.exists(marker_path):
                return "recovered"
            with open(marker_path, "w") as f:
                f.write("1")
            time.sleep(300)
            return "never"

        ref = hog.options(max_retries=3).remote(marker)
        # wait until the first attempt is running (marker exists)
        deadline = time.time() + 60
        while not os.path.exists(marker) and time.time() < deadline:
            time.sleep(0.1)
        assert os.path.exists(marker), "task never started"
        fake.write_text("0.99")  # breach the threshold
        try:
            # give the monitor one interval+grace to kill the hog, then
            # drop the pressure so the RETRY isn't also hunted (on a
            # loaded host the fast retry can lose the race with the next
            # monitor sweep and exhaust its retries)
            time.sleep(3.0)
            fake.write_text("0.0")
            assert ray_tpu.get(ref, timeout=120) == "recovered"
        finally:
            fake.write_text("0.0")

    def test_oom_reason_reaches_driver_when_not_retriable(self, oom_cluster,
                                                          tmp_path):
        fake = oom_cluster
        started = str(tmp_path / "started2")

        @ray_tpu.remote
        def hog2(path):
            with open(path, "w") as f:
                f.write("1")
            time.sleep(300)

        ref = hog2.options(max_retries=0).remote(started)
        deadline = time.time() + 60
        while not os.path.exists(started) and time.time() < deadline:
            time.sleep(0.1)
        fake.write_text("0.99")
        try:
            with pytest.raises(Exception) as ei:
                ray_tpu.get(ref, timeout=120)
            assert "memory" in str(ei.value).lower()
        finally:
            fake.write_text("0.0")
