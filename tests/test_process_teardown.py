"""A chip is free when its holder has been reaped: the one helper that
ends processes (core/node.py), the raylet's one way to retire a worker,
what ``ray_tpu.shutdown()`` leaves behind (nothing), and the lease that
waits for its chips' device files (accelerators/tpu.py)."""

import asyncio
import errno
import os
import signal
import subprocess
import sys
import time

import pytest

from ray_tpu.common.config import cfg
from ray_tpu.common.ids import WorkerID
from ray_tpu.core import node as node_mod

#: a child that lets go of what it holds DIE_S after SIGTERM, as a worker
#: with chips mapped does, and one that never does
DIE_S = 1.0
_SLOW_TO_DIE = (
    "import signal, sys, time\n"
    "def term(*_):\n"
    f"    time.sleep({DIE_S}); sys.exit(0)\n"
    "signal.signal(signal.SIGTERM, term)\n"
    "print('up', flush=True); time.sleep(600)\n"
)
_IGNORES_SIGTERM = (
    "import signal, time\n"
    "signal.signal(signal.SIGTERM, signal.SIG_IGN)\n"
    "print('up', flush=True); time.sleep(600)\n"
)
_EXITS_ON_SIGTERM = "import time; print('up', flush=True); time.sleep(600)\n"


def _child(src: str) -> subprocess.Popen:
    proc = subprocess.Popen(
        [sys.executable, "-c", src], stdout=subprocess.PIPE, text=True
    )
    assert proc.stdout.readline().strip() == "up"  # its handlers are set
    return proc


def _state(pid: int) -> str:
    """'' once the pid has left /proc, else its state letter (Z: zombie)."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0]
    except OSError:
        return ""


def _descendants(root: int) -> list:
    kids: dict = {}
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/stat") as f:
                ppid = int(f.read().rsplit(")", 1)[1].split()[1])
        except (OSError, ValueError, IndexError):
            continue
        kids.setdefault(ppid, []).append(int(pid))
    out, todo = [], [root]
    while todo:
        for kid in kids.get(todo.pop(), []):
            out.append(kid)
            todo.append(kid)
    return out


class TestStopProcesses:
    GRACE_S = 1.0

    @pytest.mark.parametrize("src,killed,at_least,at_most", [
        (_EXITS_ON_SIGTERM, False, 0.0, 0.9),
        (_IGNORES_SIGTERM, True, GRACE_S, GRACE_S + 2.0),
        (None, False, 0.0, 0.5),  # already dead, reaped or not
    ], ids=["exits_on_sigterm", "ignores_sigterm", "already_dead"])
    def test_returns_with_every_process_reaped(
        self, src, killed, at_least, at_most
    ):
        proc = _child(src or _EXITS_ON_SIGTERM)
        if src is None:
            proc.kill()
            while _state(proc.pid) not in ("Z", ""):
                time.sleep(0.01)
        t0 = time.monotonic()
        node_mod.stop_processes([proc], self.GRACE_S)
        took = time.monotonic() - t0
        assert proc.returncode is not None  # poll() has answered
        assert _state(proc.pid) == ""  # gone, not a zombie
        assert (proc.returncode == -signal.SIGKILL) == (killed or src is None)
        assert at_least <= took <= at_most

    def test_one_deadline_for_all(self):
        """Three that ignore SIGTERM cost one grace, not three; one that
        needs most of the grace to die is not killed for the others."""
        stubborn = [_child(_IGNORES_SIGTERM) for _ in range(3)]
        slow = _child(_SLOW_TO_DIE)
        t0 = time.monotonic()
        node_mod.stop_processes(stubborn + [slow], DIE_S + 0.5)
        took = time.monotonic() - t0
        assert [p.returncode for p in stubborn] == [-signal.SIGKILL] * 3
        assert slow.returncode == 0
        assert all(_state(p.pid) == "" for p in stubborn + [slow])
        assert DIE_S + 0.5 <= took <= DIE_S + 3.0

    def test_the_graces_nest(self):
        """Whoever stops a raylet gives it what it gives its workers,
        their reaping and its own close on top."""
        assert node_mod.RAYLET_STOP_GRACE_S > (
            node_mod.WORKER_STOP_GRACE_S + node_mod.REAP_CEILING_S
        )
        assert node_mod.REAP_CEILING_S >= 25  # four chips: 13-24 s (PERF.md)
        assert node_mod.GCS_STOP_GRACE_S > node_mod.WORKER_STOP_GRACE_S

    @pytest.mark.parametrize("raylet_rc", [0, -9], ids=["closed", "killed"])
    def test_node_group_stops_the_raylet_then_the_gcs(
        self, tmp_path, raylet_rc
    ):
        """... and unlinks the arena of a raylet that did not get to."""
        order = []

        class Proc:
            def __init__(self, name):
                self.name, self.returncode = name, None

            def poll(self):
                return self.returncode

            def terminate(self):
                order.append(self.name)

            def wait(self, timeout=None):
                self.returncode = raylet_rc if self.name == "raylet" else 0

        store = tmp_path / "rt_store_x"
        store.write_bytes(b"arena")
        group = node_mod.NodeProcessGroup(
            session_dir=str(tmp_path), gcs_address="", raylet_address="",
            node_id="x", store_path=str(store),
            gcs_proc=Proc("gcs"), raylet_proc=Proc("raylet"),
        )
        group.kill()
        assert order == ["raylet", "gcs"]
        assert store.exists() == (raylet_rc == 0)


def _raylet(tmp_path, n_host=4):
    """As tests/test_chip_smoke.py::TestAccelEnvFor builds its raylet: not
    started, no GCS, four chips to lease."""
    from ray_tpu.core.raylet import Raylet

    return Raylet(
        "127.0.0.1:1", resources={"CPU": 1, "TPU": n_host},
        session_dir=str(tmp_path), store_capacity=32 << 20,
    )


def _holder(r, src=_SLOW_TO_DIE):
    """A worker of ``r`` that holds all four chips."""
    from ray_tpu.core.raylet import WorkerEntry

    env = r._accel_env_for({"TPU": 4})
    w = WorkerEntry(
        worker_id=WorkerID.random(), proc=_child(src), bound_env=env,
        tpu_chips=(0, 1, 2, 3),
    )
    r.workers[w.worker_id] = w
    assert not r._tpu_chips_free
    return w


async def _longest_gap() -> float:
    """Until cancelled: the longest time this loop let 10 ms take."""
    worst, last = 0.0, time.monotonic()
    try:
        while True:
            await asyncio.sleep(0.01)
            now = time.monotonic()
            worst, last = max(worst, now - last), now
    except asyncio.CancelledError:
        return worst


class TestRayletRetiresItsWorkers:
    @pytest.mark.parametrize("retired_earlier", [False, True],
                             ids=["still_in_workers", "already_retiring"])
    def test_close_returns_when_the_chip_holder_is_reaped(
        self, tmp_path, retired_earlier
    ):
        """The holder takes a second to die.  Retired earlier
        (``ray_tpu.kill`` at the end of ``JaxTrainer.fit``) it is no
        longer in ``workers``: close() used to cancel its reclaim,
        SIGKILL it and wait for nothing."""

        async def scenario():
            r = _raylet(tmp_path)
            w = _holder(r)
            if retired_earlier:
                await r._on_worker_exit(w)
                assert w.worker_id not in r.workers and r._retiring
            ticker = asyncio.ensure_future(_longest_gap())
            t0 = time.monotonic()
            await r.close()
            took = time.monotonic() - t0
            ticker.cancel()
            return r, w, took, await ticker

        r, w, took, gap = asyncio.run(scenario())
        assert gap < 0.5, f"close() held the event loop for {gap:.2f} s"
        assert w.proc.returncode == 0  # died of SIGTERM, in its own time
        assert _state(w.proc.pid) == ""
        assert not r.workers and not r._retiring
        assert r._tpu_chips_free == {0, 1, 2, 3}
        assert DIE_S * 0.9 <= took <= DIE_S + 3.0

    def test_close_retires_all_at_once(self, tmp_path):
        async def scenario():
            from ray_tpu.core.raylet import WorkerEntry

            r = _raylet(tmp_path)
            for _ in range(4):
                w = WorkerEntry(WorkerID.random(), _child(_SLOW_TO_DIE))
                r.workers[w.worker_id] = w
            procs = [w.proc for w in r.workers.values()]
            ticker = asyncio.ensure_future(_longest_gap())
            t0 = time.monotonic()
            await r.close()
            took = time.monotonic() - t0
            ticker.cancel()
            return procs, took, await ticker

        procs, took, gap = asyncio.run(scenario())
        assert gap < 0.5, f"close() held the event loop for {gap:.2f} s"
        assert [p.returncode for p in procs] == [0] * 4
        assert all(_state(p.pid) == "" for p in procs)
        assert took <= DIE_S + 2.5  # one wait, not one a worker

    def test_a_worker_that_ignores_sigterm_is_killed_and_reaped(
        self, tmp_path, monkeypatch
    ):
        from ray_tpu.core import raylet as raylet_mod

        monkeypatch.setattr(raylet_mod, "WORKER_STOP_GRACE_S", 1.0)

        async def scenario():
            r = _raylet(tmp_path)
            w = _holder(r, _IGNORES_SIGTERM)
            w.container_kill_argv = ["true"]  # stands for `docker kill`
            await r._on_worker_exit(w)
            await asyncio.sleep(0.2)
            # still alive, so its chips are still its own
            assert w.proc.poll() is None and not r._tpu_chips_free
            assert w.container_kill_proc is None  # the grace is not over
            await asyncio.wait(list(r._retiring))
            return r, w

        r, w = asyncio.run(scenario())
        assert w.proc.returncode == -9 and _state(w.proc.pid) == ""
        # the kill at the end of the grace was _hard_kill_worker's, and
        # its command was reaped with the worker
        assert w.container_kill_proc.returncode == 0
        assert _state(w.container_kill_proc.pid) == ""
        assert r._tpu_chips_free == {0, 1, 2, 3}

    def test_gcs_lost_twice_in_one_tick_closes_once(self, tmp_path, monkeypatch):
        from ray_tpu.core import raylet as raylet_mod

        exits, closes = [], []
        monkeypatch.setattr(raylet_mod.os, "_exit", exits.append)

        async def scenario():
            r = _raylet(tmp_path)

            async def close():
                closes.append(1)

            r.close = close
            r._on_gcs_lost()
            r._on_gcs_lost()
            assert r._closing  # leases are refused from this tick on
            await r._exit_task

        asyncio.run(scenario())
        assert closes == [1] and exits == [1]

    def test_fence_hands_chips_back_only_from_a_reaped_holder(self, tmp_path):
        """_purge_for_fence hard-kills, as it must, but used to declare
        every chip free in the same breath."""

        async def scenario():
            from ray_tpu._native.store import ShmStore

            r = _raylet(tmp_path)
            r.store = ShmStore(r.store_path, r.store_capacity, create=True)
            w = _holder(r)
            await r._purge_for_fence("test")
            try:
                assert not r.workers
                # SIGKILLed at once, but nobody has reaped it yet: the
                # chips are still its own
                assert not r._tpu_chips_free
                assert list(r._retiring.values()) == [w]
                await asyncio.wait(list(r._retiring))
                assert w.proc.returncode == -9 and _state(w.proc.pid) == ""
                assert r._tpu_chips_free == {0, 1, 2, 3}
            finally:
                r.store.destroy()  # the arena the fence rebuilt

        asyncio.run(scenario())

    def test_a_lease_waits_for_retiring_chip_holders_only(self, tmp_path):
        from ray_tpu.core.raylet import WorkerEntry

        async def scenario():
            r = _raylet(tmp_path)
            holder = _holder(r)
            other = WorkerEntry(WorkerID.random(), _child(_IGNORES_SIGTERM))
            r._retire(other, hard=False)  # 5 s of grace: not waited for
            await r._on_worker_exit(holder)
            t0 = time.monotonic()
            await r._await_reclaimed_chips(4)
            took = time.monotonic() - t0
            free = set(r._tpu_chips_free)
            node_mod.stop_processes([other.proc], 0)
            return free, took

        free, took = asyncio.run(scenario())
        assert free == {0, 1, 2, 3} and took <= DIE_S + 2.5

    def test_a_lease_parked_across_close_spawns_nothing(
        self, tmp_path, monkeypatch
    ):
        """The lease entered before close(), waits for the very holder
        close() waits for, and wakes when close() has retired the last
        worker: one spawned then would be nobody's to reap."""
        from ray_tpu.core import rpc

        async def scenario():
            r = _raylet(tmp_path)
            await r._on_worker_exit(_holder(r))  # a second to die
            spawned = []
            monkeypatch.setattr(r, "_spawn_worker", spawned.append)
            lease = asyncio.ensure_future(r.rpc_lease_worker(
                None, {"resources": {"TPU": 4}, "lease_id": 1}
            ))
            await asyncio.sleep(0.1)
            assert not lease.done()  # parked in _await_reclaimed_chips
            await r.close()
            with pytest.raises(rpc.RpcError, match="closing; lease refused"):
                await lease
            return r, spawned

        r, spawned = asyncio.run(scenario())
        assert spawned == [] and not r.workers and not r._retiring
        assert r._tpu_chips_free == {0, 1, 2, 3}  # none picked for it

    def test_chips_of_a_worker_that_never_started_are_refunded(
        self, tmp_path, monkeypatch
    ):
        """The lease picked its chips, then its fresh worker died at
        start: only the fence's reset used to bring those back."""
        from ray_tpu.core import rpc

        async def scenario():
            r = _raylet(tmp_path)

            def spawn(**_):
                from ray_tpu.core.raylet import WorkerEntry

                w = WorkerEntry(WorkerID.random(), _child(_EXITS_ON_SIGTERM))
                node_mod.stop_processes([w.proc], 1)
                r.workers[w.worker_id] = w
                return w

            monkeypatch.setattr(r, "_spawn_worker", spawn)
            with pytest.raises(rpc.RpcError, match="exited at startup"):
                await r.rpc_lease_worker(
                    None, {"resources": {"TPU": 4}, "lease_id": 1}
                )
            return r

        assert asyncio.run(scenario())._tpu_chips_free == {0, 1, 2, 3}


@pytest.mark.parametrize("kill_first", [False, True],
                         ids=["actor_alive", "actor_killed_first"])
def test_shutdown_leaves_no_process_behind(kill_first):
    """init() + one actor + shutdown(), twice in one process: every
    descendant listed before is gone from /proc (not a zombie) the
    moment shutdown() returns."""
    import ray_tpu

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()

    @ray_tpu.remote
    class Holder:
        def pid(self):
            return os.getpid()

    mine = set(_descendants(os.getpid()))  # other tests' leftovers
    for _ in range(2):
        ray_tpu.init(num_cpus=2, num_tpus=0)
        try:
            actor = Holder.remote()
            pid = ray_tpu.get(actor.pid.remote())
            started = set(_descendants(os.getpid())) - mine
            assert pid in started and len(started) >= 3  # gcs, raylet, worker
            if kill_first:
                ray_tpu.kill(actor)
        finally:
            t0 = time.monotonic()
            ray_tpu.shutdown()
            took = time.monotonic() - t0
        left = {p: _state(p) for p in started if _state(p)}
        assert not left, f"still in /proc after shutdown(): {left}"
        assert took < 10


def test_a_stopped_gcs_takes_its_jobs_with_it():
    """A submitted job's entrypoint is the GCS's child: only the GCS
    can reap it, and its default SIGTERM action left it running."""
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.job_submission import JobSubmissionClient

    cluster = Cluster(initialize_head=True, connect=False,
                      head_node_args={"num_cpus": 1})
    try:
        client = JobSubmissionClient(cluster.gcs_address)
        client.submit_job(
            entrypoint=f"exec {sys.executable} -c 'import time; time.sleep(600)'"
        )
        deadline = time.monotonic() + 30
        while not (jobs := _descendants(cluster.gcs_proc.pid)):
            assert time.monotonic() < deadline, "the job never started"
            time.sleep(0.05)
    finally:
        cluster.shutdown()
    assert cluster.gcs_proc.returncode == 0
    assert {p: _state(p) for p in jobs if _state(p)} == {}


class TestLeaseWaitsForItsChips:
    @pytest.fixture
    def tpu(self, monkeypatch):
        from ray_tpu.accelerators import tpu

        monkeypatch.setenv("TPU_VISIBLE_CHIPS", "1")
        monkeypatch.setattr(tpu, "_chips_opened", False)
        monkeypatch.setattr(
            tpu, "_device_files", lambda: ["/dev/vfio/7", "/dev/vfio/9"]
        )
        return tpu

    def _chip_open_spans(self):
        from ray_tpu.util import tracing

        return [s for s in tracing.spans() if s["name"] == "rt.start.chip_open"]

    def test_busy_twice_then_free(self, tpu, monkeypatch):
        import jax

        asked, opened = [], []
        answers = [errno.EBUSY, errno.EBUSY, 0]
        monkeypatch.setattr(
            tpu, "_open_errno",
            lambda path: asked.append(path) or answers.pop(0),
        )
        devices = jax.devices
        monkeypatch.setattr(
            jax, "devices", lambda *a: opened.append(a) or devices(*a)
        )
        before = len(self._chip_open_spans())
        tpu.open_leased_chips()
        tpu.open_leased_chips()  # the second time, nothing
        assert asked == ["/dev/vfio/9"] * 3  # chip 1 is the second file
        assert len(opened) == 1
        spans = self._chip_open_spans()[before:]
        assert len(spans) == 1
        attrs = spans[0]["attributes"]
        assert 0.15 <= attrs["waited_s"] <= 2.0 and attrs["chips"] == "1"
        # the wait is inside the span (waited_s is rounded to the ms)
        assert spans[0]["duration_ms"] >= attrs["waited_s"] * 1e3 - 1

    def test_free_chips_cost_one_open_each(self, tpu, monkeypatch):
        asked = []
        monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0,1")
        monkeypatch.setattr(
            tpu, "_open_errno", lambda path: asked.append(path) or 0
        )
        before = len(self._chip_open_spans())
        tpu.open_leased_chips()
        assert asked == ["/dev/vfio/7", "/dev/vfio/9"]
        assert self._chip_open_spans()[before]["attributes"]["waited_s"] < 0.1

    @pytest.mark.parametrize("answer", [errno.EACCES, errno.ENOENT])
    def test_any_other_error_is_left_to_jax(self, tpu, monkeypatch, answer):
        monkeypatch.setattr(tpu, "_open_errno", lambda path: answer)
        assert tpu._wait_until_free("0,1") < 0.1

    def test_busy_for_ever_names_the_file(self, tpu, monkeypatch):
        import jax

        monkeypatch.setattr(tpu, "_open_errno", lambda path: errno.EBUSY)
        monkeypatch.setattr(tpu, "_holder_of", lambda path: " (held by pid 42)")
        monkeypatch.setattr(
            jax, "devices", lambda *a: pytest.fail("the backend was opened")
        )
        monkeypatch.setitem(cfg._values, "worker_start_timeout_s", 0.3)
        with pytest.raises(RuntimeError, match=(
            r"chip 1 .*/dev/vfio/9 is still busy after .*held by pid 42"
        )):
            tpu.open_leased_chips()
        assert self._chip_open_spans()[-1]["attributes"]["error"] == "RuntimeError"

    def test_fake_chips_have_no_file_to_wait_for(self, monkeypatch):
        """Tier-1's RT_TPU_CHIPS_OVERRIDE chips: ids without device files."""
        from ray_tpu.accelerators import tpu

        monkeypatch.setattr(tpu, "_device_files", lambda: [])
        monkeypatch.setattr(
            tpu, "_open_errno", lambda path: pytest.fail("nothing to probe")
        )
        assert tpu._wait_until_free("0,1,2,3") < 0.1

    def test_holder_is_read_from_proc(self, tmp_path):
        from ray_tpu.accelerators import tpu

        path = tmp_path / "chip"
        path.write_bytes(b"")
        with open(path):
            assert tpu._holder_of(str(path)) == f" (held by pid {os.getpid()})"
        assert tpu._holder_of(str(path)) == ""

    def test_device_files_are_in_chip_order(self, monkeypatch):
        import glob

        from ray_tpu.accelerators import tpu

        found = {
            "/dev/accel[0-9]*": [],
            "/dev/vfio/[0-9]*": ["/dev/vfio/10", "/dev/vfio/2", "/dev/vfio/0"],
        }
        monkeypatch.setattr(glob, "glob", lambda pat: found[pat])
        assert tpu._device_files() == [
            "/dev/vfio/0", "/dev/vfio/2", "/dev/vfio/10"
        ]
        mgr = tpu.TPUAcceleratorManager()
        monkeypatch.setitem(cfg._values, "tpu_chips_override", -1)
        assert mgr.num_chips() == 3 and mgr.detected_by == "/dev/vfio/*"
        found["/dev/accel[0-9]*"] = ["/dev/accel1", "/dev/accel0"]
        mgr = tpu.TPUAcceleratorManager()
        assert mgr.num_chips() == 2 and mgr.detected_by == "/dev/accel*"


def test_a_raylet_that_lost_its_gcs_closes_before_it_exits(monkeypatch):
    """It used to SIGTERM its workers and _exit: the workers outlived
    it and its arena stayed in /dev/shm."""
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    monkeypatch.setenv("RT_GCS_RECONNECT_MAX_DOWNTIME_S", "1")
    cluster = Cluster(initialize_head=True, connect=True,
                      head_node_args={"num_cpus": 1})
    try:

        @ray_tpu.remote
        class Holder:
            def pid(self):
                return os.getpid()

        actor = Holder.remote()
        pid = ray_tpu.get(actor.pid.remote())
        node = cluster.head_node
        assert os.path.exists(node.store_path)
        cluster.kill_gcs()
        assert node.proc.wait(timeout=60) == 1
        assert _state(pid) == "", "a worker outlived its raylet"
        assert not os.path.exists(node.store_path)
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()
