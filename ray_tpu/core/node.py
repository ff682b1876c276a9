"""Node bring-up: spawn GCS and raylet processes for a head or worker node.

Role-equivalent of ray: python/ray/_private/node.py:37 and services.py
(start_gcs_server:1432, start_raylet:1496).
"""

from __future__ import annotations

import contextlib
import json
import logging
import os
import subprocess
import sys
import time
from dataclasses import dataclass
from typing import Callable, Dict, Iterable, Optional

from ray_tpu.common.ids import NodeID
from ray_tpu.util import tracing

logger = logging.getLogger(__name__)


def _read_tagged_line(proc: subprocess.Popen, tag: str, timeout: float) -> str:
    """Read lines from proc stdout until `tag=` appears."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(
                f"process exited (code {proc.returncode}) before reporting {tag}"
            )
        line = proc.stdout.readline()
        if not line:
            time.sleep(0.01)
            continue
        line = line.decode() if isinstance(line, bytes) else line
        if line.startswith(tag + "="):
            return line.strip().split("=", 1)[1]
    raise TimeoutError(f"timed out waiting for {tag} from subprocess")


def default_session_dir() -> str:
    return os.path.join(
        "/tmp", "ray_tpu", f"session_{time.strftime('%Y%m%d-%H%M%S')}_{os.getpid()}"
    )


#: A chip is free when the process that held it has been reaped by its
#: parent; whatever ends a process keeps that rule, with these.
#: SIGTERM-to-SIGKILL grace a raylet gives a worker it retires:
WORKER_STOP_GRACE_S = 5.0
#: what the kernel may take to release a SIGKILLed process's devices (3-6 s
#: for a holder of one v5e chip, 13-24 s of four: PERF.md section 6) before
#: whoever waits for it says so, loudly, and goes on waiting:
REAP_CEILING_S = 30.0
#: grace for a raylet: its workers' grace, their reaping, its own close()
RAYLET_STOP_GRACE_S = WORKER_STOP_GRACE_S + REAP_CEILING_S + 5.0
#: ... and for a GCS, which gives its jobs' entrypoints a worker's grace
GCS_STOP_GRACE_S = WORKER_STOP_GRACE_S + 5.0


def stop_processes(
    procs: Iterable[subprocess.Popen], grace_s: float,
    kill: Callable[[subprocess.Popen], None] = subprocess.Popen.kill,
) -> None:
    """End ``procs`` and return only when every one has been reaped:
    SIGTERM all that live, wait for all against one deadline, ``kill``
    what is left (SIGKILL; the raylet's also knows containers), then wait
    until ``poll()`` answers for each (a killed process cannot refuse:
    that wait is the kernel releasing its devices).  It blocks: an event
    loop runs it in an executor (``Raylet._reap``, ``rpc_stop_job``)."""
    procs = [p for p in procs if p.poll() is None]
    for p in procs:
        p.terminate()
    deadline = time.monotonic() + grace_s
    killed = []
    for p in procs:
        try:
            p.wait(max(0.0, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            kill(p)
            killed.append(p)
    for p in killed:
        since = time.monotonic()
        while True:
            try:
                p.wait(REAP_CEILING_S)
                break
            except subprocess.TimeoutExpired:
                logger.error(
                    "pid %d is not reaped %.0f s after SIGKILL: the kernel "
                    "still holds what it had open (a chip it held is NOT "
                    "free); waiting on", p.pid, time.monotonic() - since,
                )


def unlink_arena_of(raylet_proc: subprocess.Popen, store_path: str) -> None:
    """A stopped raylet that did not get to its close() (SIGKILLed or
    crashed: any exit but 0) left its arena in /dev/shm."""
    if raylet_proc.returncode != 0:
        with contextlib.suppress(FileNotFoundError):
            os.unlink(store_path)


@dataclass
class NodeProcessGroup:
    """Handles to the subprocesses composing one logical node (plus the GCS
    when this is the head)."""

    session_dir: str
    gcs_address: str
    raylet_address: str
    node_id: str
    store_path: str
    gcs_proc: Optional[subprocess.Popen] = None
    raylet_proc: Optional[subprocess.Popen] = None

    def kill(self):
        """End this node's processes: the raylet, and when it has gone
        (its close() talks to the GCS) the GCS.  Returns with both, and
        all the raylet started, reaped."""
        if self.raylet_proc is not None:
            stop_processes([self.raylet_proc], RAYLET_STOP_GRACE_S)
            unlink_arena_of(self.raylet_proc, self.store_path)
        if self.gcs_proc is not None:
            stop_processes([self.gcs_proc], GCS_STOP_GRACE_S)


def start_gcs(session_dir: str, host: str = "127.0.0.1", port: int = 0) -> tuple:
    os.makedirs(session_dir, exist_ok=True)
    log = open(os.path.join(session_dir, "gcs.log"), "ab")
    with tracing.startup("rt.start.gcs"):  # spawned -> listening
        proc = subprocess.Popen(
            [
                sys.executable, "-m", "ray_tpu.core.gcs",
                "--host", host, "--port", str(port),
                "--session-dir", session_dir,
            ],
            stdout=subprocess.PIPE,
            stderr=log,
            env=_control_plane_env(),
        )
        log.close()
        try:
            address = _read_tagged_line(proc, "GCS_ADDRESS", 30)
        except BaseException:
            stop_processes([proc], GCS_STOP_GRACE_S)
            raise
    return proc, address


def start_raylet(
    gcs_address: str,
    session_dir: str,
    resources: Dict[str, float],
    labels: Optional[Dict[str, str]] = None,
    host: str = "127.0.0.1",
    store_capacity: int = 0,
    node_id: Optional[str] = None,
    extra_env: Optional[Dict[str, str]] = None,
) -> tuple:
    os.makedirs(session_dir, exist_ok=True)
    log = open(os.path.join(session_dir, "raylet.log"), "ab")
    cmd = [
        sys.executable,
        "-m",
        "ray_tpu.core.raylet",
        "--gcs", gcs_address,
        "--host", host,
        "--resources", json.dumps(resources),
        "--labels", json.dumps(labels or {}),
        "--store-capacity", str(store_capacity),
        "--session-dir", session_dir,
    ]
    if node_id:
        cmd += ["--node-id", node_id]
    env = _control_plane_env()
    if extra_env:
        # slice identity for the raylet and its workers (TPU_NAME etc. —
        # what accelerators/tpu.py turns into slice/head resources)
        env.update(extra_env)
    with tracing.startup("rt.start.raylet"):  # spawned -> registered
        # the raylet's own start-up spans (its workers') hang under this one
        env[tracing.START_ENV] = tracing.inject()[tracing.CARRIER_KEY]
        proc = subprocess.Popen(
            cmd, stdout=subprocess.PIPE, stderr=log, env=env
        )
        log.close()
        try:
            address = _read_tagged_line(proc, "RAYLET_ADDRESS", 60)
            nid = _read_tagged_line(proc, "RAYLET_NODE_ID", 10)
        except BaseException:
            stop_processes([proc], RAYLET_STOP_GRACE_S)
            raise
    store_path = f"/dev/shm/rt_store_{nid[:12]}"
    return proc, address, nid, store_path


def _pythonpath_with_pkg() -> str:
    """PYTHONPATH that lets subprocesses import ray_tpu even when the driver
    added the repo to sys.path manually."""
    import ray_tpu

    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(ray_tpu.__file__)))
    existing = os.environ.get("PYTHONPATH", "")
    parts = [pkg_root] + ([existing] if existing else [])
    return os.pathsep.join(parts)


def _control_plane_env() -> dict:
    """Control-plane processes must never touch the TPU (one process owns the
    chips); pin them to CPU-only jax in case anything imports it."""
    env = dict(os.environ)
    # What the raylet re-points TPU-leased workers at (_accel_env_for).
    # It is anything but "tpu" only under tier-1's fake-chip tests: they
    # run with JAX_PLATFORMS=cpu and RT_TPU_CHIPS_OVERRIDE chips, and
    # their TPU leases must come up on the host (tests/test_chip_smoke.py
    # proves that chip_smoke.py refuses such a worker).
    env.setdefault(
        "RT_TPU_JAX_PLATFORM", os.environ.get("JAX_PLATFORMS") or "tpu"
    )
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _pythonpath_with_pkg()
    return env


def detect_resources(
    num_cpus=None, num_tpus=None, extra=None, tpu_manager=None
) -> Dict[str, float]:
    """This node's resources.  With ``num_tpus`` unset the chips are
    detected; pass a ``TPUAcceleratorManager`` as ``tpu_manager`` to read
    afterwards which detection step answered (``detected_by``)."""
    res: Dict[str, float] = dict(extra or {})
    res["CPU"] = num_cpus if num_cpus is not None else float(os.cpu_count() or 1)
    if num_tpus is None:
        from ray_tpu.accelerators.tpu import TPUAcceleratorManager

        mgr = tpu_manager or TPUAcceleratorManager()
        num_tpus = mgr.num_chips()
        if num_tpus:
            res.update(mgr.extra_resources())
    if num_tpus:
        res["TPU"] = num_tpus
    res.setdefault("memory", float(_total_memory_bytes()))
    return res


def _total_memory_bytes() -> int:
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return 8 << 30
