"""Multi-node cluster harness: many raylets on one machine.

Role-equivalent of ray: python/ray/cluster_utils.py:135 (Cluster,
add_node:201) — the workhorse of the reference's scheduler/failover tests.
Each add_node() starts a real raylet subprocess with its own shm store and
resource set, all registered to one GCS, so multi-node scheduling, object
transfer, placement groups, and node-death paths run for real on a single
host (e.g. CPU-only CI, or one TPU-VM).
"""

from __future__ import annotations

import subprocess
import time
from dataclasses import dataclass
from typing import Dict, List, Optional

from ray_tpu.core import node as node_mod


@dataclass
class ClusterNode:
    node_id: str
    address: str
    store_path: str
    proc: subprocess.Popen
    resources: Dict[str, float]

    def kill(self, graceful: bool = True):
        if not graceful and self.proc.poll() is None:
            # a crash: no close(), so the workers, orphans now, leave
            # when they see the connection drop
            self.proc.kill()
        node_mod.stop_processes([self.proc], node_mod.RAYLET_STOP_GRACE_S)
        node_mod.unlink_arena_of(self.proc, self.store_path)


class Cluster:
    def __init__(
        self,
        initialize_head: bool = False,
        connect: bool = False,
        head_node_args: Optional[dict] = None,
    ):
        self.session_dir = node_mod.default_session_dir()
        self.gcs_proc, self.address = node_mod.start_gcs(self.session_dir)
        self._nodes: List[ClusterNode] = []
        self.head_node: Optional[ClusterNode] = None
        self._connected = False
        if initialize_head:
            self.head_node = self.add_node(**(head_node_args or {}))
            if connect:
                self.connect()

    @property
    def gcs_address(self) -> str:
        return self.address

    def add_node(
        self,
        num_cpus: float = 1,
        num_tpus: float = 0,
        resources: Optional[Dict[str, float]] = None,
        labels: Optional[Dict[str, str]] = None,
        object_store_bytes: int = 0,
    ) -> ClusterNode:
        res = dict(resources or {})
        res["CPU"] = float(num_cpus)
        if num_tpus:
            res["TPU"] = float(num_tpus)
        proc, address, node_id, store_path = node_mod.start_raylet(
            self.address,
            self.session_dir,
            res,
            labels=labels,
            store_capacity=object_store_bytes,
        )
        node = ClusterNode(
            node_id=node_id,
            address=address,
            store_path=store_path,
            proc=proc,
            resources=res,
        )
        self._nodes.append(node)
        if self.head_node is None:
            self.head_node = node
        return node

    def remove_node(self, node: ClusterNode, allow_graceful: bool = True):
        """Kill a raylet (and its workers); the GCS sees a node death."""
        node.kill(graceful=allow_graceful)
        if node in self._nodes:
            self._nodes.remove(node)
        if self.head_node is node:
            self.head_node = self._nodes[0] if self._nodes else None

    def wait_for_nodes(self, timeout: float = 30.0) -> None:
        """Block until every added node is alive in the GCS view."""
        import ray_tpu

        deadline = time.monotonic() + timeout
        want = {n.node_id for n in self._nodes}
        alive: set = set()
        while time.monotonic() < deadline:
            if self._connected:
                alive = {
                    n["node_id"] for n in ray_tpu.nodes() if n["alive"]
                }
            else:
                alive = set(self._query_alive())
            if want <= alive:
                return
            time.sleep(0.1)
        raise TimeoutError(
            f"nodes never all registered: want {want}, alive {alive}"
        )

    def _query_alive(self) -> List[str]:
        import asyncio

        from ray_tpu.core import rpc

        async def go():
            conn = await rpc.connect(self.address)
            try:
                nodes = await conn.call("get_nodes", {})
            finally:
                await conn.close()
            return [n["node_id"] for n in nodes if n["alive"]]

        return asyncio.run(go())

    def connect(self):
        """Attach this process as a driver to the cluster."""
        import ray_tpu

        ray_tpu.init(address=self.address)
        self._connected = True

    def kill_gcs(self):
        """kill -9 the GCS process (head fault injection)."""
        if self.gcs_proc.poll() is None:
            self.gcs_proc.kill()
            self.gcs_proc.wait(timeout=5)

    def restart_gcs(self, timeout: float = 30.0):
        """Restart the GCS on the SAME port with the same session dir, so
        raylets/drivers holding ReconnectingConnections re-attach and the
        checkpoint restores cluster state (ray: GCS FT with external Redis;
        here the CheckpointStore under the session dir)."""
        self.kill_gcs()
        host, port_s = self.address.rsplit(":", 1)
        deadline = time.monotonic() + timeout
        last_exc: Optional[BaseException] = None
        while time.monotonic() < deadline:
            try:
                self.gcs_proc, addr = node_mod.start_gcs(
                    self.session_dir, host=host, port=int(port_s)
                )
                assert addr == self.address, (addr, self.address)
                return
            except Exception as e:  # port may linger in TIME_WAIT briefly
                last_exc = e
                time.sleep(0.3)
        raise RuntimeError(f"GCS restart failed: {last_exc!r}")

    def shutdown(self):
        """Tear down all raylets and the GCS."""
        node_mod.stop_processes(
            [node.proc for node in self._nodes], node_mod.RAYLET_STOP_GRACE_S
        )
        for node in self._nodes:
            node_mod.unlink_arena_of(node.proc, node.store_path)
        self._nodes.clear()
        self.head_node = None
        node_mod.stop_processes([self.gcs_proc], node_mod.GCS_STOP_GRACE_S)
