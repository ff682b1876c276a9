"""Node providers: how the autoscaler actually creates/destroys nodes.

Role-equivalent of the reference's NodeProvider plugin surface (ray:
python/ray/autoscaler/node_provider.py:23) with the launch-config
machinery dropped: a provider maps (node_type -> running raylet) and the
autoscaler owns all policy.  `LocalSubprocessProvider` is the
FakeMultiNodeProvider analogue (ray: autoscaler/_private/fake_multi_node/
node_provider.py) — it spawns real raylet subprocesses on this host, so
autoscaling tests exercise the same node lifecycle as production.
"""

from __future__ import annotations

import logging
import subprocess
import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from ray_tpu.core import node as node_mod

logger = logging.getLogger(__name__)


@dataclass
class ProviderNode:
    provider_id: str
    node_type: str
    node_id_hex: Optional[str] = None  # raylet's cluster node id, once known
    proc: Optional[subprocess.Popen] = None
    meta: dict = field(default_factory=dict)


class NodeProvider:
    """Interface the autoscaler drives.  Implementations: local
    subprocesses (below), GKE/GCE TPU slices (deployment-specific)."""

    def create_node(self, node_type: str, resources: Dict[str, float],
                    labels: Dict[str, str]) -> ProviderNode:
        raise NotImplementedError

    def terminate_node(self, node: ProviderNode) -> None:
        raise NotImplementedError

    def non_terminated_nodes(self) -> List[ProviderNode]:
        raise NotImplementedError


class LocalSubprocessProvider(NodeProvider):
    """Raylet subprocesses on the local host (tests / single TPU-VM)."""

    def __init__(self, gcs_address: str, session_dir: str):
        self.gcs_address = gcs_address
        self.session_dir = session_dir
        self._nodes: Dict[str, ProviderNode] = {}
        self._counter = 0
        self._lock = threading.Lock()

    def create_node(self, node_type, resources, labels) -> ProviderNode:
        labels = dict(labels)
        labels["ray_tpu.node_type"] = node_type
        proc, address, node_id, _store = node_mod.start_raylet(
            self.gcs_address,
            self.session_dir,
            dict(resources),
            labels=labels,
        )
        with self._lock:
            self._counter += 1
            pn = ProviderNode(
                provider_id=f"local-{self._counter}",
                node_type=node_type,
                node_id_hex=node_id,
                proc=proc,
            )
            self._nodes[pn.provider_id] = pn
        logger.info("provider launched %s (%s) as node %s",
                    pn.provider_id, node_type, node_id)
        return pn

    def terminate_node(self, node: ProviderNode) -> None:
        with self._lock:
            self._nodes.pop(node.provider_id, None)
        if node.proc is not None:
            node_mod.stop_processes([node.proc], node_mod.RAYLET_STOP_GRACE_S)
        logger.info("provider terminated %s", node.provider_id)

    def non_terminated_nodes(self) -> List[ProviderNode]:
        with self._lock:
            out = []
            for pn in list(self._nodes.values()):
                if pn.proc is not None and pn.proc.poll() is not None:
                    del self._nodes[pn.provider_id]  # crashed out of band
                else:
                    out.append(pn)
            return out

    def shutdown(self):
        for pn in self.non_terminated_nodes():
            self.terminate_node(pn)
