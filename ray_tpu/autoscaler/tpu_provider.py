"""TPU-pod NodeProvider: slice-granular provisioning against the GCE TPU
API.

Role-equivalent of ray: python/ray/autoscaler/_private/gcp/node_provider.py:63
reshaped for TPU reality: the provisioning unit is a SLICE (all hosts of
a v5e-16, v4-32, ...), not a VM.  One ``create_node`` call asks the TPU
API for a queued resource; when the slice is READY every host runs a
raylet with the slice env injected (``TPU_NAME``, ``TPU_WORKER_ID``,
``TPU_WORKER_HOSTNAMES``, ``TPU_ACCELERATOR_TYPE``), which is exactly
what `accelerators/tpu.py` turns into the ``<slice>`` gang resource and
the ``TPU-<slice>-head`` coordinator resource.

The API client is injectable: ``FakeGceTpuApi`` (default here — this
environment has no egress) keeps slice state in memory and "boots" hosts
as local raylet subprocesses, so the autoscaler e2e path — demand →
create slice → hosts register → gang schedulable → idle → drain —
exercises the same lifecycle a real deployment has, with only the REST
transport faked.
"""

from __future__ import annotations

import logging
import subprocess
import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from ray_tpu.autoscaler.node_provider import NodeProvider, ProviderNode
from ray_tpu.core import node as node_mod

logger = logging.getLogger(__name__)

#: accelerator_type -> (n_hosts, chips_per_host, generation)
SLICE_SHAPES: Dict[str, tuple] = {
    "v5litepod-4": (1, 4, "v5e"),
    "v5litepod-8": (2, 4, "v5e"),
    "v5litepod-16": (4, 4, "v5e"),
    "v5litepod-32": (8, 4, "v5e"),
    "v4-8": (1, 4, "v4"),
    "v4-16": (2, 4, "v4"),
    "v4-32": (4, 4, "v4"),
    "v6e-8": (2, 4, "v6e"),
    "v6e-16": (4, 4, "v6e"),
}


def slice_shape(accelerator_type: str) -> tuple:
    try:
        return SLICE_SHAPES[accelerator_type]
    except KeyError:
        raise ValueError(
            f"unknown accelerator_type {accelerator_type!r}; known: "
            f"{sorted(SLICE_SHAPES)}"
        ) from None


@dataclass
class TpuSlice:
    name: str
    accelerator_type: str
    state: str = "CREATING"  # CREATING -> READY -> DELETING
    endpoints: List[str] = field(default_factory=list)
    meta: dict = field(default_factory=dict)


class GceTpuApi:
    """Transport interface to the TPU control plane (tpu.googleapis.com
    v2 nodes/queuedResources).  The real implementation is a thin REST
    client configured with project/zone credentials; it is deliberately
    not baked in here (no egress in CI) — deployments subclass or inject
    their own."""

    def create_slice(self, name: str, accelerator_type: str) -> TpuSlice:
        raise NotImplementedError

    def delete_slice(self, name: str) -> None:
        raise NotImplementedError

    def get_slice(self, name: str) -> Optional[TpuSlice]:
        raise NotImplementedError

    def list_slices(self) -> List[TpuSlice]:
        raise NotImplementedError


class FakeGceTpuApi(GceTpuApi):
    """In-memory TPU control plane: slices become READY immediately with
    one fake endpoint per host."""

    def __init__(self):
        self._slices: Dict[str, TpuSlice] = {}
        self._lock = threading.Lock()

    def create_slice(self, name, accelerator_type) -> TpuSlice:
        n_hosts, _, _ = slice_shape(accelerator_type)
        with self._lock:
            if name in self._slices:
                raise ValueError(f"slice {name!r} already exists")
            s = TpuSlice(
                name=name,
                accelerator_type=accelerator_type,
                state="READY",
                endpoints=[f"10.0.0.{i + 1}:8470" for i in range(n_hosts)],
            )
            self._slices[name] = s
            return s

    def delete_slice(self, name) -> None:
        with self._lock:
            self._slices.pop(name, None)

    def get_slice(self, name) -> Optional[TpuSlice]:
        with self._lock:
            return self._slices.get(name)

    def list_slices(self) -> List[TpuSlice]:
        with self._lock:
            return list(self._slices.values())


class GceMetadataPreemption:
    """GCE metadata-server preemption poll (the raylet's watcher source).

    A preemptible/spot TPU VM learns of its termination via the metadata
    server's ``instance/preempted`` flag (and an ACPI G2 signal) roughly
    30 s before the kill.  ``poll()`` returns the announced drain budget
    in seconds when the flag is TRUE, else 0.  The HTTP fetch is
    injectable so tests (and this egress-less environment) drive it with
    a fake; the raylet enables the real poll with ``RT_PREEMPT_METADATA``.
    """

    URL = (
        "http://metadata.google.internal/computeMetadata/v1/"
        "instance/preempted"
    )
    #: what GCE actually grants between notice and kill
    DEFAULT_DEADLINE_S = 30.0

    def __init__(self, fetch=None, deadline_s: Optional[float] = None):
        self._fetch = fetch or self._http_fetch
        self.deadline_s = (
            deadline_s if deadline_s is not None else self.DEFAULT_DEADLINE_S
        )

    def _http_fetch(self) -> str:
        import urllib.request

        req = urllib.request.Request(
            self.URL, headers={"Metadata-Flavor": "Google"}
        )
        try:
            with urllib.request.urlopen(req, timeout=1.0) as resp:
                return resp.read().decode("utf-8", "replace").strip()
        except Exception:
            return "FALSE"  # no metadata server / transient: not preempted

    def poll(self) -> float:
        """Seconds of drain budget if preempted, else 0."""
        try:
            flag = self._fetch()
        except Exception:
            return 0.0
        return self.deadline_s if str(flag).upper() == "TRUE" else 0.0


class TpuPodProvider(NodeProvider):
    """Slice-granular provider: create_node provisions a whole TPU slice
    and boots a raylet per host with the slice env injected."""

    def __init__(
        self,
        gcs_address: str,
        session_dir: str,
        api: Optional[GceTpuApi] = None,
        cpus_per_host: float = 4.0,
        slice_ready_timeout_s: float = 1800.0,
        poll_interval_s: float = 5.0,
    ):
        self.gcs_address = gcs_address
        self.session_dir = session_dir
        self.api = api or FakeGceTpuApi()
        self.cpus_per_host = cpus_per_host
        self.slice_ready_timeout_s = slice_ready_timeout_s
        self.poll_interval_s = poll_interval_s
        self._nodes: Dict[str, ProviderNode] = {}
        self._counter = 0
        self._lock = threading.Lock()

    def _wait_ready(self, tpu: TpuSlice) -> TpuSlice:
        """Poll until the slice is READY (queued resources sit in
        WAITING_FOR_RESOURCES/PROVISIONING for minutes on the real API;
        the fake answers READY immediately).  FAILED or timeout tears
        the queued resource down — a half-born slice must not leak."""
        import time

        deadline = time.monotonic() + self.slice_ready_timeout_s
        cur = tpu
        while cur.state != "READY":
            if cur.state == "FAILED":
                self.api.delete_slice(tpu.name)
                raise RuntimeError(
                    f"TPU slice {tpu.name} failed to provision: "
                    f"{cur.meta}"
                )
            if time.monotonic() > deadline:
                self.api.delete_slice(tpu.name)
                raise TimeoutError(
                    f"TPU slice {tpu.name} not READY within "
                    f"{self.slice_ready_timeout_s:.0f}s (last state "
                    f"{cur.state}, {cur.meta})"
                )
            time.sleep(self.poll_interval_s)
            nxt = self.api.get_slice(tpu.name)
            if nxt is None:
                raise RuntimeError(
                    f"TPU slice {tpu.name} vanished while provisioning"
                )
            cur = nxt
        return cur

    def _host_resources(
        self, slice_name: str, worker_id: int, accelerator_type: str
    ) -> Dict[str, float]:
        """What accelerators/tpu.py would detect on this host (explicit
        here because the fake hosts are plain subprocesses)."""
        _, chips, gen = slice_shape(accelerator_type)
        out = {
            "CPU": self.cpus_per_host,
            "TPU": float(chips),
            f"TPU-{gen}": float(chips),
            slice_name: 1.0,
        }
        if worker_id == 0:
            out[f"TPU-{slice_name}-head"] = 1.0
        return out

    def create_node(self, node_type, resources, labels) -> ProviderNode:
        """node_type must be an accelerator_type key (e.g. v5litepod-16);
        `resources` describe ONE HOST and are merged over the detected
        slice resources."""
        with self._lock:
            self._counter += 1
            slice_name = f"rt-{node_type}-{self._counter}"
        tpu = self._wait_ready(self.api.create_slice(slice_name, node_type))
        n_hosts, chips, _gen = slice_shape(node_type)
        procs: List[subprocess.Popen] = []
        node_ids: List[str] = []
        hostnames = ",".join(e.split(":")[0] for e in tpu.endpoints)
        try:
            for worker_id in range(n_hosts):
                host_res = self._host_resources(
                    slice_name, worker_id, node_type
                )
                host_res.update(resources or {})
                host_labels = dict(labels or {})
                host_labels.update({
                    "ray_tpu.node_type": node_type,
                    "ray_tpu.slice": slice_name,
                    "ray_tpu.tpu_worker_id": str(worker_id),
                })
                proc, _addr, nid, _store = node_mod.start_raylet(
                    self.gcs_address,
                    self.session_dir,
                    host_res,
                    labels=host_labels,
                    extra_env={
                        "TPU_NAME": slice_name,
                        "TPU_WORKER_ID": str(worker_id),
                        "TPU_WORKER_HOSTNAMES": hostnames,
                        "TPU_ACCELERATOR_TYPE": node_type,
                    },
                )
                procs.append(proc)
                node_ids.append(nid)
        except BaseException:
            node_mod.stop_processes(procs, node_mod.RAYLET_STOP_GRACE_S)
            self.api.delete_slice(slice_name)
            raise
        pn = ProviderNode(
            provider_id=slice_name,
            node_type=node_type,
            node_id_hex=node_ids[0],
            proc=procs[0],
            meta={"procs": procs, "node_ids": node_ids,
                  "endpoints": tpu.endpoints},
        )
        with self._lock:
            self._nodes[slice_name] = pn
        logger.info(
            "provisioned TPU slice %s (%s: %d hosts x %d chips)",
            slice_name, node_type, n_hosts, chips,
        )
        return pn

    def terminate_node(self, node: ProviderNode) -> None:
        with self._lock:
            self._nodes.pop(node.provider_id, None)
        node_mod.stop_processes(
            node.meta.get("procs", []), node_mod.RAYLET_STOP_GRACE_S
        )
        self.api.delete_slice(node.provider_id)
        logger.info("terminated TPU slice %s", node.provider_id)

    def non_terminated_nodes(self) -> List[ProviderNode]:
        with self._lock:
            out = []
            for pn in list(self._nodes.values()):
                procs = pn.meta.get("procs", [])
                if procs and all(p.poll() is not None for p in procs):
                    # every host died out of band: the slice is gone
                    del self._nodes[pn.provider_id]
                    self.api.delete_slice(pn.provider_id)
                else:
                    out.append(pn)
            return out