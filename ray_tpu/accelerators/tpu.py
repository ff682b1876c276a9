"""TPU detection and resource modelling.

Role-equivalent of ray: python/ray/_private/accelerators/tpu.py:75-398 —
chip detection (:110-120), TPU_VISIBLE_CHIPS partitioning (:174-196), pod
topology resources and the "<pod>-head" coordinator resource (:376-397) —
redesigned for this framework: detection feeds the raylet's node resources,
chip assignment happens at lease time in the raylet (raylet.py), and slice
gang scheduling uses the slice-name resource + STRICT_PACK placement groups.
"""

from __future__ import annotations

import glob
import logging
import os
import subprocess
import sys
import time
from typing import Dict, Optional

from ray_tpu.common.config import cfg
from ray_tpu.util import tracing

logger = logging.getLogger(__name__)

TPU_RESOURCE = "TPU"


#: Run by the detection probe in a child process, so that the caller —
#: a control process — never opens the chips itself.
_PROBE_SRC = (
    "import jax; ds=[d for d in jax.devices() if d.platform != 'cpu']; "
    "print(len(ds)); print(ds[0].device_kind if ds else '')"
)


def open_leased_chips() -> None:
    """Initialise the jax backend of a worker whose lease names chips
    (``TPU_VISIBLE_CHIPS``, bound by the raylet's lease), inside the
    start-up span ``rt.start.chip_open``.  JAX sends no event for it, so
    whoever is about to touch the first array calls this first: the
    decode replica before its weights, a train worker before its
    training function (after ``jax.distributed.initialize``, which must
    come before the backend).  Elsewhere, and the second time, nothing."""
    global _chips_opened
    chips = os.environ.get("TPU_VISIBLE_CHIPS")
    if not chips or _chips_opened:
        return
    _chips_opened = True
    import jax

    cpu0 = time.process_time()
    with tracing.startup("rt.start.chip_open", chips=chips) as s:
        s.attrs.update(
            device_kind=jax.devices()[0].device_kind,
            process_cpu_s=round(time.process_time() - cpu0, 3),
        )


_chips_opened = False


class TPUAcceleratorManager:
    """Detects local TPU chips and derives the node's TPU resources."""

    def __init__(self):
        self._num_chips: Optional[int] = None
        self._generation: Optional[str] = None
        #: which detection step answered (set by num_chips())
        self.detected_by: Optional[str] = None

    def num_chips(self) -> int:
        if self._num_chips is None:
            self._num_chips = self._detect()
        return self._num_chips

    def _detect(self) -> int:
        if cfg.tpu_chips_override >= 0:
            self.detected_by = "RT_TPU_CHIPS_OVERRIDE"
            return cfg.tpu_chips_override
        # 1) and 2) device files of a TPU VM: /dev/accel* or /dev/vfio/*
        n = len(glob.glob("/dev/accel*"))
        if n > 0:
            self.detected_by = "/dev/accel*"
            return n
        n = len([p for p in glob.glob("/dev/vfio/*") if p != "/dev/vfio/vfio"])
        if n > 0:
            self.detected_by = "/dev/vfio/*"
            return n
        # 3) ask jax, in a child process so that this one never claims
        #    the chips.  The child fails if this process already holds
        #    them (a caller of init() that has touched jax): that is
        #    logged, and the node then has no chips.
        n = self._probe_jax()
        self.detected_by = "jax probe" if n else "none"
        return n

    def _probe_jax(self) -> int:
        env = {
            k: v
            for k, v in os.environ.items()
            if k not in ("JAX_PLATFORMS", "XLA_FLAGS")
        }
        try:
            out = subprocess.run(
                [sys.executable, "-c", _PROBE_SRC],
                env=env, capture_output=True, timeout=60, text=True,
            )
        except (OSError, subprocess.TimeoutExpired) as e:
            logger.warning("TPU detection probe did not run: %r", e)
            return 0
        lines = out.stdout.strip().splitlines()
        if out.returncode != 0 or not lines or not lines[0].isdigit():
            logger.warning(
                "TPU detection probe failed (exit %s): %s",
                out.returncode, out.stderr.strip()[-2000:],
            )
            return 0
        if len(lines) > 1 and lines[1]:
            self._generation = _kind_to_generation(lines[1])
        return int(lines[0])

    def generation(self) -> Optional[str]:
        if self._generation is None:
            env = os.environ.get("TPU_ACCELERATOR_TYPE", "")  # e.g. v5litepod-8
            if env:
                self._generation = env.split("-")[0]
        return self._generation

    def extra_resources(self) -> Dict[str, float]:
        """Generation/topology resources advertised alongside `TPU`.

        Mirrors the reference's auto custom resources (tpu.py:376-397):
          TPU-<gen>          — generation-tagged capacity
          <slice_name>       — 1.0 on every host of a named slice
          TPU-<slice>-head   — 1.0 on worker 0 only (coordinator election)
        """
        out: Dict[str, float] = {}
        gen = self.generation()
        n = self.num_chips()
        if gen and n:
            out[f"TPU-{gen}"] = float(n)
        slice_name = os.environ.get("TPU_NAME") or cfg.tpu_topology_override
        if slice_name and n:
            out[slice_name] = 1.0
            if _tpu_worker_id() == 0:
                out[f"TPU-{slice_name}-head"] = 1.0
        return out


def _tpu_worker_id() -> int:
    for var in ("TPU_WORKER_ID", "CLOUD_TPU_TASK_ID"):
        v = os.environ.get(var)
        if v is not None and v.isdigit():
            return int(v)
    return 0


def _kind_to_generation(device_kind: str) -> str:
    # e.g. "TPU v5 lite" -> "v5e", "TPU v4" -> "v4"
    k = device_kind.lower()
    if "v5" in k and "lite" in k:
        return "v5e"
    for tag in ("v6e", "v5p", "v5", "v4", "v3", "v2"):
        if tag in k:
            return tag
    return device_kind.replace(" ", "-")
