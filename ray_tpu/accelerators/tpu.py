"""TPU detection and resource modelling.

Role-equivalent of ray: python/ray/_private/accelerators/tpu.py:75-398 —
chip detection (:110-120), TPU_VISIBLE_CHIPS partitioning (:174-196), pod
topology resources and the "<pod>-head" coordinator resource (:376-397) —
redesigned for this framework: detection feeds the raylet's node resources,
chip assignment happens at lease time in the raylet (raylet.py), and slice
gang scheduling uses the slice-name resource + STRICT_PACK placement groups.
"""

from __future__ import annotations

import errno
import glob
import logging
import os
import re
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
    come before the backend).  Elsewhere, and the second time, nothing.

    The raylet frees a chip only when the worker that held it has been
    reaped.  A holder it did not start (the run before this one, still
    dying; another tenant) it cannot see, so the lease first waits, at
    most ``cfg.worker_start_timeout_s``, until its chips' device files
    open (the span's ``waited_s``): libtpu does not wait, and a backend
    that failed to initialise cannot be asked again."""
    global _chips_opened
    chips = os.environ.get("TPU_VISIBLE_CHIPS")
    if not chips or _chips_opened:
        return
    _chips_opened = True
    import jax

    cpu0 = time.process_time()
    with tracing.startup("rt.start.chip_open", chips=chips) as s:
        waited = s.attrs["waited_s"] = round(_wait_until_free(chips), 3)
        if waited >= 1:
            logger.warning("chips %s: waited %.3f s for a holder this raylet "
                           "did not start to let go of them", chips, waited)
        s.attrs.update(
            device_kind=jax.devices()[0].device_kind,
            process_cpu_s=round(time.process_time() - cpu0, 3),
        )


_chips_opened = False


def _device_files() -> list:
    """This host's chips as device files, in the order of their ids in
    ``TPU_VISIBLE_CHIPS``: ``/dev/accel<N>``, or the numbered groups of
    ``/dev/vfio``."""
    files = glob.glob("/dev/accel[0-9]*") or glob.glob("/dev/vfio/[0-9]*")
    return sorted(files, key=lambda p: int(re.search(r"\d+", p).group()))


def _open_errno(path: str) -> int:
    """0 if ``path`` can be opened (and closed again at once)."""
    try:
        os.close(os.open(path, os.O_RDWR))
    except OSError as e:
        return e.errno
    return 0


def _holder_of(path: str) -> str:
    """" (held by pid N)" where /proc shows who has ``path`` open."""
    for fd in glob.glob("/proc/[0-9]*/fd/*"):
        try:
            if os.readlink(fd) == path:
                return f" (held by pid {fd.split('/')[2]})"
        except OSError:
            pass
    return ""


def _wait_until_free(chips: str) -> float:
    """Seconds waited until every device file of ``chips`` could be
    opened.  Only EBUSY means "held"; any other answer is left to
    ``jax.devices()``, which says what is wrong."""
    files = _device_files()
    t0 = time.monotonic()
    for c in chips.split(","):
        if not c.isdigit() or int(c) >= len(files):
            continue  # fake chips, or ids jax will have to explain
        path = files[int(c)]
        while _open_errno(path) == errno.EBUSY:
            waited = time.monotonic() - t0
            if waited > cfg.worker_start_timeout_s:
                raise RuntimeError(
                    f"chip {c} of this lease ({chips}) cannot be opened: {path} "
                    f"is still busy after {waited:.0f} s{_holder_of(path)}; "
                    "a chip belongs to one process at a time"
                )
            time.sleep(0.1)
    return time.monotonic() - t0


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
        files = _device_files()
        if files:
            kind = "accel" if "accel" in files[0] else "vfio/"
            self.detected_by = f"/dev/{kind}*"
            return len(files)
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
