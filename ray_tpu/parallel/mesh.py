"""Device-mesh construction for SPMD parallelism.

The TPU-native replacement for the reference's process-group bootstrap
(ray: python/ray/train/torch/config.py:112 `dist.init_process_group`,
ray: python/ray/util/collective/collective.py:120): instead of wiring a
NCCL communicator between worker processes, we build a
`jax.sharding.Mesh` over the slice's devices and let XLA compile
collectives onto ICI.

Axis convention (outer → inner, matching physical locality on a pod):

  dp    data parallelism (pure replication of params, gradient psum)
  fsdp  fully-sharded data parallelism (params sharded, all-gathered
        per layer; gradients reduce-scattered)
  ep    expert parallelism (MoE experts sharded; token dispatch is an
        all_to_all over this axis)
  pp    pipeline parallelism (layer stages; activations ppermute to the
        next stage once per microbatch — most latency-tolerant of the
        model axes)
  sp    sequence/context parallelism (ring attention neighbors — must
        map to an ICI ring)
  tp    tensor/model parallelism (innermost: highest-bandwidth axis)

Any axis may have size 1; the mesh is always constructed with all six
named axes so sharding rules never need to special-case missing axes.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

DP_AXIS = "dp"
FSDP_AXIS = "fsdp"
EP_AXIS = "ep"
PP_AXIS = "pp"
SP_AXIS = "sp"
TP_AXIS = "tp"

#: Mesh axes ordered outer→inner. dp/fsdp vary slowest (their collectives
#: tolerate the most latency: once-per-step gradient reductions), tp varies
#: fastest (per-layer all-gathers/reduce-scatters want nearest neighbors).
AXIS_ORDER = (DP_AXIS, FSDP_AXIS, EP_AXIS, PP_AXIS, SP_AXIS, TP_AXIS)

#: Axes over which a gradient psum runs for data parallelism.
DATA_AXES = (DP_AXIS, FSDP_AXIS)


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Logical shape of the device mesh.

    ``-1`` for at most one axis means "absorb all remaining devices",
    mirroring the reference's ScalingConfig(num_workers=...) ergonomics
    (ray: python/ray/air/config.py:103) but in mesh terms.
    """

    dp: int = -1
    fsdp: int = 1
    ep: int = 1
    pp: int = 1
    sp: int = 1
    tp: int = 1

    def resolve(self, n_devices: int) -> "MeshConfig":
        sizes = {"dp": self.dp, "fsdp": self.fsdp, "ep": self.ep,
                 "pp": self.pp, "sp": self.sp, "tp": self.tp}
        wild = [k for k, v in sizes.items() if v == -1]
        if len(wild) > 1:
            raise ValueError(f"at most one mesh axis may be -1, got {wild}")
        fixed = math.prod(v for v in sizes.values() if v != -1)
        if wild:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes {fixed}"
                )
            sizes[wild[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh {sizes} needs {fixed} devices, have {n_devices}"
            )
        return MeshConfig(**sizes)

    @property
    def shape(self) -> tuple:
        return (self.dp, self.fsdp, self.ep, self.pp, self.sp, self.tp)

    def describe(self) -> str:
        return "x".join(
            f"{a}={s}" for a, s in zip(AXIS_ORDER, self.shape) if s != 1
        ) or "single-device"


#: Process-wide active mesh, set by make_mesh / set_current_mesh.  Library
#: code (ring attention, train steps) that needs the concrete mesh for
#: shard_map fetches it here rather than threading it through every call.
_CURRENT_MESH: Optional[Mesh] = None


def set_current_mesh(mesh: Optional[Mesh]) -> None:
    global _CURRENT_MESH
    _CURRENT_MESH = mesh


def current_mesh() -> Optional[Mesh]:
    return _CURRENT_MESH


def use(mesh: Mesh):
    """Context manager binding ``mesh`` for PartitionSpec resolution."""
    return jax.set_mesh(mesh)


def make_mesh(
    config: Optional[MeshConfig] = None,
    *,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build the 6-axis mesh over ``devices`` (default: all local devices).

    Uses `jax.experimental.mesh_utils` device ordering so the innermost
    axes land on physically adjacent chips (ICI neighbors); on CPU
    meshes, where topology is flat, that is a plain reshape.
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    config = (config or MeshConfig()).resolve(len(devices))
    from jax.experimental import mesh_utils

    try:
        dev_array = mesh_utils.create_device_mesh(
            config.shape, devices=devices
        )
    except Exception as e:
        # A failed topology-aware layout on real hardware means sp/tp
        # neighbors may not be ICI-adjacent — degraded, not incorrect,
        # so warn loudly instead of failing or silently falling back.
        import warnings

        warnings.warn(
            f"mesh_utils.create_device_mesh failed ({e!r}); falling back "
            f"to flat device order — collective bandwidth may suffer"
        )
        dev_array = np.asarray(devices).reshape(config.shape)
    mesh = Mesh(dev_array, AXIS_ORDER)
    set_current_mesh(mesh)
    from ray_tpu.parallel import sharding as _sharding

    _sharding.set_active_rules(_sharding.DEFAULT_RULES)
    return mesh


#: Outermost axis of a multi-slice mesh: crosses the data-center network
#: between TPU slices, so ONLY once-per-step collectives (data-parallel
#: gradient psums) should map onto it.
DCN_AXIS = "dcn"


def make_multislice_mesh(
    n_slices: int,
    config: Optional[MeshConfig] = None,
    *,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """A dcn x ici product mesh over ``n_slices`` TPU slices.

    The SURVEY §2.5 DCN story (role-equivalent of the reference's
    hierarchical NCCL topology / MegaScale multi-slice training): the
    ``dcn`` axis is OUTERMOST — its collectives ride the slower
    inter-slice fabric exactly once per step (grad psum) while every
    model axis (fsdp/ep/pp/sp/tp) stays inside a slice on ICI.

    On real multislice hardware, devices group by their
    ``slice_index``; on a virtual CPU mesh any even partition of the
    devices validates the compile path.  Use MULTISLICE_RULES (or any
    rule table mapping "batch" onto ("dcn", "dp", "fsdp")) so the batch
    splits across slices.
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    # group by slice when the platform reports one (TPU multislice).  A
    # mismatch must FAIL, not fall back: reshaping ungrouped devices puts
    # ICI axes (per-layer tp all-gathers) across the DCN boundary — a
    # silent order-of-magnitude step-time regression.
    by_slice: dict = {}
    for d in devices:
        by_slice.setdefault(getattr(d, "slice_index", 0), []).append(d)
    if len(by_slice) > 1:
        sizes = {s: len(v) for s, v in by_slice.items()}
        if len(by_slice) != n_slices or len(set(sizes.values())) != 1:
            raise ValueError(
                f"hardware reports {len(by_slice)} slice(s) of sizes "
                f"{sizes}, but n_slices={n_slices} equal slices were "
                f"requested — the dcn axis must align with physical "
                f"slice boundaries"
            )
        devices = [d for s in sorted(by_slice) for d in by_slice[s]]
    if len(devices) % n_slices:
        raise ValueError(
            f"{len(devices)} devices not divisible into {n_slices} slices"
        )
    per_slice = len(devices) // n_slices
    config = (config or MeshConfig()).resolve(per_slice)
    dev_array = np.asarray(devices[: n_slices * per_slice]).reshape(
        (n_slices,) + config.shape
    )
    mesh = Mesh(dev_array, (DCN_AXIS,) + AXIS_ORDER)
    set_current_mesh(mesh)
    # model-internal constrain() calls must see the dcn-aware "batch"
    # rule, or every constrained activation replicates across slices
    from ray_tpu.parallel import sharding as _sharding

    _sharding.set_active_rules(_sharding.MULTISLICE_RULES)
    return mesh
