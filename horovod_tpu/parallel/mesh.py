"""Device-mesh construction for SPMD training.

The mesh is the TPU analogue of the reference's communicator hierarchy
(reference: horovod/common/common.h:119-136 Communicator::{GLOBAL,LOCAL,
CROSS}; mpi/mpi_controller.cc:44-79 rank/local/cross discovery): instead of
building MPI communicators at runtime we declare named axes once and let
XLA compile collectives over them.

Axis order is chosen for ICI locality — the innermost axes map to
physically adjacent devices, so the bandwidth-hungriest parallelism (tensor
parallelism) always rides the shortest links:

    pp  > dp > fsdp > ep > sp > tp      (outermost ... innermost)

When the job spans multiple hosts the outermost non-trivial axis is placed
on the DCN dimension (`create_hybrid_device_mesh`), mirroring how the
reference splits hierarchical collectives into an intra-node NCCL leg and a
cross-node MPI leg (reference: ops/nccl_operations.cc:187-398).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from ..common.logging import logger

# outermost → innermost
DEFAULT_AXES: tuple[str, ...] = ("pp", "dp", "fsdp", "ep", "sp", "tp")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Requested parallelism degrees; ``dp=-1`` means "all remaining
    devices" (the common case: fix model axes, scale data parallel)."""
    pp: int = 1     # pipeline stages
    dp: int = -1    # pure data parallel (gradient allreduce axis)
    fsdp: int = 1   # data parallel with sharded params/optimizer state
    ep: int = 1     # expert parallel (MoE all_to_all axis)
    sp: int = 1     # sequence/context parallel (ring attention axis)
    tp: int = 1     # tensor parallel (matmul sharding axis)

    def resolve(self, n_devices: int) -> dict[str, int]:
        sizes = {a: getattr(self, a) for a in DEFAULT_AXES}
        fixed = math.prod(v for v in sizes.values() if v > 0)
        if sizes["dp"] == -1:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes "
                    f"product {fixed} ({sizes})")
            sizes["dp"] = n_devices // fixed
            fixed *= sizes["dp"]
        if fixed != n_devices:
            raise ValueError(
                f"mesh axes {sizes} require {fixed} devices, have "
                f"{n_devices}")
        return sizes


def build_mesh(spec: MeshSpec | None = None,
               devices: Sequence[jax.Device] | None = None,
               **axis_sizes: int) -> Mesh:
    """Build a named `jax.sharding.Mesh`.

    Usage: ``build_mesh(dp=4, tp=2)`` or ``build_mesh(MeshSpec(tp=4))``.
    Single-host: uses `mesh_utils.create_device_mesh` so axis order maps
    onto the physical ICI torus. Multi-host: hybrid mesh with the
    outermost non-trivial axis spanning DCN.
    """
    if spec is None:
        spec = MeshSpec(**axis_sizes)
    elif axis_sizes:
        spec = dataclasses.replace(spec, **axis_sizes)
    if devices is None:
        devices = jax.devices()
    sizes = spec.resolve(len(devices))
    shape = tuple(sizes[a] for a in DEFAULT_AXES)

    from jax.experimental import mesh_utils
    # DCN granule = TPU slice when the runtime reports one (multi-slice
    # pods), else the owning process (CPU multi-process worlds). A single
    # multi-host slice is one ICI domain — no DCN split at all.
    slice_ids = {getattr(d, "slice_index", None) for d in devices}
    if None not in slice_ids and len(slice_ids) > 1:
        n_granules, by_process = len(slice_ids), False
    else:
        n_granules = len({getattr(d, "process_index", 0) for d in devices})
        by_process = True
        if None not in slice_ids:
            n_granules = 1   # one slice: pure ICI even across processes
    if n_granules > 1:
        # Split the outermost non-trivial axis across DCN granules
        # (ICI = "local", DCN = "cross"; reference: common.h:119-136).
        if len(devices) % n_granules:
            raise ValueError(
                f"{len(devices)} devices do not divide evenly over "
                f"{n_granules} DCN granules")
        dcn_shape, ici_shape = [], []
        remaining_dcn = n_granules
        for dim in shape:
            g = math.gcd(dim, remaining_dcn)
            dcn_shape.append(g)
            ici_shape.append(dim // g)
            remaining_dcn //= g
        if remaining_dcn != 1:
            raise ValueError(
                f"cannot split {n_granules} granules over mesh shape "
                f"{shape}")
        dev_array = mesh_utils.create_hybrid_device_mesh(
            tuple(ici_shape), tuple(dcn_shape), devices=devices,
            process_is_granule=by_process)
    else:
        try:
            dev_array = mesh_utils.create_device_mesh(shape,
                                                      devices=devices)
        except (ValueError, AssertionError) as exc:
            # Enumeration order is correct but not torus-aware: say so,
            # because collectives over the outer axes then cross more
            # ICI hops than they need to.
            logger.warning(
                "build_mesh: create_device_mesh%s failed (%s: %s); laying "
                "the %d devices out in enumeration order, which ignores "
                "the physical ICI topology", shape, type(exc).__name__,
                exc, len(devices))
            dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, DEFAULT_AXES)


def axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape.get(axis, 1)


def data_axes(mesh: Mesh) -> tuple[str, ...]:
    """The axes gradients are reduced over: every data-parallel-like axis
    that is larger than 1 (dp always; fsdp contributes after its
    reduce-scatter leg)."""
    return tuple(a for a in ("dp", "fsdp") if axis_size(mesh, a) > 1)
