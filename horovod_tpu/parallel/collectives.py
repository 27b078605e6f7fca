"""Mesh-axis collectives: the XLA data plane.

Two usage modes:

1. **Inside a shard_mapped / jitted program** — `allreduce(x, axis="dp")`
   etc. take *axis names* and lower straight to XLA collective HLOs
   (AllReduce / AllGather / AllToAll / CollectivePermute), which ride the
   ICI fabric. This replaces the reference's NCCL op dispatch
   (reference: horovod/common/ops/nccl_operations.cc:126-184).

2. **Host-level, via `device_collective`** — wraps an axis-name collective
   in `jit(shard_map(...))` over a stacked leading dimension; used by the
   XLA backend of the enqueue API and by tests.

`adasum_allreduce` implements the scale-insensitive Adasum reduction
(reference: horovod/common/ops/adasum/adasum.h:38-552) as recursive
distance-doubling over a mesh axis with `ppermute` exchanges: at level
``l`` ranks pair up (partner = rank XOR 2^l), exchange vectors, and combine

    a' = a·(1 − a·b / 2‖a‖²) + b·(1 − a·b / 2‖b‖²)

The pairwise tree matches the reference's VHDD order, so results agree
with `ops.adasum.adasum_reference` to fp precision.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def _axes(axis: str | Sequence[str]) -> tuple[str, ...]:
    return (axis,) if isinstance(axis, str) else tuple(axis)


# ---------------------------------------------------------------------------
# In-program collectives (use inside shard_map / jit)
# ---------------------------------------------------------------------------
def allreduce(x: jax.Array, axis: str | Sequence[str] = "dp",
              op: str = "sum") -> jax.Array:
    """psum / pmean over mesh axes (reference: ncclAllReduce,
    nccl_operations.cc:160)."""
    ax = _axes(axis)
    if op == "sum":
        return lax.psum(x, ax)
    if op in ("average", "mean"):
        return lax.pmean(x, ax)
    if op == "max":
        return lax.pmax(x, ax)
    if op == "min":
        return lax.pmin(x, ax)
    if op == "adasum":
        return adasum_allreduce(x, ax)
    raise ValueError(f"unknown reduce op {op!r}")


def allgather(x: jax.Array, axis: str = "dp", concat_axis: int = 0,
              tiled: bool = True) -> jax.Array:
    """Gather shards from every rank along the mesh axis
    (reference: NCCLAllgather, nccl_operations.cc:434-559)."""
    return lax.all_gather(x, axis, axis=concat_axis, tiled=tiled)


def reduce_scatter(x: jax.Array, axis: str = "dp",
                   scatter_axis: int = 0) -> jax.Array:
    """Sum then scatter shards (reference: ncclReduceScatter leg of the
    hierarchical allreduce, nccl_operations.cc:250-372)."""
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_axis,
                            tiled=True)


def alltoall(x: jax.Array, axis: str = "ep", split_axis: int = 0,
             concat_axis: int = 0) -> jax.Array:
    """Exchange equal splits with every rank on the axis
    (reference: NCCLAlltoall, nccl_operations.cc:567-619)."""
    return lax.all_to_all(x, axis, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def broadcast(x: jax.Array, axis: str = "dp", root: int = 0) -> jax.Array:
    """Every rank takes root's value (reference: NCCLBroadcast,
    nccl_operations.cc:401-432). Implemented as a masked psum — one
    AllReduce HLO, which XLA lowers efficiently on ICI."""
    idx = lax.axis_index(axis)
    contrib = jnp.where(idx == root, x, jnp.zeros_like(x))
    return lax.psum(contrib, axis)


def ppermute(x: jax.Array, axis: str,
             perm: Sequence[tuple[int, int]]) -> jax.Array:
    """Point-to-point ring/pair exchange (ICI-neighbor transport; the
    primitive under ring attention and Adasum)."""
    return lax.ppermute(x, axis, perm)


def adasum_allreduce(x: jax.Array, axis: str | Sequence[str] = "dp",
                     eps: float = 0.0) -> jax.Array:
    """Adasum over one or more mesh axes via recursive distance-doubling.

    Power-of-2 axis sizes only (the reference's VHDD pairing has the same
    constraint; reference: adasum.h power-of-2 rank pairing). Multiple
    axes are combined sequentially, innermost first (ICI before DCN),
    mirroring the hierarchical AdasumGpuAllreduceOp
    (reference: ops/adasum_gpu_operations.cc).
    """
    axes = _axes(axis)
    for ax in reversed(axes):      # innermost (ICI) leg first
        x = _adasum_one_axis(x, ax, eps)
    return x


def _adasum_one_axis(x: jax.Array, axis: str, eps: float) -> jax.Array:
    # lax.axis_size only exists on newer jax; psum of a literal 1 is the
    # portable static axis size.
    n = lax.psum(1, axis)
    if n == 1:
        return x
    if n & (n - 1):
        raise ValueError(f"Adasum requires power-of-2 axis size, "
                         f"got {axis}={n}")
    idx = lax.axis_index(axis)
    acc_dtype = jnp.float32 if x.dtype in (jnp.bfloat16, jnp.float16) \
        else x.dtype
    v = x.astype(acc_dtype)
    for level in range(int(math.log2(n))):
        distance = 1 << level
        perm = [(i, i ^ distance) for i in range(n)]
        other = lax.ppermute(v, axis, perm)
        # Canonical pair identity: `a` is held by the rank whose `level`
        # bit is clear, so both partners compute identical (a, b) and the
        # combine is symmetric (reference: adasum.h rank pairing).
        bit_clear = (idx & distance) == 0
        a = jnp.where(bit_clear, v, other)
        b = jnp.where(bit_clear, other, v)
        aa = jnp.sum(a * a)
        bb = jnp.sum(b * b)
        ab = jnp.sum(a * b)
        acoef = jnp.where(aa > eps, 1.0 - ab / (2.0 * aa + 1e-30), 1.0)
        bcoef = jnp.where(bb > eps, 1.0 - ab / (2.0 * bb + 1e-30), 1.0)
        zero = (aa == 0.0) & (bb == 0.0)
        acoef = jnp.where(zero, 1.0, acoef)
        bcoef = jnp.where(zero, 1.0, bcoef)
        v = acoef.astype(acc_dtype) * a + bcoef.astype(acc_dtype) * b
    return v.astype(x.dtype)


# ---------------------------------------------------------------------------
# Host-level wrapper
# ---------------------------------------------------------------------------
def device_collective(fn, mesh: Mesh, axis: str | Sequence[str] = "dp",
                      in_spec: Any = None, out_spec: Any = None):
    """jit(shard_map(fn)) over a stacked leading dim: input shape
    (axis_size, ...) — one slice per mesh position on `axis`; all other
    mesh axes see replicated data. Returns the compiled callable.
    """
    ax = _axes(axis)
    in_spec = P(ax) if in_spec is None else in_spec
    out_spec = P(ax) if out_spec is None else out_spec

    def wrapper(*args):
        return fn(*args)

    mapped = jax.shard_map(wrapper, mesh=mesh, in_specs=in_spec,
                           out_specs=out_spec, check_vma=False)
    return jax.jit(mapped)
