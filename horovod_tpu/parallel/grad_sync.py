"""Fused gradient synchronization — tensor fusion + compression + reduce,
compiled into the training step.

This is the SPMD re-design of the reference's hot path (SURVEY §3.2): where
the reference's background thread batches gradient tensors into a 64 MB
fusion buffer and calls ncclAllReduce per batch (reference:
horovod/common/controller.cc:778-915 FuseResponses;
ops/nccl_operations.cc:126-184), we bucket the gradient pytree into
fusion-threshold-sized flat buffers *at trace time* and emit one AllReduce
HLO per bucket. XLA schedules them back-to-back on ICI with no host in the
loop — negotiation cost is zero because SPMD guarantees every rank runs the
identical program (the property the reference's controller exists to
establish dynamically).

Compression:
- fp16/bf16 mirror horovod.torch.Compression.fp16 (reference:
  horovod/torch/compression.py:46-63): cast the bucket to a 16-bit wire
  type before the reduce, cast back after, with the reduction itself
  carried out in the wire dtype exactly like the reference's fp16 NCCL
  allreduce.
- int8/uint4 are the EQuARX-style block-quantized allreduce
  (compress/jax_ops.py): XLA fuses per-block quantize → all_to_all →
  fp32 reduce → requantize → all_gather into the step program, moving
  ~1/4 (int8) / ~1/8 (uint4) of the fp32 bytes over ICI/DCN.  With
  ``error_feedback=True`` the quantization error threads through
  ``sync_gradients_ef`` as explicit residual state (EF-SGD), so it is
  re-injected next step instead of lost.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .collectives import allreduce, adasum_allreduce

_WIRE_DTYPES = {"fp16": jnp.float16, "bf16": jnp.bfloat16,
                "none": None, None: None}
_QUANTIZED = ("int8", "uint4")


def _quantized_codec(compression):
    if compression in _QUANTIZED:
        from ..compress import codec_from_name
        return codec_from_name(compression)
    return None


@dataclasses.dataclass(frozen=True)
class GradSyncConfig:
    """Knobs mirroring the reference env contract
    (reference: common/common.h:66-96 HOROVOD_FUSION_THRESHOLD et al.)."""
    axes: tuple[str, ...] = ("dp",)
    op: str = "average"                   # sum | average | adasum
    compression: str | None = None        # fp16 | bf16 | int8 | uint4 | None
    # Quantization block for int8/uint4 (elements; even for uint4).
    compression_block_size: int = 256
    # EF-SGD residual re-injection for the quantized codecs; state
    # threads through sync_gradients_ef (see init_error_feedback).
    error_feedback: bool = False
    fusion_threshold_bytes: int = 64 * 1024 * 1024
    # Hierarchical two-stage reduction (reference: HOROVOD_HIERARCHICAL_
    # ALLREDUCE + NCCLHierarchicalAllreduce, nccl_operations.cc:187-398):
    # reduce-scatter over the LOCAL (ICI, axes[1:]) leg, allreduce the
    # shards over the CROSS (DCN, axes[0]) leg, all-gather back over local.
    # With a flat mesh XLA usually derives this itself; the explicit form
    # pins the decomposition (and the wire dtype per leg) when profiling
    # says it matters.
    hierarchical: bool = False
    # Adasum is applied per-tensor (the reference computes per-layer dot
    # products, adasum.h:38-552); sum/average fuse into buckets.

    # --- fused loss-scaling + global-norm clipping -----------------------
    # Both ride the SAME compiled pass as the reduce (and quantize/EF):
    # the squared norm is taken on the already-hot reduced flat buckets
    # and the combined unscale×clip factor folds into the existing
    # slice-out multiply — no separate tree traversals, no second pass
    # over gradient memory (the fusion arXiv:2305.06942 argues for).
    # `loss_scale`: the loss was pre-multiplied by this factor (mixed-
    # precision loss scaling); gradients are unscaled by 1/loss_scale
    # after the reduce (norms are computed on UNSCALED values).
    loss_scale: float | None = None
    # Clip the global (all-leaf) L2 norm of the reduced, unscaled
    # gradients to this value (optax.clip_by_global_norm semantics).
    clip_global_norm: float | None = None

    # --- optimizer-in-ring (ZeRO-style; arXiv:2305.06942) ----------------
    # Apply the optax update during the last reduce-scatter leg: each
    # rank updates only its shard of the flat parameter buffer (optimizer
    # state sharded over ranks), and the UPDATED PARAMS — not gradients —
    # ride the closing all-gather.  Wire volume is identical to a plain
    # allreduce, but the update math runs once per shard instead of once
    # per replica and the optimizer state is 1/world per rank.  Opt-in:
    # use sync_and_apply() (or Trainer with this flag) instead of
    # sync_gradients + tx.update.  Composes with the cast codecs on both
    # legs and the quantized codecs on the gradient leg only (updated
    # params always ride full-width or cast wires — block-quantizing
    # parameters would accumulate reconstruction error step over step).
    optimizer_in_ring: bool = False


def _bucketize(leaves: list[jax.Array], threshold: int,
               itemsize: int | None = None) -> list[list[int]]:
    """Greedy size-ordered bucketing, preserving leaf order inside a
    bucket (the reference fuses in request order with look-ahead,
    controller.cc:778-915). `itemsize` overrides the leaf dtype width so
    buckets are sized in *wire* bytes when compression is active."""
    buckets: list[list[int]] = []
    cur: list[int] = []
    cur_bytes = 0
    for i, leaf in enumerate(leaves):
        nbytes = leaf.size * (itemsize or leaf.dtype.itemsize)
        if cur and cur_bytes + nbytes > threshold:
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nbytes
    if cur:
        buckets.append(cur)
    return buckets


def sync_gradients(grads: Any, config: GradSyncConfig = GradSyncConfig()
                   ) -> Any:
    """Reduce a gradient pytree over the mesh axes. Call inside a
    shard_mapped / jitted train step."""
    with jax.named_scope("hvd.grad_sync"):
        out, _ = _sync_impl(grads, config, None)
    return out


def init_error_feedback(grads: Any) -> Any:
    """Zero EF residual state matching a gradient pytree (fp32 — the
    residual must hold error finer than the wire can carry)."""
    return jax.tree_util.tree_map(
        lambda g: jnp.zeros(jnp.shape(g), jnp.float32), grads)


def sync_gradients_ef(grads: Any, residuals: Any,
                      config: GradSyncConfig) -> tuple[Any, Any]:
    """Error-feedback variant: quantization error of THIS step's wire is
    returned as residual state and re-added to the next step's gradients
    (EF-SGD), recovering uncompressed convergence for the quantized
    codecs.  Thread ``residuals`` through the jitted step; initialize
    with :func:`init_error_feedback`.  For non-quantized codecs the
    residuals pass through untouched."""
    if _quantized_codec(config.compression) is None:
        return sync_gradients(grads, config), residuals
    with jax.named_scope("hvd.grad_sync"):
        return _sync_impl(grads, config, residuals)


def _sync_impl(grads: Any, config: GradSyncConfig,
               residuals: Any | None) -> tuple[Any, Any | None]:
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    if not leaves:
        return grads, residuals
    codec = _quantized_codec(config.compression)
    wire = _WIRE_DTYPES[config.compression] if codec is None else None

    if config.op == "adasum":
        if codec is not None:
            raise ValueError(
                "adasum does not compose with quantized compression "
                "(int8/uint4): the scale-adaptive dot products would be "
                "computed on quantized blocks. Use none, fp16 or bf16.")
        if config.loss_scale is not None or \
                config.clip_global_norm is not None:
            raise ValueError(
                "adasum does not compose with fused loss-scaling/"
                "clipping: the scale-adaptive combine is not linear in "
                "the gradients, so post-hoc unscaling would change the "
                "update direction. Unscale/clip before sync instead.")
        # Per-tensor combine (the reference computes per-layer dot
        # products, adasum.h:38-552); compression composes around the
        # exchange exactly as in the sum path.
        out = []
        for leaf in leaves:
            v = leaf
            if wire is not None and jnp.issubdtype(leaf.dtype, jnp.floating):
                v = v.astype(wire)
            out.append(adasum_allreduce(v, config.axes).astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out), residuals

    res_leaves: list | None = None
    if residuals is not None:
        res_leaves = jax.tree_util.tree_flatten(residuals)[0]
        if len(res_leaves) != len(leaves):
            raise ValueError(
                "error-feedback residual pytree does not match the "
                "gradient pytree; initialize with init_error_feedback()")
    res_out = list(res_leaves) if res_leaves is not None else None

    out: list[jax.Array | None] = [None] * len(leaves)
    # Reduced flat buckets, slice-out deferred: (member leaf idxs, flat
    # reduced buffer, dtype, floating).  Deferral lets the fused
    # loss-scaling/clipping factor — which needs the GLOBAL norm across
    # every bucket — fold into the one multiply the slice-out pass
    # already performs, instead of a second traversal.
    reduced_buckets: list[tuple[list[int], jax.Array, Any, bool]] = []
    # Group leaves by dtype so each fused buffer is homogeneous, same as
    # the reference's per-dtype responses (controller.cc ConstructResponse
    # dtype consistency check).
    by_dtype: dict[Any, list[int]] = {}
    for i, leaf in enumerate(leaves):
        by_dtype.setdefault(jnp.dtype(leaf.dtype), []).append(i)

    for dtype, idxs in by_dtype.items():
        group = [leaves[i] for i in idxs]
        quantized = codec is not None and jnp.issubdtype(dtype,
                                                         jnp.floating)
        if quantized:
            # Buckets sized in wire bytes: ~1 byte/elem (int8) or
            # ~0.5 (uint4) + block metadata; 1 is a close upper bound.
            wire_itemsize: int | None = 1
        else:
            wire_itemsize = jnp.dtype(wire).itemsize \
                if wire is not None and jnp.issubdtype(dtype, jnp.floating) \
                else None
        for bucket in _bucketize(group, config.fusion_threshold_bytes,
                                 wire_itemsize):
            members = [idxs[j] for j in bucket]
            flat = jnp.concatenate(
                [leaves[i].reshape(-1) for i in members]) \
                if len(members) > 1 else leaves[members[0]].reshape(-1)
            if quantized:
                from ..compress.jax_ops import quantized_allreduce
                # The quantized exchange is already its own two-phase
                # (scatter-reduce/gather) decomposition, so the explicit
                # hierarchical split does not apply on top of it.
                if res_out is not None:
                    rflat = jnp.concatenate(
                        [res_leaves[i].reshape(-1) for i in members]) \
                        if len(members) > 1 \
                        else res_leaves[members[0]].reshape(-1)
                    flat, new_res = quantized_allreduce(
                        flat, config.axes, config.op, codec,
                        config.compression_block_size, residual=rflat)
                    offset = 0
                    for i in members:
                        n = leaves[i].size
                        res_out[i] = new_res[offset:offset + n].reshape(
                            leaves[i].shape)
                        offset += n
                else:
                    flat = quantized_allreduce(
                        flat, config.axes, config.op, codec,
                        config.compression_block_size)
            else:
                if wire is not None and jnp.issubdtype(dtype, jnp.floating):
                    flat = flat.astype(wire)
                if config.hierarchical and len(config.axes) >= 2:
                    flat = _hierarchical_allreduce(flat, config.axes,
                                                   config.op)
                else:
                    flat = allreduce(flat, config.axes, config.op)
            reduced_buckets.append(
                (members, flat, dtype, jnp.issubdtype(dtype,
                                                      jnp.floating)))

    factor = _scale_clip_factor(
        config, [flat for _, flat, _, floating in reduced_buckets
                 if floating])
    for members, flat, dtype, floating in reduced_buckets:
        if factor is not None and floating:
            # The combined 1/loss_scale × clip factor rides the same
            # pass as the wire-dtype restore — XLA fuses both into one
            # elementwise kernel over the already-hot bucket.
            flat = (flat.astype(jnp.float32) * factor).astype(dtype)
        else:
            flat = flat.astype(dtype)
        offset = 0
        for i in members:
            n = leaves[i].size
            out[i] = flat[offset:offset + n].reshape(leaves[i].shape)
            offset += n
    synced = jax.tree_util.tree_unflatten(treedef, out)
    if res_out is None:
        return synced, residuals
    res_treedef = jax.tree_util.tree_flatten(residuals)[1]
    return synced, jax.tree_util.tree_unflatten(res_treedef, res_out)


def _scale_clip_factor(config: GradSyncConfig,
                       flats: "list[jax.Array]"):
    """Combined 1/loss_scale × global-norm-clip factor for the reduced
    flat buckets (None when neither knob is set).  The squared norm is
    computed on the buckets the sync pass just produced — no second tree
    traversal — and matches optax.clip_by_global_norm on the unscaled
    gradients: factor = inv · min(1, clip / (‖g‖ · inv))."""
    if config.loss_scale is None and config.clip_global_norm is None:
        return None
    inv = jnp.float32(1.0) if config.loss_scale is None \
        else jnp.float32(1.0 / config.loss_scale)
    if config.clip_global_norm is None:
        return inv
    gsq = jnp.float32(0.0)
    for flat in flats:
        f32 = flat.astype(jnp.float32)
        gsq = gsq + jnp.vdot(f32, f32)
    gnorm = jnp.sqrt(gsq) * inv            # norm of the UNSCALED grads
    clip = jnp.float32(config.clip_global_norm)
    return inv * jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-16))


# ---------------------------------------------------------------------------
# Optimizer-in-ring (ZeRO-style fused sync+update; arXiv:2305.06942)
# ---------------------------------------------------------------------------
def ring_chunk_size(n_params: int, world_size: int,
                    config: GradSyncConfig) -> int:
    """Per-rank flat shard length for the optimizer-in-ring layout: the
    flat parameter buffer padded to world × chunk, chunk block-aligned
    when a quantized codec rides the gradient leg (so each rank's wire
    rows quantize on block boundaries)."""
    chunk = -(-n_params // max(world_size, 1))
    if _quantized_codec(config.compression) is not None:
        bs = config.compression_block_size
        chunk = -(-chunk // bs) * bs
    return chunk


def init_ring_optimizer_state(tx, params: Any, world_size: int,
                              config: GradSyncConfig) -> Any:
    """Optimizer state for ONE rank's flat fp32 shard (call per rank, or
    inside shard_map where every rank initializes its own shard).  The
    update math runs on the flat buffer, so only elementwise-style
    transforms (sgd/adam/adamw/lamb-like: state mirrors the params or is
    scalar) are supported — per-layer-norm transforms would need the
    leaf boundaries the flat layout erases."""
    n = sum(int(np.prod(jnp.shape(leaf)))
            for leaf in jax.tree_util.tree_leaves(params))
    chunk = ring_chunk_size(n, world_size, config)
    return tx.init(jnp.zeros((chunk,), jnp.float32))


@jax.named_scope("hvd.grad_sync")
def sync_and_apply(tx, grads: Any, params: Any, opt_state: Any,
                   config: GradSyncConfig) -> tuple[Any, Any]:
    """Fused gradient sync + optimizer update (optimizer-in-ring): call
    inside a shard_mapped / jitted train step in place of
    ``sync_gradients`` + ``tx.update`` + ``apply_updates``.

      1. flatten the gradient pytree into ONE fp32 buffer, padded to
         world × chunk;
      2. reduce-scatter it over ``config.axes`` — quantized codecs ship
         int8/uint4 rows through the same all_to_all leg as
         compress/jax_ops, cast codecs ship 16-bit words;
      3. apply the optax update on THIS RANK'S shard only (``opt_state``
         is the shard state from :func:`init_ring_optimizer_state` —
         ZeRO-style, 1/world of the replicated state);
      4. all-gather the UPDATED PARAM shards (cast codec honored) and
         unflatten back to the parameter pytree.

    Fused loss-scaling/clipping (config.loss_scale /
    clip_global_norm) applies on the reduced shard with one extra scalar
    psum for the global norm.  Returns ``(new_params, new_opt_state)``.

    The update math runs in fp32 on the flat buffer (master-weights
    style: params are widened for the update and cast back to their own
    dtypes), so results match sync-then-update to fp32 round-off, not
    bitwise, for sub-fp32 parameter dtypes."""
    import optax
    from jax import lax

    if config.op not in ("sum", "average"):
        raise ValueError(
            f"optimizer-in-ring supports op=sum|average, not "
            f"{config.op!r} (adasum's per-tensor combine needs the leaf "
            f"boundaries the flat shard layout erases)")
    if config.error_feedback:
        raise ValueError(
            "optimizer-in-ring does not thread error-feedback state yet; "
            "use sync_gradients_ef + tx.update, or drop error_feedback")
    axes = (config.axes,) if isinstance(config.axes, str) \
        else tuple(config.axes)
    if not axes:
        raise ValueError(
            "optimizer-in-ring needs explicit mesh axes (pure-GSPMD "
            "mode has no manual axis to shard the update over)")

    g_leaves, g_treedef = jax.tree_util.tree_flatten(grads)
    p_leaves, p_treedef = jax.tree_util.tree_flatten(params)
    if len(g_leaves) != len(p_leaves):
        raise ValueError(
            "gradient and parameter pytrees do not match")
    if not g_leaves:
        return params, opt_state

    world = 1
    for a in axes:
        world = world * lax.psum(1, a)       # concrete at trace time
    n = sum(leaf.size for leaf in g_leaves)
    chunk = ring_chunk_size(n, world, config)
    padded_n = chunk * world

    g32 = jnp.concatenate([leaf.reshape(-1).astype(jnp.float32)
                           for leaf in g_leaves]) \
        if len(g_leaves) > 1 else g_leaves[0].reshape(-1).astype(
            jnp.float32)
    if padded_n > n:
        g32 = jnp.concatenate(
            [g32, jnp.zeros(padded_n - n, jnp.float32)])

    codec = _quantized_codec(config.compression)
    wire = _WIRE_DTYPES[config.compression] if codec is None else None
    if codec is not None:
        # Quantized gradient leg: the scatter-reduce half of
        # compress/jax_ops.quantized_allreduce — int8/uint4 rows +
        # block metadata through all_to_all, fp32 dequant+sum at the
        # owner.  One quantization of my contributions; the reduced
        # shard never requantizes (it feeds the update directly).
        from ..compress.jax_ops import dequantize_rows, quantize_rows
        bs = config.compression_block_size
        x = g32.reshape(world, chunk)
        q, s, zp = quantize_rows(x, codec, bs)
        q = lax.all_to_all(q, axes, split_axis=0, concat_axis=0,
                           tiled=True)
        s = lax.all_to_all(s, axes, split_axis=0, concat_axis=0,
                           tiled=True)
        zp = lax.all_to_all(zp, axes, split_axis=0, concat_axis=0,
                            tiled=True)
        g_shard = dequantize_rows(q, s, zp, codec, bs).sum(axis=0)
    else:
        leg = g32 if wire is None else g32.astype(wire)
        for a in axes:
            leg = lax.psum_scatter(leg, a, scatter_dimension=0,
                                   tiled=True)
        g_shard = leg.astype(jnp.float32)
    if config.op == "average":
        g_shard = g_shard / world

    # Fused unscale + clip on the shard: one scalar psum for the global
    # norm, factor folded into the shard multiply.
    if config.loss_scale is not None or \
            config.clip_global_norm is not None:
        inv = jnp.float32(1.0) if config.loss_scale is None \
            else jnp.float32(1.0 / config.loss_scale)
        if config.clip_global_norm is not None:
            gsq = jnp.vdot(g_shard, g_shard)
            for a in axes:
                gsq = lax.psum(gsq, a)
            gnorm = jnp.sqrt(gsq) * inv
            clip = jnp.float32(config.clip_global_norm)
            factor = inv * jnp.minimum(1.0, clip
                                       / jnp.maximum(gnorm, 1e-16))
        else:
            factor = inv
        g_shard = g_shard * factor

    # My shard of the flat fp32 master params.
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * lax.psum(1, a) + lax.axis_index(a)
    p32 = jnp.concatenate([leaf.reshape(-1).astype(jnp.float32)
                           for leaf in p_leaves]) \
        if len(p_leaves) > 1 else p_leaves[0].reshape(-1).astype(
            jnp.float32)
    if padded_n > n:
        p32 = jnp.concatenate(
            [p32, jnp.zeros(padded_n - n, jnp.float32)])
    p_shard = lax.dynamic_slice(p32, (idx * chunk,), (chunk,))

    updates, new_opt_state = tx.update(g_shard, opt_state, p_shard)
    p_new = optax.apply_updates(p_shard, updates)

    # Updated params — not gradients — ride the closing all-gather.
    full = p_new if wire is None else p_new.astype(wire)
    for a in reversed(axes):
        full = lax.all_gather(full, a, axis=0, tiled=True)
    full = full[:n].astype(jnp.float32)

    out: list = []
    offset = 0
    for leaf in p_leaves:
        k = leaf.size
        out.append(full[offset:offset + k].reshape(leaf.shape)
                   .astype(leaf.dtype))
        offset += k
    return jax.tree_util.tree_unflatten(p_treedef, out), new_opt_state


def _hierarchical_allreduce(flat: jax.Array, axes: Sequence[str],
                            op: str) -> jax.Array:
    """reduce_scatter(local) → allreduce(cross) → all_gather(local)
    (reference: NCCLHierarchicalAllreduce's ReduceScatter → cross-node
    MPI_Allreduce → AllGather split, nccl_operations.cc:250-372, including
    its remainder handling via padding)."""
    from jax import lax

    cross, locals_ = axes[0], tuple(axes[1:])
    local_size = 1
    for a in locals_:
        local_size *= lax.psum(1, a)
    n = flat.shape[0]
    pad = (-n) % local_size
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    # Sum-scatter over the combined local axes, innermost first.
    shard = flat
    for a in locals_:
        shard = lax.psum_scatter(shard, a, scatter_dimension=0, tiled=True)
    shard = lax.psum(shard, cross)
    full = shard
    for a in reversed(locals_):
        full = lax.all_gather(full, a, axis=0, tiled=True)
    if pad:
        full = full[:n]
    if op == "average":
        world = lax.psum(1, cross) * local_size
        full = full / world
    return full


def build_grad_sync(mesh, config: GradSyncConfig = GradSyncConfig()):
    """Host-level compiled sync over stacked per-rank gradients: each leaf
    has leading dim = prod(axis sizes); mainly for tests and the eager
    API."""
    from jax.sharding import PartitionSpec as P

    spec = P(config.axes)

    def _sync(grads):
        return sync_gradients(grads, config)

    mapped = jax.shard_map(_sync, mesh=mesh, in_specs=spec,
                           out_specs=spec, check_vma=False)
    return jax.jit(mapped)
