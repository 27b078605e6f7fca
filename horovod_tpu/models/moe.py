"""Mixture-of-Experts FFN with expert parallelism over the "ep" mesh axis.

The reference exposes ``alltoall`` as a user primitive explicitly for
MoE-style workloads but ships no routing layer (SURVEY §2.6).  This is the
TPU-native layer on top: Switch-style top-1 routing with capacity, dense
dispatch/combine einsums (mask-based, fully static shapes for XLA), and an
expert-parallel execution mode where tokens travel to their expert's rank
and back via two ``lax.all_to_all``s over "ep" — the exact communication
pattern the reference's alltoall primitive was added for.
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax
from jax.sharding import PartitionSpec as P


def _dispatch_combine(router_logits: jax.Array, capacity: int):
    """Top-1 dispatch/combine tensors. router_logits: [N, E] (N tokens).

    Returns dispatch [N, E, C] bool and combine [N, E, C] f32; tokens past
    an expert's capacity are dropped (output 0 for them, Switch behavior).
    """
    n, e = router_logits.shape
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    expert = jnp.argmax(probs, axis=-1)                       # [N]
    mask = jax.nn.one_hot(expert, e, dtype=jnp.float32)       # [N, E]
    # Position of each token within its expert's queue.
    pos = jnp.cumsum(mask, axis=0) * mask                     # [N, E]
    keep = (pos > 0) & (pos <= capacity)
    pos_clamped = jnp.clip(pos - 1, 0, capacity - 1).astype(jnp.int32)
    dispatch = jax.nn.one_hot(pos_clamped, capacity,
                              dtype=jnp.float32) * keep[..., None]
    gate = jnp.sum(probs * mask, axis=-1)                     # [N]
    combine = dispatch * gate[:, None, None]
    return dispatch, combine


class MoEMLP(nn.Module):
    """Switch-style MoE feed-forward. Input [B, T, D] → [B, T, D].

    ``ep_mesh``/``ep_axis``: when set (and axis size > 1) experts shard
    over "ep" and tokens are exchanged with two all_to_alls; otherwise all
    experts run replicated (dense einsum).  ``capacity_factor`` scales the
    per-expert token budget.
    """
    num_experts: int = 8
    d_ff: int = 256
    capacity_factor: float = 1.25
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    ep_mesh: Any = None
    ep_axis: str = "ep"

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, t, d = x.shape
        e = self.num_experts
        router = nn.Dense(e, use_bias=False, dtype=jnp.float32,
                          param_dtype=self.param_dtype, name="router")
        wi = self.param("wi", nn.initializers.lecun_normal(),
                        (e, d, self.d_ff), self.param_dtype)
        wo = self.param("wo", nn.initializers.lecun_normal(),
                        (e, self.d_ff, d), self.param_dtype)

        n_ep = 1
        if self.ep_mesh is not None:
            n_ep = self.ep_mesh.shape.get(self.ep_axis, 1)
        if self.is_initializing() or n_ep == 1:
            return self._dense_moe(router, wi, wo, x)

        # Expert-parallel: batch sharded over ep, experts sharded over ep.
        # Router logits compute outside the shard_map (replicated weights,
        # batch-parallel math); only dispatch + expert FFN go manual.
        logits = router(x)                                    # [B, T, E]
        return jax.shard_map(
            partial(_expert_parallel_moe_with_logits,
                    axis=self.ep_axis, axis_size=n_ep,
                    capacity_factor=self.capacity_factor,
                    dtype=self.dtype),
            mesh=self.ep_mesh,
            in_specs=(P(self.ep_axis), P(self.ep_axis), P(self.ep_axis),
                      P(self.ep_axis)),
            out_specs=P(self.ep_axis), check_vma=False)(
            x, logits, wi, wo)

    def _dense_moe(self, router, wi, wo, x):
        b, t, d = x.shape
        tokens = x.reshape(b * t, d)
        logits = router(x).reshape(b * t, self.num_experts)
        capacity = _capacity(b * t, self.num_experts, self.capacity_factor)
        dispatch, combine = _dispatch_combine(logits, capacity)
        expert_in = jnp.einsum("nec,nd->ecd", dispatch,
                               tokens.astype(jnp.float32))
        h = jnp.einsum("ecd,edf->ecf", expert_in,
                       wi.astype(jnp.float32))
        h = nn.gelu(h)
        expert_out = jnp.einsum("ecf,efd->ecd", h, wo.astype(jnp.float32))
        out = jnp.einsum("nec,ecd->nd", combine, expert_out)
        return out.reshape(b, t, d).astype(self.dtype)


def _capacity(n_tokens: int, num_experts: int, factor: float) -> int:
    return max(int(factor * n_tokens / num_experts), 1)


def _expert_parallel_moe_with_logits(x, logits, wi, wo, *, axis: str,
                                     axis_size: int, capacity_factor: float,
                                     dtype):
    """Per-ep-shard MoE: local batch shard [Bl, T, D], local expert shards
    wi [El, D, F] / wo [El, F, D], logits [Bl, T, E]."""
    bl, t, d = x.shape
    e = logits.shape[-1]
    el = wi.shape[0]
    assert el * axis_size == e, (el, axis_size, e)
    tokens = x.reshape(bl * t, d).astype(jnp.float32)
    capacity = _capacity(bl * t, e, capacity_factor)
    dispatch, combine = _dispatch_combine(logits.reshape(bl * t, e),
                                          capacity)
    # Local dispatch for ALL experts: [E, C, D]
    expert_in = jnp.einsum("nec,nd->ecd", dispatch, tokens)
    # To expert ranks: split expert dim over ep, gather the token groups —
    # each rank ends with [El, n*C, D]: its experts, every rank's tokens.
    expert_in = lax.all_to_all(expert_in, axis, split_axis=0,
                               concat_axis=1, tiled=True)
    h = jnp.einsum("ecd,edf->ecf", expert_in, wi.astype(jnp.float32))
    h = jax.nn.gelu(h)
    expert_out = jnp.einsum("ecf,efd->ecd", h, wo.astype(jnp.float32))
    # Send results home: inverse reshard.
    expert_out = lax.all_to_all(expert_out, axis, split_axis=1,
                                concat_axis=0, tiled=True)
    out = jnp.einsum("nec,ecd->nd", combine, expert_out)
    return out.reshape(bl, t, d).astype(dtype)
