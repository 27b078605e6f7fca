"""Mixture-of-Experts feed-forward layers.

Two layers live here.  ``MoEMLP`` is the training layer, with expert
parallelism over the "ep" mesh axis: Switch top-1 routing with a
capacity, described next.  ``RoutedExperts``, at the end of the file, is
the serving layer of today's sparse models: top-k of a wide router,
gated SiLU experts and a shared expert, **told which experts it holds**,
no capacity and no dropped token, grouped matrix products over the
experts held.  Folding the first onto the second is a later PR's.

``MoEMLP``.

The reference exposes ``alltoall`` as a user primitive explicitly for
MoE-style workloads but ships no routing layer (SURVEY §2.6).  This is the
TPU-native layer on top: Switch-style top-1 routing with capacity, dense
dispatch/combine einsums (mask-based, fully static shapes for XLA), and an
expert-parallel execution mode where tokens travel to their expert's rank
and back via two ``lax.all_to_all``s over "ep" — the exact communication
pattern the reference's alltoall primitive was added for.
"""
from __future__ import annotations

import math
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


# ---------------------------------------------------------------------------
# The routed layer: top-k of a wide router over the experts held here
# ---------------------------------------------------------------------------
# What a decode step counts, a layer (``RoutedExperts`` sows them into
# the "counters" collection; models/kvcache.py:decode_step sums them
# over the layers and the replica over its dispatches): token-expert
# pairs routed, those of them that chose an expert held here, the held
# experts that at least one token chose, and the experts held.
COUNTERS = ("moe_routed_pairs", "moe_local_pairs", "moe_experts_touched",
            "moe_expert_slots")
_VMEM_BYTES = 64 << 20      # three blocks of expert weights, twice each
_HIDDEN_BLOCK = 640         # columns of an expert's hidden width a block
                            # (1280 was 1% faster at decode and leaves a
                            # prefill's tiles no room: PERF.md, PR 34)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def route(scores: jax.Array, per_token: int, held: tuple, *,
          norm: bool = True, scaling: float = 1.0, bias=None,
          groups: tuple = (1, 1)):
    """``scores`` [N, E] float32 (the router's, over every expert of the
    model) -> the ``per_token`` largest a token: ``(weights [N, k]
    float32, local [N, k], here [N, k])``: each chosen expert's weight
    (its score, over the sum of the chosen where ``norm``, times
    ``scaling``), its index among the experts held (``first`` ..
    ``first + count - 1`` of the model's) and whether it is held at all.
    A token whose experts all live elsewhere gets nothing here; none is
    dropped for want of room.  ``bias`` [E] float32, a router's
    correction bias, enters the choice (the largest of ``scores +
    bias``) and not the weights.  ``groups = (n_group, topk_group)``:
    the experts lie in ``n_group`` groups of consecutive indices, a group
    scores by the sum of its two largest (biased) scores, and a token
    chooses among the experts of its ``topk_group`` best groups only
    (DeepSeek-V3's group-limited routing)."""
    first, count = held
    choice = scores if bias is None else scores + bias
    n_group, topk_group = groups
    if n_group > 1:
        n, e = choice.shape
        grouped = choice.reshape(n, n_group, e // n_group)
        best = lax.top_k(jnp.sum(lax.top_k(grouped, 2)[0], -1),
                         topk_group)[1]                     # [N, topk_group]
        kept = jnp.any(best[..., None] == jnp.arange(n_group), -2)
        choice = jnp.where(jnp.repeat(kept, e // n_group, axis=-1), choice,
                           -jnp.inf)
    top, chosen = lax.top_k(choice, per_token)
    if bias is not None:
        top = jnp.take_along_axis(scores, chosen, axis=-1)
    if norm:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    local = chosen - first
    return top * scaling, local, (local >= 0) & (local < count)


def tile_rows(tokens: int, per_token: int, experts: int) -> int:
    """Rows of one tile of the grouped products: twice what an expert
    sees of ``tokens`` under even routing, a power of two from 16 (a
    packed bfloat16 tile's sublanes) to 128."""
    want = 2 * tokens * per_token / experts
    return int(min(128, max(16, 1 << max(0, math.ceil(math.log2(want))))))


def group_rows(local: jax.Array, here: jax.Array, count: int, tile: int):
    """The token-expert pairs sorted by expert, each held expert's group
    padded to whole tiles of ``tile`` rows.  ``local``, ``here`` [N, k]
    -> ``(row_token [R], at [N, k], tile_expert [R / tile], tiles [1],
    sizes [count])``: the token that each row computes (0 in a group's
    padding), the row of each pair (``R`` for a pair that is not
    computed here), the expert of each tile, how many tiles are live,
    and the pairs of each expert.  ``R`` holds the worst case, every
    pair local and every group's last tile all padding but one row; the
    cost lies in the live tiles alone."""
    n, k = local.shape
    pairs = n * k
    rows = -(-(pairs + count * (tile - 1)) // tile) * tile
    key = jnp.where(here, local, count).reshape(pairs)  # elsewhere: last
    order = jnp.argsort(key, stable=True)
    sorted_key = key[order]
    sizes = jnp.zeros(count + 1, jnp.int32).at[key].add(1)[:count]
    padded = -(-sizes // tile) * tile
    ends = jnp.cumsum(padded)
    group = jnp.minimum(sorted_key, count - 1)
    at_sorted = jnp.where(
        sorted_key < count,
        (ends - padded)[group] + jnp.arange(pairs)
        - (jnp.cumsum(sizes) - sizes)[group], rows)
    row_token = jnp.zeros(rows, jnp.int32).at[at_sorted].set(
        (order // k).astype(jnp.int32), mode="drop")
    at = jnp.zeros(pairs, jnp.int32).at[order].set(at_sorted)
    tiles = ends[-1] // tile
    # A dead tile repeats the last live one's expert: nothing is fetched.
    starts = jnp.minimum(jnp.arange(rows // tile), jnp.maximum(tiles - 1, 0))
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, starts * tile, side="right"), count - 1)
    return (row_token, at.reshape(n, k), tile_expert.astype(jnp.int32),
            tiles.reshape(1).astype(jnp.int32), sizes)


def experts_plain(x: jax.Array, sizes: jax.Array, gate: jax.Array,
                  up: jax.Array, down: jax.Array) -> jax.Array:
    """``x`` [R, D], rows grouped by expert in groups of ``sizes``
    [count] -> ``down_e (silu(gate_e x) * up_e x)`` a row, float32: the
    products in the weights' type, sums in float32."""
    dot = partial(lax.ragged_dot, group_sizes=sizes,
                  preferred_element_type=jnp.float32)
    hidden = jax.nn.silu(dot(x, gate)) * dot(x, up)
    return dot(hidden.astype(x.dtype), down)


def _experts_kernel(expert_ref, tiles_ref, x_ref, gate_ref, up_ref,
                    down_ref, o_ref, acc_ref):
    """One tile of rows, one block of an expert's hidden width: the
    gate and up products, the gated SiLU, and that block's part of the
    down product, summed in float32 over the blocks."""
    from jax.experimental import pallas as pl

    block = pl.program_id(1)

    @pl.when(pl.program_id(0) < tiles_ref[0])
    def _live():
        @pl.when(block == 0)
        def _start():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        x = x_ref[...]
        gated = jnp.dot(x, gate_ref[0], preferred_element_type=jnp.float32)
        hidden = gated * jax.nn.sigmoid(gated) * jnp.dot(
            x, up_ref[0], preferred_element_type=jnp.float32)
        acc_ref[...] += jnp.dot(hidden.astype(x.dtype), down_ref[0],
                                preferred_element_type=jnp.float32)

        @pl.when(block == pl.num_programs(1) - 1)
        def _store():
            o_ref[...] = acc_ref[...]


@partial(jax.jit, static_argnames=("tile", "block", "interpret"))
def _experts_pallas(x, tile_expert, tiles, gate, up, down, *, tile: int,
                    block: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, d = x.shape
    ff = gate.shape[-1]
    blocks = ff // block

    # Past the last live tile every index repeats the one before it, so
    # a dead grid step fetches and writes nothing.
    def live(t, tiles):
        return jnp.minimum(t, jnp.maximum(tiles[0] - 1, 0))

    def at(t, j, tiles):
        return jnp.where(t < tiles[0], j, blocks - 1)

    row_tile = pl.BlockSpec((tile, d),
                            lambda t, j, expert, tiles: (live(t, tiles), 0))
    wide = pl.BlockSpec(
        (1, d, block), lambda t, j, expert, tiles:
        (expert[live(t, tiles)], 0, at(t, j, tiles)))
    return pl.pallas_call(
        _experts_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows // tile, blocks),
            in_specs=[row_tile, wide, wide,
                      pl.BlockSpec(
                          (1, block, d), lambda t, j, expert, tiles:
                          (expert[live(t, tiles)], at(t, j, tiles), 0))],
            out_specs=row_tile,
            scratch_shapes=[pltpu.VMEM((tile, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
        name="hvd.moe_experts",
    )(tile_expert, tiles, x, gate, up, down)


def hidden_block(ff: int) -> int:
    """The widest block of an expert's hidden width, whole lanes, that
    divides it and stays within ``_HIDDEN_BLOCK`` (three such blocks of
    weights are held twice over in fast memory); the width itself where
    none does (toy sizes)."""
    return max((b for b in range(128, min(ff, _HIDDEN_BLOCK) + 1, 128)
                if ff % b == 0), default=ff)


class RoutedExperts(nn.Module):
    """The expert block of a sparse decoder, as one chip of an
    expert-parallel deployment runs it.  Input [B, T, D] -> [B, T, D].

    ``s = sigmoid(W_r x)`` over all ``num_experts`` of the model, the
    ``per_token`` largest (of ``s + c`` with a correction ``bias``
    ``c``, which the weights leave out; among the best ``groups``' where
    there are groups: ``route``), weights ``s_e / sum of the
    chosen`` (``norm_topk``) times ``scaling``; ``y = sum_e w_e E_e(x) +
    E_shared(x)`` with ``E(x) = W_down (silu(W_gate x) * W_up x)`` at
    width ``d_ff`` (the shared expert ``shared`` times as wide).  The
    sum runs over the chosen experts among ``held = (first, count)``,
    the ones whose weights this chip has; the shared expert is whole.
    What the other chips' experts would add is theirs to add: one chip
    runs no exchange, and nothing here stands in for it.

    The pairs routed here are sorted by expert, each group padded to
    whole tiles (``group_rows``), and the three products of every expert
    run grouped: on a TPU one Pallas kernel, ``hvd.moe_experts``, which
    reads the weights of an expert only where a tile of rows chose it;
    elsewhere ``lax.ragged_dot`` (``experts_plain``), the kernel's
    reference.  The router and the combine are float32."""
    num_experts: int
    per_token: int
    d_ff: int
    held: tuple
    shared: int = 1
    norm_topk: bool = True
    scaling: float = 1.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    interpret: bool = False          # run hvd.moe_experts interpreted
    bias: bool = False               # a correction bias, float32 [E]
    groups: tuple = (1, 1)           # (n_group, topk_group): see route

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        b, t, d = x.shape
        first, count = self.held
        if not 0 <= first <= first + count <= self.num_experts:
            raise ValueError(f"experts {first} to {first + count - 1} of "
                             f"{self.num_experts}")
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                            batch_axis=(0,))
        router = self.param("router", nn.initializers.lecun_normal(),
                            (d, self.num_experts), self.param_dtype)
        gate = self.param("experts_gate", init, (count, d, self.d_ff),
                          self.param_dtype)
        up = self.param("experts_up", init, (count, d, self.d_ff),
                        self.param_dtype)
        down = self.param("experts_down", init, (count, self.d_ff, d),
                          self.param_dtype)
        bias = self.param("router_bias", nn.initializers.zeros,
                          (self.num_experts,), jnp.float32) \
            if self.bias else None
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype,
                        param_dtype=self.param_dtype)
        shared = 0.0
        if self.shared:
            wide = self.shared * self.d_ff
            hidden = nn.silu(dense(wide, name="shared_gate")(x)) \
                * dense(wide, name="shared_up")(x)
            shared = dense(d, name="shared_down")(hidden) \
                .reshape(b * t, d).astype(jnp.float32)

        tokens = x.reshape(b * t, d).astype(self.dtype)
        with jax.named_scope("hvd.moe_route"):
            scores = jax.nn.sigmoid(jnp.dot(
                tokens.astype(jnp.float32), router.astype(jnp.float32),
                precision=lax.Precision.HIGHEST))
            weights, local, here = route(
                scores, self.per_token, self.held, norm=self.norm_topk,
                scaling=self.scaling, bias=bias, groups=self.groups)
        # Which experts of the model each token took, [N, k], its scores
        # over all of them and the weights it gave the chosen: for a
        # caller that replays a stream and asks (nothing is kept else).
        self.sow("routing", "chosen", local + first)
        self.sow("routing", "scores", scores)
        self.sow("routing", "weights", weights)
        experts = tuple(w.astype(self.dtype) for w in (gate, up, down))
        chunk = chunk_tokens(b * t, self.per_token, d)
        if chunk:
            routed, touched = _in_chunks(self._products, chunk, tokens,
                                         weights, local, here, experts)
        else:
            routed, sizes = self._products(tokens, weights, local, here,
                                           experts)
            touched = jnp.sum(sizes > 0)
        for name, value in zip(COUNTERS, (
                here.size, jnp.sum(here), touched, count)):
            self.sow("counters", name, jnp.asarray(value, jnp.int32))
        return (shared + routed).reshape(b, t, d).astype(self.dtype)

    def _products(self, tokens, weights, local, here, experts):
        """The routed pairs of ``tokens`` [N, d] sorted by expert and run
        grouped -> ``(sum_e w_e E_e(x) [N, d] float32, pairs of each held
        expert)``."""
        count = self.held[1]
        n = tokens.shape[0]
        with jax.named_scope("hvd.moe_route"):
            tile = tile_rows(n, self.per_token, self.num_experts)
            row_token, at, tile_expert, tiles, sizes = group_rows(
                local, here, count, tile)
            rows = jnp.take(tokens, row_token, axis=0)
        if _on_tpu() or self.interpret:
            routed = _experts_pallas(
                rows, tile_expert, tiles, *experts, tile=tile,
                block=hidden_block(self.d_ff), interpret=self.interpret)
        else:
            routed = experts_plain(rows, -(-sizes // tile) * tile, *experts)
        # A row that no live tile wrote may hold anything: chosen, not
        # multiplied by 0.
        picked = jnp.where(here[..., None],
                           jnp.take(routed, jnp.minimum(at, rows.shape[0] - 1),
                                    axis=0), 0.0)
        return jnp.sum(weights[..., None] * picked, axis=1), sizes


# A call whose routed pairs' float32 rows pass this many bytes (a long
# prefill: A.X-K1's prompts of 6,144 positions and more, 1.4 to 2.4 GB at
# once where the chip has under 3 left beside its weights and cache)
# runs the expert products over chunks of tokens of CHUNK_BYTES' worth;
# at or under it (every decode step, MiMo's 8,192 prompt at 2^30 exactly)
# a call's program is the one PRs up to 39 compiled.
WHOLE_BYTES = 1 << 30
CHUNK_BYTES = 1 << 29


def chunk_tokens(tokens: int, per_token: int, d: int) -> int:
    """Tokens a chunk of the expert products takes, 0 for all at once:
    the largest power of two whose pairs' rows stay within
    ``CHUNK_BYTES``, where all of them would pass ``WHOLE_BYTES``."""
    row = per_token * d * 4
    if tokens * row <= WHOLE_BYTES:
        return 0
    return 1 << (CHUNK_BYTES // row).bit_length() - 1


def _in_chunks(products, chunk: int, tokens, weights, local, here,
               experts):
    """``products`` over consecutive chunks of ``chunk`` tokens, one after
    the other (the last padded with tokens routed nowhere) -> ``(sum [N,
    d] float32, held experts that some token chose)``."""
    n = tokens.shape[0]
    pad = -n % chunk
    split = lambda x: jnp.pad(                                # noqa: E731
        x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)).reshape(
            (n + pad) // chunk, chunk, *x.shape[1:])
    routed, sizes = lax.map(
        lambda each: products(*each, experts),
        (split(tokens), split(weights), split(local), split(here)))
    return routed.reshape(n + pad, -1)[:n], jnp.sum(jnp.sum(sizes, 0) > 0)
