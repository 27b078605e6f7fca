"""Decoder-only Transformer LM family, TPU-first.

The reference framework ships CNN benchmark models but no attention code at
all (SURVEY §5.7); long-context training is a first-class goal here, so the
flagship language model supports four attention execution strategies:

- ``dense``:   fused-by-XLA einsum softmax attention;
- ``flash``:   the Pallas MXU kernel (ops/flash_attention.py);
- ``ring``:    exact ring attention over the "sp" mesh axis — sequence
               sharded, KV rotating over ICI neighbors (parallel/ring_attention.py);
- ``ulysses``: all-to-all head/sequence reshard over "sp", full-sequence
               flash locally (parallel/ulysses.py).

Design notes (TPU-first, not a port): bf16 activations with fp32 params and
fp32 softmax/log-softmax; RoPE positions are *global* so sequence sharding
never changes the math; all shapes static; per-block ``jax.checkpoint``
(remat) trades FLOPs for HBM on long sequences.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from .family import ModelFamily


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    num_layers: int = 12
    num_heads: int = 12
    d_model: int = 768
    d_ff: int | None = None           # default 4 * d_model (SwiGLU-scaled)
    max_seq_len: int = 8192
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16         # activation/compute dtype
    param_dtype: Any = jnp.float32
    attention: str = "dense"          # dense | flash | ring | ulysses
    causal: bool = True
    remat: bool = False               # checkpoint each block
    # Remat granularity when remat=True: "full" recomputes the whole
    # block; "dots" saves matmul outputs and recomputes only the cheap
    # elementwise work (jax.checkpoint_policies.checkpoint_dots) — less
    # recompute for modestly more HBM, the middle point of the
    # memory/FLOPs trade (SURVEY: jax.checkpoint for remat).
    remat_policy: str = "full"        # full | dots
    # flash kernel tiling (bwd defaults to the fwd blocks; the backward
    # kernel holds more live VMEM tiles so its optimum is often smaller)
    block_q: int = 128
    block_k: int = 128
    block_q_bwd: int | None = None
    block_k_bwd: int | None = None
    flash_interpret: bool = False     # run Pallas kernels interpreted (tests)
    # sequence-parallel wiring (ring/ulysses)
    mesh: Any = None
    sp_axis: str = "sp"
    batch_spec: Any = None            # PartitionSpec for the batch dim
    # Mixture-of-Experts FFN (0 = dense MLP). With a mesh carrying an
    # "ep" axis > 1, experts shard over it (two all_to_alls per layer).
    moe_experts: int = 0
    moe_capacity_factor: float = 1.25
    ep_axis: str = "ep"
    # Incremental (KV-cache) decoding for inference serving: each
    # Attention layer keeps cached_key/cached_value [B, max_seq_len, H, D]
    # plus a per-batch-element write index in the mutable "cache"
    # collection, so continuous batching (serving/batcher.py) pays one
    # token of compute per step instead of re-running the full forward.
    # Parameters are identical to the decode=False model; see prefill()
    # and decode_step() below.  Mutually exclusive with ring/ulysses.
    decode: bool = False
    # Paged KV cache (ISSUE 14, serving/kvpool.py): with decode=True and
    # paged=True each layer's KV state is a shared block pool
    # [kv_pool_blocks + 1, kv_block_tokens, H, D] (the last row is a
    # write sink for padded positions) instead of dense per-slot
    # arrays; every apply takes explicit block_tables [B, M] (logical
    # block i of row b lives in pool row block_tables[b, i]) and
    # cursors [B] (each row's write position).  Storage scales with
    # live token residency; parameters are unchanged, and the math is
    # parity-tested against the dense decode path.
    paged: bool = False
    kv_pool_blocks: int = 0
    kv_block_tokens: int = 16

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.num_heads == 0
        return self.d_model // self.num_heads

    @property
    def ff_dim(self) -> int:
        return self.d_ff if self.d_ff is not None else 4 * self.d_model

    @property
    def family(self):
        """What the serving replica asks of a model (models/family.py)."""
        return FAMILY


# ---------------------------------------------------------------------------
# RoPE (global positions — invariant under sequence sharding)
# ---------------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float) -> jax.Array:
    """x: [B, T, H, D]; positions: [T] global token positions shared by
    the batch, or [B, T] per-element positions (KV-cache decode, where
    every sequence in the continuous batch sits at its own depth)."""
    freqs = rope_frequencies(x.shape[-1], theta)          # [D/2]
    if positions.ndim == 1:
        positions = positions[None, :]                    # [1,T]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B|1,T,D/2]
    cos = jnp.cos(angles)[:, :, None, :]                  # [B|1,T,1,D/2]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention dispatch
# ---------------------------------------------------------------------------
def _axis_is_manual(axis: str) -> bool:
    """True when tracing inside a shard_map manual region over ``axis``."""
    try:
        jax.lax.axis_index(axis)
        return True
    except Exception:  # noqa: BLE001 - unbound axis name
        return False


def _make_attention(cfg: TransformerConfig) -> Callable:
    """Returns attn(q, k, v) for global [B, T, H, D] BTHD tensors."""
    if cfg.attention == "dense":
        from ..ops.flash_attention import mha_reference
        return partial(mha_reference, causal=cfg.causal)
    if cfg.attention == "flash":
        from ..ops.flash_attention import flash_attention
        return partial(flash_attention, causal=cfg.causal,
                       block_q=cfg.block_q, block_k=cfg.block_k,
                       block_q_bwd=cfg.block_q_bwd,
                       block_k_bwd=cfg.block_k_bwd,
                       interpret=cfg.flash_interpret)
    if cfg.attention in ("ring", "ulysses"):
        if cfg.mesh is None:
            raise ValueError(
                f"attention='{cfg.attention}' needs cfg.mesh to shard the "
                f"sequence over axis '{cfg.sp_axis}'")
        n = cfg.mesh.shape.get(cfg.sp_axis, 1)
        # cfg.batch_spec names the mesh axis (or axis tuple) the batch dim
        # is sharded over, e.g. "dp" — None means replicated batch.
        spec = P(cfg.batch_spec, cfg.sp_axis, None, None)
        if cfg.attention == "ring":
            from ..parallel.ring_attention import ring_attention
            inner = partial(ring_attention, axis=cfg.sp_axis,
                            causal=cfg.causal, axis_size=n)
        else:
            from ..parallel.ulysses import ulysses_attention
            inner = partial(ulysses_attention, axis=cfg.sp_axis,
                            causal=cfg.causal, axis_size=n,
                            attn_fn=partial(_bthd_attn_adapter,
                                            cfg=cfg))

        if _axis_is_manual(cfg.sp_axis):
            # Already inside a manual region over sp (the Trainer maps the
            # whole step over (dp, sp)): q/k/v are local sequence shards,
            # call the SP algorithm directly.
            return inner

        def dispatch(q, k, v):
            return jax.shard_map(inner, mesh=cfg.mesh,
                                 in_specs=(spec, spec, spec),
                                 out_specs=spec, check_vma=True)(q, k, v)
        return dispatch
    raise ValueError(f"Unknown attention impl: {cfg.attention}")


def _bthd_attn_adapter(q, k, v, causal=False, sm_scale=None, *,
                       cfg: TransformerConfig):
    """Full-sequence attention used inside Ulysses' head shard: flash on
    TPU, dense elsewhere."""
    if jax.default_backend() == "tpu" or cfg.flash_interpret:
        from ..ops.flash_attention import flash_attention
        return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                               block_q=cfg.block_q, block_k=cfg.block_k,
                               block_q_bwd=cfg.block_q_bwd,
                               block_k_bwd=cfg.block_k_bwd,
                               interpret=cfg.flash_interpret)
    from ..ops.flash_attention import mha_reference
    return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale)


# ---------------------------------------------------------------------------
# Modules
# ---------------------------------------------------------------------------
class RMSNorm(nn.Module):
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param("scale", nn.initializers.ones,
                           (x.shape[-1],), self.param_dtype)
        x32 = x.astype(jnp.float32)
        norm = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps)
        return (norm * scale).astype(self.dtype)


class Attention(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x: jax.Array, block_tables=None, cursors=None,
                 lengths=None) -> jax.Array:
        cfg = self.cfg
        b, t, _ = x.shape
        dense = partial(nn.DenseGeneral, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype)
        qkv_shape = (cfg.num_heads, cfg.head_dim)
        q = dense(features=qkv_shape, name="wq")(x)
        k = dense(features=qkv_shape, name="wk")(x)
        v = dense(features=qkv_shape, name="wv")(x)

        if cfg.decode and not self.is_initializing():
            if cfg.attention in ("ring", "ulysses"):
                raise ValueError(
                    "cfg.decode is incompatible with sequence-parallel "
                    f"attention ('{cfg.attention}'): the KV cache is a "
                    "whole-sequence structure")
            if cfg.paged:
                if block_tables is None or cursors is None:
                    raise ValueError(
                        "paged decode needs block_tables [B, M] and "
                        "cursors [B] on every apply")
                with jax.named_scope("hvd.decode_attend"):
                    out = self._decode_attend_paged(
                        q, k, v, block_tables, cursors, lengths)
            else:
                with jax.named_scope("hvd.decode_attend"):
                    out = self._decode_attend(q, k, v)
        else:
            if cfg.attention in ("ring", "ulysses") and \
                    _axis_is_manual(cfg.sp_axis) and \
                    not self.is_initializing():
                # Sequence dim is a local shard: RoPE positions are global.
                positions = jax.lax.axis_index(cfg.sp_axis) * t \
                    + jnp.arange(t)
            else:
                positions = jnp.arange(t)
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)

            if self.is_initializing() and \
                    cfg.attention in ("ring", "ulysses"):
                # Shape-only trace with a tiny batch: parameter shapes
                # don't depend on the attention execution strategy.
                attn = _make_attention(
                    dataclasses.replace(cfg, attention="dense"))
            else:
                attn = _make_attention(cfg)
            out = attn(q, k, v)                           # [B,T,H,D]
        out = out.astype(cfg.dtype)
        return dense(features=cfg.d_model, axis=(-2, -1), name="wo")(out)

    def _decode_attend(self, q: jax.Array, k: jax.Array,
                       v: jax.Array) -> jax.Array:
        """Incremental attention over the mutable KV cache: write this
        call's K/V at each batch element's own cache depth, attend
        causally over the cached prefix.  Positions are absolute, so the
        RoPE math matches the full forward pass exactly; fp32 softmax
        like every other path in this file."""
        cfg = self.cfg
        b, t, h, d = q.shape
        s = cfg.max_seq_len
        cached_k = self.variable("cache", "cached_key", jnp.zeros,
                                 (b, s, h, d), cfg.dtype)
        cached_v = self.variable("cache", "cached_value", jnp.zeros,
                                 (b, s, h, d), cfg.dtype)
        index = self.variable("cache", "cache_index",
                              lambda: jnp.zeros((b,), jnp.int32))
        idx = index.value                                   # [B]
        positions = idx[:, None] + jnp.arange(t)[None, :]   # [B,T]
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        write = jax.vmap(lambda cache, new, i:
                         jax.lax.dynamic_update_slice(cache, new,
                                                      (i, 0, 0)))
        cached_k.value = write(cached_k.value, k.astype(cfg.dtype), idx)
        cached_v.value = write(cached_v.value, v.astype(cfg.dtype), idx)
        index.value = idx + t
        # Causal mask over absolute positions.  Right-padded prefill
        # garbage always sits at key positions strictly greater than the
        # current query position (prefill() rewinds the write cursor to
        # the true length, and decode overwrites forward from there), so
        # key_pos <= q_pos alone keeps it invisible.
        key_pos = jnp.arange(s)
        mask = key_pos[None, None, :] <= positions[:, :, None]  # [B,T,S]
        qf = q.astype(jnp.float32)
        kf = cached_k.value.astype(jnp.float32)
        vf = cached_v.value.astype(jnp.float32)
        logits = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) / math.sqrt(d)
        logits = jnp.where(mask[:, None, :, :], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, vf)

    def _decode_attend_paged(self, q: jax.Array, k: jax.Array,
                             v: jax.Array, block_tables, cursors,
                             lengths) -> jax.Array:
        """Incremental attention over the shared block pool (ISSUE 14):
        this call's K/V scatter into pool rows addressed through each
        row's block table, then the table gathers the sequence back as
        [B, M*bt, H, D] (one block-table-indexed gather — logical
        position p of row b lives at pool[tables[b, p//bt], p%bt]) for
        the same absolute-position causal attention as the dense path.
        ``lengths`` masks right-padded prefill calls: padded positions
        write to the pool's sink row (never a real block) and padded
        logits are garbage the caller ignores, exactly like the dense
        path's masked tail."""
        cfg = self.cfg
        b, t, h, d = q.shape
        bt = cfg.kv_block_tokens
        if cfg.kv_pool_blocks <= 0:
            raise ValueError(
                "cfg.paged needs kv_pool_blocks > 0 (the per-layer "
                "block pool size)")
        sink = cfg.kv_pool_blocks                    # the write sink row
        key_pool = self.variable("cache", "key_pool", jnp.zeros,
                                 (sink + 1, bt, h, d), cfg.dtype)
        value_pool = self.variable("cache", "value_pool", jnp.zeros,
                                   (sink + 1, bt, h, d), cfg.dtype)
        tables = jnp.asarray(block_tables, jnp.int32)      # [B, M]
        cursors = jnp.asarray(cursors, jnp.int32)          # [B]
        m = tables.shape[1]
        if lengths is None:
            valid = jnp.ones((b, t), bool)
        else:
            valid = jnp.arange(t)[None, :] \
                < jnp.asarray(lengths, jnp.int32)[:, None]
        positions = cursors[:, None] + jnp.arange(t)[None, :]   # [B,T]
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        logical = jnp.minimum(positions // bt, m - 1)
        phys = jnp.take_along_axis(tables, logical, axis=1)     # [B,T]
        phys = jnp.where(valid, phys, sink)
        offs = positions % bt
        kp = key_pool.value.at[phys.reshape(-1), offs.reshape(-1)].set(
            k.astype(cfg.dtype).reshape(b * t, h, d))
        vp = value_pool.value.at[phys.reshape(-1), offs.reshape(-1)].set(
            v.astype(cfg.dtype).reshape(b * t, h, d))
        key_pool.value, value_pool.value = kp, vp
        # Gather each row's sequence back in logical order; positions
        # past the cursor (stale or sink-backed) are masked exactly like
        # the dense path's not-yet-overwritten tail.
        k_seq = jnp.take(kp, tables, axis=0).reshape(b, m * bt, h, d)
        v_seq = jnp.take(vp, tables, axis=0).reshape(b, m * bt, h, d)
        key_pos = jnp.arange(m * bt)
        mask = key_pos[None, None, :] <= positions[:, :, None]  # [B,T,S]
        qf = q.astype(jnp.float32)
        kf = k_seq.astype(jnp.float32)
        vf = v_seq.astype(jnp.float32)
        logits = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) / math.sqrt(d)
        logits = jnp.where(mask[:, None, :, :], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, vf)


class MLP(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        dense = partial(nn.Dense, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype)
        gate = dense(cfg.ff_dim, name="gate")(x)
        up = dense(cfg.ff_dim, name="up")(x)
        return dense(cfg.d_model, name="down")(nn.silu(gate) * up)


class Block(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x: jax.Array, block_tables=None, cursors=None,
                 lengths=None) -> jax.Array:
        cfg = self.cfg
        x = x + Attention(cfg, name="attn")(
            RMSNorm(cfg.dtype, cfg.param_dtype, name="attn_norm")(x),
            block_tables, cursors, lengths)
        if cfg.moe_experts > 0:
            from .moe import MoEMLP
            ffn = MoEMLP(num_experts=cfg.moe_experts, d_ff=cfg.ff_dim,
                         capacity_factor=cfg.moe_capacity_factor,
                         dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         ep_mesh=cfg.mesh, ep_axis=cfg.ep_axis,
                         name="moe")
        else:
            ffn = MLP(cfg, name="mlp")
        x = x + ffn(RMSNorm(cfg.dtype, cfg.param_dtype, name="mlp_norm")(x))
        return x


class TransformerLM(nn.Module):
    """Decoder-only LM. ``apply(variables, tokens[B,T] int32) -> logits
    [B, T, vocab]`` in ``cfg.dtype``.

    Logits stay in the compute dtype on purpose: at benchmark scale the
    fp32 copy of a [B, S, vocab] tensor is gigabytes of HBM traffic,
    and the loss (`training.cross_entropy_loss` → ops/loss.py streaming
    CE) does its math in fp32 without needing an fp32 input tensor."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens: jax.Array, train: bool = False,
                 block_tables=None, cursors=None,
                 lengths=None) -> jax.Array:
        cfg = self.cfg
        embed = nn.Embed(cfg.vocab_size, cfg.d_model,
                         dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         name="embed")
        x = embed(tokens)
        block = Block
        if cfg.remat:
            policy = None
            if cfg.remat_policy == "dots":
                policy = jax.checkpoint_policies.checkpoint_dots
            elif cfg.remat_policy != "full":
                raise ValueError(
                    f"unknown remat_policy {cfg.remat_policy!r} "
                    "(expected 'full' or 'dots')")
            block = nn.remat(Block, prevent_cse=False, policy=policy)
        for i in range(cfg.num_layers):
            x = block(cfg, name=f"layer_{i}")(x, block_tables, cursors,
                                              lengths)
        x = RMSNorm(cfg.dtype, cfg.param_dtype, name="final_norm")(x)
        return nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="lm_head")(x)


# ---------------------------------------------------------------------------
# KV-cache incremental decoding (inference serving; serving/replica.py)
# ---------------------------------------------------------------------------
def _with_cache_index(cache: dict, lengths) -> dict:
    """Return ``cache`` with every layer's write cursor set to
    ``lengths`` (scalar or [B] int32) — prefill() rewinds past padding
    with it, and the serving replica resets recycled batch slots."""
    lengths = jnp.asarray(lengths, jnp.int32)

    def fix(node):
        if not isinstance(node, dict):
            return node
        return {key: (jnp.broadcast_to(lengths, val.shape).astype(val.dtype)
                      if key == "cache_index" else fix(val))
                for key, val in node.items()}
    from flax.core import unfreeze
    return fix(unfreeze(cache))


def prefill(model: TransformerLM, variables: dict, tokens: jax.Array,
            lengths=None) -> tuple[jax.Array, dict]:
    """Run the prompt through a ``decode=True`` model and return
    ``(logits [B, T, vocab], cache)``.

    ``lengths`` ([B] or scalar) gives each row's true prompt length when
    ``tokens`` is right-padded to a shared bucket: the KV write cursor
    rewinds to it so the first decode_step overwrites the pad garbage,
    and the causal mask keeps the not-yet-overwritten tail invisible
    (it sits at strictly greater positions than every live query).  The
    next-token logits of row b are ``logits[b, lengths[b] - 1]``."""
    from flax.core import unfreeze
    logits, mut = model.apply(variables, tokens, mutable=["cache"])
    cache = unfreeze(mut["cache"])
    if lengths is not None:
        cache = _with_cache_index(cache, lengths)
    return logits, cache


def fresh_cache(model: TransformerLM, params, slots: int) -> dict:
    """A dense slot cache of ``slots`` empty rows: one apply creates the
    cache collection (its only writes land at position 0), and the write
    cursors go back to 0."""
    _, mut = model.apply({"params": params},
                         jnp.zeros((slots, 1), jnp.int32), mutable=["cache"])
    return _with_cache_index(mut["cache"], 0)


def decode_step(model: TransformerLM, variables: dict, cache: dict,
                tokens: jax.Array) -> tuple[jax.Array, dict]:
    """One incremental step of a ``decode=True`` model: ``tokens``
    [B, 1] (or [B]) → ``(logits [B, 1, vocab], updated cache)``.  Each
    batch element advances at its own cache depth, which is what lets
    continuous batching admit a fresh prefill into a half-decoded
    batch."""
    from flax.core import unfreeze
    if tokens.ndim == 1:
        tokens = tokens[:, None]
    logits, mut = model.apply({**variables, "cache": cache}, tokens,
                              mutable=["cache"])
    return logits, unfreeze(mut["cache"])


def paged_apply(model: TransformerLM, variables: dict, cache: dict,
                tokens: jax.Array, block_tables, cursors,
                lengths=None) -> tuple[jax.Array, dict]:
    """One paged-cache apply (``decode=True, paged=True``): prefill and
    decode are the SAME call — ``tokens [B, T]`` (T = 1 for a decode
    step, a padded prompt bucket for prefill) write into the pool
    through each row's ``block_tables`` entry at its ``cursors``
    position and attend over the gathered prefix.  No write-cursor
    rewinding: ``lengths`` keeps padded positions out of real blocks
    entirely (they land in the pool's sink row)."""
    from flax.core import unfreeze
    if tokens.ndim == 1:
        tokens = tokens[:, None]
    logits, mut = model.apply({**variables, "cache": cache}, tokens,
                              block_tables=block_tables,
                              cursors=cursors, lengths=lengths,
                              mutable=["cache"])
    return logits, unfreeze(mut["cache"])


def paged_copy_block(cache: dict, src: int, dst: int) -> dict:
    """The tensor half of a copy-on-write: copy pool row ``src`` to
    ``dst`` in every layer's key/value pool (the id half lives in
    serving/kvpool.py ``cow``)."""
    def fix(node):
        if not isinstance(node, dict):
            return node
        return {key: (val.at[dst].set(val[src])
                      if key in ("key_pool", "value_pool") else fix(val))
                for key, val in node.items()}
    from flax.core import unfreeze
    return fix(unfreeze(cache))


def _decode_flops(cfg: TransformerConfig, context: float) -> float:
    from ..telemetry import perfmodel
    return perfmodel.transformer_decode_flops(cfg, context)


FAMILY = ModelFamily(name="transformer", build=TransformerLM,
                     fresh_cache=fresh_cache, prefill=prefill,
                     decode_step=decode_step, decode_flops=_decode_flops)


# ---------------------------------------------------------------------------
# Presets
# ---------------------------------------------------------------------------
def gpt_small(**overrides) -> TransformerConfig:
    """~124M params (GPT-2 small shape)."""
    return TransformerConfig(**{**dict(
        vocab_size=50304, num_layers=12, num_heads=12, d_model=768,
        max_seq_len=1024), **overrides})


def gpt_medium(**overrides) -> TransformerConfig:
    """~350M params."""
    return TransformerConfig(**{**dict(
        vocab_size=50304, num_layers=24, num_heads=16, d_model=1024,
        max_seq_len=2048), **overrides})


def gpt_tiny(**overrides) -> TransformerConfig:
    """Test-sized config."""
    return TransformerConfig(**{**dict(
        vocab_size=256, num_layers=2, num_heads=4, d_model=64,
        max_seq_len=256), **overrides})
