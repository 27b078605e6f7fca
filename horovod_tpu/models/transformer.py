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
from .kvcache import (_with_cache_index, cached_attention,  # noqa: F401
                      decode_step, fresh_cache, paged_apply,
                      paged_attention, paged_copy_block, paged_pool_leaves,
                      prefill)


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
    # Attention layer keeps its keys and values in the mutable "cache"
    # collection (models/kvcache.py has the layouts and the calls), so
    # continuous batching pays one token of compute per step.  Same
    # parameters as decode=False; mutually exclusive with ring/ulysses.
    decode: bool = False
    # Paged KV cache (ISSUE 14): with decode=True each layer's KV state
    # is a shared pool of kv_pool_blocks blocks of kv_block_tokens, and
    # every apply takes block_tables [B, M] and cursors [B]
    # (kvcache.paged_apply); parity-tested against the dense layout.
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
               theta: float, freqs=None) -> jax.Array:
    """x: [B, T, H, D]; positions: [T] global token positions shared by
    the batch, or [B, T] per-element positions (KV-cache decode, where
    every sequence in the continuous batch sits at its own depth).
    ``freqs`` [D/2], where given, replaces ``theta``'s (YaRN's)."""
    if freqs is None:
        freqs = rope_frequencies(x.shape[-1], theta)      # [D/2]
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
            # Through the cache (models/kvcache.py): RoPE at absolute
            # positions, fp32 softmax like every other path in this file.
            scale = 1.0 / math.sqrt(cfg.head_dim)
            rotate = partial(apply_rope, theta=cfg.rope_theta)
            if cfg.paged:
                if block_tables is None or cursors is None:
                    raise ValueError(
                        "paged decode needs block_tables [B, M] and "
                        "cursors [B] on every apply")
                if cfg.kv_pool_blocks <= 0:
                    raise ValueError(
                        "cfg.paged needs kv_pool_blocks > 0 (the per-layer "
                        "block pool size)")
                out = paged_attention(
                    self, q, k, v, block_tables, cursors, lengths,
                    pool_blocks=cfg.kv_pool_blocks,
                    block_tokens=cfg.kv_block_tokens, dtype=cfg.dtype,
                    scale=scale, rotate=rotate)
            else:
                out = cached_attention(
                    self, q, k, v, max_seq_len=cfg.max_seq_len,
                    dtype=cfg.dtype, scale=scale, rotate=rotate)
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


# Serving: the entry points of models/family.py are models/kvcache.py's
# (benchmarks/chip/reference.py reads ``_with_cache_index`` from here).
def _decode_flops(cfg: TransformerConfig, context: float) -> float:
    from ..telemetry import perfmodel
    return perfmodel.transformer_decode_flops(cfg, context)


FAMILY = ModelFamily(name="transformer", build=TransformerLM,
                     fresh_cache=fresh_cache, prefill=prefill,
                     decode_step=decode_step, decode_flops=_decode_flops,
                     paged_apply=paged_apply,
                     paged_copy_block=paged_copy_block,
                     paged_pool_leaves=paged_pool_leaves)


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
