"""Hybrid decoder: Mamba-2 layers beside grouped-query attention.

The family of IBM's Granite 4.0-H (``model_type`` ``granitemoehybrid``,
dense: no routed experts).  What sets it apart from
``models/transformer.py``, all of it configuration and none of it a
switch there:

- layer kinds by list (``layer_types``: ``"mamba"`` or ``"attention"``);
- grouped-query attention with **no positional encoding** and a score
  scale that is a published constant (``attention_multiplier``);
- scaled residuals (``h + residual_multiplier * f(norm(h))``), an
  embedding multiplier, logits divided by ``logits_scaling``, a tied head;
- the Mamba-2 mixer: ``[z, xBC, dt] = in_proj(x)``, a causal depthwise
  convolution with bias and SiLU over ``xBC``, the selective-state
  recurrence (``ops/ssm.py``), ``RMSNorm(y * silu(z))`` with the gate
  before the norm, ``out_proj``.

RMSNorm and the gated MLP are ``transformer.py``'s.

Serving (``decode=True``): the cache collection keeps the attention
layers' keys, values and write cursors (``models/kvcache.py``) and gains,
for each Mamba layer, ``conv_state`` [B, conv - 1, channels] (the last
inputs of the convolution, in the activations' type) and ``ssm_state``
(the H state matrices of P x N, **float32**: the recurrence sums over
thousands of steps; stored as ``ops/ssm.py:state_shape`` lays them out).
Every leaf has the slot on axis 0.  A call without a cache is a prefill:
the chunked scan over the prompt, which with ``lengths`` **stops at the
true length** (a recurrence cannot be rewound past padding the way a
write cursor can): padded positions leave the state unchanged, and the
window holds the last real positions, zeros before the start.  A call
with a cache is one decode step: the window shifts by one and the state
is updated once, in place (``hvd.ssm_update``).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops import ssm
from .family import ModelFamily
from .kvcache import (attend, cached_attention, decode_step, fresh_cache,
                      prefill)
from .transformer import MLP, RMSNorm

STATE_LEAVES = ("conv_state", "ssm_state")


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_size: int = 256
    d_model: int = 64
    d_ff: int = 256
    layer_types: tuple = ("mamba", "attention", "mamba")
    # attention layers
    num_heads: int = 4
    num_kv_heads: int = 2
    attention_multiplier: float = 0.25       # the score scale itself
    # Mamba-2 layers: heads x head_dim inner channels, one group
    mamba_heads: int = 4
    mamba_head_dim: int = 16
    mamba_state: int = 16
    mamba_conv: int = 4
    mamba_chunk: int = 8
    # the residual stream
    residual_multiplier: float = 1.0
    embedding_multiplier: float = 1.0
    logits_scaling: float = 1.0
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 256
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    decode: bool = False
    ssm_interpret: bool = False       # run hvd.ssm_update interpreted (tests)

    def __post_init__(self):
        unknown = set(self.layer_types) - {"mamba", "attention"}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        if self.num_heads % self.num_kv_heads or \
                self.d_model % self.num_heads:
            raise ValueError(
                f"{self.num_heads} query heads over {self.num_kv_heads} "
                f"key-value heads at width {self.d_model}")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads

    @property
    def ff_dim(self) -> int:
        return self.d_ff

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:
        """x, B and C pass the convolution together (one group)."""
        return self.d_inner + 2 * self.mamba_state

    @property
    def family(self):
        """What the serving replica asks of a model (models/family.py)."""
        return FAMILY


class GroupedAttention(nn.Module):
    """Query head ``h`` reads key-value head ``h // group``; no position
    term; ``softmax(attention_multiplier * q k^T + causal mask) v``."""
    cfg: HybridConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        dense = partial(nn.DenseGeneral, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype)
        kv, d = cfg.num_kv_heads, cfg.head_dim
        q = dense(features=(cfg.num_heads, d), name="wq")(x)
        k = dense(features=(kv, d), name="wk")(x)
        v = dense(features=(kv, d), name="wv")(x)
        if cfg.decode and not self.is_initializing():
            out = cached_attention(
                self, q, k, v, max_seq_len=cfg.max_seq_len,
                dtype=cfg.dtype, scale=cfg.attention_multiplier)
        else:
            out = attend(q, k, v, jnp.arange(x.shape[1])[None, :],
                         cfg.attention_multiplier)
        out = out.astype(cfg.dtype)
        return dense(features=cfg.d_model, axis=(-2, -1), name="wo")(out)


class Mamba2Mixer(nn.Module):
    cfg: HybridConfig

    @nn.compact
    def __call__(self, x: jax.Array, lengths=None) -> jax.Array:
        cfg = self.cfg
        b, t, _ = x.shape
        h, p, n = cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state
        inner, channels, width = cfg.d_inner, cfg.conv_channels, \
            cfg.mamba_conv
        dense = partial(nn.Dense, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype)
        conv_w = self.param("conv_kernel", nn.initializers.lecun_normal(),
                            (width, channels), cfg.param_dtype)
        conv_b = self.param("conv_bias", nn.initializers.zeros,
                            (channels,), cfg.param_dtype)
        a_log = self.param("A_log", _a_log_init, (h,), jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (h,), jnp.float32)
        skip = self.param("D", nn.initializers.ones, (h,), jnp.float32)

        proj = dense(2 * inner + 2 * n + h, name="in_proj")(x)
        z, xbc, dt = jnp.split(proj, [inner, inner + channels], axis=-1)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)  # [B, T, H]
        a = -jnp.exp(a_log)

        cached = cfg.decode and not self.is_initializing()
        stepping = cached and self.has_variable("cache", "ssm_state")
        if cached:
            window = self.variable("cache", "conv_state", jnp.zeros,
                                   (b, width - 1, channels), cfg.dtype)
            state = self.variable("cache", "ssm_state", jnp.zeros,
                                  ssm.state_shape(b, h, p, n), jnp.float32)
        with jax.named_scope("hvd.ssm_conv"):
            before = window.value if stepping \
                else jnp.zeros((b, width - 1, channels), xbc.dtype)
            padded = jnp.concatenate([before, xbc], axis=1)
            if cached:
                # The last inputs of the convolution: of the true length
                # where the prompt is padded, zeros before its start.
                # (A static ``padded[:, t:]`` for a decode step reads
                # simpler and cost the v5e 0.34 ms a step: PERF.md, PR 29.)
                end = jnp.full((b,), t, jnp.int32) if lengths is None \
                    else jnp.broadcast_to(
                        jnp.asarray(lengths, jnp.int32), (b,))
                window.value = jax.vmap(
                    lambda row, at: jax.lax.dynamic_slice_in_dim(
                        row, at, width - 1, axis=0))(padded, end)
            conv = sum(padded[:, i:i + t].astype(jnp.float32)
                       * conv_w[i].astype(jnp.float32)
                       for i in range(width)) + conv_b.astype(jnp.float32)
            xbc = nn.silu(conv).astype(cfg.dtype)
        xs, bs, cs = jnp.split(xbc, [inner, inner + n], axis=-1)
        xs = xs.reshape(b, t, h, p)

        if stepping:
            if t != 1:
                raise ValueError("a decode step takes one token a slot")
            y, state.value = ssm.ssm_update(
                state.value, xs[:, 0], dt[:, 0], a, bs[:, 0], cs[:, 0],
                skip, interpret=cfg.ssm_interpret)
            y = y[:, None]
        else:
            y, final = ssm.ssm_scan(xs, dt, a, bs, cs, skip,
                                    chunk=cfg.mamba_chunk, lengths=lengths)
            if cached:
                state.value = final
        y = y.reshape(b, t, inner) * nn.silu(z.astype(jnp.float32))
        y = RMSNorm(cfg.dtype, cfg.param_dtype, cfg.rms_norm_eps,
                    name="norm")(y)
        return dense(cfg.d_model, name="out_proj")(y)


def _a_log_init(key, shape, dtype):
    """``A = -exp(A_log)`` uniform on -16 to -1, the family's own."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype):
    """The inverse softplus of a log-uniform step on 0.001 to 0.1."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, jnp.log(1e-3),
                                    jnp.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


class HybridBlock(nn.Module):
    cfg: HybridConfig
    kind: str

    @nn.compact
    def __call__(self, x: jax.Array, lengths=None) -> jax.Array:
        cfg = self.cfg
        norm = partial(RMSNorm, cfg.dtype, cfg.param_dtype,
                       cfg.rms_norm_eps)
        mixed = Mamba2Mixer(cfg, name="mamba")(
            norm(name="mixer_norm")(x), lengths) if self.kind == "mamba" \
            else GroupedAttention(cfg, name="attn")(
                norm(name="mixer_norm")(x))
        x = x + cfg.residual_multiplier * mixed
        return x + cfg.residual_multiplier * MLP(cfg, name="mlp")(
            norm(name="mlp_norm")(x))


class HybridLM(nn.Module):
    """``apply(variables, tokens [B, T]) -> logits [B, T, vocab]`` in
    ``cfg.dtype``; ``lengths`` gives the true lengths of right-padded
    rows to a prefill through the cache."""
    cfg: HybridConfig

    @nn.compact
    def __call__(self, tokens: jax.Array, train: bool = False,
                 lengths=None) -> jax.Array:
        cfg = self.cfg
        embed = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="embed")
        x = embed(tokens) * cfg.embedding_multiplier
        for i, kind in enumerate(cfg.layer_types):
            x = HybridBlock(cfg, kind, name=f"layer_{i}")(x, lengths)
        x = RMSNorm(cfg.dtype, cfg.param_dtype, cfg.rms_norm_eps,
                    name="final_norm")(x)
        return embed.attend(x) / cfg.logits_scaling          # a tied head


# Serving: the entry points of models/family.py are models/kvcache.py's;
# its ``prefill`` hands ``lengths`` to the model (see Mamba2Mixer).
def _decode_flops(cfg: HybridConfig, context: float) -> float:
    from ..telemetry import perfmodel
    return perfmodel.hybrid_decode_flops(cfg, context)


FAMILY = ModelFamily(
    name="hybrid", build=HybridLM, fresh_cache=fresh_cache, prefill=prefill,
    decode_step=decode_step, decode_flops=_decode_flops,
    state_leaves=STATE_LEAVES,
    paged_missing="recurrent state in KVBlockPool: a Mamba layer's "
                  "convolution window and state matrix are a fixed size a "
                  "slot and live until replaced, and serving/kvpool.py "
                  "holds blocks of keys and values only")
