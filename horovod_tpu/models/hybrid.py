"""Hybrid decoder: recurrent layers beside grouped-query attention.

First the family of IBM's Granite 4.0-H (``model_type``
``granitemoehybrid``, dense: no routed experts), then that of Upstage's
Solar Open 2 (``solar_open2``: delta-rule layers, a gated softmax layer
in four, routed experts).  What sets it apart from
``models/transformer.py``, all of it configuration and none of it a
switch there:

- layer kinds by list (``layer_types``: ``"mamba"``, ``"kda"``,
  ``"attention"`` or ``"window"``);
- grouped-query attention with **no positional encoding** and a score
  scale that is a published constant (``attention_multiplier``);
- scaled residuals (``h + residual_multiplier * f(norm(h))``), an
  embedding multiplier, logits divided by ``logits_scaling``, a tied head;
- the Mamba-2 mixer: ``[z, xBC, dt] = in_proj(x)``, a causal depthwise
  convolution with bias and SiLU over ``xBC``, the selective-state
  recurrence (``ops/ssm.py``), ``RMSNorm(y * silu(z))`` with the gate
  before the norm, ``out_proj``.

- the delta-rule mixer (Kimi Delta Attention, ``"kda"``): ``q``, ``k``,
  ``v`` through a causal depthwise convolution with SiLU, ``q`` and ``k``
  normalised a head, a decay a key channel and a write strength a head
  (``ops/kda.py`` has the recurrence), ``RMSNorm`` a head times a
  sigmoid gate of low rank, ``out_proj``;
- for Solar Open 2: a head width of its own (``attn_head_dim``) and an
  elementwise sigmoid gate (``attn_gate``) on the attention layer, the
  routed expert block of ``models/moe.py`` in place of the MLP where
  ``num_experts`` is set, an untied head (``tie_embeddings=False``).

- for Xiaomi's MiMo-V2 (``mimo_v2``): a second kind of softmax layer,
  ``"window"`` (the last ``window`` keys, a learned sink a query head in
  the softmax, key-value heads and a rotary base of its own), keys and
  queries wider than values (``attn_value_dim``), rotary positions on
  the first ``attn_rotary_dim`` channels of a head, values scaled
  (``attn_value_scale``), a dense MLP in the layers ``dense_layers``
  lists and the expert block elsewhere, the router's correction bias
  (``router_bias``) and no shared expert.

- for SK Telecom's A.X-K1 (``axk1``, DeepSeek-V3's layer): a fifth kind,
  ``"latent"``, multi-head latent attention (``LatentAttention``): a
  query through a rank of ``q_lora_rank`` and a norm, one latent of
  ``kv_lora_rank`` channels with a norm and one rotary key of
  ``qk_rope_head_dim`` channels that every head shares, YaRN rotary
  frequencies and score scale (``rope_scaling``), and in the router
  expert groups (``n_group``, ``topk_group``).

- for ByteDance's Ouro (``ouro``, a looped language model): the stack
  run ``loops`` times a token with the same weights, ``final_norm``
  after every pass and the head after the last, each pass with a cache
  of its own (``models/kvcache.py``: pass ``u`` > 1 keeps its leaves
  under the scope ``pass_<u>``), and ``sandwich_norm``: an RMSNorm after
  each sub-layer as well as before it,
  ``h + norm_post(f(norm_pre(h)))``.

RMSNorm, the gated MLP and ``apply_rope`` are ``transformer.py``'s.

Serving (``decode=True``): the cache collection keeps the attention
layers' keys, values and write cursors (``models/kvcache.py``; a window
layer's are rings of ``window`` positions) and gains,
for each Mamba layer, ``conv_state`` [B, conv - 1, channels] (the last
inputs of the convolution, in the activations' type) and ``ssm_state``
(the H state matrices of P x N, **float32**: the recurrence sums over
thousands of steps; stored as ``ops/ssm.py:state_shape`` lays them out);
for each delta-rule layer a ``conv_state`` likewise and ``kda_state``
[B, H, K, V], float32 too.  Every leaf has the slot on axis 0.  A call
without a cache is a prefill:
the chunked scan over the prompt, which with ``lengths`` **stops at the
true length** (a recurrence cannot be rewound past padding the way a
write cursor can): padded positions leave the state unchanged, and the
window holds the last real positions, zeros before the start.  A call
with a cache is one decode step: the window shifts by one and the state
is updated once, in place (``hvd.ssm_update``, ``hvd.kda_update``).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from ..ops import kda, mla, ssm
from . import moe
from .family import ModelFamily
from .kvcache import (attend, cached_attention, decode_step, fresh_cache,
                      latent_attention, prefill)
from .transformer import MLP, RMSNorm, apply_rope

STATE_LEAVES = ("conv_state", "ssm_state", "kda_state")
KINDS = ("mamba", "kda", "attention", "window", "latent")


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
    attn_head_dim: int = 0           # 0: d_model // num_heads
    attn_gate: bool = False          # out * sigmoid(W_gate x), elementwise
    attn_value_dim: int = 0          # 0: as wide as the keys
    attn_value_scale: float = 1.0    # sum_j p_ij (scale * v_j)
    attn_rotary_dim: int = 0         # channels of a head that rotate; 0: none
    rope_theta: float = 10000.0
    attn_sink: bool = False          # a learned column of the softmax
    # "window" layers: the last ``window`` keys, the query's own among
    # them; their own key-value heads (0: num_kv_heads), rotary base (0:
    # rope_theta) and sink
    window: int = 0
    window_kv_heads: int = 0
    window_rope_theta: float = 0.0
    window_sink: bool = False
    # "latent" layers (multi-head latent attention): the query's and the
    # latent's ranks, a head's channels without and with rotary
    # positions (its values are attn_value_dim wide), and YaRN's
    # settings as a checkpoint's rope_scaling gives them (None: plain)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    rope_scaling: Any = None
    # Mamba-2 layers: heads x head_dim inner channels, one group
    mamba_heads: int = 4
    mamba_head_dim: int = 16
    mamba_state: int = 16
    mamba_conv: int = 4
    mamba_chunk: int = 8
    # delta-rule layers: heads x head_dim channels each of q, k and v
    kda_heads: int = 4
    kda_head_dim: int = 16
    kda_conv: int = 4
    kda_chunk: int = 64
    # the routed expert block (models/moe.py), where num_experts is set:
    # the router's width, the experts a token takes, their hidden width,
    # and which of them this chip holds, (first, count)
    num_experts: int = 0
    experts_per_token: int = 0
    expert_ff: int = 0
    experts_held: tuple = ()
    shared_experts: int = 1
    norm_topk: bool = True
    routed_scaling: float = 1.0
    router_bias: bool = False        # a correction bias in the choice
    n_group: int = 1                 # the router's expert groups, and how
    topk_group: int = 1              # many of them a token chooses among
    dense_layers: tuple = ()         # layers that keep the MLP of d_ff
    # the residual stream
    residual_multiplier: float = 1.0
    embedding_multiplier: float = 1.0
    logits_scaling: float = 1.0
    tie_embeddings: bool = True
    # passes of the whole stack a token, the weights shared, final_norm
    # after each; a norm after each sub-layer too (sandwich)
    loops: int = 1
    sandwich_norm: bool = False
    rms_norm_eps: float = 1e-5
    max_seq_len: int = 256
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    decode: bool = False
    # run the family's kernels interpreted (tests): hvd.ssm_update,
    # hvd.kda_update, hvd.moe_experts
    interpret: bool = False

    def __post_init__(self):
        if isinstance(self.rope_scaling, dict):      # hashable, as a field
            object.__setattr__(self, "rope_scaling",
                               tuple(sorted(self.rope_scaling.items())))
        unknown = set(self.layer_types) - set(KINDS)
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        if self.num_heads % self.num_kv_heads or \
                self.num_heads % (self.window_kv_heads or 1) or \
                (not self.attn_head_dim and self.d_model % self.num_heads):
            raise ValueError(
                f"{self.num_heads} query heads over {self.num_kv_heads} "
                f"key-value heads at width {self.d_model}")
        if "window" in self.layer_types and self.window < 1:
            raise ValueError("a \"window\" layer needs ``window``")
        if self.attn_rotary_dim % 2 or self.attn_rotary_dim > self.head_dim:
            raise ValueError(f"{self.attn_rotary_dim} rotary channels of "
                             f"{self.head_dim}")
        # A pass keeps a cache of its own in cached_attention's leaves
        # alone: a recurrent state or a latent leaf would be shared.
        looped = set(self.layer_types) - {"attention", "window"}
        if self.loops < 1 or (self.loops > 1 and looped):
            raise ValueError(f"loops={self.loops}: a stack runs at least "
                             "once, and more often only where every layer "
                             f"is attention (not {sorted(looped)})")

    @property
    def head_dim(self) -> int:
        if self.kv_lora_rank:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.attn_head_dim or self.d_model // self.num_heads

    @property
    def value_dim(self) -> int:
        return self.attn_value_dim or self.head_dim

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
    def kda_inner(self) -> int:
        return self.kda_heads * self.kda_head_dim

    @property
    def kda_rank(self) -> int:
        """Of the decay's and the output gate's low-rank pairs: a head's
        width (``kda_use_full_proj: false``)."""
        return self.kda_head_dim

    @property
    def family(self):
        """What the serving replica asks of a model (models/family.py);
        with routed experts its decode step also counts the routing."""
        return ROUTED_FAMILY if self.num_experts else FAMILY


def yarn_frequencies(width: int, theta: float, scaling) -> np.ndarray:
    """The inverse frequencies of ``width`` rotary channels under YaRN
    (DeepSeek-V3's ``yarn_find_correction_range``): pair ``i``'s plain
    ``theta^(-2i / width)`` below ``low``, divided by ``factor`` above
    ``high``, a linear ramp between, where ``low`` and ``high`` are the
    pairs that turn ``beta_fast`` and ``beta_slow`` times over the
    original context (10 and 23 of A.X-K1's 32 pairs)."""
    yarn = dict(scaling)
    factor, original = yarn["factor"], \
        yarn["original_max_position_embeddings"]

    def pair(turns):
        return width * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(pair(yarn["beta_fast"])), 0)
    high = min(math.ceil(pair(yarn["beta_slow"])), width - 1)
    plain = theta ** -(np.arange(0, width, 2, dtype=np.float64) / width)
    ramp = np.clip((np.arange(width // 2) - low)
                   / ((high - low) or 0.001), 0.0, 1.0)
    return (plain / factor * ramp + plain * (1.0 - ramp)).astype(np.float32)


def latent_scale(cfg: "HybridConfig") -> float:
    """A latent layer's score scale: ``(nope + rope)^-1/2``, times YaRN's
    ``m^2`` with ``m = 0.1 mscale_all_dim ln(factor) + 1`` where the
    context is stretched (A.X-K1: ``192^-1/2 x 1.34657^2 = 0.130861``)."""
    scale = cfg.head_dim ** -0.5
    yarn = dict(cfg.rope_scaling or ())
    if yarn.get("mscale_all_dim") and yarn["factor"] > 1:
        scale *= (0.1 * yarn["mscale_all_dim"] * math.log(yarn["factor"])
                  + 1.0) ** 2
    return scale


def _rotate(x: jax.Array, positions: jax.Array, *, width: int,
            theta: float, scaling=None) -> jax.Array:
    """Rotary positions on the first ``width`` channels of every head of
    ``x`` [B, T, H, D], pairs ``(i, i + width / 2)`` as ``apply_rope``
    pairs them; the other channels as they are.  ``scaling``: YaRN's
    frequencies (its factor on cos and sin, ``mscale`` over
    ``mscale_all_dim``'s, is 1 where the two agree: A.X-K1's)."""
    freqs = jnp.asarray(yarn_frequencies(width, theta, scaling)) \
        if scaling else None
    if width == x.shape[-1]:
        return apply_rope(x, positions, theta, freqs)
    return jnp.concatenate([apply_rope(x[..., :width], positions, theta,
                                       freqs), x[..., width:]], axis=-1)


class GroupedAttention(nn.Module):
    """Query head ``h`` reads key-value head ``h // group``;
    ``softmax(attention_multiplier * q k^T + causal mask) v``, with
    ``attn_gate`` times ``sigmoid(W_gate x)`` a channel before ``wo``.
    Heads are ``cfg.head_dim`` wide, whatever ``d_model`` is, their
    values ``cfg.value_dim``; no position term unless
    ``attn_rotary_dim``.  ``windowed`` is the ``"window"`` kind: the
    last ``cfg.window`` keys, and the window's own key-value heads,
    rotary base and sink.  ``loop``: the pass of the stack this call
    belongs to, whose cache it reads and writes."""
    cfg: HybridConfig
    windowed: bool = False

    @nn.compact
    def __call__(self, x: jax.Array, lengths=None, loop: int = 0
                 ) -> jax.Array:
        cfg = self.cfg
        dense = partial(nn.DenseGeneral, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype)
        d, wide = cfg.head_dim, cfg.value_dim
        kv, theta, has_sink, window = cfg.num_kv_heads, cfg.rope_theta, \
            cfg.attn_sink, 0
        if self.windowed:
            kv, theta, has_sink, window = (
                cfg.window_kv_heads or kv, cfg.window_rope_theta or theta,
                cfg.window_sink, cfg.window)
        q = dense(features=(cfg.num_heads, d), name="wq")(x)
        k = dense(features=(kv, d), name="wk")(x)
        v = dense(features=(kv, wide), name="wv")(x)
        sink = self.param("sink", nn.initializers.normal(1.0),
                          (cfg.num_heads,), jnp.float32) if has_sink else None
        rotate = partial(_rotate, width=cfg.attn_rotary_dim, theta=theta) \
            if cfg.attn_rotary_dim else None
        # What the softmax is fed (before positions), for a caller that
        # replays a stream and checks what came out (nothing is kept
        # else).
        for name, fed in (("q", q), ("k", k), ("v", v)):
            self.sow("attention", name, fed)
        if cfg.decode and not self.is_initializing():
            out = cached_attention(
                self, q, k, v, max_seq_len=cfg.max_seq_len,
                dtype=cfg.dtype, scale=cfg.attention_multiplier,
                rotate=rotate, window=window, sink=sink, lengths=lengths,
                loop=loop)
        else:
            positions = jnp.arange(x.shape[1])[None, :]
            if rotate is not None:
                q, k = rotate(q, positions), rotate(k, positions)
            out = attend(q, k, v, positions, cfg.attention_multiplier, sink,
                         window)
        if cfg.attn_value_scale != 1.0:    # on the sum: the same function
            out = out * cfg.attn_value_scale
        self.sow("attention", "out", out)
        if cfg.attn_gate:
            gate = dense(features=(cfg.num_heads, d), name="wg")(x)
            out = out.astype(jnp.float32) \
                * jax.nn.sigmoid(gate.astype(jnp.float32))
        out = out.astype(cfg.dtype)
        return dense(features=cfg.d_model, axis=(-2, -1), name="wo")(out)


class LatentAttention(nn.Module):
    """Multi-head latent attention (``"latent"``; DeepSeek-V3's, ISSUE 40),
    ``x`` the normalised residual stream, h over ``num_heads``:

    - ``c_q = RMSNorm(W_qa x)`` (``q_lora_rank``), ``[q_nope_h |
      q_pe_h] = (W_qb c_q)_h`` (``qk_nope_head_dim``, ``qk_rope_head_dim``);
    - ``[c_kv | k_pe] = W_kva x``, ``c_kv <- RMSNorm(c_kv)``
      (``kv_lora_rank``): ``k_pe`` is one rotary key that every head
      shares;
    - ``[k_nope_h | v_h] = (W_kvb c_kv)_h`` (values ``attn_value_dim``);
    - ``s_hj = (q_nope_h . k_nope_hj + rope(q_pe_h) . rope(k_pe_j)) tau``,
      ``tau = latent_scale(cfg)``; ``o = W_o concat_h(sum_j p_hj v_hj)``.

    Rotary positions with YaRN's frequencies where ``rope_scaling`` says
    so, pairs ``(i, i + R / 2)`` (a checkpoint that interleaves its
    pairs is a fixed permutation of ``W_qb``'s and ``W_kva``'s rotary
    columns).  Without a cache, the expanded form; through it,
    ``models/kvcache.py:latent_attention`` (the absorbed form at a
    decode step, ``ops/mla.py``)."""
    cfg: HybridConfig

    @nn.compact
    def __call__(self, x: jax.Array, lengths=None) -> jax.Array:
        cfg = self.cfg
        dense = partial(nn.DenseGeneral, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype)
        norm = partial(RMSNorm, cfg.dtype, cfg.param_dtype, cfg.rms_norm_eps)
        h, nope, rope = cfg.num_heads, cfg.qk_nope_head_dim, \
            cfg.qk_rope_head_dim
        rank, wide = cfg.kv_lora_rank, cfg.value_dim
        q = dense(features=(h, nope + rope), name="wq_b")(
            norm(name="q_norm")(dense(features=cfg.q_lora_rank,
                                      name="wq_a")(x)))
        kv = dense(features=rank + rope, name="wkv_a")(x)
        q_nope, q_pe, c_kv, k_pe = q[..., :nope], q[..., nope:], \
            kv[..., :rank], kv[..., rank:]
        # What the softmax is fed (before the latent's norm and before
        # positions), for a caller that replays a stream and checks what
        # came out (nothing is kept else).
        for name, fed in (("q_nope", q_nope), ("q_pe", q_pe),
                          ("c_kv", c_kv), ("k_pe", k_pe)):
            self.sow("attention", name, fed)
        c_kv = norm(name="kv_norm")(c_kv)
        w_kvb = self.param("wkv_b", nn.initializers.lecun_normal(
            in_axis=0, out_axis=(1, 2)), (rank, h, nope + wide),
            cfg.param_dtype)
        w_uk, w_uv = w_kvb[..., :nope], w_kvb[..., nope:]
        rotate = partial(_rotate, width=rope, theta=cfg.rope_theta,
                         scaling=cfg.rope_scaling)
        scale = latent_scale(cfg)
        if cfg.decode and not self.is_initializing():
            out = latent_attention(
                self, q_nope, q_pe, c_kv, k_pe, w_uk, w_uv,
                max_seq_len=cfg.max_seq_len, dtype=cfg.dtype, scale=scale,
                rotate=rotate)
        else:
            positions = jnp.arange(x.shape[1])[None, :]
            out = attend(*mla.expand(
                q_nope, rotate(q_pe, positions), c_kv,
                rotate(k_pe[:, :, None, :], positions)[:, :, 0], w_uk,
                w_uv), positions, scale)
        self.sow("attention", "out", out)
        return dense(features=cfg.d_model, axis=(-2, -1), name="wo")(
            out.astype(cfg.dtype))


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
            state = self.variable("cache", "ssm_state", jnp.zeros,
                                  ssm.state_shape(b, h, p, n), jnp.float32)
        xbc = _windowed_conv(self, cfg, xbc, conv_w, conv_b, lengths,
                             cached=cached, stepping=stepping,
                             scope="hvd.ssm_conv")
        xs, bs, cs = jnp.split(xbc, [inner, inner + n], axis=-1)
        xs = xs.reshape(b, t, h, p)

        if stepping:
            if t != 1:
                raise ValueError("a decode step takes one token a slot")
            y, state.value = ssm.ssm_update(
                state.value, xs[:, 0], dt[:, 0], a, bs[:, 0], cs[:, 0],
                skip, interpret=cfg.interpret)
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


def _windowed_conv(module, cfg, x, kernel, bias, lengths, *, cached: bool,
                   stepping: bool, scope: str) -> jax.Array:
    """The causal depthwise convolution of a recurrent mixer with SiLU,
    ``x`` [B, T, channels], and the update of ``module``'s window,
    ``conv_state`` [B, width - 1, channels], where the call runs through
    the cache: a decode step (``stepping``) reads the window before it."""
    b, t, channels = x.shape
    width = kernel.shape[0]
    if cached:
        window = module.variable("cache", "conv_state", jnp.zeros,
                                 (b, width - 1, channels), cfg.dtype)
    with jax.named_scope(scope):
        before = window.value if stepping \
            else jnp.zeros((b, width - 1, channels), x.dtype)
        padded = jnp.concatenate([before, x], axis=1)
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
                   * kernel[i].astype(jnp.float32)
                   for i in range(width))
        if bias is not None:
            conv = conv + bias.astype(jnp.float32)
        return nn.silu(conv).astype(cfg.dtype)


class KDAMixer(nn.Module):
    """The delta-rule mixer (Kimi Delta Attention).  ``in_proj`` is the
    maps of ``x`` side by side: ``q``, ``k``, ``v`` (``heads x head_dim``
    each), the low-rank halves of the decay and of the output gate
    (``kda_rank`` each) and the write strength (a head)."""
    cfg: HybridConfig

    @nn.compact
    def __call__(self, x: jax.Array, lengths=None) -> jax.Array:
        cfg = self.cfg
        b, t, _ = x.shape
        h, d, inner, rank = cfg.kda_heads, cfg.kda_head_dim, \
            cfg.kda_inner, cfg.kda_rank
        dense = partial(nn.Dense, use_bias=False, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype)
        conv_w = self.param("conv_kernel", nn.initializers.lecun_normal(),
                            (cfg.kda_conv, 3 * inner), cfg.param_dtype)
        a_log = self.param("A_log", _a_log_init, (h,), jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (inner,),
                             jnp.float32)

        proj = dense(3 * inner + 2 * rank + h, name="in_proj")(x)
        qkv, decay, gate, beta = jnp.split(
            proj, [3 * inner, 3 * inner + rank, 3 * inner + 2 * rank],
            axis=-1)
        cached = cfg.decode and not self.is_initializing()
        stepping = cached and self.has_variable("cache", "kda_state")
        if cached:
            state = self.variable("cache", "kda_state", jnp.zeros,
                                  (b, h, d, d), jnp.float32)
        qkv = _windowed_conv(self, cfg, qkv, conv_w, None, lengths,
                             cached=cached, stepping=stepping,
                             scope="hvd.kda_conv")
        q, k, v = (each.reshape(b, t, h, d).astype(jnp.float32)
                   for each in jnp.split(qkv, 3, axis=-1))
        q, k = (each * jax.lax.rsqrt(
            jnp.sum(each * each, axis=-1, keepdims=True) + _L2_EPS)
            for each in (q, k))
        q = q * d ** -0.5
        # The log decay a key channel, at most 0, and the write strength
        # a head, 0 to 2 (``kda_allow_neg_eigval``), both float32.
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
            dense(inner, name="decay_up")(decay).astype(jnp.float32)
            + dt_bias).reshape(b, t, h, d)
        beta = _WRITE_SCALE * jax.nn.sigmoid(beta.astype(jnp.float32))
        # What the recurrence is fed, for a caller that replays a stream
        # and checks the state it reached (nothing is kept else).
        for name, fed in (("k", k), ("v", v), ("g", g), ("beta", beta)):
            self.sow("recurrence", name, fed)

        if stepping:
            if t != 1:
                raise ValueError("a decode step takes one token a slot")
            o, state.value = kda.kda_update(
                state.value, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                interpret=cfg.interpret)
            o = o[:, None]
        else:
            o, final = kda.kda_scan(q, k, v, g, beta, chunk=cfg.kda_chunk,
                                    lengths=lengths)
            if cached:
                state.value = final
        o = RMSNorm(cfg.dtype, cfg.param_dtype, cfg.rms_norm_eps,
                    name="norm")(o)                           # a head
        gate = dense(inner, name="gate_up")(gate).reshape(b, t, h, d)
        o = o.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))
        return dense(cfg.d_model, name="out_proj")(
            o.reshape(b, t, inner).astype(cfg.dtype))


_L2_EPS = 1e-6      # under the root of a head's norm, as the kernels of
                    # the published implementation have it
_WRITE_SCALE = 2.0  # kda_allow_neg_eigval: b on 0 to 2, so that
                    # I - b k k^T has eigenvalues down to -1


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
    dense: bool = False     # the MLP of d_ff, whatever num_experts says

    @nn.compact
    def __call__(self, x: jax.Array, lengths=None, loop: int = 0
                 ) -> jax.Array:
        cfg = self.cfg
        norm = partial(RMSNorm, cfg.dtype, cfg.param_dtype,
                       cfg.rms_norm_eps)
        mixed = norm(name="mixer_norm")(x)
        if self.kind in ("attention", "window"):
            mixed = GroupedAttention(cfg, self.kind == "window",
                                     name="attn")(mixed, lengths, loop)
        elif self.kind == "latent":
            mixed = LatentAttention(cfg, name="attn")(mixed)
        else:
            mixer = Mamba2Mixer if self.kind == "mamba" else KDAMixer
            mixed = mixer(cfg, name=self.kind)(mixed, lengths)
        if cfg.sandwich_norm:
            mixed = norm(name="mixer_post_norm")(mixed)
        x = x + cfg.residual_multiplier * mixed
        ffn = MLP(cfg, name="mlp") if self.dense or not cfg.num_experts \
            else moe.RoutedExperts(
                cfg.num_experts, cfg.experts_per_token, cfg.expert_ff,
                tuple(cfg.experts_held), cfg.shared_experts, cfg.norm_topk,
                cfg.routed_scaling, cfg.dtype, cfg.param_dtype,
                cfg.interpret, bias=cfg.router_bias,
                groups=(cfg.n_group, cfg.topk_group), name="moe")
        out = ffn(norm(name="mlp_norm")(x))
        if cfg.sandwich_norm:
            out = norm(name="mlp_post_norm")(out)
        return x + cfg.residual_multiplier * out


class HybridLM(nn.Module):
    """``apply(variables, tokens [B, T]) -> logits [B, T, vocab]`` in
    ``cfg.dtype``; ``lengths`` gives the true lengths of right-padded
    rows to a prefill through the cache.  The layers run ``cfg.loops``
    times over, the same modules each pass, ``final_norm`` after each."""
    cfg: HybridConfig

    @nn.compact
    def __call__(self, tokens: jax.Array, train: bool = False,
                 lengths=None) -> jax.Array:
        cfg = self.cfg
        embed = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="embed")
        x = embed(tokens) * cfg.embedding_multiplier
        blocks = [HybridBlock(cfg, kind, i in cfg.dense_layers,
                              name=f"layer_{i}")
                  for i, kind in enumerate(cfg.layer_types)]
        final_norm = RMSNorm(cfg.dtype, cfg.param_dtype, cfg.rms_norm_eps,
                             name="final_norm")
        for loop in range(cfg.loops):
            for block in blocks:
                x = block(x, lengths, loop)
            x = final_norm(x)
        logits = embed.attend(x) if cfg.tie_embeddings \
            else nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                          param_dtype=cfg.param_dtype, name="lm_head")(x)
        return logits / cfg.logits_scaling


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
ROUTED_FAMILY = dataclasses.replace(FAMILY, decode_counters=moe.COUNTERS)
