"""Fused flash attention for TPU (Pallas).

The reference framework has no attention code at all (SURVEY §5.7) — long
context on TPU is a first-class goal of this rebuild, so the hot op is a
native MXU kernel: blockwise attention with online softmax, FlashAttention-2
style forward and backward, streaming KV blocks through VMEM so memory is
O(block) instead of O(seq²).

Layout: [batch*heads, seq, head_dim] inside the kernels; the public API
takes [batch, seq, heads, head_dim] (BTHD, the framework-wide convention).

On non-TPU backends a numerically identical pure-JAX blockwise path runs
instead (same online-softmax math, differentiable); the Pallas kernels can
also be exercised anywhere via ``interpret=True`` (used by the unit tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG_INF = -1e30
_LANE = 128   # TPU lane width: last-dim tile alignment


def _cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def _out_struct(shape, dtype, *like):
    """ShapeDtypeStruct carrying the union of the inputs' varying mesh axes
    (vma) — required for pallas_call inside shard_map regions with
    check_vma=True."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in like))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


# ===========================================================================
# Pure-JAX reference (also the CPU fallback and the autodiff oracle)
# ===========================================================================
def mha_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                  causal: bool = False,
                  sm_scale: float | None = None) -> jax.Array:
    """Dense softmax attention. q,k,v: [B, T, H, D] (BTHD)."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


# ===========================================================================
# Pallas forward kernel
# ===========================================================================
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *,
                sm_scale: float, causal: bool, causal_offset: int,
                block_q: int, block_k: int, n_kv: int):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Causal: blocks strictly above the diagonal contribute nothing.
    run = True
    if causal:
        run = kj * block_k <= qi * block_q + (block_q - 1) + causal_offset

    @pl.when(run)
    def _compute():
        # MXU dots take the inputs in their own (bf16) dtype with fp32
        # accumulation: casting inputs to fp32 first would force fp32
        # multiply passes at a fraction of the bf16 MXU rate. Softmax
        # statistics stay fp32 (standard flash numerics).
        q = q_ref[0]                                  # [bq, d]
        k = k_ref[0]                                  # [bk, d]
        v = v_ref[0]                                  # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # [bq, bk]
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows + causal_offset >= cols, s, NEG_INF)

        m_prev = m_ref[:, 0]                          # [bq]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)               # [bq]
        p = jnp.exp(s - m_cur[:, None])               # [bq, bk]
        l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
        m_ref[:, 0] = m_cur
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(kj == n_kv - 1)
    def _finalize():
        l = l_ref[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)
        # lse is per-row but stored lane-broadcast at [bq, _LANE]: TPU
        # blocks need their last two dims (8, 128)-tileable, so a bare
        # [1, bq] output is unmappable (same layout as the upstream jax
        # flash kernel's l/m outputs).
        lse = jnp.where(l == 0.0, NEG_INF, m_ref[:, 0] + jnp.log(l_safe))
        lse_ref[0] = jax.lax.broadcast_in_dim(lse, (block_q, _LANE), (0,))


def _flash_fwd_pallas(q, k, v, *, sm_scale, causal, block_q, block_k,
                      interpret):
    """q,k,v: [BH, T, D] → (o [BH, T, D], lse [BH, T, _LANE] lane-bcast)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, tq, d = q.shape
    tk = k.shape[1]
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    assert tq % block_q == 0 and tk % block_k == 0, \
        f"seq lengths ({tq},{tk}) must divide blocks ({block_q},{block_k})"
    n_q, n_kv = tq // block_q, tk // block_k

    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal,
        causal_offset=tk - tq, block_q=block_q, block_k=block_k, n_kv=n_kv)
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANE), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            _out_struct((bh, tq, d), q.dtype, q, k, v),
            _out_struct((bh, tq, _LANE), jnp.float32, q, k, v),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANE), jnp.float32),
            pltpu.VMEM((block_q, _LANE), jnp.float32),
        ],
        interpret=interpret,
        name="hvd.flash_fwd",
    )(q, k, v)
    return o, lse


# ===========================================================================
# Pallas backward kernels (FlashAttention-2 split: dq, then dk/dv)
# ===========================================================================
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc, *,
                   sm_scale: float, causal: bool, causal_offset: int,
                   block_q: int, block_k: int, n_kv: int):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    run = True
    if causal:
        run = kj * block_k <= qi * block_q + (block_q - 1) + causal_offset

    @pl.when(run)
    def _compute():
        # bf16 MXU inputs + fp32 accumulation (see _fwd_kernel note).
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, :, 0]                        # lane-bcast → [bq]
        delta = delta_ref[0, :, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows + causal_offset >= cols, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])                 # [bq, bk]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bq, bk]
        ds = p * (dp - delta[:, None]) * sm_scale
        dq_acc[...] += jax.lax.dot(ds.astype(k.dtype), k,
                                   preferred_element_type=jnp.float32)

    @pl.when(kj == n_kv - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *,
                    sm_scale: float, causal: bool, causal_offset: int,
                    block_q: int, block_k: int, n_q: int):
    from jax.experimental import pallas as pl

    kj = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    run = True
    if causal:
        run = kj * block_k <= qi * block_q + (block_q - 1) + causal_offset

    @pl.when(run)
    def _compute():
        # bf16 MXU inputs + fp32 accumulation (see _fwd_kernel note).
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, :, 0]                        # lane-bcast → [bq]
        delta = delta_ref[0, :, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # [bq, bk]
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows + causal_offset >= cols, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])                 # [bq, bk]
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bk, d]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bq, bk]
        ds = (p * (dp - delta[:, None]) * sm_scale).astype(q.dtype)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bk, d]

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_pallas(q, k, v, o, lse, do, *, sm_scale, causal,
                      block_q, block_k, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, tq, d = q.shape
    tk = k.shape[1]
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    n_q, n_kv = tq // block_q, tk // block_k

    # lse and delta ride lane-broadcast at [BH, T, _LANE] so their blocks
    # satisfy the (8, 128) tiling rule (materialized only for the span of
    # the two backward kernels).
    lse = jnp.broadcast_to(lse[:, :, None], (bh, tq, _LANE))
    delta = jnp.broadcast_to(
        jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                axis=-1)[:, :, None], (bh, tq, _LANE))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          causal_offset=tk - tq,
                          block_q=block_q, block_k=block_k, n_kv=n_kv),
        grid=(bh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANE), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANE), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=_out_struct((bh, tq, d), q.dtype, q, k, v, do),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="hvd.flash_bwd",
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          causal_offset=tk - tq,
                          block_q=block_q, block_k=block_k, n_q=n_q),
        grid=(bh, n_kv, n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANE), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANE), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            _out_struct((bh, tk, d), k.dtype, q, k, v, do),
            _out_struct((bh, tk, d), v.dtype, q, k, v, do),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name="hvd.flash_bwd",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ===========================================================================
# Blockwise pure-JAX path (CPU fallback; numerically matches the kernel)
# ===========================================================================
def _blockwise_jax(q, k, v, *, sm_scale, causal):
    """[BH, T, D] online-softmax attention with lse, differentiable."""
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        rows = jnp.arange(tq)[:, None]
        cols = jnp.arange(tk)[None, :]
        # Bottom-right alignment for tq != tk, matching mha_reference's
        # tril(k=tk-tq) (cross-attention / decode windows).
        s = jnp.where(rows + (tk - tq) >= cols, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)) / l[..., None]
    lse = m + jnp.log(l)
    return o.astype(q.dtype), lse


# ===========================================================================
# Public API with custom VJP
# ===========================================================================
def _merge_heads(x):
    """[B, T, H, D] → [B*H, T, D]."""
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _split_heads(x, b, h):
    bh, t, d = x.shape
    return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, sm_scale, causal, block_q, block_k,
           block_q_bwd, block_k_bwd, interpret):
    o, _res = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k,
                         interpret)
    return o


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    # The residual keeps lse at [BH, T]: holding the kernels' lane-
    # broadcast [BH, T, _LANE] layout across fwd→bwd would pin 128× the
    # HBM for the whole backward span; the backward re-broadcasts it.
    with jax.named_scope("hvd.flash_fwd"):
        if _on_tpu() or interpret:
            o, lse = _flash_fwd_pallas(q, k, v, sm_scale=sm_scale,
                                       causal=causal, block_q=block_q,
                                       block_k=block_k,
                                       interpret=interpret)
            lse = lse[:, :, 0]
        else:
            o, lse = _blockwise_jax(q, k, v, sm_scale=sm_scale,
                                    causal=causal)
    return o, (q, k, v, o, lse)


def _flash_fwd_rule(q, k, v, sm_scale, causal, block_q, block_k,
                    block_q_bwd, block_k_bwd, interpret):
    o, res = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k,
                        interpret)
    return o, res


def _flash_bwd_rule(sm_scale, causal, block_q, block_k,
                    block_q_bwd, block_k_bwd, interpret, res, g):
    # The backward kernel holds more live tiles than the forward (dq, dk,
    # dv accumulators + recomputed p), so its VMEM-optimal blocks are
    # usually SMALLER; they default to the forward's but are sweepable
    # independently (r3 found fwd 1024/1024 optimal while 1024/2048
    # exceeded the 16 MiB scoped-vmem limit).
    q, k, v, o, lse = res
    with jax.named_scope("hvd.flash_bwd"):
        if _on_tpu() or interpret:
            dq, dk, dv = _flash_bwd_pallas(
                q, k, v, o, lse, g, sm_scale=sm_scale, causal=causal,
                block_q=block_q_bwd or block_q,
                block_k=block_k_bwd or block_k, interpret=interpret)
        else:
            _, vjp = jax.vjp(
                lambda q_, k_, v_: _blockwise_jax(
                    q_, k_, v_, sm_scale=sm_scale, causal=causal)[0],
                q, k, v)
            dq, dk, dv = vjp(g)
    return dq, dk, dv


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _fit_block(t: int, block: int) -> int:
    """Largest block <= requested that tiles the sequence exactly AND that
    the Pallas TPU lowering accepts: a divisor of ``t`` that is a multiple
    of 8 (the sublane tile), or the whole sequence.  Raises on every
    backend, so a length the chip would refuse (T=2000 used to degrade to
    a 125-row block) fails on the CPU paths and in tests too."""
    if block >= t:
        return t
    for cand in range(block - block % 8, 0, -8):
        if t % cand == 0:
            return cand
    raise ValueError(
        f"flash attention cannot tile a sequence of {t} with blocks of at "
        f"most {block}: a block must divide the sequence length and be a "
        f"multiple of 8 (the TPU sublane tile), or span the whole "
        f"sequence; pad the sequence or pass a larger block")


def _check_dtypes(q: jax.Array, k: jax.Array, v: jax.Array) -> None:
    """The kernels feed q/k/v to the MXU dots in their RAW dtypes (fp32
    casts would forfeit the bf16 MXU rate), so mixed-dtype inputs either
    fail Mosaic lowering with an opaque error or silently change
    accumulation.  Make the contract explicit at the entry point."""
    if not (q.dtype == k.dtype == v.dtype):
        raise ValueError(
            f"flash attention requires q, k and v to share one dtype "
            f"(got q={q.dtype}, k={k.dtype}, v={v.dtype}); cast the "
            f"inputs to a common dtype first")


def _check_causal_shapes(causal: bool, tq: int, tk: int) -> None:
    """Bottom-right causal alignment leaves the first tq-tk query rows with
    zero valid keys when tq > tk — attention is undefined there (the dense
    reference degenerates to uniform weights over garbage). Reject loudly
    instead of silently diverging."""
    if causal and tq > tk:
        raise ValueError(
            f"causal attention requires tq <= tk (got tq={tq}, tk={tk}): "
            "with bottom-right alignment the leading query rows would "
            "attend to nothing")


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False, sm_scale: float | None = None,
                    block_q: int = 128, block_k: int = 128,
                    block_q_bwd: int | None = None,
                    block_k_bwd: int | None = None,
                    interpret: bool = False) -> jax.Array:
    """Fused multi-head attention. q,k,v: [B, T, H, D] (BTHD). Differentiable
    (custom VJP with Pallas backward kernels on TPU).  ``block_*_bwd``
    override the backward kernel's tiling (defaults: same as forward)."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    _check_dtypes(q, k, v)
    _check_causal_shapes(causal, q.shape[1], k.shape[1])
    b, _, h, _ = q.shape
    block_q = _fit_block(q.shape[1], block_q)
    block_k = _fit_block(k.shape[1], block_k)
    bq_bwd = _fit_block(q.shape[1], block_q_bwd) if block_q_bwd else 0
    bk_bwd = _fit_block(k.shape[1], block_k_bwd) if block_k_bwd else 0
    out = _flash(_merge_heads(q), _merge_heads(k), _merge_heads(v),
                 float(sm_scale), bool(causal), int(block_q), int(block_k),
                 int(bq_bwd), int(bk_bwd), bool(interpret))
    return _split_heads(out, b, h)


def flash_attention_with_lse(q: jax.Array, k: jax.Array, v: jax.Array,
                             causal: bool = False,
                             sm_scale: float | None = None,
                             block_q: int = 128, block_k: int = 128,
                             interpret: bool = False
                             ) -> tuple[jax.Array, jax.Array]:
    """Like :func:`flash_attention` but also returns the log-sum-exp
    [B, H, T] — the merge statistic ring attention needs. Differentiation
    flows through the non-lse output only."""
    b, _, h, _ = q.shape
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    _check_dtypes(q, k, v)
    _check_causal_shapes(causal, q.shape[1], k.shape[1])
    block_q = _fit_block(q.shape[1], block_q)
    block_k = _fit_block(k.shape[1], block_k)
    qm, km, vm = _merge_heads(q), _merge_heads(k), _merge_heads(v)
    if _on_tpu() or interpret:
        o, lse = _flash_fwd_pallas(qm, km, vm, sm_scale=float(sm_scale),
                                   causal=causal, block_q=block_q,
                                   block_k=block_k, interpret=interpret)
        lse = lse[:, :, 0]   # un-broadcast the lane dim
    else:
        o, lse = _blockwise_jax(qm, km, vm, sm_scale=float(sm_scale),
                                causal=causal)
    t = q.shape[1]
    return _split_heads(o, b, h), lse.reshape(b, h, t)
