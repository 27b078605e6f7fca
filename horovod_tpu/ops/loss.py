"""Streaming (vocab-chunked) softmax cross entropy.

Large-vocab LM heads pay more for the loss than for the matmul that
produced the logits: the naive path materializes a second fp32
``[tokens, vocab]`` tensor for ``log_softmax`` (6.6 GB at
batch 16 x seq 2048 x vocab 50304) and its fp32 gradient — all pure HBM
traffic.

This op computes the same mean cross entropy (with optional label
smoothing) without ever materializing an fp32 logits-sized tensor:

- forward: one streamed pass over vocab chunks with an online
  max/sum-exp (the flash-attention trick applied to the vocab axis),
  carrying three ``[tokens]`` fp32 vectors; the label logit comes from
  one gather.
- backward: ``d_logits = (softmax * target_mass - target) * g / tokens``
  is emitted chunk-by-chunk straight into the logits' own (usually
  bf16) dtype — one read of the logits, one write of the gradient,
  nothing fp32 of logits size.

Out-of-range labels (e.g. -1 as an ignore/padding index) follow the
dense ``jax.nn.one_hot`` semantics exactly: the one-hot target mass for
such rows is zero, so without smoothing they contribute nothing to loss
or gradient; with smoothing they still receive the uniform eps/V target
component (that is what the dense path computes).

Reference analogue: none — the reference's benchmarks stop at the
framework boundary (tf_cnn_benchmarks / synthetic torch models,
reference: docs/benchmarks.rst:20-43); this exists because on TPU the
loss epilogue is a first-class HBM-bandwidth consumer.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

def _pick_chunk(vocab: int, target: int) -> int:
    """Largest divisor of ``vocab`` <= target; ``vocab`` itself when the
    only such divisors are degenerately small (< target/8 — a prime
    vocab would otherwise degenerate to chunk=1: ~50k sequential
    one-column scan slices, in an op built to be fast)."""
    if vocab <= target:
        return vocab
    floor = max(1, target // 8)
    for n_chunks in range(2, vocab // floor + 1):
        if vocab % n_chunks == 0 and vocab // n_chunks <= target:
            return vocab // n_chunks
    return vocab


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _streaming_ce(logits2d: jax.Array, labels1d: jax.Array,
                  label_smoothing: float, chunk: int) -> jax.Array:
    loss, _ = _streaming_ce_fwd(logits2d, labels1d, label_smoothing, chunk)
    return loss


def _lse_scan(logits2d: jax.Array, chunk: int, need_total: bool):
    """One streamed pass: per-row logsumexp (and, for label smoothing,
    the per-row sum of logits)."""
    tokens, vocab = logits2d.shape
    n_chunks = vocab // chunk

    def body(carry, i):
        m, s, tot = carry
        xc = lax.dynamic_slice_in_dim(
            logits2d, i * chunk, chunk, axis=1).astype(jnp.float32)
        mc = jnp.max(xc, axis=-1)
        m_new = jnp.maximum(m, mc)
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(xc - m_new[:, None]), axis=-1)
        if need_total:
            tot = tot + jnp.sum(xc, axis=-1)
        return (m_new, s, tot), None

    init = (jnp.full((tokens,), -jnp.inf, jnp.float32),
            jnp.zeros((tokens,), jnp.float32),
            jnp.zeros((tokens,), jnp.float32))
    (m, s, tot), _ = lax.scan(body, init, jnp.arange(n_chunks))
    return m + jnp.log(s), tot


def _streaming_ce_fwd(logits2d, labels1d, label_smoothing, chunk):
    tokens, vocab = logits2d.shape
    eps = label_smoothing
    lse, tot = _lse_scan(logits2d, chunk, need_total=bool(eps))
    valid = ((labels1d >= 0) & (labels1d < vocab))
    label_logit = jnp.take_along_axis(
        logits2d, jnp.clip(labels1d, 0, vocab - 1)[:, None],
        axis=1)[:, 0].astype(jnp.float32)
    # one_hot semantics: out-of-range labels carry zero one-hot mass.
    nll = jnp.where(valid, lse - label_logit, 0.0)
    if eps:
        nll = (1.0 - eps) * nll + eps * (lse - tot / vocab)
    return jnp.mean(nll), (logits2d, labels1d, lse)


def _streaming_ce_bwd(label_smoothing, chunk, res, g):
    logits2d, labels1d, lse = res
    tokens, vocab = logits2d.shape
    n_chunks = vocab // chunk
    eps = label_smoothing
    scale = (g / tokens).astype(jnp.float32)
    valid = ((labels1d >= 0) & (labels1d < vocab)).astype(jnp.float32)
    # d(-sum(target*logp))/dx = softmax * sum(target) - target.
    # sum(target) per row: (1-eps)*valid + eps  (eps/V rides every row).
    target_mass = (1.0 - eps) * valid + eps if eps else valid

    def body(dl, i):
        xc = lax.dynamic_slice_in_dim(
            logits2d, i * chunk, chunk, axis=1).astype(jnp.float32)
        p = jnp.exp(xc - lse[:, None])
        local = labels1d - i * chunk
        onehot = (local[:, None] == jnp.arange(chunk)[None, :]).astype(
            jnp.float32) * valid[:, None]
        target = (1.0 - eps) * onehot + eps / vocab if eps else onehot
        dchunk = ((p * target_mass[:, None] - target) * scale).astype(
            logits2d.dtype)
        return lax.dynamic_update_slice_in_dim(dl, dchunk, i * chunk,
                                               axis=1), None

    dlogits, _ = lax.scan(body, jnp.zeros_like(logits2d),
                          jnp.arange(n_chunks))
    return dlogits, None


_streaming_ce.defvjp(_streaming_ce_fwd, _streaming_ce_bwd)


def streaming_softmax_cross_entropy(logits: jax.Array, labels: jax.Array,
                                    label_smoothing: float = 0.0,
                                    chunk_target: int = 8192) -> jax.Array:
    """Mean softmax cross entropy over integer labels, streamed over the
    vocab axis so no fp32 logits-sized tensor is ever materialized.

    Numerically identical to the dense
    ``-mean(sum(one_hot(labels) * log_softmax(logits)))`` with fp32
    accumulation (same math, chunked), including one_hot's zero-mass
    treatment of out-of-range labels; gradients flow to ``logits`` in
    the logits' own dtype. ``chunk_target`` bounds the fp32 working
    chunk to ``[tokens, <=chunk_target]``.
    """
    vocab = logits.shape[-1]
    logits2d = logits.reshape(-1, vocab)
    labels1d = labels.reshape(-1).astype(jnp.int32)
    chunk = _pick_chunk(vocab, chunk_target)
    return _streaming_ce(logits2d, labels1d, float(label_smoothing), chunk)
