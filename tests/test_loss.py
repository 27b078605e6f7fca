"""Streaming (vocab-chunked) cross entropy == dense log_softmax CE.

The streaming op only engages above the training.ce_streaming_threshold() size in the
trainer path; these tests call it directly on small shapes so the
chunked math (online logsumexp, chunked backward, label smoothing) is
pinned against the dense reference at test scale.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.loss import (_pick_chunk,
                                  streaming_softmax_cross_entropy)
from horovod_tpu.training import cross_entropy_loss


def _dense_ce(logits, labels, smoothing=0.0):
    num_classes = logits.shape[-1]
    onehot = jax.nn.one_hot(labels, num_classes, dtype=jnp.float32)
    if smoothing:
        onehot = (1.0 - smoothing) * onehot + smoothing / num_classes
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.mean(jnp.sum(onehot * logp, axis=-1))


def test_pick_chunk():
    assert _pick_chunk(50304, 8192) == 6288      # 8 chunks
    assert _pick_chunk(4096, 8192) == 4096       # fits whole
    # no useful divisor (prime / only tiny divisors): one vocab-wide
    # chunk, never a degenerate chunk=1 scan
    assert _pick_chunk(50023, 8192) == 50023     # prime
    assert _pick_chunk(2 * 25013, 8192) == 50026  # 2 x prime
    assert _pick_chunk(100, 30) == 25


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("smoothing", [0.0, 0.1])
def test_streaming_matches_dense(dtype, smoothing):
    key = jax.random.key(0)
    T, V = 48, 96   # chunk_target=32 -> 3 chunks of 32
    logits = (jax.random.normal(key, (T, V), jnp.float32) * 4).astype(dtype)
    labels = jax.random.randint(jax.random.key(1), (T,), 0, V)

    got = streaming_softmax_cross_entropy(logits, labels, smoothing,
                                          chunk_target=32)
    want = _dense_ce(logits, labels, smoothing)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-6, atol=2e-6)

    # gradients: same fp32 math, emitted in the logits dtype
    g_got = jax.grad(lambda l: streaming_softmax_cross_entropy(
        l, labels, smoothing, chunk_target=32))(logits)
    g_want = jax.grad(lambda l: _dense_ce(l, labels, smoothing))(logits)
    assert g_got.dtype == dtype
    # bf16 grads are independently-rounded results of different fp32
    # reduction orders: compare at the dtype's own precision.
    tol = 2e-6 if dtype == jnp.float32 else 8e-3
    np.testing.assert_allclose(np.asarray(g_got, np.float32),
                               np.asarray(g_want.astype(dtype), np.float32),
                               rtol=tol, atol=tol)


def test_streaming_handles_batch_dims():
    logits = jax.random.normal(jax.random.key(2), (4, 6, 64), jnp.float32)
    labels = jax.random.randint(jax.random.key(3), (4, 6), 0, 64)
    got = streaming_softmax_cross_entropy(logits, labels, chunk_target=16)
    want = _dense_ce(logits, labels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_trainer_loss_dispatches_below_threshold():
    # Small logits keep the dense path (no scan in the jaxpr).
    logits = jnp.ones((8, 32), jnp.float32)
    labels = jnp.zeros((8,), jnp.int32)
    jaxpr = jax.make_jaxpr(cross_entropy_loss)(logits, labels)
    assert "scan" not in str(jaxpr)


@pytest.mark.parametrize("smoothing", [0.0, 0.1])
def test_ignore_index_matches_dense(smoothing):
    # Out-of-range labels (-1 padding) must follow one_hot semantics in
    # BOTH branches: zero one-hot mass, uniform eps/V target only.
    T, V = 24, 64
    logits = jax.random.normal(jax.random.key(5), (T, V), jnp.float32) * 3
    labels = jax.random.randint(jax.random.key(6), (T,), 0, V)
    labels = labels.at[::3].set(-1)
    got = streaming_softmax_cross_entropy(logits, labels, smoothing,
                                          chunk_target=16)
    want = _dense_ce(logits, labels, smoothing)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    g_got = jax.grad(lambda l: streaming_softmax_cross_entropy(
        l, labels, smoothing, chunk_target=16))(logits)
    g_want = jax.grad(lambda l: _dense_ce(l, labels, smoothing))(logits)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_want),
                               rtol=1e-5, atol=1e-6)
    if not smoothing:
        # ignored rows get exactly zero gradient
        assert np.all(np.asarray(g_got)[::3] == 0.0)


def test_extreme_logits_stable():
    # Online logsumexp must not overflow where naive exp would.
    logits = jnp.array([[1e4, -1e4, 0.0, 5e3]] * 2, jnp.float32)
    labels = jnp.array([0, 3])
    got = streaming_softmax_cross_entropy(logits, labels, chunk_target=2)
    want = _dense_ce(logits, labels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    assert np.isfinite(float(got))
