"""Attention kernels and sequence parallelism: numerical equivalence of
flash (Pallas, interpreted), ring (ppermute over "sp"), and Ulysses
(all_to_all over "sp") against dense softmax attention — forward AND
gradients (SURVEY §5.7: long-context support is TPU-native, not ported).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map

from horovod_tpu.ops.flash_attention import (flash_attention,
                                             flash_attention_with_lse,
                                             mha_reference)
from horovod_tpu.parallel import MeshSpec, build_mesh
from horovod_tpu.parallel.ring_attention import ring_attention
from horovod_tpu.parallel.ulysses import ulysses_attention

B, T, H, D = 2, 64, 4, 32


@pytest.fixture(scope="module")
def qkv():
    key = jax.random.key(0)
    return tuple(
        jax.random.normal(jax.random.fold_in(key, i), (B, T, H, D),
                          jnp.float32) for i in range(3))


@pytest.mark.parametrize("causal", [False, True])
class TestFlashAttention:
    def test_forward_matches_reference(self, qkv, causal):
        q, k, v = qkv
        ref = mha_reference(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, block_q=16,
                              block_k=16, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_gradients_match_reference(self, qkv, causal):
        q, k, v = qkv

        def loss(fn):
            return lambda q, k, v: (fn(q, k, v) ** 2).sum()

        ref_fn = loss(partial(mha_reference, causal=causal))
        fl_fn = loss(partial(flash_attention, causal=causal, block_q=16,
                             block_k=16, interpret=True))
        g_ref = jax.grad(ref_fn, argnums=(0, 1, 2))(q, k, v)
        g_fl = jax.grad(fl_fn, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ref, g_fl):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       atol=5e-5)

    def test_bf16_forward_and_grads_match_dense(self, qkv, causal):
        # Pins the bf16 MXU-input path: on TPU the kernels feed the dots
        # bf16 operands with fp32 accumulation and downcast p/ds between
        # the two matmuls (p.astype(v.dtype), ds.astype(k.dtype)). The
        # fp32 tests above make every one of those casts a no-op; this
        # runs the identical kernel code on bf16 inputs (interpret mode)
        # so a misplaced cast — e.g. exp() in bf16, or accumulation
        # without preferred_element_type — shows up here, not as silent
        # loss degradation on hardware.
        q, k, v = (x.astype(jnp.bfloat16) for x in qkv)
        ref = mha_reference(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, block_q=16,
                              block_k=16, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=0.05)   # bf16 has ~3 decimal digits

        def loss(fn):
            return lambda q, k, v: (fn(q, k, v).astype(jnp.float32)
                                    ** 2).sum()

        g_ref = jax.grad(loss(partial(mha_reference, causal=causal)),
                         argnums=(0, 1, 2))(q, k, v)
        g_fl = jax.grad(loss(partial(flash_attention, causal=causal,
                                     block_q=16, block_k=16,
                                     interpret=True)),
                        argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ref, g_fl):
            a = np.asarray(a, np.float32)
            b = np.asarray(b, np.float32)
            rel = np.linalg.norm(b - a) / (1e-6 + np.linalg.norm(a))
            assert rel < 0.03, rel

    def test_lse_consistent(self, qkv, causal):
        q, k, v = qkv
        o, lse = flash_attention_with_lse(q, k, v, causal=causal,
                                          block_q=16, block_k=16,
                                          interpret=True)
        assert lse.shape == (B, H, T)
        # lse is the log-normalizer: exp(s - lse) sums to 1 per row.
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (D ** -0.5)
        if causal:
            mask = jnp.tril(jnp.ones((T, T), bool))
            s = jnp.where(mask[None, None], s, -1e30)
        sums = jnp.sum(jnp.exp(s - lse[..., None]), axis=-1)
        np.testing.assert_allclose(np.asarray(sums), 1.0, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
class TestRingAttention:
    def test_matches_dense(self, qkv, causal):
        q, k, v = qkv
        mesh = build_mesh(MeshSpec(dp=1, sp=8))
        ring = jax.jit(shard_map(
            partial(ring_attention, axis="sp", causal=causal, axis_size=8),
            mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp")))
        ref = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(ring(q, k, v)),
                                   np.asarray(ref), atol=2e-5)

    def test_gradients_match_dense(self, qkv, causal):
        q, k, v = qkv
        mesh = build_mesh(MeshSpec(dp=1, sp=8))
        ring = shard_map(
            partial(ring_attention, axis="sp", causal=causal, axis_size=8),
            mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"))
        g_ref = jax.grad(
            lambda q, k, v: (mha_reference(q, k, v, causal=causal)
                             ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
        g_ring = jax.grad(
            lambda q, k, v: (ring(q, k, v) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ref, g_ring):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       atol=5e-5)

    def test_dp_sp_composition(self, qkv, causal):
        """Ring over sp composes with a dp-sharded batch."""
        q, k, v = qkv
        mesh = build_mesh(MeshSpec(dp=2, sp=4))
        ring = jax.jit(shard_map(
            partial(ring_attention, axis="sp", causal=causal, axis_size=4),
            mesh=mesh, in_specs=(P("dp", "sp"),) * 3,
            out_specs=P("dp", "sp")))
        ref = mha_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(ring(q, k, v)),
                                   np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_dense(qkv, causal):
    q, k, v = qkv
    mesh = build_mesh(MeshSpec(dp=2, sp=4))
    uly = jax.jit(shard_map(
        partial(ulysses_attention, axis="sp", causal=causal, axis_size=4),
        mesh=mesh, in_specs=(P("dp", "sp"),) * 3,
        out_specs=P("dp", "sp")))
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(uly(q, k, v)), np.asarray(ref),
                               atol=2e-5)


def test_ulysses_rejects_indivisible_heads(qkv):
    q, k, v = qkv    # H=4 heads
    mesh = build_mesh(MeshSpec(dp=1, sp=8))
    uly = shard_map(
        partial(ulysses_attention, axis="sp", axis_size=8),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"))
    with pytest.raises(ValueError, match="heads not divisible"):
        jax.jit(uly)(q, k, v)
