"""SPMD data-plane tests on the 8-device virtual CPU mesh (SURVEY §4:
the JAX analogue of the reference's multi-process localhost testing)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map

from horovod_tpu.ops.adasum import adasum_reference
from horovod_tpu.parallel import (GradSyncConfig, MeshSpec, adasum_allreduce,
                                  build_grad_sync, build_mesh,
                                  device_collective, ShardingRules,
                                  shard_params, sync_gradients)
from horovod_tpu.parallel import collectives as coll


@pytest.fixture(scope="module")
def mesh8():
    return build_mesh(dp=8)


@pytest.fixture(scope="module")
def mesh_dp_tp():
    return build_mesh(dp=4, tp=2)


def stacked(n, shape, seed=0, dtype=np.float32):
    rng = np.random.RandomState(seed)
    return rng.randn(n, *shape).astype(dtype)


class TestMeshBuild:
    def test_resolve_infers_dp(self):
        assert MeshSpec(tp=2).resolve(8)["dp"] == 4

    def test_bad_divisibility(self):
        with pytest.raises(ValueError):
            MeshSpec(tp=3).resolve(8)

    def test_axis_names(self, mesh_dp_tp):
        assert mesh_dp_tp.shape["dp"] == 4
        assert mesh_dp_tp.shape["tp"] == 2
        assert mesh_dp_tp.shape["pp"] == 1


class TestCollectives:
    def test_psum(self, mesh8):
        x = stacked(8, (4, 3))
        fn = device_collective(lambda v: coll.allreduce(v, "dp", "sum"),
                               mesh8, "dp")
        out = np.asarray(fn(x))
        expect = x.sum(axis=0, keepdims=True).repeat(8, axis=0)
        np.testing.assert_allclose(out, expect, rtol=1e-5)

    def test_pmean(self, mesh8):
        x = stacked(8, (5,))
        fn = device_collective(lambda v: coll.allreduce(v, "dp", "average"),
                               mesh8, "dp")
        np.testing.assert_allclose(np.asarray(fn(x))[0], x.mean(0),
                                   rtol=1e-5)

    def test_broadcast(self, mesh8):
        x = stacked(8, (6,))
        fn = device_collective(lambda v: coll.broadcast(v, "dp", root=3),
                               mesh8, "dp")
        out = np.asarray(fn(x))
        for r in range(8):
            np.testing.assert_allclose(out[r], x[3], rtol=1e-6)

    def test_allgather_reduce_scatter_roundtrip(self, mesh8):
        x = stacked(8, (4,))
        fn = device_collective(
            lambda v: coll.reduce_scatter(coll.allgather(v, "dp"), "dp"),
            mesh8, "dp")
        out = np.asarray(fn(x))
        # allgather stacks all shards; reduce_scatter sums and re-shards:
        # each rank ends with 8 * its own shard
        np.testing.assert_allclose(out, 8 * x, rtol=1e-5)

    def test_alltoall(self, mesh8):
        x = stacked(8, (8, 2))
        # shard_map keeps the stacked leading dim (size 1 per rank), so the
        # exchange axis of the local block is axis 1.
        fn = device_collective(
            lambda v: coll.alltoall(v, "dp", split_axis=1, concat_axis=1),
            mesh8, "dp")
        out = np.asarray(fn(x))
        # row j of rank i's output == row i of rank j's input
        for i in range(8):
            for j in range(8):
                np.testing.assert_allclose(out[i, j], x[j, i], rtol=1e-6)


class TestAdasum:
    @pytest.mark.parametrize("n", [2, 4, 8])
    def test_matches_reference_tree(self, n):
        mesh = build_mesh(dp=n, devices=jax.devices()[:n])
        x = stacked(n, (33,), seed=n)
        fn = device_collective(lambda v: adasum_allreduce(v, "dp"),
                               mesh, "dp")
        out = np.asarray(fn(x))
        expect = adasum_reference(list(x))
        for r in range(n):
            np.testing.assert_allclose(out[r], expect, rtol=1e-4)

    def test_identical_inputs_average(self, mesh8):
        # Adasum of identical vectors = the vector itself (a·b = ‖a‖²
        # → coefs 1/2) — the scale-insensitivity property.
        v = np.tile(stacked(1, (16,), seed=3), (8, 1))
        fn = device_collective(lambda t: adasum_allreduce(t, "dp"),
                               mesh8, "dp")
        np.testing.assert_allclose(np.asarray(fn(v))[0], v[0], rtol=1e-4)

    def test_non_pow2_raises(self):
        mesh = build_mesh(dp=3, devices=jax.devices()[:3])
        x = stacked(3, (8,))
        fn = device_collective(lambda v: adasum_allreduce(v, "dp"),
                               mesh, "dp")
        with pytest.raises(ValueError, match="power-of-2"):
            fn(x)


class TestGradSync:
    def _tree(self, n, seed=0):
        rng = np.random.RandomState(seed)
        return {
            "dense": {"kernel": rng.randn(n, 8, 4).astype(np.float32),
                      "bias": rng.randn(n, 4).astype(np.float32)},
            "head": {"kernel": rng.randn(n, 4, 2).astype(np.float32)},
        }

    def test_average_matches_manual(self, mesh8):
        tree = self._tree(8)
        fn = build_grad_sync(mesh8, GradSyncConfig(op="average"))
        out = fn(tree)
        for path in [("dense", "kernel"), ("dense", "bias"),
                     ("head", "kernel")]:
            got = np.asarray(out[path[0]][path[1]])
            want = tree[path[0]][path[1]].mean(0, keepdims=True)
            np.testing.assert_allclose(got, np.repeat(want, 8, 0), rtol=1e-5)

    def test_fusion_small_buckets_same_result(self, mesh8):
        tree = self._tree(8, seed=1)
        big = build_grad_sync(mesh8, GradSyncConfig(op="sum"))
        tiny = build_grad_sync(
            mesh8, GradSyncConfig(op="sum", fusion_threshold_bytes=16))
        a, b = big(tree), tiny(tree)
        jax.tree_util.tree_map(
            lambda x, y: np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), rtol=1e-5), a, b)

    def test_fp16_compression_reduces_in_fp16(self, mesh8):
        tree = {"w": stacked(8, (64,), seed=2)}
        fn = build_grad_sync(
            mesh8, GradSyncConfig(op="average", compression="fp16"))
        out = np.asarray(fn(tree)["w"])
        expect = np.mean(tree["w"].astype(np.float16), axis=0,
                         dtype=np.float32)
        np.testing.assert_allclose(out[0], expect, atol=2e-3)
        assert out.dtype == np.float32   # decompressed back

    def test_adasum_tree(self, mesh8):
        tree = {"w": stacked(8, (17,), seed=5)}
        fn = build_grad_sync(mesh8, GradSyncConfig(op="adasum"))
        out = np.asarray(fn(tree)["w"])
        expect = adasum_reference(list(tree["w"]))
        np.testing.assert_allclose(out[0], expect, rtol=1e-4)

    def test_mixed_dtype_tree(self, mesh8):
        tree = {"f32": stacked(8, (10,), seed=6),
                "bf16": stacked(8, (12,), seed=7).astype(jnp.bfloat16)}
        fn = build_grad_sync(mesh8, GradSyncConfig(op="sum"))
        out = fn(tree)
        np.testing.assert_allclose(np.asarray(out["f32"])[0],
                                   tree["f32"].sum(0), rtol=1e-5)
        assert out["bf16"].dtype == jnp.bfloat16


class TestSharding:
    def test_rules_place_params(self, mesh_dp_tp):
        params = {"attn": {"kernel": np.zeros((8, 16), np.float32)},
                  "bias": np.zeros((16,), np.float32)}
        rules = ShardingRules([(r"attn.*kernel", P(None, "tp"))])
        placed = shard_params(params, mesh_dp_tp, rules)
        kspec = placed["attn"]["kernel"].sharding.spec
        assert tuple(kspec) == (None, "tp")
        bspec = placed["bias"].sharding.spec
        assert tuple(bspec) == ()

    def test_rule_rank_mismatch_falls_through(self, mesh_dp_tp):
        rules = ShardingRules([(r".*", P(None, "tp"))])
        params = {"bias": np.zeros((4,), np.float32)}
        placed = shard_params(params, mesh_dp_tp, rules)
        assert tuple(placed["bias"].sharding.spec) == ()

    def test_overlapping_rules_first_match_wins(self):
        rules = ShardingRules([
            (r"attn.*kernel", P(None, "tp")),
            (r".*kernel", P("dp", None)),
        ])
        assert tuple(rules.spec_for("attn/q/kernel")) == (None, "tp")
        assert tuple(rules.spec_for("mlp/up/kernel")) == ("dp", None)

    def test_patterns_are_searched_not_anchored(self):
        # search(), not fullmatch(): a mid-path token matches, and an
        # author who wants anchoring spells ^...$ explicitly.
        rules = ShardingRules([(r"mlp/up", P(None, "tp")),
                               (r"^bias$", P("dp"))])
        assert tuple(rules.spec_for("layer0/mlp/up/kernel")) \
            == (None, "tp")
        assert tuple(rules.spec_for("bias")) == ("dp",)
        assert tuple(rules.spec_for("layer0/bias")) == ()

    def test_empty_spec_rule_blocks_later_rules(self):
        # P() is a legitimate "explicitly replicated" terminal rule —
        # it wins for its paths and never rank-skips (len 0 fits any
        # leaf).
        rules = ShardingRules([(r"norm", P()),
                               (r".*", P("dp"))])
        leaf = np.zeros((4,), np.float32)
        assert tuple(rules.spec_for("norm/scale", leaf)) == ()
        assert tuple(rules.spec_for("w", leaf)) == ("dp",)

    def test_validate_flags_unknown_axis(self, mesh_dp_tp):
        rules = ShardingRules([(r".*kernel", P(None, "model"))])
        params = {"attn": {"kernel": np.zeros((2, 2), np.float32)}}
        problems = rules.validate(mesh_dp_tp, params)
        assert any("HVD802" in p and "'model'" in p for p in problems)

    def test_validate_flags_dead_rule(self, mesh_dp_tp):
        rules = ShardingRules([(r"decoder.*kernel", P(None, "tp"))])
        params = {"attn": {"kernel": np.zeros((2, 2), np.float32)}}
        problems = rules.validate(mesh_dp_tp, params)
        assert any("HVD801 dead rule" in p and "decoder" in p
                   for p in problems)

    def test_validate_flags_uncovered_sibling(self, mesh_dp_tp):
        # wq is sharded; wk under the same parent falls through to
        # replicated — the classic forgotten-sibling hole.
        rules = ShardingRules([(r"attn/wq", P(None, "tp"))])
        params = {"attn": {"wq": np.zeros((2, 2), np.float32),
                           "wk": np.zeros((2, 2), np.float32)}}
        problems = rules.validate(mesh_dp_tp, params)
        assert any("HVD801 uncovered path" in p and "attn/wk" in p
                   for p in problems)

    def test_validate_clean_table_returns_empty(self, mesh_dp_tp):
        rules = ShardingRules([(r"attn/w[qk]", P(None, "tp"))])
        params = {"attn": {"wq": np.zeros((2, 2), np.float32),
                           "wk": np.zeros((2, 2), np.float32)}}
        assert rules.validate(mesh_dp_tp, params) == []


def test_hierarchical_allreduce_matches_flat():
    """Explicit reduce_scatter->cross->all_gather equals the flat psum
    (reference: NCCLHierarchicalAllreduce semantics)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from horovod_tpu.parallel import MeshSpec, build_mesh
    from horovod_tpu.parallel.grad_sync import (GradSyncConfig,
                                                build_grad_sync)

    mesh = build_mesh(MeshSpec(dp=2, fsdp=4))
    # 8 stacked per-rank gradients; sizes chosen to force local padding
    # (13 not divisible by local_size 4).
    grads = {"w": jnp.arange(8 * 13, dtype=jnp.float32).reshape(8, 13),
             "b": jnp.ones((8, 4), jnp.float32)}
    flat_fn = build_grad_sync(mesh, GradSyncConfig(
        axes=("dp", "fsdp"), op="average"))
    hier_fn = build_grad_sync(mesh, GradSyncConfig(
        axes=("dp", "fsdp"), op="average", hierarchical=True))
    a = flat_fn(grads)
    b = hier_fn(grads)
    for k in grads:
        np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                   rtol=1e-6)


def test_profiler_hooks(tmp_path):
    import horovod_tpu as hvd
    hvd.start_profiler(str(tmp_path))
    with hvd.profiler_annotation("step"):
        import jax.numpy as jnp
        (jnp.ones(8) * 2).block_until_ready()
    hvd.stop_profiler()
    import os
    assert any(os.scandir(str(tmp_path)))


# ---------------------------------------------------------------------------
# Pipeline parallelism (VERDICT r1 item 6: exactness vs unpipelined)
# ---------------------------------------------------------------------------
class TestPipeline:
    def _setup(self, n_stages=4, m=4, batch=8, dim=6):
        from horovod_tpu.parallel.pipeline import pipeline_apply

        rng = np.random.default_rng(0)
        # One dense stage per pp rank: h -> tanh(h @ W + b)
        Ws = rng.standard_normal((n_stages, dim, dim)).astype(np.float32) * 0.3
        bs = rng.standard_normal((n_stages, dim)).astype(np.float32) * 0.1
        x = rng.standard_normal((batch, dim)).astype(np.float32)

        def stage_fn(params, h):
            W, b = params
            return jnp.tanh(h @ W + b)

        def serial(Ws, bs, x):
            h = x
            for i in range(n_stages):
                h = stage_fn((Ws[i], bs[i]), h)
            return h

        mesh = build_mesh(MeshSpec(pp=n_stages))  # dp absorbs the rest

        def piped(Ws, bs, x):
            return shard_map(
                lambda W, b, xx: pipeline_apply(
                    stage_fn, (W[0], b[0]), xx, axis="pp",
                    num_microbatches=m, axis_size=n_stages),
                mesh=mesh, in_specs=(P("pp"), P("pp"), P()),
                out_specs=P(), axis_names=frozenset({"pp"}),
                check_vma=False)(Ws, bs, x)

        return Ws, bs, x, serial, piped

    def test_forward_matches_serial(self):
        Ws, bs, x, serial, piped = self._setup()
        np.testing.assert_allclose(jax.jit(piped)(Ws, bs, x),
                                   serial(Ws, bs, x), rtol=1e-5, atol=1e-6)

    def test_gradients_match_serial(self):
        Ws, bs, x, serial, piped = self._setup()

        def loss_p(Ws, bs):
            return jnp.sum(piped(Ws, bs, x) ** 2)

        def loss_s(Ws, bs):
            return jnp.sum(serial(Ws, bs, x) ** 2)

        gp = jax.jit(jax.grad(loss_p, argnums=(0, 1)))(Ws, bs)
        gs = jax.grad(loss_s, argnums=(0, 1))(Ws, bs)
        for a, b in zip(gp, gs):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)

    def test_uneven_microbatches(self):
        # m != n_stages exercises fill/drain bookkeeping.
        Ws, bs, x, serial, piped = self._setup(n_stages=2, m=4, batch=8)
        np.testing.assert_allclose(jax.jit(piped)(Ws, bs, x),
                                   serial(Ws, bs, x), rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Mixture-of-Experts (VERDICT r1 item 6: ep all_to_all path + capacity)
# ---------------------------------------------------------------------------
class TestMoE:
    def test_expert_parallel_matches_dense(self):
        """With capacity high enough that nothing drops, the two
        all_to_all expert-parallel path must equal the dense einsum."""
        from horovod_tpu.models.moe import MoEMLP

        mesh = build_mesh(MeshSpec(ep=4))  # dp absorbs the rest
        b, t, d, e = 8, 4, 6, 4
        rng = np.random.default_rng(1)
        x = rng.standard_normal((b, t, d)).astype(np.float32)

        dense_moe = MoEMLP(num_experts=e, d_ff=16, capacity_factor=float(e),
                           ep_mesh=None)
        ep_moe = MoEMLP(num_experts=e, d_ff=16, capacity_factor=float(e),
                        ep_mesh=mesh, ep_axis="ep")
        variables = dense_moe.init(jax.random.key(0), jnp.asarray(x))
        out_dense = dense_moe.apply(variables, jnp.asarray(x))
        out_ep = jax.jit(lambda v, xx: ep_moe.apply(v, xx))(
            variables, jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(out_ep),
                                   np.asarray(out_dense),
                                   rtol=1e-4, atol=1e-5)

    def test_expert_parallel_gradients_match_dense(self):
        from horovod_tpu.models.moe import MoEMLP

        mesh = build_mesh(MeshSpec(ep=4))  # dp absorbs the rest
        b, t, d, e = 8, 4, 6, 4
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.standard_normal((b, t, d)).astype(np.float32))
        dense_moe = MoEMLP(num_experts=e, d_ff=16, capacity_factor=float(e),
                           ep_mesh=None)
        ep_moe = MoEMLP(num_experts=e, d_ff=16, capacity_factor=float(e),
                        ep_mesh=mesh, ep_axis="ep")
        variables = dense_moe.init(jax.random.key(0), x)

        gd = jax.grad(lambda v: jnp.sum(dense_moe.apply(v, x) ** 2))(
            variables)
        ge = jax.jit(jax.grad(
            lambda v: jnp.sum(ep_moe.apply(v, x) ** 2)))(variables)
        flat_d = jax.tree_util.tree_leaves_with_path(gd)
        flat_e = jax.tree_util.tree_leaves_with_path(ge)
        for (pd, ld), (pe, le) in zip(flat_d, flat_e):
            assert pd == pe
            np.testing.assert_allclose(np.asarray(le), np.asarray(ld),
                                       rtol=1e-3, atol=1e-4,
                                       err_msg=str(pd))

    def test_capacity_drops_tokens(self):
        """Switch semantics: tokens beyond an expert's capacity produce
        zero output (dropped), not an error."""
        from horovod_tpu.models.moe import _capacity, _dispatch_combine

        n, e = 8, 2
        # All tokens prefer expert 0.
        logits = np.full((n, e), -10.0, dtype=np.float32)
        logits[:, 0] = 10.0
        cap = _capacity(n, e, factor=0.5)   # 2 slots for expert 0
        dispatch, combine = _dispatch_combine(jnp.asarray(logits), cap)
        kept = np.asarray(jnp.sum(dispatch, axis=(1, 2)))
        assert kept.sum() == cap            # only `cap` tokens kept
        np.testing.assert_array_equal(kept[:cap], np.ones(cap))
        np.testing.assert_array_equal(kept[cap:], np.zeros(n - cap))

    def test_moe_transformer_trains_over_ep(self):
        """TransformerLM(moe_experts=N) under the GSPMD Trainer on a
        dp x ep mesh: one full train step, finite loss, step advances."""
        import dataclasses

        import optax

        from horovod_tpu import models, training

        mesh = build_mesh(MeshSpec(dp=2, ep=4))
        cfg = dataclasses.replace(
            models.gpt_tiny(dtype=jnp.float32), num_layers=2,
            moe_experts=4, mesh=mesh)
        lm = models.TransformerLM(cfg)
        trainer = training.Trainer(
            lm, optax.adamw(1e-3), mesh,
            sync=GradSyncConfig(axes=(), op="average"),
            batch_spec=P(("dp", "ep")))
        batch = training.synthetic_text_batch(8, seq_len=16,
                                              vocab_size=cfg.vocab_size)
        state = trainer.init(jax.random.key(0), batch)
        state, metrics = trainer.step(state, batch)
        assert int(state.step) == 1
        assert np.isfinite(float(metrics["loss"]))


class TestKvBarrier:
    """kv_barrier protocol (parallel/multihost.py): rendezvous-KV barrier
    with a per-world sequence — the non-collective alignment primitive
    the compile→barrier→dispatch pattern relies on."""

    def _fake_world(self, monkeypatch, rank, size, store):
        from horovod_tpu.parallel import multihost

        class FakeKV:
            def put(self, scope, key, value):
                store[(scope, key)] = value

            def wait(self, scope, key, timeout=5.0):
                import time
                end = time.time() + timeout
                while (scope, key) not in store:
                    if time.time() > end:
                        raise TimeoutError(key)
                    time.sleep(0.01)
                return store[(scope, key)]

        monkeypatch.setattr(multihost, "_initialized_here", True)
        monkeypatch.setattr(multihost, "_world",
                            (rank, size, FakeKV(), "ep0"))
        return multihost

    def test_barrier_waits_for_every_rank(self, monkeypatch):
        import threading

        store: dict = {}
        mh = self._fake_world(monkeypatch, 0, 2, store)
        monkeypatch.setattr(mh, "_barrier_seq", 0)
        done = threading.Event()

        def rank0():
            mh.kv_barrier("t", timeout=5.0)
            done.set()

        t = threading.Thread(target=rank0, daemon=True)
        t.start()
        # Rank 0 has published its key but must still be blocked on
        # rank 1's.
        assert not done.wait(0.3)
        assert ("barrier", "ep0:t:1:0") in store
        store[("barrier", "ep0:t:1:1")] = b"1"   # rank 1 arrives
        assert done.wait(5.0)
        t.join(5.0)

    def test_sequence_advances_per_call(self, monkeypatch):
        store: dict = {}
        mh = self._fake_world(monkeypatch, 0, 2, store)
        monkeypatch.setattr(mh, "_barrier_seq", 0)
        store[("barrier", "ep0:a:1:1")] = b"1"
        store[("barrier", "ep0:b:2:1")] = b"1"
        mh.kv_barrier("a", timeout=2.0)
        mh.kv_barrier("b", timeout=2.0)
        assert ("barrier", "ep0:a:1:0") in store
        assert ("barrier", "ep0:b:2:0") in store

    def test_noop_outside_world(self, monkeypatch):
        from horovod_tpu.parallel import multihost
        monkeypatch.setattr(multihost, "_initialized_here", False)
        multihost.kv_barrier("t", timeout=0.1)   # must not raise
