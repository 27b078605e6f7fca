"""compress/ subsystem battery: quantize/dequantize round-trip bounds,
wire serialization, the numpy/jax twin parity, error-feedback convergence
on a tiny quadratic, codec negotiation (mismatch -> structured ERROR),
cache invalidation on codec change, and int8 allreduce equivalence across
the eager planes (threaded tcp/shm here; subprocess tcp/shm/xla worlds
via mp_worker batteries) and the compiled grad_sync path."""
from __future__ import annotations

import os
import sys
import threading

import numpy as np
import pytest

from horovod_tpu.compress import (CAST_CODECS, CompressionCodec,
                                  QUANTIZED_CODECS, chunk_bounds,
                                  codec_from_name, codec_name,
                                  dequantize, from_bytes, quantize,
                                  roundtrip_error_bound, serialized_nbytes,
                                  to_bytes)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Quantize / dequantize units
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("codec", [CompressionCodec.INT8,
                                   CompressionCodec.UINT4])
@pytest.mark.parametrize("block_size", [16, 64, 256])
def test_roundtrip_error_bound(codec, block_size):
    rng = np.random.default_rng(0)
    for n in (1, 5, block_size, block_size + 3, 4 * block_size, 10_000):
        x = (rng.standard_normal(n) * rng.uniform(0.1, 30)).astype(
            np.float32)
        qb = quantize(x, codec, block_size)
        xh = dequantize(qb)
        bound = roundtrip_error_bound(x, codec, block_size)
        assert xh.shape == x.shape
        assert np.all(np.abs(x - xh) <= bound + 1e-6), \
            (codec, n, float(np.abs(x - xh).max()))


@pytest.mark.parametrize("codec", [CompressionCodec.INT8,
                                   CompressionCodec.UINT4])
def test_wire_serialization_roundtrip_and_size(codec):
    rng = np.random.default_rng(1)
    for n in (0, 1, 7, 255, 1000):
        x = rng.standard_normal(n).astype(np.float32)
        qb = quantize(x, codec, 64)
        raw = to_bytes(qb)
        assert len(raw) == serialized_nbytes(n, codec, 64)
        qb2 = from_bytes(np.frombuffer(raw, np.uint8), n, codec, 64)
        np.testing.assert_array_equal(dequantize(qb2), dequantize(qb))
    # Wire-byte ratios vs fp32: the whole point of the subsystem.
    n = 1 << 16
    fp32 = n * 4
    assert serialized_nbytes(n, CompressionCodec.INT8, 256) * 3.5 < fp32
    assert serialized_nbytes(n, CompressionCodec.UINT4, 256) * 7.0 < fp32


def test_quantize_edge_cases():
    # Constant blocks: zero range must not divide by zero, and must
    # reconstruct exactly.
    x = np.full(100, 3.25, np.float32)
    np.testing.assert_array_equal(dequantize(quantize(
        x, CompressionCodec.INT8, 32)), x)
    # Tail block shorter than block_size keeps its own scale.
    x = np.concatenate([np.zeros(64, np.float32),
                        np.full(3, 1000.0, np.float32)])
    xh = dequantize(quantize(x, CompressionCodec.INT8, 64))
    np.testing.assert_allclose(xh[:64], 0.0, atol=1e-6)
    np.testing.assert_allclose(xh[64:], 1000.0, rtol=1e-2)


def test_codec_registry():
    assert codec_from_name("int8") == CompressionCodec.INT8
    assert codec_from_name(None) == CompressionCodec.NONE
    assert codec_from_name(CompressionCodec.UINT4) == CompressionCodec.UINT4
    assert codec_name(CompressionCodec.BF16) == "bf16"

    class Marker:
        wire_codec = "uint4"
    assert codec_from_name(Marker) == CompressionCodec.UINT4
    with pytest.raises(ValueError, match="Unknown compression codec"):
        codec_from_name("int7")
    assert set(QUANTIZED_CODECS) | set(CAST_CODECS) | \
        {CompressionCodec.NONE} == set(CompressionCodec)


def test_jax_matches_numpy():
    """The compiled twin must apply the identical scale rule and
    rounding, so planes and grad_sync land in one error bound."""
    import jax.numpy as jnp

    from horovod_tpu.compress import jax_ops

    rng = np.random.default_rng(2)
    for codec in (CompressionCodec.INT8, CompressionCodec.UINT4):
        m, bs = 512, 64
        x = (rng.standard_normal(m) * 5).astype(np.float32)
        qb = quantize(x, codec, bs)
        q, s, zp = jax_ops.quantize_rows(jnp.asarray(x)[None, :], codec, bs)
        np.testing.assert_array_equal(np.asarray(q)[0], qb.payload)
        np.testing.assert_array_equal(np.asarray(s)[0], qb.scales)
        np.testing.assert_array_equal(np.asarray(zp)[0], qb.zero_points)
        deq = jax_ops.dequantize_rows(q, s, zp, codec, bs)
        np.testing.assert_array_equal(np.asarray(deq)[0], dequantize(qb))


# ---------------------------------------------------------------------------
# Error feedback
# ---------------------------------------------------------------------------
def test_error_feedback_store_roundtrip():
    from horovod_tpu.compress import ErrorFeedback

    ef = ErrorFeedback(CompressionCodec.UINT4, block_size=32)
    x = np.random.default_rng(3).standard_normal(200).astype(np.float32)
    comp = ef.compensate("g", x)
    wire = ef.update("g", comp)
    res = ef.residual("g")
    np.testing.assert_allclose(comp, wire + res, rtol=1e-6, atol=1e-6)
    # Second step re-injects the residual.
    comp2 = ef.compensate("g", x)
    np.testing.assert_allclose(comp2, x + res, rtol=1e-6, atol=1e-6)


def test_error_feedback_telescopes():
    """The EF identity: over T steps, sum(wire_t) == sum(grad_t) - e_T —
    no gradient mass is ever lost, only delayed by the final residual."""
    from horovod_tpu.compress import ErrorFeedback

    rng = np.random.default_rng(5)
    ef = ErrorFeedback(CompressionCodec.UINT4, block_size=32)
    grads = rng.standard_normal((20, 128)).astype(np.float32)
    wire_sum = np.zeros(128, np.float32)
    for g in grads:
        wire_sum += ef.update("w", ef.compensate("w", g))
    np.testing.assert_allclose(wire_sum + ef.residual("w"),
                               grads.sum(0), rtol=1e-4, atol=1e-4)


def test_error_feedback_quadratic_convergence():
    """EF-SGD convergence on a tiny heterogeneous quadratic: two ranks
    minimize mean_r 0.5||w - c_r||^2 (optimum = mean(c_r)).  Local
    gradients at the optimum are NONZERO (±(c_0-c_1)/2), so each rank's
    block quantization error has a persistent floor — plain quantized
    gradient descent stalls there, while error feedback re-injects the
    error and keeps descending (the EF-SGD guarantee)."""
    rng = np.random.default_rng(5)
    n, bs = 256, 64
    c = (rng.standard_normal((2, n)) * 50).astype(np.float32)
    w_opt = c.mean(axis=0)
    codec = CompressionCodec.INT8

    def run(use_ef: bool, steps=400, lr=0.2) -> float:
        w = np.zeros(n, np.float32)
        res = np.zeros((2, n), np.float32)
        for _ in range(steps):
            gsum = np.zeros(n, np.float32)
            for r in range(2):
                g = w - c[r]
                if use_ef:
                    comp = g + res[r]
                    wire = dequantize(quantize(comp, codec, bs))
                    res[r] = comp - wire
                    gsum += wire
                else:
                    gsum += dequantize(quantize(g, codec, bs))
            w = w - lr * gsum / 2
        return float(np.linalg.norm(w - w_opt))

    dist_plain = run(False)
    dist_ef = run(True)
    assert dist_ef < 1.0, dist_ef                    # ~0.4 measured
    assert dist_ef * 3 < dist_plain, (dist_ef, dist_plain)   # ~2.5


# ---------------------------------------------------------------------------
# Controller negotiation + cache
# ---------------------------------------------------------------------------
def test_codec_mismatch_structured_error():
    from horovod_tpu.common.message import (Request, RequestType,
                                            ResponseType)
    from util_world import InProcWorld, make_controller, run_ranks

    world = InProcWorld(2)

    def rank_fn(r):
        ctrl = make_controller(r, 2, world)
        ctrl.tensor_queue.push_back_to_queue(Request(
            request_rank=r, request_type=RequestType.ALLREDUCE,
            tensor_name="g", tensor_shape=(4,),
            codec=int(CompressionCodec.INT8) if r == 0 else 0,
            codec_block_size=256 if r == 0 else 0))
        return ctrl.compute_response_list()

    lists = run_ranks(2, rank_fn)
    for rl in lists:
        assert len(rl.responses) == 1
        resp = rl.responses[0]
        assert resp.response_type == ResponseType.ERROR
        assert "codec" in resp.error_message.lower()


def test_codec_negotiated_into_response():
    from horovod_tpu.common.message import (Request, RequestType,
                                            ResponseType)
    from util_world import InProcWorld, make_controller, run_ranks

    world = InProcWorld(2)

    def rank_fn(r):
        ctrl = make_controller(r, 2, world)
        ctrl.tensor_queue.push_back_to_queue(Request(
            request_rank=r, request_type=RequestType.ALLREDUCE,
            tensor_name="g", tensor_shape=(4,),
            codec=int(CompressionCodec.UINT4), codec_block_size=128))
        return ctrl.compute_response_list()

    for rl in run_ranks(2, rank_fn):
        (resp,) = rl.responses
        assert resp.response_type == ResponseType.ALLREDUCE
        assert resp.codec == int(CompressionCodec.UINT4)
        assert resp.codec_block_size == 128


def test_adasum_quantized_rejected():
    from horovod_tpu.common.message import (Request, RequestType,
                                            ResponseType)
    from util_world import InProcWorld, make_controller, run_ranks

    world = InProcWorld(2)

    def rank_fn(r):
        ctrl = make_controller(r, 2, world)
        ctrl.tensor_queue.push_back_to_queue(Request(
            request_rank=r, request_type=RequestType.ADASUM,
            tensor_name="g", tensor_shape=(4,),
            codec=int(CompressionCodec.INT8), codec_block_size=256))
        return ctrl.compute_response_list()

    for rl in run_ranks(2, rank_fn):
        (resp,) = rl.responses
        assert resp.response_type == ResponseType.ERROR
        assert "adasum" in resp.error_message.lower()


def test_response_cache_invalidates_on_codec_change():
    from horovod_tpu.common.message import (Request, RequestType, Response,
                                            ResponseType)
    from horovod_tpu.common.response_cache import CacheState, ResponseCache

    cache = ResponseCache(16)
    req = Request(request_rank=0, request_type=RequestType.ALLREDUCE,
                  tensor_name="g", tensor_shape=(8,),
                  codec=0, codec_block_size=0)
    cache.put(Response(response_type=ResponseType.ALLREDUCE,
                       tensor_names=["g"], tensor_sizes=[8]), req)
    assert cache.cached(req) == CacheState.HIT
    flipped = Request(request_rank=0, request_type=RequestType.ALLREDUCE,
                      tensor_name="g", tensor_shape=(8,),
                      codec=int(CompressionCodec.INT8),
                      codec_block_size=256)
    assert cache.cached(flipped) == CacheState.INVALID


def test_wire_roundtrip_codec_fields():
    from horovod_tpu.common.message import (Request, RequestList,
                                            RequestType, Response,
                                            ResponseList, ResponseType)

    req = Request(request_rank=1, request_type=RequestType.ALLREDUCE,
                  tensor_name="g", tensor_shape=(3, 3),
                  codec=int(CompressionCodec.INT8), codec_block_size=512)
    decoded = RequestList.from_bytes(
        RequestList(requests=[req]).to_bytes()).requests[0]
    assert decoded == req

    resp = Response(response_type=ResponseType.ALLREDUCE,
                    tensor_names=["g"], tensor_sizes=[9],
                    codec=int(CompressionCodec.UINT4),
                    codec_block_size=64)
    rl = ResponseList(responses=[resp], tuned_codec=int(
        CompressionCodec.FP16))
    decoded = ResponseList.from_bytes(rl.to_bytes())
    assert decoded.responses[0] == resp
    assert decoded.tuned_codec == int(CompressionCodec.FP16)


# ---------------------------------------------------------------------------
# Eager planes (threaded in-process worlds)
# ---------------------------------------------------------------------------
@pytest.fixture()
def kv():
    from horovod_tpu.runner.network import (RendezvousClient,
                                            RendezvousServer)
    server = RendezvousServer()
    port = server.start()
    yield RendezvousClient("127.0.0.1", port, 10.0)
    server.stop()


def _threaded(n, fn, timeout=60.0):
    results: list = [None] * n
    errors: list = []

    def worker(r):
        try:
            results[r] = fn(r)
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(r,), daemon=True)
               for r in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout)
        assert not t.is_alive(), "rank thread hung"
    if errors:
        raise errors[0]
    return results


def _plane_error_bound(data, codec, block_size):
    size = data.shape[0]
    input_bound = sum(roundtrip_error_bound(data[r], codec, block_size)
                     for r in range(size))
    ref = data.sum(axis=0)
    b = chunk_bounds(ref.size, size)
    requant = np.concatenate(
        [roundtrip_error_bound(ref[b[r]:b[r + 1]], codec, block_size)
         for r in range(size)])
    return 2 * input_bound + requant + 1e-5


@pytest.mark.parametrize("codec", [CompressionCodec.INT8,
                                   CompressionCodec.UINT4])
@pytest.mark.parametrize("size", [2, 3])
def test_tcp_quantized_allreduce(kv, codec, size):
    from horovod_tpu.backend.tcp import TcpCollectives
    from horovod_tpu.runner.network import PeerMesh

    rng = np.random.default_rng(10)
    n = 5000
    data = (rng.standard_normal((size, n)) * 3).astype(np.float32)
    meshes: list = [None] * size

    def worker(r):
        mesh = PeerMesh(r, size, kv, scope=f"tq{codec}{size}",
                        timeout=10.0)
        meshes[r] = mesh
        return TcpCollectives(mesh).quantized_allreduce(
            data[r].copy(), codec, 128)

    try:
        outs = _threaded(size, worker)
        for r in range(1, size):
            np.testing.assert_array_equal(outs[0], outs[r])
        bound = _plane_error_bound(data, codec, 128)
        err = np.abs(outs[0].astype(np.float64) - data.sum(0))
        assert np.all(err <= bound), (float(err.max()),)
        # Wire volume: strictly below the fp32 ring's 2(N-1)/N·4n bytes.
        fp32_ring = 2 * (size - 1) * n * 4 // size
        assert meshes[0].bytes_sent < fp32_ring / 2.5
    finally:
        for m in meshes:
            if m is not None:
                m.close()


def test_shm_quantized_matches_tcp_bitwise(kv):
    """Planes interoperate, so their quantized reconstructions must be
    bit-identical (same quantize order, same rank-order fp32 sum)."""
    from horovod_tpu.backend.shm import ShmBackend, ShmWorld
    from horovod_tpu.backend.tcp import TcpCollectives
    from horovod_tpu.common.dtypes import from_any
    from horovod_tpu.common.message import Response, ResponseType
    from horovod_tpu.common.tensor_queue import TensorTableEntry
    from horovod_tpu.runner.network import PeerMesh

    size, n = 3, 3000
    rng = np.random.default_rng(11)
    data = rng.standard_normal((size, n)).astype(np.float32)
    worlds: list = [None] * size

    def form(r):
        worlds[r] = ShmWorld(r, size, kv, scope="sq", capacity=1 << 20,
                             timeout=10.0)
        return worlds[r]

    _threaded(size, form)
    if not all(w.formed for w in worlds):
        pytest.skip("shm world did not form on this host")

    def shm_run(r):
        be = ShmBackend(worlds[r])
        resp = Response(response_type=ResponseType.ALLREDUCE,
                        tensor_names=["x"], tensor_sizes=[n],
                        tensor_type=from_any(np.dtype(np.float32)),
                        codec=int(CompressionCodec.INT8),
                        codec_block_size=128)
        entry = TensorTableEntry(tensor_name="x", tensor=data[r].copy())
        assert be.enabled(resp, [entry])
        assert be.allreduce(resp, [entry]).ok_p()
        return entry.output

    meshes: list = [None] * size

    def tcp_run(r):
        mesh = PeerMesh(r, size, kv, scope="sqt", timeout=10.0)
        meshes[r] = mesh
        return TcpCollectives(mesh).quantized_allreduce(
            data[r].copy(), CompressionCodec.INT8, 128)

    try:
        shm_outs = _threaded(size, shm_run)
        tcp_outs = _threaded(size, tcp_run)
        np.testing.assert_array_equal(shm_outs[0], shm_outs[1])
        np.testing.assert_array_equal(shm_outs[0], tcp_outs[0])
    finally:
        for w in worlds:
            w.close()
        for m in meshes:
            if m is not None:
                m.close()


def test_shm_declines_oversized_quantized(kv):
    """Capacity accounting must use the QUANTIZED staging size and stay
    rank-symmetric: a payload whose staged chunks exceed the region
    falls through to the TCP plane."""
    from horovod_tpu.backend.shm import ShmBackend, ShmWorld
    from horovod_tpu.common.dtypes import from_any
    from horovod_tpu.common.message import Response, ResponseType
    from horovod_tpu.common.tensor_queue import TensorTableEntry
    from horovod_tpu.compress import staged_nbytes

    size = 2
    capacity = 1 << 12
    worlds = _threaded(size, lambda r: ShmWorld(
        r, size, kv, scope="cap", capacity=capacity, timeout=10.0))
    if not all(w.formed for w in worlds):
        pytest.skip("shm world did not form on this host")
    try:
        be = ShmBackend(worlds[0])
        # Quantized int8 fits where fp32 would not (4x), and a payload
        # larger than the quantized budget is declined.
        n_fits = capacity // 2      # 2KB as int8+meta; 8KB as fp32
        per, total = staged_nbytes(n_fits, size, CompressionCodec.INT8,
                                   256)
        assert total + max(per) <= capacity

        def resp(n, codec):
            return Response(response_type=ResponseType.ALLREDUCE,
                            tensor_names=["x"], tensor_sizes=[n],
                            tensor_type=from_any(np.dtype(np.float32)),
                            codec=int(codec), codec_block_size=256)

        entry = TensorTableEntry(
            tensor_name="x", tensor=np.zeros(n_fits, np.float32))
        assert be.enabled(resp(n_fits, CompressionCodec.INT8), [entry])
        assert not be.enabled(resp(n_fits, CompressionCodec.NONE),
                              [entry])
        big = TensorTableEntry(
            tensor_name="x", tensor=np.zeros(4 * capacity, np.float32))
        assert not be.enabled(resp(4 * capacity, CompressionCodec.INT8),
                              [big])
    finally:
        for w in worlds:
            w.close()


# ---------------------------------------------------------------------------
# Compiled grad_sync path (virtual CPU mesh from conftest)
# ---------------------------------------------------------------------------
def _dp_mesh(n=4):
    import jax
    from jax.sharding import Mesh

    devices = np.array(jax.devices()[:n])
    return Mesh(devices, ("dp",))


def test_grad_sync_int8_matches_fp32_within_bound():
    import jax

    from horovod_tpu.parallel import GradSyncConfig, build_grad_sync

    world = 4
    mesh = _dp_mesh(world)
    rng = np.random.default_rng(20)
    grads = {"w": (rng.standard_normal((world, 33, 7)) * 2).astype(
        np.float32),
        "b": rng.standard_normal((world, 11)).astype(np.float32)}

    ref_fn = build_grad_sync(mesh, GradSyncConfig(op="average"))
    q_fn = build_grad_sync(mesh, GradSyncConfig(
        op="average", compression="int8", compression_block_size=64))
    ref = jax.tree_util.tree_map(np.asarray, ref_fn(grads))
    out = jax.tree_util.tree_map(np.asarray, q_fn(grads))
    for key in grads:
        flat = grads[key].reshape(world, -1)
        bound = _plane_error_bound(flat, CompressionCodec.INT8, 64) / world
        err = np.abs(out[key].reshape(world, -1)[0].astype(np.float64)
                     - ref[key].reshape(world, -1)[0])
        assert np.all(err <= bound.reshape(-1)[:err.size] + 1e-5), \
            (key, float(err.max()))
        # Replicated output: every rank row identical.
        for r in range(1, world):
            np.testing.assert_array_equal(out[key][0], out[key][r])


def test_grad_sync_ef_training_within_5pct_of_fp32():
    """Acceptance criterion: a small training run with compression="int8"
    + error feedback reaches a loss within 5% of the fp32 baseline in the
    same step count.  Linear regression on a fixed dataset, dp=2, the EF
    residual threading through the jitted step."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from horovod_tpu.parallel import (GradSyncConfig, init_error_feedback,
                                      sync_gradients, sync_gradients_ef)

    world = 2
    mesh = _dp_mesh(world)
    rng = np.random.default_rng(21)
    w_true = rng.standard_normal((16, 4)).astype(np.float32)
    X = rng.standard_normal((world, 64, 16)).astype(np.float32)
    Y = np.einsum("rbi,io->rbo", X, w_true).astype(np.float32)

    def make_step(cfg, use_ef):
        def local_step(w, res, x, y):
            def loss_of(w):
                pred = x[0] @ w
                return jnp.mean((pred - y[0]) ** 2)

            loss, g = jax.value_and_grad(loss_of)(w[0])
            if use_ef:
                g, new_res = sync_gradients_ef(g, res[0], cfg)
            else:
                g, new_res = sync_gradients(g, cfg), res[0]
            w = w[0] - 0.05 * g
            return (w[None], new_res[None],
                    jax.lax.pmean(loss, "dp")[None])

        mapped = shard_map(
            local_step, mesh=mesh,
            in_specs=(P("dp"), P("dp"), P("dp"), P("dp")),
            out_specs=(P("dp"), P("dp"), P("dp")),
            check_vma=False)
        return jax.jit(mapped)

    def train(cfg, use_ef, steps=60):
        w = np.zeros((world, 16, 4), np.float32)
        res = np.asarray(jax.tree_util.tree_map(
            lambda z: np.zeros((world,) + z.shape, np.float32),
            init_error_feedback(np.zeros((16, 4), np.float32))))
        step = make_step(cfg, use_ef)
        loss = None
        for _ in range(steps):
            w, res, loss = step(w, res, X, Y)
        return float(np.asarray(loss)[0])

    base = train(GradSyncConfig(op="average"), use_ef=False)
    ef = train(GradSyncConfig(op="average", compression="int8",
                              compression_block_size=64,
                              error_feedback=True), use_ef=True)
    # Same step count, loss within 5% of the fp32 baseline (both are
    # tiny; compare the gap to the initial loss scale to avoid 0/0).
    init_loss = float(np.mean(Y ** 2))
    assert ef <= base + 0.05 * init_loss, (base, ef, init_loss)


def test_grad_sync_adasum_rejects_quantized():
    from horovod_tpu.parallel import GradSyncConfig
    from horovod_tpu.parallel.grad_sync import _sync_impl

    with pytest.raises(ValueError, match="adasum"):
        _sync_impl({"g": np.ones(4, np.float32)},
                   GradSyncConfig(op="adasum", compression="int8"), None)


def test_quantized_allreduce_uint4_requires_even_block():
    import jax.numpy as jnp

    from horovod_tpu.compress import jax_ops

    with pytest.raises(ValueError, match="even block"):
        jax_ops.quantized_allreduce(jnp.zeros(8), ("dp",), "sum",
                                    CompressionCodec.UINT4, 3)


# ---------------------------------------------------------------------------
# Subprocess worlds: eager end-to-end over the real planes
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("size", [2, 3])
def test_eager_compress_tcp_world(size):
    from test_multiprocess import _run_world
    _run_world(size, "compress", timeout=180.0)


def test_eager_compress_shm_world():
    from test_multiprocess import _run_world
    _run_world(2, "compress_shm", timeout=180.0)


def test_eager_compress_xla_world():
    from test_multiprocess import _run_world
    _run_world(2, "compress_xla", timeout=240.0)
