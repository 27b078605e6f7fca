"""Worker script for multi-process parallel tests.

The analogue of the reference's test/parallel/* files, which are plain
pytest files executed under `mpirun -np 2` (SURVEY §4).  Here each worker
process runs the same battery of cross-rank semantic assertions; the parent
test spawns N of them against one rendezvous server and checks exit codes.

Usage: python mp_worker.py <rank> <size> <rendezvous_port> [battery]
"""
import os
import sys
import traceback

import numpy as np


def battery_collectives(hvd, rank, size):
    # -- allreduce sum ---------------------------------------------------
    x = np.arange(16, dtype=np.float32) + rank
    expected = np.arange(16, dtype=np.float32) * size + sum(range(size))
    out = hvd.allreduce(x, op=hvd.Sum, name="ar_sum")
    np.testing.assert_allclose(out, expected, rtol=1e-6)

    # -- allreduce average ----------------------------------------------
    out = hvd.allreduce(x, op=hvd.Average, name="ar_avg")
    np.testing.assert_allclose(out, expected / size, rtol=1e-6)

    # -- pre/postscale ----------------------------------------------------
    out = hvd.allreduce(np.ones(8, dtype=np.float32), op=hvd.Sum,
                        name="ar_scale", prescale_factor=2.0,
                        postscale_factor=0.5)
    np.testing.assert_allclose(out, np.full(8, float(size)), rtol=1e-6)

    # -- 16-bit dtypes ----------------------------------------------------
    for dt, tag in ((np.float16, "fp16"), (np.float64, "fp64"),
                    (np.int32, "i32"), (np.int64, "i64")):
        v = (np.ones(32) * (rank + 1)).astype(dt)
        out = hvd.allreduce(v, op=hvd.Sum, name=f"ar_{tag}")
        np.testing.assert_allclose(
            np.asarray(out, dtype=np.float64),
            np.full(32, sum(range(1, size + 1)), dtype=np.float64))

    import ml_dtypes
    v = np.ones(32, dtype=ml_dtypes.bfloat16) * (rank + 1)
    out = hvd.allreduce(v, op=hvd.Sum, name="ar_bf16")
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.full(32, sum(range(1, size + 1))))

    # -- grouped allreduce ------------------------------------------------
    xs = [np.full((4,), rank + i, dtype=np.float32) for i in range(3)]
    outs = hvd.grouped_allreduce(xs, op=hvd.Sum, name="gar")
    for i, out in enumerate(outs):
        np.testing.assert_allclose(
            out, np.full((4,), sum(r + i for r in range(size))))

    # -- allgather (variable first dim) ----------------------------------
    local = np.full((rank + 1, 3), rank, dtype=np.float32)
    out = hvd.allgather(local, name="ag")
    expected_rows = []
    for r in range(size):
        expected_rows.append(np.full((r + 1, 3), r, dtype=np.float32))
    np.testing.assert_array_equal(out, np.concatenate(expected_rows))

    # -- allgather burst: async submissions land in one cycle and fuse
    # (controller allgather fusion); correctness must hold either way,
    # with mixed trailing shapes sharing the packed exchange.
    handles = [hvd.allgather_async(
        np.full((rank + 1, i + 2), 10.0 * rank + i, np.float32),
        name=f"ag_burst{i}") for i in range(4)]
    for i, h in enumerate(handles):
        out = hvd.synchronize(h)
        expected = np.concatenate([np.full((r + 1, i + 2), 10.0 * r + i,
                                           np.float32)
                                   for r in range(size)])
        np.testing.assert_array_equal(out, expected)

    # -- broadcast --------------------------------------------------------
    root = size - 1
    v = np.arange(6, dtype=np.float64) * (rank + 1)
    out = hvd.broadcast(v, root_rank=root, name="bc")
    np.testing.assert_array_equal(out,
                                  np.arange(6, dtype=np.float64) * (root + 1))

    # -- alltoall ---------------------------------------------------------
    splits = [2] * size
    v = np.arange(2 * size, dtype=np.float32) + 100 * rank
    out, recv_splits = hvd.alltoall(v, splits=splits, name="a2a")
    expected = np.concatenate(
        [np.arange(2 * r, 2 * r + 2, dtype=np.float32)
         + 100 * r + (2 * rank - 2 * r) for r in range(size)])
    # rank r sends rows [2*dest, 2*dest+2) to dest; we receive from each
    # peer their slice targeted at us.
    expected = np.concatenate(
        [np.arange(2 * rank, 2 * rank + 2, dtype=np.float32) + 100 * r
         for r in range(size)])
    np.testing.assert_array_equal(out, expected)
    np.testing.assert_array_equal(np.asarray(recv_splits), np.array([2] * size))

    # -- barrier ----------------------------------------------------------
    hvd.barrier()

    # -- steady-state cache loop -----------------------------------------
    for _ in range(5):
        out = hvd.allreduce(np.ones(4, dtype=np.float32), op=hvd.Sum,
                            name="steady")
        np.testing.assert_allclose(out, np.full(4, float(size)))


def battery_matrix(hvd, rank, size):
    """Reference-scale semantic sweep (VERDICT r2 item 6; modeled on the
    grid in /root/reference/test/parallel/test_torch.py, 2448 LoC): every
    wire dtype x {allreduce, grouped, allgather, broadcast, alltoall},
    prescale/postscale on floats, 64-bit exactness through the TCP plane,
    and grouped mismatch error cases."""
    import ml_dtypes

    int_dtypes = [np.int8, np.uint8, np.int32, np.int64]
    float_dtypes = [np.float16, ml_dtypes.bfloat16, np.float32, np.float64]

    # -- allreduce: every dtype, odd length (exercises ring chunking) ----
    for dt in int_dtypes + float_dtypes:
        tag = np.dtype(dt).name
        v = (np.arange(17) % 5 + rank + 1).astype(dt)
        out = hvd.allreduce(v, op=hvd.Sum, name=f"mx_ar_{tag}")
        expected = sum(
            (np.arange(17) % 5 + r + 1).astype(np.float64)
            for r in range(size))
        assert np.asarray(out).dtype == np.dtype(dt), (tag, out.dtype)
        np.testing.assert_allclose(np.asarray(out, np.float64), expected,
                                   rtol=1e-2 if np.dtype(dt).itemsize <= 2
                                   else 1e-6, err_msg=f"allreduce {tag}")

    # bool rides as logical-or under summation semantics.
    v = np.array([rank == 0, True, False])
    out = hvd.allreduce(v, op=hvd.Sum, name="mx_ar_bool")
    np.testing.assert_array_equal(np.asarray(out),
                                  np.array([True, True, False]))

    # -- 64-bit exactness: values that fp32 canonicalization would break
    # (the XLA plane must decline; the TCP ring is exact) ----------------
    big = np.array([2 ** 40 + rank, -(2 ** 50) + rank], dtype=np.int64)
    out = hvd.allreduce(big, op=hvd.Sum, name="mx_i64_exact")
    np.testing.assert_array_equal(
        np.asarray(out),
        np.array([size * 2 ** 40 + sum(range(size)),
                  -size * 2 ** 50 + sum(range(size))], dtype=np.int64))
    fine = np.array([1.0 + rank * 2.0 ** -40], dtype=np.float64)
    out = hvd.allreduce(fine, op=hvd.Sum, name="mx_f64_exact")
    np.testing.assert_array_equal(
        np.asarray(out),
        np.array([size * 1.0 + sum(range(size)) * 2.0 ** -40]))

    # -- prescale/postscale + average on every float dtype ---------------
    for dt in float_dtypes:
        tag = np.dtype(dt).name
        out = hvd.allreduce(np.ones(9, dt), op=hvd.Sum,
                            name=f"mx_scale_{tag}",
                            prescale_factor=2.0, postscale_factor=0.25)
        np.testing.assert_allclose(np.asarray(out, np.float64),
                                   np.full(9, size / 2.0), rtol=1e-2,
                                   err_msg=f"pre/post {tag}")
        out = hvd.allreduce((np.ones(9) * (rank + 1)).astype(dt),
                            op=hvd.Average, name=f"mx_avg_{tag}")
        np.testing.assert_allclose(
            np.asarray(out, np.float64),
            np.full(9, sum(range(1, size + 1)) / size), rtol=1e-2,
            err_msg=f"average {tag}")

    # -- grouped allreduce per dtype --------------------------------------
    for dt in (np.int32, np.float32, np.float64):
        tag = np.dtype(dt).name
        xs = [np.full(5 + i, rank + i + 1).astype(dt) for i in range(3)]
        outs = hvd.grouped_allreduce(xs, op=hvd.Sum, name=f"mx_gar_{tag}")
        for i, out in enumerate(outs):
            np.testing.assert_allclose(
                np.asarray(out, np.float64),
                np.full(5 + i, sum(r + i + 1 for r in range(size))),
                err_msg=f"grouped {tag}[{i}]")

    # -- allgather (ragged first dim) per dtype ---------------------------
    for dt in (np.uint8, np.int64, np.float16, np.float32, np.float64):
        tag = np.dtype(dt).name
        local = np.full((rank + 1, 2), rank + 1).astype(dt)
        out = hvd.allgather(local, name=f"mx_ag_{tag}")
        expected = np.concatenate([np.full((r + 1, 2), r + 1)
                                   for r in range(size)])
        np.testing.assert_array_equal(np.asarray(out, np.float64),
                                      expected, err_msg=f"allgather {tag}")

    # -- broadcast per dtype ----------------------------------------------
    root = size - 1
    for dt in (np.int8, np.int64, ml_dtypes.bfloat16, np.float64):
        tag = np.dtype(dt).name
        v = (np.arange(7) * (rank + 1)).astype(dt)
        out = hvd.broadcast(v, root_rank=root, name=f"mx_bc_{tag}")
        np.testing.assert_array_equal(
            np.asarray(out, np.float64),
            (np.arange(7) * (root + 1)).astype(dt).astype(np.float64),
            err_msg=f"broadcast {tag}")

    # -- alltoall (uneven splits) per dtype -------------------------------
    for dt in (np.int32, np.int64, np.float32):
        tag = np.dtype(dt).name
        splits = [rank + 1] * size
        v = (np.arange((rank + 1) * size) + 10 * rank).astype(dt)
        out, recv = hvd.alltoall(v, splits=splits, name=f"mx_a2a_{tag}")
        expected = np.concatenate(
            [(np.arange(rank * (r + 1), (rank + 1) * (r + 1))
              + 10 * r).astype(dt) for r in range(size)])
        np.testing.assert_array_equal(out, expected,
                                      err_msg=f"alltoall {tag}")
        np.testing.assert_array_equal(
            np.asarray(recv), np.arange(1, size + 1))

    # -- reducescatter: dtypes + the empty-chunk ragged edge --------------
    for dt in (np.int32, np.float32, np.float64):
        tag = np.dtype(dt).name
        x = (np.arange(2 * size * 2).reshape(2 * size, 2)
             * (rank + 1)).astype(dt)
        out = hvd.reducescatter(x, op=hvd.Sum, name=f"mx_rs_{tag}")
        total = (np.arange(2 * size * 2).reshape(2 * size, 2)
                 .astype(np.float64) * sum(r + 1 for r in range(size)))
        np.testing.assert_allclose(np.asarray(out, np.float64),
                                   total[rank * 2:(rank + 1) * 2],
                                   err_msg=f"reducescatter {tag}")
    if size > 1:
        # Fewer rows than ranks: the last rank's chunk is empty.
        y = np.ones((size - 1, 3), np.float32) * (rank + 1)
        out = hvd.reducescatter(y, op=hvd.Sum, name="mx_rs_empty")
        rows = 1 if rank < size - 1 else 0
        assert out.shape == (rows, 3), out.shape
        if rows:
            np.testing.assert_allclose(
                out, np.ones((1, 3)) * sum(r + 1 for r in range(size)))

    # -- grouped mismatch: shape desync inside a group must produce a
    # structured error on every rank, and the world must survive ---------
    shapes = [(4,), (5,) if rank == 0 else (6,)]
    try:
        hvd.grouped_allreduce(
            [np.ones(s, np.float32) for s in shapes],
            op=hvd.Sum, name="mx_gar_mismatch")
    except hvd.HorovodInternalError as e:
        assert "shape" in str(e).lower(), e
    else:
        raise AssertionError("expected HorovodInternalError (shape)")

    # dtype desync is likewise a structured error.
    dt = np.float32 if rank == 0 else np.float64
    try:
        hvd.allreduce(np.ones(4, dt), op=hvd.Sum, name="mx_dtype_mismatch")
    except hvd.HorovodInternalError as e:
        assert "type" in str(e).lower(), e
    else:
        raise AssertionError("expected HorovodInternalError (dtype)")

    # world still functional after both errors
    out = hvd.allreduce(np.ones(3, np.float32), op=hvd.Sum, name="mx_after")
    np.testing.assert_allclose(out, np.full(3, float(size)))


def battery_autotune(hvd, rank, size):
    """Autotuned (fusion threshold, cycle time) propagate from the
    coordinator to every rank via the ResponseList tuned_* fields
    (reference: Controller::SynchronizeParameters, controller.cc:39-53)."""
    from horovod_tpu.core import _global

    # warmup 1 sample x 2 steps + 3 scored samples x 2 steps, plus slack;
    # every allreduce is one counted cycle.
    for i in range(30):
        hvd.allreduce(np.ones(256, dtype=np.float32), op=hvd.Sum,
                      name=f"tune_{i % 3}")
    if rank == 0:
        assert _global.parameter_manager is not None
        assert _global.parameter_manager._done
        assert _global.controller.pending_tuned_params is None
    # The search may legitimately CONVERGE BACK to the default (the
    # initial setting is one of the scored samples), so assert liveness +
    # cross-rank consistency, not inequality; the deterministic
    # propagation check lives in test_controller.py.
    hvd.barrier()
    tuned = _global.controller.tensor_fusion_threshold
    assert (1 << 20) <= tuned <= (1 << 28), tuned
    gathered = hvd.allgather(np.array([[float(tuned)]]), name="tune_thr")
    assert np.all(np.asarray(gathered) == float(tuned)), \
        (rank, tuned, np.asarray(gathered))


def battery_algotune(hvd, rank, size):
    """ISSUE 18 acceptance (the negotiated half): the autotuner's
    algo x tree-threshold sweep proposes every candidate through
    ResponseList.tuned_algo / tuned_tree_threshold and pins the winner
    on EVERY rank's live TcpCollectives — selection inputs stay
    rank-symmetric end to end (the deadlock-freedom invariant)."""
    from horovod_tpu.core import _global

    # Window ladder at WARMUP=1, STEPS_PER_SAMPLE=1, BO_MAX_SAMPLES=1:
    # 1 warmup + 5 pipeline (4 candidates + pin) + 3 fused + 5 algo
    # + 1 BO ~= 15 counted cycles; 70 allreduces give generous slack.
    for i in range(70):
        hvd.allreduce(np.ones(256, dtype=np.float32), op=hvd.Sum,
                      name=f"algotune_{i % 3}")
    if rank == 0:
        pm = _global.parameter_manager
        assert pm is not None and pm._done
        assert pm._algo_candidates == []          # sweep ran to the end
        assert len(pm._algo_scores) == 4, pm._algo_scores
        assert _global.controller.pending_tuned_algo is None
    hvd.barrier()
    # The pinned winner reached every rank's dispatch layer identically
    # (tuned_algo is applied BEFORE dispatch on the broadcast cycle).
    from horovod_tpu.common.topology import ALGO_NAMES, algo_index
    colls = _global.tcp_collectives
    assert colls, "TCP data plane expected (HOROVOD_SHM_OPERATIONS=0)"
    algo, thr = colls[0].algo, colls[0].tree_threshold
    assert algo in ALGO_NAMES, algo
    assert all((c.algo, c.tree_threshold) == (algo, thr) for c in colls)
    gathered = np.asarray(hvd.allgather(
        np.array([[float(algo_index(algo)), float(thr)]]),
        name="algotune_verdict"))
    assert np.all(gathered == gathered[0]), (rank, algo, thr, gathered)


def battery_stall(hvd, rank, size):
    """Stall inspector end-to-end (reference: test/integration/
    test_stall.py + stall_inspector.cc): rank 0 submits a collective that
    rank 1 never joins; past HOROVOD_STALL_SHUTDOWN_TIME_SECONDS the
    coordinator aborts the job with a structured error instead of letting
    the world hang forever."""
    import time as _time

    if rank == 0:
        try:
            hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum,
                          name="lonely")
        except hvd.HorovodInternalError:
            return
        raise AssertionError("stalled collective completed?!")
    # Other ranks: never submit; the shutdown must arrive on its own.
    deadline = _time.time() + 20
    from horovod_tpu.core import _global
    while _time.time() < deadline:
        if not _global.initialized or _global.shutdown_requested:
            return
        _time.sleep(0.2)
    raise AssertionError("stall shutdown never propagated to idle rank")


def battery_flow(hvd, rank, size):
    """ISSUE 12 acceptance (the runtime half): the seeded rank-gated
    collective from tests/fixtures/lint/flow/divergent_battery.py — the
    very file hvdflow flags with HVD601, naming the tainted branch and
    the two arms' fingerprint streams — is caught by strict-mode
    fingerprinting as a structured divergence ERROR on EVERY rank,
    naming the divergent op, within one negotiation cycle."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "fixtures", "lint", "flow"))
    import divergent_battery

    t = np.ones(64, np.float32)
    for i in range(3):
        out = hvd.allreduce(t, op=hvd.Sum, name=f"flow_warm{i}")
        np.testing.assert_allclose(np.asarray(out), t * size)
    seed = int(os.environ.get("HOROVOD_FLOW_SEED_RANK", "2"))
    try:
        divergent_battery.rank_gated_step(hvd, t, rank, seed)
    except Exception as exc:
        msg = str(exc)
        assert "fingerprint divergence" in msg.lower(), msg
        assert "flow_extra" in msg or "flow_step" in msg, msg
        print(f"FLOW_DIVERGENCE_CAUGHT rank={rank} {msg[:200]}",
              flush=True)
        return
    raise AssertionError("rank-gated collective completed without a "
                         "fingerprint divergence ERROR")


def battery_shard(hvd, rank, size):
    """ISSUE 17 acceptance (the runtime half): the seeded
    spec-divergent collective from tests/fixtures/lint/shard/
    divergent_spec_battery.py — the very file hvdshard flags with
    HVD803 — is caught by strict-mode op×name×dtype×dims×spec
    fingerprinting as a structured divergence ERROR on EVERY rank,
    naming the first spec-divergent op and both ranks' spec tokens."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "fixtures", "lint", "shard"))
    import divergent_spec_battery

    t = np.ones(64, np.float32)
    # Warm-up: a rank-INVARIANT spec folds identically everywhere —
    # annotated collectives must stay fingerprint-green.
    for i in range(3):
        out = hvd.allreduce(t, op=hvd.Sum, name=f"shard_warm{i}",
                            spec="(dp,*)")
        np.testing.assert_allclose(np.asarray(out), t * size)
    seed = int(os.environ.get("HOROVOD_SHARD_SEED_RANK", "1"))
    try:
        for _ in range(4):
            divergent_spec_battery.spec_gated_step(hvd, t, rank, seed)
    except Exception as exc:
        msg = str(exc)
        assert "fingerprint divergence" in msg.lower(), msg
        assert "shard_step" in msg, msg
        assert "spec=(dp,*)" in msg or "spec=(tp,*)" in msg, msg
        assert "--shard" in msg, msg          # the HVD803 cross-hint
        print(f"SHARD_DIVERGENCE_CAUGHT rank={rank} {msg[:240]}",
              flush=True)
        return
    raise AssertionError("spec-divergent collective completed without "
                         "a fingerprint divergence ERROR")


def battery_shard_compat(hvd, rank, size):
    """ISSUE 17 mixed-world leg: rank 1 pins wire proto 2 (pre-sharding
    schema), so every mesh negotiates FEATURE_SHARDING off — sp_spec is
    blanked at the wire and the fingerprint folds the 5-column identity
    on EVERY rank symmetrically.  The same spec-divergent step that
    kills the native-proto world must stay fingerprint-green here, with
    correct numerics."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "fixtures", "lint", "shard"))
    import divergent_spec_battery

    from horovod_tpu import core as _core
    from horovod_tpu.common import wire as _wire
    from horovod_tpu.runner.network import PeerMesh as _PeerMesh

    meshes = [r for r in _core.global_state().resources
              if isinstance(r, _PeerMesh)]
    assert meshes, "no TCP meshes formed"
    for m in meshes:
        assert m.negotiated_proto == 2, m.negotiated_proto
        assert not (m.negotiated_features & _wire.FEATURE_SHARDING), \
            m.negotiated_features

    t = np.ones(64, np.float32) * (rank + 1)
    want = np.ones(64, np.float32) * (size + 1) / 2   # default op: average
    for i in range(4):
        out = divergent_spec_battery.spec_gated_step(hvd, t, rank, 1)
        np.testing.assert_allclose(np.asarray(out), want)
    print(f"SHARD_COMPAT_GREEN rank={rank} proto=2", flush=True)


def battery_errors(hvd, rank, size):
    # Shape mismatch must raise a structured error on every rank, not hang.
    shape = (4,) if rank == 0 else (5,)
    try:
        hvd.allreduce(np.ones(shape, dtype=np.float32), op=hvd.Sum,
                      name="mismatch")
    except hvd.HorovodInternalError as e:
        assert "shape" in str(e).lower()
    else:
        raise AssertionError("expected HorovodInternalError")
    # The world must still be usable afterwards.
    out = hvd.allreduce(np.ones(4, dtype=np.float32), op=hvd.Sum,
                        name="after_mismatch")
    np.testing.assert_allclose(out, np.full(4, float(size)))


def battery_join(hvd, rank, size):
    # Uneven steps: every rank does `rank+1` allreduces, then joins.
    total = None
    for step in range(rank + 1):
        out = hvd.allreduce(np.ones(4, dtype=np.float32), op=hvd.Sum,
                            name=f"uneven_{step}")
        total = out
    joined_last = hvd.join()
    # Last step only ranks >= step participated... every completed allreduce
    # sums over all ranks still present; with zero stand-ins from joined
    # ranks the result is the count of non-joined participants — but rank
    # ordering of join is asynchronous, so only check the join result and
    # that the world survives.
    assert 0 <= joined_last < size
    out = hvd.allreduce(np.ones(2, dtype=np.float32), op=hvd.Sum,
                        name="after_join")
    np.testing.assert_allclose(out, np.full(2, float(size)))

    # Cached allgather + join: warm the cache, then have rank size-1
    # join while the others resubmit the cached name.  The joined rank
    # must NOT assert the cached allgather bit (it cannot fabricate a
    # shaped block) — it invalidates it, peers renegotiate, and
    # ConstructResponse surfaces the structured join-unsupported error
    # on the submitting ranks instead of a hang or a phantom execution.
    for _ in range(2):   # insert + steady-state hit
        hvd.allgather(np.full((rank + 1, 2), rank, np.float32),
                      name="join_ag")
    if rank == size - 1:
        hvd.join()
    else:
        try:
            hvd.allgather(np.full((rank + 1, 2), rank, np.float32),
                          name="join_ag")
            raise SystemExit("cached allgather with a joined rank "
                             "must error")
        except hvd.HorovodInternalError as e:
            assert "join" in str(e).lower(), e
        hvd.join()
    out = hvd.allreduce(np.ones(2, dtype=np.float32), op=hvd.Sum,
                        name="after_join2")
    np.testing.assert_allclose(out, np.full(2, float(size)))


def battery_adasum_np(hvd, rank, size):
    """Numpy-only Adasum VHDD semantics (no torch/TF imports — the
    framework delta-optimizer halves run at size 2 only; spinning up
    torch AND tensorflow in 4 more workers adds ~1 min of pure import
    serialization on 1-CPU CI for no extra coverage)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from horovod_tpu.ops.adasum import adasum_reference
    vecs = [np.linspace(0.1 * (r + 1), 1.0 * (r + 1), 16,
                        dtype=np.float64) for r in range(size)]
    out = hvd.allreduce(vecs[rank], op=hvd.Adasum, name="adasum0")
    expected = adasum_reference(vecs)
    np.testing.assert_allclose(out, expected, rtol=1e-10)


def battery_adasum(hvd, rank, size):
    battery_adasum_np(hvd, rank, size)
    from horovod_tpu.ops.adasum import adasum_reference

    # -- torch Adasum delta-optimizer (VERDICT r2 item 3; reference:
    #    torch/optimizer.py:335-503): one step must equal
    #    p0 + adasum([-lr * grad_r for each rank]).
    import torch
    import horovod_tpu.torch as hvt

    lr = 0.2
    torch.manual_seed(5)
    model = torch.nn.Linear(6, 3)
    hvt.broadcast_parameters(model.state_dict(), root_rank=0)
    p0 = {k: v.detach().clone() for k, v in model.named_parameters()}

    g = torch.Generator().manual_seed(17)
    X = torch.randn(4 * size, 6, generator=g)
    Y = torch.randn(4 * size, 3, generator=g)
    xs = X[rank * 4:(rank + 1) * 4]
    ys = Y[rank * 4:(rank + 1) * 4]

    opt = hvt.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=lr),
        named_parameters=model.named_parameters(), op=hvt.Adasum)
    loss = ((model(xs) - ys) ** 2).mean()
    loss.backward()
    opt.step()

    # Serial expectation: per-rank grads at p0 → deltas → adasum combine.
    ref = torch.nn.Linear(6, 3)
    ref.load_state_dict({k: v for k, v in p0.items()}, strict=False)
    per_rank_grads = {k: [] for k in p0}
    for r in range(size):
        ref.zero_grad()
        rl = ((ref(X[r * 4:(r + 1) * 4]) - Y[r * 4:(r + 1) * 4]) ** 2).mean()
        rl.backward()
        for k, v in ref.named_parameters():
            per_rank_grads[k].append(v.grad.detach().numpy().copy())
    for k, p in model.named_parameters():
        deltas = [(-lr * gr).reshape(-1).astype(np.float64)
                  for gr in per_rank_grads[k]]
        want = p0[k].numpy().reshape(-1) + adasum_reference(deltas)
        np.testing.assert_allclose(p.detach().numpy().reshape(-1), want,
                                   rtol=1e-5, atol=1e-7,
                                   err_msg=f"torch adasum param {k}")

    # backward_passes_per_step accumulation path runs end-to-end (fresh
    # model: hooks from the first optimizer stay registered on `model`).
    torch.manual_seed(6)
    model2 = torch.nn.Linear(6, 3)
    hvt.broadcast_parameters(model2.state_dict(), root_rank=0)
    opt2 = hvt.DistributedOptimizer(
        torch.optim.SGD(model2.parameters(), lr=0.05),
        named_parameters=model2.named_parameters(), op=hvt.Adasum,
        backward_passes_per_step=2)
    for _ in range(2):
        loss = ((model2(xs) - ys) ** 2).mean()
        loss.backward()
    opt2.step()
    opt2.zero_grad()

    # -- TF Adasum delta-optimizer (reference: tensorflow/__init__.py:
    #    504-598): same one-step semantic check.
    import tensorflow as tf
    import horovod_tpu.tensorflow as htf

    w0 = np.linspace(0.5, 1.5, 4).astype(np.float32)
    x_r = np.linspace(1.0, 2.0, 4).astype(np.float32) * (rank + 1)
    y_r = np.linspace(0.0, 1.0, 4).astype(np.float32) * (rank + 1)
    w = tf.Variable(w0)
    topt = htf.DistributedOptimizer(tf.keras.optimizers.SGD(lr),
                                    op=htf.Adasum)
    with tf.GradientTape() as tape:
        tf_loss = tf.reduce_mean((w * x_r - y_r) ** 2)
    (gw,) = tape.gradient(tf_loss, [w])
    topt.apply_gradients([(gw, w)])

    deltas = []
    for r in range(size):
        xr = np.linspace(1.0, 2.0, 4).astype(np.float64) * (r + 1)
        yr = np.linspace(0.0, 1.0, 4).astype(np.float64) * (r + 1)
        grad_r = 2.0 * xr * (w0.astype(np.float64) * xr - yr) / 4.0
        deltas.append(-lr * grad_r)
    want = w0.astype(np.float64) + adasum_reference(deltas)
    np.testing.assert_allclose(w.numpy().astype(np.float64), want,
                               rtol=1e-5, atol=1e-6,
                               err_msg="tf adasum variable")


def battery_torch(hvd, rank, size):
    """DistributedOptimizer end-to-end: sharded-batch DP training matches a
    single-process run on the full batch (the reference's core semantic,
    torch/optimizer.py)."""
    import torch
    import horovod_tpu.torch as hvt

    def make_model():
        torch.manual_seed(7)
        return torch.nn.Sequential(
            torch.nn.Linear(8, 16), torch.nn.Tanh(), torch.nn.Linear(16, 4))

    g = torch.Generator().manual_seed(42)
    X = torch.randn(4 * size, 8, generator=g)
    Y = torch.randn(4 * size, 4, generator=g)
    xs, ys = X[rank * 4:(rank + 1) * 4], Y[rank * 4:(rank + 1) * 4]

    def train(model, opt, inputs, targets, steps=3):
        for _ in range(steps):
            opt.zero_grad()
            loss = ((model(inputs) - targets) ** 2).mean()
            loss.backward()
            opt.step()

    # Distributed: per-rank shard + averaged gradients.
    model = make_model()
    opt = hvt.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=0.1),
        named_parameters=model.named_parameters())
    hvt.broadcast_parameters(model.state_dict(), root_rank=0)
    train(model, opt, xs, ys)

    # Serial baseline on the full batch (equal shards → full-batch grad ==
    # average of shard grads).
    serial = make_model()
    train(serial, torch.optim.SGD(serial.parameters(), lr=0.1), X, Y)

    for (name, p), (_, q) in zip(model.named_parameters(),
                                 serial.named_parameters()):
        np.testing.assert_allclose(p.detach().numpy(), q.detach().numpy(),
                                   rtol=1e-4, atol=1e-6,
                                   err_msg=f"param {name} diverged")

    # Replicas must agree bit-for-bit with each other.
    for name, p in model.named_parameters():
        flat = p.detach().flatten().unsqueeze(0)
        gathered = hvt.allgather(flat, name=f"agree.{name}")
        for r in range(size):
            np.testing.assert_array_equal(gathered[r].numpy(),
                                          flat[0].numpy())

    # -- torch reducescatter: summed dim-0 slice --------------------------
    t = torch.arange(4 * size * 2, dtype=torch.float32).reshape(4 * size, 2) \
        * (rank + 1)
    out = hvt.reducescatter(t, op=hvt.Sum, name="t_rs")
    full = torch.arange(4 * size * 2, dtype=torch.float32) \
        .reshape(4 * size, 2) * sum(r + 1 for r in range(size))
    np.testing.assert_allclose(out.numpy(),
                               full[rank * 4:(rank + 1) * 4].numpy(),
                               rtol=1e-6)

    # Grouped + fp16-compressed + backward_passes_per_step variant runs.
    model2 = make_model()
    opt2 = hvt.DistributedOptimizer(
        torch.optim.SGD(model2.parameters(), lr=0.05),
        named_parameters=model2.named_parameters(),
        compression=hvt.Compression.fp16, backward_passes_per_step=2,
        groups=2)
    hvt.broadcast_parameters(model2.state_dict(), root_rank=0)
    for _ in range(2):  # 2 backward passes per step
        loss = ((model2(xs) - ys) ** 2).mean()
        loss.backward()
    opt2.step()
    opt2.zero_grad()

    # Optimizer-state broadcast: momentum buffers diverge (per-rank data),
    # then broadcast must reconcile them to rank 0's.
    m3 = make_model()
    opt3 = torch.optim.SGD(m3.parameters(), lr=0.1, momentum=0.9)
    loss = ((m3(xs) - ys) ** 2).mean()
    loss.backward()
    opt3.step()
    hvt.broadcast_optimizer_state(opt3, root_rank=0)
    for sid, s in sorted(opt3.state_dict()["state"].items()):
        for k, v in sorted(s.items()):
            if isinstance(v, torch.Tensor):
                flat = v.detach().flatten().unsqueeze(0)
                gathered = hvt.allgather(flat, name=f"opt3.{sid}.{k}")
                for r in range(size):
                    np.testing.assert_array_equal(gathered[r].numpy(),
                                                  gathered[0].numpy())


def battery_sparse(hvd, rank, size):
    """Gather-based sparse gradient reduction (reference: torch sparse
    path): embedding-style sparse grads with overlapping indices."""
    import torch
    import horovod_tpu.torch as hvt

    # Overlapping rows across ranks: row `rank` and row 0.
    idx = torch.tensor([[0, rank + 1]])
    val = torch.ones(2, 4) * (rank + 1)
    sp = torch.sparse_coo_tensor(idx, val, size=(size + 2, 4))
    out = hvt.sparse_allreduce(sp, name="sp0", op=hvt.Sum)
    dense = out.to_dense().numpy()
    np.testing.assert_allclose(dense[0], np.full(4, sum(
        r + 1 for r in range(size))))
    for r in range(size):
        np.testing.assert_allclose(dense[r + 1], np.full(4, float(r + 1)))

    # End-to-end: DistributedOptimizer with a sparse-grad embedding.
    torch.manual_seed(3)
    emb = torch.nn.Embedding(8, 4, sparse=True)
    opt = hvt.DistributedOptimizer(
        torch.optim.SGD(emb.parameters(), lr=0.1),
        named_parameters=emb.named_parameters())
    hvt.broadcast_parameters(emb.state_dict(), root_rank=0)
    before = emb.weight.detach().clone()
    tokens = torch.tensor([rank, rank])
    loss = emb(tokens).sum()
    opt.zero_grad()
    loss.backward()
    opt.step()
    after = emb.weight.detach()
    # Every rank must apply the identical averaged sparse update.
    gathered = hvd.allgather(after.numpy().reshape(1, -1), name="sp_w")
    for r in range(size):
        np.testing.assert_allclose(np.asarray(gathered)[r],
                                   after.numpy().reshape(-1), rtol=1e-6)
    assert not torch.allclose(before[rank], after[rank])


def battery_tensorflow(hvd, rank, size):
    """TF binding semantics across ranks (reference: test/parallel/
    test_tensorflow.py core cases): allreduce, broadcast_variables, and
    DistributedGradientTape gradient averaging."""
    import tensorflow as tf
    import horovod_tpu.tensorflow as htf

    x = tf.constant(np.arange(8, dtype=np.float32) * (rank + 1))
    out = htf.allreduce(x, average=False, name="tf_ar")
    expected = np.arange(8, dtype=np.float32) * sum(
        r + 1 for r in range(size))
    np.testing.assert_allclose(out.numpy(), expected, rtol=1e-6)

    v = tf.Variable(np.full(4, float(rank), np.float32))
    htf.broadcast_variables([v], root_rank=0)
    np.testing.assert_allclose(v.numpy(), np.zeros(4))

    w = tf.Variable([float(rank + 1)])
    with tf.GradientTape() as tape:
        loss = w * w
    dtape = htf.DistributedGradientTape(tape)
    (g,) = dtape.gradient(loss, [w])
    expected_grad = np.mean([2.0 * (r + 1) for r in range(size)])
    np.testing.assert_allclose(g.numpy(), [expected_grad], rtol=1e-6)

    gathered = htf.allgather(tf.constant([float(rank)]), name="tf_ag")
    np.testing.assert_allclose(gathered.numpy(),
                               np.arange(size, dtype=np.float32))

    # reducescatter: summed dim-0 slice + gradient round-trip.
    t = tf.constant(np.arange(2 * size * 3, dtype=np.float32)
                    .reshape(2 * size, 3) * (rank + 1))
    with tf.GradientTape() as tape:
        tape.watch(t)
        rs = htf.reducescatter(t, op=htf.Sum, name="tf_rs")
        loss = tf.reduce_sum(rs)
    full = np.arange(2 * size * 3, dtype=np.float32).reshape(2 * size, 3) \
        * sum(r + 1 for r in range(size))
    np.testing.assert_allclose(rs.numpy(),
                               full[rank * 2:(rank + 1) * 2], rtol=1e-6)
    g = tape.gradient(loss, t)
    np.testing.assert_allclose(g.numpy(), np.ones((2 * size, 3)),
                               rtol=1e-6)


def battery_tf_function(hvd, rank, size):
    """Graph-mode TF binding (VERDICT r1 item 4): collectives must survive
    tf.function tracing, gradients must be registered, model.fit with
    DistributedOptimizer must match serial, backward_passes_per_step must
    aggregate, sync-BN must use global moments, and Keras elastic state
    must commit/restore/sync."""
    import tensorflow as tf
    import horovod_tpu.tensorflow as htf

    # -- collective inside tf.function (compiled twice = steady state) ---
    @tf.function
    def compiled_ar(x):
        return htf.allreduce(x, op=htf.Sum, name="tff_ar")

    for _ in range(2):
        out = compiled_ar(tf.constant([1.0, 2.0]) * (rank + 1))
    np.testing.assert_allclose(
        out.numpy(), np.array([1.0, 2.0]) * sum(r + 1 for r in range(size)),
        rtol=1e-6)

    # -- compiled model.fit parity with serial ---------------------------
    def make_model():
        tf.keras.utils.set_random_seed(11)
        return tf.keras.Sequential([
            tf.keras.layers.Input(shape=(6,)),
            tf.keras.layers.Dense(8, activation="tanh"),
            tf.keras.layers.Dense(3)])

    rng = np.random.default_rng(5)
    X = rng.standard_normal((8 * size, 6)).astype(np.float32)
    Y = rng.standard_normal((8 * size, 3)).astype(np.float32)
    xs, ys = X[rank * 8:(rank + 1) * 8], Y[rank * 8:(rank + 1) * 8]

    model = make_model()
    opt = htf.DistributedOptimizer(tf.keras.optimizers.SGD(0.1))
    model.compile(optimizer=opt, loss="mse")
    model.fit(xs, ys, batch_size=8, epochs=3, shuffle=False, verbose=0,
              callbacks=[htf.BroadcastGlobalVariablesCallback(0)])

    serial = make_model()
    serial.compile(optimizer=tf.keras.optimizers.SGD(0.1), loss="mse")
    serial.fit(X, Y, batch_size=8 * size, epochs=3, shuffle=False,
               verbose=0)
    for p, q in zip(model.get_weights(), serial.get_weights()):
        np.testing.assert_allclose(p, q, rtol=1e-4, atol=1e-5)

    # -- backward_passes_per_step aggregation (eager apply path) ---------
    v = tf.Variable([10.0])
    agg_opt = htf.DistributedOptimizer(
        tf.keras.optimizers.SGD(1.0), backward_passes_per_step=2)
    agg_opt.apply_gradients([(tf.constant([1.0]), v)])
    np.testing.assert_allclose(v.numpy(), [10.0])   # accumulated only
    agg_opt.apply_gradients([(tf.constant([3.0]), v)])
    # applied: lr * avg-of-2-passes allreduced average = (1+3)/2 = 2
    np.testing.assert_allclose(v.numpy(), [8.0], rtol=1e-6)

    # -- sparse IndexedSlices allreduce ----------------------------------
    sp = tf.IndexedSlices(
        values=tf.constant([[1.0, 2.0]]) * (rank + 1),
        indices=tf.constant([rank], dtype=tf.int64),
        dense_shape=tf.constant([size + 1, 2], dtype=tf.int64))
    red = htf.allreduce(sp, op=htf.Average, name="tff_sparse")
    dense = tf.math.unsorted_segment_sum(
        red.values, red.indices, size + 1).numpy()
    for r in range(size):
        np.testing.assert_allclose(
            dense[r], np.array([1.0, 2.0]) * (r + 1) / size, rtol=1e-6)

    # -- SyncBatchNormalization: global moments --------------------------
    g = np.random.default_rng(3)
    full = g.standard_normal((4 * size, 5)).astype(np.float32)
    local = full[rank * 4:(rank + 1) * 4]
    sbn = htf.SyncBatchNormalization(momentum=0.5, epsilon=1e-3)
    out = sbn(tf.constant(local), training=True).numpy()
    mean, var = full.mean(axis=0), full.var(axis=0)
    expected = (local - mean) / np.sqrt(var + 1e-3)
    np.testing.assert_allclose(out, expected, rtol=1e-3, atol=1e-4)

    # -- Keras elastic state ---------------------------------------------
    state = htf.TensorFlowKerasState(model, opt, epoch=0)
    state.save()
    w0 = [w.copy() for w in model.get_weights()]
    model.set_weights([w * 0 for w in w0])
    state.restore()
    for a, b in zip(model.get_weights(), w0):
        np.testing.assert_array_equal(a, b)
    # Divergent weights re-sync to rank 0's.
    model.set_weights([w + rank for w in w0])
    state.sync()
    for a, b in zip(model.get_weights(), w0):
        np.testing.assert_allclose(a, b)


def battery_syncbn(hvd, rank, size):
    """SyncBatchNorm forward/backward == single-process BN on the full
    batch (reference: torch/sync_batch_norm.py semantics)."""
    import torch
    import horovod_tpu.torch as hvt

    g = torch.Generator().manual_seed(3)
    X = torch.randn(2 * size, 5, 4, 4, generator=g)
    xs = X[rank * 2:(rank + 1) * 2].clone().requires_grad_(True)

    bn = hvt.SyncBatchNorm(5)
    bn.train()
    out = bn(xs)
    loss = (out ** 2).mean() * size  # scale: serial mean is over size× rows
    loss.backward()

    ref_x = X.clone().requires_grad_(True)
    ref_bn = torch.nn.BatchNorm2d(5)
    ref_bn.train()
    ref_out = ref_bn(ref_x)
    ref_loss = (ref_out ** 2).mean()
    ref_loss.backward()

    np.testing.assert_allclose(
        out.detach().numpy(),
        ref_out[rank * 2:(rank + 1) * 2].detach().numpy(),
        rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        xs.grad.numpy(), ref_x.grad[rank * 2:(rank + 1) * 2].numpy(),
        rtol=1e-3, atol=1e-6)
    np.testing.assert_allclose(bn.running_mean.numpy(),
                               ref_bn.running_mean.numpy(),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(bn.running_var.numpy(),
                               ref_bn.running_var.numpy(),
                               rtol=1e-3, atol=1e-5)


def battery_xla(hvd, rank, size):
    """XLA/ICI data plane (VERDICT r1 item 3): the eager core's op chain
    must select the XlaBackend when the JAX world spans the ranks, execute
    device collectives, and fall back to TCP for unsupported ops
    (reference: operations.cc:143-252 Enabled()-priority)."""
    import jax

    assert jax.process_count() == size, jax.process_count()
    from horovod_tpu.core import _global
    names = [b.name for b in _global.op_manager.backends]
    assert names[0] == "xla", names

    x = np.arange(32, dtype=np.float32) + rank
    out = hvd.allreduce(x, op=hvd.Sum, name="xla_ar")
    np.testing.assert_allclose(
        out, np.arange(32, dtype=np.float32) * size + sum(range(size)),
        rtol=1e-6)
    # The XLA backend must actually have executed (compiled-program cache
    # is the lazy-communicator analogue, nccl_operations.cc:61-94).
    xla_backend = _global.op_manager.backends[0]
    assert xla_backend.comm._cache, "xla backend never executed"

    # fp16 rides the widened fp32 accumulation path.
    v = np.ones(16, dtype=np.float16) * (rank + 1)
    out = hvd.allreduce(v, op=hvd.Sum, name="xla_fp16")
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.full(16, sum(range(1, size + 1))))

    # Average + prescale go through the same fused program.
    out = hvd.allreduce(x, op=hvd.Average, name="xla_avg")
    np.testing.assert_allclose(
        out, (np.arange(32, dtype=np.float32) * size
              + sum(range(size))) / size, rtol=1e-6)

    # Broadcast on-device. float64 broadcast falls through to TCP unless
    # x64 is enabled — use float32 to stay on the device plane.
    b = np.arange(8, dtype=np.float32) * (rank + 1)
    out = hvd.broadcast(b, root_rank=1, name="xla_bc")
    np.testing.assert_array_equal(out, np.arange(8, dtype=np.float32) * 2)

    # Ragged allgather rides the device plane (VERDICT r2 item 2: the
    # NCCLAllgather analogue, nccl_operations.cc:434-559).
    gathered = hvd.allgather(np.full((rank + 1, 2), rank, np.float32),
                             name="xla_ag")
    expected = np.concatenate([np.full((r + 1, 2), r, np.float32)
                               for r in range(size)])
    np.testing.assert_array_equal(gathered, expected)
    assert any(k[0] == "allgather" for k in xla_backend.comm._cache), \
        "allgather did not ride the XLA plane"

    # Fused allgather on the device plane: a multi-entry response moves
    # every entry's packed bytes in ONE padded all-gather (direct
    # lockstep call, as in the shm/hierarchical batteries).
    from horovod_tpu.common.dtypes import from_any
    from horovod_tpu.common.message import Response, ResponseType
    from horovod_tpu.common.tensor_queue import TensorTableEntry
    fents = [TensorTableEntry(
        tensor_name=f"xla_fag{i}",
        tensor=np.full((rank + 1, i + 1), 10.0 * rank + i, np.float32))
        for i in range(2)]
    fsizes = []
    for i in range(2):
        fsizes.extend(r + 1 for r in range(size))
    fresp = Response(response_type=ResponseType.ALLGATHER,
                     tensor_names=[e.tensor_name for e in fents],
                     tensor_type=from_any(np.dtype(np.float32)),
                     tensor_sizes=fsizes)
    fst = xla_backend.allgather(fresp, fents)
    assert fst.ok_p(), fst
    for i, e in enumerate(fents):
        expected = np.concatenate([np.full((r + 1, i + 1), 10.0 * r + i,
                                           np.float32)
                                   for r in range(size)])
        np.testing.assert_array_equal(e.output, expected)

    # Ragged alltoall on-device (NCCLAlltoall analogue).
    splits = [rank + 1] * size
    v = np.arange((rank + 1) * size, dtype=np.float32) + 1000 * rank
    out, recv = hvd.alltoall(v, splits=splits, name="xla_a2a")
    expected = np.concatenate(
        [np.arange(rank * (r + 1), (rank + 1) * (r + 1), dtype=np.float32)
         + 1000 * r for r in range(size)])
    np.testing.assert_array_equal(out, expected)
    np.testing.assert_array_equal(np.asarray(recv),
                                  np.array([r + 1 for r in range(size)]))
    assert any(k[0] == "alltoall" for k in xla_backend.comm._cache), \
        "alltoall did not ride the XLA plane"

    # Even reducescatter on-device (true reduce-scatter, half the bytes of
    # allreduce+slice); ragged dim-0 falls through to TCP.
    x = np.arange(4 * size * 3, dtype=np.float32).reshape(4 * size, 3) \
        * (rank + 1)
    out = hvd.reducescatter(x, op=hvd.Sum, name="xla_rs")
    full = np.arange(4 * size * 3, dtype=np.float32).reshape(4 * size, 3) \
        * sum(r + 1 for r in range(size))
    np.testing.assert_allclose(out, full[rank * 4:(rank + 1) * 4],
                               rtol=1e-6)
    assert any(k[0] == "reducescatter" for k in xla_backend.comm._cache), \
        "reducescatter did not ride the XLA plane"

    ragged = np.ones((size + 1, 2), dtype=np.float32) * (rank + 1)
    out = hvd.reducescatter(ragged, op=hvd.Sum, name="xla_rs_ragged")
    rows = (size + 1) // size + (1 if rank < (size + 1) % size else 0)
    np.testing.assert_allclose(
        out, np.ones((rows, 2), np.float32) * sum(
            r + 1 for r in range(size)), rtol=1e-6)

    # Steady-state cached cycles stay on the device plane.
    for _ in range(5):
        out = hvd.allreduce(np.ones(4, dtype=np.float32), op=hvd.Sum,
                            name="xla_steady")
        np.testing.assert_allclose(out, np.full(4, float(size)))



def battery_mxnet(hvd, rank, size):
    """MXNet binding semantics against the stub module (reference:
    test/parallel/test_mxnet1.py / test_mxnet2.py patterns)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import mxnet_stub
    mx = mxnet_stub.install()
    import horovod_tpu.mxnet as hmx

    # -- average allreduce (out-of-place NDArray) -------------------------
    x = mx.nd.array(np.arange(8, dtype=np.float32) + rank)
    out = hmx.allreduce(x, average=True, name="mx_avg")
    np.testing.assert_allclose(
        out.asnumpy(), np.arange(8, dtype=np.float32) + (size - 1) / 2)

    # -- in-place sum with prescale --------------------------------------
    y = mx.nd.array(np.ones(4, dtype=np.float32) * (rank + 1))
    hmx.allreduce_(y, average=False, name="mx_sum", prescale_factor=0.5)
    np.testing.assert_allclose(
        y.asnumpy(), np.full(4, 0.5 * sum(range(1, size + 1))))

    # -- allgather (variable first dim) ----------------------------------
    g = mx.nd.array(np.full((rank + 1, 2), rank, dtype=np.float32))
    out = hmx.allgather(g, name="mx_ag")
    assert out.shape == (sum(r + 1 for r in range(size)), 2), out.shape

    # -- broadcast --------------------------------------------------------
    b = mx.nd.array(np.full(3, rank, dtype=np.float32))
    out = hmx.broadcast(b, root_rank=0, name="mx_bc")
    np.testing.assert_allclose(out.asnumpy(), np.zeros(3))

    # -- alltoall (equal splits) -----------------------------------------
    a = mx.nd.array(np.arange(size * 2, dtype=np.float32) + 100 * rank)
    out = hmx.alltoall(a, name="mx_a2a")
    exp = np.concatenate([np.arange(2, dtype=np.float32) + 2 * rank + 100 * r
                          for r in range(size)])
    np.testing.assert_allclose(out.asnumpy(), exp)

    # -- grouped in-place -------------------------------------------------
    gs = [mx.nd.array(np.full(4, rank + i, dtype=np.float32))
          for i in range(3)]
    hmx.grouped_allreduce_(gs, average=False, name="mx_gar")
    for i, t in enumerate(gs):
        np.testing.assert_allclose(
            t.asnumpy(), np.full(4, float(sum(r + i for r in range(size)))))

    # -- DistributedTrainer: weights agree and equal mean-gradient SGD ----
    params = [mx.gluon.Parameter(f"w{i}", np.ones(4, dtype=np.float32)
                                 * (i + 1)) for i in range(3)]
    for i, p in enumerate(params):
        p.list_grad()[0][:] = np.full(4, (rank + 1) * (i + 1),
                                      dtype=np.float32)
    trainer = hmx.DistributedTrainer(
        params, "sgd", optimizer_params={"learning_rate": 0.1})
    trainer.step(batch_size=1)
    for i, p in enumerate(params):
        mean = np.mean([(r + 1) * (i + 1) for r in range(size)])
        np.testing.assert_allclose(
            p.data().asnumpy(), np.ones(4) * (i + 1) - 0.1 * mean,
            rtol=1e-5)

    # -- num_groups grouped path -----------------------------------------
    params2 = [mx.gluon.Parameter(f"v{i}", np.zeros(2, dtype=np.float32))
               for i in range(4)]
    for i, p in enumerate(params2):
        p.list_grad()[0][:] = np.full(2, float(rank + i), dtype=np.float32)
    tr2 = hmx.DistributedTrainer(
        params2, "sgd", optimizer_params={"learning_rate": 1.0},
        prefix="g2", num_groups=2)
    tr2.step(batch_size=1)
    for i, p in enumerate(params2):
        mean = np.mean([r + i for r in range(size)])
        np.testing.assert_allclose(p.data().asnumpy(),
                                   np.full(2, -mean), rtol=1e-5)

    # -- DistributedOptimizer: sum-allreduce + rescale fold ---------------
    opt = hmx.DistributedOptimizer(
        mx.optimizer.SGD(learning_rate=0.5, rescale_grad=1.0))
    w = mx.nd.array(np.zeros(3, dtype=np.float32))
    gr = mx.nd.array(np.full(3, float(rank + 1), dtype=np.float32))
    opt.update(7, w, gr, None)
    exp_w = -0.5 * (1.0 / size) * sum(range(1, size + 1))
    np.testing.assert_allclose(w.asnumpy(), np.full(3, exp_w), rtol=1e-5)

    # -- broadcast_parameters --------------------------------------------
    pd = {f"p{i}": mx.gluon.Parameter(
        f"p{i}", np.full(2, float(rank * (i + 1)), dtype=np.float32))
        for i in range(2)}
    hmx.broadcast_parameters(pd, root_rank=0)
    for i in range(2):
        np.testing.assert_allclose(pd[f"p{i}"].data().asnumpy(),
                                   np.zeros(2))

    # -- deferred-init param: broadcast rides the post-init hook ----------
    dp = mx.gluon.Parameter("deferred")          # no data yet
    hmx.broadcast_parameters({"d": dp}, root_rank=0)
    dp._init_impl(np.full(3, float(rank + 1), dtype=np.float32))
    np.testing.assert_allclose(dp.data().asnumpy(), np.ones(3))



def battery_shm(hvd, rank, size):
    """Same-host shared-memory data plane (reference parity: Gloo shm
    transport / MPI shared-memory windows): the op chain must select the
    shm backend for allreduce on a same-host world, produce flat-path
    results, fall through to TCP above the region capacity, and keep the
    lockstep consistent across a mixed op stream."""
    from horovod_tpu.core import _global

    names = [b.name for b in _global.op_manager.backends]
    assert "shm" in names and names.index("shm") < names.index("tcp"), names
    shm = _global.op_manager.backends[names.index("shm")]
    assert shm.world.formed

    import ml_dtypes
    for dt, rtol in ((np.float32, 1e-6), (np.float64, 0),
                     (np.int64, 0), (ml_dtypes.bfloat16, 1e-2),
                     (np.float16, 1e-2)):
        v = (np.arange(1001) % 7 + rank + 1).astype(dt)
        out = hvd.allreduce(v, op=hvd.Sum, name=f"shm_{np.dtype(dt).name}")
        expected = sum((np.arange(1001) % 7 + r + 1).astype(np.float64)
                       for r in range(size))
        assert np.asarray(out).dtype == np.dtype(dt)
        np.testing.assert_allclose(np.asarray(out, np.float64), expected,
                                   rtol=rtol)
    # bool rides logical-or semantics like the TCP plane.
    out = hvd.allreduce(np.array([rank == 0, False]), op=hvd.Sum,
                        name="shm_bool")
    np.testing.assert_array_equal(np.asarray(out), [True, False])

    executed = shm.ops_executed
    assert executed >= 6, executed

    # Average + scales ride the same path.
    out = hvd.allreduce(np.ones(17, np.float32) * (rank + 1),
                        op=hvd.Average, name="shm_avg")
    np.testing.assert_allclose(out,
                               np.full(17, (size + 1) / 2), rtol=1e-6)

    # Grouped/fused multi-entry response through pack/unpack.
    xs = [np.full((3 + i,), rank + i, dtype=np.float32) for i in range(3)]
    outs = hvd.grouped_allreduce(xs, op=hvd.Sum, name="shm_gar")
    for i, o in enumerate(outs):
        np.testing.assert_allclose(
            o, np.full((3 + i,), sum(r + i for r in range(size))))

    # Above-capacity payload falls through to the TCP ring (capacity is
    # pinned to 1 MB by the battery env below).
    before = shm.ops_executed
    big = np.ones((1 << 20) // 2, dtype=np.float32) * (rank + 1)  # 2 MB
    out = hvd.allreduce(big, op=hvd.Sum, name="shm_big")
    np.testing.assert_allclose(out[:8],
                               np.full(8, sum(range(1, size + 1))))
    assert shm.ops_executed == before, "oversized op must ride TCP"

    # Broadcast rides shm (root writes once, peers read the region).
    before = shm.ops_executed
    root = size - 1
    v = np.arange(12, dtype=np.float64).reshape(3, 4) * (rank + 1)
    out = hvd.broadcast(v, root_rank=root, name="shm_bc")
    np.testing.assert_array_equal(
        out, np.arange(12, dtype=np.float64).reshape(3, 4) * (root + 1))
    assert shm.ops_executed == before + 1, "broadcast must ride shm"

    # Scalar broadcast keeps 0-d shape ON EVERY RANK (regression: numpy
    # ascontiguousarray promotes 0-d to 1-d, which broke TF's
    # BroadcastGlobalVariables on the optimizer iteration counter).
    s = hvd.broadcast(np.float32(7.5 * (rank + 1)), root_rank=0,
                      name="shm_bc_scalar")
    assert np.asarray(s).shape == (), np.asarray(s).shape
    assert float(np.asarray(s)) == 7.5
    assert shm.ops_executed == before + 2, "scalar bcast must ride shm"

    # Ragged allgather rides shm (per-rank blocks from owners' regions).
    g = hvd.allgather(np.full((rank + 1, 2), rank, np.float32),
                      name="shm_ag")
    expected = np.concatenate([np.full((r + 1, 2), r, np.float32)
                               for r in range(size)])
    np.testing.assert_array_equal(g, expected)
    assert shm.ops_executed == before + 3, "allgather must ride shm"

    # Fused allgather rides shm in ONE staging pass: the response packs
    # three tensors (entry-major per rank), yet ops_executed moves by 1.
    from horovod_tpu.common.dtypes import from_any
    from horovod_tpu.common.message import Response, ResponseType
    from horovod_tpu.common.tensor_queue import TensorTableEntry
    before = shm.ops_executed
    ents = [TensorTableEntry(
        tensor_name=f"shm_fag{i}",
        tensor=np.full((rank + 1, i + 1), 10.0 * rank + i, np.float32))
        for i in range(3)]
    fsizes = []
    for i in range(3):
        fsizes.extend(r + 1 for r in range(size))
    fresp = Response(response_type=ResponseType.ALLGATHER,
                     tensor_names=[e.tensor_name for e in ents],
                     tensor_type=from_any(np.dtype(np.float32)),
                     tensor_sizes=fsizes)
    assert shm.enabled(fresp, ents), "fused allgather must ride shm"
    st = shm.allgather(fresp, ents)
    assert st.ok_p(), st
    for i, e in enumerate(ents):
        expected = np.concatenate([np.full((r + 1, i + 1), 10.0 * r + i,
                                           np.float32)
                                   for r in range(size)])
        np.testing.assert_array_equal(e.output, expected)
    assert shm.ops_executed == before + 1, "fused allgather is ONE shm op"

    # Alltoall rides shm (uneven splits; receivers pull their slice from
    # each sender's region using the header split table).
    before = shm.ops_executed
    splits = [rank + 1] * size
    v = (np.arange((rank + 1) * size, dtype=np.float32) + 10 * rank)
    a2a, recv = hvd.alltoall(v, splits=splits, name="shm_a2a")
    expected = np.concatenate(
        [(np.arange(rank * (r + 1), (rank + 1) * (r + 1))
          + 10 * r).astype(np.float32) for r in range(size)])
    np.testing.assert_array_equal(a2a, expected)
    np.testing.assert_array_equal(np.asarray(recv),
                                  np.arange(1, size + 1))
    assert shm.ops_executed == before + 1, "alltoall must ride shm"

    # Reducescatter rides shm (uneven rows; last rank may get fewer).
    before = shm.ops_executed
    x = (np.arange((2 * size + 1) * 3, dtype=np.float32)
         .reshape(2 * size + 1, 3) * (rank + 1))
    out = hvd.reducescatter(x, op=hvd.Sum, name="shm_rs")
    total = (np.arange((2 * size + 1) * 3, dtype=np.float64)
             .reshape(2 * size + 1, 3) * sum(r + 1 for r in range(size)))
    base, rem = divmod(2 * size + 1, size)
    starts = [r * base + min(r, rem) for r in range(size + 1)]
    np.testing.assert_allclose(np.asarray(out, np.float64),
                               total[starts[rank]:starts[rank + 1]],
                               rtol=1e-6)
    assert shm.ops_executed == before + 1, "reducescatter must ride shm"

    # Oversized alltoall (2 MB > the 1 MB battery capacity): every rank
    # delegates to the TCP exchange mid-protocol via the header flag.
    rows_per_dst = (2 << 20) // 4 // size + 1   # ~2 MB total buffer
    v = np.arange(rows_per_dst * size, dtype=np.float32) + 1000 * rank
    a2a, recv = hvd.alltoall(v, splits=[rows_per_dst] * size,
                             name="shm_a2a_big")
    expected = np.concatenate(
        [np.arange(rank * rows_per_dst, (rank + 1) * rows_per_dst,
                   dtype=np.float32) + 1000 * r for r in range(size)])
    np.testing.assert_array_equal(a2a, expected)
    assert shm.ops_executed == before + 1, "oversized a2a must delegate"

    for i in range(5):
        out = hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum,
                            name="shm_steady")
        np.testing.assert_allclose(out, np.full(4, float(size)))


def battery_hierarchical(hvd, rank, size):
    """Two-level eager allreduce/allgather (VERDICT r3 item 3; reference:
    NCCLHierarchicalAllreduce, nccl_operations.cc:187-398, and
    MPIHierarchicalAllgather): with HOROVOD_HIERARCHICAL_* set the op
    chain must select the hierarchical backend, produce results equal to
    the flat path, and actually execute the two-leg schedule (per-leg
    byte counters prove the path taken — the cross leg must carry only
    1/local_size of the payload)."""
    from horovod_tpu.core import _global

    names = [b.name for b in _global.op_manager.backends]
    assert "tcp-hierarchical" in names, names
    assert names.index("tcp-hierarchical") < names.index("tcp"), names
    hier = _global.op_manager.backends[names.index("tcp-hierarchical")]
    lsize = hvd.local_size()
    if os.environ.get("HOROVOD_SHM_OPERATIONS") == "0":
        assert hier.shm_local is None   # TCP local legs under test
    else:
        # Localhost "hosts" share one memory domain: the intra-host legs
        # must ride the per-host shm world.
        assert hier.shm_local is not None and hier.shm_local.formed

    # -- allreduce sum, odd length (uneven shard bounds) ------------------
    x = np.arange(17, dtype=np.float32) + rank
    out = hvd.allreduce(x, op=hvd.Sum, name="h_ar")
    flat_expected = np.arange(17, dtype=np.float32) * size + sum(range(size))
    np.testing.assert_allclose(out, flat_expected, rtol=1e-6)
    assert hier.leg_ops["local_rs"] == 1, hier.leg_ops
    assert hier.leg_ops["cross_ar"] == 1, hier.leg_ops
    assert hier.leg_ops["local_ag"] == 1, hier.leg_ops

    # -- average + pre/postscale -----------------------------------------
    out = hvd.allreduce(x, op=hvd.Average, name="h_avg")
    np.testing.assert_allclose(out, flat_expected / size, rtol=1e-6)
    out = hvd.allreduce(np.ones(8, dtype=np.float32), op=hvd.Sum,
                        name="h_scale", prescale_factor=2.0,
                        postscale_factor=0.5)
    np.testing.assert_allclose(out, np.full(8, float(size)), rtol=1e-6)

    # -- cross leg carries exactly 1/local_size of an even payload --------
    before_rs = hier.leg_bytes["local_rs"]
    before_ar = hier.leg_bytes["cross_ar"]
    out = hvd.allreduce(np.ones(64 * lsize, dtype=np.float32), op=hvd.Sum,
                        name="h_ratio")
    np.testing.assert_allclose(out, np.full(64 * lsize, float(size)))
    d_rs = hier.leg_bytes["local_rs"] - before_rs
    d_ar = hier.leg_bytes["cross_ar"] - before_ar
    assert d_rs == 64 * lsize * 4 and d_ar == 64 * 4, (d_rs, d_ar)

    # -- grouped (fused multi-entry response through pack/unpack) ---------
    xs = [np.full((5 + i,), rank + i, dtype=np.float32) for i in range(3)]
    outs = hvd.grouped_allreduce(xs, op=hvd.Sum, name="h_gar")
    for i, o in enumerate(outs):
        np.testing.assert_allclose(
            o, np.full((5 + i,), sum(r + i for r in range(size))))

    # -- 16-bit wire dtypes ------------------------------------------------
    import ml_dtypes
    for dt, tag in ((np.float16, "fp16"), (ml_dtypes.bfloat16, "bf16")):
        v = np.ones(33, dtype=dt) * (rank + 1)
        out = hvd.allreduce(v, op=hvd.Sum, name=f"h_{tag}")
        np.testing.assert_allclose(np.asarray(out, np.float64),
                                   np.full(33, sum(range(1, size + 1))))

    # -- tiny tensor: empty shards on some local ranks --------------------
    out = hvd.allreduce(np.array([float(rank)], np.float32), op=hvd.Sum,
                        name="h_tiny")
    np.testing.assert_allclose(out, [float(sum(range(size)))])

    # -- hierarchical allgather (ragged first dims) -----------------------
    local = np.full((rank + 1, 3), rank, dtype=np.float32)
    out = hvd.allgather(local, name="h_ag")
    expected = np.concatenate([np.full((r + 1, 3), r, np.float32)
                               for r in range(size)])
    np.testing.assert_array_equal(out, expected)
    assert hier.leg_ops["local_gather"] >= 1, hier.leg_ops
    assert hier.leg_ops["cross_gather"] >= 1, hier.leg_ops

    # -- fused allgather: N entries ride TWO collectives with leg spans --
    # Direct lockstep call (every rank executes the same fused response
    # at the same program point — the identical-response-order invariant
    # the background loop provides for real fused responses).
    from horovod_tpu.common.dtypes import from_any
    from horovod_tpu.common.message import Response, ResponseType
    from horovod_tpu.common.tensor_queue import TensorTableEntry

    tl_path = f"/tmp/h_tl_{os.environ['HOROVOD_RENDEZVOUS_EPOCH']}.json"
    if rank == 0:
        hvd.start_timeline(tl_path)
    before = dict(hier.leg_ops)
    ents = [TensorTableEntry(
        tensor_name=f"h_fag{i}",
        tensor=np.full((rank + 1, i + 1), 10 * rank + i, np.float32))
        for i in range(3)]
    sizes = []
    for i in range(3):
        sizes.extend(r + 1 for r in range(size))
    resp = Response(response_type=ResponseType.ALLGATHER,
                    tensor_names=[e.tensor_name for e in ents],
                    tensor_type=from_any(np.dtype(np.float32)),
                    tensor_sizes=sizes)
    st = hier.allgather(resp, ents)
    assert st.ok_p(), st
    for i, e in enumerate(ents):
        expected = np.concatenate([np.full((r + 1, i + 1), 10 * r + i,
                                           np.float32)
                                   for r in range(size)])
        np.testing.assert_array_equal(e.output, expected)
    # 3 fused tensors -> exactly one local gather + one cross exchange.
    assert hier.leg_ops["local_gather"] == before["local_gather"] + 1, \
        hier.leg_ops
    assert hier.leg_ops["cross_gather"] == before["cross_gather"] + 1, \
        hier.leg_ops
    if rank == 0:
        hvd.stop_timeline()
        import json
        names = {ev.get("name", "")
                 for ev in json.load(open(tl_path))}
        assert "LOCAL_GATHER" in names, names
        assert "CROSS_GATHER" in names, names
        os.unlink(tl_path)

    # -- adasum is NOT claimed: falls through to the flat backend ---------
    from horovod_tpu.ops.adasum import adasum_reference
    vecs = [np.linspace(0.1 * (r + 1), 1.0 * (r + 1), 8,
                        dtype=np.float64) for r in range(size)]
    before = dict(hier.leg_ops)
    out = hvd.allreduce(vecs[rank], op=hvd.Adasum, name="h_adasum")
    np.testing.assert_allclose(out, adasum_reference(vecs), rtol=1e-10)
    assert hier.leg_ops == before, "adasum must not ride hierarchical"

    # -- steady state (response cache) keeps the hierarchical path --------
    before_n = hier.leg_ops["local_rs"]
    for _ in range(5):
        out = hvd.allreduce(np.ones(4, dtype=np.float32), op=hvd.Sum,
                            name="h_steady")
        np.testing.assert_allclose(out, np.full(4, float(size)))
    assert hier.leg_ops["local_rs"] == before_n + 5, hier.leg_ops


def battery_peerdeath(hvd, rank, size):
    """Hard peer death mid-run (SURVEY §5.3 failure detection): the last
    rank os._exit()s between collectives; every survivor's next
    collective must raise HorovodInternalError within the transport
    timeout — a hang here is the failure mode this battery guards."""
    small = np.ones(4, np.float32)
    hvd.allreduce(small, op=hvd.Sum, name="warm")   # world fully formed
    if rank == size - 1:
        os._exit(37)
    try:
        for i in range(1000):
            hvd.allreduce(small, op=hvd.Sum, name=f"after{i}")
    except hvd.HorovodInternalError:
        print("peer death surfaced as HorovodInternalError")
        return
    raise AssertionError("collectives kept succeeding after peer death")



def battery_resilience_kill(hvd, rank, size):
    """ISSUE 5 acceptance: chaos SIGKILLs rank 2 mid-allreduce (global
    collective index 3); every survivor must raise RanksFailedError
    naming rank 2 within 2x HOROVOD_FAULT_TIMEOUT (wall-clock bound
    asserted) — the deadlock-to-error conversion, end to end."""
    import time as _time

    small = np.ones(8, np.float32)
    for i in range(3):   # collectives 0..2: world healthy
        out = hvd.allreduce(small, op=hvd.Sum, name=f"warm{i}")
        np.testing.assert_allclose(out, np.full(8, float(size)))
    fault_timeout = float(os.environ["HOROVOD_FAULT_TIMEOUT"])
    t0 = _time.monotonic()
    try:
        for i in range(50):   # collective 3 kills rank 2 pre-dispatch
            hvd.allreduce(small, op=hvd.Sum, name=f"after{i}")
    except hvd.RanksFailedError as e:
        elapsed = _time.monotonic() - t0
        assert 2 in e.failed_ranks, e
        assert elapsed < 2 * fault_timeout, (elapsed, fault_timeout)
        # ISSUE 7 acceptance: every survivor's conversion dumped the
        # flight recorder, and the dump's tail names the in-flight op
        # (the 'after*' allreduce this rank dispatched and never
        # completed).
        import json as _json
        from horovod_tpu.telemetry import flight as _flight
        rec = _flight.recorder()
        assert rec.enabled and rec.dumps >= 1, \
            (rec.enabled, getattr(rec, "dumps", None))
        # Another failure conversion (controller poison + data plane
        # both dump) may still be REWRITING the file when this thread
        # reads it — retry briefly instead of decoding a half-written
        # dump (a rare but real tier-1 flake).
        for _ in range(40):
            try:
                payload = _json.load(open(rec.last_dump_path))
                break
            except ValueError:
                _time.sleep(0.05)
        else:
            raise AssertionError(
                f"flight dump at {rec.last_dump_path} never became "
                f"valid JSON")
        assert payload["rank"] == rank
        events = payload["events"]
        kinds = [ev["kind"] for ev in events]
        assert "ranks-failed" in kinds, kinds
        dispatched = [ev for ev in events if ev["kind"] == "dispatch"
                      and ev["name"].startswith("after")]
        assert dispatched, kinds
        assert dispatched[-1]["trace"], dispatched[-1]
        # The tail IS the failure: nothing after the last in-flight
        # dispatch except failure records (no 'done' for it).
        last_dispatch = max(i for i, ev in enumerate(events)
                            if ev["kind"] == "dispatch"
                            and ev["name"].startswith("after"))
        assert not any(ev["kind"] == "done"
                       and ev["name"] == events[last_dispatch]["name"]
                       for ev in events[last_dispatch:]), events[-4:]
        print(f"survivor {rank}: RanksFailedError("
              f"{sorted(e.failed_ranks)}) in {elapsed:.2f}s "
              f"op={e.op!r} phase={e.phase!r} "
              f"flight={rec.last_dump_path}")
        return
    raise AssertionError("collectives kept succeeding after chaos kill")


def battery_resilience_retry(hvd, rank, size):
    """Delayed-send chaos (rank 1's first data-mesh send to rank 2 held
    for longer than the fault timeout) blows the op deadline on attempt
    0 on EVERY rank; HOROVOD_ON_FAILURE=retry rebuilds all channels
    under a bumped rendezvous epoch with exponential backoff and the
    re-run succeeds (the chaos action's count=1 is exhausted)."""
    from horovod_tpu import resilience
    from horovod_tpu.resilience import policy as _policy

    ones = np.ones(16, np.float32)
    out = resilience.run_with_recovery(
        lambda: hvd.allreduce(ones, op=hvd.Sum, name="retry0"),
        policy="retry", max_retries=3, base_backoff=0.2)
    np.testing.assert_allclose(out, np.full(16, float(size)))
    assert _policy.last_attempts >= 2, \
        f"chaos delay never triggered a retry (attempts=" \
        f"{_policy.last_attempts})"
    # The rebuilt world is fully healthy.
    out = hvd.allreduce(ones * (rank + 1), op=hvd.Sum, name="after_retry")
    np.testing.assert_allclose(out, np.full(16, float(sum(
        r + 1 for r in range(size)))))
    print(f"rank {rank}: retry converged after {_policy.last_attempts} "
          f"attempt(s)")


def battery_resilience_freeze(hvd, rank, size):
    """Wedged-rank detection: chaos freezes rank 1 for far longer than
    the fault timeout at collective 1.  Its PID lives and its heartbeat
    thread keeps beating — only the per-op DEADLINE can convert rank
    0's wait, which must raise RanksFailedError naming rank 1 within
    2x the timeout."""
    import time as _time

    small = np.ones(4, np.float32)
    hvd.allreduce(small, op=hvd.Sum, name="fwarm")   # collective 0
    fault_timeout = float(os.environ["HOROVOD_FAULT_TIMEOUT"])
    if rank == 1:
        # This rank freezes pre-dispatch of collective 1; whatever the
        # world looks like when it thaws (peer may have exited), any
        # structured error is acceptable — only a hang is a failure.
        try:
            hvd.allreduce(small, op=hvd.Sum, name="frozen")
            hvd.allreduce(small, op=hvd.Sum, name="thawed")
        except hvd.HorovodInternalError as e:
            print(f"thawed rank: structured error after freeze: {e}")
        return
    t0 = _time.monotonic()
    try:
        hvd.allreduce(small, op=hvd.Sum, name="frozen")
        hvd.allreduce(small, op=hvd.Sum, name="thawed")
    except hvd.RanksFailedError as e:
        elapsed = _time.monotonic() - t0
        assert 1 in e.failed_ranks, e
        assert elapsed < 2 * fault_timeout, (elapsed, fault_timeout)
        print(f"rank {rank}: wedged peer converted in {elapsed:.2f}s")
        return
    raise AssertionError("frozen peer never converted to an error")


def battery_resilience_off(hvd, rank, size):
    """Zero-overhead off mode: with HOROVOD_FAULT_TOLERANCE unset and
    HOROVOD_CHAOS unset there must be NO monitor thread, NO chaos
    engine, NO socket timeouts and NO resilience state captured by the
    meshes — byte-identical hot paths to the pre-resilience tree."""
    from census import assert_thread_absent

    from horovod_tpu import resilience
    from horovod_tpu.core import _global

    assert resilience.active_state() is None
    assert resilience.chaos.active() is None
    assert _global.chaos is None
    assert_thread_absent("heartbeat")
    for coll in _global.tcp_collectives:
        mesh = coll.mesh
        assert mesh._resilience is None and mesh._chaos is None
        for ch in mesh._channels.values():
            assert ch._res is None
            # Dialed sockets historically keep the formation connect
            # timeout (create_connection); off mode must only never
            # install the SHORT resilience poll timeout.
            t = ch.sock.gettimeout()
            assert t is None or t >= 10.0, \
                f"off mode must not install poll timeouts (got {t})"
    out = hvd.allreduce(np.ones(8, np.float32), op=hvd.Sum, name="off0")
    np.testing.assert_allclose(out, np.full(8, float(size)))
    # Still none after traffic (lazy paths must not re-resolve).
    assert_thread_absent("heartbeat")


def battery_torch_grid(hvd, rank, size):
    """Torch-binding semantic grid (modeled on the dtype x op x variant
    sweep of /root/reference/test/parallel/test_torch.py): every wire
    dtype through the torch surface, in-place variants, async handles
    with poll/synchronize, scales, and splits-alltoall with received
    splits."""
    import torch
    import horovod_tpu.torch as hvt

    int_dtypes = [torch.uint8, torch.int8, torch.int32, torch.int64]
    float_dtypes = [torch.float16, torch.bfloat16, torch.float32,
                    torch.float64]

    # -- allreduce out-of-place + in-place, every dtype -------------------
    for dt in int_dtypes + float_dtypes:
        tag = str(dt).split(".")[-1]
        base = torch.arange(17) % 4 + rank + 1
        expected = sum((np.arange(17) % 4 + r + 1).astype(np.float64)
                       for r in range(size))
        rtol = 1e-2 if dt in (torch.float16, torch.bfloat16) else 1e-6
        out = hvt.allreduce(base.to(dt), op=hvt.Sum, name=f"tg_ar_{tag}")
        assert out.dtype == dt, (tag, out.dtype)
        np.testing.assert_allclose(out.to(torch.float64).numpy(),
                                   expected, rtol=rtol, err_msg=tag)
        t2 = base.to(dt).clone()
        ret = hvt.allreduce_(t2, op=hvt.Sum, name=f"tg_ari_{tag}")
        assert ret is t2   # in-place returns the same tensor
        np.testing.assert_allclose(t2.to(torch.float64).numpy(),
                                   expected, rtol=rtol,
                                   err_msg=f"inplace {tag}")

    # -- prescale/postscale through the torch surface ---------------------
    out = hvt.allreduce(torch.ones(9), op=hvt.Sum, name="tg_scale",
                        prescale_factor=2.0, postscale_factor=0.25)
    np.testing.assert_allclose(out.numpy(), np.full(9, size / 2.0),
                               rtol=1e-6)

    # -- async handles: enqueue several, poll, synchronize out of order --
    handles = [hvt.allreduce_async(torch.ones(4) * (rank + i),
                                   op=hvt.Sum, name=f"tg_async_{i}")
               for i in range(3)]
    for i in reversed(range(3)):
        out = hvt.synchronize(handles[i])
        assert hvt.poll(handles[i])
        np.testing.assert_allclose(
            out.numpy(), np.full(4, float(sum(r + i for r in range(size)))),
            rtol=1e-6, err_msg=f"async {i}")

    # -- grouped in-place per dtype ---------------------------------------
    for dt in (torch.int32, torch.float32, torch.float64):
        tag = str(dt).split(".")[-1]
        ts = [torch.full((5 + i,), float(rank + i)).to(dt)
              for i in range(3)]
        hvt.grouped_allreduce_(ts, op=hvt.Sum, name=f"tg_gar_{tag}")
        for i, t in enumerate(ts):
            np.testing.assert_allclose(
                t.to(torch.float64).numpy(),
                np.full(5 + i, float(sum(r + i for r in range(size)))),
                err_msg=f"grouped {tag}[{i}]")

    # -- broadcast_ in place ----------------------------------------------
    t = torch.full((3,), float(rank))
    hvt.broadcast_(t, root_rank=size - 1, name="tg_bc")
    np.testing.assert_allclose(t.numpy(), np.full(3, float(size - 1)))

    # -- alltoall with uneven splits + received splits ---------------------
    # Sender r sends (d+1) rows to destination d, all rows carrying r.
    rows = sum(d + 1 for d in range(size))
    t = torch.full((rows, 2), float(rank))
    splits = torch.tensor([d + 1 for d in range(size)], dtype=torch.int32)
    out, recv = hvt.alltoall(t, splits=splits, name="tg_a2a")
    np.testing.assert_array_equal(recv.numpy(),
                                  np.full(size, rank + 1, np.int32))
    expected_rows = np.concatenate(
        [np.full(((rank + 1), 2), float(r)) for r in range(size)])
    np.testing.assert_allclose(out.numpy(), expected_rows)



def battery_tf_grid(hvd, rank, size):
    """TF-surface dtype grid (reference: test/parallel/test_tensorflow.py
    dtype sweep): every wire dtype through the tf binding, scales, and
    uneven-splits alltoall."""
    import tensorflow as tf
    import horovod_tpu.tensorflow as htf

    dtypes = [tf.uint8, tf.int8, tf.int32, tf.int64, tf.float16,
              tf.bfloat16, tf.float32, tf.float64]
    for dt in dtypes:
        tag = dt.name
        base = tf.cast(tf.range(17) % 4 + rank + 1, dt)
        expected = sum((np.arange(17) % 4 + r + 1).astype(np.float64)
                      for r in range(size))
        rtol = 1e-2 if dt in (tf.float16, tf.bfloat16) else 1e-6
        out = htf.allreduce(base, average=False, name=f"tfg_ar_{tag}")
        assert out.dtype == dt, (tag, out.dtype)
        np.testing.assert_allclose(
            tf.cast(out, tf.float64).numpy(), expected, rtol=rtol,
            err_msg=tag)

    # prescale/postscale
    out = htf.allreduce(tf.ones(9), average=False, name="tfg_scale",
                        prescale_factor=2.0, postscale_factor=0.25)
    np.testing.assert_allclose(out.numpy(), np.full(9, size / 2.0),
                               rtol=1e-6)

    # allgather variable first dim per dtype
    for dt in (tf.int64, tf.float16, tf.float64):
        local = tf.cast(tf.fill((rank + 1, 2), rank + 1), dt)
        out = htf.allgather(local, name=f"tfg_ag_{dt.name}")
        assert out.shape == (sum(r + 1 for r in range(size)), 2)

    # broadcast from the last rank
    out = htf.broadcast(tf.fill((3,), float(rank)), root_rank=size - 1,
                        name="tfg_bc")
    np.testing.assert_allclose(out.numpy(), np.full(3, float(size - 1)))

    # alltoall with uneven splits: sender r sends (d+1) rows to dest d
    rows = sum(d + 1 for d in range(size))
    t = tf.fill((rows, 2), float(rank))
    out = htf.alltoall(t, splits=[d + 1 for d in range(size)],
                       name="tfg_a2a")
    got = out[0] if isinstance(out, (tuple, list)) else out
    expected_rows = np.concatenate(
        [np.full(((rank + 1), 2), float(r)) for r in range(size)])
    np.testing.assert_allclose(np.asarray(got), expected_rows)


def _compress_reference(size, n=4096, seed=123):
    """Deterministic per-rank payloads + their exact fp32 sum (identical
    on every rank: same seed)."""
    rng = np.random.default_rng(seed)
    data = rng.standard_normal((size, n)).astype(np.float32) * 2.0
    return data, data.sum(axis=0)


def _compress_error_bound(data, codec, block_size):
    """Documented bound for the eager quantized allreduce: every rank's
    input quantization error, plus one requantization of the reduced
    chunk (half a block step of the reduced values under the owner-chunk
    split, widened by the input error the accumulator already carries)."""
    from horovod_tpu.compress import chunk_bounds, roundtrip_error_bound
    size = data.shape[0]
    input_bound = sum(roundtrip_error_bound(data[r], codec, block_size)
                      for r in range(size))
    ref = data.sum(axis=0)
    b = chunk_bounds(ref.size, size)
    requant = np.concatenate(
        [roundtrip_error_bound(ref[b[r]:b[r + 1]], codec, block_size)
         for r in range(size)])
    return 2 * input_bound + requant + 1e-5


def battery_compress(hvd, rank, size):
    """Quantized-collective subsystem over the TCP plane: int8/uint4
    equivalence within the documented bound, measurably fewer wire
    bytes than fp32 for the same payload (the plane's byte counters),
    fp16 cast codec, and the codec-mismatch structured ERROR."""
    from horovod_tpu.backend.tcp import TcpBackend
    from horovod_tpu.compress import CompressionCodec
    from horovod_tpu.core import _global

    block_size = 256   # the HOROVOD_COMPRESSION_BLOCK_SIZE default
    data, ref = _compress_reference(size)
    x = data[rank]
    tcp = next(b for b in _global.op_manager.backends
               if isinstance(b, TcpBackend))
    mesh = tcp.coll.mesh

    base = mesh.bytes_sent
    out32 = hvd.allreduce(x.copy(), op=hvd.Sum, name="c_fp32")
    fp32_bytes = mesh.bytes_sent - base
    np.testing.assert_allclose(out32, ref, rtol=1e-5, atol=1e-5)
    assert fp32_bytes > 0, "fp32 allreduce moved no counted bytes"

    for codec_name, codec, min_ratio in (
            ("int8", CompressionCodec.INT8, 3.0),
            ("uint4", CompressionCodec.UINT4, 5.0)):
        base = mesh.bytes_sent
        out_q = hvd.allreduce(x.copy(), op=hvd.Sum,
                              name=f"c_{codec_name}",
                              compression=codec_name)
        q_bytes = mesh.bytes_sent - base
        bound = _compress_error_bound(data, codec, block_size)
        err = np.abs(np.asarray(out_q, np.float64) - ref)
        assert np.all(err <= bound), \
            (codec_name, float(err.max()), float(bound.max()))
        # The acceptance criterion: the tcp plane transmits measurably
        # fewer bytes for the same bucket.
        assert q_bytes * min_ratio < fp32_bytes, \
            (codec_name, q_bytes, fp32_bytes)

    # Cast codec: half the wire bytes, fp16-grade accuracy.
    base = mesh.bytes_sent
    out16 = hvd.allreduce(x.copy(), op=hvd.Sum, name="c_fp16",
                          compression="fp16")
    fp16_bytes = mesh.bytes_sent - base
    np.testing.assert_allclose(out16, ref, rtol=2e-2, atol=2e-2)
    assert fp16_bytes * 1.8 < fp32_bytes, (fp16_bytes, fp32_bytes)

    # Averaging composes through the postscale factor.
    out_avg = hvd.allreduce(x.copy(), op=hvd.Average, name="c_avg8",
                            compression="int8")
    bound = _compress_error_bound(data, CompressionCodec.INT8,
                                  block_size) / size
    assert np.all(np.abs(np.asarray(out_avg, np.float64) - ref / size)
                  <= bound)

    # Codec mismatch across ranks -> structured ERROR, never a hang or
    # a corrupted reduce; the world stays usable afterwards.
    try:
        hvd.allreduce(x.copy(), op=hvd.Sum, name="c_mismatch",
                      compression="int8" if rank == 0 else None)
    except hvd.HorovodInternalError as e:
        assert "codec" in str(e).lower(), str(e)
    else:
        raise AssertionError("expected HorovodInternalError")
    out_after = hvd.allreduce(np.ones(8, np.float32), op=hvd.Sum,
                              name="c_after")
    np.testing.assert_allclose(out_after, np.full(8, float(size)))

    # Adasum + quantized codec is rejected with a structured error too.
    try:
        hvd.allreduce(x.copy(), op=hvd.Adasum, name="c_adasum8",
                      compression="int8")
    except hvd.HorovodInternalError as e:
        assert "adasum" in str(e).lower(), str(e)
    else:
        raise AssertionError("expected HorovodInternalError")


def battery_compress_shm(hvd, rank, size):
    """Quantized allreduce over the same-host shm plane: the shm backend
    must claim it (quantized staging fits the region), reconstruct
    within the shared bound, and fall through to TCP when the region is
    too small for the staged quantized chunks."""
    from horovod_tpu.compress import CompressionCodec
    from horovod_tpu.core import _global

    names = [b.name for b in _global.op_manager.backends]
    assert "shm" in names, names
    shm = _global.op_manager.backends[names.index("shm")]
    assert shm.world.formed

    block_size = 256
    data, ref = _compress_reference(size)
    executed = shm.ops_executed
    out_q = hvd.allreduce(data[rank].copy(), op=hvd.Sum, name="s_int8",
                          compression="int8")
    assert shm.ops_executed == executed + 1, "shm plane did not claim it"
    bound = _compress_error_bound(data, CompressionCodec.INT8, block_size)
    assert np.all(np.abs(np.asarray(out_q, np.float64) - ref) <= bound)

    # Oversized quantized payload falls through to the TCP ring with the
    # same numerics (capacity is 1 MB in this battery; 2M floats stage
    # ~2 MB even quantized).
    big, big_ref = _compress_reference(size, n=2_000_000, seed=7)
    executed = shm.ops_executed
    out_big = hvd.allreduce(big[rank].copy(), op=hvd.Sum, name="s_big8",
                            compression="int8")
    assert shm.ops_executed == executed, "oversized op must not ride shm"
    bound = _compress_error_bound(big, CompressionCodec.INT8, block_size)
    assert np.all(np.abs(np.asarray(out_big, np.float64) - big_ref)
                  <= bound)


def battery_compress_xla(hvd, rank, size):
    """Quantized allreduce over the XLA device plane: the xla backend
    claims the response, the device program dequantizes+sums the int8
    payload, and the reconstruction stays within the shared bound."""
    from horovod_tpu.backend.xla import XlaBackend
    from horovod_tpu.compress import CompressionCodec
    from horovod_tpu.core import _global

    xla = next(b for b in _global.op_manager.backends
               if isinstance(b, XlaBackend))
    claimed = []
    orig = xla.allreduce

    def counting_allreduce(resp, entries):
        claimed.append(resp.tensor_names[0])
        return orig(resp, entries)

    xla.allreduce = counting_allreduce
    block_size = 256
    data, ref = _compress_reference(size)
    out_q = hvd.allreduce(data[rank].copy(), op=hvd.Sum, name="x_int8",
                          compression="int8")
    assert any("x_int8" in nm for nm in claimed), claimed
    bound = _compress_error_bound(data, CompressionCodec.INT8, block_size)
    assert np.all(np.abs(np.asarray(out_q, np.float64) - ref) <= bound)

    out4 = hvd.allreduce(data[rank].copy(), op=hvd.Average, name="x_u4",
                         compression="uint4")
    bound = _compress_error_bound(data, CompressionCodec.UINT4,
                                  block_size) / size
    assert np.all(np.abs(np.asarray(out4, np.float64) - ref / size)
                  <= bound)


def battery_streams(hvd, rank, size):
    """Multi-stream response dispatch (HOROVOD_NUM_STREAMS=2, fusion off
    so a burst of async allreduces becomes several responses round-robined
    across streams): exact results, per-stream channel traffic, mixed
    codecs, and a steady-state thread census."""
    import threading

    from horovod_tpu import core
    from horovod_tpu.compress import CompressionCodec
    st = core.global_state()
    assert st.stream_dispatcher is not None, "dispatcher not formed"
    assert st.stream_dispatcher.num_streams == 2
    assert len(st.op_managers) == 2 and len(st.tcp_collectives) == 2

    def burst(tag):
        handles = [hvd.allreduce_async(
            np.arange(4096, dtype=np.float32) * (i + 1) + rank,
            op=hvd.Sum, name=f"{tag}{i}") for i in range(6)]
        for i, h in enumerate(handles):
            out = hvd.synchronize(h)
            expected = np.arange(4096, dtype=np.float32) * (i + 1) * size \
                + sum(range(size))
            np.testing.assert_array_equal(out, expected)

    burst("first")           # negotiated path
    for cycle in range(3):   # response-cache steady state
        burst(f"c{cycle}")

    # Stream isolation: BOTH per-stream channel sets carried payload.
    for s, coll in enumerate(st.tcp_collectives):
        assert coll.mesh.bytes_received > 0, f"stream {s} never used"

    # Mixed ops across streams in one cycle (broadcast is stream-safe on
    # the TCP plane; values exact).
    handles = [hvd.allreduce_async(np.full(1024, float(rank + i),
                                           np.float32),
                                   op=hvd.Sum, name=f"mix_ar{i}")
               for i in range(2)]
    bh = hvd.broadcast_async(np.arange(64, dtype=np.float64) * (rank + 1),
                             root_rank=0, name="mix_bc")
    for i, h in enumerate(handles):
        np.testing.assert_array_equal(
            hvd.synchronize(h),
            np.full(1024, float(sum(range(size)) + size * i), np.float32))
    np.testing.assert_array_equal(hvd.synchronize(bh),
                                  np.arange(64, dtype=np.float64))

    # Cast + quantized codecs ride the per-stream channels too; small
    # integer values are exact through the bf16 wire, int8 within the
    # block-quantization bound.
    v = np.arange(2048, dtype=np.float32) % 97
    out = hvd.allreduce(v, op=hvd.Sum, name="s_bf16", compression="bf16")
    np.testing.assert_array_equal(out, v * size)
    data = np.stack([(np.arange(2048, dtype=np.float32) % 53) + r
                     for r in range(size)])
    out_q = hvd.allreduce(data[rank].copy(), op=hvd.Sum, name="s_int8",
                          compression="int8")
    bound = _compress_error_bound(data, CompressionCodec.INT8, 256)
    assert np.all(np.abs(np.asarray(out_q, np.float64) - data.sum(0))
                  <= bound)

    # Steady-state census: cached multi-stream cycles spawn no threads.
    before = threading.active_count()
    burst("census")
    assert threading.active_count() <= before, \
        (before, threading.active_count())


def battery_telemetry(hvd, rank, size):
    """Observability layer end-to-end (ISSUE 4 acceptance): a 4-rank
    HOROVOD_METRICS=on world serves a real Prometheus scrape with
    per-plane latency histograms and per-peer byte counters, and with
    rank size-1 delayed 50 ms per step the coordinator names that rank
    as the straggler within two aggregation windows (window=8 via env)."""
    import time as _time
    import urllib.request

    from horovod_tpu.core import _global
    from horovod_tpu.telemetry import MetricsExporter

    assert _global.telemetry.enabled
    delayed = size - 1

    # Unique names force one negotiation per step — the wire the arrival
    # times and per-rank snapshots ride.  The delayed rank submits 50 ms
    # behind its peers every step.
    for step in range(20):
        if rank == delayed:
            _time.sleep(0.05)
        out = hvd.allreduce(np.ones(64, np.float32), op=hvd.Sum,
                            name=f"tm_{step}")
        np.testing.assert_allclose(out, np.full(64, float(size)))

    if rank == 0:
        agg = _global.controller.straggler
        assert agg is not None
        assert agg.windows_completed >= 2, agg.windows_completed
        assert agg.last_straggler == delayed, \
            (agg.last_straggler, agg.last_skew_ms)
        assert agg.last_skew_ms > 20.0, agg.last_skew_ms
        g = _global.telemetry.gauge("horovod_controller_straggler_rank")
        assert g.value == float(delayed), g.value

    # Cached steady state exercises the hit counter + per-plane latency.
    for _ in range(5):
        hvd.allreduce(np.ones(32, np.float32), op=hvd.Sum,
                      name="tm_steady")
    assert _global.controller._m_cache_hit.value >= 3

    # Real HTTP scrape of this rank's exporter.
    exporter = next(r for r in _global.resources
                    if isinstance(r, MetricsExporter))
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{exporter.port}/metrics",
        timeout=10).read().decode()
    assert "horovod_collective_latency_ms_bucket" in body
    assert 'plane="tcp"' in body, body[:2000]
    assert "horovod_tcp_bytes_sent_total" in body
    assert "horovod_tcp_bytes_received_total" in body
    if rank == 0:
        # Coordinator re-exports every rank's snapshot + the straggler.
        assert "horovod_controller_straggler_rank" in body
        assert "horovod_rank_cycle_ms" in body
    hvd.barrier()
    # The JSON dump itself is written at shutdown; the parent test
    # (test_multiprocess.test_telemetry_observability_4rank) asserts its
    # contents after the world exits.


def battery_perfscope(hvd, rank, size):
    """perfscope smoke (ISSUE 19): a 2-rank HOROVOD_METRICS=on world
    runs allreduces spanning three size buckets; every rank's registry
    must carry busbw cells whose roofline-relative efficiency lands in
    (0, 1.05] with a known algorithm label (at 2 ranks every schedule
    degenerates to the ring).  The parent test merges the shutdown
    dumps through the perf CLI and gates them with perfcheck."""
    from horovod_tpu.core import _global
    from horovod_tpu.telemetry import perfmodel

    assert _global.telemetry.enabled
    # 2 KiB / 32 KiB / 512 KiB payloads → 4KiB / 64KiB / 1MiB buckets.
    for step in range(4):
        for tag, n in (("s", 512), ("m", 8192), ("l", 131072)):
            out = hvd.allreduce(np.ones(n, np.float32), op=hvd.Sum,
                                name=f"pf_{tag}_{step}")
            np.testing.assert_allclose(out, np.full(n, float(size)))
    hvd.barrier()

    ledger = perfmodel.build_ledger([_global.telemetry.snapshot()])
    rows = ledger.get("busbw", [])
    assert rows, "no busbw cells in the local registry"
    buckets = {r["size_bucket"] for r in rows}
    assert {"4KiB", "64KiB", "1MiB"} <= buckets, buckets
    for r in rows:
        assert 0.0 < r["efficiency"] <= 1.05, r
        assert r["algo"] in ("ring", "tree", "rhd", "torus",
                             "hierarchical"), r
    # The degenerate 2-rank world keeps the ring fast path everywhere.
    assert {r["algo"] for r in rows} == {"ring"}, rows
    # The shutdown JSON dump (asserted by the parent) rides hvd.shutdown.


def battery_trace(hvd, rank, size):
    """ISSUE 7 acceptance (4-rank, in-battery half): uniquely-named
    allreduces under per-rank HOROVOD_TIMELINE files while chaos
    freezes rank size-1 for 120 ms before dispatching every tr_*
    collective (the PR 5 deterministic delay injection).  The parent
    test (test_multiprocess.test_trace_merge_and_critical_path_4rank)
    merges the four files and asserts flow-linked spans + critical-path
    attribution naming the delayed rank."""
    from horovod_tpu.core import _global

    assert _global.timeline is not None and _global.timeline.enabled
    assert _global.flight.enabled   # default-on flight recorder
    delayed = size - 1
    if rank != 0:
        # Worker ranks probed a real clock offset against rank 0.
        assert _global.timeline._clock_offset_us is not None
        assert _global.timeline._clock_rtt_us > 0.0
    for step in range(12):
        out = hvd.allreduce(np.ones(32, np.float32), op=hvd.Sum,
                            name=f"tr_{step}")
        np.testing.assert_allclose(out, np.full(32, float(size)))
    # Trace ids advanced monotonically with the lockstep cycles.
    assert _global.controller._trace_cycle > 0
    if rank == delayed:
        assert _global.chaos is not None
        assert any(a.fired for a in _global.chaos.actions)
    hvd.barrier()


def battery_san(hvd, rank, size):
    """ISSUE 8 acceptance (in-battery half): the HOROVOD_SAN runtime
    witness is live, collectives stay exact under the lock wrappers,
    per-thread acquisition-order edges were recorded — including the
    init-time controller<->transport edge (core._init_lock held while
    the clock-offset probes touch the ctrl mesh's counter lock) — and
    first observations rode the flight-recorder ring.  The parent test
    (test_multiprocess.test_lock_witness_matches_static_graph) diffs
    the shutdown dumps against the static lock graph."""
    from horovod_tpu.analysis.hvdsan import san
    from horovod_tpu.core import _global

    assert san.enabled(), "HOROVOD_SAN=1 did not enable the witness"
    w = san.witness()
    assert w is not None
    for step in range(6):
        out = hvd.allreduce(np.ones(16, np.float32), op=hvd.Sum,
                            name=f"san_{step}")
        np.testing.assert_allclose(out, np.full(16, float(size)))
    hvd.barrier()
    snap = w.snapshot()
    edges = {(e["src"], e["dst"]) for e in snap["edges"]}
    assert edges, "witness recorded no acquisition-order edges"
    assert any(s.startswith("horovod_tpu/core.py:")
               and d.startswith("horovod_tpu/runner/network.py:")
               for s, d in edges), sorted(edges)
    # First edge observations land in the flight ring (ISSUE 8).
    kinds = {e["kind"] for e in _global.flight.snapshot()}
    assert "lock-order" in kinds, kinds


def battery_serving(hvd, rank, size):
    """ISSUE 9 acceptance (4-rank): continuous-batching serving with a
    chaos SIGKILL of rank 2 mid-serve.  The world shrinks 4->3; every
    survivor finishes every request it had admitted (zero failed
    in-flight on survivors), the front end's accounting balances
    (served + lost == offered, bounded shed), and a post-shrink burst
    of hopeless-SLO requests is shed at admission — never prefilled."""
    import random as _random
    import time as _time

    from horovod_tpu.serving import ReplicaExecutor, ServeConfig

    ex = ReplicaExecutor(ServeConfig.from_env(
        max_batch=4, token_budget=64, max_seq=64, slo_ms=120000.0))
    assert ex.num_groups == size
    n_requests = 24
    if rank == 0:
        rng = _random.Random(7)
        for _ in range(n_requests):
            toks = [rng.randrange(2, ex.model.cfg.vocab_size)
                    for _ in range(rng.randint(2, 10))]
            ex.stats["offered"] += 1
            assert ex.queue.submit(toks, 12) is not None

    t0 = _time.monotonic()
    ex.serve_loop(stop_when=lambda: True)   # drain then stop
    phase1_wall = _time.monotonic() - t0

    # --- phase-1 assertions: the kill happened and survivors absorbed it
    assert ex.size == size - 1, (ex.size, size)
    assert ex.stats["shrinks"] and \
        ex.stats["shrinks"][0]["dead"] == [2], ex.stats["shrinks"]
    missing = ex.prefilled - set(ex.completed)
    assert not missing, \
        f"survivor {rank} failed admitted in-flight requests: {missing}"
    phase1_prefilled = len(ex.prefilled)
    if rank == 0:
        st = ex.stats
        assert st["served"] + st["lost"] == n_requests, st
        assert st["lost"] <= 4, st          # at most rank 2's slots
        assert st["expired"] == 0, st       # generous SLOs: bounded shed
        assert ex.admission._m_outcome["shed"].value == 0
        lat = st["latencies_ms"]
        assert len(lat) == st["served"] and min(lat) > 0.0
        fault_timeout = float(os.environ["HOROVOD_FAULT_TIMEOUT"])
        # The shrink detour is bounded: detection (<= 2x fault timeout)
        # + confirmation polling (<= 2x) + rebuild, with wide margin.
        assert phase1_wall < 10 * fault_timeout, phase1_wall
        print(f"serving: {st['served']}/{n_requests} served, "
              f"{st['lost']} lost with rank 2, shrink at step "
              f"{st['shrinks'][0]['step']} in {phase1_wall:.1f}s")

    # --- phase 2: overload with hopeless SLOs -> shed at admission,
    # never executed (no new prefill on ANY survivor).
    served_before = ex.stats["served"]
    if ex.rank == ex.front:
        for _ in range(8):
            # Deadline passes while queued -> 'expired' at pop.
            assert ex.queue.submit([3, 4, 5], 4, slo_ms=0.5) is not None
        for _ in range(4):
            # Feasibility shed: 200 decode steps can never fit 3 ms.
            assert ex.queue.submit([3] * 8, 200, slo_ms=3.0) is not None
    ex._stop_requested = False
    ex.serve_loop(stop_when=lambda: True)
    assert len(ex.prefilled) == phase1_prefilled, \
        "hopeless-SLO requests must never be executed"
    assert ex.stats["served"] == served_before
    if ex.rank == ex.front:
        shed_total = (ex.stats["expired"]
                      + ex.admission._m_outcome["shed"].value)
        assert shed_total == 12, \
            (ex.stats["expired"], ex.admission._m_outcome["shed"].value)
        print(f"serving: post-shrink hopeless burst shed at admission "
              f"(expired={ex.stats['expired']}, "
              f"shed={ex.admission._m_outcome['shed'].value:g})")
    hvd.barrier()


def battery_serving_paged(hvd, rank, size):
    """ISSUE 14 acceptance (4-rank): paged-KV continuous serving rides
    the same chaos SIGKILL of rank 2 mid-serve as the dense battery.
    The world shrinks 4->3 with block tables resynced from ground
    truth, every survivor finishes every admitted request (zero failed
    in-flight), repeated prompts hit the prefix cache, and after the
    drain every survivor's pool passes the refcount-leak census
    (active blocks == 0)."""
    import random as _random
    import time as _time

    from horovod_tpu.serving import ReplicaExecutor, ServeConfig

    ex = ReplicaExecutor(ServeConfig.from_env(
        max_batch=4, token_budget=64, max_seq=64, slo_ms=120000.0,
        paged=True, block_tokens=8))
    assert ex.num_groups == size
    assert ex.cfg.slots == 8 and ex.cache.pool is not None
    n_requests = 24
    if rank == 0:
        rng = _random.Random(7)
        # A pool of 6 prompts offered 4x each: the repeated-prompt
        # profile the prefix cache exists for.
        prompts = [[rng.randrange(2, ex.model.cfg.vocab_size)
                    for _ in range(rng.randint(2, 10))]
                   for _ in range(6)]
        for i in range(n_requests):
            ex.stats["offered"] += 1
            assert ex.queue.submit(prompts[i % 6], 12) is not None

    t0 = _time.monotonic()
    ex.serve_loop(stop_when=lambda: True)   # drain then stop
    phase1_wall = _time.monotonic() - t0

    # --- the kill happened, survivors absorbed it with paged KV intact
    assert ex.size == size - 1, (ex.size, size)
    assert ex.stats["shrinks"] and \
        ex.stats["shrinks"][0]["dead"] == [2], ex.stats["shrinks"]
    missing = ex.prefilled - set(ex.completed)
    assert not missing, \
        f"survivor {rank} failed admitted in-flight requests: {missing}"
    # Four ranks, then three: no exchange stayed in the process.
    assert ex.stats["exchanges"] > 0 \
        and ex.stats["local_exchanges"] == 0, ex.stats
    kv = ex.kv_stats()
    assert kv["active"] == 0, f"rank {rank} leaked KV blocks: {kv}"
    print(f"serving_paged: rank {rank} kv census clean "
          f"(hits={kv['prefix_hits']:g} cow={kv['cow_copies']:g})")
    if rank == 0:
        st = ex.stats
        assert st["served"] + st["lost"] == n_requests, st
        assert st["lost"] <= 8, st          # at most rank 2's slots
        assert st["expired"] == 0, st
        assert kv["prefix_hits"] > 0, kv    # repeated prompts hit
        # Block-table resync: after the drain the front end's block
        # mirror is empty again — reservations freed exactly once.
        assert ex.batcher.inflight == {} and \
            all(b == 0 for b in ex.batcher._blocks), \
            (ex.batcher.inflight, ex.batcher._blocks)
        fault_timeout = float(os.environ["HOROVOD_FAULT_TIMEOUT"])
        assert phase1_wall < 10 * fault_timeout, phase1_wall
        print(f"serving_paged: {st['served']}/{n_requests} served, "
              f"{st['lost']} lost with rank 2, shrink at step "
              f"{st['shrinks'][0]['step']} in {phase1_wall:.1f}s, "
              f"max_concurrent={ex.batcher.max_concurrent}")
    ex.close()
    hvd.barrier()


def battery_serving_disagg(hvd, rank, size):
    """ISSUE 14 acceptance (2-rank, strict fingerprint): disaggregated
    prefill/decode — rank 1 is a prefill-only rank streaming finished
    KV blocks to the rank-0 decode replica over the kvstream mesh.
    Every long prompt is prefilled OFF the decode rank (zero local
    fallbacks), everything offered is served, and the strict-mode
    collective fingerprint stays clean over the split-role step loop
    (any divergence would abort the battery with a structured ERROR)."""
    import random as _random

    from horovod_tpu.serving import ReplicaExecutor, ServeConfig

    ex = ReplicaExecutor(ServeConfig.from_env(
        max_batch=4, token_budget=256, max_seq=64, slo_ms=120000.0,
        paged=True, block_tokens=8, prefill_ranks=1))
    assert ex.decode_size == 1 and ex.prefill_rank_list == [1]
    assert ex.is_prefill == (rank == 1)
    n_requests = 12
    if rank == 0:
        rng = _random.Random(5)
        for _ in range(n_requests):
            # Long prompts (3-5 blocks): the traffic whose prefill
            # used to stall co-scheduled decode steps.
            toks = [rng.randrange(2, ex.model.cfg.vocab_size)
                    for _ in range(rng.randint(24, 40))]
            ex.stats["offered"] += 1
            assert ex.queue.submit(toks, 8) is not None

    ex.serve_loop(stop_when=lambda: True)

    # Two ranks broadcast every plan and gather every completion list:
    # only a replica alone in its world keeps them in the process.
    assert ex.stats["exchanges"] > 0 \
        and ex.stats["local_exchanges"] == 0, ex.stats
    if rank == 0:
        st = ex.stats
        kv = ex.kv_stats()
        assert st["served"] == n_requests, st
        assert kv["prefill_fallbacks"] == 0, kv
        assert kv["active"] == 0, kv
        assert ex.batcher.inflight == {}, ex.batcher.inflight
        print(f"serving_disagg: {st['served']}/{n_requests} served via "
              f"streamed prefill, zero local fallbacks")
    else:
        assert ex.stats["prefill_streams"] == n_requests, ex.stats
        from horovod_tpu import telemetry
        sent = telemetry.metrics().counter(
            "horovod_serve_prefill_stream_bytes_total",
            labels={"role": "sent"}).value
        assert sent > 0, "prefill rank streamed no bytes"
        print(f"serving_disagg: rank 1 streamed "
              f"{ex.stats['prefill_streams']} prefills "
              f"({sent:g} payload bytes)")
    ex.close()
    hvd.barrier()


def _statesync_state(n=1 << 18):
    """Deterministic replicated training state: params/opt evolve by the
    (identical-on-every-rank) allreduce output, so donors' snapshots are
    coherent and digests comparable."""
    return {"params": np.zeros(n, np.float32),
            "opt": np.zeros(n, np.float32),
            "step": np.zeros((), np.int64)}


def _statesync_train_step(hvd, state):
    """One lockstep training step; returns the reduced output after
    applying the deterministic symmetric update."""
    n = state["params"].size
    my = np.full(n, float(hvd.rank() + 1), np.float32)
    out = hvd.allreduce(my, op=hvd.Sum,
                        name=f"sst.train.{int(state['step'])}")
    expected = hvd.size() * (hvd.size() + 1) / 2.0
    np.testing.assert_allclose(out[:8], np.full(8, expected))
    state["params"] += 0.01 * out
    state["opt"] += out * out
    state["step"] += 1
    return out


def _statesync_witness_dump(tag, launch_rank):
    """End-of-battery flight dump for the hvdmc trace witness: the
    driver test replays every WITNESS_DUMP file through
    horovod_tpu.analysis.hvdmc.witness and fails on any observed
    membership transition the model does not know.  Keyed by LAUNCH
    rank, not world rank — elastic renumbering would otherwise collide
    a departed rank's dump with a renumbered survivor's."""
    from horovod_tpu.telemetry import flight

    rec = flight.recorder()
    if not rec.enabled:
        return
    epoch0 = os.environ["HOROVOD_RENDEZVOUS_EPOCH"].split("~", 1)[0]
    rec.path = f"/tmp/hvd_witness_{epoch0}.launch{launch_rank}.json"
    path = rec.dump(reason=f"hvdmc witness ({tag})")
    if path:
        print(f"WITNESS_DUMP {path}")


def _statesync_digest_check(hvd, state):
    """Every rank's state must be bit-identical after a grow."""
    from horovod_tpu import statesync

    digest = statesync.state_digest(statesync.flatten_state(state))
    views = hvd.allgather_object(digest,
                                 name=f"sst.digest.{int(state['step'])}")
    assert len(set(views)) == 1, f"post-grow state divergence: {views}"
    return digest


def battery_rolling(hvd, rank, size):
    """ISSUE 15 rolling-upgrade battery: rank 1 advertises wire proto 1
    (the still-old framework version; HOROVOD_PROTO_COMPAT set in main
    before init) — the world negotiates the min common schema at every
    mesh HELLO and completes training steps with zero failed steps and
    zero fingerprint divergence under strict mode; then the lagging
    rank "upgrades" (compat lifted) and the whole world rejoins under a
    fresh epoch, negotiating the native schema again."""
    from horovod_tpu import core as _core
    from horovod_tpu.common import wire as _wire
    from horovod_tpu.runner.network import PeerMesh as _PeerMesh

    def _meshes():
        return [r for r in _core.global_state().resources
                if isinstance(r, _PeerMesh)]

    def _steps(tag):
        t = np.ones(256, np.float32) * (rank + 1)
        want = np.ones(256, np.float32) * (size * (size + 1) / 2)
        for i in range(4):
            out = hvd.allreduce(t, op=hvd.Sum, name=f"{tag}{i}")
            np.testing.assert_allclose(np.asarray(out), want)

    meshes = _meshes()
    assert meshes, "no TCP meshes formed"
    for m in meshes:
        assert m.negotiated_proto == 1, m.negotiated_proto
        assert m.negotiated_features == 0, m.negotiated_features
        assert m.peer_protos, m.peer_protos
    _steps("rollold")

    # The old rank upgrades: drain, lift the compat pin, rejoin at N+1.
    hvd.shutdown()
    os.environ.pop("HOROVOD_PROTO_COMPAT", None)
    os.environ["HOROVOD_RENDEZVOUS_EPOCH"] = \
        os.environ.get("HOROVOD_RENDEZVOUS_EPOCH", "0") + "~u1"
    hvd.init()
    meshes = _meshes()
    assert meshes
    for m in meshes:
        assert m.negotiated_proto == _wire.PROTO_VERSION
        assert m.negotiated_features == _wire.FEATURES_ALL
    _steps("rollnew")
    print(f"ROLLING_OK rank={rank} proto "
          f"1->{_wire.PROTO_VERSION}", flush=True)


def battery_statesync_grow(hvd, rank, size):
    """ISSUE 10 acceptance (4-rank, rides 4->3->4): chaos SIGKILLs rank
    2 mid-training; survivors shrink with zero failed steps after the
    conversion, then launch-rank 0 spawns a replacement process that
    joins via peer state streaming — incumbents never fail a step while
    it catches up, and after the grow every rank's state is
    bit-identical (digest-exchanged in-battery)."""
    import subprocess as _subprocess
    import sys as _sys
    import time as _time

    from horovod_tpu import statesync

    state = _statesync_state()
    svc = statesync.StateSyncService(lambda: state)
    shrunk = grown = False
    stop_at = None
    joiner_proc = None
    launch_rank = rank
    deadline = _time.monotonic() + 150.0
    while _time.monotonic() < deadline:
        try:
            _statesync_train_step(hvd, state)
            change = svc.step_boundary()
        except hvd.RanksFailedError as exc:
            assert not shrunk, f"step failed AFTER the shrink: {exc}"
            change = svc.shrink_on_failure(exc)
        if change is not None and change.kind == "shrink":
            shrunk = True
            assert hvd.size() == size - 1, hvd.size()
            assert 2 in change.dead, change
            # Realign replicated state: survivors may have caught the
            # kill on different steps (one applied the last update, one
            # did not) — the most-advanced rank is the authority.
            state = statesync.resync_replicated(state,
                                                int(state["step"]))
            if hvd.rank() == 0:
                env = dict(os.environ)
                for k in ("HOROVOD_CHAOS", "HOROVOD_RANK",
                          "HOROVOD_SIZE"):
                    env.pop(k, None)
                joiner_proc = _subprocess.Popen(
                    [_sys.executable, os.path.abspath(__file__),
                     "0", "0",
                     os.environ["HOROVOD_GLOO_RENDEZVOUS_PORT"],
                     "statesync_joiner"],
                    env=env, stdout=_subprocess.PIPE,
                    stderr=_subprocess.STDOUT)
        elif change is not None and change.kind == "grow":
            grown = True
            assert shrunk, "grew before the shrink?"
            assert hvd.size() == size, hvd.size()
            stop_at = int(state["step"]) + 3
        if stop_at is not None and int(state["step"]) >= stop_at:
            break
    assert shrunk and grown, (shrunk, grown)
    _statesync_digest_check(hvd, state)
    _statesync_witness_dump("grow battery", launch_rank)
    svc.close()
    if joiner_proc is not None:
        out, _ = joiner_proc.communicate(timeout=60.0)
        text = out.decode(errors="replace")
        print("--- joiner output ---\n" + text)
        assert joiner_proc.returncode == 0, \
            f"joiner failed rc={joiner_proc.returncode}:\n{text}"
        assert "joiner: catch-up" in text
    print(f"launch rank {launch_rank}: rode {size}->{size - 1}->{size} "
          f"to step {int(state['step'])} with zero failed "
          f"post-shrink steps")


def battery_statesync_joiner(port):
    """The replacement rank of the grow battery: runs BEFORE hvd.init —
    join_world streams state from the live donors, verifies it, and
    enters the world; then it trains in lockstep with the incumbents."""
    import time as _time

    from horovod_tpu import statesync

    t0 = _time.monotonic()
    template = _statesync_state()
    tree, info = statesync.join_world(template)
    import horovod_tpu as hvd

    assert hvd.is_initialized() and hvd.rank() == info.rank
    # Bit-identical to the donors' snapshot: recompute the digest of
    # the assembled state against the unanimous stamp (the acceptance
    # criterion's independent check; pull_round verified it once).
    image = statesync.flatten_state(tree)
    assert statesync.state_digest(image) == info.stamp.digest
    # Bounded catch-up: the bulk transfer from N donors in parallel
    # must cost no more than ~one donor's own streaming time (x2 +
    # formation slack) — the sharded-stream win over a single source.
    max_donor_s = max((w for _, w in info.donor_stats.values()),
                      default=0.0)
    bulk_s = info.catch_up_ms / 1e3
    assert bulk_s < 2.0 * max_donor_s + 10.0, \
        (bulk_s, max_donor_s, info.donor_stats)
    state = tree
    svc = statesync.StateSyncService(lambda: state)
    stop_at = int(state["step"]) + 3
    while int(state["step"]) < stop_at:
        _statesync_train_step(hvd, state)
        svc.step_boundary()
    _statesync_digest_check(hvd, state)
    _statesync_witness_dump("grow battery joiner", "J")
    if os.environ.get("HOROVOD_LIFE_CENSUS") == "1":
        # The life battery's census-done sync: incumbents census their
        # fabric after the last training step; this rank must not tear
        # the shared world down under them (see battery_statesync_life).
        hvd.allgather_object("J", name="life.census.done")
    svc.close()
    print(f"joiner: catch-up {info.catch_up_ms:.0f} ms for "
          f"{info.bulk_bytes} bytes from {len(info.donor_stats)} "
          f"donors; entered as rank {info.rank}/{info.size} at step "
          f"{stop_at - 3}; total wall "
          f"{_time.monotonic() - t0:.1f}s")
    hvd.shutdown()
    return 0


def battery_statesync_preempt(hvd, rank, size):
    """ISSUE 10 SIGTERM-grace acceptance (3-rank): chaos delivers
    SIGTERM to rank 1 mid-training.  The preempted rank finishes its
    in-flight step, announces departure through the boundary check,
    fast-donates its opt state, writes bye| and exits 0; survivors
    shrink PROACTIVELY at the same boundary — no RanksFailedError is
    ever raised, and the heartbeat monitor never declares rank 1
    failed."""
    import time as _time

    from horovod_tpu import resilience, statesync
    from horovod_tpu.runner.network import RendezvousClient

    state = _statesync_state(n=1 << 12)
    svc = statesync.StateSyncService(
        lambda: state,
        donate_provider=lambda: {"shard": state["opt"]})
    kv = RendezvousClient("127.0.0.1",
                          int(os.environ["HOROVOD_GLOO_RENDEZVOUS_PORT"]),
                          20.0)
    launch_rank = rank
    shrunk_at = None
    pre_epoch = os.environ["HOROVOD_RENDEZVOUS_EPOCH"]
    deadline = _time.monotonic() + 60.0
    while _time.monotonic() < deadline:
        prev_epoch = os.environ["HOROVOD_RENDEZVOUS_EPOCH"]
        # No try/except: ANY RanksFailedError here fails the battery —
        # the whole point of grace is that survivors never see one.
        _statesync_train_step(hvd, state)
        change = svc.step_boundary()
        if change is not None and change.kind == "departed":
            assert launch_rank == 1, launch_rank
            raw = kv.get("hb", f"{prev_epoch}:1")
            assert raw is not None and raw.startswith(b"bye|"), raw
            _statesync_witness_dump("preempt battery departed",
                                    launch_rank)
            print("preempted rank: departed with bye| stamp inside "
                  "the grace window")
            return
        if change is not None and change.kind == "shrink":
            assert change.dead == (1,), change
            assert hvd.size() == size - 1
            shrunk_at = int(state["step"])
            # The departed rank's fast-donated opt shard is fetchable
            # and digest-verified.
            donated = statesync.fetch_donation(
                prev_epoch, 1, {"shard": np.zeros_like(state["opt"])},
                kv=kv)
            assert donated is not None
            state = statesync.resync_replicated(state,
                                                int(state["step"]))
        if shrunk_at is not None and int(state["step"]) >= shrunk_at + 3:
            break
    assert shrunk_at is not None, "the preemption never happened"
    st = resilience.active_state()
    assert st is None or not st.failed_ranks(), \
        f"proactive shrink must beat the heartbeat: {st.failed_ranks()}"
    assert os.environ["HOROVOD_RENDEZVOUS_EPOCH"] != pre_epoch
    _statesync_witness_dump("preempt battery survivor", launch_rank)
    svc.close()
    print(f"survivor {launch_rank}: proactive shrink at step "
          f"{shrunk_at}, no RanksFailedError anywhere")


def battery_statesync_life(hvd, rank, size):
    """ISSUE 13 acceptance battery (4-rank, rides 4->3->4 via
    statesync): every survivor censuses its live thread/fd/socket/mmap
    fabric before and after one full grow-shrink cycle, with the
    seeded HVD704 epoch-leak fixture ARMED — one real socket leaks per
    world transition.  The runtime census witness must (a) catch
    EXACTLY the seeded drift (+2 sockets on survivors, nothing else),
    proving the dynamic half fires on the same leak the static rule
    flags, and (b) census baseline-equal once the seed is released,
    proving the product fabric itself leaks nothing across elastic
    reinit cycles."""
    import importlib.util
    import subprocess as _subprocess
    import sys as _sys
    import time as _time

    from census import settle_census, stable_snapshot

    from horovod_tpu import statesync
    from horovod_tpu.analysis.hvdlife import census as life_census

    fixture_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "fixtures", "lint", "life", "epoch_leak.py")
    spec = importlib.util.spec_from_file_location("epoch_leak_fx",
                                                  fixture_path)
    leak = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(leak)
    # NOT armed yet: the first leak.reinit_world() below fires at the
    # first world transition, so the baseline census predates every
    # leaked socket and release_all() returns exactly to it.

    state = _statesync_state()
    svc = statesync.StateSyncService(lambda: state)
    launch_rank = rank
    # Warm the fabric so the lazy machinery (sender lanes) exists on
    # both sides of the comparison, then baseline.
    for _ in range(3):
        _statesync_train_step(hvd, state)
        svc.step_boundary()
    baseline = stable_snapshot(f"baseline:world{size}")
    w = life_census.witness()
    assert w.enabled, "battery must run under HOROVOD_LIFE_CENSUS=1"
    w.snapshots.append(baseline)
    w.rank = launch_rank

    shrunk = grown = False
    stop_at = None
    joiner_proc = None
    transitions = 0
    deadline = _time.monotonic() + 150.0
    while _time.monotonic() < deadline:
        try:
            _statesync_train_step(hvd, state)
            change = svc.step_boundary()
        except hvd.RanksFailedError as exc:
            assert not shrunk, f"step failed AFTER the shrink: {exc}"
            change = svc.shrink_on_failure(exc)
        if change is not None and change.kind in ("shrink", "grow"):
            # The seeded leak: one unreleased socket per world epoch.
            leak.reinit_world()
            transitions += 1
        if change is not None and change.kind == "shrink":
            shrunk = True
            assert hvd.size() == size - 1, hvd.size()
            state = statesync.resync_replicated(state,
                                                int(state["step"]))
            if hvd.rank() == 0:
                env = dict(os.environ)
                for k in ("HOROVOD_CHAOS", "HOROVOD_RANK",
                          "HOROVOD_SIZE"):
                    env.pop(k, None)
                joiner_proc = _subprocess.Popen(
                    [_sys.executable, os.path.abspath(__file__),
                     "0", "0",
                     os.environ["HOROVOD_GLOO_RENDEZVOUS_PORT"],
                     "statesync_joiner"],
                    env=env, stdout=_subprocess.PIPE,
                    stderr=_subprocess.STDOUT)
        elif change is not None and change.kind == "grow":
            grown = True
            assert hvd.size() == size, hvd.size()
            stop_at = int(state["step"]) + 3
        if stop_at is not None and int(state["step"]) >= stop_at:
            break
    assert shrunk and grown and transitions == 2, \
        (shrunk, grown, transitions)
    _statesync_digest_check(hvd, state)

    # (a) The census catches the seeded leak — and ONLY it: the diff
    # against the size-4 baseline settles to exactly the two leaked
    # sockets (threads, shm, and the product's own sockets all
    # returned; the watcher/heartbeat KV polls flicker a transient
    # socket, which settle_census rides out).
    leak.shutdown()                  # the seeded teardown: releases nothing
    expected_drift = (f"sockets: {baseline['sockets']} -> "
                      f"{baseline['sockets'] + 2} (+2)",)
    armed = settle_census(baseline, expect=expected_drift,
                          label=f"armed:world{size}",
                          context=f"launch rank {launch_rank}")
    w.snapshots.append(armed)
    assert leak.leaked_count() == 2
    print(f"launch rank {launch_rank}: census caught the seeded "
          f"epoch leak: {expected_drift[0]}")

    # (b) Release the seed: the fabric itself is baseline-equal after
    # a full 4->3->4 cycle.
    leak.release_all()
    final = settle_census(baseline, expect=(),
                          label=f"baseline:world{size}:final",
                          context=f"4->{size - 1}->4 cycle, launch "
                                  f"rank {launch_rank}")
    w.snapshots.append(final)
    # Census-done sync: until EVERY rank (joiner included) has taken
    # its final census, nobody may start shutdown — a peer's shutdown
    # broadcast retires this rank's background loop mid-census and the
    # settle loop would read it as a lost thread.
    hvd.allgather_object(launch_rank, name="life.census.done")
    path = life_census.dump_census()
    if path:
        print(f"CENSUS_DUMP {path}")
    svc.close()
    if joiner_proc is not None:
        out, _ = joiner_proc.communicate(timeout=60.0)
        text = out.decode(errors="replace")
        print("--- joiner output ---\n" + text)
        assert joiner_proc.returncode == 0, \
            f"joiner failed rc={joiner_proc.returncode}:\n{text}"
    print(f"launch rank {launch_rank}: census baseline-equal after "
          f"{size}->{size - 1}->{size} at step {int(state['step'])}")


_SERVE_GROW_CFG = dict(max_batch=4, token_budget=64, max_seq=64,
                       slo_ms=120000.0)


def _serve_grow_submit(ex, seed, count):
    import random as _random

    rng = _random.Random(seed)
    for _ in range(count):
        toks = [rng.randrange(2, ex.model.cfg.vocab_size)
                for _ in range(rng.randint(2, 10))]
        ex.stats["offered"] += 1
        assert ex.queue.submit(toks, 10) is not None


def battery_statesync_serve(hvd, rank, size):
    """Serving grow mid-serve (2->3): a joiner replica enters via param
    streaming while requests are in flight (the incumbents' params are
    perturbed away from the seed, so the stream is the only way to
    match them), then a second request wave is served by the grown
    world — the front end's report records world.grows and positive
    goodput before/during/after."""
    import subprocess as _subprocess
    import sys as _sys

    import jax
    import jax.numpy as jnp

    from horovod_tpu import statesync
    from horovod_tpu.serving import ReplicaExecutor, ServeConfig
    from horovod_tpu.serving.loadgen import _goodput_phases
    from horovod_tpu.serving.replica import serving_params_template

    cfg = ServeConfig.from_env(**_SERVE_GROW_CFG)
    tmpl = serving_params_template(cfg)
    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a + 0.25),
                                    tmpl["params"])
    ex = ReplicaExecutor(cfg, params=params)
    service = statesync.StateSyncService(state_provider=ex.state_tree,
                                         static_state=True)
    ex.attach_statesync(service)
    joiner_proc = None
    if rank == 0:
        _serve_grow_submit(ex, 11, 24)
        env = dict(os.environ)
        for k in ("HOROVOD_RANK", "HOROVOD_SIZE"):
            env.pop(k, None)
        joiner_proc = _subprocess.Popen(
            [_sys.executable, os.path.abspath(__file__), "0", "0",
             os.environ["HOROVOD_GLOO_RENDEZVOUS_PORT"],
             "statesync_serve_joiner"],
            env=env, stdout=_subprocess.PIPE, stderr=_subprocess.STDOUT)
    # Phase 1: serve the first wave until the joiner has entered (the
    # front end keeps assembling plans while it streams — goodput never
    # goes to zero) and the wave drained.
    ex.serve_loop(stop_when=lambda: bool(ex.stats["grows"]))
    assert ex.stats["grows"], "the joiner never entered"
    assert ex.size == size + 1, ex.size
    assert not ex.stats["shrinks"]
    # Phase 2: a post-grow wave, served by the grown world (the joiner
    # runs the same second serve_loop and exits on its plan.stop).
    ex._stop_requested = False
    if ex.rank == ex.front:
        _serve_grow_submit(ex, 13, 12)
    ex.serve_loop(stop_when=lambda: True)
    assert ex.stats["local_exchanges"] == 0, ex.stats   # 2, then 3 ranks
    if rank == 0:
        st = ex.stats
        assert st["served"] == st["offered"] == 36, st
        assert st["lost"] == 0 and st["expired"] == 0, st
        phases = _goodput_phases(ex, 1.0)
        assert phases is not None and phases["after_rps"] > 0.0, phases
        g = st["grows"][0]
        assert g["from"] == size and g["to"] == size + 1, g
        out, _ = joiner_proc.communicate(timeout=60.0)
        text = out.decode(errors="replace")
        print("--- serve joiner output ---\n" + text)
        assert joiner_proc.returncode == 0, text
        assert "streamed params verified" in text
        print(f"serving grow: {st['served']} served across "
              f"{size}->{size + 1}; goodput phases {phases}")
    service.close()


def battery_statesync_serve_joiner(port):
    """The serving joiner: streams the incumbents' perturbed params,
    enters mid-serve, and serves both phases until the front drains."""
    import jax
    import numpy as _np

    from horovod_tpu.serving import ServeConfig
    from horovod_tpu.serving.replica import (join_serving_world,
                                             serving_params_template)

    cfg = ServeConfig.from_env(**_SERVE_GROW_CFG)
    ex = join_serving_world(cfg)
    # The streamed params must be the incumbents' PERTURBED values —
    # the seed template plus 0.25 — not anything derivable locally.
    mine = _np.asarray(jax.tree_util.tree_leaves(ex.params)[0])
    seed = _np.asarray(jax.tree_util.tree_leaves(
        serving_params_template(cfg)["params"])[0])
    _np.testing.assert_allclose(mine, seed + 0.25, rtol=0, atol=1e-6)
    print("serve joiner: streamed params verified (seed + 0.25)")
    import horovod_tpu as hvd

    ex.serve_loop()                    # phase 1: exits on plan.stop
    ex._stop_requested = False
    ex.serve_loop()                    # phase 2
    print(f"serve joiner: entered as rank {ex.rank}/{ex.size}, "
          f"served group {ex.group}, completed "
          f"{len(ex.completed)} locally")
    ex.statesync.close()
    hvd.shutdown()
    return 0


def _battery_fleet_train(port):
    """ISSUE 20 fleet battery, training side (launch ranks 0-2, world
    size 3): rank 0 hosts the FleetController + WeightPublisher; the
    serving burst drives a train->serve migration of rank 2 (orderly
    statesync departure — no RanksFailedError), survivors keep
    training and publishing snapshots until the serving front posts
    the done flag."""
    import time as _time

    import jax

    from horovod_tpu import statesync
    from horovod_tpu.fleet import (FleetController, FleetPolicy,
                                   WeightPublisher, poll_depart,
                                   publish_gauge)
    from horovod_tpu.runner.network import RendezvousClient

    launch_rank = int(sys.argv[1])
    os.environ["HOROVOD_SIZE"] = "3"
    os.environ["HOROVOD_STATESYNC_WORLD"] = "train"
    import horovod_tpu as hvd

    hvd.init()
    kv = RendezvousClient("127.0.0.1", port, 20.0)
    state = _statesync_state(n=1 << 10)
    svc = statesync.StateSyncService(
        lambda: state,
        donate_provider=lambda: {"shard": state["opt"]})
    ctl = pub = ptree = None
    if launch_rank == 0:
        from horovod_tpu.serving import ServeConfig
        from horovod_tpu.serving.replica import serving_params_template

        # The continuously-deployed params are serving-model-shaped:
        # the publisher's snapshot must unflatten into the replicas'
        # param template bit-for-bit.
        ptree = serving_params_template(
            ServeConfig.from_env(**_SERVE_GROW_CFG))
        policy = FleetPolicy(min_train=2, min_serve=1,
                             hysteresis_rounds=2, cooldown_rounds=1000,
                             up_shed_rate=0.05, up_queue_fraction=0.25,
                             idle_queue_fraction=0.01,
                             train_lag_ms=1e9, queue_depth_limit=8)
        ctl = FleetController(kv, policy, interval_s=0.25,
                              migrate_timeout_s=240.0)
        ctl.start()
        pub = WeightPublisher(kv, publish_steps=5, chunk_bytes=1 << 14,
                              keep=10)
        pub.start()
    directive = None
    shrunk = False
    departed = False
    step = 0
    deadline = _time.monotonic() + 300.0
    while True:
        # Bare collectives: any RanksFailedError fails the battery —
        # the migration must ride the orderly-departure boundary.
        _statesync_train_step(hvd, state)
        change = svc.step_boundary()
        step += 1
        if change is not None and change.kind == "departed":
            departed = True
            break
        if change is not None and change.kind == "shrink":
            assert change.dead == (2,), change
            assert hvd.size() == 2
            shrunk = True
            state = statesync.resync_replicated(state,
                                                int(state["step"]))
        if launch_rank == 0:
            ptree = {"params": jax.tree_util.tree_map(
                lambda a: np.asarray(a) + np.float32(0.001),
                ptree["params"])}
            pub.maybe_publish(step, ptree)
            publish_gauge(kv, "train", hvd.size(),
                          straggler_lag_ms=0.0)
        if directive is None:
            directive = poll_depart(kv, "train", hvd.rank())
            if directive is not None:
                svc.request_depart()
        # The trainers stop at the SAME step, on a vote that every step
        # takes in the order of their collectives: each polls the front's
        # flag (and rank 0 its journal record) on its own, and one that
        # left a step earlier, or on its own clock, would shut down under
        # its peer's next allreduce.  Ready once the front posted done and
        # the controller closed the record; the deadline only guards.
        ready = kv.get("fleet.test", "done") is not None and (
            launch_rank != 0 or ctl.stats["completed"] > 0)
        late = _time.monotonic() > deadline
        votes = hvd.allreduce(np.asarray([ready, late], np.float32),
                              op=hvd.Sum, name="fleet.stop")
        if votes[0] == hvd.size() or votes[1] > 0:
            break
        _time.sleep(0.1)
    if departed:
        assert launch_rank == 2 and directive is not None, \
            (launch_rank, directive)
        svc.close()
        hvd.shutdown()
        return _battery_fleet_mover(port, int(directive["mid"]))
    assert shrunk, "the migration never happened"
    assert votes[0] == hvd.size(), \
        f"no done flag and closed journal record in 300 s: {votes}"
    if launch_rank == 0:
        # The controller observed the joined mark and closed the
        # journal record (done) — one migration, zero aborts.
        assert ctl.stats["migrations"] == 1, ctl.stats
        assert ctl.stats["completed"] == 1, ctl.stats
        assert ctl.stats["aborted"] == 0, ctl.stats
        assert pub.published >= 2, pub.published
        pub.drain()
        pub.close()
        ctl.stop()
        print(f"fleet trainer 0: migration journal closed "
              f"{ctl.stats}; {pub.published} snapshots published")
    _statesync_witness_dump("fleet battery trainer", launch_rank)
    svc.close()
    print(f"fleet trainer {launch_rank}: survived 3->2 at step "
          f"{int(state['step'])}, no RanksFailedError anywhere")
    return 0


def _battery_fleet_mover(port, mid):
    """The moved rank's second life: after the orderly train-world
    departure it joins the serving world via peer-streamed state,
    writes the joined mark that closes the controller's journal
    record, and serves until the front drains — swapping in published
    weights at the same broadcast plan boundaries as the incumbent."""
    import jax

    from horovod_tpu.fleet import mark_joined
    from horovod_tpu.runner.network import RendezvousClient
    from horovod_tpu.serving import ServeConfig
    from horovod_tpu.serving.replica import join_serving_world
    from horovod_tpu.statesync.snapshot import (flatten_state,
                                                state_digest)

    base = os.environ["HOROVOD_RENDEZVOUS_EPOCH"].split("~", 1)[0]
    os.environ["HOROVOD_RENDEZVOUS_EPOCH"] = f"{base}~serve"
    os.environ["HOROVOD_STATESYNC_WORLD"] = "serve"
    os.environ.pop("HOROVOD_RANK", None)
    os.environ.pop("HOROVOD_SIZE", None)
    kv = RendezvousClient("127.0.0.1", port, 20.0)
    cfg = ServeConfig.from_env(**_SERVE_GROW_CFG)
    ex = join_serving_world(cfg)
    mark_joined(kv, mid, rank=ex.rank, size=ex.size)
    ex.attach_fleet(kv, interval_s=0.1)
    import horovod_tpu as hvd

    ex.serve_loop()                    # exits on the front's plan.stop
    assert ex.weight_version >= 1, \
        "no weight push landed on the moved replica"
    last = ex.stats["weight_swaps"][-1]
    assert last["version"] == ex.weight_version, ex.stats
    image = flatten_state({"params": jax.tree_util.tree_map(
        np.asarray, ex.params)})
    assert state_digest(image) == last["digest"], \
        "post-swap params diverge from the published snapshot digest"
    print(f"fleet mover: joined serving as rank {ex.rank}/{ex.size} "
          f"(mig {mid}), swapped to v{ex.weight_version}, digest "
          f"verified")
    _statesync_witness_dump("fleet battery mover", 2)
    ex.close()
    ex.statesync.close()
    hvd.shutdown()
    return 0


def _battery_fleet_serve(port):
    """ISSUE 20 fleet battery, serving side (launch rank 3 = the
    size-1 serving world's front): a request burst overloads the
    queue gauge, the controller migrates a trainer rank in (1->2
    grow mid-serve), and the continuously-deployed weights roll out
    to every replica at one broadcast plan boundary — zero failed
    admitted requests, goodput phases recorded."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu import statesync
    from horovod_tpu.fleet import publish_gauge
    from horovod_tpu.runner.network import RendezvousClient
    from horovod_tpu.serving import ReplicaExecutor, ServeConfig
    from horovod_tpu.serving.loadgen import _goodput_phases
    from horovod_tpu.serving.replica import serving_params_template

    base = os.environ["HOROVOD_RENDEZVOUS_EPOCH"]
    os.environ["HOROVOD_RENDEZVOUS_EPOCH"] = f"{base}~serve"
    os.environ["HOROVOD_RANK"] = "0"
    os.environ["HOROVOD_SIZE"] = "1"
    os.environ["HOROVOD_STATESYNC_WORLD"] = "serve"
    import horovod_tpu as hvd

    hvd.init()
    kv = RendezvousClient("127.0.0.1", port, 20.0)
    cfg = ServeConfig.from_env(**_SERVE_GROW_CFG)
    tmpl = serving_params_template(cfg)
    ex = ReplicaExecutor(cfg, params=jax.tree_util.tree_map(
        jnp.asarray, tmpl["params"]))
    service = statesync.StateSyncService(state_provider=ex.state_tree,
                                         static_state=True)
    ex.attach_statesync(service)
    ex.attach_fleet(kv, interval_s=0.1)
    _serve_grow_submit(ex, 11, 24)     # the traffic burst
    progress = {"v_at_grow": None, "wave": 100}

    def tick():
        # The front's per-step gauge publish IS the policy's input:
        # outstanding work (queued + in-flight) over the configured
        # depth limit is what the controller's policy thresholds.
        depth = float(ex.queue.depth() + ex.batcher.inflight_count())
        publish_gauge(kv, "serve", ex.size, shed_rate=0.0,
                      queue_depth=depth)
        if not ex.stats["grows"]:
            if depth < 4:
                # Keep the burst hot until the migration lands — the
                # policy needs the overload to hold across its
                # hysteresis window.
                progress["wave"] += 1
                _serve_grow_submit(ex, progress["wave"], 4)
            return False
        if progress["v_at_grow"] is None:
            progress["v_at_grow"] = ex.weight_version
            _serve_grow_submit(ex, 13, 12)   # post-migration wave
        # Drain only after a weight push landed post-grow: the swap
        # is scheduled at min(staged) across ranks, so reaching it
        # proves the rollout hit the moved replica too.
        return ex.weight_version > progress["v_at_grow"]

    ex.serve_loop(stop_when=tick)
    st = ex.stats
    assert ex.size == 2 and st["grows"], (ex.size, st["grows"])
    g = st["grows"][0]
    assert g["from"] == 1 and g["to"] == 2, g
    # Alone until the grow: those steps' exchanges stayed in the process,
    # every later one went through the collectives.
    assert 0 < st["local_exchanges"] < st["exchanges"], st
    assert st["offered"] >= 36, st
    assert st["served"] == st["offered"], st
    assert st["lost"] == 0 and st["expired"] == 0, st
    phases = _goodput_phases(ex, 1.0)
    assert phases is not None and phases["after_rps"] > 0.0, phases
    assert st["weight_swaps"], st
    last = st["weight_swaps"][-1]
    assert last["version"] == ex.weight_version \
        > progress["v_at_grow"], (last, progress)
    image = statesync.flatten_state({"params": jax.tree_util.tree_map(
        np.asarray, ex.params)})
    assert statesync.state_digest(image) == last["digest"], \
        "post-swap params diverge from the published snapshot digest"
    kv.put("fleet.test", "done", b"1")
    print(f"fleet front: {st['served']} served across 1->2 with "
          f"rollout to v{ex.weight_version}; goodput phases {phases}")
    dump_dir = os.environ.get("HOROVOD_FLEET_DUMP_DIR")
    if dump_dir:
        # Console-fixture capture (tests/fixtures/console/regen_fleet
        # .py): the front's loadgen report is the goodput/weights
        # evidence the == fleet == panel renders.
        from horovod_tpu.serving import loadgen

        report = loadgen.build_report(
            ex, offered=st["offered"], wall_s=1.0,
            args_echo={"battery": "fleet"})
        loadgen.write_report(
            report, os.path.join(dump_dir, "SERVE_r{rank}.json"), 0)
    _statesync_witness_dump("fleet battery front", 3)
    ex.close()
    service.close()
    hvd.shutdown()
    return 0


def battery_fleet(port):
    """ISSUE 20 acceptance (4 launch ranks, PRE-INIT): two statesync
    worlds on ONE coordinator KV — launch ranks 0-2 train, launch
    rank 3 serves.  A serving burst triggers a traffic-driven
    train->serve migration AND a mid-run weight push lands on every
    serving replica at one broadcast plan boundary."""
    launch_rank = int(sys.argv[1])
    if launch_rank == 3:
        return _battery_fleet_serve(port)
    return _battery_fleet_train(port)


BATTERIES = {
    "collectives": battery_collectives,
    "serving": battery_serving,
    "serving_paged": battery_serving_paged,
    "serving_disagg": battery_serving_disagg,
    "san": battery_san,
    "trace": battery_trace,
    "telemetry": battery_telemetry,
    "perfscope": battery_perfscope,
    "streams": battery_streams,
    "matrix": battery_matrix,
    "autotune": battery_autotune,
    "stall": battery_stall,
    "xla": battery_xla,
    "errors": battery_errors,
    "join": battery_join,
    "adasum": battery_adasum,
    "adasum_np": battery_adasum_np,
    "torch": battery_torch,
    "torch_grid": battery_torch_grid,
    "syncbn": battery_syncbn,
    "tensorflow": battery_tensorflow,
    "tf_grid": battery_tf_grid,
    "tf_function": battery_tf_function,
    "sparse": battery_sparse,
    # Merged one-world batteries: the torch/TF imports (~8-12 s per
    # spawned rank) dominated separate 2-rank worlds, so the 2-rank
    # coverage shares one spin-up per framework (the reference CI
    # likewise groups framework tests per container,
    # .buildkite/gen-pipeline.sh); the 3- and 4-rank worlds stay
    # separate.
    "torch_all": lambda hvd, rank, size: [
        battery_torch(hvd, rank, size),
        battery_torch_grid(hvd, rank, size),
        battery_sparse(hvd, rank, size),
        battery_syncbn(hvd, rank, size)],
    "tensorflow_all": lambda hvd, rank, size: [
        battery_tensorflow(hvd, rank, size),
        battery_tf_grid(hvd, rank, size),
        battery_tf_function(hvd, rank, size)],
    "rolling": battery_rolling,
    "hierarchical": battery_hierarchical,
    "shm": battery_shm,
    "compress": battery_compress,
    "compress_shm": battery_compress_shm,
    "compress_xla": battery_compress_xla,
    "mxnet": battery_mxnet,
    "peerdeath": battery_peerdeath,
    # resilience/ chaos batteries (ISSUE 5): every one runs under the
    # hard timeout guard in tests/test_resilience.py so a regression
    # re-introducing a deadlock fails fast.
    "resilience_kill": battery_resilience_kill,
    "resilience_retry": battery_resilience_retry,
    "resilience_freeze": battery_resilience_freeze,
    "resilience_off": battery_resilience_off,
    # statesync/ elastic-grow batteries (ISSUE 10).  The *_joiner
    # entries are PRE-INIT batteries: main() dispatches them before
    # hvd.init — join_world performs its own world entry.
    "statesync_grow": battery_statesync_grow,
    "statesync_preempt": battery_statesync_preempt,
    "statesync_serve": battery_statesync_serve,
    # hvdlife runtime census witness (ISSUE 13): the 4->3->4 cycle must
    # census baseline-equal on every survivor, and the seeded HVD704
    # fixture must be caught by the census diff.
    "statesync_life": battery_statesync_life,
    # hvdflow runtime cross-check (ISSUE 12): the seeded rank-gated
    # collective must die as a structured fingerprint ERROR, not a hang.
    "flow": battery_flow,
    # hvdshard runtime cross-check (ISSUE 17): the seeded spec-divergent
    # collective dies under op×spec identity; the proto-2 mixed world
    # negotiates sp_* off and stays green on the same step.
    "shard": battery_shard,
    "shard_compat": battery_shard_compat,
    # ISSUE 18: autotuned algo x tree-threshold sweep, negotiated
    # end-to-end through ResponseList.tuned_algo.
    "algotune": battery_algotune,
}

def battery_fleetsim(port):
    """ISSUE 16 fleet-scale acceptance: ONE worker process hosts the
    whole virtual fleet — hundreds of protocol-only ranks running the
    real rendezvous client / heartbeat / membership paths against the
    external (possibly replicated) control plane, with chaos from
    HOROVOD_CHAOS composing unchanged.  Pre-init: the fleet never calls
    hvd.init (no tensor data plane).  Prints the FLEETSIM_SUMMARY line
    the test asserts on; rc 0 iff zero failed steps."""
    from horovod_tpu.fleetsim.__main__ import main as fleet_main
    return fleet_main()


PREINIT_BATTERIES = {
    "statesync_joiner": battery_statesync_joiner,
    "statesync_serve_joiner": battery_statesync_serve_joiner,
    # ISSUE 20: unified train+serve fleet — launch ranks enter their
    # own worlds (two statesync worlds, one coordinator KV), and the
    # moved rank re-enters the other world mid-battery.
    "fleet": battery_fleet,
    # ISSUE 16: the rank-virtualized fleet harness (one process = the
    # whole fleet; `size` counts host processes, not virtual ranks).
    "fleetsim": battery_fleetsim,
}


def main() -> int:
    rank, size, port = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
    battery = sys.argv[4] if len(sys.argv) > 4 else "collectives"
    os.environ["HOROVOD_RANK"] = str(rank)
    os.environ["HOROVOD_SIZE"] = str(size)
    # A replicated-control-plane harness passes a multi-endpoint seed
    # list through the env; plain worlds get the localhost default
    # (test_multiprocess._run_world pops any stale inherited value).
    os.environ.setdefault("HOROVOD_GLOO_RENDEZVOUS_ADDR", "127.0.0.1")
    os.environ["HOROVOD_GLOO_RENDEZVOUS_PORT"] = str(port)
    # Generous under CI load: a peer may still be importing torch/tf when
    # this rank reaches rendezvous.
    os.environ.setdefault("HOROVOD_GLOO_TIMEOUT_SECONDS", "90")
    if battery == "fleetsim":
        # The whole fleet lives in THIS process: metrics + flight on so
        # the episode leaves console-renderable rank-stamped evidence.
        os.environ.setdefault("HOROVOD_METRICS", "on")
        _dump = os.environ.get("HOROVOD_FLEETSIM_DUMP_DIR")
        if _dump:
            # The dump dir owns the episode's evidence: force the
            # flight file into it (an inherited default — e.g. the
            # pytest conftest's — would strand the flight dump outside
            # the directory the console is pointed at).
            os.environ["HOROVOD_FLIGHT_FILE"] = \
                os.path.join(_dump, "flight.json")
    if battery == "stall":
        os.environ["HOROVOD_STALL_CHECK_TIME_SECONDS"] = "1"
        os.environ["HOROVOD_STALL_SHUTDOWN_TIME_SECONDS"] = "3"
    if battery == "rolling":
        # Rank 1 is the still-old framework version: it advertises wire
        # proto 1, so every mesh negotiates the base schema until the
        # battery lifts the pin mid-run (the rolling upgrade).  Strict
        # fingerprinting turns any schema asymmetry into a structured
        # divergence ERROR within one cycle.
        if rank == 1:
            os.environ["HOROVOD_PROTO_COMPAT"] = "1"
        os.environ.setdefault("HOROVOD_FINGERPRINT", "strict")
        os.environ["HOROVOD_SHM_OPERATIONS"] = "0"
    if battery == "flow":
        # Strict mode: divergence surfaces within one forced
        # negotiation heartbeat even in cache steady state.
        os.environ.setdefault("HOROVOD_FINGERPRINT", "strict")
        os.environ.setdefault("HOROVOD_FLOW_SEED_RANK", "2")
    if battery in ("shard", "shard_compat"):
        # Strict mode so the op×spec divergence (or, in the compat
        # world, its negotiated absence) is judged every cycle.
        os.environ.setdefault("HOROVOD_FINGERPRINT", "strict")
    if battery == "shard_compat":
        # Rank 1 is the pre-sharding framework version: proto 2 carries
        # fp_/tm_/trace_ but not sp_*, so every mesh negotiates
        # FEATURE_SHARDING off and both ranks fold 5-column identity.
        if rank == 1:
            os.environ["HOROVOD_PROTO_COMPAT"] = "2"
    if battery == "autotune":
        os.environ["HOROVOD_AUTOTUNE"] = "1"
        os.environ["HOROVOD_AUTOTUNE_WARMUP_SAMPLES"] = "1"
        os.environ["HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE"] = "2"
        os.environ["HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES"] = "3"
    if battery == "algotune":
        os.environ["HOROVOD_AUTOTUNE"] = "1"
        os.environ["HOROVOD_AUTOTUNE_PIPELINE"] = "1"
        os.environ["HOROVOD_AUTOTUNE_WARMUP_SAMPLES"] = "1"
        os.environ["HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE"] = "1"
        os.environ["HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES"] = "1"
        # Pin the TCP plane: the algo verdict lands on TcpCollectives.
        os.environ["HOROVOD_SHM_OPERATIONS"] = "0"
    if battery == "telemetry":
        os.environ["HOROVOD_METRICS"] = "on"
        os.environ["HOROVOD_METRICS_WINDOW"] = "8"
        os.environ["HOROVOD_STRAGGLER_THRESHOLD_MS"] = "10"
        os.environ["HOROVOD_METRICS_PORT"] = "19730"   # +rank; ephemeral fallback
        os.environ["HOROVOD_METRICS_FILE"] = \
            f"/tmp/hvd_tm_{os.environ['HOROVOD_RENDEZVOUS_EPOCH']}.json"
        # Pin the TCP plane so the per-peer byte counters see the traffic.
        os.environ["HOROVOD_SHM_OPERATIONS"] = "0"
    if battery == "perfscope":
        os.environ["HOROVOD_METRICS"] = "on"
        os.environ["HOROVOD_METRICS_FILE"] = \
            f"/tmp/hvd_perf_{os.environ['HOROVOD_RENDEZVOUS_EPOCH']}.json"
        # Pin the TCP plane so the busbw cells land on one plane; fusion
        # off keeps each named payload its own size-bucket sample.
        os.environ["HOROVOD_SHM_OPERATIONS"] = "0"
        os.environ["HOROVOD_FUSION_THRESHOLD"] = "0"
    if battery == "streams":
        # Two dispatch streams over the TCP plane; fusion off so async
        # bursts negotiate into SEVERAL responses per cycle (the unit the
        # round-robin stream assignment distributes).
        os.environ["HOROVOD_NUM_STREAMS"] = "2"
        os.environ["HOROVOD_SHM_OPERATIONS"] = "0"
        os.environ["HOROVOD_FUSION_THRESHOLD"] = "0"
    if battery == "shm":
        os.environ["HOROVOD_SHM_OPERATIONS"] = "1"   # require formation
        os.environ["HOROVOD_SHM_CAPACITY"] = str(1 << 20)
    if battery == "san":
        # Runtime lock-order witness (ISSUE 8): must be in the env
        # BEFORE horovod_tpu imports so the wrappers install ahead of
        # every package lock creation.  TCP plane pinned so the
        # controller<->transport edge is deterministic.
        os.environ["HOROVOD_SAN"] = "1"
        os.environ["HOROVOD_SAN_FILE"] = \
            f"/tmp/hvd_san_{os.environ['HOROVOD_RENDEZVOUS_EPOCH']}.json"
        os.environ["HOROVOD_SHM_OPERATIONS"] = "0"
    if battery == "trace":
        epoch = os.environ["HOROVOD_RENDEZVOUS_EPOCH"]
        os.environ["HOROVOD_TIMELINE"] = f"/tmp/hvd_trace_{epoch}.json"
        os.environ["HOROVOD_SHM_OPERATIONS"] = "0"
        # PR 5 deterministic delay injection: the last rank freezes
        # 120 ms before dispatching every tr_* collective.
        os.environ["HOROVOD_CHAOS"] = \
            f"freeze:rank={size - 1},name=tr_,ms=120"
        os.environ["HOROVOD_FLIGHT_FILE"] = \
            f"/tmp/hvd_flight_{epoch}.json"
    if battery.startswith("statesync"):
        # Elastic-grow batteries: TCP plane pinned (worlds rebuild at
        # several sizes; shm formation at each would dominate wall
        # time), flight dumps in /tmp, generous per-round deadline for
        # CI load.
        os.environ["HOROVOD_SHM_OPERATIONS"] = "0"
        os.environ["HOROVOD_FLIGHT_FILE"] = \
            f"/tmp/hvd_flight_{os.environ['HOROVOD_RENDEZVOUS_EPOCH']}.json"
        os.environ.setdefault("HOROVOD_STATESYNC_TIMEOUT_SECONDS", "45")
        os.environ.setdefault("HOROVOD_FAULT_TOLERANCE", "1")
    if battery == "fleet":
        # ISSUE 20: two statesync worlds (train + serve) share one
        # coordinator KV.  TCP plane pinned, flight dumps for the
        # hvdmc witness, generous deadlines — the moved rank compiles
        # the serving model mid-migration.
        os.environ["HOROVOD_SHM_OPERATIONS"] = "0"
        os.environ["HOROVOD_FLIGHT_FILE"] = \
            f"/tmp/hvd_flight_{os.environ['HOROVOD_RENDEZVOUS_EPOCH']}.json"
        os.environ.setdefault("HOROVOD_STATESYNC_TIMEOUT_SECONDS", "120")
        os.environ.setdefault("HOROVOD_FAULT_TOLERANCE", "1")
        os.environ.setdefault("HOROVOD_FAULT_TIMEOUT", "30")
        os.environ.setdefault("HOROVOD_METRICS", "on")
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if battery == "statesync_grow":
        os.environ.setdefault("HOROVOD_FAULT_TIMEOUT", "5")
        # Real SIGKILL of rank 2 mid-training (~step 4: each step costs
        # three responses — the train allreduce + the two halves of the
        # membership allgather).
        os.environ.setdefault("HOROVOD_CHAOS", "kill:rank=2,op=13,sig=9")
    if battery == "statesync_life":
        os.environ.setdefault("HOROVOD_FAULT_TIMEOUT", "5")
        os.environ.setdefault("HOROVOD_CHAOS", "kill:rank=2,op=13,sig=9")
        # The runtime census witness around every world transition,
        # dumped rank-stamped to /tmp for the driver's check_dumps.
        os.environ["HOROVOD_LIFE_CENSUS"] = "1"
        os.environ["HOROVOD_LIFE_CENSUS_FILE"] = \
            f"/tmp/hvd_census_" \
            f"{os.environ['HOROVOD_RENDEZVOUS_EPOCH']}.json"
    if battery == "statesync_preempt":
        # Grace must beat the heartbeat: generous fault timeout, SIGTERM
        # at collective 6, 20 s to reach the next step boundary.
        os.environ.setdefault("HOROVOD_FAULT_TIMEOUT", "30")
        os.environ["HOROVOD_PREEMPT_GRACE_S"] = "20"
        os.environ.setdefault("HOROVOD_CHAOS", "preempt:rank=1,op=6")
    if battery in ("statesync_serve", "statesync_serve_joiner"):
        os.environ.setdefault("HOROVOD_FAULT_TIMEOUT", "10")
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if battery.startswith("resilience"):
        # Chaos batteries pin the TCP plane so the socket-level deadline
        # guards are the ones exercised (the shm plane has its own).
        os.environ["HOROVOD_SHM_OPERATIONS"] = "0"
        # Pin the flat ring schedule: the chaos scripts target specific
        # ring edges (e.g. rank 1 -> rank 2 delayed-send), which the
        # small-tensor tree leg (ISSUE 18) would never traverse.
        os.environ["HOROVOD_TREE_THRESHOLD_BYTES"] = "0"
        # Flight dumps land in /tmp, not the repo working directory.
        os.environ["HOROVOD_FLIGHT_FILE"] = \
            f"/tmp/hvd_flight_{os.environ['HOROVOD_RENDEZVOUS_EPOCH']}.json"
    if battery in ("resilience_kill", "resilience_retry",
                   "resilience_freeze"):
        os.environ["HOROVOD_FAULT_TOLERANCE"] = "1"
    if battery in ("serving", "serving_paged"):
        # ISSUE 9: data-parallel serving over the TCP plane with chaos
        # SIGKILL of rank 2 mid-serve (global collective index 11 = the
        # completion exchange of serve step 2, with ~16 requests
        # in-flight).  Fault tolerance on so survivors convert the dead
        # peer and shrink; metrics on so admission keys off live gauges.
        # serving_paged (ISSUE 14) rides the identical chaos with the
        # paged KV plane under it.
        os.environ["HOROVOD_SHM_OPERATIONS"] = "0"
        os.environ["HOROVOD_FAULT_TOLERANCE"] = "1"
        os.environ["HOROVOD_FAULT_TIMEOUT"] = "5"
        os.environ["HOROVOD_METRICS"] = "on"
        os.environ["HOROVOD_CHAOS"] = "kill:rank=2,op=11,sig=9"
        os.environ["HOROVOD_FLIGHT_FILE"] = \
            f"/tmp/hvd_flight_{os.environ['HOROVOD_RENDEZVOUS_EPOCH']}.json"
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if battery == "serving_disagg":
        # ISSUE 14 split-role loop under the STRICT fingerprint: a
        # rank-divergent collective anywhere in the prefill/decode role
        # split would surface as a structured ERROR within one cycle.
        os.environ["HOROVOD_SHM_OPERATIONS"] = "0"
        os.environ["HOROVOD_METRICS"] = "on"
        os.environ["HOROVOD_FINGERPRINT"] = "strict"
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if battery == "resilience_kill":
        os.environ["HOROVOD_FAULT_TIMEOUT"] = "5"
        # Real SIGKILL mid-allreduce at global collective index 3
        # (ISSUE 5 acceptance criterion).
        os.environ["HOROVOD_CHAOS"] = "kill:rank=2,op=3,sig=9"
    if battery == "resilience_retry":
        os.environ["HOROVOD_FAULT_TIMEOUT"] = "3"
        os.environ["HOROVOD_ON_FAILURE"] = "retry"
        # Hold rank 1's FIRST data-mesh send to rank 2 for 9 s: over the
        # 3 s deadline on attempt 0, exhausted (count=1) on the retry.
        os.environ["HOROVOD_CHAOS"] = \
            "delay:rank=1,mesh=data,peer=2,send=0,ms=9000,count=1"
    if battery == "resilience_freeze":
        os.environ["HOROVOD_FAULT_TIMEOUT"] = "3"
        os.environ["HOROVOD_CHAOS"] = "freeze:rank=1,op=1,ms=12000"
    if battery == "compress":
        # Pin the TCP plane so its byte counters see the traffic, and
        # the ring schedule so the asserted 2(N-1)/N wire-byte fractions
        # hold (the small-tensor tree of ISSUE 18 trades bytes for
        # latency: whole-buffer contributions gather to the root).
        os.environ["HOROVOD_SHM_OPERATIONS"] = "0"
        os.environ["HOROVOD_TREE_THRESHOLD_BYTES"] = "0"
    if battery == "compress_shm":
        os.environ["HOROVOD_SHM_OPERATIONS"] = "1"
        os.environ["HOROVOD_SHM_CAPACITY"] = str(1 << 20)
    if battery == "compress_xla":
        os.environ["HOROVOD_JAX_DISTRIBUTED"] = "1"
        os.environ["HOROVOD_XLA_OPERATIONS"] = "1"
        os.environ["HOROVOD_GLOO_TIMEOUT_SECONDS"] = "60"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax
        jax.config.update("jax_platforms", "cpu")
    if battery == "hierarchical_tcp":
        os.environ["HOROVOD_SHM_OPERATIONS"] = "0"
        battery = "hierarchical"
    if battery == "hierarchical":
        # Two hosts x two slots, homogeneous host-major layout (what the
        # launcher assigns); both knobs on.
        local_size = 2
        os.environ["HOROVOD_LOCAL_RANK"] = str(rank % local_size)
        os.environ["HOROVOD_LOCAL_SIZE"] = str(local_size)
        os.environ["HOROVOD_CROSS_RANK"] = str(rank // local_size)
        os.environ["HOROVOD_CROSS_SIZE"] = str(size // local_size)
        os.environ["HOROVOD_HIERARCHICAL_ALLREDUCE"] = "1"
        os.environ["HOROVOD_HIERARCHICAL_ALLGATHER"] = "1"
    if battery == "xla":
        # Form the JAX world + device data plane (CPU multi-process).
        os.environ["HOROVOD_JAX_DISTRIBUTED"] = "1"
        os.environ["HOROVOD_XLA_OPERATIONS"] = "1"
        os.environ["HOROVOD_GLOO_TIMEOUT_SECONDS"] = "60"
        os.environ["JAX_PLATFORMS"] = "cpu"

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

    if battery in PREINIT_BATTERIES:
        # Joiner batteries enter the world themselves (join_world runs
        # core.init after its streamed state verifies).
        try:
            return PREINIT_BATTERIES[battery](port)
        except BaseException:
            traceback.print_exc()
            return 1

    import horovod_tpu as hvd

    hvd.init()
    try:
        assert hvd.rank() == rank
        assert hvd.size() == size
        BATTERIES[battery](hvd, rank, size)
    except BaseException:
        traceback.print_exc()
        return 1
    finally:
        hvd.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
