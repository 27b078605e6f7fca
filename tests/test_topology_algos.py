"""Topology-aware collective algorithms battery (ISSUE 18).

Covers the tentpole layers and their contracts:

- topology declaration (common/topology.py): HOROVOD_TOPOLOGY parsing,
  torus boustrophedon / host-grouped ring orders, hierarchy levels, and
  the launcher-uniform degradation to flat on invalid specs;
- per-size algorithm selection (_select_algo) is a pure, rank-symmetric
  function of the negotiated payload size and the tuned/launcher knobs,
  with symmetric feasibility fallbacks (pow-2 for halving/doubling,
  declared torus, 2-rank degeneration);
- 2/4-rank parity for the tree / recursive-halving-doubling / two-phase
  torus legs across fp32, int32, bf16-cast and int8/uint4 quantized
  wires — BITWISE against the flat ring wherever rank-order fp32
  accumulation is preserved (ints; codec paths with block-aligned chunk
  bounds), documented last-ulp fp32 tolerance where the reduction tree
  legitimately re-associates (plain fp32 tree/rhd/torus);
- topology-ordered rings produce the identical result as the identity
  order (chunk ownership follows ring POSITION, not rank);
- the ResponseList tuned_algo / tuned_tree_threshold wire round-trip
  and the autotuner's algo×threshold sweep mechanics;
- the transport spawns NO per-step threads on any of the new legs
  (thread census across a tree+rhd+torus workload);
- every bench payload is stamped with the declared topology/algo;
- (slow) 8-rank parity and the 4-rank A/B: the small-tensor tree beats
  the flat ring at <=64 KiB, and auto selection costs the segmented
  ring nothing measurable at >=4 MiB.

The negotiated end-to-end path (tuned_algo broadcast -> applied before
dispatch on every rank) rides the `algotune` battery in
tests/test_multiprocess.py / mp_worker.py.
"""
from __future__ import annotations

import os
import sys
import threading
import time
import types

import numpy as np
import pytest

import horovod_tpu.native as native
from horovod_tpu.backend.tcp import TcpCollectives
from horovod_tpu.common import topology
from horovod_tpu.common.message import ResponseList
from horovod_tpu.compress import CompressionCodec
from horovod_tpu.runner.network import PeerMesh

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def kv():
    from horovod_tpu.runner.network import (RendezvousClient,
                                            RendezvousServer)
    server = RendezvousServer()
    port = server.start()
    yield RendezvousClient("127.0.0.1", port, 15.0)
    server.stop()


def _threaded(n, fn, timeout=90.0):
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


def _world(kv, size, scope, fn, coll_kwargs=None, timeout=90.0):
    """Form a PeerMesh world and run fn(coll, rank) on every rank;
    `coll_kwargs` go to every rank's TcpCollectives (algo / torus /
    ring_order are launcher-uniform knobs, so identical per rank)."""
    meshes: list = [None] * size
    kwargs = coll_kwargs or {}

    def worker(r):
        meshes[r] = PeerMesh(r, size, kv, scope=scope, timeout=15.0)
        return fn(TcpCollectives(meshes[r], **kwargs), r)

    try:
        return _threaded(size, worker, timeout=timeout)
    finally:
        for m in meshes:
            if m is not None:
                m.close()


# ---------------------------------------------------------------------------
# Topology declaration: parse, ring orders, levels
# ---------------------------------------------------------------------------
def test_parse_torus_and_snake_ring_order():
    topo = topology.parse("torus:2x3", size=6)
    assert topo.kind == "torus" and (topo.rows, topo.cols) == (2, 3)
    # Boustrophedon: row 0 left-to-right, row 1 right-to-left — every
    # ring hop lands on a grid neighbor.
    assert topo.ring_order() == [0, 1, 2, 5, 4, 3]
    assert topo.levels() == [3, 2]          # cols (fast) first
    assert topo.describe() == "torus:2x3"


def test_parse_torus_shape_mismatch_degrades_to_flat():
    for spec in ("torus:2x3", "torus:0x4", "torus:nonsense", "torus:2"):
        topo = topology.parse(spec, size=8)
        assert topo.kind == "flat", spec
        assert topo.ring_order() == list(range(8))
        assert topo.levels() == [8]


def test_parse_host_grouping_and_explicit_map():
    topo = topology.parse("host", size=8, local_size=4)
    assert topo.kind == "host"
    assert topo.levels() == [4, 2]
    assert topo.describe() == "host:2x4"
    # Homogeneous host-major launch: already grouped, identity order.
    assert topo.ring_order() == list(range(8))
    # Explicit elastic slot map: ranks regroup by host, stably.
    mapped = topology.parse("host", size=4, local_size=2,
                            hosts=(1, 0, 1, 0))
    assert mapped.ring_order() == [1, 3, 0, 2]
    # No multi-slot hosts -> flat (identity, single level).
    assert topology.parse("host", size=4, local_size=1).kind == "flat"


def test_parse_auto_and_unknown():
    auto = topology.parse("", size=8, local_size=4, cross_size=2)
    assert auto.kind == "host" and auto.levels() == [4, 2]
    assert topology.parse("", size=8).kind == "flat"
    assert topology.parse("wormhole", size=8).kind == "flat"
    assert topology.parse("flat", size=8).describe() == "flat"


def test_parse_auto_uses_explicit_host_map_on_uneven_layouts():
    """An uneven slot layout (1+3) defeats the homogeneous local x cross
    product test, but an explicit HOROVOD_HOST_IDS map still groups the
    ring by host; local_size stays pinned to 1 so every rank builds the
    IDENTICAL Topology (per-rank local_size differs across hosts here)
    and the level ladder stays flat (hierarchy needs homogeneity)."""
    topo = topology.parse("", size=4, local_size=1, cross_size=1,
                          hosts=(0, 1, 1, 1))
    assert topo.kind == "host" and topo.local_size == 1
    assert topo.ring_order() == [0, 1, 2, 3]
    assert topo.levels() == [4]
    regrouped = topology.parse("", size=4, hosts=(1, 0, 1, 0))
    assert regrouped.ring_order() == [1, 3, 0, 2]
    # Degenerate maps change nothing: single host, all-distinct hosts,
    # or a length mismatch (stale env across an elastic resize).
    assert topology.parse("", size=4, hosts=(0, 0, 0, 0)).kind == "flat"
    assert topology.parse("", size=4, hosts=(0, 1, 2, 3)).kind == "flat"
    assert topology.parse("", size=4, hosts=(0, 1)).kind == "flat"


def test_host_ids_env_is_rank_ordered_and_first_appearance_indexed():
    from horovod_tpu.runner.hosts import (get_host_assignments,
                                          host_ids_env, parse_hosts)
    ids = host_ids_env(get_host_assignments(parse_hosts("a:1,b:3"), 4))
    assert ids == "0,1,1,1"
    # Host indices follow first appearance in rank order regardless of
    # the assignment list's ordering.
    slots = get_host_assignments(parse_hosts("x:2,y:2"), 4)
    assert host_ids_env(list(reversed(slots))) == "0,0,1,1"


def test_resolve_reads_knob(monkeypatch):
    monkeypatch.setenv("HOROVOD_TOPOLOGY", "torus:2x2")
    assert topology.resolve(4).kind == "torus"
    monkeypatch.setenv("HOROVOD_TOPOLOGY", "flat")
    assert topology.resolve(4).kind == "flat"


def test_algo_vocabulary_wire_indices():
    for name in topology.ALGO_NAMES:
        assert topology.algo_name(topology.algo_index(name)) == name
    # Out-of-range indices (a newer peer's vocabulary) degrade to auto.
    assert topology.algo_name(-1) == "auto"
    assert topology.algo_name(99) == "auto"


# ---------------------------------------------------------------------------
# Per-size selection: pure function of rank-symmetric inputs
# ---------------------------------------------------------------------------
def _selector(size, algo="auto", tree_threshold=64 * 1024, torus=None):
    stub = types.SimpleNamespace(size=size, algo=algo,
                                 tree_threshold=tree_threshold,
                                 _torus=torus)
    return lambda nbytes: TcpCollectives._select_algo(stub, nbytes)


def test_select_algo_matrix():
    sel = _selector(4)
    assert sel(1024) == "tree"              # small -> latency-bound
    assert sel(64 * 1024) == "tree"         # threshold is inclusive
    assert sel(64 * 1024 + 1) == "ring"     # past crossover -> bandwidth
    # Declared torus: large tensors take the two-phase schedule.
    sel = _selector(4, torus=(2, 2))
    assert sel(1024) == "tree"
    assert sel(1 << 20) == "torus"
    # Threshold 0 disables the tree leg entirely.
    assert _selector(4, tree_threshold=0)(8) == "ring"
    # Explicit knobs pin the algorithm regardless of size...
    assert _selector(4, algo="ring")(8) == "ring"
    assert _selector(4, algo="tree")(1 << 24) == "tree"
    # ...with SYMMETRIC feasibility fallbacks: halving/doubling needs a
    # power-of-two world, torus needs a declared torus.
    assert _selector(4, algo="rhd")(1 << 20) == "rhd"
    assert _selector(6, algo="rhd")(1 << 20) == "tree"
    assert _selector(4, algo="torus")(1 << 20) == "ring"
    # Two ranks: every schedule degenerates to one exchange; keep the
    # ring's native fast path.
    for algo in ("tree", "rhd", "torus", "auto"):
        assert _selector(2, algo=algo, torus=(1, 2))(8) == "ring"


def test_tuned_algo_wire_roundtrip():
    rl = ResponseList(tuned_algo=topology.algo_index("tree"),
                      tuned_tree_threshold=1 << 16)
    back = ResponseList.from_bytes(rl.to_bytes())
    assert back.tuned_algo == topology.algo_index("tree")
    assert back.tuned_tree_threshold == 1 << 16
    # Defaults (-1 = unchanged) survive the trip too.
    back = ResponseList.from_bytes(ResponseList().to_bytes())
    assert back.tuned_algo == -1 and back.tuned_tree_threshold == -1


# ---------------------------------------------------------------------------
# Autotuner algo x threshold sweep mechanics
# ---------------------------------------------------------------------------
def test_algo_sweep_proposes_then_pins_winner(monkeypatch):
    from horovod_tpu.common.parameter_manager import ParameterManager
    monkeypatch.setenv("HOROVOD_AUTOTUNE_WARMUP_SAMPLES", "0")
    monkeypatch.setenv("HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE", "1")
    monkeypatch.setenv("HOROVOD_AUTOTUNE_PIPELINE", "1")
    ctl = types.SimpleNamespace(
        tensor_fusion_threshold=1 << 26, pending_tuned_params=None,
        pending_tuned_codec=None, pending_tuned_pipeline=None,
        pending_tuned_fused=None, pending_tuned_algo=None)
    pm = ParameterManager(ctl, active=True)
    candidates = list(pm._algo_candidates)
    assert candidates and candidates[0][0] == topology.algo_index("ring")
    assert all(0 <= a < len(topology.ALGO_NAMES) for a, _ in candidates)
    # Skip straight to the algo sweep (the earlier sweeps have their own
    # batteries); each observe() closes one sample window.
    pm._codec_candidates = []
    pm._pipeline_candidates = []
    pm._fused_candidates = []
    proposed = []
    for i in range(len(candidates)):
        pm.observe(["t"], 4096 * (i + 1))
        proposed.append(pm._controller.pending_tuned_algo)
    assert proposed == candidates            # every candidate was scored
    pm.observe(["t"], 4096)                  # closes the last window
    winner = pm._controller.pending_tuned_algo
    assert winner in candidates              # the winner is pinned
    assert pm._algo_candidates == []         # sweep complete -> BO next
    assert len(pm._algo_scores) == len(candidates)


# ---------------------------------------------------------------------------
# Parity: tree / rhd / torus vs the flat ring, 2- and 4-rank worlds
# ---------------------------------------------------------------------------
def _run_algo(kv, size, scope, op, coll_kwargs):
    def fn(coll, r):
        return op(coll, r)
    return _world(kv, size, scope, fn, coll_kwargs=coll_kwargs)


ALGO_WORLDS = [
    ("tree", {"algo": "tree"}),
    ("rhd", {"algo": "rhd"}),
    ("torus", {"algo": "torus", "torus": (2, 2)}),
]


@pytest.mark.parametrize("algo,kwargs", ALGO_WORLDS)
def test_algo_parity_fp32(kv, monkeypatch, algo, kwargs):
    """Plain fp32: tree/rhd/torus legitimately re-associate the sum
    (ring reduces chunk-owner order; tree reduces at the root), so the
    contract is the documented last-ulp tolerance — plus exact
    cross-rank agreement within each algorithm (symmetric-result)."""
    monkeypatch.setattr(native, "ring_allreduce", lambda *a, **k: False)
    size, n = 4, 12345
    rng = np.random.default_rng(18)
    data = (rng.standard_normal((size, n)) * 5).astype(np.float32)

    def op(coll, r):
        return coll.allreduce(data[r].copy())

    ring = _run_algo(kv, size, f"fp32-ring-{algo}", op, {"algo": "ring"})
    out = _run_algo(kv, size, f"fp32-{algo}", op, kwargs)
    for r in range(size):
        np.testing.assert_allclose(out[r], ring[r], rtol=1e-6, atol=1e-5)
        np.testing.assert_array_equal(out[0], out[r])


@pytest.mark.parametrize("algo,kwargs", ALGO_WORLDS)
def test_algo_parity_int32_bitwise(kv, monkeypatch, algo, kwargs):
    """Integer adds are associative: every schedule must be EXACT."""
    monkeypatch.setattr(native, "ring_allreduce", lambda *a, **k: False)
    size, n = 4, 9973
    rng = np.random.default_rng(19)
    data = rng.integers(-1000, 1000, size=(size, n)).astype(np.int32)

    def op(coll, r):
        return coll.allreduce(data[r].copy())

    ring = _run_algo(kv, size, f"i32-ring-{algo}", op, {"algo": "ring"})
    out = _run_algo(kv, size, f"i32-{algo}", op, kwargs)
    for r in range(size):
        np.testing.assert_array_equal(out[r], ring[r])
        np.testing.assert_array_equal(out[0], out[r])


def test_cast_allreduce_tree_bitwise(kv, monkeypatch):
    """bf16 cast wire: both the ring (chunk owners accumulate rank 0..N-1
    in fp32, round once) and the tree (root accumulates rank 0..N-1 in
    fp32, rounds once) preserve rank-order accumulation -> BITWISE."""
    monkeypatch.setattr(native, "ring_allreduce", lambda *a, **k: False)
    import ml_dtypes
    size, n = 4, 12345
    rng = np.random.default_rng(20)
    data = (rng.standard_normal((size, n)) * 5).astype(np.float32)
    wire = np.dtype(ml_dtypes.bfloat16)

    def op(coll, r):
        return coll.cast_allreduce(data[r].copy(), wire)

    ring = _run_algo(kv, size, "bf16-ring", op,
                     {"algo": "ring", "tree_threshold": 0})
    tree = _run_algo(kv, size, "bf16-tree", op,
                     {"algo": "tree", "tree_threshold": 1 << 30})
    for r in range(size):
        np.testing.assert_array_equal(np.asarray(tree[r]),
                                      np.asarray(ring[r]))


@pytest.mark.parametrize("codec,block", [
    (CompressionCodec.INT8, 128), (CompressionCodec.UINT4, 128)])
def test_quantized_allreduce_tree_bitwise_aligned(kv, monkeypatch, codec,
                                                  block):
    """Quantized wires: with n divisible by size*block the ring's chunk
    bounds align to quantization blocks, so the ring's owner-reduce and
    the tree's root-reduce see identical block statistics -> BITWISE.
    (Unaligned n splits blocks across chunk owners; that case carries
    the documented fp32 tolerance and is not asserted bitwise.)"""
    monkeypatch.setattr(native, "ring_allreduce", lambda *a, **k: False)
    size = 4
    n = size * block * 5                     # block-aligned chunk bounds
    rng = np.random.default_rng(21)
    data = (rng.standard_normal((size, n)) * 5).astype(np.float32)

    def op(coll, r):
        return coll.quantized_allreduce(data[r].copy(), codec, block)

    tag = "i8" if codec == CompressionCodec.INT8 else "u4"
    ring = _run_algo(kv, size, f"q-{tag}-ring", op,
                     {"algo": "ring", "tree_threshold": 0})
    tree = _run_algo(kv, size, f"q-{tag}-tree", op,
                     {"algo": "tree", "tree_threshold": 1 << 30})
    for r in range(size):
        np.testing.assert_array_equal(tree[r], ring[r])
        np.testing.assert_array_equal(tree[0], tree[r])


def test_snake_ring_order_matches_identity_bitwise(kv, monkeypatch):
    """Topology-ordered ring: chunk ownership follows ring POSITION, so
    a permuted walk moves the same chunks through the same elementwise
    adds in a different rank rotation — integer-exact either way, and
    every rank still converges on the identical buffer."""
    monkeypatch.setattr(native, "ring_allreduce", lambda *a, **k: False)
    size, n = 4, 10007
    rng = np.random.default_rng(22)
    data = rng.integers(-500, 500, size=(size, n)).astype(np.int64)
    snake = topology.Topology(size=size, kind="torus", rows=2,
                              cols=2).ring_order()

    def op(coll, r):
        return coll.allreduce(data[r].copy())

    ident = _run_algo(kv, size, "order-ident", op, {"algo": "ring"})
    perm = _run_algo(kv, size, "order-snake", op,
                     {"algo": "ring", "ring_order": snake})
    for r in range(size):
        np.testing.assert_array_equal(perm[r], ident[r])


def test_two_rank_degeneration_runs_the_ring(kv, monkeypatch):
    """A 2-rank world with algo=tree/rhd/torus must not hang or diverge:
    selection degenerates every schedule to the ring's single exchange."""
    monkeypatch.setattr(native, "ring_allreduce", lambda *a, **k: False)
    size, n = 2, 4096
    rng = np.random.default_rng(23)
    data = rng.standard_normal((size, n)).astype(np.float32)
    expect = data.sum(axis=0)

    for algo in ("tree", "rhd"):
        def op(coll, r):
            out = coll.allreduce(data[r].copy())
            assert coll.last_algo == "ring"
            return out
        got = _run_algo(kv, size, f"deg-{algo}", op, {"algo": algo})
        for r in range(size):
            np.testing.assert_allclose(got[r], expect, rtol=1e-6)


def test_last_algo_reflects_selection(kv, monkeypatch):
    """Telemetry's algo= label source: last_algo names what actually
    ran, per size class, on every rank identically."""
    monkeypatch.setattr(native, "ring_allreduce", lambda *a, **k: False)
    size = 4
    small = np.ones(64, dtype=np.float32)          # 256 B <= threshold
    large = np.ones(64 * 1024, dtype=np.float32)   # 256 KiB > threshold

    def fn(coll, r):
        seen = []
        coll.allreduce(small.copy())
        seen.append(coll.last_algo)
        coll.allreduce(large.copy())
        seen.append(coll.last_algo)
        return seen

    out = _world(kv, size, "lastalgo", fn,
                 coll_kwargs={"algo": "auto", "tree_threshold": 64 * 1024})
    assert out == [["tree", "ring"]] * size


# ---------------------------------------------------------------------------
# Thread census: the new legs spawn ZERO per-step threads
# ---------------------------------------------------------------------------
def test_no_per_step_thread_spawn_on_new_algos(kv, monkeypatch):
    """Tree, halving/doubling and two-phase torus all ride the persistent
    per-peer sender lanes: after a warmup touches every peer channel,
    a mixed tree+rhd+torus workload constructs no new Thread."""
    monkeypatch.setattr(native, "ring_allreduce", lambda *a, **k: False)
    size = 4
    spawned: list[str] = []
    orig_init = threading.Thread.__init__

    def counting_init(self, *args, **kwargs):
        spawned.append(kwargs.get("name") or "anon")
        orig_init(self, *args, **kwargs)

    monkeypatch.setattr(threading.Thread, "__init__", counting_init)

    sync = threading.Barrier(size)
    marker: dict[str, int] = {}
    rng = np.random.default_rng(24)
    data = rng.standard_normal((size, 20000)).astype(np.float32)

    def workload(coll, r):
        for algo in ("tree", "rhd", "torus", "tree"):
            coll.algo = algo
            coll.allreduce(data[r].copy())
        coll.algo = "tree"
        coll.cast_allreduce(data[r][:4096].copy(), np.dtype(np.float16))
        coll.quantized_allreduce(data[r][:2048].copy(),
                                 CompressionCodec.INT8, 128)

    def fn(coll, r):
        # Warmup runs the SAME legs once: every directed peer channel
        # any schedule touches (tree parent/child edges, rhd partners,
        # torus row/column rings) spins up its lazy sender lane before
        # the census window opens.
        workload(coll, r)
        sync.wait()
        if r == 0:
            marker["before"] = len(spawned)
        sync.wait()
        workload(coll, r)
        sync.wait()
        if r == 0:
            marker["after"] = len(spawned)
        return True

    _world(kv, size, "algo-census", fn,
           coll_kwargs={"torus": (2, 2), "tree_threshold": 1 << 30})
    assert marker["after"] == marker["before"], \
        (f"{marker['after'] - marker['before']} thread(s) spawned during "
         f"tree/rhd/torus collectives: {spawned[marker['before']:]}")


# ---------------------------------------------------------------------------
# Slow: 8-rank parity + the 4-rank latency/bandwidth A/B
# ---------------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.parametrize("algo,kwargs", [
    ("tree", {"algo": "tree"}),
    ("rhd", {"algo": "rhd"}),
    ("torus", {"algo": "torus", "torus": (2, 4)}),
])
def test_algo_parity_eight_ranks(kv, monkeypatch, algo, kwargs):
    monkeypatch.setattr(native, "ring_allreduce", lambda *a, **k: False)
    size, n = 8, 30011
    rng = np.random.default_rng(25)
    fdata = (rng.standard_normal((size, n)) * 3).astype(np.float32)
    idata = rng.integers(-100, 100, size=(size, n)).astype(np.int32)

    def fop(coll, r):
        return coll.allreduce(fdata[r].copy())

    def iop(coll, r):
        return coll.allreduce(idata[r].copy())

    fring = _run_algo(kv, size, f"8f-ring-{algo}", fop, {"algo": "ring"})
    fout = _run_algo(kv, size, f"8f-{algo}", fop, kwargs)
    iring = _run_algo(kv, size, f"8i-ring-{algo}", iop, {"algo": "ring"})
    iout = _run_algo(kv, size, f"8i-{algo}", iop, kwargs)
    for r in range(size):
        np.testing.assert_allclose(fout[r], fring[r], rtol=1e-6,
                                   atol=1e-5)
        np.testing.assert_array_equal(fout[0], fout[r])
        np.testing.assert_array_equal(iout[r], iring[r])


def _timed_world(kv, size, scope, coll_kwargs, nbytes, reps):
    """Median barrier-synced wall time of one allreduce at rank 0."""
    sync = threading.Barrier(size)
    samples: list[float] = []
    n = nbytes // 4

    def fn(coll, r):
        x = np.ones(n, dtype=np.float32)
        for _ in range(3):                     # warm lanes + buffers
            coll.allreduce(x.copy())
        for _ in range(reps):
            y = x.copy()
            sync.wait()
            t0 = time.perf_counter()
            coll.allreduce(y)
            sync.wait()
            if r == 0:
                samples.append(time.perf_counter() - t0)
        return True

    _world(kv, size, scope, fn, coll_kwargs=coll_kwargs, timeout=240.0)
    return float(np.median(samples))


@pytest.mark.slow
def test_small_tensor_tree_beats_flat_ring(kv, monkeypatch):
    """The acceptance A/B: at <=64 KiB the latency-bound leg (tree) must
    beat the flat ring by >=1.2x on a 4-rank world — the ring pays
    2(N-1)=6 serialized hops per step, the binomial tree 2*log2(N)=4."""
    monkeypatch.setattr(native, "ring_allreduce", lambda *a, **k: False)
    reps, nbytes = 15, 16 * 1024
    ring = _timed_world(kv, 4, "ab-small-ring", {"algo": "ring"},
                        nbytes, reps)
    tree = _timed_world(kv, 4, "ab-small-tree", {"algo": "tree"},
                        nbytes, reps)
    assert ring >= 1.2 * tree, \
        f"tree {tree * 1e6:.0f}us vs ring {ring * 1e6:.0f}us at {nbytes}B"


@pytest.mark.slow
def test_large_tensor_auto_matches_segmented_ring(kv, monkeypatch):
    """At >=4 MiB auto selection must pick the segmented ring and cost
    nothing measurable: within 5% of the explicitly pinned ring.  Both
    settings run INTERLEAVED in the same world so system drift between
    two sequential worlds cannot masquerade as a selection cost."""
    monkeypatch.setattr(native, "ring_allreduce", lambda *a, **k: False)
    size, reps, n = 4, 9, (4 << 20) // 4
    sync = threading.Barrier(size)
    samples: dict[str, list[float]] = {"ring": [], "auto": []}

    def fn(coll, r):
        coll.tree_threshold = 64 * 1024
        x = np.ones(n, dtype=np.float32)
        for _ in range(2):                     # warm lanes + buffers
            coll.allreduce(x.copy())
        for _ in range(reps):
            for algo in ("ring", "auto"):
                coll.algo = algo
                y = x.copy()
                sync.wait()
                t0 = time.perf_counter()
                coll.allreduce(y)
                assert coll.last_algo == "ring"   # auto picked the ring
                sync.wait()
                if r == 0:
                    samples[algo].append(time.perf_counter() - t0)
        return True

    _world(kv, size, "ab-big", fn, timeout=240.0)
    ring = float(np.median(samples["ring"]))
    auto = float(np.median(samples["auto"]))
    assert auto <= 1.05 * ring, \
        f"auto {auto * 1e3:.2f}ms vs ring {ring * 1e3:.2f}ms at 4 MiB"
