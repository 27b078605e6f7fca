"""statesync/ battery (ISSUE 10): zero-downtime elastic world grow —
peer-to-peer live state streaming, preemption grace, the autoscale
policy loop, and the ring-sharded checkpoint round trip.

Process-level acceptance (mp_worker batteries under the hard SIGALRM
guard):

- 4-rank chaos battery rides 4->3->4: SIGKILL of rank 2 mid-training →
  survivors shrink with zero failed post-shrink steps → a replacement
  process joins via peer streaming (zero failed incumbent steps,
  catch-up wall bounded by ~one donor-stream, streamed state
  digest-identical to the donors' snapshot);
- SIGTERM-grace battery: the preempted rank departs with its ``bye|``
  stamp inside the grace window and survivors shrink proactively — no
  RanksFailedError anywhere;
- serving variant (slow): a joiner replica enters mid-serve, the
  loadgen report records world.grows and goodput before/during/after.

Unit level: snapshot flatten/digest/stamp semantics, the streaming
protocol over real PeerMesh channels (including resume across a donor
death and torn/corrupt-round rejection), ring-shard re-layout math and
the checkpoint round trip at changed world sizes (parity vs the
replicated optimizer), autoscale hysteresis, blacklist re-admission,
the chaos ``preempt`` action, and the HVD1007 lint rule.
"""
from __future__ import annotations

import json
import os
import signal
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_multiprocess import _run_world  # noqa: E402

from horovod_tpu.common.tcp_transport import (  # noqa: E402
    STATE_DATA, STATE_META, pack_state_frame, unpack_state_frame)
from horovod_tpu.runner.network import (  # noqa: E402
    RendezvousClient, RendezvousServer)
from horovod_tpu.statesync import (  # noqa: E402
    AutoscaleController, AutoscalePolicy, DonorServer, JoinerPuller,
    Snapshot, SnapshotStamp, StreamError, TornSnapshotError,
    concat_ring_shards, flatten_state, reshard_ring_state,
    shard_for_rank, state_digest, unflatten_state)
from horovod_tpu.statesync.stream import StreamGuard  # noqa: E402

HARD_GUARD_SECONDS = 420


@pytest.fixture(autouse=True)
def hard_timeout_guard():
    """A re-introduced membership deadlock must fail fast, not eat the
    tier-1 budget (the resilience-suite discipline)."""
    def _expired(signum, frame):
        raise TimeoutError(
            f"statesync test exceeded the {HARD_GUARD_SECONDS}s hard "
            f"guard — a blocking wait has lost its deadline")
    old = signal.signal(signal.SIGALRM, _expired)
    signal.alarm(HARD_GUARD_SECONDS)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


# ---------------------------------------------------------------------------
# Process-level acceptance batteries
# ---------------------------------------------------------------------------
def _witness_env(battery: str, size: int) -> dict:
    """A deep flight ring so the membership transitions survive the
    per-step enqueue/dispatch churn until the end-of-battery witness
    dump (mp_worker routes the dump files themselves to launch-rank-
    keyed /tmp paths); stale dumps from earlier runs are removed."""
    import glob
    for stale in glob.glob(f"/tmp/hvd_witness_{battery}{size}"
                           f".launch*.json"):
        os.unlink(stale)
    return {"HOROVOD_FLIGHT_EVENTS": "4096"}


def _replay_witness(outputs, expect_kinds):
    """ISSUE 11 acceptance: the battery's flight/event logs replay
    through the hvdmc trace witness and every observed membership
    transition exists in the model (problems == unsound spec)."""
    from horovod_tpu.analysis import hvdmc

    dumps = sorted({line.split(" ", 1)[1].strip()
                    for out in outputs for line in out.splitlines()
                    if line.startswith("WITNESS_DUMP ")})
    assert dumps, "no battery wrote a witness dump"
    report = hvdmc.witness_check(hvdmc.load_dumps(dumps))
    assert report.problems == [], "\n".join(report.problems)
    assert expect_kinds <= set(report.observed), \
        (sorted(report.observed), expect_kinds)
    return report


def test_statesync_grow_rides_4_3_4():
    """ISSUE 10 acceptance: SIGKILL a rank mid-training, survivors
    shrink with zero failed steps, a replacement joins via peer
    streaming with zero failed incumbent steps, catch-up wall bounded,
    streamed state digest-verified bit-identical (all asserted
    in-battery; the joiner's lifecycle is owned by launch rank 0).
    ISSUE 11: the observed flight events replay through the hvdmc
    trace witness against the grow model."""
    outputs = _run_world(4, "statesync_grow", timeout=240.0,
                         expected_rcs={2: -signal.SIGKILL},
                         extra_env=_witness_env("statesync_grow", 4))
    for r in (0, 1, 3):
        assert "rode 4->3->4" in outputs[r], outputs[r]
    assert "joiner: catch-up" in outputs[0], outputs[0]
    _replay_witness(outputs, {"shrink", "donate", "grow",
                              "join-announce", "join-ready",
                              "join-entered"})


def test_statesync_preempt_grace_3rank():
    """ISSUE 10 SIGTERM-grace acceptance: the preempted rank departs
    with bye| inside the grace window (exit 0 — never a signal death)
    and survivors shrink proactively with no RanksFailedError raised
    anywhere (the battery runs its collectives bare: any structured
    failure is a worker failure here).  ISSUE 11: the observed flight
    events replay through the hvdmc trace witness."""
    outputs = _run_world(3, "statesync_preempt", timeout=150.0,
                         extra_env=_witness_env("statesync_preempt", 3))
    assert "departed with bye| stamp" in outputs[1], outputs[1]
    for r in (0, 2):
        assert "no RanksFailedError anywhere" in outputs[r], outputs[r]
    _replay_witness(outputs, {"sigterm-grace", "departed",
                              "shrink-proactive"})


@pytest.mark.slow
def test_statesync_serving_grow_2rank():
    """Grow mid-serve: a joiner replica streams the incumbents'
    perturbed params, enters at a step boundary, and the grown world
    serves a second wave — world.grows and goodput phases recorded."""
    outputs = _run_world(2, "statesync_serve", timeout=420.0)
    assert "serving grow: 36 served across 2->3" in outputs[0], \
        outputs[0]


# ---------------------------------------------------------------------------
# Snapshot / stamp semantics
# ---------------------------------------------------------------------------
def _tree(n=64, seed=3):
    rng = np.random.default_rng(seed)
    return {"params": rng.standard_normal(n).astype(np.float32),
            "opt": rng.standard_normal(n).astype(np.float32),
            "step": np.int64(17)}


class TestSnapshot:
    def test_flatten_unflatten_roundtrip(self):
        tree = _tree()
        out = unflatten_state(flatten_state(tree), tree)
        for k in tree:
            np.testing.assert_array_equal(out[k], tree[k])

    def test_snapshot_is_a_copy(self):
        """COW semantics: training mutates live arrays freely while a
        donor streams the frozen image."""
        tree = _tree()
        snap = Snapshot(tree, "e", 1)
        before = bytes(snap.data)
        tree["params"] += 1.0
        assert bytes(snap.data) == before

    def test_digest_changes_on_any_flip(self):
        buf = flatten_state(_tree(n=100000))
        d = state_digest(buf)
        for pos in (0, 70000, len(buf) - 1):
            tampered = bytearray(buf)
            tampered[pos] ^= 1
            assert state_digest(tampered) != d

    def test_unflatten_rejects_size_mismatch(self):
        tree = _tree()
        with pytest.raises(ValueError, match="does not match"):
            unflatten_state(flatten_state(tree)[:-4], tree)

    def test_stamp_meta_roundtrip(self):
        s = SnapshotStamp("ep~g1", 42, 0xdeadbeef, 1024)
        assert SnapshotStamp.from_meta(s.as_meta()) == s


# ---------------------------------------------------------------------------
# The state-frame wire verb
# ---------------------------------------------------------------------------
class TestStateFrames:
    def test_roundtrip_with_payload(self):
        raw = pack_state_frame(STATE_DATA, {"o": 8, "crc": 5}, b"pay")
        kind, meta, payload = unpack_state_frame(raw)
        assert (kind, meta, bytes(payload)) == \
            (STATE_DATA, {"o": 8, "crc": 5}, b"pay")

    def test_meta_only_frame(self):
        kind, meta, payload = unpack_state_frame(
            pack_state_frame(STATE_META, {"step": 3}))
        assert kind == STATE_META and meta == {"step": 3}
        assert payload.nbytes == 0

    def test_rejects_foreign_frame(self):
        with pytest.raises(ValueError, match="bad magic"):
            unpack_state_frame(b"\x00\x01\x02 not a state frame")


# ---------------------------------------------------------------------------
# Streaming protocol over real PeerMesh channels (in-process donors)
# ---------------------------------------------------------------------------
@pytest.fixture()
def kv_server():
    srv = RendezvousServer()
    port = srv.start()
    yield RendezvousClient("127.0.0.1", port, 20.0)
    srv.stop()


def _spawn_donors(kv, scope, snap, num_donors, donor_cls=DonorServer,
                  dying=()):
    donors = []
    for r in range(num_donors):
        cls = donor_cls if r in dying else DonorServer
        d = cls(kv, scope, r, num_donors, chunk_bytes=32768,
                timeout=15.0)
        d.offer_snapshot(0, snap)
        d.start()
        donors.append(d)
    return donors


class TestStreaming:
    def test_bulk_round_bit_identical(self, kv_server):
        snap = Snapshot(_tree(n=200000), "e0", 5)
        donors = _spawn_donors(kv_server, "sssync.u.0", snap, 3)
        p = JoinerPuller(kv_server, "sssync.u.0", 3, timeout=15.0)
        p.connect()
        image, stamp = p.pull_round(0)
        assert bytes(image) == bytes(snap.data)
        assert stamp == snap.stamp
        # Every donor served a DISJOINT shard (bytes sum to the image).
        assert sum(b for b, _ in p.donor_stats.values()) == len(image)
        p.close()
        for d in donors:
            d.join(10.0)
            assert d.error is None

    def test_second_round_streams_fresh_snapshot(self, kv_server):
        tree = _tree(n=50000)
        snap0 = Snapshot(tree, "e0", 5)
        donors = _spawn_donors(kv_server, "sssync.u.1", snap0, 2)
        p = JoinerPuller(kv_server, "sssync.u.1", 2, timeout=15.0)
        p.connect()
        img0, st0 = p.pull_round(0)
        tree["params"] *= 2.0
        snap1 = Snapshot(tree, "e0", 9)
        for d in donors:
            d.offer_snapshot(1, snap1)
        img1, st1 = p.pull_round(1)
        assert bytes(img1) == bytes(snap1.data) != bytes(img0)
        assert st1.step == 9
        p.close()

    def test_resume_across_donor_death(self, kv_server):
        """A donor dying mid-range (channel closed) reassigns its
        unfinished tail to the survivors; the assembled image still
        digest-verifies bit-identical."""
        class DyingDonor(DonorServer):
            def _serve_range(self, mesh, joiner, snap, offset, length,
                             counter):
                import zlib
                view = memoryview(snap.data)
                n = min(self.chunk_bytes, length)
                chunk = view[offset:offset + n]
                mesh.send(joiner, pack_state_frame(
                    STATE_DATA,
                    {"o": offset, "n": n, "crc": zlib.crc32(chunk)},
                    chunk))
                raise StreamError("unit-test chaos: donor dies")

        snap = Snapshot(_tree(n=300000), "e0", 5)
        _spawn_donors(kv_server, "sssync.u.2", snap, 3,
                      donor_cls=DyingDonor, dying={1})
        p = JoinerPuller(kv_server, "sssync.u.2", 3, timeout=10.0)
        p.connect()
        image, stamp = p.pull_round(0)
        assert bytes(image) == bytes(snap.data)
        assert 1 in p._dead
        p.close()

    def test_torn_snapshot_rejected(self, kv_server):
        """Donors stamped at different steps = a torn snapshot: the
        round is rejected before a single byte is interpreted."""
        t = _tree(n=4096)
        snap_a = Snapshot(t, "e0", 5)
        t["params"] += 1.0
        snap_b = Snapshot(t, "e0", 6)
        d0 = DonorServer(kv_server, "sssync.u.3", 0, 2,
                         chunk_bytes=1024, timeout=10.0)
        d1 = DonorServer(kv_server, "sssync.u.3", 1, 2,
                         chunk_bytes=1024, timeout=10.0)
        d0.offer_snapshot(0, snap_a)
        d1.offer_snapshot(0, snap_b)
        d0.start()
        d1.start()
        p = JoinerPuller(kv_server, "sssync.u.3", 2, timeout=10.0)
        p.connect()
        with pytest.raises(TornSnapshotError, match="torn snapshot"):
            p.pull_round(0)
        p.close()

    def test_verify_round_rejects_corrupt_image(self):
        snap = Snapshot(_tree(), "e0", 5)
        image = bytearray(snap.data)
        image[3] ^= 0xff
        with pytest.raises(TornSnapshotError, match="stale or corrupt"):
            JoinerPuller.verify_round(image, snap.stamp)

    def test_stream_guard_bounds_waits(self):
        guard = StreamGuard(0.2)
        guard.check(0, 0.1, "recv")   # under the deadline: no raise
        with pytest.raises(StreamError, match="no bytes"):
            guard.check(0, 0.3, "recv")


# ---------------------------------------------------------------------------
# Ring-shard re-layout + checkpoint round trip
# ---------------------------------------------------------------------------
class TestRingReshard:
    def test_shard_concat_roundtrip(self):
        full = np.arange(23, dtype=np.float32)
        for world in (1, 2, 3, 4, 5):
            shards = [shard_for_rank(full, 23, world, r)
                      for r in range(world)]
            np.testing.assert_array_equal(
                concat_ring_shards(shards, 23), full)

    def test_reshard_preserves_values_any_world(self):
        import optax

        n = 37
        tx = optax.adam(1e-2)
        full_m = np.arange(n, dtype=np.float32) * 3 + 1
        full_v = np.arange(n, dtype=np.float32) * 7 + 2
        import jax.numpy as jnp

        from horovod_tpu.statesync.snapshot import ring_chunk
        world_old = 4
        chunk_old = ring_chunk(n, world_old)
        shards = []
        for r in range(world_old):
            st = tx.init(jnp.zeros((chunk_old,), jnp.float32))
            st = (st[0]._replace(
                count=jnp.int32(9),
                mu=jnp.asarray(shard_for_rank(full_m, n, world_old, r)),
                nu=jnp.asarray(shard_for_rank(full_v, n, world_old, r))),
                st[1])
            shards.append(st)
        for new_world in (1, 2, 5):
            for nr in range(new_world):
                out = reshard_ring_state(shards, n, new_world, nr)
                np.testing.assert_array_equal(
                    out[0].mu, shard_for_rank(full_m, n, new_world, nr))
                np.testing.assert_array_equal(
                    out[0].nu, shard_for_rank(full_v, n, new_world, nr))
                assert int(out[0].count) == 9

    def test_reshard_rejects_torn_replicated_leaf(self):
        import optax
        import jax.numpy as jnp

        from horovod_tpu.statesync.snapshot import ring_chunk
        tx = optax.adam(1e-2)
        chunk = ring_chunk(8, 2)
        s0 = tx.init(jnp.zeros((chunk,), jnp.float32))
        s1 = (s0[0]._replace(count=jnp.int32(3)), s0[1])
        with pytest.raises(ValueError, match="differs across shards"):
            reshard_ring_state([s0, s1], 8, 1, 0)


class TestRingCheckpoint:
    def _run_ring_steps(self, world, steps, tx, params, grads_by_step,
                        cfg):
        """Drive sync_and_apply on a virtual device mesh; returns
        (params, stacked per-rank opt state) after `steps` steps."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh
        from jax.sharding import PartitionSpec as P

        from jax import shard_map
        from horovod_tpu.parallel import (init_ring_optimizer_state,
                                          sync_and_apply)

        mesh = Mesh(np.array(jax.devices()[:world]), ("dp",))
        os0 = init_ring_optimizer_state(tx, params, world, cfg)
        os_stacked = jax.tree_util.tree_map(
            lambda leaf: jnp.broadcast_to(leaf, (world,) + leaf.shape)
            if getattr(leaf, "ndim", 0) >= 1 else leaf, os0)
        os_specs = jax.tree_util.tree_map(
            lambda leaf: P("dp") if getattr(leaf, "ndim", 0) >= 2
            else P(), os_stacked)
        p_stacked = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(jnp.asarray(x),
                                       (world,) + x.shape), params)

        def step(g, p, s):
            p_local = jax.tree_util.tree_map(lambda x: x[0], p)
            s_local = jax.tree_util.tree_map(
                lambda leaf: leaf[0] if getattr(leaf, "ndim", 0) >= 2
                else leaf, s)
            new_p, new_s = sync_and_apply(tx, g, p_local, s_local, cfg)
            return (jax.tree_util.tree_map(lambda x: x[None], new_p),
                    jax.tree_util.tree_map(
                        lambda leaf: leaf[None]
                        if getattr(leaf, "ndim", 0) >= 1 else leaf,
                        new_s))

        fn = jax.jit(shard_map(step, mesh=mesh,
                               in_specs=(P("dp"), P("dp"), os_specs),
                               out_specs=(P("dp"), os_specs),
                               check_vma=False))
        for k in range(steps):
            p_stacked, os_stacked = fn(grads_by_step[k], p_stacked,
                                       os_stacked)
        params_out = jax.tree_util.tree_map(
            lambda x: np.asarray(x)[0], p_stacked)
        return params_out, os_stacked

    def test_round_trip_across_world_sizes_matches_replicated(
            self, tmp_path):
        """The satellite's parity criterion: ring shards saved at world
        4 restore at worlds 1/2/3 bit-identical to the re-cut layout,
        and the world-1 restore equals the REPLICATED optimizer state
        of the same training prefix (flat-padded layout)."""
        import jax.numpy as jnp
        import optax

        from horovod_tpu import checkpoint as ck
        from horovod_tpu.parallel import GradSyncConfig
        from horovod_tpu.statesync.snapshot import ring_chunk

        world = 4
        rng = np.random.default_rng(7)
        params = {"w": rng.standard_normal(11).astype(np.float32)}
        grads = [{"w": np.tile(
            rng.standard_normal(11).astype(np.float32), (world, 1))}
            for _ in range(2)]
        tx = optax.adam(1e-2)
        cfg = GradSyncConfig(axes=("dp",), op="average",
                             optimizer_in_ring=True)
        _, os_stacked = self._run_ring_steps(world, 2, tx, params,
                                             grads, cfg)
        import jax

        for r in range(world):
            shard = jax.tree_util.tree_map(
                lambda leaf, r=r: np.asarray(leaf)[r]
                if getattr(leaf, "ndim", 0) >= 2 else np.asarray(leaf),
                os_stacked)
            ck.save_ring_checkpoint(str(tmp_path), shard, rank=r,
                                    world=world, n_params=11, step=2)
        # Parity vs the replicated path: the same two updates applied
        # by a replicated optimizer over the padded flat buffer.
        n = 11
        chunk1 = ring_chunk(n, 1)
        rep_state = tx.init(jnp.zeros((chunk1,), jnp.float32))
        for g in grads:
            flat = np.zeros(chunk1, np.float32)
            flat[:n] = np.asarray(g["w"]).mean(axis=0)
            upd, rep_state = tx.update(jnp.asarray(flat), rep_state,
                                       jnp.zeros((chunk1,),
                                                 jnp.float32))
        restored1, step = ck.restore_ring_checkpoint(
            str(tmp_path), tx, rank=0, world=1, n_params=n)
        assert step == 2
        np.testing.assert_allclose(np.asarray(restored1[0].mu)[:n],
                                   np.asarray(rep_state[0].mu)[:n],
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(restored1[0].nu)[:n],
                                   np.asarray(rep_state[0].nu)[:n],
                                   rtol=1e-6, atol=1e-7)
        assert int(restored1[0].count) == int(rep_state[0].count) == 2
        # Restores at other world sizes are exact re-cuts of world 1.
        full_mu = np.asarray(restored1[0].mu)
        for new_world in (2, 3):
            for nr in range(new_world):
                st, _ = ck.restore_ring_checkpoint(
                    str(tmp_path), tx, rank=nr, world=new_world,
                    n_params=n)
                np.testing.assert_array_equal(
                    np.asarray(st[0].mu),
                    shard_for_rank(full_mu[:n], n, new_world, nr))

    def test_restore_rejects_corrupt_and_torn(self, tmp_path):
        import optax

        from horovod_tpu import checkpoint as ck
        from horovod_tpu.statesync.snapshot import ring_chunk
        import jax.numpy as jnp

        tx = optax.adam(1e-2)
        chunk = ring_chunk(6, 2)
        for r in range(2):
            ck.save_ring_checkpoint(
                str(tmp_path), tx.init(jnp.zeros((chunk,), jnp.float32)),
                rank=r, world=2, n_params=6, step=r)   # torn: steps 0,1
        with pytest.raises(ValueError, match="torn ring checkpoint"):
            ck.restore_ring_checkpoint(str(tmp_path), tx, rank=0,
                                       world=2, n_params=6)
        # Corrupt one shard's bytes: the digest check refuses.
        victim = os.path.join(str(tmp_path), "ring-1-of-2.state")
        blob = bytearray(open(victim, "rb").read())
        blob[0] ^= 0xff
        open(victim, "wb").write(bytes(blob))
        with pytest.raises(ValueError, match="digest check"):
            ck.restore_ring_checkpoint(str(tmp_path), tx, rank=0,
                                       world=2, n_params=6)


# ---------------------------------------------------------------------------
# Autoscale policy + controller
# ---------------------------------------------------------------------------
class TestAutoscale:
    def _policy(self, **kw):
        kw.setdefault("up_shed_rate", 0.05)
        kw.setdefault("up_queue_fraction", 0.5)
        kw.setdefault("down_lag_ms", 50.0)
        kw.setdefault("hysteresis_rounds", 3)
        kw.setdefault("queue_depth_limit", 100)
        return AutoscalePolicy(2, 8, **kw)

    def test_scale_up_needs_sustained_overload(self):
        p = self._policy()
        assert p.observe(4, shed_rate=0.5) is None
        assert p.observe(4, shed_rate=0.5) is None
        d = p.observe(4, shed_rate=0.5)
        assert d is not None and d.direction == "up" and d.target == 5

    def test_one_burst_never_flaps(self):
        p = self._policy()
        assert p.observe(4, shed_rate=0.5) is None
        assert p.observe(4, shed_rate=0.0) is None   # streak broken
        assert p.observe(4, shed_rate=0.5) is None
        assert p.observe(4, shed_rate=0.5) is None
        assert p.observe(4, shed_rate=0.5) is not None

    def test_cooldown_after_decision(self):
        p = self._policy(hysteresis_rounds=1)
        assert p.observe(4, shed_rate=0.5).direction == "up"
        # Cooldown: the next interval cannot fire even under overload.
        assert p.observe(5, shed_rate=0.9) is None

    def test_scale_down_on_idle_straggler(self):
        p = self._policy(hysteresis_rounds=2)
        assert p.observe(4, straggler_lag_ms=80.0) is None
        d = p.observe(4, straggler_lag_ms=80.0)
        assert d is not None and d.direction == "down" and d.target == 3

    def test_no_scale_down_under_load(self):
        """A dragging rank under active shedding is an overload signal
        (scale up wins), never a scale-down."""
        p = self._policy(hysteresis_rounds=1)
        d = p.observe(4, straggler_lag_ms=80.0, shed_rate=0.2)
        assert d is not None and d.direction == "up"

    def test_bounds_respected(self):
        p = self._policy(hysteresis_rounds=1)
        assert p.observe(8, shed_rate=0.9) is None       # at max_np
        p2 = self._policy(hysteresis_rounds=1)
        assert p2.observe(2, straggler_lag_ms=99.0) is None   # at min_np

    def test_controller_drives_driver_and_metrics(self):
        class StubDriver:
            def __init__(self):
                self.targets = []

            def world_size(self):
                return 4

            def set_target_np(self, n):
                self.targets.append(n)

        gauges = {"queue_depth": 0.0, "shed_rate": 0.4,
                  "straggler_lag_ms": 0.0}
        driver = StubDriver()
        ctl = AutoscaleController(
            driver, lambda: dict(gauges),
            self._policy(hysteresis_rounds=2), interval=999.0)
        assert ctl.tick() is None
        d = ctl.tick()
        assert d is not None and driver.targets == [5]
        assert ctl.decisions == [d]


# ---------------------------------------------------------------------------
# Elastic driver: blacklist re-admission + autoscale target
# ---------------------------------------------------------------------------
class TestBlacklistReadmission:
    def _mgr(self, slots=2, cooldown=None):
        from collections import OrderedDict

        from horovod_tpu.elastic.discovery import (FixedHostDiscovery,
                                                   HostManager)
        return HostManager(
            FixedHostDiscovery(OrderedDict(a=slots, b=2)),
            blacklist_cooldown=cooldown)

    def test_manual_clear_readmits_with_fresh_slots(self):
        from collections import OrderedDict

        from horovod_tpu.elastic.discovery import (FixedHostDiscovery,
                                                   HostManager)
        disc = FixedHostDiscovery(OrderedDict(a=2, b=2))
        mgr = HostManager(disc)
        mgr.update_available_hosts()
        mgr.blacklist("a")
        mgr.update_available_hosts()
        assert "a" not in mgr.current_hosts
        # The host returns with a DIFFERENT slot count; clearing must
        # pick up the refreshed count, not any remembered one.
        disc._hosts["a"] = 4
        assert mgr.clear_blacklist("a") is True
        assert not mgr.is_blacklisted("a")
        mgr.update_available_hosts()
        assert mgr.current_hosts["a"] == 4

    def test_clear_unknown_host_is_noop(self):
        mgr = self._mgr()
        assert mgr.clear_blacklist("nope") is False

    def test_cooldown_expiry_readmits(self):
        mgr = self._mgr(cooldown=0.05)
        mgr.update_available_hosts()
        mgr.blacklist("a")
        assert mgr.is_blacklisted("a")
        mgr.update_available_hosts()
        assert "a" not in mgr.current_hosts
        time.sleep(0.08)
        mgr.update_available_hosts()
        assert "a" in mgr.current_hosts
        assert not mgr.blacklisted_hosts

    def test_explicit_cooldown_overrides_default(self):
        mgr = self._mgr(cooldown=None)
        mgr.blacklist("a", cooldown=0.05)
        time.sleep(0.08)
        assert not mgr.is_blacklisted("a")

    def test_forever_without_cooldown(self):
        mgr = self._mgr()
        mgr.blacklist("a")
        time.sleep(0.05)
        assert mgr.is_blacklisted("a")

    def test_driver_target_np_clamped(self):
        from horovod_tpu.elastic.driver import ElasticDriver
        from horovod_tpu.elastic.discovery import FixedHostDiscovery
        from collections import OrderedDict

        driver = ElasticDriver(FixedHostDiscovery(OrderedDict(a=8)),
                               min_np=2, max_np=6)
        driver.set_target_np(99)
        assert driver.target_np() == 6
        driver.set_target_np(1)
        assert driver.target_np() == 2
        driver.set_target_np(4)
        assert driver.target_np() == 4


# ---------------------------------------------------------------------------
# Chaos preempt action
# ---------------------------------------------------------------------------
class TestChaosPreempt:
    def test_parse_and_defaults(self):
        from horovod_tpu.resilience.chaos import parse_spec

        act = parse_spec("preempt:rank=2,op=7")[0]
        assert act.kind == "preempt"
        assert act.rank == 2 and act.op == 7
        assert act.count == 1   # one notice, not a repeating signal

    def test_delivers_sigterm_and_survives(self):
        """The preempt action sends SIGTERM and KEEPS RUNNING — the
        grace path owns the departure."""
        from horovod_tpu.resilience.chaos import ChaosEngine

        hits = []
        old = signal.signal(signal.SIGTERM, lambda s, f: hits.append(s))
        try:
            eng = ChaosEngine("preempt:rank=0,op=1", rank=0)
            assert eng.on_response(["t0"]) is None
            assert not hits
            assert eng.on_response(["t1"]) is None   # op 1: fires
            assert hits == [signal.SIGTERM]
            assert eng.on_response(["t2"]) is None   # count exhausted
            assert hits == [signal.SIGTERM]
        finally:
            signal.signal(signal.SIGTERM, old)

    def test_launch_rank_identity_survives_renumbering(self):
        """The PR 9 kill-fix discipline holds for preempt: the engine
        (and its rank identity) is reused across a re-init as long as
        the spec is unchanged."""
        from horovod_tpu.resilience import chaos as chaos_mod

        os.environ["HOROVOD_CHAOS"] = "preempt:rank=1,op=99"
        try:
            e1 = chaos_mod.configure(1)
            e2 = chaos_mod.configure(0)   # renumbered after a shrink
            assert e1 is e2 and e2.rank == 1
        finally:
            del os.environ["HOROVOD_CHAOS"]
            chaos_mod.configure(0)


# ---------------------------------------------------------------------------
# Donation + lint rule
# ---------------------------------------------------------------------------
class TestDonation:
    def test_fetch_donation_verifies_digest(self, kv_server):
        from horovod_tpu.statesync.service import (_donate_scope,
                                                   fetch_donation)

        tree = {"shard": np.arange(32, dtype=np.float32)}
        image = flatten_state(tree)
        kv_server.put(_donate_scope("ep"), "1.meta", json.dumps(
            {"digest": state_digest(image), "nbytes": len(image),
             "seq": 3}).encode())
        kv_server.put(_donate_scope("ep"), "1", bytes(image))
        out = fetch_donation("ep", 1, {"shard": np.zeros(32, np.float32)},
                             kv=kv_server)
        np.testing.assert_array_equal(out["shard"], tree["shard"])
        # Tampered payload: rejected, never unflattened.
        kv_server.put(_donate_scope("ep"), "1",
                      bytes(bytearray([image[0] ^ 0xff]) + image[1:]))
        assert fetch_donation("ep", 1,
                              {"shard": np.zeros(32, np.float32)},
                              kv=kv_server) is None

    def test_missing_donation_is_none(self, kv_server):
        from horovod_tpu.statesync.service import fetch_donation

        assert fetch_donation("ep", 7, {"x": np.zeros(1)},
                              kv=kv_server) is None

    def test_kv_delete_consumes_marks(self, kv_server):
        """RendezvousClient.delete: a failed join attempt consumes its
        stale announcement so no watcher ever replays it."""
        kv_server.put("ssgrow.e", "join:0", b"{}")
        assert kv_server.get("ssgrow.e", "join:0") == b"{}"
        kv_server.delete("ssgrow.e", "join:0")
        assert kv_server.get("ssgrow.e", "join:0") is None


class TestHttpSource:
    def test_scrapes_exposition_and_deltas(self):
        import http.server
        import threading as _threading

        from horovod_tpu.statesync.autoscale import http_source

        body = [(b"# HELP x\n"
                 b'horovod_serve_requests_total{outcome="served"} 10\n'
                 b'horovod_serve_requests_total{outcome="shed"} 0\n'
                 b"horovod_serve_queue_depth 12\n"
                 b"horovod_controller_straggler_lag_ms 7.5\n")]

        class H(http.server.BaseHTTPRequestHandler):
            def do_GET(self):
                self.send_response(200)
                self.send_header("Content-Length", str(len(body[0])))
                self.end_headers()
                self.wfile.write(body[0])

            def log_message(self, *a):
                pass

        srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), H)
        _threading.Thread(target=srv.serve_forever, daemon=True).start()
        try:
            src = http_source(
                f"http://127.0.0.1:{srv.server_address[1]}/")
            s1 = src()
            assert s1["queue_depth"] == 12.0
            assert s1["straggler_lag_ms"] == 7.5
            # Second scrape: 10 more served, 10 shed -> shed_rate 0.5.
            body[0] = (
                b'horovod_serve_requests_total{outcome="served"} 20\n'
                b'horovod_serve_requests_total{outcome="shed"} 10\n')
            s2 = src()
            assert s2["shed_rate"] == pytest.approx(0.5)
        finally:
            srv.shutdown()
            srv.server_close()

    def test_unreachable_endpoint_reads_idle(self):
        from horovod_tpu.statesync.autoscale import http_source

        src = http_source("http://127.0.0.1:1/", timeout=0.2)
        s = src()
        assert s == {"queue_depth": 0.0, "shed_rate": 0.0,
                     "straggler_lag_ms": 0.0}


class TestLintRule:
    FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "fixtures", "lint", "statesync",
                           "unverified_frame.py")

    def test_fixture_flags_unverified_reads_only(self):
        from horovod_tpu.analysis.lint import lint_paths

        violations = [v for v in lint_paths([self.FIXTURE])
                      if v.rule.id == "HVD1007"]
        assert len(violations) == 2, violations
        # The verified forms (digest in scope / pull_round) pass.
        texts = "\n".join(v.text() for v in violations)
        assert "apply_streamed_state" in texts
        assert "apply_chunk_blind" in texts
        assert "apply_verified_state" not in texts
        assert "pull_and_apply" not in texts

    def test_statesync_tree_is_hvd1007_clean(self):
        from horovod_tpu.analysis.lint import LintConfig, lint_paths

        cfg = LintConfig(select={"HVD1007"})
        violations = lint_paths(
            [os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "horovod_tpu",
                "statesync")], cfg)
        assert violations == [], "\n".join(v.text() for v in violations)
