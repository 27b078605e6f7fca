"""fleetsim — the rank-virtualized O(500) scale harness (ISSUE 16).

- Loopback fabric units: barrier-allgather completion, arrival capture,
  idempotent transitions, removal of silently-dead members, abort.
- Host-group KV proxy units: heartbeat stamps coalesce into put_many
  batches; bye stamps bypass the buffer; snapshot reads collapse the
  per-peer poll fan-out.
- WAL group-commit coalescing at N=64 (telemetry-counter asserted).
- In-process fleet episodes: clean run, chaos kill/preempt composition
  through the UNCHANGED grammar, straggler attribution at fleet scale.
- Tier-1 smoke: one worker process hosting 32 virtual ranks against a
  real external rendezvous server (the mp battery plumbing).
- Slow battery: 256 virtual ranks riding a coordkill of the primary
  mid-run plus a 10% preemption wave — zero failed steps, bounded
  control-plane verb latency, console renders the episode.
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_multiprocess import _run_world  # noqa: E402

from horovod_tpu import telemetry  # noqa: E402
from horovod_tpu.fleetsim import (FleetConfig, FleetDesyncError,  # noqa: E402
                                  FleetSim, HostGroupSession,
                                  LoopbackFabric)
from horovod_tpu.runner import controlplane as cp  # noqa: E402
from horovod_tpu.runner.network import (RendezvousClient,  # noqa: E402
                                        RendezvousServer, free_port)


# --- loopback fabric --------------------------------------------------------
class TestLoopbackFabric:
    def test_exchange_completes_and_captures_arrivals(self):
        fab = LoopbackFabric(range(3), "e0")
        out = {}

        def body(vid):
            views, arrivals = fab.exchange("e0", 0, vid, {"v": vid}, 5.0)
            out[vid] = (views, arrivals)

        threads = [threading.Thread(target=body, args=(v,))
                   for v in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10.0)
        assert set(out) == {0, 1, 2}
        for views, arrivals in out.values():
            assert {v["v"] for v in views.values()} == {0, 1, 2}
            assert set(arrivals) == {0, 1, 2}

    def test_remove_unblocks_survivors(self):
        fab = LoopbackFabric(range(2), "e0")
        got = {}

        def body():
            got["views"], _ = fab.exchange("e0", 0, 0, {"v": 0}, 5.0)

        t = threading.Thread(target=body)
        t.start()
        time.sleep(0.05)
        fab.remove(1)           # silent death: no slot ever arrives
        t.join(5.0)
        assert not t.is_alive()
        assert set(got["views"]) == {0}   # missing slot = hard failure

    def test_transition_idempotent_and_divergence_detected(self):
        fab = LoopbackFabric(range(3), "e0")
        fab.transition("e1", [0, 1])
        fab.transition("e1", [0, 1])      # second folder: verify only
        assert fab.epoch == "e1"
        with pytest.raises(FleetDesyncError):
            fab.transition("e1", [0, 2])  # divergent fold
        with pytest.raises(FleetDesyncError):
            fab.exchange("e0", 5, 0, {}, 0.1)   # stale epoch

    def test_abort_wakes_waiters(self):
        fab = LoopbackFabric(range(2), "e0")
        err = {}

        def body():
            try:
                fab.exchange("e0", 0, 0, {}, 30.0)
            except FleetDesyncError as exc:
                err["exc"] = exc

        t = threading.Thread(target=body)
        t.start()
        time.sleep(0.05)
        fab.abort()
        t.join(5.0)
        assert not t.is_alive()
        assert "aborted" in str(err["exc"])


# --- host-group KV proxy ----------------------------------------------------
class _FakeClient:
    def __init__(self):
        self.puts = []
        self.batches = []
        self.scope_reads = 0

    def put(self, scope, key, value):
        self.puts.append((scope, key, value))

    def put_many(self, records):
        self.batches.append(list(records))

    def get_scope(self, scope):
        self.scope_reads += 1
        return {"0": b"1|100"}


class TestHostGroupSession:
    def test_hb_stamps_coalesce_into_batches(self):
        client = _FakeClient()
        sess = HostGroupSession(client, group_size=4, flush_age_s=60.0)
        for vid in range(4):
            sess.put("hb", f"e:{vid}", f"{vid}|1".encode())
        assert len(client.batches) == 1       # full group -> one batch
        assert len(client.batches[0]) == 4
        assert client.puts == []

    def test_bye_stamps_bypass_the_buffer(self):
        client = _FakeClient()
        sess = HostGroupSession(client, group_size=8, flush_age_s=60.0)
        sess.put("hb", "e:0", b"bye|7")
        assert client.puts == [("hb", "e:0", b"bye|7")]
        assert client.batches == []

    def test_flush_drains_partial_buffer(self):
        client = _FakeClient()
        sess = HostGroupSession(client, group_size=8, flush_age_s=60.0)
        sess.put("hb", "e:0", b"0|1")
        sess.put("hb", "e:0", b"0|2")   # later stamp overwrites
        sess.flush()
        assert len(client.batches) == 1
        assert client.batches[0] == [("hb", "e:0", b"0|2")]

    def test_snapshot_collapses_poll_fanout(self):
        client = _FakeClient()
        sess = HostGroupSession(client, group_size=4,
                                snapshot_ttl_s=60.0)
        for _ in range(32):
            sess.snapshot_get("hb", "e:0")
        assert client.scope_reads == 1        # one dump serves them all


# --- WAL group commit -------------------------------------------------------
def test_wal_group_commit_coalesces_at_64(tmp_path):
    """ISSUE 16 satellite: one host-group put_many of 64 heartbeat
    stamps must land as 64 WAL records in a HANDFUL of fsync batches
    (the group-commit telemetry counters are the evidence)."""
    os.environ["HOROVOD_METRICS"] = "on"
    try:
        reg = telemetry.configure()

        def counter(name):
            return sum(e["value"] for e in reg.snapshot()["metrics"]
                       if e["name"] == name)

        server = RendezvousServer(wal_dir=str(tmp_path))
        port = server.start()
        try:
            client = RendezvousClient(f"127.0.0.1:{port}", timeout=10.0)
            base_records = counter(
                "horovod_rendezvous_wal_records_total")
            base_batches = counter(
                "horovod_rendezvous_wal_commit_batches_total")
            client.put_many([("hb", f"fleet:{i}", f"{i}|{os.getpid()}"
                              .encode()) for i in range(64)])
            records = counter(
                "horovod_rendezvous_wal_records_total") - base_records
            batches = counter(
                "horovod_rendezvous_wal_commit_batches_total") \
                - base_batches
            assert records == 64
            assert 1 <= batches <= 16, batches   # >=4x coalescing
            # All 64 are durable + readable (FIFO lane: the last
            # record's commit implies the rest).
            assert client.get("hb", "fleet:63") == b"63|%d" % os.getpid()
            # And survive a replay (they really hit the log).
            replayed = cp.replay_state(cp.wal_path(str(tmp_path)))
            assert replayed["kv"]["hb"]["fleet:0"] == b"0|%d" % os.getpid()
        finally:
            server.stop()
    finally:
        os.environ.pop("HOROVOD_METRICS", None)
        telemetry.configure()


# --- in-process fleet episodes ----------------------------------------------
def _in_proc_fleet(tmp_path, monkeypatch, *, ranks, steps, chaos="",
                   **cfg_kw):
    monkeypatch.setenv("HOROVOD_METRICS", "on")
    if chaos:
        monkeypatch.setenv("HOROVOD_CHAOS", chaos)
    else:
        monkeypatch.delenv("HOROVOD_CHAOS", raising=False)
    telemetry.configure()
    server = RendezvousServer()
    port = server.start()
    try:
        cfg = FleetConfig(ranks=ranks, steps=steps, step_ms=2.0,
                          heartbeat_s=0.2, fault_timeout_s=10.0,
                          step_timeout_s=30.0, host_group=8,
                          epoch=f"flt-{tmp_path.name}",
                          endpoints=f"127.0.0.1:{port}", **cfg_kw)
        fleet = FleetSim(cfg)
        return fleet.run()
    finally:
        server.stop()
        telemetry.configure()


def test_clean_episode_all_finish(tmp_path, monkeypatch):
    report = _in_proc_fleet(tmp_path, monkeypatch, ranks=12, steps=6)
    assert report.failed_steps == 0
    assert report.outcomes == {"finished": 12}
    assert report.total_rank_steps == 12 * 6
    assert report.final_world == list(range(12))
    # Host-group batching carried the liveness plane: real put_many
    # traffic was observed by the client histogram.
    assert report.kv_latency_ms.get("put_many", {}).get("count", 0) > 0


def test_chaos_grammar_composes_virtualized(tmp_path, monkeypatch):
    """The UNCHANGED chaos grammar addresses virtual ranks: a silent
    kill at step 2 and an orderly preemption at step 4 both shrink the
    fleet, with zero failed steps for the survivors."""
    report = _in_proc_fleet(
        tmp_path, monkeypatch, ranks=10, steps=8,
        chaos="kill:rank=3,op=2;preempt:rank=7,op=4")
    assert report.outcomes.get("killed") == 1
    assert report.outcomes.get("preempted") == 1
    assert report.outcomes.get("finished") == 8
    assert report.departures == {"kill": 1, "preempt": 1}
    assert report.transitions >= 2
    assert report.failed_steps == 0
    assert report.final_world == [v for v in range(10)
                                  if v not in (3, 7)]


def test_straggler_attributed_at_fleet_scale(tmp_path, monkeypatch):
    """Rank 11 sleeps 40 ms more than its peers every step, and the
    coordinator's aggregator names it by its median lag over a window:
    a loaded host that stalls another rank at one boundary of a window
    does not name that rank."""
    report = _in_proc_fleet(tmp_path, monkeypatch, ranks=16, steps=8,
                            straggler_vid=11, straggler_ms=40.0)
    assert report.failed_steps == 0
    assert report.straggler_rank == 11
    assert report.straggler_lag_ms > 10.0


def test_dump_evidence_roundtrips_through_console(tmp_path,
                                                  monkeypatch):
    dump_dir = tmp_path / "dumps"
    monkeypatch.setenv("HOROVOD_FLIGHT_FILE",
                       str(dump_dir / "flight.json"))
    from horovod_tpu.telemetry import flight
    flight.configure(0)
    try:
        report = _in_proc_fleet(tmp_path, monkeypatch, ranks=6, steps=4,
                                dump_dir=str(dump_dir))
        assert report.failed_steps == 0
        from horovod_tpu.console import load_dump_dir, render
        ep = load_dump_dir(str(dump_dir))
        assert not ep.empty
        assert len(ep.summaries) == 1
        text = render(ep)
        assert "ranks=6 steps=4" in text
        assert "outcomes: finished=6" in text
    finally:
        monkeypatch.delenv("HOROVOD_FLIGHT_FILE", raising=False)
        flight.configure(0)


# --- tier-1 battery: 32 virtual ranks, external control plane --------------
def _parse_summary(outputs):
    for out in outputs:
        for line in out.splitlines():
            if line.startswith("FLEETSIM_SUMMARY "):
                return json.loads(line.split(" ", 1)[1])
    raise AssertionError("no FLEETSIM_SUMMARY line:\n" + "\n".join(outputs))


def test_fleetsim_smoke_32_vranks():
    """One worker process hosts 32 virtual ranks against a real
    external rendezvous server: every rank finishes every step, the
    straggler is attributed, and the host-group batch verbs carried
    the liveness plane."""
    outputs = _run_world(
        1, "fleetsim", timeout=240.0,
        extra_env={
            "HOROVOD_FLEETSIM_RANKS": "32",
            "HOROVOD_FLEETSIM_STEPS": "8",
            "HOROVOD_FLEETSIM_STEP_MS": "2",
            "HOROVOD_FLEETSIM_HOST_GROUP": "8",
            "HOROVOD_FLEETSIM_HEARTBEAT_S": "0.2",
            "HOROVOD_FLEETSIM_FAULT_TIMEOUT_S": "15",
            "HOROVOD_FLEETSIM_STRAGGLER_RANK": "5",
            "HOROVOD_FLEETSIM_STRAGGLER_MS": "30",
        })
    s = _parse_summary(outputs)
    assert s["ranks"] == 32
    assert s["failed_steps"] == 0
    assert s["outcomes"] == {"finished": 32}
    assert s["total_rank_steps"] == 32 * 8
    assert s["straggler_rank"] == 5
    assert s["kv_latency_ms"]["put_many"]["count"] > 0
    assert s["kv_latency_ms"]["get_scope"]["count"] > 0


# --- slow battery: 256 vranks + coordkill + 10% preemption wave ------------
def _spawn_primary(tmp_path, endpoints, lease_ms=500.0):
    port = int(endpoints[0].rsplit(":", 1)[1])
    proc = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.runner.controlplane",
         "--port", str(port), "--wal-dir", str(tmp_path),
         "--replica-id", "0", "--endpoints", ",".join(endpoints),
         "--lease-ms", str(lease_ms)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    line = proc.stdout.readline().decode()
    assert line.startswith("READY"), line
    return proc


@pytest.mark.slow
def test_fleetsim_256_coordkill_preempt_battery(tmp_path):
    """ISSUE 16 acceptance: 256 virtual ranks ride a SIGKILL of the
    rendezvous primary mid-run plus a 10% preemption wave.  Zero
    failed steps, bounded p99 on the rendezvous KV verbs (from the
    client telemetry histograms), and the console renders the full
    episode — failover, preemption departures, straggler attribution —
    from the rank-stamped dumps."""
    ports = [free_port(), free_port()]
    eps = [f"127.0.0.1:{p}" for p in ports]
    proc = _spawn_primary(tmp_path, eps, lease_ms=500.0)
    # Metrics ON in this process BEFORE the standby exists: its
    # WalWriter binds the group-commit counters here, so the test can
    # assert the post-promotion fan-in coalesced.
    os.environ["HOROVOD_METRICS"] = "on"
    reg = telemetry.configure()
    standby = RendezvousServer(port=ports[1], wal_dir=str(tmp_path),
                               replica_id=1, endpoints=eps,
                               lease_ms=500.0, standby=True)
    standby.start()
    dump_dir = tmp_path / "dumps"
    ranks, steps = 256, 10
    victims = list(range(10, 10 + ranks // 10))   # 10% wave: v10..v35
    chaos = ";".join(["coordkill:at=4"]
                     + [f"preempt:rank={v},op=6" for v in victims])
    try:
        outputs = _run_world(
            1, "fleetsim", timeout=540.0,
            extra_env={
                "HOROVOD_GLOO_RENDEZVOUS_ADDR": ",".join(eps),
                "HOROVOD_GLOO_RENDEZVOUS_PORT": str(ports[0]),
                "HOROVOD_RENDEZVOUS_EPOCH": "fleet256",
                "HOROVOD_CHAOS": chaos,
                "HOROVOD_FLEETSIM_RANKS": str(ranks),
                "HOROVOD_FLEETSIM_STEPS": str(steps),
                "HOROVOD_FLEETSIM_STEP_MS": "5",
                "HOROVOD_FLEETSIM_HOST_GROUP": "16",
                "HOROVOD_FLEETSIM_HEARTBEAT_S": "1.0",
                "HOROVOD_FLEETSIM_FAULT_TIMEOUT_S": "60",
                "HOROVOD_FLEETSIM_STEP_TIMEOUT_S": "120",
                # 256 GIL-contended threads put the scheduling-noise
                # floor on boundary arrivals around ~100ms; the
                # injected straggler delay must dominate it for the
                # attribution to name the right rank.
                "HOROVOD_FLEETSIM_STRAGGLER_RANK": "100",
                "HOROVOD_FLEETSIM_STRAGGLER_MS": "400",
                "HOROVOD_FLEETSIM_DUMP_DIR": str(dump_dir),
            })
        s = _parse_summary(outputs)
        # Zero failed steps across the whole episode.
        assert s["failed_steps"] == 0, s
        assert s["ranks"] == ranks
        assert s["outcomes"].get("finished") == ranks - len(victims)
        assert s["outcomes"].get("preempted") == len(victims)
        assert s["departures"] == {"preempt": len(victims)}
        assert s["transitions"] >= 1
        assert len(s["final_world"]) == ranks - len(victims)
        # The coordkill really landed and the standby promoted.
        proc.wait(timeout=10)
        assert proc.returncode == -signal.SIGKILL
        assert standby.controlplane.role == "primary"
        assert standby.controlplane.failovers == 1
        assert s["primaries_seen"] == eps    # both replicas led
        # Bounded control-plane latency THROUGH the failover: p99 per
        # rendezvous KV verb from the client-side histograms.
        lat = s["kv_latency_ms"]
        assert lat["put_many"]["count"] > 0
        for verb, row in lat.items():
            assert row["p99"] < 15000.0, (verb, row)
        for verb in ("put_many", "get_scope"):
            assert lat[verb]["p99"] < 8000.0, (verb, lat[verb])
        # WAL group commit coalesced the fleet's liveness fan-in: the
        # promoted standby's lane counters live in THIS process.
        def counter(name):
            return sum(e["value"] for e in reg.snapshot()["metrics"]
                       if e["name"] == name)
        records = counter("horovod_rendezvous_wal_records_total")
        batches = counter("horovod_rendezvous_wal_commit_batches_total")
        assert records > 0
        assert batches <= records
        # Console renders the full episode from the rank-stamped dumps.
        from horovod_tpu.console import (load_dump_dir, render,
                                         summary_lines)
        ep = load_dump_dir(str(dump_dir))
        assert not ep.empty
        text = render(ep)
        assert f"ranks={ranks} steps={steps}" in text
        assert "failovers: 1" in text
        assert f"preempted={len(victims)}" in text
        assert "rank=100" in text            # straggler attribution
        lines = summary_lines(ep)
        assert any("failovers=1" in line for line in lines)
        assert any(f"preempt={len(victims)}" in line
                   for line in lines)
    finally:
        if proc.poll() is None:
            proc.kill()
        standby.stop()
        os.environ.pop("HOROVOD_METRICS", None)
        telemetry.configure()
