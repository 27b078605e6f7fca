"""fleet/ — unified train+serve controller (ISSUE 20).

Three layers:

- **units** — FleetPolicy (pure logic: hysteresis, cooldown, floors,
  the oscillation bound), FleetController against a fake KV (journal
  lifecycle, failover-mid-migration resume/abort, deadline abort), and
  the WeightPublisher/WeightPuller round-trip (shards -> meta -> head
  ordering, digest verify-before-stage, torn-fetch retry, GC);
- **loadgen accounting** — the SERVE report's weight-version mix and
  staleness fields;
- **the 4-rank acceptance battery** — two live statesync worlds on one
  coordinator KV: a serving burst triggers a traffic-driven
  train->serve migration (orderly departure, peer-streamed join) AND a
  mid-run weight push lands on every serving replica at one broadcast
  plan boundary; the flight dumps replay through the hvdmc witness.
"""
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_multiprocess import _run_world  # noqa: E402
from test_statesync import _replay_witness, _witness_env  # noqa: E402

from horovod_tpu.fleet import (  # noqa: E402
    CTL_SCOPE, JOURNAL_SCOPE, PUB_SCOPE, SERVE_TO_TRAIN, TRAIN_TO_SERVE,
    FleetController, FleetPolicy, WeightPublisher, WeightPuller,
    mark_joined, poll_depart, publish_gauge)
from horovod_tpu.statesync.snapshot import (  # noqa: E402
    flatten_state, state_digest)


class FakeKV:
    """Dict-backed stand-in for the rendezvous KV client: the exact
    call surface the fleet modules use (put/put_many/get/get_scope/
    claim/delete)."""

    def __init__(self):
        self.data: dict = {}
        self.counters: dict = {}

    def put(self, scope, key, value):
        self.data[(scope, key)] = bytes(value)

    def put_many(self, records):
        for scope, key, value in records:
            self.put(scope, key, value)

    def get(self, scope, key):
        return self.data.get((scope, key))

    def get_scope(self, scope):
        return {k: v for (s, k), v in self.data.items() if s == scope}

    def claim(self, scope, key, **_kw):
        self.counters[(scope, key)] = \
            self.counters.get((scope, key), 0) + 1
        return self.counters[(scope, key)]

    def delete(self, scope, key):
        self.data.pop((scope, key), None)


# ---------------------------------------------------------------------------
# FleetPolicy
# ---------------------------------------------------------------------------
def _policy(**kw):
    base = dict(min_train=1, min_serve=1, up_shed_rate=0.05,
                up_queue_fraction=0.5, idle_queue_fraction=0.2,
                train_lag_ms=50.0, hysteresis_rounds=3,
                cooldown_rounds=0, queue_depth_limit=10)
    base.update(kw)
    return FleetPolicy(**base)


def test_policy_hysteresis_requires_consecutive_rounds():
    p = _policy(hysteresis_rounds=3)
    assert p.observe(4, 2, queue_depth=10.0) is None
    assert p.observe(4, 2, queue_depth=10.0) is None
    # A cold round breaks the streak: the count starts over.
    assert p.observe(4, 2, queue_depth=0.0) is None
    assert p.observe(4, 2, queue_depth=10.0) is None
    assert p.observe(4, 2, queue_depth=10.0) is None
    d = p.observe(4, 2, queue_depth=10.0)
    assert d is not None and d.direction == TRAIN_TO_SERVE and d.n == 1


def test_policy_shed_rate_alone_marks_serving_hot():
    p = _policy(hysteresis_rounds=1)
    d = p.observe(4, 2, shed_rate=0.10, queue_depth=0.0)
    assert d is not None and d.direction == TRAIN_TO_SERVE
    assert "shed" in d.reason


def test_policy_cooldown_silences_after_decision():
    p = _policy(hysteresis_rounds=1, cooldown_rounds=2)
    assert p.observe(4, 2, queue_depth=10.0) is not None
    # Two cooldown rounds: hot gauges are ignored entirely.
    assert p.observe(4, 2, queue_depth=10.0) is None
    assert p.observe(4, 2, queue_depth=10.0) is None
    assert p.observe(4, 2, queue_depth=10.0) is not None


def test_policy_reverse_direction_needs_idle_serving():
    p = _policy(hysteresis_rounds=1)
    # Trainer drags but serving is NOT idle: no move.
    assert p.observe(4, 2, queue_depth=5.0,
                     straggler_lag_ms=200.0) is None
    d = p.observe(4, 2, queue_depth=0.0, straggler_lag_ms=200.0)
    assert d is not None and d.direction == SERVE_TO_TRAIN


def test_policy_floors_are_hard():
    p = _policy(hysteresis_rounds=1, min_train=2, min_serve=2)
    # train at the floor: the hot serving gauge proposes nothing.
    for _ in range(5):
        assert p.observe(2, 2, queue_depth=10.0) is None
    # serve at the floor: the starved trainer proposes nothing.
    for _ in range(5):
        assert p.observe(4, 2, queue_depth=0.0,
                         straggler_lag_ms=200.0) is None
    assert p.decisions == 0
    assert p.observe(3, 2, queue_depth=10.0) is not None


def test_policy_oscillation_bound_under_adversarial_gauges():
    """Migrations in any window of R rounds are bounded by
    R / (hysteresis + cooldown) no matter how the gauges flap."""
    hys, cool, rounds = 2, 3, 120
    p = _policy(hysteresis_rounds=hys, cooldown_rounds=cool)
    decisions = 0
    for i in range(rounds):
        if (i // 2) % 2 == 0:          # flap every two rounds
            d = p.observe(4, 4, queue_depth=10.0)
        else:
            d = p.observe(4, 4, queue_depth=0.0,
                          straggler_lag_ms=200.0)
        decisions += d is not None
    assert decisions == p.decisions
    assert decisions <= rounds // (hys + cool) + 1, decisions


# ---------------------------------------------------------------------------
# FleetController: journal lifecycle + failover
# ---------------------------------------------------------------------------
def _controller(kv, **kw):
    # Cooldown matters here: the gauges in the KV stay hot after a
    # migration settles, and without it the very next tick would fire
    # a second one.
    base = dict(policy=_policy(hysteresis_rounds=1, cooldown_rounds=100),
                interval_s=0.01, migrate_timeout_s=60.0)
    base.update(kw)
    ctl = FleetController(kv, **base)
    ctl.recover()
    return ctl


def test_controller_full_migration_lifecycle():
    kv = FakeKV()
    ctl = _controller(kv)
    publish_gauge(kv, "train", 4, straggler_lag_ms=0.0)
    publish_gauge(kv, "serve", 2, shed_rate=0.0, queue_depth=10.0)
    rec = ctl.tick()
    assert rec is not None and rec["state"] == "departing"
    assert rec["direction"] == TRAIN_TO_SERVE and rec["rank"] == 3
    # The directive is addressed to the donor world's highest rank.
    directive = poll_depart(kv, "train", 3)
    assert directive is not None and directive["mid"] == rec["mid"]
    assert poll_depart(kv, "train", 2) is None
    # One move settles before the next is considered.
    assert ctl.tick() is None
    mark_joined(kv, rec["mid"], rank=2, size=3)
    ctl.tick()
    journal = json.loads(kv.get(JOURNAL_SCOPE, f"mig:{rec['mid']}"))
    assert journal["state"] == "done"
    assert poll_depart(kv, "train", 3) is None   # directive withdrawn
    # A closed migration leaves nothing in the actuation scope.
    assert kv.get(CTL_SCOPE, f"joined:{rec['mid']}") is None
    assert ctl.stats["completed"] == 1 and not ctl.open


def test_controller_deadline_aborts_wedged_migration():
    """Deadline expiry first only REQUESTS the abort: the directive is
    withdrawn and the journal moves to 'aborting' (the donor may have
    already consumed the directive); silence through the grace window
    finalises it."""
    kv = FakeKV()
    ctl = _controller(kv, migrate_timeout_s=0.0)
    publish_gauge(kv, "train", 4)
    publish_gauge(kv, "serve", 2, queue_depth=10.0)
    rec = ctl.tick()
    assert rec is not None
    ctl.tick()                          # past the (zero) deadline
    journal = json.loads(kv.get(JOURNAL_SCOPE, f"mig:{rec['mid']}"))
    assert journal["state"] == "aborting"
    assert poll_depart(kv, "train", 3) is None   # directive withdrawn
    assert ctl.stats["aborted"] == 0
    assert rec["mid"] in ctl.open       # still watching for a late join
    ctl.tick()                          # past the (zero) abort grace
    journal = json.loads(kv.get(JOURNAL_SCOPE, f"mig:{rec['mid']}"))
    assert journal["state"] == "aborted"
    assert ctl.stats["aborted"] == 1 and not ctl.open


def test_controller_abort_request_reconciles_late_join():
    """The deadline-abort race the journal must not lie about: the
    donor consumed the directive just before the controller withdrew
    it, so the rank really departs and its joined mark lands inside the
    abort grace.  The record reconciles to done — an 'aborted' journal
    here would leak the joined record and let the policy double-shrink
    the donor."""
    kv = FakeKV()
    ctl = _controller(kv, migrate_timeout_s=0.0)
    publish_gauge(kv, "train", 4)
    publish_gauge(kv, "serve", 2, queue_depth=10.0)
    rec = ctl.tick()
    assert rec is not None
    ctl.tick()                          # deadline -> aborting
    assert json.loads(kv.get(
        JOURNAL_SCOPE, f"mig:{rec['mid']}"))["state"] == "aborting"
    mark_joined(kv, rec["mid"], rank=2, size=3)   # the late arrival
    ctl.tick()
    journal = json.loads(kv.get(JOURNAL_SCOPE, f"mig:{rec['mid']}"))
    assert journal["state"] == "done"
    assert "reconciled" in journal["why"]
    assert ctl.stats["completed"] == 1 and ctl.stats["aborted"] == 0
    assert kv.get(CTL_SCOPE, f"joined:{rec['mid']}") is None
    assert not ctl.open


def test_controller_directive_uses_membership_size_over_stale_gauge():
    """The donor gauge says 4 ranks but the statesync membership record
    (refreshed at every world transition) says the world already shrank
    to 3: the directive must address rank 2, not the nonexistent rank 3
    (which would wedge until the deadline abort)."""
    kv = FakeKV()
    ctl = _controller(kv)
    kv.put("statesync", "train",
           json.dumps({"epoch": "e1", "size": 3, "seq": 7}).encode())
    publish_gauge(kv, "train", 4)       # stale: published pre-shrink
    publish_gauge(kv, "serve", 2, queue_depth=10.0)
    rec = ctl.tick()
    assert rec is not None and rec["rank"] == 2
    assert poll_depart(kv, "train", 2) is not None
    assert poll_depart(kv, "train", 3) is None


def test_controller_failover_resumes_departing_migration():
    """The crash window AFTER the directive was published: a successor
    adopts the journal record under its own claimed epoch and keeps
    waiting for the mover's joined mark."""
    kv = FakeKV()
    a = _controller(kv)
    publish_gauge(kv, "train", 4)
    publish_gauge(kv, "serve", 2, queue_depth=10.0)
    rec = a.tick()
    assert rec is not None              # journal=departing, directive up
    b = _controller(kv)                 # controller A dies; B recovers
    assert b.epoch > a.epoch
    assert b.stats["resumed"] == 1 and rec["mid"] in b.open
    adopted = json.loads(kv.get(JOURNAL_SCOPE, f"mig:{rec['mid']}"))
    assert adopted["state"] == "departing"
    assert adopted["epoch"] == b.epoch
    # The mover (possibly mid-join through the whole failover) arrives:
    # B closes the record it never opened.
    mark_joined(kv, rec["mid"], rank=2, size=3)
    b.tick()
    journal = json.loads(kv.get(JOURNAL_SCOPE, f"mig:{rec['mid']}"))
    assert journal["state"] == "done" and b.stats["completed"] == 1


def test_controller_failover_aborts_planned_migration():
    """The crash window BETWEEN journal(planned) and the directive: no
    rank can be acting on the record, so the successor aborts it."""
    kv = FakeKV()
    a = _controller(kv)
    mid = kv.claim(JOURNAL_SCOPE, "seq")
    kv.put(JOURNAL_SCOPE, f"mig:{mid}", json.dumps(
        {"mid": mid, "direction": TRAIN_TO_SERVE, "world": "train",
         "rank": 3, "state": "planned", "epoch": a.epoch,
         "ts": 0.0, "deadline": 1e18}).encode())
    b = _controller(kv)
    journal = json.loads(kv.get(JOURNAL_SCOPE, f"mig:{mid}"))
    assert journal["state"] == "aborted"
    assert "failover" in journal["why"]
    assert b.stats["aborted"] == 1 and not b.open
    assert poll_depart(kv, "train", 3) is None


# ---------------------------------------------------------------------------
# WeightPublisher / WeightPuller round-trip
# ---------------------------------------------------------------------------
def _pub_tree(n=24, fill=1.0):
    return {"params": {"w": np.full(n, fill, np.float32)}}


def _drive(pub):
    """Run the publisher's queued work synchronously (no thread)."""
    while pub._work:
        version, step, image = pub._work.pop(0)
        pub._publish(version, step, image)


def test_publish_pull_roundtrip_with_digest_verify():
    kv = FakeKV()
    pub = WeightPublisher(kv, publish_steps=2, chunk_bytes=16, keep=2)
    assert pub.maybe_publish(1, _pub_tree()) is None   # off-cadence
    assert pub.maybe_publish(2, _pub_tree(fill=2.0)) == 1
    _drive(pub)
    meta = json.loads(kv.get(PUB_SCOPE, "meta:1"))
    assert meta["shards"] > 1                          # really chunked
    assert kv.get(PUB_SCOPE, "head") == b"1"
    staged = []
    pul = WeightPuller(kv, lambda v, img, m: staged.append((v, img, m)))
    assert pul.poll_once() == 1
    assert pul.poll_once() is None                     # no news
    (v, img, m), = staged
    assert v == 1 and m == meta
    assert state_digest(img) == meta["digest"]
    tree = _pub_tree(fill=2.0)
    assert bytes(flatten_state(tree)) == bytes(img)
    assert pul.pulled == 1 and pul.verify_failures == 0


def test_puller_rejects_corrupt_shard_before_staging():
    kv = FakeKV()
    pub = WeightPublisher(kv, publish_steps=1, chunk_bytes=16, keep=2)
    pub.maybe_publish(1, _pub_tree())
    _drive(pub)
    corrupt = bytearray(kv.get(PUB_SCOPE, "shard:1.0"))
    corrupt[0] ^= 0xFF
    kv.put(PUB_SCOPE, "shard:1.0", bytes(corrupt))
    staged = []
    pul = WeightPuller(kv, lambda *a: staged.append(a))
    assert pul.poll_once() is None
    assert pul.verify_failures == 1 and staged == []
    assert pul.seen == 0               # will retry, never staged


def test_puller_retries_torn_fetch():
    kv = FakeKV()
    pub = WeightPublisher(kv, publish_steps=1, chunk_bytes=16, keep=2)
    pub.maybe_publish(1, _pub_tree())
    _drive(pub)
    shard = kv.get(PUB_SCOPE, "shard:1.1")
    kv.delete(PUB_SCOPE, "shard:1.1")  # head visible, shard not yet
    staged = []
    pul = WeightPuller(kv, lambda *a: staged.append(a))
    assert pul.poll_once() is None
    assert pul.verify_failures == 0 and staged == []
    kv.put(PUB_SCOPE, "shard:1.1", shard)
    assert pul.poll_once() == 1 and len(staged) == 1


def test_publisher_gc_keeps_newest_versions():
    kv = FakeKV()
    pub = WeightPublisher(kv, publish_steps=1, chunk_bytes=16, keep=2)
    for step in range(1, 4):
        # Drive each version through: the pending slot coalesces, so
        # only versions that actually commit exercise the GC.
        pub.maybe_publish(step, _pub_tree(fill=float(step)))
        _drive(pub)
    assert kv.get(PUB_SCOPE, "head") == b"3"
    assert kv.get(PUB_SCOPE, "meta:1") is None
    assert not [k for k in kv.get_scope(PUB_SCOPE)
                if k.startswith("shard:1.")]
    for v in (2, 3):
        meta = json.loads(kv.get(PUB_SCOPE, f"meta:{v}"))
        assert all(kv.get(PUB_SCOPE, f"shard:{v}.{i}") is not None
                   for i in range(meta["shards"]))


def test_publisher_pending_queue_is_bounded_and_coalesces():
    """The hand-off to the publisher thread holds AT MOST ONE pending
    image: KV commits running slower than the publish cadence must not
    accumulate full flattened param images on the trainer host.  A
    superseded pending version is simply never published — pullers only
    want the newest."""
    kv = FakeKV()
    pub = WeightPublisher(kv, publish_steps=1, chunk_bytes=16, keep=2)
    for step in range(1, 4):            # thread not started: all pend
        pub.maybe_publish(step, _pub_tree(fill=float(step)))
    assert len(pub._work) == 1          # bounded: newest image only
    assert pub.coalesced == 2
    _drive(pub)
    assert kv.get(PUB_SCOPE, "head") == b"3"
    assert pub.published == 1           # v1/v2 were never committed
    assert kv.get(PUB_SCOPE, "meta:1") is None
    assert kv.get(PUB_SCOPE, "meta:2") is None


def test_puller_stage_refusal_keeps_watermark_and_retries():
    """A stage callback returning False (the replica's staging window
    is full) leaves the puller's watermark untouched: the version is
    delayed, never dropped — the next poll offers the then-current head
    again."""
    kv = FakeKV()
    pub = WeightPublisher(kv, publish_steps=1, chunk_bytes=16, keep=2)
    pub.maybe_publish(1, _pub_tree())
    _drive(pub)
    staged = []
    accept = [False]
    pul = WeightPuller(
        kv, lambda v, img, m: staged.append((v, img)) or accept[0])
    assert pul.poll_once() is None      # refused: window full
    assert pul.seen == 0 and pul.pulled == 0
    accept[0] = True
    assert pul.poll_once() == 1         # retried, now staged
    assert pul.seen == 1 and pul.pulled == 1
    assert [v for v, _ in staged] == [1, 1]


# ---------------------------------------------------------------------------
# --fleet runtime wiring (fleet/wiring.py)
# ---------------------------------------------------------------------------
def test_fleet_wiring_gates_on_flag_and_publishes_serve_gauges(
        monkeypatch):
    """HOROVOD_FLEET off -> attach_replica is inert; on -> the replica
    gets the puller KV attached and the front gauge hook publishes
    size + queue depth + per-interval shed rate computed from the
    admission outcome counters."""
    from horovod_tpu.fleet import wiring

    class _Q:
        def depth(self):
            return 3

    class _B:
        def inflight_count(self):
            return 2

    class _Adm:
        totals = {"shed": 0, "expired": 0, "served": 0}

        def outcome_totals(self):
            return dict(self.totals)

    class _Ex:
        size = 2
        queue = _Q()
        batcher = _B()

        def __init__(self):
            self.admission = _Adm()
            self.attached = None

        def attach_fleet(self, kv):
            self.attached = kv

    ex = _Ex()
    monkeypatch.delenv("HOROVOD_FLEET", raising=False)
    assert wiring.attach_replica(ex) is None       # flag off: inert
    assert ex.attached is None
    monkeypatch.setenv("HOROVOD_FLEET", "1")
    kv = FakeKV()
    monkeypatch.setattr(wiring, "_fleet_kv", lambda: kv)
    rt = wiring.attach_replica(ex)
    assert rt is not None and ex.attached is kv
    ex.admission.totals = {"shed": 2, "expired": 1, "served": 7}
    ex._fleet_gauge(ex)
    gauge = json.loads(kv.get("fleet.gauges", "serve"))
    assert gauge["size"] == 2
    assert gauge["queue_depth"] == 5.0             # queued + in-flight
    assert gauge["shed_rate"] == pytest.approx(0.3)
    # No controller/publisher on the serving side to tear down.
    assert rt.controller is None and rt.publisher is None
    rt.close()


# ---------------------------------------------------------------------------
# replica staging + boundary swap (unit level: no serving world)
# ---------------------------------------------------------------------------
def _bare_replica():
    """A ReplicaExecutor skeleton with exactly the state the fleet
    staging/swap path touches — no serving world, no threads."""
    import threading

    from horovod_tpu.serving.replica import ReplicaExecutor

    ex = object.__new__(ReplicaExecutor)
    ex._fleet_lock = threading.Lock()
    ex._fleet_staged = {}
    ex._fleet_reported = set()
    ex.weight_version = 0
    ex._weight_step = 0
    ex._step = 0
    ex.stats = {"weight_swaps": []}
    ex.params = {"w": np.zeros(6, np.float32)}
    return ex


def test_replica_swaps_exactly_the_scheduled_version():
    """The boundary swap applies EXACTLY the version the front
    broadcast — never "newest staged locally", which can differ across
    ranks when a puller staged a newer image after the completions
    exchange (mixed weights inside one sharded replica group)."""
    ex = _bare_replica()
    trees = {v: {"w": np.full(6, float(v), np.float32)}
             for v in (1, 2, 3)}
    ex._fleet_staged = {v: (trees[v], 10 * v, v) for v in (1, 2, 3)}
    ex._fleet_swap(2)                   # v3 staged, but 2 is scheduled
    assert ex.weight_version == 2 and ex._weight_step == 20
    assert np.allclose(np.asarray(ex.params["w"]), 2.0)
    # Superseded v1 pruned at swap time; newer v3 stays staged.
    assert ex._fleet_staged_versions() == (3,)
    ex._fleet_swap(3)
    assert ex.weight_version == 3
    assert ex._fleet_staged_versions() == ()
    ex._fleet_swap(9)                   # not staged (local restart):
    assert ex.weight_version == 3       # keep serving, no crash
    assert [s["version"] for s in ex.stats["weight_swaps"]] == [2, 3]


def test_replica_stage_window_evicts_unreported_refuses_reported():
    """The staging window is bounded by _FLEET_STAGE_CAP.  At the cap,
    a version never reported in a completions exchange is evicted for
    a newer one (the front cannot have scheduled what it never saw —
    and while the serve loop pauses for a grow resync, refusal would
    wedge the group on versions the publisher GCs).  Once every staged
    version HAS been reported the callback refuses (False -> the
    puller retries): a reported version may be scheduled, so only the
    swap path may drop it."""
    ex = _bare_replica()
    image = bytes(flatten_state({"params": ex.params}))
    cap = ex._FLEET_STAGE_CAP
    for v in range(1, cap + 1):        # serve loop paused: no reports
        assert ex._fleet_stage(v, image, {"step": v, "digest": v})
    # Full of UNREPORTED versions: the oldest is evicted, not refused.
    assert ex._fleet_stage(cap + 1, image, {"step": 9, "digest": 9})
    assert ex._fleet_staged_versions() == tuple(range(2, cap + 2))
    # That call reported the window: now every slot is load-bearing.
    assert ex._fleet_stage(cap + 2, image, {"step": 9, "digest": 9}) \
        is False
    assert ex._fleet_staged_versions() == tuple(range(2, cap + 2))
    # Duplicates and stale versions report success without staging.
    assert ex._fleet_stage(cap, image, {"step": 9, "digest": 9}) is True
    ex.weight_version = 2
    assert ex._fleet_stage(2, image, {"step": 9, "digest": 9}) is True
    # The swap path is what frees a reported window.
    ex._fleet_swap(cap + 1)
    assert ex._fleet_staged_versions() == ()
    assert ex._fleet_stage(cap + 2, image, {"step": 9, "digest": 9})


def test_replicas_whose_windows_hold_different_versions_still_swap():
    """The front's window froze at the grow's first exchange while the
    joiner pulled only newer heads: two full windows of reported
    versions, none in common.  The exchange that finds no common
    version frees both windows, so the next head both pull is staged
    by both, becomes common and is scheduled, where refusing it for
    ever would leave the group on its old weights."""
    import types

    front, joiner = _bare_replica(), _bare_replica()
    image = bytes(flatten_state({"params": front.params}))
    cap = front._FLEET_STAGE_CAP
    for ex, first in ((front, 1), (joiner, cap + 1)):
        for v in range(first, first + cap):
            assert ex._fleet_stage(v, image, {"step": v, "digest": v})
    for ex, peer in ((front, joiner), (joiner, front)):
        ex.size, ex._gen, ex.slots, ex._unreported = 2, 0, [], []
        ex.stats.update(exchanges=0, local_exchanges=0)
        ex.hvd = types.SimpleNamespace(
            allgather_object=lambda mine, name, peer=peer: [
                mine, {"done": [], "staged": tuple(peer._fleet_staged)}])
    for ex in (front, joiner):
        ex._exchange_completions()
    assert front._fleet_common == joiner._fleet_common == 0
    for head in range(2 * cap + 1, 2 * cap + 4):
        staged = [ex._fleet_stage(head, image, {"step": head, "digest": 1})
                  for ex in (front, joiner)]
        for ex in (front, joiner):
            ex._exchange_completions()
        if front._fleet_common:
            break
    assert staged == [True, True]
    assert front._fleet_common == joiner._fleet_common == 2 * cap + 1
    front._fleet_swap(front._fleet_common)
    joiner._fleet_swap(joiner._fleet_common)
    assert front.weight_version == joiner.weight_version == 2 * cap + 1


# ---------------------------------------------------------------------------
# loadgen staleness accounting
# ---------------------------------------------------------------------------
def test_loadgen_weights_report_versions_and_staleness():
    from horovod_tpu.serving.loadgen import _weights_report

    class _Ex:
        weight_version = 2
        completed = {
            1: {"weights": 1, "weights_stale_steps": 0},
            2: {"weights": 1, "weights_stale_steps": 5},
            3: {"weights": 2, "weights_stale_steps": 3},
        }
        stats = {"weight_swaps": [
            {"version": 1, "step": 4, "digest": 7, "at": 0.0},
            {"version": 2, "step": 9, "digest": 8, "at": 1.0},
        ]}

    rep = _weights_report(_Ex())
    assert rep["final_version"] == 2
    assert rep["versions"] == {"1": 2, "2": 1}
    assert rep["max_staleness_steps"] == 5
    assert rep["swaps"] == [{"version": 1, "step": 4},
                            {"version": 2, "step": 9}]


# ---------------------------------------------------------------------------
# the 4-rank acceptance battery
# ---------------------------------------------------------------------------
def test_fleet_battery_4rank():
    """ISSUE 20 acceptance: launch ranks 0-2 train (world size 3),
    launch rank 3 serves (world size 1) — both statesync worlds on ONE
    coordinator KV (HOROVOD_STATESYNC_WORLD namespacing).  The serving
    burst drives the controller's policy over its hysteresis window;
    rank 2 departs the training world at a statesync boundary (no
    RanksFailedError anywhere), joins the serving world via
    peer-streamed state, and the journal record closes as done.  The
    trainer's published snapshots roll out to BOTH serving replicas at
    one broadcast plan boundary (digest-asserted against the live
    params on each), with zero failed admitted requests and goodput
    phases recorded.  The flight dumps replay through the hvdmc
    witness against the fleet + membership models."""
    outputs = _run_world(4, "fleet", timeout=360.0,
                         extra_env=_witness_env("fleet", 4))
    assert "fleet front:" in outputs[3], outputs[3]
    assert "across 1->2" in outputs[3], outputs[3]
    assert "fleet mover: joined serving" in outputs[2], outputs[2]
    assert "digest verified" in outputs[2], outputs[2]
    for r in (0, 1):
        assert "no RanksFailedError anywhere" in outputs[r], outputs[r]
    assert "migration journal closed" in outputs[0], outputs[0]
    _replay_witness(outputs, {"fleet-migrate", "fleet-depart",
                              "fleet-join", "fleet-publish",
                              "fleet-pull", "fleet-swap", "departed"})
