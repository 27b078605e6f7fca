"""serving/ battery (ISSUE 9): continuous batching, admission control,
per-request deadline propagation, the chaos shrink mid-serve, and the
loadgen SLO harness.

Process-level acceptance (4-rank mp_worker "serving" battery under the
hard SIGALRM guard): chaos SIGKILLs rank 2 mid-serve; the world shrinks
4->3, every survivor completes every request it had admitted (zero
failed in-flight on survivors), accounting balances with bounded shed,
and a post-shrink hopeless-SLO burst is shed at admission — never
prefilled on any rank.

Unit level: bounded ingress queue with deadlines stamped at the door,
token-budgeted continuous batch assembly, admission verdicts
(expired / load shed / infeasible / admitted) keyed off live telemetry,
deadline_scope -> per-op deadline propagation, and the loadgen report
schema (the tier-1 smoke: --requests 64 --duration 5).
"""
from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_multiprocess import _run_world  # noqa: E402

from horovod_tpu.serving.admission import AdmissionController  # noqa: E402
from horovod_tpu.serving.batcher import ContinuousBatcher  # noqa: E402
from horovod_tpu.serving.queue import RequestQueue, ServeRequest  # noqa: E402
from horovod_tpu.telemetry.registry import MetricsRegistry  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HARD_GUARD_SECONDS = 420


@pytest.fixture(autouse=True)
def hard_timeout_guard():
    """Serving tests exercise deadline machinery: a regression that
    re-introduces an unbounded wait must fail fast, not eat the tier-1
    budget (the resilience-suite convention)."""
    def _expired(signum, frame):
        raise TimeoutError(
            f"serving test exceeded the {HARD_GUARD_SECONDS}s hard "
            f"guard — a blocking wait has lost its deadline")
    old = signal.signal(signal.SIGALRM, _expired)
    signal.alarm(HARD_GUARD_SECONDS)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


def _mkreq(rid=0, tokens=(1, 2, 3), max_new=4, slo_ms=1000.0,
           age_s=0.0) -> ServeRequest:
    now = time.monotonic()
    return ServeRequest(rid=rid, tokens=list(tokens),
                        max_new_tokens=max_new, arrival=now - age_s,
                        deadline=now - age_s + slo_ms / 1e3,
                        slo_ms=slo_ms)


class _AdmitAll:
    def __init__(self):
        self.counts = {}

    def admit(self, req, depth, now=None):
        self.count("admitted")
        return True, "admitted"

    def count(self, outcome, n=1):
        self.counts[outcome] = self.counts.get(outcome, 0) + n


# --- ingress queue ----------------------------------------------------------
def test_queue_bounded_and_deadline_stamped():
    reg = MetricsRegistry(0)
    q = RequestQueue(maxsize=2, default_slo_ms=500.0, registry=reg)
    t0 = time.monotonic()
    assert q.submit([1, 2], 4) == 0
    assert q.submit([3], 4, slo_ms=50.0) == 1
    # Full queue sheds at the door (never blocks, never buffers).
    assert q.submit([4], 4) is None
    assert reg.counter("horovod_serve_requests_total",
                       labels={"outcome": "rejected_full"}).value == 1
    assert reg.gauge("horovod_serve_queue_depth").value == 2
    ready, expired = q.pop_ready(10)
    assert [r.rid for r in ready] == [0, 1] and expired == []
    # Deadlines were stamped at ingress, per-request SLO honored.
    assert ready[0].deadline == pytest.approx(t0 + 0.5, abs=0.05)
    assert ready[1].deadline == pytest.approx(t0 + 0.05, abs=0.05)


def test_queue_expires_while_queued():
    q = RequestQueue(maxsize=8, default_slo_ms=1000.0,
                     registry=MetricsRegistry(0))
    q.submit([1], 2, slo_ms=1.0)     # expires in 1 ms
    q.submit([2], 2)                 # healthy
    time.sleep(0.02)
    ready, expired = q.pop_ready(10)
    assert [r.rid for r in expired] == [0]
    assert [r.rid for r in ready] == [1]


def test_queue_close_sheds_new_but_drains_old():
    q = RequestQueue(maxsize=8, registry=MetricsRegistry(0))
    assert q.submit([1], 2) == 0
    q.close()
    assert q.submit([2], 2) is None
    ready, _ = q.pop_ready(10)
    assert [r.rid for r in ready] == [0]


# --- continuous batcher -----------------------------------------------------
def test_batcher_fills_least_loaded_within_budget():
    reg = MetricsRegistry(0)
    q = RequestQueue(maxsize=64, registry=reg)
    adm = _AdmitAll()
    b = ContinuousBatcher(2, slots_per_replica=2, token_budget=8)
    for i in range(4):
        q.submit([1] * 3, 4)
    plan, expired = b.assemble(0, q, adm)
    assert expired == []
    # 2 replicas x 2 slots, 3 prefill tokens each within budget 8.
    assert len(plan.assign) == 4
    assert sorted(a.replica for a in plan.assign) == [0, 0, 1, 1]
    assert b.inflight_count() == 4
    # Slots full: nothing more is assembled until completions free them.
    q.submit([1] * 3, 4)
    plan2, _ = b.assemble(1, q, adm)
    assert plan2.assign == []
    b.note_done(plan.assign[0].rid)
    plan3, _ = b.assemble(2, q, adm)
    assert len(plan3.assign) == 1
    assert plan3.assign[0].replica == plan.assign[0].replica


def test_batcher_token_budget_defers_not_sheds():
    """A prompt that exceeds this step's remaining token budget is
    back-pressure: requeued at the head, admitted on a later step —
    never silently dropped."""
    reg = MetricsRegistry(0)
    q = RequestQueue(maxsize=64, registry=reg)
    adm = _AdmitAll()
    b = ContinuousBatcher(1, slots_per_replica=4, token_budget=10)
    q.submit([1] * 8, 4)
    q.submit([2] * 8, 4)             # 16 prefill tokens > budget 10
    plan, _ = b.assemble(0, q, adm)
    assert [a.rid for a in plan.assign] == [0]
    assert q.depth() == 1
    plan2, _ = b.assemble(1, q, adm)
    assert [a.rid for a in plan2.assign] == [1]


def test_batcher_rebuild_reports_lost():
    b = ContinuousBatcher(3, slots_per_replica=2, token_budget=64)
    b.inflight = {0: 0, 1: 1, 2: 2, 3: 2}
    b._active = [1, 1, 2]
    lost = b.rebuild([[0], [1]])     # replica 2 died with rids 2, 3
    assert lost == [2, 3]
    assert b.inflight == {0: 0, 1: 1}
    assert b._active == [1, 1]


def test_batcher_aging_rescues_starved_big_prompt():
    """ISSUE 14 starvation fix: an over-budget prompt requeued-at-head
    every step used to be bypassed indefinitely by smaller admissions.
    After HOROVOD_SERVE_MAX_DEFERRALS deferrals it turns urgent —
    bypasses the token budget and reserves the step (barrier) — so it
    lands as soon as a slot frees."""
    q = RequestQueue(maxsize=256, registry=MetricsRegistry(0))
    adm = _AdmitAll()
    b = ContinuousBatcher(1, slots_per_replica=2, token_budget=10,
                          max_deferrals=3)
    huge = q.submit([9] * 40, 4)         # 40 prefill tokens >> budget
    admitted_at = None
    for step in range(12):
        for _ in range(2):
            q.submit([1] * 3, 2)         # relentless small-prompt stream
        plan, _ = b.assemble(step, q, adm)
        for a in plan.assign:            # everything finishes instantly
            b.note_done(a.rid)
        if any(a.rid == huge for a in plan.assign):
            admitted_at = step
            break
    # Deferred steps 0..2 (budget), urgent at step 3: admitted there.
    assert admitted_at is not None and admitted_at <= 4, admitted_at


def test_batcher_urgent_barrier_reserves_the_step():
    """While an urgent prompt still lacks a slot, nothing behind it is
    admitted — smaller requests cannot keep stealing the capacity it
    is waiting for."""
    q = RequestQueue(maxsize=64, registry=MetricsRegistry(0))
    adm = _AdmitAll()
    b = ContinuousBatcher(1, slots_per_replica=1, token_budget=10,
                          max_deferrals=0)   # urgent immediately
    q.submit([9] * 40, 4)                    # needs the (occupied) slot
    q.submit([1] * 2, 2)
    blocker = q.submit([1] * 2, 2)
    del blocker
    # Occupy the only slot so even the urgent prompt cannot land.
    b.inflight[99] = 0
    b._active = [1]
    plan, _ = b.assemble(0, q, adm)
    assert plan.assign == []                 # barrier held everything
    b.note_done(99)
    plan, _ = b.assemble(1, q, adm)
    assert [a.tokens[0] for a in plan.assign] == [9]   # urgent first


def test_batcher_block_capacity_defers_admissions():
    """Paged mode: the batcher mirrors each replica's block pool and
    defers admissions whose worst-case reservation (prompt + max_new,
    + 1 block COW headroom) would not fit — reserve-at-admission is
    what makes mid-decode pool exhaustion impossible."""
    q = RequestQueue(maxsize=64, registry=MetricsRegistry(0))
    adm = _AdmitAll()
    b = ContinuousBatcher(1, slots_per_replica=8, token_budget=1000,
                          block_capacity=10, block_tokens=16)
    for _ in range(4):
        q.submit([1] * 16, 16)       # ceil(32/16)+1 = 3 blocks each
    plan, _ = b.assemble(0, q, adm)
    assert len(plan.assign) == 3 and b._blocks == [9]
    assert q.depth() == 1            # 4th deferred: 9 + 3 > 10
    b.note_done(plan.assign[0].rid)
    plan2, _ = b.assemble(1, q, adm)
    assert len(plan2.assign) == 1 and b._blocks == [9]


# --- admission control ------------------------------------------------------
def test_admission_verdicts():
    reg = MetricsRegistry(0)
    adm = AdmissionController(registry=reg, queue_depth_limit=10,
                              shed_fraction=0.5, step_ms_seed=10.0)
    # Already past its deadline: expired, never executed.
    ok, outcome = adm.admit(_mkreq(slo_ms=1.0, age_s=1.0), 0)
    assert (ok, outcome) == (False, "expired")
    # Queue pressure beyond the gauge threshold: load shed.
    ok, outcome = adm.admit(_mkreq(slo_ms=10000.0), 9)
    assert (ok, outcome) == (False, "shed")
    # Deadline-infeasible: 100 decode steps never fit 50 ms at ~10 ms
    # per step.
    ok, outcome = adm.admit(_mkreq(max_new=100, slo_ms=50.0), 0)
    assert (ok, outcome) == (False, "shed")
    # Feasible and unloaded: admitted.
    ok, outcome = adm.admit(_mkreq(max_new=4, slo_ms=10000.0), 0)
    assert (ok, outcome) == (True, "admitted")
    counts = {m["labels"]["outcome"]: m["value"]
              for m in reg.snapshot()["metrics"]
              if m["name"] == "horovod_serve_requests_total"
              and m["value"] > 0}
    assert counts == {"admitted": 1, "expired": 1, "shed": 2}


def test_admission_estimate_tracks_live_step_time():
    adm = AdmissionController(registry=MetricsRegistry(0),
                              queue_depth_limit=100, step_ms_seed=1.0)
    assert adm.step_ms() == pytest.approx(1.0)
    for _ in range(16):
        adm.observe_step_ms(40.0)
    # The shared Histogram.quantile path takes over from the EWMA seed.
    assert 20.0 < adm.step_ms() <= 40.0
    req = _mkreq(max_new=9)
    assert adm.estimate_completion_ms(req) >= 10 * 20.0


def test_admission_reads_straggler_gauge():
    reg = MetricsRegistry(0)
    reg.gauge("horovod_controller_straggler_lag_ms",
              labels={"stat": "mean"}).set(25.0)
    adm = AdmissionController(registry=reg, queue_depth_limit=100,
                              step_ms_seed=5.0)
    assert adm.straggler_lag_ms() == 25.0
    assert adm.estimate_completion_ms(_mkreq(max_new=1)) \
        == pytest.approx(2 * 30.0)


# --- deadline propagation into resilience ----------------------------------
class _FakeMonitor:
    def failed_ranks(self):
        return frozenset()

    def confirmed_failed_ranks(self):
        return frozenset()

    def mark_failed(self, r, reason, confirmed=True):
        pass

    def stop(self):
        pass


def test_deadline_scope_flows_into_per_op_timeout():
    from horovod_tpu.resilience.context import (ResilienceState,
                                                deadline_scope, op_scope,
                                                pending_deadline)
    state = ResilienceState(0, 2, _FakeMonitor(), fault_timeout=10.0)
    assert state.op_timeout() == 10.0
    # A propagated request deadline tightens the wait bound...
    with op_scope("serve.plan", deadline=time.monotonic() + 1.0):
        assert 0.5 < state.op_timeout() <= 1.01
        # ...and nests (inner scope wins, outer restored).
        with op_scope("inner", deadline=time.monotonic() + 0.6):
            assert state.op_timeout() <= 0.61
        assert 0.5 < state.op_timeout() <= 1.01
    assert state.op_timeout() == 10.0
    # A hopeless deadline floors at two poll slices: a late request
    # alone must never instantly declare a healthy peer wedged.
    with op_scope("serve.plan", deadline=time.monotonic() - 5.0):
        assert state.op_timeout() == pytest.approx(
            2.0 * state.poll_interval)
    # The caller-side half: deadline_scope parks the deadline for core's
    # enqueue stamping (TensorTableEntry.deadline).
    assert pending_deadline() is None
    with deadline_scope(123.0):
        assert pending_deadline() == 123.0
        with deadline_scope(None):
            assert pending_deadline() is None
        assert pending_deadline() == 123.0
    assert pending_deadline() is None


def test_entry_deadline_field_defaults_none():
    from horovod_tpu.common.tensor_queue import TensorTableEntry
    assert TensorTableEntry(tensor_name="x").deadline is None


# --- loadgen ----------------------------------------------------------------
def test_arrival_profiles_shape_rates():
    import random

    from horovod_tpu.serving import loadgen
    rng = random.Random(1)
    steady = loadgen.arrival_times(rng, 10000, 10.0, 100.0, "steady")
    assert 0 < len(steady) <= 10000
    assert steady == sorted(steady) and steady[-1] < 10.0
    rng = random.Random(1)
    burst = loadgen.arrival_times(rng, 10 ** 6, 10.0, 100.0, "burst")
    mid = [t for t in burst if 4.0 <= t < 6.0]
    rest = [t for t in burst if t < 4.0 or t >= 6.0]
    # 4x rate through the middle fifth: its per-second density dominates.
    assert len(mid) / 2.0 > 2.0 * len(rest) / 8.0
    rng = random.Random(1)
    ramp = loadgen.arrival_times(rng, 10 ** 6, 10.0, 100.0, "ramp")
    assert len([t for t in ramp if t >= 5.0]) > \
        2 * len([t for t in ramp if t < 5.0])


def _run_loadgen_inproc(tmp_path, argv):
    import horovod_tpu as hvd

    from horovod_tpu.serving import loadgen
    hvd.shutdown()                   # a clean single-rank world
    for var in ("HOROVOD_RANK", "HOROVOD_SIZE"):
        os.environ.pop(var, None)
    args = loadgen.make_parser().parse_args(
        argv + ["--output", str(tmp_path / "SERVE_r{rank}.json")])
    if args.slo_ms == 0.0:
        args.slo_ms = None
    return loadgen.run(args), tmp_path / "SERVE_r0.json"


def test_loadgen_report_schema(tmp_path):
    from horovod_tpu.serving import loadgen
    report, path = _run_loadgen_inproc(tmp_path, [
        "--requests", "6", "--duration", "3", "--rate", "50",
        "--max-new-tokens", "4", "--prompt-tokens", "6"])
    assert report["schema"] == loadgen.SCHEMA
    for key in ("offered", "served", "served_within_slo", "shed",
                "expired", "lost_on_failure", "latency_ms", "step_ms",
                "goodput_rps", "offered_rps", "world", "steps",
                "tokens_generated", "wall_s"):
        assert key in report, key
    assert report["offered"] == 6 == report["served"]
    assert report["shed"] == 0 and report["expired"] == 0
    assert report["latency_ms"]["p50"] > 0.0
    assert report["latency_ms"]["p999"] >= report["latency_ms"]["p99"] \
        >= report["latency_ms"]["p50"]
    assert report["step_ms"]["count"] > 0      # shared quantile path
    assert report["tokens_generated"] == 6 * 4
    on_disk = json.loads(path.read_text())
    assert on_disk["schema"] == loadgen.SCHEMA
    assert on_disk["served"] == 6


def test_loadgen_overload_sheds_at_admission(tmp_path):
    """Offered load beyond capacity with tight SLOs: requests that
    cannot meet their deadline are shed/expired at admission — goodput
    degrades by refusal, not by executing doomed work."""
    report, _ = _run_loadgen_inproc(tmp_path, [
        "--requests", "40", "--duration", "2", "--rate", "400",
        "--max-new-tokens", "64", "--prompt-tokens", "6",
        "--slo-ms", "40", "--max-batch", "2", "--token-budget", "16"])
    assert report["offered"] == 40
    assert report["shed"] + report["expired"] > 0
    assert report["served"] + report["shed"] + report["expired"] \
        + report["lost_on_failure"] == report["offered"]


def test_loadgen_smoke_cli(tmp_path):
    """The tier-1 loadgen smoke (ISSUE 9 CI satellite): the documented
    CLI drives a single-rank serve world end to end and writes the
    SERVE_r*.json report next to where the bench payloads land."""
    out = tmp_path / "SERVE_r{rank}.json"
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.serving.loadgen",
         "--requests", "64", "--duration", "5", "--rate", "40",
         "--max-new-tokens", "4", "--prompt-tokens", "8",
         "--output", str(out)],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads((tmp_path / "SERVE_r0.json").read_text())
    assert report["served"] > 0
    assert report["served"] + report["shed"] + report["expired"] \
        + report["lost_on_failure"] == report["offered"]
    assert report["latency_ms"]["p99"] >= report["latency_ms"]["p50"] > 0
    assert report["goodput_rps"] > 0
    assert "loadgen: report written" in proc.stdout


# --- paged KV end to end (single-rank worlds) -------------------------------
def _solo_world():
    import horovod_tpu as hvd
    hvd.shutdown()
    for var in ("HOROVOD_RANK", "HOROVOD_SIZE"):
        os.environ.pop(var, None)
    hvd.init()
    return hvd


def _paged_cfg(**kw):
    from horovod_tpu.serving import ServeConfig
    base = dict(max_batch=2, token_budget=64, max_seq=64,
                slo_ms=60000.0, block_tokens=8)
    base.update(kw)
    return ServeConfig.from_env(**base)


def test_paged_serve_parity_prefix_hits_and_refcount_census():
    """ISSUE 14 acceptance (tier-1 half): for an identical admitted
    stream, paged decode produces token-for-token the dense output;
    repeated prompts hit the prefix cache (refcount bumps instead of
    re-prefill, COW on the first divergent write); and after the drain
    the pool's active count is ZERO — the refcount-leak census."""
    import random

    from horovod_tpu.serving import ReplicaExecutor

    streams = {}
    for paged in (False, True):
        hvd = _solo_world()
        ex = ReplicaExecutor(_paged_cfg(paged=paged))
        rng = random.Random(7)
        prompts = [[rng.randrange(2, 256)
                    for _ in range(rng.randint(2, 12))]
                   for _ in range(4)]
        n = 12
        for i in range(n):
            ex.stats["offered"] += 1
            assert ex.queue.submit(prompts[i % 4], 6) is not None
        ex.serve_loop(stop_when=lambda: True)
        assert ex.stats["served"] == n
        if paged:
            kv = ex.kv_stats()
            assert kv["active"] == 0, kv          # refcount census
            assert kv["prefix_hits"] > 0, kv      # repeated prompts hit
            assert kv["cow_copies"] > 0, kv       # shared tails COWed
            assert kv["prefill_skipped"] > 0, kv  # full hits skip prefill
            assert kv["max_concurrent_seqs"] > ex.cfg.max_batch
        streams[paged] = {rid: rec["generated"]
                          for rid, rec in ex.completed.items()}
        ex.close()
        hvd.shutdown()
    assert streams[False] == streams[True]        # bitwise token parity


def test_paged_eviction_then_readmission_stays_correct():
    """Cached prefix blocks evicted under pool pressure must not change
    behavior: a re-admitted prompt misses, re-prefills fresh and
    reproduces its original generation exactly."""
    import random

    from horovod_tpu.serving import ReplicaExecutor

    hvd = _solo_world()
    # Tiny pool: 2 in-flight sequences fit, but waves of distinct
    # prompts force LRU eviction of the cached ones.
    ex = ReplicaExecutor(_paged_cfg(paged=True, paged_slots=2,
                                    pool_blocks=8))
    rng = random.Random(11)
    prompts = [[rng.randrange(2, 256) for _ in range(9)]
               for _ in range(4)]
    rid_prompt = {}
    for wave in (0, 1):
        for p in prompts:
            ex.stats["offered"] += 1
            rid = ex.queue.submit(p, 6)
            assert rid is not None
            rid_prompt[rid] = tuple(p)
        ex._stop_requested = False
        ex.serve_loop(stop_when=lambda: True)
    kv = ex.kv_stats()
    assert ex.stats["served"] == 8
    assert kv["evictions"] > 0, kv               # pressure really evicted
    assert kv["active"] == 0, kv
    # Re-admissions (same prompt, wave 2) reproduced wave-1 streams.
    by_prompt = {}
    for rid, rec in sorted(ex.completed.items()):
        by_prompt.setdefault(rid_prompt[rid], []).append(rec["generated"])
    for p, gens in by_prompt.items():
        assert len(gens) == 2 and gens[0] == gens[1], p
    ex.close()
    hvd.shutdown()


def test_loadgen_paged_report_carries_kv_section(tmp_path, monkeypatch):
    monkeypatch.setenv("HOROVOD_SERVE_PAGED", "1")
    report, _ = _run_loadgen_inproc(tmp_path, [
        "--requests", "12", "--duration", "3", "--rate", "50",
        "--max-new-tokens", "4", "--prompt-tokens", "6",
        "--prompt-pool", "3"])
    assert report["served"] == 12
    kv = report["kv"]
    assert kv is not None and kv["active"] == 0
    assert kv["prefix_hits"] > 0                 # repeated-prompt pool
    assert report["max_concurrent_seqs"] >= 1
    assert report["config"]["paged"] is True


# --- the 4-rank chaos acceptance battery ------------------------------------
@pytest.mark.slow
def test_serving_chaos_shrink_4rank():
    """ISSUE 9 acceptance: chaos SIGKILLs rank 2 mid-serve (global
    collective index 11, ~16 requests in flight); the 4-rank world
    shrinks to 3, every survivor completes every admitted in-flight
    request (asserted in-battery), accounting balances with bounded
    shed, and a post-shrink hopeless-SLO burst is shed at admission
    without ever being prefilled.  Slow tier: the paged chaos battery
    below rides the same 4->3 shrink machinery (plus paged-KV checks)
    and stays in tier-1."""
    outputs = _run_world(4, "serving", timeout=360.0,
                         expected_rcs={2: -signal.SIGKILL})
    assert "shrink at step" in outputs[0], outputs[0]
    assert "shed at admission" in outputs[0], outputs[0]


def test_serving_paged_chaos_shrink_4rank():
    """ISSUE 14 acceptance: the paged-KV serving plane rides the same
    4->3 chaos shrink — block tables resynced from ground truth, zero
    failed admitted requests on survivors, prefix-cache hits under
    repeated prompts, and every survivor's pool passes the
    refcount-leak census after the drain."""
    outputs = _run_world(4, "serving_paged", timeout=360.0,
                         expected_rcs={2: -signal.SIGKILL})
    assert "shrink at step" in outputs[0], outputs[0]
    for r in (0, 1, 3):
        assert "kv census clean" in outputs[r], outputs[r]


def test_serving_disagg_prefill_decode_2rank():
    """ISSUE 14 disaggregation: rank 1 prefill-only, rank 0 decode;
    long prompts land on the decode replica via streamed KV blocks
    (zero local fallbacks) under the STRICT collective fingerprint —
    the split-role step loop provably never diverges on a
    collective."""
    outputs = _run_world(2, "serving_disagg", timeout=240.0)
    assert "served via streamed prefill" in outputs[0], outputs[0]
    assert "rank 1 streamed" in outputs[1], outputs[1]


# --- spans and part timers inside the serve step (ISSUE 26) -----------------
STEP_PARTS = ("assemble", "plan_exchange", "decode_dispatch", "token_fetch",
              "slot_update", "completion_exchange", "account")
ADMIT_CHILDREN = ("prefill_dispatch", "cache_insert", "first_token_fetch")


def _toy_executor(requests=2, max_new=8, **kw):
    """A dense two-slot replica on a world of one, with ``requests``
    five-token prompts waiting in its queue."""
    import random

    from horovod_tpu.serving import ReplicaExecutor

    hvd = _solo_world()
    ex = ReplicaExecutor(_paged_cfg(paged=False, **kw))
    rng = random.Random(3)
    for _ in range(requests):
        ex.stats["offered"] += 1
        assert ex.queue.submit([rng.randrange(2, 256) for _ in range(5)],
                               max_new) is not None
    return hvd, ex


def test_step_parts_sum_to_every_step_and_feed_the_counters(monkeypatch):
    """The parts and ``other`` of every step of a toy replica sum to its
    total; stats keep them by kind of step, and with metrics on the same
    numbers feed horovod_serve_step_part_ms{part}."""
    from horovod_tpu import telemetry

    monkeypatch.setenv("HOROVOD_METRICS", "on")
    hvd, ex = _toy_executor(requests=3, max_new=6)
    reg = telemetry.metrics()
    assert reg.enabled
    seen = []
    note = ex._note_step_parts
    monkeypatch.setattr(
        ex, "_note_step_parts",
        lambda step, seconds, admits, gc2: (
            seen.append((dict(seconds), admits)),
            note(step, seconds, admits, gc2)))
    try:
        ex.serve_loop(stop_when=lambda: True)
        assert ex.stats["served"] == 3
        assert len(seen) >= 8
        for seconds, admits in seen:
            total = seconds.pop("total")
            assert total > 0 and seconds["other"] >= 0
            assert sum(seconds.values()) == pytest.approx(total, abs=1e-9)
            assert set(STEP_PARTS) - {"token_fetch"} <= set(seconds)
            assert ("admit" in seconds) == bool(admits)
        # A step fetches the decode step enqueued by the one before it
        # (ISSUE 35): the first step finds none in flight, nor does the
        # step that admits the third request into a drained replica.
        unfetched = [i for i, (seconds, _) in enumerate(seen)
                     if "token_fetch" not in seconds]
        assert len(unfetched) == 2 and unfetched[0] == 0
        assert all(seen[i][1] for i in unfetched)
        kinds = ex.stats["steps"]
        assert kinds["admit"] == sum(bool(a) for _, a in seen) >= 2
        assert kinds["decode"] == len(seen) - kinds["admit"]
        for kind in ("admit", "decode"):
            sums = ex.stats["step_parts_s"][kind]
            assert sum(v for k, v in sums.items() if k != "total") \
                == pytest.approx(sums["total"], abs=1e-9)
        hist = {m["labels"]["part"]: m["count"]
                for m in reg.snapshot()["metrics"]
                if m["name"] == "horovod_serve_step_part_ms"}
        assert hist["token_fetch"] + 2 == hist["other"] == len(seen)
        assert hist["admit"] == kinds["admit"]
        assert "total" not in hist
    finally:
        ex.close()
        hvd.shutdown()
        monkeypatch.delenv("HOROVOD_METRICS")
        telemetry.configure()


def test_serve_spans_nest_in_a_profiler_session(tmp_path):
    """A jax.profiler session around five steps holds every span of the
    serve step once a step, each inside hvd.serve.step on one thread,
    and the admit span with its three children on the admit step only."""
    import glob

    import jax
    from jax.profiler import ProfileData

    hvd, ex = _toy_executor(requests=2, max_new=8)
    try:
        jax.profiler.start_trace(str(tmp_path))
        for _ in range(5):
            assert ex._serve_step()
        jax.profiler.stop_trace()
    finally:
        ex.close()
        hvd.shutdown()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    lines = [[ev for ev in line.events if ev.name.startswith("hvd.")]
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines]
    (events,) = [evs for evs in lines if evs]          # one thread
    by_name: dict = {}
    for ev in events:
        by_name.setdefault(ev.name, []).append(
            (ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats)))
    steps = by_name["hvd.serve.step"]
    assert [s[2]["step"] for s in steps] == list(range(5))
    assert [s[2]["admits"] for s in steps] == [2, 0, 0, 0, 0]
    assert [s[2]["decoded"] for s in steps] == [2] * 5

    def holders(spans, inner):
        return [i for i, (lo, hi, _) in enumerate(spans)
                if lo <= inner[0] and inner[1] <= hi]

    for part in STEP_PARTS:
        found = by_name["hvd.serve." + part]
        # The first step has no step in flight to fetch (ISSUE 35).
        assert [holders(steps, f) for f in found] \
            == [[i] for i in range(part == "token_fetch", 5)]
    admits = by_name["hvd.serve.admit"]
    assert [holders(steps, a) for a in admits] == [[0], [0]]
    assert sorted(a[2]["rid"] for a in admits) == [0, 1]
    assert all(a[2]["bucket"] == 8 for a in admits)
    for child in ADMIT_CHILDREN:
        found = by_name["hvd.serve." + child]
        assert [holders(admits, f) for f in found] == [[0], [1]]
    assert set(by_name) == {"hvd.serve." + n for n in
                            ("step", "admit") + STEP_PARTS + ADMIT_CHILDREN}


def test_a_slow_step_leaves_one_record_that_names_its_part(monkeypatch):
    """A stall patched into _exchange_completions at one step yields
    exactly one slow-step record that names completion_exchange; a
    patched gc.collect(2) sets its flag; the list is bounded."""
    import gc
    import resource

    from horovod_tpu.serving import replica
    from horovod_tpu.telemetry import flight

    hvd, ex = _toy_executor(requests=2, max_new=40)
    began = time.monotonic()
    plan, done = ex._exchange_plan, ex._exchange_completions

    def slow_plan(p):
        time.sleep(0.05)       # a steady 50 ms step: noise stays under 3x
        return plan(p)

    def stalling_done():
        if ex._step == 21:     # _step is already the next step's number
            time.sleep(0.5)
        if ex._step == 31:
            time.sleep(0.5)
            gc.collect(2)
        return done()

    monkeypatch.setattr(ex, "_exchange_plan", slow_plan)
    monkeypatch.setattr(ex, "_exchange_completions", stalling_done)
    gc.disable()               # no collection but the patched one
    try:
        ex.serve_loop(stop_when=lambda: True)
        assert ex.stats["served"] == 2
        assert len(ex.stats["slow_steps"]) == 2, ex.stats["slow_steps"]
        first, second = ex.stats["slow_steps"]
        assert (first["step"], second["step"]) == (20, 30)
        for record in (first, second):
            assert record["kind"] == "decode" and record["admits"] == 0
            assert record["slowest"] == "completion_exchange"
            assert record["parts_ms"]["completion_exchange"] >= 500
            assert record["total_ms"] == pytest.approx(
                sum(record["parts_ms"].values()), abs=0.01)
        assert (first["gc2"], second["gc2"]) == (False, True)
        assert ex.stats["slow_steps_total"] == 2
        # This run's: a lone replica's steps no longer push four eager
        # operations each through the process's ring, so the records of
        # earlier tests are still in it.
        kinds = [e["kind"] for e in flight.recorder().snapshot()
                 if e["ts"] >= began]
        assert kinds.count("serve_slow_step") == 2

        # Bounded: 50 more slow steps (one in three, so that the median
        # stays a fast step's) keep the newest 32.
        fast = {"token_fetch": 0.01, "other": 0.0, "total": 0.01}
        slow = {"token_fetch": 0.5, "other": 0.0, "total": 0.5}
        for n in range(150):
            ex._note_step_parts(
                1000 + n, dict(slow if n % 3 == 2 else fast), 0,
                (gc.get_stats()[2]["collections"],
                 resource.getrusage(resource.RUSAGE_THREAD)))
        assert len(ex.stats["slow_steps"]) == replica._SLOW_STEPS_KEPT == 32
        assert ex.stats["slow_steps"][-1]["step"] == 1149
        assert ex.stats["slow_steps_total"] == 52
    finally:
        gc.enable()
        ex.close()
        hvd.shutdown()


def test_decode_program_carries_the_scope_names():
    """hvd.decode_attend is in the lowered decode and prefill programs'
    debug info (and nowhere in the program itself); hvd.sample, which
    reached no device event, is gone."""
    import jax.numpy as jnp

    hvd, ex = _toy_executor(requests=0)
    try:
        decode, args = ex.cache._decode_call(
            ex.params, ex._last_tokens, ex._token_on_host)
        lowered = decode.lower(*args)
        for program in (lowered, ex.cache._prefill_jit.lower(
                ex.params, jnp.zeros((1, 8), jnp.int32), jnp.int32(3))):
            named = program.as_text(debug_info=True)
            assert "hvd.decode_attend" in named
            assert "hvd.sample" not in named
            assert "hvd." not in program.as_text()
    finally:
        ex.close()
        hvd.shutdown()


# --- the slot cache is updated in place (ISSUE 27) --------------------------
def _executor(paged: bool, **kw):
    from horovod_tpu.serving import ReplicaExecutor

    hvd = _solo_world()
    return hvd, ReplicaExecutor(_paged_cfg(paged=paged, **kw))


def _cache_leaves(ex):
    import jax
    return jax.tree_util.tree_leaves(ex.cache.tree)


def _submit(ex, prompts, max_new):
    for prompt in prompts:
        ex.stats["offered"] += 1
        assert ex.queue.submit(list(prompt), max_new) is not None


def _reference(ex):
    """What the replica has to generate for a request, as a function of
    (prompt, max_new): a plain greedy loop over the family's own prefill
    and decode_step on a dense cache of the request's own, nothing
    donated, the prompt padded to the executor's bucket."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.serving.replica import _decode_model_cfg
    from horovod_tpu.serving.slotcache import prompt_bucket

    family = ex.family
    # The dense model: the paged replica's own reads the block pool.
    model = family.build(_decode_model_cfg(ex.cfg))
    variables = {"params": ex.params}
    prefill = jax.jit(lambda v, t, n: family.prefill(model, v, t, lengths=n))
    decode = jax.jit(lambda v, c, t: family.decode_step(model, v, c, t))

    def stream(prompt, max_new):
        padded = np.zeros((1, prompt_bucket(ex.cfg, len(prompt))),
                          np.int32)
        padded[0, :len(prompt)] = prompt
        logits, cache = prefill(variables, jnp.asarray(padded),
                                jnp.int32(len(prompt)))
        out = [int(jnp.argmax(logits[0, len(prompt) - 1]))]
        while len(out) < max_new:
            logits, cache = decode(variables, cache,
                                   jnp.asarray([[out[-1]]], jnp.int32))
            out.append(int(jnp.argmax(logits[0, -1])))
        return out
    return stream


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_every_program_that_writes_the_cache_deletes_the_one_it_got(paged):
    """An admission (dense: prefill then the jitted insert; paged: the
    prefill into the pool, with a copy-on-write for the repeated prompt)
    and a decode-only step each leave every leaf of the cache they
    started from deleted: the executor holds one copy, and the compiled
    decode program aliases all of it."""
    hvd, ex = _executor(paged)
    try:
        assert ex.stats["cache_bytes"] \
            == sum(leaf.nbytes for leaf in _cache_leaves(ex)) > 0
        assert ex.stats["cache_aliased_bytes"] == ex.stats["cache_bytes"]
        prompt = [5, 9, 200, 31, 77, 3, 18, 64, 120]
        _submit(ex, [prompt, prompt], 6)
        kinds = []
        while ex.batcher.inflight_count() or ex.queue.depth():
            before = _cache_leaves(ex)
            admits = ex.stats["steps"]["admit"]
            programs = ex.stats["decode_dispatches"]
            assert ex._serve_step()
            kinds.append(ex.stats["steps"]["admit"] > admits)
            # (The last step of a request enqueues nothing: it fetches
            # the row enqueued a step earlier, ISSUE 35.)
            if kinds[-1] or ex.stats["decode_dispatches"] > programs:
                assert all(leaf.is_deleted() for leaf in before), kinds
            assert not any(leaf.is_deleted() for leaf in _cache_leaves(ex))
        assert True in kinds and False in kinds     # both kinds of step
        if paged:
            assert ex.kv_stats()["cow_copies"] > 0
    finally:
        ex.close()
        hvd.shutdown()


def test_one_insert_program_writes_any_slot_like_the_eager_insert():
    """The jitted insert, compiled once by warm-up, equals
    ``big.at[slot].set(small[0])`` leaf by leaf for every slot, the
    write cursors included, and leaves the other slots' rows alone."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.serving.slotcache import DenseSlotCache

    # The insert needs nothing of an executor, so its compiled programs
    # are shared by every executor of the process: count from here.
    compiled = jax.jit(DenseSlotCache._insert_impl,
                       donate_argnums=0)._cache_size
    before = compiled()
    hvd, ex = _executor(False, max_batch=4, max_seq=96)
    try:
        assert len(ex.cfg.warmup_buckets) == 2
        assert compiled() == before + 1             # whatever the bucket
        rng = np.random.default_rng(27)

        def noise(leaf):
            if jnp.issubdtype(leaf.dtype, jnp.integer):
                return rng.integers(1, 60, leaf.shape).astype(leaf.dtype)
            return rng.standard_normal(leaf.shape).astype(leaf.dtype)

        big = jax.tree_util.tree_map(noise, ex.cache.tree)
        _, row = ex.cache._prefill_jit(
            ex.params, jnp.asarray(rng.integers(2, 256, (1, 16)), jnp.int32),
            jnp.int32(11))
        assert any(leaf.dtype == np.int32 and (np.asarray(leaf) == 11).all()
                   for leaf in jax.tree_util.tree_leaves(row))
        for slot in range(ex.cfg.slots):
            want = jax.tree_util.tree_map(
                lambda b, s: np.asarray(jnp.asarray(b).at[slot].set(s[0])),
                big, row)
            given = jax.tree_util.tree_map(jnp.asarray, big)
            got = ex.cache._insert_jit(given, row, np.int32(slot))
            assert all(leaf.is_deleted()
                       for leaf in jax.tree_util.tree_leaves(given))
            for g, w, b in zip(*map(jax.tree_util.tree_leaves,
                                    (got, want, big))):
                np.testing.assert_array_equal(np.asarray(g), w)
                others = np.arange(ex.cfg.slots) != slot
                np.testing.assert_array_equal(np.asarray(g)[others],
                                              b[others])
        assert compiled() == before + 1             # and whatever the slot
    finally:
        ex.close()
        hvd.shutdown()


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_closed_loop_generates_what_a_loop_that_donates_nothing_does(paged):
    """A seeded closed loop (more requests than slots, prompts repeated
    so that the paged replica meets prefix hits and copies on write)
    generates token for token what tfm.prefill and tfm.decode_step
    generate for each request alone, nothing donated."""
    import random

    hvd, ex = _executor(paged)
    try:
        rng = random.Random(27)
        pool = [[rng.randrange(2, 256) for _ in range(rng.randint(3, 13))]
                for _ in range(3)]
        prompts = [pool[i % 3] for i in range(7)]
        _submit(ex, prompts, 7)
        ex.serve_loop(stop_when=lambda: True)
        assert ex.stats["served"] == len(prompts)
        if paged:
            kv = ex.kv_stats()
            assert kv["prefix_hits"] > 0 and kv["cow_copies"] > 0, kv
        reference = _reference(ex)
        want = {tuple(p): reference(p, 7) for p in pool}
        # Request ids are handed out in order of submission.
        rids = sorted(ex.completed)
        assert len(rids) == len(prompts)
        for rid, prompt in zip(rids, prompts):
            assert ex.completed[rid]["generated"] == want[tuple(prompt)], \
                (rid, prompt)
    finally:
        ex.close()
        hvd.shutdown()


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_warmup_leaves_a_live_fresh_cache_and_init_cache_works_again(paged):
    """Warm-up ran every program on a donated cache and rebound each
    result; what it leaves is live and equal to a fresh cache (zeros but
    for the one write that creates the collection), and so is a second
    ``_init_cache()``."""
    import jax
    import numpy as np

    hvd, ex = _executor(paged)
    try:
        fresh = jax.tree_util.tree_map(np.asarray,
                                       ex.cache._init_cache_jit(ex.params))
        for _ in range(2):
            leaves = _cache_leaves(ex)
            assert leaves and not any(leaf.is_deleted() for leaf in leaves)
            for got, want in zip(leaves, jax.tree_util.tree_leaves(fresh)):
                np.testing.assert_array_equal(np.asarray(got), want)
                if got.dtype == np.int32:
                    assert not np.asarray(got).any()     # write cursors
                elif not paged:
                    assert not np.asarray(got)[:, 1:].any()
            ex._init_cache()
    finally:
        ex.close()
        hvd.shutdown()


# --- a replica alone in its world exchanges in the process (ISSUE 31) -------
_LONE_PROMPTS = ([5, 9, 200, 31, 77], [3, 18, 64, 120, 7, 11, 250, 2, 90],
                 [44, 45, 46], [5, 9, 200, 31, 77])
# The plain numbers of executor.stats that two runs of one request table
# share whatever the host's speed (the rest are times).
_PLAIN_STATS = ("offered", "expired", "served", "served_slo", "lost",
                "steps", "exchanges", "prefill_streams",
                "prefill_fallbacks", "prefill_skipped", "shrinks", "grows",
                "weight_swaps")


def _patch_object_collectives(patched, hvd, allowed=False):
    """Patch hvd.broadcast_object and hvd.allgather_object: to raise, or
    where ``allowed`` to run and leave their names in the list returned."""
    calls = []

    def patch(name):
        real = getattr(hvd, name)

        def call(*args, **kwargs):
            if not allowed:
                raise AssertionError(f"hvd.{name} in the step of a replica "
                                     "that is alone in its world")
            calls.append(name)
            return real(*args, **kwargs)
        patched.setattr(hvd, name, call)
    for name in ("broadcast_object", "allgather_object"):
        patch(name)
    return calls


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_a_lone_replica_serves_what_the_collectives_serve(monkeypatch, paged):
    """One request table twice on a world of one: with both object
    collectives patched to raise, and with the size the executor reads
    set to 2, which sends the same steps through the real broadcast and
    gather.  Same tokens, completions and plain stats; each is what a
    loop over prefill and decode_step alone generates."""
    seen = {}
    for collectives in (False, True):
        hvd, ex = _executor(paged)
        try:
            if collectives:
                ex.size = 2            # what a grow leaves; hvd.size() is 1
            with monkeypatch.context() as patched:
                calls = _patch_object_collectives(patched, hvd, collectives)
                _submit(ex, _LONE_PROMPTS, 9)
                ex.serve_loop(stop_when=lambda: True)
            steps = sum(ex.stats["steps"].values())
            # A plan and a completion exchange a step, and the stop plan.
            assert ex.stats["exchanges"] == 2 * steps + 1
            if collectives:
                assert ex.stats["local_exchanges"] == 0
                assert calls.count("broadcast_object") == steps + 1
                assert calls.count("allgather_object") == steps
            else:
                assert calls == []
                assert ex.stats["local_exchanges"] == ex.stats["exchanges"]
            assert ex.batcher.inflight == {} and ex._unreported == []
            seen[collectives] = {
                "generated": {rid: rec["generated"]
                              for rid, rec in ex.completed.items()},
                "completions": {rid: (rec["replica"], rec["tokens"],
                                      rec["weights"])
                                for rid, rec in ex.completed.items()},
                "stats": {key: ex.stats[key] for key in _PLAIN_STATS},
                "next_step": ex._step}
            if not collectives:
                reference = _reference(ex)
                for rid, prompt in zip(sorted(ex.completed), _LONE_PROMPTS):
                    assert ex.completed[rid]["generated"] \
                        == reference(prompt, 9), (rid, prompt)
        finally:
            ex.close()
            hvd.shutdown()
    assert seen[False] == seen[True]
    assert seen[False]["stats"]["served"] == len(_LONE_PROMPTS)


@pytest.mark.parametrize("size", [1, 2], ids=["alone", "two_ranks"])
@pytest.mark.parametrize("fails", [False, True], ids=["handed_over", "failed"])
def test_unreported_completions_are_cleared_only_after_the_hand_over(
        monkeypatch, size, fails):
    """_exchange_completions returns the records that were waiting and
    clears _unreported once they are handed over; an exchange that fails
    before that (the gather on two ranks, the staged-versions read alone)
    leaves them for the re-send."""
    from horovod_tpu.common.exceptions import RanksFailedError

    hvd, ex = _toy_executor(requests=0)
    try:
        ex.size = size
        waiting = [{"rid": 7, "tokens": 3}, {"rid": 9, "tokens": 5}]
        ex._unreported.extend(waiting)

        def failing(*args, **kwargs):
            assert ex._unreported == waiting       # not cleared before
            raise RanksFailedError([1], "gone")
        if fails and size == 1:
            monkeypatch.setattr(ex, "_fleet_staged_versions", failing)
        elif fails:
            monkeypatch.setattr(hvd, "allgather_object", failing)
        if fails:
            with pytest.raises(RanksFailedError):
                ex._exchange_completions()
            assert ex._unreported == waiting
        else:
            done = ex._exchange_completions()
            assert done == waiting and done is not ex._unreported
            assert ex._unreported == []
            assert ex.stats["exchanges"] == 1
            assert ex.stats["local_exchanges"] == (size == 1)
    finally:
        ex.close()
        hvd.shutdown()


def test_a_lone_fleet_replica_swaps_the_version_it_staged(monkeypatch):
    """The version a lone replica staged is reported by the exchange in
    the process, becomes _fleet_common, is scheduled into the next plan
    and swapped in at that boundary, once; requests in flight go on
    under the new weights."""
    import jax
    import numpy as np

    hvd, ex = _toy_executor(requests=2, max_new=12)
    try:
        _patch_object_collectives(monkeypatch, hvd)
        assert ex._serve_step() and ex._fleet_common == 0
        new = jax.tree_util.tree_map(lambda x: np.asarray(x) * 0.5,
                                     ex.params)
        with ex._fleet_lock:
            ex._fleet_staged[3] = (new, 30, 1234)
        assert ex._serve_step()            # its exchange reports {3}
        assert ex._fleet_common == 3 and ex.weight_version == 0
        assert ex._fleet_reported == {3}
        assert ex._serve_step()            # the plan schedules the swap
        assert ex.weight_version == 3 and ex._fleet_scheduled == 3
        assert [(s["version"], s["step"], s["digest"])
                for s in ex.stats["weight_swaps"]] == [(3, 3, 1234)]
        assert ex._fleet_staged == {} and ex._fleet_common == 0
        for got, want in zip(jax.tree_util.tree_leaves(ex.params),
                             jax.tree_util.tree_leaves(new)):
            np.testing.assert_array_equal(np.asarray(got), want)
        ex.serve_loop(stop_when=lambda: True)
        assert ex.stats["served"] == 2
        assert len(ex.stats["weight_swaps"]) == 1
        assert {rec["weights"] for rec in ex.completed.values()} == {3}
        assert ex.stats["local_exchanges"] == ex.stats["exchanges"] > 0
    finally:
        ex.close()
        hvd.shutdown()


class _TwoRankHvd:
    """Stands in for the ``hvd`` module an executor holds: records the
    object collectives asked of it and answers as a world with one more,
    idle, rank would."""

    def __init__(self):
        self.calls = []

    def broadcast_object(self, obj, root_rank=0, name=None):
        self.calls.append(("broadcast_object", name))
        return obj

    def allgather_object(self, obj, name=None):
        self.calls.append(("allgather_object", name))
        idle = {"done": [], "staged": (), "rids": [],
                "step": obj.get("step", 0), "gen": obj.get("gen", 0)}
        return [obj, idle]


class _GrowAtThirdBoundary:
    """A statesync service that admits a joiner at its third boundary."""
    boundaries = 0
    grow_windows = [(1.0, 1.5)]

    def step_boundary(self):
        import types
        self.boundaries += 1
        if self.boundaries == 3:
            return types.SimpleNamespace(kind="grow", join_id=7, rank=0,
                                         size=2)
        return None


def _grown_by_statesync(ex):
    """1 -> 2 as a grow reaches a rank: the boundary check at the end of
    the third step calls _grow_resync."""
    ex.attach_statesync(_GrowAtThirdBoundary())
    for _ in range(3):
        assert ex._serve_step()
    assert ex.statesync.boundaries == 3
    assert ex.stats["grows"][0]["from"] == 1 \
        and ex.stats["grows"][0]["to"] == 2
    return [("allgather_object", "serve.growsync.7")]


def _shrunk_then_regrown(ex):
    """2 -> 1 -> 2 by the value _shrink_and_resume maintains: two steps
    on two ranks, three alone, then back."""
    ex.size = 2
    assert ex._serve_step() and ex._serve_step()
    ex.size = 1                        # as a shrink to one survivor leaves
    for _ in range(3):
        assert ex._serve_step()
    ex.size = 2
    return [("broadcast_object", "serve.plan.g0.0"),
            ("allgather_object", "serve.done.g0.1"),
            ("broadcast_object", "serve.plan.g0.1"),
            ("allgather_object", "serve.done.g0.2")]


@pytest.mark.parametrize("change", [_grown_by_statesync,
                                    _shrunk_then_regrown],
                         ids=["statesync_grow", "shrink_then_grow"])
def test_the_exchange_follows_the_size_shrink_and_grow_maintain(change):
    """The size is read at the step: the steps a world of one runs ask
    nothing of hvd, and the first step after the world has two ranks
    broadcasts its plan and gathers its completions under the names every
    larger world uses; local_exchanges counts only the former."""
    hvd, ex = _toy_executor(requests=2, max_new=12)
    fake = _TwoRankHvd()
    ex.hvd = fake
    try:
        before = change(ex)
        assert fake.calls == before
        local = ex.stats["local_exchanges"]
        assert local == 6          # three steps alone, either way
        assert ex.stats["exchanges"] - local == sum(
            name.startswith(("serve.plan.", "serve.done."))
            for _, name in before)
        assert ex.size == 2 and sorted(ex.batcher.inflight) == [0, 1]
        step, gen = ex._step, ex._gen
        assert ex._serve_step()
        assert fake.calls[len(before):] == [
            ("broadcast_object", f"serve.plan.g{gen}.{step}"),
            ("allgather_object", f"serve.done.g{gen}.{step + 1}")]
        ex.serve_loop(stop_when=lambda: True)
        assert ex.stats["served"] == 2 and ex.stats["lost"] == 0
        assert ex.stats["local_exchanges"] == local
        assert all(len(rec["generated"]) == 12
                   for rec in ex.completed.values())
    finally:
        ex.hvd = hvd
        ex.close()
        hvd.shutdown()


def test_a_stop_plan_ends_a_lone_replicas_loop(monkeypatch):
    """A drained front's stop plan comes back from the exchange in the
    process as from the broadcast: the step returns False and advances
    the step number, nothing is gathered or accounted."""
    hvd, ex = _toy_executor(requests=0)
    try:
        _patch_object_collectives(monkeypatch, hvd)
        ex.request_stop()
        assert ex._serve_step() is False
        assert (ex._step, ex._gen) == (1, 0)
        assert (ex.stats["exchanges"], ex.stats["local_exchanges"]) == (1, 1)
        assert ex.stats["steps"] == {"admit": 0, "decode": 0}
        _submit(ex, [[7, 8, 9]], 3)    # in flight: served before the stop
        ex.serve_loop(stop_when=lambda: True)
        assert ex.stats["served"] == 1 and ex.batcher.inflight == {}
    finally:
        ex.close()
        hvd.shutdown()


# --- one decode step of lookahead (ISSUE 35) --------------------------------
def _hybrid_toy():
    """The hybrid configuration at the rehearsal sizes of its own file
    (Mamba, attention, Mamba; hidden 64), in float32, and the benchmark's
    seeded weights for it (under flax's own a token's row of the tied
    matrix wins every arg-max): (model configuration, parameters)."""
    import types

    chip = os.path.join(REPO, "benchmarks", "chip")
    if chip not in sys.path:
        sys.path.insert(0, chip)
    import granite_reference
    import run as harness

    from horovod_tpu.models import hybrid
    cfg = harness.load_json(harness.HERE, "configs",
                            "granite-4.0-h-micro.serve.json")
    cfg = harness.merged(cfg, cfg["rehearsal"])
    cfg["model"] = {**cfg["model"], "args": {
        "dtype": "@jax.numpy:float32", "param_dtype": "@jax.numpy:float32"}}
    return hybrid.HybridConfig(**harness.build_args(cfg)), \
        granite_reference.weights(types.SimpleNamespace(
            config=cfg, seed=35, resolve=harness.resolve))


_LOOKAHEAD = {"dense-transformer": dict(paged=False),
              "paged-transformer": dict(paged=True, paged_slots=3),
              "dense-hybrid": dict(paged=False, hybrid=True)}


def _lookahead_executor(kind: str, **kw):
    """Three slots of the layout and the family ``kind`` names."""
    from horovod_tpu.serving import ReplicaExecutor, ServeConfig

    layout = dict(_LOOKAHEAD[kind])
    model_cfg, params = _hybrid_toy() if layout.pop("hybrid", False) \
        else (None, None)
    hvd = _solo_world()
    return hvd, ReplicaExecutor(ServeConfig(**{**dict(
        model_cfg=model_cfg, max_batch=3, token_budget=64, max_seq=64,
        slo_ms=60000.0, block_tokens=8, warmup_buckets=(8, 16)),
        **layout, **kw}), params=params)


def _lookahead_requests():
    """Nine requests of unequal lengths over three slots, in three waves:
    the later ones are admitted into a batch that is decoding, into
    slots their predecessors left."""
    import random

    rng = random.Random(35)
    lengths = (5, 2, 9, 13, 3, 16, 7, 1, 11)
    new = (6, 3, 9, 2, 12, 4, 7, 5, 1)
    prompts = [[rng.randrange(2, 256) for _ in range(n)] for n in lengths]
    return list(zip(prompts, new))


def _drive(ex, waves, each_step=lambda: None) -> dict:
    """Submit a wave, run three steps, submit the next; then drain.
    ``each_step`` runs after every step.  rid -> (prompt, max_new)."""
    asked = {}
    for wave in waves:
        for prompt, new in wave:
            ex.stats["offered"] += 1
            rid = ex.queue.submit(list(prompt), new)
            assert rid is not None
            asked[rid] = (prompt, new)
        for _ in range(3):
            assert ex._serve_step()
            each_step()
    for _ in range(200):
        if not (ex.batcher.inflight_count() or ex.queue.depth()):
            break
        assert ex._serve_step()
        each_step()
    assert ex.stats["served"] == len(asked)
    return asked


def _short_requests():
    """Twelve requests of 2 to 4 tokens out, in one wave: a slot frees
    every few steps while the others decode, so most steps that admit
    find a decode step in flight."""
    import random

    rng = random.Random(45)
    return [([rng.randrange(2, 256) for _ in range(n)], new)
            for n, new in zip((4, 9, 2, 14, 6, 3, 11, 5, 8, 1, 13, 7),
                              (2, 3, 4, 2, 4, 3, 2, 3, 4, 2, 3, 2))]


@pytest.mark.parametrize("kind", [*_LOOKAHEAD, "paged-short-outputs"])
def test_the_lookahead_serves_what_a_plain_greedy_loop_serves(kind):
    """Admissions into a decoding batch, unequal output lengths, slots
    used again: request by request the replica serves exactly the tokens
    of a one-request greedy loop over the family's own decode_step, and
    nearly every decode program was enqueued with the one before it
    still unfetched.  With short outputs most steps that admit enqueue
    their first prefill while a decode step is in flight."""
    short = kind == "paged-short-outputs"
    hvd, ex = _lookahead_executor("paged-transformer" if short else kind)
    try:
        if short:
            asked = _drive(ex, [_short_requests()])
        else:
            requests = _lookahead_requests()
            asked = _drive(ex, [requests[:4], requests[4:6], requests[6:]])
        greedy = _reference(ex)
        for rid, (prompt, new) in asked.items():
            assert ex.completed[rid]["generated"] == greedy(prompt, new), rid
            assert ex.completed[rid]["tokens"] == new
        stats = ex.stats
        assert stats["decode_dispatches"] > stats["decode_overlapped"] \
            >= stats["decode_dispatches"] - stats["steps"]["admit"] > 0
        assert stats["admissions"] == len(asked)
        assert 0 < stats["admit_overlapped"] < stats["admissions"]
        if short:              # a step's first admission, in most steps
            assert stats["admit_overlapped"] > stats["steps"]["admit"] / 2
        assert ex._in_flight is None and ex.slots == [None] * 3
    finally:
        ex.close()
        hvd.shutdown()


@pytest.mark.parametrize("kind", list(_LOOKAHEAD))
def test_a_stream_ends_at_its_end_token_and_the_row_behind_it_is_dropped(
        kind):
    """With ``eos_id`` set to a token that greedy decoding emits in
    mid-stream, the program enqueued behind it has a row for the slot
    already: that row's token appears nowhere (not in ``generated``, not
    in the completion's count), the stream ends at its end token, a
    request is complete only once nothing of it is in flight, and the
    slot's next occupant is served its own tokens."""
    hvd, plain = _lookahead_executor(kind)
    try:
        greedy = _reference(plain)
        requests = _lookahead_requests()
        streams = [greedy(prompt, new) for prompt, new in requests]
    finally:
        plain.close()
        hvd.shutdown()
    # An end token that cuts a stream short, with tokens still to come.
    eos = next(stream[j] for stream in streams if len(stream) > 4
               for j in (2,) if stream[j] not in stream[1:j])

    def until_eos(stream):
        stop = [j for j in range(1, len(stream)) if stream[j] == eos]
        return stream[:stop[0] + 1] if stop else stream

    want = [until_eos(stream) for stream in streams]
    assert any(len(w) < len(s) for w, s in zip(want, streams))
    hvd, ex = _lookahead_executor(kind, eos_id=eos)
    try:
        early = []

        def each_step():
            for s in ex.slots:
                if s is not None:
                    assert s.rid not in ex.completed
                    early.append(s.remaining == 0 and s.in_flight)
            for rec in ex.completed.values():
                assert rec["tokens"] == len(rec["generated"])

        asked = _drive(ex, [requests[:4], requests[4:6], requests[6:]],
                       each_step)
        # Some stopped slot did wait for the row enqueued behind its end
        # token, and was not complete while it waited.
        assert any(early)
        for rid, stream in zip(sorted(asked), want):
            assert ex.completed[rid]["generated"] == stream, rid
        assert sum(rec["tokens"] for rec in ex.completed.values()) \
            == sum(map(len, want))
        assert ex._in_flight is None and ex.slots == [None] * 3
        if ex.cfg.paged:
            assert ex.kv_stats()["active"] == 0      # nothing leaked
    finally:
        ex.close()
        hvd.shutdown()


class _Recorded:
    """The cache's ``admit``, ``decode`` and ``fetch`` with their order
    kept: an admission is ``("prefill", slot)``, each decode gets a
    number, and a fetch names the decode it waits for; with
    ``first_tokens`` a first token's fetch is ``("first", slot)``."""

    def __init__(self, ex, first_tokens=False):
        self.calls, self._ids = [], {}
        self._kept = []               # alive, so that no id comes twice
        decode, fetch = ex.cache.decode, ex.cache.fetch
        admit, first_token = ex.cache.admit, ex.cache.first_token
        slots = {}                    # id of a first token -> its slot

        def recorded_admit(params, slot, *args):
            first = admit(params, slot, *args)
            self._kept.append(first)
            slots[id(first)] = slot
            self.calls.append(("prefill", slot))
            return first

        def recorded_first_token(first):
            if first_tokens:
                self.calls.append(("first", slots[id(first)]))
            return first_token(first)

        ex.cache.admit, ex.cache.first_token = \
            recorded_admit, recorded_first_token

        def recorded_decode(*args):
            result = decode(*args)
            self._kept.append(result)
            self._ids[id(result)] = len(self._ids)
            self.calls.append(("decode", self._ids[id(result)]))
            return result

        def recorded_fetch(result):
            self.calls.append(("fetch", self._ids[id(result)]))
            return fetch(result)

        ex.cache.decode, ex.cache.fetch = recorded_decode, recorded_fetch


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_a_step_enqueues_the_next_decode_before_it_fetches_the_last(paged):
    """In a step that admits nothing the dispatch of step k+1 precedes
    the fetch of step k; a step that admits enqueues its prefill, then
    fetches the step in flight, and starts the chain again; a request's
    last step has only a fetch left; the three counters count what
    happened."""
    hvd, ex = _executor(paged, max_batch=3)
    try:
        seen = _Recorded(ex)

        def step():
            del seen.calls[:]
            assert ex._serve_step()
            return seen.calls

        _submit(ex, [[5, 9, 200], [31, 77, 3, 18]], 6)
        assert step() == [("prefill", 0), ("prefill", 1),  # admits both
                          ("decode", 0)]
        assert step() == [("decode", 1), ("fetch", 0)]
        assert step() == [("decode", 2), ("fetch", 1)]
        _submit(ex, [[64, 120]], 3)
        # Admits the third: its prefill is enqueued behind step 2.
        assert step() == [("prefill", 2), ("fetch", 2), ("decode", 3)]
        assert step() == [("decode", 4), ("fetch", 3)]
        assert sorted(len(s.generated) for s in ex.slots if s is not None) \
            == [2, 5, 5]
        assert ex.completed == {}      # each one's last row is in flight
        assert step() == [("fetch", 4)]
        assert sorted(ex.completed) == [0, 1, 2]
        assert [ex.completed[rid]["tokens"] for rid in range(3)] == [6, 6, 3]
        assert (ex.stats["decode_dispatches"],
                ex.stats["decode_overlapped"]) == (5, 3)
        assert (ex.stats["admissions"], ex.stats["admit_overlapped"]) \
            == (3, 1)
        assert ex.stats["steps"] == {"admit": 2, "decode": 4}
    finally:
        ex.close()
        hvd.shutdown()


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_a_step_that_admits_two_leaves_one_prefill_unfetched_at_most(paged):
    """One step admits two requests with a decode step in flight: the
    first prefill is enqueued, the step in flight fetched, the first
    token fetched, and only then is the second prefill enqueued; the
    second finds nothing in flight.  Both are served the greedy loop's
    tokens."""
    hvd, ex = _executor(paged, max_batch=3)
    try:
        seen = _Recorded(ex, first_tokens=True)
        _submit(ex, [[5, 9, 200]], 8)
        assert ex._serve_step() and ex._serve_step()
        assert ex._in_flight is not None
        del seen.calls[:]
        _submit(ex, [[31, 77, 3, 18], [64, 120]], 4)
        assert ex._serve_step()
        assert seen.calls == [("prefill", 1), ("fetch", 1), ("first", 1),
                              ("prefill", 2), ("first", 2), ("decode", 2)]
        unfetched = 0
        for call, _ in seen.calls:
            unfetched += {"prefill": 1, "first": -1}.get(call, 0)
            assert unfetched <= 1
        assert (ex.stats["admissions"], ex.stats["admit_overlapped"]) \
            == (3, 1)
        for _ in range(12):
            assert ex._serve_step()
        greedy = _reference(ex)
        for rid, (prompt, new) in enumerate([([5, 9, 200], 8),
                                             ([31, 77, 3, 18], 4),
                                             ([64, 120], 4)]):
            assert ex.completed[rid]["generated"] == greedy(prompt, new)
    finally:
        ex.close()
        hvd.shutdown()


def _settled_by_state_tree(ex):
    assert set(ex.state_tree()) == {"params"}


def _settled_by_close(ex):
    ex.close()


def _settled_by_a_stop(ex):
    from horovod_tpu.serving.batcher import BatchPlan
    ex._exchange_plan = lambda plan: BatchPlan(step=ex._step, stop=True)
    assert ex._serve_step() is False


def _settled_by_a_world_change(ex):
    ex.hvd = _TwoRankHvd()             # answers the resync's gather
    ex._resync()
    assert ex.hvd.calls == [("allgather_object", "serve.resync.g0")]


def _settled_by_a_grow(ex):
    ex.hvd = _TwoRankHvd()
    ex._grow_resync(7, 0, 2)
    assert ex.size == 2


@pytest.mark.parametrize("settle", [
    _settled_by_state_tree, _settled_by_close, _settled_by_a_stop,
    _settled_by_a_world_change, _settled_by_a_grow],
    ids=["state_tree", "close", "stop", "resync", "grow_resync"])
def test_what_leaves_the_step_leaves_nothing_in_flight(settle):
    """Whatever reads or replaces the cache, the parameters or the world
    from outside a step first fetches the decode step in flight and
    advances its slots: nothing is leaked and no token is lost."""
    hvd, ex = _toy_executor(requests=2, max_new=8)
    try:
        for _ in range(3):
            assert ex._serve_step()
        assert ex._in_flight is not None
        assert [(len(s.generated), s.in_flight) for s in ex.slots] \
            == [(3, 1), (3, 1)]
        settle(ex)
        assert ex._in_flight is None
        assert [(len(s.generated), s.in_flight, s.remaining)
                for s in ex.slots] == [(4, 0, 4), (4, 0, 4)]
        assert list(ex._last_tokens) == [s.generated[-1] for s in ex.slots]
    finally:
        ex.hvd = hvd
        ex.close()
        hvd.shutdown()
