"""An admission, counted where it happens (ISSUE 38): the flat counters
of ``executor.stats`` against hand-made sequences on a toy decoder, a
request's three marks in a profiler session, ``report --request``, and
what a slow step's record says of the serving thread."""
import glob
import json
import os
import time
import types

import pytest

from horovod_tpu.serving.batcher import Assignment, BatchPlan
from horovod_tpu.telemetry import report
from horovod_tpu.telemetry.spans import StepParts

LAYOUTS = pytest.mark.parametrize("paged", [False, True],
                                  ids=["dense", "paged"])
# What ``executor.stats`` counts of the admissions: each a plain number
# that a per-layer metric reads.
COUNTERS = ("admissions", "admit_s", "prefill_prompt_tokens",
            "prefill_bucket_positions", "admit_overlapped")


@pytest.fixture
def solo():
    import horovod_tpu as hvd
    hvd.shutdown()
    for var in ("HOROVOD_RANK", "HOROVOD_SIZE"):
        os.environ.pop(var, None)
    hvd.init()
    yield hvd
    hvd.shutdown()


def executor(paged: bool = False, **kw):
    from horovod_tpu.serving import ReplicaExecutor, ServeConfig
    base = dict(max_batch=2, token_budget=128, max_seq=128, slo_ms=60000.0,
                block_tokens=8, paged=paged)
    base.update(kw)
    return ReplicaExecutor(ServeConfig.from_env(**base))


def prompt(n: int, first: int = 2) -> list:
    return [first + i for i in range(n)]


def serve(ex, prompts: list, max_new=(4, 4, 4)) -> list:
    rids = []
    for toks, new in zip(prompts, max_new):
        ex.stats["offered"] += 1
        rids.append(ex.queue.submit(toks, new))
    ex._stop_requested = False
    ex.serve_loop(stop_when=lambda: True)
    assert None not in rids and all(rid in ex.completed for rid in rids)
    return rids


@LAYOUTS
def test_warm_up_is_no_admission(solo, paged):
    """Both warm buckets compiled and prefilled, and every counter of
    the admissions still reads 0: no compile in admit_s, no warm-up
    prompt in the padding share."""
    ex = executor(paged)
    try:
        assert ex.cfg.warmup_buckets == (8, 16)
        assert {ex.stats[key] for key in COUNTERS} == {0}
        assert ex.stats["prefill_by_bucket"] == {}
    finally:
        ex.close()


@LAYOUTS
def test_prompt_tokens_and_bucket_positions_of_three_prompts(solo, paged):
    """Prompts of 5, 9 and 33 tokens are 47 prompt tokens in buckets of
    8 + 16 + 64 positions, one admission each, in either layout; the
    seconds nest: an admission lies inside a step that admits.  The
    counters are those that a metric reads and no others."""
    ex = executor(paged)
    try:
        serve(ex, [prompt(5), prompt(9, 40), prompt(33, 80)])
        stats = ex.stats
        assert stats["admissions"] == stats["served"] == 3
        assert stats["prefill_prompt_tokens"] == 47
        assert stats["prefill_bucket_positions"] == 8 + 16 + 64
        by_bucket = stats["prefill_by_bucket"]
        assert {b: row[:2] for b, row in by_bucket.items()} \
            == {8: [1, 5], 16: [1, 9], 64: [1, 33]}
        assert sum(row[2] for row in by_bucket.values()) \
            == pytest.approx(stats["admit_s"])
        parts_s = stats["step_parts_s"]["admit"]
        assert 0 < stats["admit_s"] <= parts_s["total"]
        assert stats["admit_s"] == pytest.approx(parts_s["admit"])
        # What the benchmark's counter reader takes: plain numbers.
        assert all(type(stats[key]) in (int, float) for key in COUNTERS)
        # Without a reader, and gone (REVIEW, PR 38): the spans
        # hvd.serve.prefill_dispatch, .first_token_fetch and .token_fetch
        # and step_parts_s hold these already.
        assert not {"step_s_admit", "step_s_decode", "admit_settle_s",
                    "admit_stalled_streams", "prefill_dispatch_s",
                    "prefill_wait_s", "queue_wait_s"} & set(stats)
        table = report.admission_table(by_bucket).splitlines()
        assert table[0].split() == ["bucket", "count", "mean_ms",
                                    "ms_per_ktoken", "padding_%"]
        assert [row.split()[0] for row in table[2:]] == ["8", "16", "64"]
        assert table[-1].split()[-1] == f"{100 * (1 - 33 / 64):.2f}"
    finally:
        ex.close()


@LAYOUTS
def test_an_admission_behind_a_step_in_flight_is_counted_once(
        solo, monkeypatch, paged):
    """Two slots, outputs of 4 and 12: the third request is admitted
    while the second decodes, its prefill enqueued behind the step in
    flight (``admit_overlapped``).  Each admission is counted once; the
    settle inside it is that step's ``token_fetch`` and not its
    ``admit`` part, so every step's parts, ``other`` not negative, sum to
    it, while ``admit_s`` (what the streams waited) holds the settle."""
    ex = executor(paged, paged_slots=2)
    seen = []
    note = ex._note_step_parts
    monkeypatch.setattr(
        ex, "_note_step_parts",
        lambda step, seconds, admits, before: (
            seen.append((dict(seconds), admits)),
            note(step, seconds, admits, before)))
    try:
        serve(ex, [prompt(5), prompt(9, 40), prompt(33, 80)], (4, 12, 4))
        stats = ex.stats
        assert (stats["admissions"], stats["admit_overlapped"]) == (3, 1)
        assert stats["prefill_prompt_tokens"] == 47
        assert stats["prefill_bucket_positions"] == 8 + 16 + 64
        by_bucket = stats["prefill_by_bucket"]
        assert {b: row[:2] for b, row in by_bucket.items()} \
            == {8: [1, 5], 16: [1, 9], 64: [1, 33]}
        assert sum(row[2] for row in by_bucket.values()) \
            == pytest.approx(stats["admit_s"])
        admitting = [seconds for seconds, admits in seen if admits]
        assert len(admitting) == stats["steps"]["admit"] == 2
        for seconds in admitting:
            total = seconds.pop("total")
            assert seconds["other"] >= 0
            assert sum(seconds.values()) == pytest.approx(total, abs=1e-9)
        # The one settle of an admit step lies inside its admission.
        assert "token_fetch" not in admitting[0]
        settle = admitting[1]["token_fetch"]
        parts_s = stats["step_parts_s"]["admit"]
        assert parts_s["token_fetch"] == pytest.approx(settle)
        assert stats["admit_s"] == pytest.approx(parts_s["admit"] + settle)
    finally:
        ex.close()


def test_a_full_prefix_hit_is_an_admission_without_prompt_tokens(solo):
    """The paged layout: a prompt whose blocks are all resident is given
    a slot (an admission) and prefills nothing anew; its program still
    runs the last token again, in the smallest bucket."""
    ex = executor(paged=True)
    try:
        serve(ex, [prompt(12)])
        first = dict(ex.stats)
        assert (first["admissions"], first["prefill_prompt_tokens"],
                first["prefill_bucket_positions"]) == (1, 12, 16)
        serve(ex, [prompt(12)])
        stats = ex.stats
        assert stats["prefill_skipped"] == 1
        assert stats["admissions"] == 2
        assert stats["prefill_prompt_tokens"] == 12
        assert stats["prefill_bucket_positions"] == 16 + 8
        # A partial hit prefills what the prefix cache lacks: all but
        # the first whole block of 8.
        serve(ex, [prompt(12) + prompt(9, 100)])
        assert stats["admissions"] == 3
        assert stats["prefill_prompt_tokens"] == 12 + 13
        assert stats["prefill_bucket_positions"] == 16 + 8 + 16
        # By the positions each prefill ran over, not the prompt's bucket:
        # the hit in the smallest with no token computed anew, the partial
        # hit's 13 tokens in 16 positions and not 21 in 32.
        assert {b: row[:2] for b, row
                in stats["prefill_by_bucket"].items()} \
            == {16: [2, 25], 8: [1, 0]}
        table = report.admission_table(stats["prefill_by_bucket"])
        assert table.splitlines()[2].split()[3:] == ["-", "100.00"]
    finally:
        ex.close()


def test_stalled_streams_and_queue_wait_against_a_driven_clock(
        solo, monkeypatch, tmp_path):
    """The ingress and the batcher read a driven clock: a request that
    waited 0.25 s and two that waited 2 s before their plan formed carry
    that, and what their admitting steps took of their own, as their
    ``hvd.serve.admit``'s ``queue_wait_ms``; the first admission stalls
    nobody (``running``), the second the first stream, the third (the
    same plan) both."""
    import jax
    from jax.profiler import ProfileData

    from horovod_tpu.serving import batcher, queue

    clock = [100.0]
    driven = types.SimpleNamespace(monotonic=lambda: clock[0])
    monkeypatch.setattr(queue, "time", driven)
    monkeypatch.setattr(batcher, "time", driven)
    ex = executor(max_batch=3)
    try:
        jax.profiler.start_trace(str(tmp_path))
        assert ex.queue.submit(prompt(5), 40) is not None
        clock[0] += 0.25
        assert ex._serve_step()
        stats = ex.stats
        assert stats["admissions"] == 1
        assert ex.queue.submit(prompt(9, 40), 40) is not None
        assert ex.queue.submit(prompt(33, 80), 40) is not None
        clock[0] += 2.0
        assert ex._serve_step() and ex._serve_step()
        jax.profiler.stop_trace()
        assert stats["admissions"] == 3 == stats["steps"]["admit"] + 1
        assert stats["steps"]["decode"] == 1
        step_ms = stats["step_parts_s"]["admit"]["total"] * 1e3
        second_ms = stats["prefill_by_bucket"][16][2] * 1e3
    finally:
        ex.close()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    admits = sorted(
        (dict(ev.stats) for plane in ProfileData.from_file(path).planes
         for line in plane.lines for ev in line.events
         if ev.name == "hvd.serve.admit"), key=lambda a: a["rid"])
    assert [a["running"] for a in admits] == [0, 1, 2]
    waits = [a["queue_wait_ms"] for a in admits]
    assert 250 <= waits[0] <= 250 + step_ms
    assert 2000 <= waits[1] <= 2000 + step_ms
    # The third waited for the second's admission as well.
    assert 2000 + second_ms <= waits[2] <= 2000 + step_ms


def test_the_disaggregated_path_counts_an_admission_once(solo):
    """A request whose prefill another rank streams is given its slot
    once: parked, it is an admission with no prompt tokens; the local
    prefill that its patience falls back to adds the tokens and no second
    admission; a prompt the prefix cache holds takes the local path
    inside the same single admission."""
    ex = executor(paged=True)
    try:
        def apply(rid, toks):
            plan = BatchPlan(step=rid, assign=[Assignment(
                rid=rid, replica=0, tokens=toks, max_new_tokens=4,
                age_ms=0.0, deadline_rel_ms=60000.0, slo_ms=60000.0,
                prefill=1)])
            parts = StepParts("serve", step=rid)
            try:
                return ex._apply_plan(plan, parts)
            finally:
                parts.close()

        assert apply(0, prompt(12)) == 1
        stats = ex.stats
        assert ex.slots[0].pending is not None
        assert (stats["admissions"], stats["prefill_prompt_tokens"]) == (1, 0)
        ex.slots[0].pending_since -= 3600.0       # its patience is over
        ex._integrate_prefills()
        assert ex.slots[0].pending is None
        assert stats["prefill_fallbacks"] == 1
        assert (stats["admissions"], stats["prefill_prompt_tokens"],
                stats["prefill_bucket_positions"]) == (1, 12, 16)
        assert apply(1, prompt(12)) == 1          # resident: a full hit
        assert ex.slots[1].pending is None
        assert (stats["admissions"], stats["prefill_prompt_tokens"],
                stats["prefill_skipped"]) == (2, 12, 1)
        # A parked admission settles before it begins; these two found
        # nothing in flight.
        assert stats["admit_overlapped"] == 0
        # Parked, it prefilled nothing in its admission (bucket 0: the
        # fallback's tokens came later, outside it); the full hit ran the
        # last token again in the smallest bucket.
        assert {b: row[:2] for b, row
                in stats["prefill_by_bucket"].items()} \
            == {0: [1, 0], 8: [1, 0]}
        assert report.admission_table(
            stats["prefill_by_bucket"]).splitlines()[2].split()[3:] \
            == ["-", "-"]
    finally:
        ex.close()


# ------------------------------------------------ one request in a session
@pytest.fixture
def session(solo, tmp_path):
    """A CPU profiler session around three requests on two slots, from
    their submission to the last one's completion: (path, executor's
    completed records)."""
    import jax

    ex = executor()
    try:
        jax.profiler.start_trace(str(tmp_path))
        serve(ex, [prompt(5), prompt(9, 40), prompt(33, 80)], (6, 10, 6))
        jax.profiler.stop_trace()
        completed = dict(ex.completed)
    finally:
        ex.close()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    return path, completed


def test_one_rid_marks_a_requests_enqueue_admit_and_complete(session):
    from jax.profiler import ProfileData

    path, completed = session
    marks: dict = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("hvd.serve.enqueue", "hvd.serve.admit",
                               "hvd.serve.complete"):
                    stats = dict(ev.stats)
                    marks.setdefault(stats["rid"], {})[ev.name] = (
                        ev.start_ns, stats)
    assert sorted(marks) == [0, 1, 2]
    for rid, found in marks.items():
        enqueue, admit, complete = (
            found["hvd.serve." + name]
            for name in ("enqueue", "admit", "complete"))
        assert enqueue[0] < admit[0] < complete[0]
        assert set(admit[1]) == {"rid", "bucket", "slot", "prompt_tokens",
                                 "running", "queue_wait_ms"}
        assert set(complete[1]) == {"rid", "tokens", "steps"}
        assert complete[1]["tokens"] == completed[rid]["tokens"] \
            == (6, 10, 6)[rid]
        # A token a step, the first with the admission: that step also
        # enqueues the decode step whose token the next one fetches.
        assert complete[1]["steps"] == complete[1]["tokens"]
    buckets = {rid: found["hvd.serve.admit"][1] for rid, found
               in marks.items()}
    assert [(a["bucket"], a["prompt_tokens"]) for _, a
            in sorted(buckets.items())] == [(8, 5), (16, 9), (64, 33)]
    # Two slots: the third waits for one, and then stalls the other.
    assert [buckets[rid]["running"] for rid in (0, 1, 2)] == [0, 1, 1]
    assert buckets[2]["queue_wait_ms"] > buckets[0]["queue_wait_ms"] > 0


def test_report_prints_one_requests_phases(session, capsys):
    path, _ = session
    assert report.main([path, "--request", "2"]) == 0
    text = capsys.readouterr().out
    assert "request 2\n" in text and "spans in" not in text
    rows = {line.strip().split("  ")[0]: line for line in text.splitlines()}
    for phase in ("queue wait", "admission", "prefill_dispatch",
                  "cache_insert", "first_token_fetch", "decode steps",
                  "tokens", "first to last token"):
        assert phase in rows, (phase, text)
    # Request 0 left its slot while request 1 decoded: request 2's
    # admission holds the settle of the decode step in flight.
    assert "behind the prefill's dispatch" in rows["token_fetch"]
    assert list(rows).index("cache_insert") < list(rows).index(
        "token_fetch") < list(rows).index("first_token_fetch")
    assert "from its enqueue mark" in rows["queue wait"]
    assert "bucket 64, prompt_tokens 33" in rows["admission"]
    assert rows["tokens"].split()[1] == "6"
    assert rows["decode steps"].split()[2] == "5"     # and the admitting one
    assert report.main([path, "--request", "99"]) == 0
    assert "no hvd.serve.enqueue" in capsys.readouterr().out
    # Without a rid: the span tables, the admissions by bucket and the
    # requests that waited longest for a first token.
    whole = report.summarize_file(path)
    assert "spans in hvd.serve.step [admits > 0]" in whole
    assert "hvd.serve.enqueue" not in whole
    table = whole.split("admissions by bucket\n")[1].splitlines()
    assert [row.split()[:2] for row in table[2:5]] \
        == [["8", "1"], ["16", "1"], ["64", "1"]]
    slowest = whole.split("slowest requests to a first token")[1]
    assert "3 admitted in the session" in slowest
    assert slowest.splitlines()[3].split()[0] == "2"   # it waited for a slot


def test_report_tells_a_queued_request_from_one_admitted_earlier():
    """Only an enqueue mark: the request was still queued when the
    session ended, and no step of the session is its own; only a complete
    mark: it was admitted before the session opened."""
    def ev(name, start, end, **stats):
        return (start, end, name, stats)

    spans = report._nest([
        ev("hvd.serve.step", 0, 100, admits=0),
        ev("hvd.serve.complete", 40, 40, rid=3, tokens=9, steps=9),
        ev("hvd.serve.step", 100, 200, admits=0),
        ev("hvd.serve.enqueue", 150, 150, rid=7)])
    found = report._requests(spans)
    queued = report.request_report(found, 7)
    assert "still queued at the session's end" in queued
    assert "before the session opened" not in queued
    assert "none in the session" in queued and "decoding" not in queued
    earlier = report.request_report(found, 3)
    assert "before the session opened" in earlier
    assert "still queued" not in earlier
    assert [ln.split()[:3] for ln in earlier.splitlines()
            if ln.startswith("decode steps")] == [["decode", "steps", "1"]]


# ----------------------------------------- was the serving thread running?
@pytest.mark.parametrize("planted", ["sleep", "busy"])
def test_a_slow_steps_record_says_whether_the_thread_ran(
        solo, monkeypatch, planted):
    """A step that sleeps 0.3 s leaves a record with next to no CPU time;
    one that spins for 0.3 s of the thread's own time one with all of
    it."""
    ex = executor()
    ex.queue.submit(prompt(5), 40)
    plan = ex._exchange_plan

    def stall(seconds, clock):
        until = clock() + seconds
        while clock() < until:
            if planted == "sleep":
                time.sleep(until - clock())

    def exchange(p):
        # A steady 20 ms step, so that noise stays under three times it.
        stall(0.3 if ex._step == 20 else 0.02,
              time.perf_counter if planted == "sleep" or ex._step != 20
              else time.thread_time)
        return plan(p)

    monkeypatch.setattr(ex, "_exchange_plan", exchange)
    try:
        ex.serve_loop(stop_when=lambda: True)
        (record,) = [r for r in ex.stats["slow_steps"] if r["step"] == 20]
        assert record["slowest"] == "plan_exchange"
        assert record["total_ms"] >= 300
        assert isinstance(record["nivcsw"], int) and record["nivcsw"] >= 0
        if planted == "sleep":
            assert record["cpu_ms"] < 100, record
        else:
            assert record["cpu_ms"] >= 300, record
        json.dumps(record)             # it goes to the log as it is
    finally:
        ex.close()


# --------------------------------------------------- the benchmark's reading
def test_the_mimo_rehearsals_pad_share_is_its_tables(capsys):
    """``replica.prefill_pad_share`` of the MiMo cell's traced rehearsal
    is what its rehearsal table and ``prompt_bucket`` give for the
    requests it admitted, the fill included."""
    from benchmarks.chip import test_chip_benchmark as bench
    from horovod_tpu.serving.slotcache import prompt_bucket

    cell = "mimov25_serve_mixlen_sat"
    assert bench.rehearse("--workload", cell, "--trace", "1") == 0
    out = capsys.readouterr().out
    line = bench.result_line(out)
    notes = json.loads(next(ln for ln in out.splitlines()
                            if " notes {" in ln).split(" notes ", 1)[1])
    admitted = notes["stats.admissions"]
    assert notes["completed"] <= admitted <= notes["submitted"]
    traffic = bench.harness.load_json(bench.HERE, "traffic",
                                      "mixlen_sat.json")["rehearsal"]
    config = bench.harness.load_json(
        bench.HERE, "configs", "MiMo-V2.5.serve.json")["rehearsal"]
    cfg = types.SimpleNamespace(
        max_seq=config["serve"]["max_seq"],
        warmup_buckets=tuple(config["serve"]["warmup_buckets"]))
    table = traffic["requests"]
    prompts = [table[i % len(table)][0] for i in range(admitted)]
    positions = sum(prompt_bucket(cfg, n) for n in prompts)
    assert notes["stats.prefill_prompt_tokens"] == sum(prompts)
    assert notes["stats.prefill_bucket_positions"] == positions
    assert line["metrics"]["replica.prefill_pad_share"]["value"] \
        == pytest.approx(100 * (positions - sum(prompts)) / positions)
    assert {"replica.prefill_time_share", "replica.prefill_ms_per_ktoken",
            "replica.admit_stall_ms_mean", "replica.prefill_dispatch_ms_p50",
            "replica.admit_settle_ms_p50"} <= set(line["metrics"])
    # Left out (REVIEW, PR 38): over a whole run it read the load
    # generator's simultaneous fill, not the batcher.
    assert "batcher.queue_wait_ms_mean" not in line["metrics"]
