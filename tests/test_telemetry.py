"""telemetry/ unit tests (ISSUE 4): registry semantics + thread safety,
Prometheus exposition golden file, straggler aggregation, exporter HTTP
endpoint, JSON dump + report CLI, wire snapshot round-trip, timeline
counter events, and the HOROVOD_METRICS=off no-op contract."""
from __future__ import annotations

import json
import threading
import urllib.request

import numpy as np
import pytest

from horovod_tpu.common.message import RequestList
from horovod_tpu.common.timeline import Timeline
from horovod_tpu.telemetry import (NULL_METRIC, NULL_REGISTRY,
                                   MetricsExporter, MetricsRegistry,
                                   StragglerAggregator, dump_json,
                                   resolve_dump_path)
from horovod_tpu.telemetry.registry import bucket_upper_bound
from horovod_tpu.telemetry.report import (summarize_dump, summarize_file,
                                          summarize_timeline)

import os

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures", "telemetry")


# --- registry ---------------------------------------------------------------
def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry(0)
    c = reg.counter("c_total", "help")
    c.inc()
    c.inc(4)
    assert c.value == 5
    # Same (name, labels) -> same object; different labels -> different.
    assert reg.counter("c_total") is c
    assert reg.counter("c_total", labels={"x": "1"}) is not c

    g = reg.gauge("g")
    g.set(2.5)
    g.set(1.0)
    assert g.value == 1.0

    h = reg.histogram("h_ms")
    for v in (0.5, 1.5, 3.0, 12.0):
        h.observe(v)
    assert h.count == 4
    assert h.sum == pytest.approx(17.0)
    assert h.mean == pytest.approx(4.25)
    # log2 buckets: p50 falls in the <=2 bucket, p99 in the <=16 bucket.
    assert h.percentile(50) == 2.0
    assert h.percentile(99) == 16.0
    bounds = [b for b, _ in h.nonzero_buckets()]
    assert bounds == [0.5, 2.0, 4.0, 16.0]


def test_histogram_quantile_interpolates_and_clamps():
    """ISSUE 9 satellite: quantile(q) interpolates geometrically inside
    the log2 bucket (serving SLO p50/p99/p999 and training step times
    share this one path) and clamps to the observed min/max, unlike the
    bucket-bound percentile()."""
    reg = MetricsRegistry(0)
    h = reg.histogram("q_ms")
    for v in (0.5, 1.5, 3.0, 12.0):
        h.observe(v)
    assert h.quantile(0.5) == pytest.approx(2.0)
    # p99 interpolates to ~15.6 inside the (8, 16] bucket, then clamps
    # to the observed max of 12 — percentile() would report 16.
    assert h.quantile(0.99) == pytest.approx(12.0)
    assert h.percentile(99) == 16.0
    assert h.quantile(0.0) == pytest.approx(0.5)   # clamped to min
    assert h.quantile(1.0) == pytest.approx(12.0)
    # Single-bucket histogram: every quantile stays inside the bucket.
    h2 = reg.histogram("one_bucket")
    for _ in range(100):
        h2.observe(3.0)
    assert h2.quantile(0.5) == pytest.approx(3.0)
    assert h2.quantile(0.999) == pytest.approx(3.0)
    # Empty histogram: 0.0, and the snapshot carries quantile p50/p99.
    assert reg.histogram("empty").quantile(0.5) == 0.0
    snap = {m["name"]: m for m in reg.snapshot()["metrics"]}
    assert snap["q_ms"]["p50"] == pytest.approx(2.0)
    assert snap["q_ms"]["p99"] == pytest.approx(12.0)


def test_histogram_quantile_edge_cases():
    """ISSUE 19 satellite: the degenerate shapes the busbw ledger folds
    over — a single observation, everything in one bucket, and an empty
    histogram — must all produce sane quantiles (the PERF.json p50/p99
    columns are built from exactly these)."""
    reg = MetricsRegistry(0)
    # Single observation: every quantile is that value (min == max
    # clamps both ends of the interpolation).
    h = reg.histogram("single")
    h.observe(7.25)
    for q in (0.0, 0.5, 0.99, 1.0):
        assert h.quantile(q) == pytest.approx(7.25)
    # All observations in one bucket: interpolation cannot escape it.
    h2 = reg.histogram("uniform")
    for _ in range(50):
        h2.observe(3.0)
    assert h2.quantile(0.01) == pytest.approx(3.0)
    assert h2.quantile(0.999) == pytest.approx(3.0)
    # Empty: quantiles are 0.0 at every q, no division by zero.
    h3 = reg.histogram("void")
    for q in (0.0, 0.5, 1.0):
        assert h3.quantile(q) == 0.0


def test_histogram_bucket_edges():
    reg = MetricsRegistry(0)
    h = reg.histogram("edges")
    h.observe(0.0)       # non-positive -> bucket 0
    h.observe(-3.0)
    h.observe(2.0 ** 50)  # beyond the top bound -> clamped to last bucket
    assert h.count == 3
    top = h.nonzero_buckets()[-1][0]
    assert top == bucket_upper_bound(63)


def test_registry_thread_safety_under_concurrent_workers():
    """The stream-worker scenario: N threads hammering one counter and
    one histogram concurrently must lose no updates."""
    reg = MetricsRegistry(0)
    c = reg.counter("hits_total")
    h = reg.histogram("lat_ms")
    n_threads, per_thread = 8, 5000

    def worker(k):
        for i in range(per_thread):
            c.inc()
            h.observe(float(i % 7) + 0.5)

    threads = [threading.Thread(target=worker, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * per_thread
    assert h.count == n_threads * per_thread
    assert sum(n for _, n in h.nonzero_buckets()) == h.count


def test_prometheus_exposition_golden_file():
    reg = MetricsRegistry(0)
    reg.counter("horovod_autoscale_decisions_total", "Autoscale decisions",
                labels={"direction": "up"}).inc()
    h_catch = reg.histogram("horovod_catch_up_ms",
                            "Joiner bulk catch-up wall time")
    h_catch.observe(850.0)
    reg.counter("horovod_statesync_bytes_total", "State bytes streamed",
                labels={"role": "donor"}).inc(4096)
    reg.counter("horovod_statesync_bytes_total",
                labels={"role": "joiner"}).inc(4096)
    reg.gauge("horovod_world_size", "Live world size").set(4)
    # Rendezvous control plane (ISSUE 15): per-replica role, promotion
    # counter, and the per-peer wire proto gauge of the HELLO handshake.
    reg.gauge("horovod_rendezvous_role",
              "1 while this replica is the rendezvous primary, 0 as "
              "standby", labels={"replica": "0"}).set(1)
    reg.gauge("horovod_rendezvous_role",
              labels={"replica": "1"}).set(0)
    reg.counter("horovod_rendezvous_failovers_total",
                "Leader promotions this replica performed").inc()
    reg.gauge("horovod_wire_proto_version",
              "Wire protocol version the peer advertised at channel "
              "establishment",
              labels={"mesh": "ctrl0", "peer": "1"}).set(2)
    for state, n in (("free", 24), ("active", 6), ("cached", 2)):
        reg.gauge("horovod_serve_kv_blocks", "Paged KV blocks by state",
                  labels={"state": state}).set(n)
    reg.counter("horovod_serve_prefix_hits_total",
                "Prompt blocks served from the prefix cache").inc(5)
    reg.counter("horovod_serve_prefix_misses_total",
                "Prompt blocks prefilled fresh").inc(3)
    reg.counter("horovod_serve_prefill_stream_bytes_total",
                "KV bytes streamed prefill->decode",
                labels={"role": "sent"}).inc(8192)
    # Core-dispatch collective metrics (ISSUE 18): the latency histogram
    # carries the algo label and the per-algorithm verdict counter rides
    # next to it.
    reg.histogram("horovod_collective_latency_ms",
                  "End-to-end latency of one executed response, by data "
                  "plane, op, wire codec and collective algorithm",
                  labels={"plane": "tcp", "op": "allreduce",
                          "codec": "none", "algo": "tree"}).observe(2.0)
    reg.counter("horovod_collective_algo_total",
                "Executed responses by collective algorithm (ring / tree "
                "/ rhd / torus / hierarchical / ... — the per-size "
                "selection verdict)", labels={"algo": "tree"}).inc(1)
    # perfscope roofline metrics (ISSUE 19): the busbw histogram with
    # the size-bucket axis, the self-calibrated peak gauge, and the
    # efficiency/MFU gauges the PERF.json ledger merges.
    reg.histogram("horovod_collective_busbw_mbps",
                  "Bus bandwidth of one executed collective (MB/s, "
                  "nccl-tests convention)",
                  labels={"plane": "tcp", "op": "allreduce",
                          "codec": "none", "algo": "ring",
                          "size_bucket": "1MiB"}).observe(260.0)
    reg.gauge("horovod_collective_busbw_peak_mbps",
              "Best demonstrated bus bandwidth — the self-calibrated "
              "roofline").set(314.6)
    reg.gauge("horovod_collective_efficiency",
              "Latest bus bandwidth over the roofline",
              labels={"plane": "tcp", "algo": "ring",
                      "size_bucket": "1MiB"}).set(0.83)
    reg.gauge("horovod_train_mfu",
              "Model-FLOPs utilization of the last train step").set(0.41)
    reg.counter("hvd_test_bytes_total", "Bytes moved",
                labels={"peer": "1"}).inc(2048)
    reg.counter("hvd_test_bytes_total", labels={"peer": "2"}).inc(1024)
    reg.gauge("hvd_test_depth", "Queue depth").set(7)
    h = reg.histogram("hvd_test_latency_ms", "Latency")
    for v in (0.5, 1.5, 3.0, 12.0):
        h.observe(v)
    with open(os.path.join(FIXTURES, "exposition.prom")) as f:
        golden = f.read()
    assert reg.render_prometheus() == golden


def test_null_registry_is_inert():
    assert NULL_REGISTRY.enabled is False
    assert NULL_REGISTRY.counter("x") is NULL_METRIC
    assert NULL_REGISTRY.histogram("y") is NULL_METRIC
    NULL_METRIC.inc(5)
    NULL_METRIC.observe(1.0)
    NULL_METRIC.set(2.0)
    assert NULL_METRIC.value == 0.0
    assert NULL_REGISTRY.snapshot()["metrics"] == []
    assert NULL_REGISTRY.render_prometheus() == ""


# --- straggler aggregation --------------------------------------------------
def test_straggler_window_names_slowest_rank():
    reg = MetricsRegistry(0)
    agg = StragglerAggregator(4, reg, window=4, threshold_ms=5.0)
    t0 = 1000.0
    for _ in range(4):
        agg.observe_tensor({0: t0, 1: t0 + 0.001, 2: t0 + 0.002,
                            3: t0 + 0.050})
        t0 += 1.0
    assert agg.windows_completed == 1
    assert agg.last_straggler == 3
    assert 45.0 < agg.last_skew_ms < 55.0
    assert reg.gauge("horovod_controller_straggler_rank").value == 3.0
    assert reg.gauge("horovod_controller_straggler_lag_ms").value > 45.0
    assert reg.counter(
        "horovod_controller_straggler_windows_total").value == 1
    p99 = reg.gauge("horovod_controller_negotiation_lag_ms",
                    labels={"stat": "p99"}).value
    assert 45.0 < p99 < 55.0


def test_one_long_stall_does_not_outweigh_a_persistent_straggler():
    """Rank 3 arrives 40 ms late at every tensor of the window; rank 1
    is stalled 200 ms at one of them, as a loaded host stalls a thread.
    Rank 3 is named, by its 40 ms (a mean over the window would name
    rank 1, at 50 ms)."""
    reg = MetricsRegistry(0)
    agg = StragglerAggregator(4, reg, window=4, threshold_ms=5.0)
    t0 = 1000.0
    for stalled in (0.0, 0.2, 0.0, 0.0):
        agg.observe_tensor({0: t0, 1: t0 + 0.001 + stalled,
                            2: t0 + 0.002, 3: t0 + 0.040})
        t0 += 1.0
    assert agg.last_straggler == 3
    assert 38.0 < agg.last_skew_ms < 42.0
    assert reg.gauge("horovod_controller_straggler_rank").value == 3.0


def test_straggler_below_threshold_clears_gauge():
    reg = MetricsRegistry(0)
    agg = StragglerAggregator(2, reg, window=2, threshold_ms=5.0)
    for _ in range(2):
        agg.observe_tensor({0: 1.0, 1: 1.0 + 0.0005})   # 0.5 ms skew
    assert agg.windows_completed == 1
    assert reg.gauge("horovod_controller_straggler_rank").value == -1.0
    assert reg.counter(
        "horovod_controller_straggler_windows_total").value == 0


def test_straggler_snapshot_gauges():
    reg = MetricsRegistry(0)
    agg = StragglerAggregator(2, reg, window=8)
    gathered = [
        RequestList(tm_cycles=10, tm_cycle_ms=25.0, tm_sync_wait_ms=5.0,
                    tm_queue_depth=3),
        RequestList(tm_cycles=5, tm_cycle_ms=50.0, tm_sync_wait_ms=0.5,
                    tm_queue_depth=0),
    ]
    agg.observe_snapshots(gathered)
    assert reg.gauge("horovod_rank_cycle_ms",
                     labels={"rank": "0"}).value == pytest.approx(2.5)
    assert reg.gauge("horovod_rank_cycle_ms",
                     labels={"rank": "1"}).value == pytest.approx(10.0)
    assert reg.gauge("horovod_rank_sync_wait_ms",
                     labels={"rank": "1"}).value == pytest.approx(0.1)
    assert reg.gauge("horovod_rank_queue_depth",
                     labels={"rank": "0"}).value == 3.0


# --- wire snapshot ----------------------------------------------------------
def test_request_list_tm_fields_roundtrip():
    rl = RequestList(tm_cycles=17, tm_cycle_ms=42.5,
                     tm_sync_wait_ms=3.25, tm_queue_depth=9)
    decoded = RequestList.from_bytes(rl.to_bytes())
    assert decoded.tm_cycles == 17
    assert decoded.tm_cycle_ms == 42.5
    assert decoded.tm_sync_wait_ms == 3.25
    assert decoded.tm_queue_depth == 9
    # Defaults stay zero (metrics off ships an all-zero snapshot).
    empty = RequestList.from_bytes(RequestList().to_bytes())
    assert (empty.tm_cycles, empty.tm_cycle_ms,
            empty.tm_sync_wait_ms, empty.tm_queue_depth) == (0, 0.0, 0.0, 0)


# --- exporter + dump + report ----------------------------------------------
def test_exporter_scrape_and_close():
    from horovod_tpu.runner.network import free_port
    reg = MetricsRegistry(0)
    reg.counter("x_total", "help").inc(3)
    exp = MetricsExporter(reg, rank=0, base_port=free_port())
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{exp.port}/metrics", timeout=10
        ).read().decode()
        assert "x_total 3" in body
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{exp.port}/nope", timeout=10)
    finally:
        exp.close()


def test_exporter_port_conflict_falls_back_to_ephemeral():
    reg = MetricsRegistry(0)
    a = MetricsExporter(reg, rank=0, base_port=0)   # ephemeral
    try:
        b = MetricsExporter(reg, rank=0, base_port=a.port)  # busy -> fallback
        try:
            assert b.port != a.port and b.port > 0
        finally:
            b.close()
    finally:
        a.close()


def test_resolve_dump_path():
    assert resolve_dump_path("/tmp/m_{rank}.json", 3) == "/tmp/m_3.json"
    assert resolve_dump_path("/tmp/m.json", 2) == "/tmp/m.r2.json"
    assert resolve_dump_path("/tmp/m", 1) == "/tmp/m.r1"


def test_dump_json_and_report_cli(tmp_path):
    reg = MetricsRegistry(1)
    reg.counter("bytes_total", labels={"peer": "0"}).inc(100)
    reg.histogram("lat_ms").observe(2.0)
    path = dump_json(reg, str(tmp_path / "m.json"), 1)
    assert path.endswith("m.r1.json")
    out = summarize_file(path)
    assert "bytes_total" in out and "lat_ms" in out
    # Dump payload carries full histogram detail.
    snap = json.loads((tmp_path / "m.r1.json").read_text())
    hist = next(m for m in snap["metrics"] if m["name"] == "lat_ms")
    assert hist["count"] == 1 and hist["buckets"] == [[2.0, 1]]


def test_report_summarizes_timeline_spans():
    events = [
        {"ph": "B", "name": "ALLREDUCE", "ts": 0, "pid": 0, "tid": 0},
        {"ph": "B", "name": "TCP_RING_ALLREDUCE", "ts": 100, "pid": 0,
         "tid": 0},
        {"ph": "E", "name": "", "ts": 4100, "pid": 0, "tid": 0},
        {"ph": "E", "name": "", "ts": 5000, "pid": 0, "tid": 0},
        {"ph": "C", "name": "tensor_queue_depth", "ts": 5000, "pid": 0,
         "args": {"depth": 2}},
    ]
    out = summarize_timeline(events)
    assert "ALLREDUCE" in out and "TCP_RING_ALLREDUCE" in out
    assert "5.00" in out      # ALLREDUCE total 5 ms
    assert "4.00" in out      # nested ring span 4 ms
    assert "tensor_queue_depth" in out


def test_report_summarizes_empty_dump():
    out = summarize_dump({"rank": 0, "metrics": []})
    assert "HOROVOD_METRICS=on" in out


# --- timeline counter events + batched writer -------------------------------
def test_timeline_counter_events_and_batched_writer(tmp_path):
    path = tmp_path / "tl.json"
    tl = Timeline(str(path))
    # Well past the write batch size: the writer must batch without
    # losing events, and stop() must drain everything (unbounded join).
    for i in range(200):
        tl.activity_start(f"t{i % 5}", "OP")
        tl.activity_end(f"t{i % 5}")
    tl.counter("tensor_queue_depth", {"depth": 3})
    tl.counter("wire_bytes", {"sent": 10, "received": 20})
    tl.stop()
    events = json.loads(path.read_text())
    assert sum(1 for e in events if e.get("ph") == "B") == 200
    assert sum(1 for e in events if e.get("ph") == "E") == 200
    counters = [e for e in events if e.get("ph") == "C"]
    assert len(counters) == 2
    assert counters[0]["args"] == {"depth": 3}
    assert counters[1]["args"] == {"sent": 10, "received": 20}
    assert all("ts" in e for e in counters)


# --- HOROVOD_METRICS=off no-op contract -------------------------------------
def test_metrics_off_world_is_noop(monkeypatch):
    """With the knob off: Null registry, no exporter thread, no metrics
    anywhere — the thread census is exactly the no-telemetry baseline."""
    monkeypatch.delenv("HOROVOD_METRICS", raising=False)
    monkeypatch.delenv("HOROVOD_METRICS_PORT", raising=False)
    monkeypatch.delenv("HOROVOD_METRICS_FILE", raising=False)
    import horovod_tpu as hvd
    from horovod_tpu import core

    from census import assert_no_new_threads, assert_thread_absent, \
        thread_names
    before = thread_names()
    hvd.init()
    try:
        st = core.global_state()
        assert st.telemetry is NULL_REGISTRY
        out = hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum,
                            name="tm_off")
        np.testing.assert_allclose(out, np.ones(4))
        assert_thread_absent("hvd-metrics")
        # Only the background loop was added to the census.
        assert_no_new_threads(before, allow={"hvd-background"},
                              context="metrics-off world")
        assert st.telemetry.snapshot()["metrics"] == []
    finally:
        hvd.shutdown()


def test_metrics_on_world_records(monkeypatch):
    monkeypatch.setenv("HOROVOD_METRICS", "on")
    monkeypatch.delenv("HOROVOD_METRICS_PORT", raising=False)
    import horovod_tpu as hvd
    from horovod_tpu import core, telemetry

    hvd.init()
    try:
        st = core.global_state()
        assert st.telemetry.enabled
        for i in range(3):
            hvd.allreduce(np.ones(8, np.float32), op=hvd.Sum,
                          name="tm_on")
        names = {m["name"] for m in st.telemetry.snapshot()["metrics"]}
        assert "horovod_controller_cycle_ms" in names
        assert "horovod_collective_latency_ms" in names
        assert "horovod_controller_cache_hit_total" in names
        summ = telemetry.summary()
        assert summ["cache_hit_rate"] > 0.0
        assert "stream_busy_ms" in summ
    finally:
        hvd.shutdown()


# --- spans on the profiler's clock (ISSUE 26) -------------------------------
def test_step_parts_time_every_part_and_the_remainder():
    import time

    from horovod_tpu.telemetry.spans import StepParts

    parts = StepParts("serve", step=7)
    with parts("a"):
        time.sleep(0.01)
    for _ in range(2):                 # a part met twice adds up
        with parts("b", rid=1):
            time.sleep(0.005)
    time.sleep(0.002)                  # nobody's: the remainder
    assert parts.elapsed() >= 0.022
    seconds = parts.close(admits=0)
    assert list(seconds) == ["a", "b", "other", "total"]
    assert seconds["a"] >= 0.01 and seconds["b"] >= 0.01
    assert seconds["other"] >= 0.002
    assert seconds["a"] + seconds["b"] + seconds["other"] \
        == pytest.approx(seconds["total"], abs=1e-12)


def test_a_part_inside_another_is_counted_once():
    """A part opened inside another (the settle inside an admission)
    leaves the enclosing part's slot, so the parts and ``other`` still
    sum to the step; the enclosing timer's own seconds keep it."""
    import time

    from horovod_tpu.telemetry.spans import StepParts

    parts = StepParts("serve", step=3)
    admit = parts("admit", rid=1)
    with admit:
        time.sleep(0.004)
        inner = parts("token_fetch")
        with inner:
            time.sleep(0.006)
        time.sleep(0.002)
    outer = parts("token_fetch")
    with outer:                        # the same part, at the top level
        time.sleep(0.003)
    seconds = parts.close(admits=1)
    assert set(seconds) == {"admit", "token_fetch", "other", "total"}
    assert seconds["admit"] == pytest.approx(admit.seconds - inner.seconds,
                                             abs=1e-12)
    assert seconds["admit"] >= 0.006 and admit.seconds >= 0.012
    assert seconds["token_fetch"] == pytest.approx(
        inner.seconds + outer.seconds, abs=1e-12)
    assert seconds["other"] >= 0
    assert seconds["admit"] + seconds["token_fetch"] + seconds["other"] \
        == pytest.approx(seconds["total"], abs=1e-12)


def test_profiler_annotation_is_the_span_helper():
    import horovod_tpu as hvd
    from horovod_tpu.telemetry.spans import span

    assert type(hvd.profiler_annotation("x", k=1)) is type(span("x"))


def _session_spans(path):
    """name -> [(start, end)] of the hvd.* events of a session file."""
    from jax.profiler import ProfileData
    found: dict = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("hvd."):
                    found.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    return found


def test_report_reads_a_profiler_session(tmp_path):
    """telemetry.report on a CPU session's .xplane.pb prints every span
    with its count, by kind of step, and a self time of the step that is
    its duration less its children's."""
    import glob
    import time

    import jax

    from horovod_tpu.telemetry.spans import StepParts, span

    jax.profiler.start_trace(str(tmp_path))
    for step in range(4):
        parts = StepParts("serve", step=step)
        with parts("plan_exchange"):
            time.sleep(0.002)
        if step == 0:
            with parts("admit", rid=step, bucket=8):
                with span("serve.cache_insert"):
                    time.sleep(0.003)
        with parts("token_fetch"):
            time.sleep(0.004)
        time.sleep(0.001)
        parts.close(admits=int(step == 0), decoded=2)
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    text = summarize_file(path)
    assert "(profiler session)" in text
    tables = text.split("spans in ")[1:]
    assert [t.splitlines()[0] for t in tables] == [
        "hvd.serve.step [admits = 0]", "hvd.serve.step [admits > 0]"]
    rows = [{line.split()[0]: line.split() for line in t.splitlines()
             if line.startswith("hvd.")} for t in tables]
    assert list(rows[0]) == ["hvd.serve.step", "hvd.serve.plan_exchange",
                             "hvd.serve.token_fetch"]
    assert list(rows[1]) == ["hvd.serve.step", "hvd.serve.plan_exchange",
                             "hvd.serve.admit", "hvd.serve.cache_insert",
                             "hvd.serve.token_fetch"]
    assert [rows[0][name][1] for name in rows[0]] == ["3", "3", "3"]
    assert [rows[1][name][1] for name in rows[1]] == ["1"] * 5
    # The admit step's self time: its duration less its three children
    # (cache_insert is admit's child, not the step's).
    spans = _session_spans(path)
    step0 = spans["hvd.serve.step"][0]
    children = [spans["hvd.serve." + n][0]
                for n in ("plan_exchange", "admit", "token_fetch")]
    own = (step0[1] - step0[0]) - sum(e - s for s, e in children)
    header = tables[1].splitlines()[1].split()
    assert float(rows[1]["hvd.serve.step"][header.index("self_p50_ms")]) \
        == pytest.approx(own / 1e6, abs=0.001)
    assert own / 1e6 >= 1.0            # the sleep that is nobody's
    admit = rows[1]["hvd.serve.admit"]
    assert float(admit[header.index("self_p50_ms")]) < 1.0 \
        < float(admit[header.index("p50_ms")])


def test_report_names_idle_gaps_by_the_innermost_span():
    """A device's idle gaps go to the innermost span that holds their
    middle, a gap between two steps to nobody."""
    from horovod_tpu.telemetry.report import _nest, idle_gaps

    ms = 1_000_000
    spans = _nest([(0, 100 * ms, "hvd.serve.step", {"admits": 0}),
                   (10 * ms, 30 * ms, "hvd.serve.plan_exchange", {}),
                   (40 * ms, 90 * ms, "hvd.serve.token_fetch", {}),
                   (200 * ms, 300 * ms, "hvd.serve.step", {"admits": 0})])
    ops = [(5 * ms, 20 * ms), (35 * ms, 50 * ms), (50 * ms, 60 * ms),
           (95 * ms, 96 * ms), (150 * ms, 160 * ms), (215 * ms, 290 * ms)]
    text = idle_gaps(ops, spans)
    assert "busy 0.1260 s of the 0.2850 s" in text
    assert "idle 55.79%" in text
    by_span, longest = text.split("longest idle gaps")
    rows = {line.split()[0]: line.split()[1:]
            for line in by_span.splitlines()[3:] if line.strip()}
    assert rows == {"outside_hvd_spans": ["2", "109.0", "54.500"],
                    "hvd.serve.token_fetch": ["1", "35.0", "35.000"],
                    "hvd.serve.plan_exchange": ["1", "15.0", "15.000"]}
    assert [line.split() for line in longest.splitlines()[2:]] == [
        ["outside_hvd_spans", "55.000"], ["outside_hvd_spans", "54.000"],
        ["hvd.serve.token_fetch", "35.000"],
        ["hvd.serve.plan_exchange", "15.000"]]
