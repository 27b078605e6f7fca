"""The chip benchmark's own tests: CPU only, one process, under a minute.

    python -m pytest benchmarks/chip/test_chip_benchmark.py -q

They hold the manifest to the contract, the data files to the manifest,
and the yardstick (token accounting, gap tail, operation counts, trace
reduction) to hand-made inputs; and they walk each driver's control flow
through ``--rehearse-cpu``, and each configuration against the plain
reference it names, at toy sizes.  The cells are read from
``BENCHMARK.json`` as the module is imported, so a cell added later is
rehearsed with no edit here, and one case adds a configuration of
another architecture in a copy of the tree without touching a file of the
original (``addition_example/``).  No test starts a child that opens JAX
and none describes a TPU topology.  A rehearsal proves nothing about the
chip: its line is stamped ``cpu`` and ``rehearsal``.
"""
from __future__ import annotations

import filecmp
import json
import os
import re
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for path in (HERE, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)

# The first test to open JAX fixes the number of virtual CPU devices for
# the whole process: enough for the widest cell's rehearsal.
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4").strip()

import counts  # noqa: E402
import run as harness  # noqa: E402
import serve  # noqa: E402
import tracing  # noqa: E402
from tracing import Span, Timeline  # noqa: E402

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# Read as the module is imported: a cell added later is rehearsed too.
CELLS = [w["name"] for w in
         harness.load_json(ROOT, "BENCHMARK.json")["workloads"]]


@pytest.fixture(scope="module")
def manifest() -> dict:
    return harness.load_json(ROOT, "BENCHMARK.json")


# The files are found where the harness finds them (``harness.ROOT`` and
# ``harness.HERE``), which the addition test points at a copy.
def here(*parts: str) -> str:
    return os.path.join(harness.HERE, *parts)


def cells_of(metric: dict, manifest: dict) -> list[str]:
    return metric.get("workloads",
                      [w["name"] for w in manifest["workloads"]])


# ------------------------------------------------------------- the manifest
def test_manifest_keeps_the_contract(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["benchmarks/chip"]
    assert manifest["command"] == ["python3", "benchmarks/chip/run.py"]
    seconds, cells = manifest["run_seconds"], 24
    assert isinstance(seconds, int) and 1 <= seconds <= 51
    assert (2 + 14 * cells) * (seconds + 60) + cells * 2 * 90 + 1200 \
        <= 43200
    names = [e["name"] for kind in ("configs", "workloads", "end_to_end",
                                    "per_layer") for e in manifest[kind]]
    assert all(NAME.fullmatch(n) for n in names)
    for kind in ("configs", "workloads"):
        kinds = [e["name"] for e in manifest[kind]]
        assert len(kinds) == len(set(kinds))
    metrics = [m["name"] for m in manifest["end_to_end"]
               + manifest["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for cfg in manifest["configs"]:
        assert set(cfg) == {"name", "source", "file", "reduced", "why"}
        assert cfg["file"].startswith("benchmarks/chip/configs/")
        assert all(NAME.fullmatch(k) for k in cfg["reduced"])
        assert any(w["config"] == cfg["name"]
                   for w in manifest["workloads"])
    for cell in manifest["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
        assert NAME.fullmatch(cell["traffic"])
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)
    for metric in manifest["end_to_end"]:
        assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                               "bound", "source"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    for metric in manifest["per_layer"]:
        assert set(metric) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}
        assert metric["source"] in SOURCES
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.fullmatch(metric["unit"])
        assert metric["better"] in ("lower", "higher")
    setup = next(m for m in manifest["end_to_end"]
                 if m["name"] == "setup_s")
    assert "workloads" not in setup and setup["bound"] <= 0.1
    assert len(json.dumps(manifest)) < 64 * 1024


def test_names_the_issue_fixed(manifest):
    """A floor and no fence: additions pass, a removal or a rename fails."""
    assert [w["name"] for w in manifest["workloads"]][:2] == [
        "lm7b_serve_chat_sat", "lm7b_train_s4k_dp4"]
    assert {c["name"] for c in manifest["configs"]} >= {
        "deepseek-llm-7b.train", "deepseek-llm-7b.serve"}
    assert {m["name"] for m in manifest["end_to_end"]} >= {
        "train_items_per_s_per_chip", "scaling_efficiency",
        "serve_total_tokens_per_s", "serve_itl_ms_p95", "setup_s"}
    assert {m["name"] for m in manifest["per_layer"]} >= {
        "trainer.mfu", "trainer.step_device_ms",
        "grad_sync.step_overhead_ms", "grad_sync.collective_device_ms",
        "batcher.slot_occupancy", "replica.decode_step_ms_p50",
        "replica.decode_device_ms_per_step", "kernels.decode_roofline",
        # PR 28: the program's spans and counters, and the step's mfu.
        "replica.plan_exchange_ms_p50", "replica.token_fetch_ms_p50",
        "replica.completion_exchange_ms_p50", "replica.slot_update_ms_p50",
        "batcher.assemble_ms_p50", "replica.cache_insert_ms_p50",
        "kernels.flash_device_ms_per_step", "replica.cache_aliased_share",
        "replica.step_mfu"}


def test_every_cell_finds_its_files_and_reports_enough(manifest):
    for cell in manifest["workloads"]:
        entry = next(c for c in manifest["configs"]
                     if c["name"] == cell["config"])
        config = harness.load_json(harness.ROOT, entry["file"])
        traffic = harness.load_json(here("traffic",
                                         cell["traffic"] + ".json"))
        assert config["name"] == cell["config"]
        assert traffic["name"] == cell["traffic"]
        assert config["reduced"] == entry["reduced"]
        assert config["source"].startswith(entry["source"])
        assert os.path.exists(here(config["driver"] + ".py"))
        # Every cell is rehearsed and checked against the reference its
        # configuration names: neither has a default.
        assert {"assumed", "deployment", "rehearsal", "reference"} \
            <= set(config)
        assert "rehearsal" in traffic
        for kind in ("end_to_end", "per_layer"):
            reported = [m["name"] for m in manifest[kind]
                        if cell["name"] in cells_of(m, manifest)]
            assert len(reported) >= (2 if kind == "end_to_end" else 1)


def test_layer_metrics_are_data_and_move_what_their_cells_report(manifest):
    end_to_end = {m["name"]: m for m in manifest["end_to_end"]}
    for metric in manifest["per_layer"]:
        spec = harness.load_json(here("layer_metrics",
                                      metric["name"] + ".json"))
        for key in ("name", "layer", "unit", "moves"):
            assert spec[key] == metric[key], (metric["name"], key)
        assert spec["reader"]["kind"] in tracing._KINDS
        moved = end_to_end[metric["moves"]]
        assert set(cells_of(metric, manifest)) \
            <= set(cells_of(moved, manifest)), metric["name"]
    on_disk = {f[:-5] for f in os.listdir(here("layer_metrics"))}
    assert on_disk == {m["name"] for m in manifest["per_layer"]}


# ------------------------------------------------------------- the traffic
def quantiles(low: int, high: int, points: int) -> list[int]:
    return [round(low * (high / low) ** ((i + 0.5) / points))
            for i in range(points)]


def test_chat_sat_tables_are_the_laws_quantiles():
    table = harness.load_json(HERE, "traffic", "chat_sat.json")["requests"]
    assert sorted(p for p, _ in table) == quantiles(32, 1024, 32)
    assert sorted(o for _, o in table) == quantiles(256, 1024, 32)
    assert 285 < sum(p for p, _ in table) / 32 < 287
    assert 553 < sum(o for _, o in table) / 32 < 555
    # Every prompt pads to a bucket the configuration warms.
    buckets = harness.load_json(
        HERE, "configs", "deepseek-llm-7b.serve.json"
    )["serve"]["warmup_buckets"]
    assert {max(8, 1 << (p - 1).bit_length()) for p, _ in table} \
        == set(buckets)


class FakeQueue:
    def __init__(self) -> None:
        self.seen: list[tuple[list, int]] = []

    def submit(self, tokens, new_tokens, slo_ms):
        self.seen.append((list(tokens), new_tokens))
        return len(self.seen) - 1


class FakeExecutor:
    def __init__(self) -> None:
        self.queue, self.stats = FakeQueue(), {"offered": 0}


class FakeRun:
    def __init__(self, seed: int) -> None:
        self.seed = seed
        self.traffic = harness.load_json(HERE, "traffic", "chat_sat.json")
        self.config = {"vocab_size": 102400}


def submitted(seed: int, count: int = 40) -> list[tuple[list, int]]:
    loop = serve.ClosedLoop(FakeRun(seed), FakeExecutor(), 1e6)
    for i in range(count):
        loop.submit(i % loop.clients)
    return loop.executor.queue.seen


def test_the_seed_makes_token_ids_and_nothing_else():
    a, b = submitted(101), submitted(2_000_000_102)
    assert [(len(t), n) for t, n in a] == [(len(t), n) for t, n in b]
    assert all(ta != tb for (ta, _), (tb, _) in zip(a, b))
    assert a == submitted(101)
    table = FakeRun(0).traffic["requests"]
    # Client i's first output is cut to (i + 1)/16; later ones are whole.
    assert [n for _, n in a[:16]] == [
        -(-table[i][1] * (i + 1) // 16) for i in range(16)]
    assert [n for _, n in a[16:40]] == [table[i % 32][1]
                                        for i in range(16, 40)]
    assert all(2 <= t < 102400 for toks, _ in a for t in toks)


# ----------------------------------------------------------- the accounting
def test_token_accounting_and_gap_tail_on_a_hand_made_log():
    step = serve.Step
    log = [step(10.00, 0, 0, 0, 2, 0),      # window opens at this boundary
           step(10.03, 0, 3, 3, 3, 0),      # plain: 3 streams, 30 ms
           step(10.13, 100, 4, 2, 4, 1),    # admits 100: 2 new + 2 old
           step(10.16, 0, 4, 4, 4, 0),
           step(10.20, 0, 4, 4, 3, 0),      # closes here
           step(10.23, 0, 3, 3, 3, 0)]      # outside
    seen = serve.account(log, 10.00, 10.20)
    assert seen["steps"] == 4 and seen["admit_steps"] == 1
    assert seen["prompt_tokens"] == 100 and seen["output_tokens"] == 15
    assert seen["total_tokens_per_s"] == pytest.approx(115 / 0.20)
    assert seen["gap_samples"] == 13
    # 13 gaps: 3 of 30 ms, 2 of 100 ms, 4 of 30 ms, 4 of 40 ms; the
    # nearest rank of 95% is the 13th, of 50% the 7th.
    assert seen["itl_ms_p95"] == pytest.approx(100.0)
    assert seen["itl_ms_p50"] == pytest.approx(30.0)
    assert seen["plain_step_ms_p50"] == pytest.approx(30.0)
    assert seen["occupied_slot_steps"] == 14
    assert serve.weighted_percentile([(1.0, 95), (9.0, 5)], 0.95) == 1.0
    assert serve.weighted_percentile([(1.0, 94), (9.0, 6)], 0.95) == 9.0


# --------------------------------------------------------------- the counts
def test_operation_and_byte_counts():
    lm = harness.load_json(HERE, "configs", "deepseek-llm-7b.train.json")
    assert counts.transformer_train_flops(lm, {"seq_len": 4096}) \
        == 4_259_315_712
    served = harness.load_json(HERE, "configs", "deepseek-llm-7b.serve.json")
    layer = 4 * 4096 * 4096 + 3 * 4096 * 11008
    weights = 4 * layer + 4096 * 102400
    assert counts.transformer_decode_bytes(served, [100, 300]) == \
        (weights + 2 * 4096) * 2 + 400 * 2 * 4 * 4096 * 2
    # The widths are the configuration's types, and an unknown one fails.
    wide = {**served, "model": {"args": {"param_dtype": "@jax.numpy:float32",
                                         "dtype": "@jax.numpy:bfloat16"}}}
    assert counts.transformer_decode_bytes(wide, [100, 300]) == \
        (weights + 2 * 4096) * 4 + 400 * 2 * 4 * 4096 * 2
    with pytest.raises(KeyError):
        counts.dtype_bytes({"model": {"args": {"dtype": "@x:int4"}}},
                           "dtype")
    assert counts.transformer_decode_flops(served, [100, 300]) == \
        2 * weights * 2 + 4 * 4096 * 4 * 400
    assert counts.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("cpu")


# ------------------------------------------------------ the trace reduction
def hand_made_timeline() -> tuple[Timeline, dict]:
    # Two steps dispatched back to back at the chunk's start: where their
    # modules run on the device has nothing to do with the dispatch spans.
    spans = [Span("bench.window", 0.0, 10.0),
             Span("bench.train.reported", 0.6, 9.4),
             Span("bench.train.chunk", 1.0, 9.0),
             Span("bench.train.step", 1.0, 1.2),
             Span("bench.train.step", 1.2, 1.4)]
    ops = [Span("fusion.1", 1.0, 3.0, "fusion"),
           Span("fusion.2", 2.0, 4.0, "fusion"),
           Span("psum.7", 4.0, 5.0, "all-reduce"),
           Span("all-reduce-start.3", 5.0, 5.1, "all-reduce-start"),
           Span("all-reduce-done.3", 6.9, 7.0, "all-reduce-done"),
           Span("fusion.1", 8.0, 9.0, "fusion")]
    lines = {"XLA Ops": ops,
             "Async XLA Ops": [Span("all-reduce-start.3", 5.0, 7.0,
                                    "all-reduce-start")],
             "XLA Modules": [Span("jit_local_step", -2.0, -1.0),  # warm-up
                             Span("jit_local_step", 1.0, 4.5),
                             Span("jit_local_step", 4.5, 7.5),
                             Span("jit_other", 8.0, 9.0)]}
    labels = {"bench.train.reported": [None],
              "bench.train.chunk": ["mesh"],
              "bench.train.step": ["mesh", "mesh"]}
    return Timeline(spans, {0: lines, 1: {"XLA Ops": ops[:1]}}), labels


def test_trace_reduction_on_a_hand_made_trace():
    assert tracing.union([(1, 3), (2, 4), (6, 7), (7, 8)]) \
        == [(1, 4), (6, 8)]
    assert tracing.short_name(
        "%fusion.12 = (f32[8,128]{1,0:T(8,128)S(1)}, f32[8]{0}) fusion("
        "f32[8]{0} %p.1), kind=kLoop, calls=%fused_computation.3") \
        == ("fusion.12", "fusion")
    assert tracing.short_name(
        "%psum.181 = bf16[52428800]{0:T(1024)(128)(2,1)} all-reduce("
        "%convert.5), channel_id=1") == ("psum.181", "all-reduce")
    assert tracing.short_name("jit_local_step(1609426033)") \
        == ("jit_local_step", "")
    tl, labels = hand_made_timeline()
    # Device 0 is busy 1-5, 5-5.1, 6.9-7 and 8-9; device 1 only 1-3.
    busy, window = tracing.device_busy(tl, [[0, 1]])
    assert window == pytest.approx(10.0)
    assert busy == pytest.approx((5.2 + 2.0) / 2)
    seen = tracing.breakdown(tl, 0)
    assert seen["device_ops"][0] == ["fusion.1_in_jit_local_step",
                                     pytest.approx(2.0)]
    assert ["fusion.1_in_jit_other", pytest.approx(1.0)] \
        in seen["device_ops"]
    assert all(len(name) <= 80 for name, _ in seen["device_ops"])
    assert seen["idle_gaps"][0] == ["bench.train.chunk",
                                    pytest.approx(1.8)]
    assert seen["idle_gaps"][-1][0] == "outside_bench_spans"
    facts = {"timeline": tl, "labels": labels, "counters": {"n": 4},
             "metrics": {}, "peaks": counts.peaks("TPU v5 lite")}
    spec = harness.load_json(HERE, "layer_metrics",
                             "grad_sync.collective_device_ms.json")
    # One synchronous all-reduce (1 s) and one start/done pair (2 s,
    # counted once) over the 2 steps of the mesh part: 1.5 s a step.
    assert tracing.evaluate(spec["reader"], facts) \
        == pytest.approx(1500.0)
    step_ms = harness.load_json(HERE, "layer_metrics",
                                "trainer.step_device_ms.json")
    # Both steps' modules, and not the warm-up's: median of 3.5 and 3 s.
    assert tracing.evaluate(step_ms["reader"], facts) \
        == pytest.approx(3250.0)
    assert tracing.evaluate(
        {"kind": "ratio", "num": {"kind": "counter", "key": "n"},
         "den": {"kind": "peak", "key": "hbm_bytes_per_s"}}, facts) \
        == pytest.approx(4 / 819e9)
    assert tracing.evaluate({"kind": "counter", "key": "absent"},
                            facts) is None
    with pytest.raises(ValueError):
        tracing.select_spans(tl, {"bench.train.step": ["mesh"]},
                             "train.step", "mesh")


def serve_timeline() -> tuple[Timeline, dict]:
    """Three serve steps, the second admitting, with the program's spans
    inside the benchmark's frames."""
    spans = [Span("bench.window", 0.0, 10.0),
             Span("bench.serve.step", 1.0, 2.0),
             Span("hvd.serve.step", 1.01, 1.99),
             Span("hvd.serve.token_fetch", 1.1, 1.5),
             Span("bench.serve.step", 2.0, 4.0),
             Span("hvd.serve.step", 2.01, 3.99),
             Span("hvd.serve.cache_insert", 2.1, 2.3),
             Span("hvd.serve.token_fetch", 2.5, 3.7),
             Span("bench.serve.step", 4.0, 5.0),
             Span("hvd.serve.step", 4.01, 4.99),
             Span("hvd.serve.token_fetch", 4.1, 4.7)]
    ops = [Span("fusion.1", 1.1, 1.4, "fusion"),
           Span("fusion.1", 2.5, 3.6, "fusion"),
           Span("fusion.1", 4.1, 4.3, "fusion")]
    labels = {"bench.serve.step": ["decode", "admit", "decode"]}
    return Timeline(spans, {0: {"XLA Ops": ops}}), labels


def test_span_stat_reads_a_program_span_within_the_labelled_steps():
    tl, labels = serve_timeline()
    facts = {"timeline": tl, "labels": labels, "counters": {},
             "metrics": {}, "peaks": {}}
    spec = harness.load_json(HERE, "layer_metrics",
                             "replica.token_fetch_ms_p50.json")
    # The decode steps' fetches, 0.4 and 0.6 s; the admit step's 1.2 s
    # is left out by ``within``.
    assert tracing.evaluate(spec["reader"], facts) == pytest.approx(500.0)
    everywhere = {**spec["reader"], "within": None}
    assert tracing.evaluate(everywhere, facts) == pytest.approx(600.0)
    insert = harness.load_json(HERE, "layer_metrics",
                               "replica.cache_insert_ms_p50.json")
    assert tracing.evaluate(insert["reader"], facts) == pytest.approx(200.0)
    # A program span is named in full and a benchmark span without its
    # prefix; a span that is not in the trace reads nothing, never 0.
    assert [s.name for s in tracing.select_spans(tl, labels, "serve.step",
                                                 "admit")] \
        == ["bench.serve.step"]
    assert len(tracing.select_spans(tl, labels, "hvd.serve.step")) == 3
    absent = {**spec["reader"], "span": "hvd.serve.no_such_part"}
    assert tracing.evaluate(absent, facts) is None
    # The driver labels its own calls only.
    with pytest.raises(ValueError):
        tracing.select_spans(tl, labels, "hvd.serve.step", "decode")


def test_an_idle_gap_is_named_by_the_innermost_program_span():
    tl, _ = serve_timeline()
    gaps = tracing.breakdown(tl, 0)["idle_gaps"]
    # 4.3 to 10: its middle lies in no span; 3.6 to 4.1: in no part of
    # the second step, so the step's own span names it; 1.4 to 2.5: its
    # middle, 1.95, lies in the first program step past its fetch.
    assert gaps[0] == ["outside_bench_spans", pytest.approx(5.7)]
    assert ["hvd.serve.step", pytest.approx(1.1)] in gaps
    assert ["hvd.serve.step", pytest.approx(0.5)] in gaps
    fetch = [Span("hvd.serve.token_fetch", 1.9, 1.98)]
    named = tracing.breakdown(Timeline(sorted(tl.spans + fetch,
                                              key=lambda s: s.start),
                                       tl.device), 0)["idle_gaps"]
    assert ["hvd.serve.token_fetch", pytest.approx(1.1)] in named


def test_the_loader_keeps_the_programs_spans(tmp_path):
    """One real profiler session on the CPU: ``hvd.*`` and ``bench.*``
    annotations come back, anything else is dropped."""
    import jax
    from horovod_tpu.telemetry import spans as program_spans

    tracer = tracing.Tracer(True)
    try:
        with tracer.window([0]):
            with tracer.span("serve.step", "decode"):
                with program_spans.span("serve.token_fetch", step=1):
                    with jax.profiler.TraceAnnotation("somebody.else"):
                        jax.block_until_ready(jax.numpy.ones(8) + 1)
        tl = tracing.load(tracer.files)
    finally:
        tracer.close()
    names = {s.name for s in tl.spans}
    assert names == {"bench.window", "bench.serve.step",
                     "hvd.serve.token_fetch"}
    step, = tracing.select_spans(tl, tracer.labels, "serve.step", "decode")
    fetch, = tracing.select_spans(tl, {}, "hvd.serve.token_fetch")
    assert step.start <= fetch.start and fetch.end <= step.end


class CountedRun:
    config = {"hidden_size": 2, "counts": {
        "decode_bytes_per_step": "counts:transformer_decode_bytes",
        "state_bytes_per_step": "counts:transformer_decode_flops",
        "flops_per_item": "counts:transformer_train_flops"}}

    def count(self, name):
        return harness.resolve(self.config["counts"][name])


def test_per_step_counts_and_stats_counters():
    count_of = serve.per_step_counts(CountedRun())
    assert set(count_of) == {"decode_bytes_per_step",
                             "state_bytes_per_step"}
    assert count_of["state_bytes_per_step"] \
        is counts.transformer_decode_flops
    stats = {"offered": 7, "cache_bytes": 4096, "cache_aliased_bytes": 4100,
             "mean_ms": 1.5, "latencies_ms": [1.0], "steps": {"admit": 1},
             "flag": True}
    seen = {"occupied_slot_steps": 6, "steps": 2}
    found = serve.counters(seen, 4, {"decode_bytes_per_step": [10, 20],
                                     "state_bytes_per_step": []}, stats)
    assert found == {"slot_occupancy_pct": 75.0,
                     "decode_bytes_per_step": 15.0,
                     "state_bytes_per_step": None,
                     "stats.offered": 7, "stats.cache_bytes": 4096,
                     "stats.cache_aliased_bytes": 4100,
                     "stats.mean_ms": 1.5}
    facts = {"timeline": Timeline([], {}), "labels": {}, "counters": found,
             "metrics": {}, "peaks": {}}
    share = harness.load_json(HERE, "layer_metrics",
                              "replica.cache_aliased_share.json")
    assert tracing.evaluate(share["reader"], facts) \
        == pytest.approx(100.0 * 4100 / 4096)
    # A count that no traced step fed leaves its metric out of the line.
    assert tracing.evaluate({"kind": "counter",
                             "key": "state_bytes_per_step"}, facts) is None


# ---------------------------------------------------------- the entry point
class FakeDevice:
    platform, device_kind = "tpu", "TPU v5 lite"

    def __init__(self, peak: int) -> None:
        self.peak = peak

    def memory_stats(self) -> dict:
        return {"peak_bytes_in_use": self.peak, "bytes_in_use": 1}


def test_memory_peak_is_the_allocators_on_the_fullest_chip():
    stamp = harness.device_stamp([FakeDevice(5), FakeDevice(9),
                                  FakeDevice(7)])
    assert stamp == {"platform": "tpu", "kind": "TPU v5 lite", "count": 3,
                     "memory_peak_bytes": 9}


def result_line(out: str) -> dict | None:
    lines = [ln for ln in out.splitlines() if ln.startswith("{")]
    return json.loads(lines[-1]) if lines else None


def test_without_a_tpu_there_is_no_result(capsys, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    code = harness.main(["--workload", "lm7b_serve_chat_sat",
                         "--seconds", "1"])
    captured = capsys.readouterr()
    assert code != 0 and result_line(captured.out) is None
    assert "no CPU fallback" in captured.err


def rehearse(*args: str) -> int:
    return harness.main([*args, "--seed", "2147483659", "--seconds", "1",
                         "--rehearse-cpu"])


def rehearsed(cell: str, trace: int, capsys, manifest: dict) -> dict:
    """One rehearsal of a cell, held to what a run's line owes."""
    code = rehearse("--workload", cell, "--trace", str(trace))
    captured = capsys.readouterr()
    line = result_line(captured.out)
    assert code == 0 and line["correct"] and line["failed"] == 0
    assert line["attempted"] > 0
    # Stamped, so that no reader takes it for a measurement.
    assert line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    # Each number compared beside its limit: the line's last key, and the
    # last lines of standard error.
    assert list(line)[-1] == "compared" and line["compared"]
    assert all(pair["value"] <= pair["limit"]
               for pair in line["compared"].values())
    assert captured.err.splitlines()[-1].startswith(
        "compared " + list(line["compared"])[-1])
    kind = "per_layer" if trace else "end_to_end"
    wanted = {m["name"] for m in manifest[kind]
              if cell in cells_of(m, manifest)}
    if trace:                     # a CPU has no device planes and no peak
        assert set(line["metrics"]) <= wanted and "breakdown" in line
        assert {"busy_s", "window_s"} <= set(line["device"])
        # What needs no device is read here too: the spans, the
        # program's among them, and the counters.
        assert {m["name"] for m in manifest["per_layer"]
                if m["name"] in wanted
                and m["source"] in ("program_span", "program_counter")} \
            <= set(line["metrics"])
    else:
        assert set(line["metrics"]) == wanted
        assert all(m["value"] > 0 for m in line["metrics"].values())
    return line


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_walks_the_driver(cell, trace, capsys, manifest):
    rehearsed(cell, trace, capsys, manifest)


def checked(cell: str, capsys, manifest: dict) -> dict:
    """``--check reference`` of a cell at toy sizes and one fixed seed:
    what its one line says."""
    code = harness.main(["--workload", cell, "--seed", "0",
                         "--rehearse-cpu", "--check", "reference"])
    line, = [ln for ln in capsys.readouterr().out.splitlines()
             if " check {" in ln]
    seen = json.loads(line[line.index("{"):])
    assert code == 0 and seen["ok"]
    entry = next(w for w in manifest["workloads"] if w["name"] == cell)
    assert seen["config"] == entry["config"]
    assert 0 < seen["error"] <= seen["tolerance"] <= 0.05
    return seen


@pytest.mark.parametrize("cell", CELLS)
def test_the_program_agrees_with_the_reference_its_configuration_names(
        cell, capsys, manifest):
    """On every PR: the program against its plain reference, through the
    function the configuration's file names."""
    checked(cell, capsys, manifest)


def test_a_configuration_without_a_reference_is_an_error(monkeypatch,
                                                         capsys):
    load = harness.load_json

    def without(*parts):
        data = load(*parts)
        data.pop("reference", None)
        return data

    monkeypatch.setattr(harness, "load_json", without)
    with pytest.raises(KeyError, match="reference"):
        harness.main(["--workload", CELLS[0], "--rehearse-cpu",
                      "--check", "reference"])


# ------------------------------------------- the comparison that is correct
def with_overrides(monkeypatch, **groups):
    """The data files as they are, with each of ``groups`` laid over the
    rehearsal overrides of the file that has all of its keys."""
    load = harness.load_json

    def patched(*parts):
        data = load(*parts)
        for over in groups.values():
            if set(over) <= set(data):
                data["rehearsal"] = harness.merged(data["rehearsal"], over)
        return data

    monkeypatch.setattr(harness, "load_json", patched)


@pytest.mark.parametrize("seed", [3, 2147483659, 2000000011])
def test_the_control_in_int8_comes_out_not_correct(seed, monkeypatch,
                                                   capsys):
    """``--check control`` at a size a test can hold: the program's
    served tokens stay inside the limits and the tokens that the
    reference computed in int8 puts first do not.  A traced window counts
    steps, so the sample is the same on every machine; 64 requests give
    some 600 served tokens."""
    with_overrides(monkeypatch,
                   config={"served_check": {"requests": 64}},
                   traffic={"trace_steps": 500})
    code = harness.main(["--workload", "lm7b_serve_chat_sat", "--seed",
                         str(seed), "--trace", "1", "--rehearse-cpu",
                         "--check", "control"])
    line, = [ln for ln in capsys.readouterr().out.splitlines()
             if " check {" in ln]
    seen = json.loads(line[line.index("{"):])
    assert code == 0 and seen["ok"] and not seen["problems"]
    assert seen["served_tokens"] > 400
    assert seen["gap"] <= seen["limits"]["gap"] < seen["control_gap"]


def test_an_altered_token_comes_out_not_correct(monkeypatch, capsys):
    """The timed path broken underneath: the replica alters the tokens
    where a decode step produces them (and feeds them back, so everything
    after is consistent with them); every request has one, since none is
    shorter than 2 tokens.  The run ends, and ``correct`` is false by the
    comparison with the reference alone."""
    from horovod_tpu.serving import replica

    produce = replica.ReplicaExecutor._decode_once

    def altered(self, parts):
        active, tokens = produce(self, parts)
        return active, (tokens + 1) % 256 if active else tokens

    monkeypatch.setattr(replica.ReplicaExecutor, "_decode_once", altered)
    code = rehearse("--workload", "lm7b_serve_chat_sat", "--trace", "0")
    line = result_line(capsys.readouterr().out)
    assert code == 0 and line["correct"] is False
    assert line["failed"] == line["attempted"] > 0
    over = {name for name, pair in line["compared"].items()
            if not pair["value"] <= pair["limit"]}
    assert over and over <= {"served_logit_gap", "served_logit_gap_mean"}
    assert line["compared"]["served_logit_gap"]["value"] > 1.0


# ----------------------------------------- what a later PR can add, unedited
def files_under(top: str) -> list[str]:
    return sorted(os.path.relpath(os.path.join(folder, name), top)
                  for folder, _, names in os.walk(top) for name in names
                  if "__pycache__" not in folder)


def test_a_later_pr_adds_a_configuration_without_an_edit(
        tmp_path, monkeypatch, capsys, manifest):
    """The acceptance test of ``PERF.md`` section 3's checklist: in a copy
    of the tree, the files of ``addition_example/`` and its manifest
    entries bring a configuration of another architecture (its own
    configuration class, reference, two ``_per_step`` counts, traffic,
    cell, and per-layer metrics over an ``hvd.*`` span, a ``stats.*``
    counter and the second count), and no file of the original changes."""
    root = str(tmp_path / "tree")
    copy = os.path.join(root, "benchmarks", "chip")
    shutil.copytree(HERE, copy, ignore=shutil.ignore_patterns(
        "__pycache__", "addition_example"))
    original = files_under(copy)
    example = os.path.join(HERE, "addition_example")
    added = [f for f in files_under(example)
             if f not in ("manifest_entries.json", "README.txt")]
    assert not set(added) & set(original)          # new files only
    for name in added:
        os.makedirs(os.path.dirname(os.path.join(copy, name)),
                    exist_ok=True)
        shutil.copy(os.path.join(example, name), os.path.join(copy, name))

    entries = harness.load_json(example, "manifest_entries.json")
    grown = json.loads(json.dumps(manifest))
    cell = entries["workloads"][0]["name"]
    for kind in ("configs", "workloads", "per_layer"):
        grown[kind] += entries[kind]               # appended, in order
    for kind, names in entries["reports"].items():
        for metric in grown[kind]:
            if metric["name"] in names:            # an existing metric
                metric["workloads"].append(cell)   # that the cell reports
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(grown, f, indent=1)

    monkeypatch.setattr(harness, "ROOT", root)
    monkeypatch.setattr(harness, "HERE", copy)
    monkeypatch.syspath_prepend(copy)     # the new modules are found here
    try:
        test_manifest_keeps_the_contract(grown)
        test_names_the_issue_fixed(grown)
        test_every_cell_finds_its_files_and_reports_enough(grown)
        test_layer_metrics_are_data_and_move_what_their_cells_report(grown)
        untraced = rehearsed(cell, 0, capsys, grown)
        traced = rehearsed(cell, 1, capsys, grown)
        seen = checked(cell, capsys, grown)
    finally:                  # nothing imported from the copy outlives it
        for name, module in list(sys.modules.items()):
            if (getattr(module, "__file__", None) or "").startswith(root):
                del sys.modules[name]
    assert set(untraced["metrics"]) == {
        "serve_total_tokens_per_s", "serve_itl_ms_p95", "setup_s"}
    # The program's span, the executor's counter and the second count.
    assert traced["metrics"]["stub.decode_dispatch_ms_p50"]["value"] > 0
    assert traced["metrics"]["stub.cache_bytes"]["value"] > 0
    assert 0 < traced["metrics"]["stub.state_share"]["value"] < 100
    assert {"batcher.slot_occupancy", "replica.decode_step_ms_p50"} \
        <= set(traced["metrics"])
    # Its own reference function, with its own tolerance.
    assert seen["reference"] == "stub_reference:check"
    assert seen["tolerance"] == 0.03

    # No file copied from the original differs, and the manifest's
    # entries are the original's, each ``workloads`` list only longer.
    same, differ, errors = filecmp.cmpfiles(HERE, copy, original,
                                            shallow=False)
    assert (sorted(same), differ, errors) == (original, [], [])
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for before, after in zip(manifest[kind], grown[kind]):
            listed = before.get("workloads", [])
            assert after.get("workloads", [])[:len(listed)] == listed
            assert {k: v for k, v in after.items() if k != "workloads"} \
                == {k: v for k, v in before.items() if k != "workloads"}
    assert all(grown[key] == manifest[key]
               for key in ("command", "paths", "run_seconds"))
