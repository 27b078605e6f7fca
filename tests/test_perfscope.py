"""perfscope end-to-end battery (ISSUE 19 acceptance): the 2-rank
metrics-on world produces busbw cells the perf CLI merges into one
PERF.json, perfcheck gates that ledger against itself (pass) and against
a doctored -30% busbw twin (structured failure naming the cell), the
4-rank synthetic merge covers ring/tree/rhd at three size buckets, and
the Trainer reports a nonzero MFU for a TransformerLM step on CPU."""
from __future__ import annotations

import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu.telemetry import perf, perfcheck, perfmodel
from horovod_tpu.telemetry.registry import MetricsRegistry

from test_multiprocess import _run_world


def _synthetic_dumps(tmp_path, ranks=4):
    """Rank metric dumps with busbw cells for ring/tree/rhd across the
    4KiB/64KiB/1MiB buckets — the shape a 4-rank algo-sweep run leaves
    behind, without needing a power-of-two live world in this test."""
    base = {"4KiB": 40.0, "64KiB": 160.0, "1MiB": 260.0}
    factor = {"ring": 1.0, "rhd": 0.95, "tree": 0.5}
    paths = []
    for r in range(ranks):
        reg = MetricsRegistry(r)
        for algo, f in factor.items():
            for bucket, busbw in base.items():
                h = reg.histogram(
                    "horovod_collective_busbw_mbps", "busbw",
                    labels={"plane": "tcp", "op": "allreduce",
                            "codec": "none", "algo": algo,
                            "size_bucket": bucket})
                for i in range(3):
                    h.observe(busbw * f * (1.0 + 0.01 * ((r + i) % 3)))
        path = tmp_path / f"dump.r{r}.json"
        path.write_text(json.dumps(reg.snapshot()))
        paths.append(str(path))
    return paths


def test_perf_cli_merges_4rank_synthetic_algo_sweep(tmp_path, capsys):
    """Acceptance: the CLI merges 4 rank dumps into one PERF.json whose
    busbw table covers ring/tree/rhd at >= 3 size buckets with
    roofline-relative efficiency."""
    paths = _synthetic_dumps(tmp_path)
    out = tmp_path / "PERF.json"
    rc = perf.main(paths + ["-o", str(out), "--size", "4",
                            "--topology", "torus:2x2"])
    assert rc == 0
    ledger = json.loads(out.read_text())
    assert ledger["schema"] == 1
    assert ledger["world"] == {"ranks": 4, "dumps": 4,
                               "topology": "torus:2x2"}
    rows = ledger["busbw"]
    for algo in ("ring", "tree", "rhd"):
        buckets = {r["size_bucket"] for r in rows if r["algo"] == algo}
        assert {"4KiB", "64KiB", "1MiB"} <= buckets, (algo, buckets)
    assert ledger["peak_source"] == "self-calibrated"
    assert ledger["peak_mbps"] == pytest.approx(
        max(r["busbw_mbps"] for r in rows))
    for r in rows:
        assert 0.0 < r["efficiency"] <= 1.05, r
        assert r["roofline_mbps"] > 0.0
        assert r["algo_overhead"] >= 1.0
    # The tree runs at half the ring's busbw in the synthetic data; the
    # efficiency column must show that gap, not normalize it away.
    ring_1m = next(r for r in rows
                   if r["algo"] == "ring" and r["size_bucket"] == "1MiB")
    tree_1m = next(r for r in rows
                   if r["algo"] == "tree" and r["size_bucket"] == "1MiB")
    assert tree_1m["efficiency"] < 0.6 * ring_1m["efficiency"]


def test_perfcheck_catches_seeded_regression(tmp_path, capsys):
    """Acceptance: perfcheck passes a ledger against itself and fails a
    doctored -30% busbw current with a structured finding naming the
    (plane, algo, size-bucket) cell."""
    paths = _synthetic_dumps(tmp_path)
    out = tmp_path / "PERF.json"
    assert perf.main(paths + ["-o", str(out), "--size", "4"]) == 0
    # Self-comparison: identical cells, no findings, exit 0.
    assert perfcheck.main([str(out), "--baseline", str(out)]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["findings"] == []

    doctored = json.loads(out.read_text())
    for row in doctored["busbw"]:
        row["busbw_mbps"] *= 0.7
    bad = tmp_path / "PERF.regressed.json"
    bad.write_text(json.dumps(doctored))
    rc = perfcheck.main([str(bad), "--baseline", str(out),
                         "--tolerance-pct", "10"])
    captured = capsys.readouterr()
    assert rc == 1
    assert "REGRESSION" in captured.err
    report = json.loads(captured.out)
    assert report["findings"], captured.out
    for f in report["findings"]:
        assert f["metric"] == "busbw_mbps"
        assert f["plane"] == "tcp"
        assert f["size_bucket"] in ("4KiB", "64KiB", "1MiB")
        assert f["algo"] in ("ring", "tree", "rhd")
        assert f["delta_pct"] == pytest.approx(-30.0, abs=0.2)


def test_perfscope_2rank_world(tmp_path, capsys):
    """ISSUE 19 tier-1 smoke: a real 2-rank metrics-on world (in-battery
    assertions: ledger produced, efficiency in (0, 1.05], known algos)
    whose shutdown dumps merge through the perf CLI and pass perfcheck
    against their own ledger; a doctored -30% baseline window fails."""
    for stale in glob.glob("/tmp/hvd_perf_perfscope2.r*.json"):
        os.unlink(stale)
    _run_world(2, "perfscope", timeout=240.0)
    dumps = [f"/tmp/hvd_perf_perfscope2.r{r}.json" for r in range(2)]
    for d in dumps:
        assert os.path.exists(d), f"rank dump missing: {d}"
    out = tmp_path / "PERF.json"
    assert perf.main(dumps + ["-o", str(out), "--size", "2"]) == 0
    ledger = json.loads(out.read_text())
    rows = ledger["busbw"]
    assert rows, "2-rank world produced no busbw cells"
    assert ledger["world"]["dumps"] == 2
    assert {"4KiB", "64KiB", "1MiB"} <= {r["size_bucket"] for r in rows}
    for r in rows:
        assert 0.0 < r["efficiency"] <= 1.05, r
        assert r["algo"] == "ring", r   # 2 ranks: every schedule degenerates
    # Gate against itself: clean.
    assert perfcheck.main([str(out), "--baseline", str(out)]) == 0
    capsys.readouterr()
    # Doctor the CURRENT ledger 30% down; the gate must name a cell.
    doctored = json.loads(out.read_text())
    for row in doctored["busbw"]:
        row["busbw_mbps"] *= 0.7
    bad = tmp_path / "PERF.regressed.json"
    bad.write_text(json.dumps(doctored))
    rc = perfcheck.main([str(bad), "--baseline", str(out),
                         "--tolerance-pct", "10"])
    captured = capsys.readouterr()
    assert rc == 1
    finding = json.loads(captured.out)["findings"][0]
    assert finding["plane"] == "tcp"
    assert finding["algo"] == "ring"
    assert finding["size_bucket"] in ("4KiB", "64KiB", "1MiB")


def _toy_trainer(**model_overrides):
    from horovod_tpu import training
    from horovod_tpu.models.transformer import TransformerLM, gpt_tiny
    from horovod_tpu.parallel import GradSyncConfig, MeshSpec, build_mesh

    mesh = build_mesh(MeshSpec(dp=8))
    model = TransformerLM(gpt_tiny(dtype=jnp.float32, **model_overrides))
    trainer = training.Trainer(
        model, optax.adamw(1e-3), mesh,
        sync=GradSyncConfig(axes=("dp",), op="average"))
    batch = training.synthetic_text_batch(8, seq_len=16, vocab_size=256)
    return trainer, trainer.init(jax.random.key(0), batch), batch


def test_trainer_mfu_needs_a_known_peak(monkeypatch):
    """Step time and MFU are set only from an interval that ends in a
    host fetch, which `fit` has at every epoch's end: a loop of bare
    `step` calls (two dispatches are microseconds apart, the device's
    steps are not) sets neither.  On a device kind the peak table does
    not know (the CPU here) `fit` sets no horovod_train_mfu either, and
    reports one once HOROVOD_PERF_PEAK_FLOPS names a peak."""
    from horovod_tpu import telemetry

    monkeypatch.setenv("HOROVOD_METRICS", "on")
    monkeypatch.setenv("HOROVOD_PERF_PEAK_FLOPS", "1e12")
    reg = telemetry.configure()
    try:
        trainer, state, batch = _toy_trainer()
        state, _ = trainer.step(state, batch)
        state, metrics = trainer.step(state, batch)
        jax.block_until_ready(metrics)
        snap = {m["name"]: m for m in reg.snapshot()["metrics"]}
        assert "horovod_train_mfu" not in snap
        assert "horovod_train_step_ms" not in snap
        assert not hasattr(trainer, "_last_dispatch")
        # The analytic FLOPs match the model card.
        assert reg.gauge("horovod_train_step_flops").value \
            == pytest.approx(perfmodel.transformer_train_flops(
                trainer.model.cfg, 8, 16))

        monkeypatch.delenv("HOROVOD_PERF_PEAK_FLOPS")
        assert perfmodel.peak_flops(jax.devices()[0].device_kind) is None
        state, _ = trainer.fit(state, [batch] * 3, epochs=2)
        snap = {m["name"]: m for m in reg.snapshot()["metrics"]}
        assert "horovod_train_mfu" not in snap
        assert snap["horovod_train_step_ms"]["count"] == 2    # an epoch

        monkeypatch.setenv("HOROVOD_PERF_PEAK_FLOPS", "1e12")
        state, _ = trainer.fit(state, [batch] * 3)
        mfu = reg.gauge("horovod_train_mfu").value
        assert 0.0 < mfu < 1.0, mfu
    finally:
        monkeypatch.delenv("HOROVOD_METRICS", raising=False)
        telemetry.configure()


def test_fit_times_an_epoch_over_its_steps(monkeypatch):
    """`fit` observes horovod_train_step_ms once an epoch, as the
    interval that ends in the epoch's metrics fetch over the steps
    dispatched in it, and keeps the host seconds spent waiting for
    batches and inside callbacks."""
    import time

    from horovod_tpu import telemetry

    monkeypatch.setenv("HOROVOD_METRICS", "on")
    reg = telemetry.configure()
    try:
        trainer, state, batch = _toy_trainer()
        state, _ = trainer.step(state, batch)      # the compile

        def slow_loader(epoch):
            for _ in range(4):
                time.sleep(0.03)
                yield batch

        class Sleepy:
            def __getattr__(self, name):           # every hook: no-op
                if not name.startswith("on_"):
                    raise AttributeError(name)
                return lambda *a: None

            def on_batch_end(self, i, metrics):
                time.sleep(0.01)

        began = time.perf_counter()
        state, history = trainer.fit(state, slow_loader, epochs=2,
                                     callbacks=[Sleepy()])
        wall = time.perf_counter() - began
        assert len(history) == 2
        hist = reg.histogram("horovod_train_step_ms")
        assert hist.count == 2
        # 4 steps an epoch, each behind 30 ms of loader and 10 ms of
        # callback: an interval over its steps is at least 40 ms, and
        # the two epochs' intervals fit into the wall time.
        assert hist.sum >= 2 * 40.0
        assert hist.sum * 4 <= wall * 1e3
        assert trainer.stats["steps"] == 9
        assert trainer.stats["data_wait_s"] >= 8 * 0.03
        assert trainer.stats["callbacks_s"] >= 8 * 0.01
        assert 0 < trainer.stats["dispatch_s"] < wall + 60
    finally:
        monkeypatch.delenv("HOROVOD_METRICS", raising=False)
        telemetry.configure()


def _operations(hlo: str) -> list:
    """(shape, opcode) of every instruction of a compiled module, in
    order: what it computes, without the names XLA gives instructions
    (some of which it derives from the name stack)."""
    import re
    found = [re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\S+) ([\w\-]+)\(", line)
             for line in hlo.splitlines()]
    return [m.groups() for m in found if m]


def test_scope_names_are_metadata_and_change_no_operation(monkeypatch):
    """The five scopes of the train step are in the lowered program's
    debug info and nowhere else: the compiled step has the same
    operations with and without them."""
    import contextlib

    texts = {}
    for scoped in (True, False):
        if not scoped:
            monkeypatch.setattr(jax, "named_scope",
                                lambda name: contextlib.nullcontext())
        trainer, state, batch = _toy_trainer(attention="flash")
        lowered = trainer._build(state).lower(state, batch)
        named = lowered.as_text(debug_info=True)
        for scope in ("hvd.flash_fwd", "hvd.flash_bwd", "hvd.loss",
                      "hvd.grad_sync", "hvd.optimizer"):
            assert (scope in named) == scoped, scope
        assert "hvd." not in lowered.as_text()
        texts[scoped] = _operations(lowered.compile().as_text())
    assert len(texts[True]) > 100
    assert texts[True] == texts[False]


def test_summary_stamps_perf_ledger(monkeypatch):
    """bench payload stamp: telemetry.summary() carries the perf ledger
    whenever busbw or step evidence exists in the registry."""
    from horovod_tpu import telemetry

    monkeypatch.setenv("HOROVOD_METRICS", "on")
    reg = telemetry.configure()
    try:
        reg.histogram(
            "horovod_collective_busbw_mbps", "busbw",
            labels={"plane": "tcp", "op": "allreduce", "codec": "none",
                    "algo": "ring", "size_bucket": "64KiB"}).observe(120.0)
        out = telemetry.summary()
        assert "perf" in out
        assert out["perf"]["busbw"][0]["algo"] == "ring"
        assert out["perf"]["busbw"][0]["efficiency"] == pytest.approx(1.0)
    finally:
        monkeypatch.delenv("HOROVOD_METRICS", raising=False)
        telemetry.configure()
