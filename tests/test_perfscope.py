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


def test_trainer_mfu_needs_a_known_peak(monkeypatch):
    """The Trainer sets no horovod_train_mfu gauge on a device kind the
    peak table does not know (the CPU here) and reports an MFU once
    HOROVOD_PERF_PEAK_FLOPS names a peak.  MFU needs two steps: the
    first dispatch only arms the inter-dispatch clock."""
    from horovod_tpu import telemetry, training
    from horovod_tpu.models.transformer import TransformerLM, gpt_tiny
    from horovod_tpu.parallel import GradSyncConfig, MeshSpec, build_mesh

    monkeypatch.setenv("HOROVOD_METRICS", "on")
    monkeypatch.delenv("HOROVOD_PERF_PEAK_FLOPS", raising=False)
    reg = telemetry.configure()
    try:
        mesh = build_mesh(MeshSpec(dp=8))
        model = TransformerLM(gpt_tiny(dtype=jnp.float32))
        trainer = training.Trainer(
            model, optax.adamw(1e-3), mesh,
            sync=GradSyncConfig(axes=("dp",), op="average"))
        batch = training.synthetic_text_batch(8, seq_len=16,
                                              vocab_size=256)
        state = trainer.init(jax.random.key(0), batch)
        state, _ = trainer.step(state, batch)
        state, metrics = trainer.step(state, batch)
        jax.block_until_ready(metrics)
        assert perfmodel.peak_flops(jax.devices()[0].device_kind) is None
        snap = {m["name"]: m for m in reg.snapshot()["metrics"]}
        assert "horovod_train_mfu" not in snap
        assert snap["horovod_train_step_ms"]["count"] >= 1
        # The analytic FLOPs match the model card.
        assert reg.gauge("horovod_train_step_flops").value \
            == pytest.approx(perfmodel.transformer_train_flops(
                model.cfg, 8, 16))

        monkeypatch.setenv("HOROVOD_PERF_PEAK_FLOPS", "1e12")
        state, metrics = trainer.step(state, batch)
        jax.block_until_ready(metrics)
        mfu = reg.gauge("horovod_train_mfu").value
        assert 0.0 < mfu < 1.0, mfu
    finally:
        monkeypatch.delenv("HOROVOD_METRICS", raising=False)
        telemetry.configure()


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
