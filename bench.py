"""Synthetic training throughput on the TPU, one model per invocation.

TPU-native analogue of the reference's synthetic benchmark
(reference: examples/pytorch/pytorch_synthetic_benchmark.py): time the full
compiled train step (forward + backward + fused gradient allreduce +
optimizer update) on random data, bf16 compute, in THIS process over every
local chip.

Baseline: the reference's published absolute number is 1656.82 images/sec
on 16 P100 GPUs for ResNet-101 tf_cnn_benchmarks (docs/benchmarks.rst:32-43)
= 103.55 images/sec/device. vs_baseline = our images/sec/chip / 103.55.

The model legs (resnet50/resnet101/vgg16/inception3/gpt) measure the chip
and nothing else: without a TPU they exit non-zero naming the cause, and an
exception in a step is the exit status, not a JSON line.  ``--model eager``
and ``--model serve`` measure the host control plane and the serving
scheduler on the CPU and say so (``cpu-eager``).

"mfu" reports achieved_flops/peak_flops from XLA cost analysis against the
chip's published peak.  Prints ONE JSON line per metric:
{"metric", "value", "unit", "vs_baseline", ...}.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

BASELINE_IMG_PER_SEC_PER_DEVICE = 1656.82 / 16  # reference, P100
# Per-model published absolute baselines (images/sec/device). The only
# absolute number the reference publishes is ResNet-101 tf_cnn_benchmarks
# (docs/benchmarks.rst:32-43); resnet50 keeps it as a documented proxy
# (slightly lighter model, conservative ratio). VGG/Inception have only
# scaling-efficiency percentages → no ratio (0.0).
_BASELINES = {"resnet50": BASELINE_IMG_PER_SEC_PER_DEVICE,
              "resnet101": BASELINE_IMG_PER_SEC_PER_DEVICE}


def _require_tpu(model: str) -> dict:
    """Open the backend (once, in this process) and return its stamp;
    exit non-zero when it is not a TPU — a CPU number is never printed
    under a device metric's name."""
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(
            f"bench: no TPU: jax.default_backend() is {backend!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r}); "
            f"--model {model} measures the chip and has no CPU fallback")
    return {"backend": backend,
            "device_kind": jax.devices()[0].device_kind}


def _step_flops(trainer, state, batch) -> float:
    """Per-device FLOPs of one compiled train step, via XLA cost analysis."""
    cost = trainer._step_fn.lower(state, batch).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return float(cost["flops"])


def _emit(payload: dict) -> None:
    # Every bench payload records WHAT ran, not just how fast: the
    # declared fabric topology and allreduce-algorithm knob ride along so
    # the perf trajectory can attribute a shift to a layout/algo change.
    # Env-sourced (not registry) so even failure payloads from processes
    # that never imported the package carry the stamp; legs that know the
    # runtime-selected value set the keys explicitly and win (setdefault).
    payload.setdefault("topology",
                       os.environ.get("HOROVOD_TOPOLOGY", "") or "flat")
    payload.setdefault("algo", os.environ.get("HOROVOD_ALGO", "") or "auto")
    print(json.dumps(payload))


def _sync(metrics) -> float:
    """End a timed region: fetch the loss scalar to the host, which cannot
    return before the step that produced it has run.  The fetched loss
    doubles as a liveness check (NaN shows up in the output)."""
    import numpy as np
    return float(np.asarray(metrics["loss"]))


def _time_steps(trainer, state, batch, warmup: int, iters: int
                ) -> tuple[float, float]:
    """(seconds for ``iters`` steady steps, per-device step FLOPs)."""
    for _ in range(max(warmup, 1)):   # >=1: excludes compile from timing
        state, metrics = trainer.step(state, batch)
    _sync(metrics)
    flops = _step_flops(trainer, state, batch)
    t0 = time.perf_counter()
    for _ in range(iters):
        state, metrics = trainer.step(state, batch)
    _sync(metrics)
    return time.perf_counter() - t0, flops


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", default="resnet50",
                        choices=["resnet50", "resnet101", "vgg16",
                                 "inception3", "gpt", "eager", "serve"],
                        help="resnet50: headline images/sec benchmark; "
                        "resnet101/vgg16/inception3: the reference's "
                        "other headline CNNs (docs/benchmarks.rst:13-43); "
                        "gpt: transformer tokens/sec (flash attention); "
                        "eager: controller/TCP eager-core microbenchmark; "
                        "serve: serving loadgen smoke (goodput + SLO "
                        "latency; report to SERVE_r*.json, "
                        "docs/serving.md)")
    parser.add_argument("--batch-size", type=int, default=128)
    parser.add_argument("--stem", default="conv7",
                        choices=["conv7", "space_to_depth"],
                        help="resnet*: stem layout (space_to_depth folds "
                        "the 7x7/s2 3-channel conv into an equivalent "
                        "4x4/s1 12-channel conv for the MXU)")
    parser.add_argument("--image-size", type=int, default=None,
                        help="default: the model's canonical input "
                        "(299 for inception3, else 224)")
    parser.add_argument("--seq-len", type=int, default=2048)
    parser.add_argument("--gpt-preset", default="small",
                        choices=["small", "medium"],
                        help="gpt: model size (small=124M, medium=350M; "
                        "medium's d_model=1024 shapes map better onto "
                        "the 128x128 MXU)")
    parser.add_argument("--warmup", type=int, default=3)
    parser.add_argument("--iters", type=int, default=20)
    parser.add_argument("--remat", type=int, default=0,
                        help="gpt: rematerialize each block (saves HBM, "
                        "costs recompute; default off for throughput)")
    parser.add_argument("--remat-policy", default="full",
                        choices=["full", "dots"],
                        help="gpt remat granularity: 'dots' saves matmul "
                        "outputs (less recompute, more HBM)")
    # 1024/1024 is the largest pair that fits the 16 MiB scoped VMEM at
    # gpt-small shapes (a 2048-row forward block needs 17.24M); the flash
    # kernel clamps a block to a divisor of the sequence or raises.
    parser.add_argument("--block-q", type=int, default=1024)
    parser.add_argument("--block-k", type=int, default=1024)
    # 0 = same as forward; the bwd kernel's VMEM-optimal tiling is often
    # smaller (it holds dq/dk/dv accumulators + the recomputed p block).
    parser.add_argument("--block-q-bwd", type=int, default=0)
    parser.add_argument("--block-k-bwd", type=int, default=0)
    args = parser.parse_args()
    if args.model.startswith("resnet") and args.stem == "space_to_depth" \
            and (args.image_size or 224) % 2:
        parser.error(f"--stem space_to_depth needs an even --image-size "
                     f"(got {args.image_size})")
    if args.model == "eager":   # CPU/localhost only
        try:
            return bench_eager(args)
        except Exception as exc:
            import traceback
            traceback.print_exc()
            _emit({"metric": "eager_failed", "value": 0.0, "unit": "error",
                   "vs_baseline": 0.0,
                   "error": f"{type(exc).__name__}: {exc}",
                   "failure": {"class": "harness-exception",
                               "exception": type(exc).__name__,
                               "retryable": True}})
            return 1
    if args.model == "serve":   # CPU/localhost only
        try:
            return bench_serve(args)
        except Exception as exc:
            import traceback
            traceback.print_exc()
            _emit({"metric": "serve_failed", "value": 0.0, "unit": "error",
                   "vs_baseline": 0.0,
                   "error": f"{type(exc).__name__}: {exc}",
                   "failure": {"class": "harness-exception",
                               "exception": type(exc).__name__,
                               "retryable": True}})
            return 1
    info = _require_tpu(args.model)
    from horovod_tpu.common.compile_cache import configure_compile_cache
    configure_compile_cache()
    if args.model == "gpt":
        return bench_gpt(args, info)
    return bench_resnet(args, info)   # all CNN families


def bench_serve(args) -> int:
    """Serving loadgen A/B (ISSUE 9 smoke + ISSUE 14 paged leg): the
    open-loop SLO harness runs TWICE at fixed hardware — the dense
    baseline, then the paged+prefix configuration — under the same
    burst arrival profile and the same repeated-prompt pool.  The dense
    numbers keep the trajectory comparable (serve_goodput); the paged
    leg adds serve_goodput_paged / serve_p99_paged and the
    max_concurrent_seqs the block pool sustained next to the dense
    batch bound, so the trajectory finally records a serving perf
    delta."""
    # Saturating burst (4x through the middle fifth) with a tight SLO:
    # below saturation both configs serve everything and the A/B says
    # nothing; at this load the dense leg queues behind prefills while
    # the paged leg's prefix hits + wider slot packing absorb the burst.
    common = ["--requests", "96", "--duration", "5", "--rate", "120",
              "--max-new-tokens", "4", "--prompt-tokens", "8",
              "--profile", "burst", "--prompt-pool", "6",
              "--max-batch", "4", "--slo-ms", "400"]

    def leg(name: str, extra_env: dict, output: str) -> dict | None:
        out = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.serving.loadgen",
             *common, "--output", output],
            capture_output=True, text=True, timeout=600,
            env={**os.environ, "JAX_PLATFORMS": "cpu", **extra_env})
        if out.returncode != 0:
            _emit({"metric": f"serve_{name}_failed", "value": 0.0,
                   "unit": "error", "vs_baseline": 0.0,
                   "error": out.stderr[-500:] or out.stdout[-500:],
                   "failure": {"class": "loadgen-crash", "rc": out.returncode,
                               "retryable": True}})
            return None
        with open(output.replace("{rank}", "0")) as f:
            return json.load(f)

    dense = leg("dense", {"HOROVOD_SERVE_PAGED": "0"},
                "SERVE_r{rank}.json")
    if dense is None:
        return 1
    _emit({"metric": "serve_goodput", "value": dense["goodput_rps"],
           "unit": "req/s", "vs_baseline": 0.0, "backend": "cpu-eager",
           "offered_rps": dense["offered_rps"],
           "served": dense["served"], "shed": dense["shed"],
           "expired": dense["expired"],
           "latency_ms": dense["latency_ms"],
           "step_ms": dense["step_ms"],
           "report": "SERVE_r0.json"})
    # Paged leg at EQUAL memory budget: the pool auto-sizes to the
    # dense layout's token memory (max_batch x max_seq), slots widen to
    # 2 x max_batch — concurrency beyond the dense batch shape comes
    # from residency, not extra HBM.
    paged = leg("paged", {"HOROVOD_SERVE_PAGED": "1"},
                "SERVE_PAGED_r{rank}.json")
    if paged is None:
        return 1
    kv = paged.get("kv") or {}
    _emit({"metric": "serve_goodput_paged",
           "value": paged["goodput_rps"], "unit": "req/s",
           "vs_baseline": (paged["goodput_rps"] / dense["goodput_rps"]
                           if dense["goodput_rps"] else 0.0),
           "backend": "cpu-eager",
           "served": paged["served"], "shed": paged["shed"],
           "latency_ms": paged["latency_ms"],
           "dense_goodput": dense["goodput_rps"],
           "dense_p99_ms": dense["latency_ms"]["p99"],
           "prefix_hits": kv.get("prefix_hits", 0),
           "prefix_misses": kv.get("prefix_misses", 0),
           "report": "SERVE_PAGED_r0.json"})
    _emit({"metric": "serve_p99_paged",
           "value": paged["latency_ms"]["p99"], "unit": "ms",
           "vs_baseline": (paged["latency_ms"]["p99"]
                           / dense["latency_ms"]["p99"]
                           if dense["latency_ms"]["p99"] else 0.0),
           "dense_p99_ms": dense["latency_ms"]["p99"]})
    _emit({"metric": "max_concurrent_seqs",
           "value": float(paged["max_concurrent_seqs"]), "unit": "seqs",
           "vs_baseline": 0.0,
           "dense_max_batch": 4,
           "dense_max_concurrent": dense["max_concurrent_seqs"],
           "pool_blocks": kv.get("pool_blocks", 0),
           "block_tokens": kv.get("block_tokens", 0)})
    return 0


def bench_resnet(args, info: dict) -> int:
    # Telemetry on for the multichip payload (same contract as the eager
    # payload): the trajectory records counters next to the throughput.
    os.environ.setdefault("HOROVOD_METRICS", "on")
    import jax
    import optax
    from jax.sharding import NamedSharding

    from horovod_tpu import models, telemetry, training
    from horovod_tpu.parallel import GradSyncConfig, MeshSpec, build_mesh
    from horovod_tpu.telemetry import perfmodel

    n_dev = len(jax.devices())
    mesh = build_mesh(MeshSpec(dp=n_dev))

    # bf16 compute by default for every CNN family.
    ctor = {"resnet50": models.ResNet50, "resnet101": models.ResNet101,
            "vgg16": models.VGG16, "inception3": models.InceptionV3}
    if args.image_size is None:   # per-model canonical input
        args.image_size = 299 if args.model == "inception3" else 224
    kw = {}
    if args.model.startswith("resnet"):
        kw["stem"] = args.stem
    model = ctor[args.model](num_classes=1000, **kw)
    trainer = training.Trainer(
        model, optax.sgd(0.1, momentum=0.9), mesh,
        sync=GradSyncConfig(axes=("dp",), op="average",
                            compression="bf16"))

    global_batch = args.batch_size * n_dev
    # Placed once, one shard per chip: feeding the default-device array
    # would re-shard the whole batch from chip 0 every step.
    batch = jax.device_put(
        training.synthetic_image_batch(global_batch,
                                       image_size=args.image_size),
        NamedSharding(mesh, trainer.batch_spec))
    state = trainer.init(jax.random.key(0), batch)
    elapsed, flops = _time_steps(trainer, state, batch, args.warmup,
                                 args.iters)

    per_chip = global_batch * args.iters / elapsed / n_dev
    peak = perfmodel.peak_flops(info["device_kind"])
    baseline = _BASELINES.get(args.model)
    _emit({
        "metric": f"{args.model}_synthetic_images_per_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(per_chip / baseline, 3) if baseline else 0.0,
        "mfu": round(flops * args.iters / elapsed / peak, 4)
        if peak else None,
        "n_devices": n_dev,
        # Observability rides the multichip payload like the eager one:
        # wire bytes / cache hit rate / stream utilization (empty-ish on
        # the pure-SPMD path, populated whenever the eager runtime is in
        # the loop) — docs/observability.md.
        "metrics": telemetry.summary(),
        **info,
    })
    return 0


def bench_gpt(args, info: dict) -> int:
    """Transformer LM throughput (tokens/sec/chip) with the Pallas flash
    attention kernel; secondary benchmark covering the long-context path."""
    os.environ.setdefault("HOROVOD_METRICS", "on")
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding

    from horovod_tpu import models, telemetry, training
    from horovod_tpu.parallel import GradSyncConfig, MeshSpec, build_mesh
    from horovod_tpu.telemetry import perfmodel

    n_dev = len(jax.devices())
    mesh = build_mesh(MeshSpec(dp=n_dev))

    preset = models.gpt_medium if args.gpt_preset == "medium" \
        else models.gpt_small
    cfg = preset(
        max_seq_len=args.seq_len, attention="flash",
        remat=bool(args.remat), remat_policy=args.remat_policy,
        block_q=args.block_q, block_k=args.block_k,
        block_q_bwd=args.block_q_bwd or None,
        block_k_bwd=args.block_k_bwd or None,
        dtype=jnp.bfloat16)
    model = models.TransformerLM(cfg)
    trainer = training.Trainer(
        model, optax.adamw(3e-4), mesh,
        sync=GradSyncConfig(axes=("dp",), op="average",
                            compression="bf16"))

    batch_size = max(args.batch_size // 16, 1) * n_dev
    batch = jax.device_put(
        training.synthetic_text_batch(batch_size, seq_len=args.seq_len,
                                      vocab_size=cfg.vocab_size),
        NamedSharding(mesh, trainer.batch_spec))
    state = trainer.init(jax.random.key(0), batch)
    elapsed, flops = _time_steps(trainer, state, batch, args.warmup,
                                 args.iters)

    per_chip = batch_size * args.seq_len * args.iters / elapsed / n_dev
    peak = perfmodel.peak_flops(info["device_kind"])
    _emit({
        "metric": f"gpt_{args.gpt_preset}_tokens_per_sec_per_chip",
        "value": round(per_chip, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": 0.0,   # no reference LM baseline exists
        "mfu": round(flops * args.iters / elapsed / peak, 4)
        if peak else None,
        "n_devices": n_dev,
        "metrics": telemetry.summary(),
        **info,
    })
    return 0


def _eager_worker(payload_mb: int, cycles: int) -> dict:
    """Per-rank body for bench_eager; must be module-level (pickled to
    spawned workers by horovod_tpu.run)."""
    import numpy as np

    import horovod_tpu as hvd

    # Telemetry rides along so the perf trajectory records counters
    # (bytes on wire, cache hit rate, stream utilization) next to the
    # latency numbers (docs/observability.md).
    os.environ["HOROVOD_METRICS"] = "on"
    hvd.init()
    try:
        small = np.ones(64, dtype=np.float32)
        for _ in range(20):  # fill the response cache / steady state
            hvd.allreduce(small, op=hvd.Sum, name="cycle")
        t0 = time.perf_counter()
        for _ in range(cycles):
            hvd.allreduce(small, op=hvd.Sum, name="cycle")
        cycles_per_sec = cycles / (time.perf_counter() - t0)

        big = np.ones(payload_mb * (1 << 20) // 4, dtype=np.float32)
        for _ in range(2):
            hvd.allreduce(big, op=hvd.Sum, name="ring")
        reps = 5
        t0 = time.perf_counter()
        for _ in range(reps):
            hvd.allreduce(big, op=hvd.Sum, name="ring")
        dt = time.perf_counter() - t0
        # Ring allreduce moves 2*(n-1)/n of the payload per rank each op.
        n = hvd.size()
        moved = reps * payload_mb * (1 << 20) * 2 * (n - 1) / n

        # Fused-vs-reference codec A/B (ISSUE 6): the same payload
        # through the int8 quantized plane with the single-pass fused
        # kernels on, then off (= the PR 3 pipelined reference chain).
        # The dispatch flip is safe mid-run: both settings move one
        # frame per peer per leg and reduce bitwise-identically.
        from horovod_tpu import core as _core
        st = _core.global_state()

        def _set_fused(on: bool) -> None:
            for c in st.tcp_collectives:
                c.fused = on
            for mgr in (st.op_managers or
                        ([st.op_manager] if st.op_manager else [])):
                for be in mgr.backends:
                    if be.name == "shm":   # localhost worlds ride shm
                        be.fused = on

        def _time_quantized() -> float:
            t0 = time.perf_counter()
            for _ in range(reps):
                hvd.allreduce(big, op=hvd.Sum, name="qring",
                              compression="int8")
            return (time.perf_counter() - t0) / reps

        _set_fused(True)
        hvd.allreduce(big, op=hvd.Sum, name="qring", compression="int8")
        codec_fused_s = _time_quantized()
        _set_fused(False)
        hvd.allreduce(big, op=hvd.Sum, name="qring", compression="int8")
        codec_reference_s = _time_quantized()
        _set_fused(True)

        from horovod_tpu import telemetry
        return {"cycles_per_sec": cycles_per_sec,
                "ring_gbyte_per_sec": moved / dt / 1e9,
                "codec_fused_ms": codec_fused_s * 1e3,
                "codec_reference_ms": codec_reference_s * 1e3,
                "metrics": telemetry.summary()}
    finally:
        hvd.shutdown()


def _ladder_worker(sizes_bytes: tuple, reps: int) -> dict:
    """Per-rank body for the allreduce size-ladder leg (median latency
    per algorithm × payload size); module-level for pickling."""
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu import core as _core

    # Pin the flat TCP plane: the ladder compares ring vs tree SCHEDULES,
    # so the shm/XLA planes (which ignore the algo knob) must not claim
    # the op on localhost worlds.
    os.environ["HOROVOD_SHM_OPERATIONS"] = "0"
    os.environ["HOROVOD_XLA_OPERATIONS"] = "0"
    hvd.init()
    try:
        st = _core.global_state()
        out: dict = {}
        for algo in ("ring", "tree"):
            # Symmetric flip (every rank runs this same line before the
            # same op sequence) — the same mechanism as tuned_algo.
            for c in st.tcp_collectives:
                c.algo = algo
            for nb in sizes_bytes:
                x = np.ones(max(nb // 4, 1), dtype=np.float32)
                name = f"ladder_{algo}_{nb}"
                hvd.allreduce(x, op=hvd.Sum, name=name)   # warm the cache
                samples = []
                for _ in range(reps):
                    t0 = time.perf_counter()
                    hvd.allreduce(x, op=hvd.Sum, name=name)
                    samples.append(time.perf_counter() - t0)
                out[f"{algo}_{nb}"] = sorted(samples)[len(samples) // 2] \
                    * 1e3
        return out
    finally:
        hvd.shutdown()


def bench_eager(args) -> int:
    """Eager-core microbenchmark: steady-state cached negotiation cycle rate
    and TCP-ring allreduce bandwidth (reference analogue: the 1ms
    RunLoopOnce cycle + the NCCL ring, horovod/common/operations.cc:589-647).

    Runs entirely on CPU/localhost — measures the controller + transport
    planes, not XLA."""
    # Force (not setdefault) the CPU backend: a controller/TCP
    # microbenchmark's workers must not contend for the chip.
    os.environ["JAX_PLATFORMS"] = "cpu"
    import horovod_tpu

    results = horovod_tpu.run(_eager_worker, args=(16, 200), np=2)
    r = results[0]
    fused_ms = r.get("codec_fused_ms", 0.0)
    ref_ms = r.get("codec_reference_ms", 0.0)

    # Allreduce size ladder (ISSUE 18): median latency per algorithm ×
    # payload size on a 4-rank world (tree degenerates to ring at 2
    # ranks), plus the measured tree/ring crossover — the empirical
    # counterpart of HOROVOD_TREE_THRESHOLD_BYTES.
    ladder_sizes = (4 << 10, 64 << 10, 1 << 20)
    lad = horovod_tpu.run(_ladder_worker, args=(ladder_sizes, 5), np=4)[0]
    ladder = {str(nb): {"ring_ms": round(lad[f"ring_{nb}"], 3),
                        "tree_ms": round(lad[f"tree_{nb}"], 3)}
              for nb in ladder_sizes}
    crossover = 0
    for nb in ladder_sizes:
        if lad[f"tree_{nb}"] < lad[f"ring_{nb}"]:
            crossover = nb
    _emit({
        "metric": "eager_cached_cycles_per_sec",
        "value": round(r["cycles_per_sec"], 1),
        "unit": "cycles/sec (2 ranks, localhost)",
        "vs_baseline": 0.0,
        "ring_gbyte_per_sec": round(r["ring_gbyte_per_sec"], 2),
        # ISSUE 6 A/B: int8 quantized allreduce, fused single-pass
        # kernels vs the PR 3 pipelined reference chain (per-op ms;
        # ratio > 1 means fused is faster).
        "codec_fused_ms": round(fused_ms, 2),
        "codec_reference_ms": round(ref_ms, 2),
        "codec_fused_speedup": round(ref_ms / fused_ms, 3)
        if fused_ms > 0 else 0.0,
        # ISSUE 18 size ladder: per-algo median latency by payload size
        # and the largest size where the tree still beat the ring (0 =
        # the ring won everywhere).
        "allreduce_ladder": ladder,
        "tree_ring_crossover_bytes": crossover,
        # End-of-run telemetry snapshot: the trajectory records counters
        # (wire bytes, cache hit rate, stream utilization) alongside
        # the latency headline.
        "metrics": r.get("metrics", {}),
    })
    return 0


if __name__ == "__main__":
    sys.exit(main())
