#!/usr/bin/env python3
"""Chip smoke: drive the Trainer and the serving replica once on the TPU.

    python3 chip_smoke.py            # on a machine with a TPU; exits 0
    python3 chip_smoke.py --dry-run-cpu   # control flow only, for tests

One process opens the chip once and uses every local chip it finds; it
starts no child.  Three legs run in order through the public API, each at
the full width of a model the repo supports, on seeded random weights:

1. GPT-small training (seq 2048, bf16, Pallas flash attention, AdamW, bf16
   gradient wire, batch 8 per chip): six steps on one batch.  Every loss is
   finite, the last is below the first, the step counter reads 6, and the
   compiled step contains the Mosaic kernels, so no reference path can
   stand in for them.
2. ResNet-50 training (batch 128 per chip at 224x224, SGD momentum, bf16
   wire): six steps, finite losses, step counter right.
3. Serving: a ReplicaExecutor over GPT-small answers 8 seeded prompts of
   100-500 tokens with 32 new tokens each, once with the dense KV cache and
   once paged.  8 of 8 are served with exactly 32 tokens, none shed,
   expired or lost.

With more than one chip it also checks that every device holds one distinct
batch shard, that every parameter is addressable on all devices, that the
compiled step has all-reduces, and that GPT-small at a global batch of 8
gives the same first three losses on the full mesh as on one device.

The numbers it prints (compile seconds, step milliseconds, memory) are smoke
observations for the bring-up record, not benchmark metrics.  Without a TPU
it exits non-zero at once; any leg that raises is the exit status.  The last
line of a passing chip run is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}.
"""
from __future__ import annotations

import argparse
import functools
import gc
import importlib.metadata
import json
import os
import random
import re
import statistics
import sys
import time

TRAIN_STEPS = 6          # one compiling step and five more
XCHECK_STEPS = 3
XCHECK_GLOBAL_BATCH = 8
XCHECK_RTOL = 2e-2
SERVE_REQUESTS = 8


class SmokeFailure(RuntimeError):
    """A leg ran but what came out is wrong."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class Smoke:
    """What every leg shares: the device stamp, the sizes (full or dry-run),
    the printer and the compile clock."""

    def __init__(self, dry: bool) -> None:
        import jax
        import jax.numpy as jnp

        from horovod_tpu import models

        self.dry = dry
        self.tag = "DRY RUN (cpu) " if dry else ""
        self.devices = jax.devices()
        self.n = len(self.devices)
        try:
            libtpu = importlib.metadata.version("libtpu")
        except importlib.metadata.PackageNotFoundError:
            libtpu = "not installed"
        self.stamp = {"platform": self.devices[0].platform,
                      "device_kind": self.devices[0].device_kind,
                      "device_count": self.n,
                      "jax": jax.__version__, "libtpu": libtpu}
        # Seconds inside XLA's backend compile, persistent-cache loads
        # included, and how many of those were cache hits (jax.monitoring).
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

        if dry:
            # Same control flow, toy sizes; the Pallas kernels run
            # interpreted, so there is no Mosaic call to count.
            self.seq = 32
            self.gpt_batch = 2
            self.gpt_cfg = dict(block_q=16, block_k=16,
                                flash_interpret=True)
            self.gpt_preset = functools.partial(models.gpt_tiny,
                                                num_layers=1)
            self.resnet = models.ResNet(
                stage_sizes=(1, 1), num_classes=16, num_filters=8,
                block_cls=models.resnet.BottleneckBlock)
            self.image_batch, self.image_size, self.classes = 4, 16, 16
            self.serve = dict(max_seq=64, max_batch=4, token_budget=64,
                              warmup_buckets=(32,))
            self.prompt_range, self.new_tokens = (17, 30), 4
        else:
            self.seq = 2048
            self.gpt_batch = 8
            # 1024/1024: the largest 128-aligned pair that fits the 16 MiB
            # scoped VMEM at these shapes.
            self.gpt_cfg = dict(block_q=1024, block_k=1024)
            self.gpt_preset = models.gpt_small
            self.resnet = models.ResNet50(num_classes=1000)
            self.image_batch, self.image_size, self.classes = 128, 224, 1000
            self.serve = dict(max_seq=1024, max_batch=8, token_budget=1024,
                              warmup_buckets=(128, 256, 512))
            self.prompt_range, self.new_tokens = (100, 500), 32
        self.dtype = jnp.bfloat16

    def _on_secs(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def say(self, text: str) -> None:
        print(f"{self.tag}{text}", flush=True)

    def observe(self, leg: str, **values) -> None:
        """One line per leg: smoke observations, not benchmark metrics."""
        self.say(f"smoke-observation {leg} "
                 + json.dumps({**self.stamp, **values}, sort_keys=True))

    def memory(self) -> dict:
        """Device memory as the backend reports it, worst device; the peak
        is the process's high-water mark so far.  None where the backend
        reports nothing (the CPU)."""
        stats = [d.memory_stats() or {} for d in self.devices]
        return {key: max((s[key] for s in stats if key in s), default=None)
                for key in ("bytes_in_use", "peak_bytes_in_use",
                            "bytes_limit")}


def run_steps(trainer, state, batch, steps: int):
    """``steps`` train steps on one batch; every timed step ends in a host
    fetch of its loss.  Returns (state, losses, seconds per step)."""
    import numpy as np

    losses, seconds = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        state, metrics = trainer.step(state, batch)
        losses.append(float(np.asarray(metrics["loss"])))
        seconds.append(time.perf_counter() - t0)
    return state, losses, seconds


def place(batch: dict, mesh, trainer) -> dict:
    """Put the global batch on the mesh once, one shard per device."""
    import jax
    from jax.sharding import NamedSharding
    return jax.device_put(batch, NamedSharding(mesh, trainer.batch_spec))


def compiled_step(trainer, state, batch):
    """The compiled train step (the one private reach: the jitted step
    lives on the trainer)."""
    return trainer._step_fn.lower(state, batch).compile()


def check_layout(smoke: Smoke, batch: dict, state, text: str) -> dict:
    """Multi-chip only: the batch is split, the parameters are everywhere
    and the step talks over the interconnect."""
    import jax

    devices = set(smoke.devices)
    for name, arr in batch.items():
        shards = arr.addressable_shards
        check({s.device for s in shards} == devices
              and len({str(s.index) for s in shards}) == smoke.n,
              f"batch[{name!r}] is not one distinct shard per device: "
              f"{[(s.device.id, s.index) for s in shards]}")
    for path, leaf in jax.tree_util.tree_leaves_with_path(state.params):
        check({s.device for s in leaf.addressable_shards} == devices,
              f"parameter {jax.tree_util.keystr(path)} is not addressable "
              f"on all {smoke.n} devices")
    all_reduces = len(re.findall(r"\ball-reduce(?:-start)?\(", text))
    check(all_reduces > 0, "the compiled step has no all-reduce")
    return {"all_reduces": all_reduces}


def train_leg(smoke: Smoke, leg: str, trainer, mesh, batch: dict) -> dict:
    """Six steps through ``Trainer``; returns the leg's observations after
    checking finite losses and the step counter."""
    import jax
    import numpy as np

    batch = place(batch, mesh, trainer)
    compile0, hits0 = smoke.compile_s, smoke.cache_hits
    state = trainer.init(jax.random.key(0), batch)
    state, losses, seconds = run_steps(trainer, state, batch, TRAIN_STEPS)
    check(all(np.isfinite(losses)), f"{leg}: non-finite loss in {losses}")
    check(int(state.step) == TRAIN_STEPS,
          f"{leg}: state.step is {int(state.step)}, not {TRAIN_STEPS}")
    seen = {"losses": [round(x, 4) for x in losses],
            "first_step_s": round(seconds[0], 2),
            "compile_s": round(smoke.compile_s - compile0, 2),
            "compile_cache_hits": smoke.cache_hits - hits0,
            "steady_step_ms": round(
                statistics.median(seconds[1:]) * 1e3, 2)}
    step = compiled_step(trainer, state, batch)
    text = step.as_text()
    seen["mosaic_calls"] = text.count('custom_call_target="tpu_custom_call"')
    if smoke.n > 1:
        seen.update(check_layout(smoke, batch, state, text))
    # The allocator's peak leaves out what a program needs while it runs;
    # the compiler's plan for the step has it.
    plan = step.memory_analysis()
    seen.update(step_argument_bytes=plan.argument_size_in_bytes,
                step_temp_bytes=plan.temp_size_in_bytes,
                **smoke.memory())
    return seen


def gpt_trainer(smoke: Smoke, mesh):
    import optax

    from horovod_tpu import models, training
    from horovod_tpu.parallel import GradSyncConfig
    model = models.TransformerLM(smoke.gpt_preset(
        max_seq_len=smoke.seq, attention="flash", dtype=smoke.dtype,
        **smoke.gpt_cfg))
    return training.Trainer(
        model, optax.adamw(3e-4), mesh,
        sync=GradSyncConfig(axes=("dp",), op="average",
                            compression="bf16"))


def gpt_leg(smoke: Smoke, mesh) -> None:
    from horovod_tpu import training

    trainer = gpt_trainer(smoke, mesh)
    vocab = trainer.model.cfg.vocab_size
    batch = training.synthetic_text_batch(
        smoke.gpt_batch * smoke.n, seq_len=smoke.seq, vocab_size=vocab)
    seen = train_leg(smoke, "gpt_train", trainer, mesh, batch)
    losses = seen["losses"]
    check(losses[-1] < losses[0],
          f"gpt_train: loss did not fall on a repeated batch: {losses}")
    if not smoke.dry:   # interpreted kernels are not Mosaic calls
        check(seen["mosaic_calls"] > 0,
              "gpt_train: no Mosaic custom call in the compiled step — "
              "the flash kernel is not in the program")
    # Which cross-entropy path the per-device logits took: the switch is
    # derived from the device's reported memory, so a smaller reported
    # limit flips it silently.
    logits = smoke.gpt_batch * smoke.seq * vocab
    threshold = training.ce_streaming_threshold()
    smoke.observe("gpt_train", ce_path="streaming" if logits >= threshold
                  else "dense", ce_logits=logits, ce_threshold=threshold,
                  batch_per_chip=smoke.gpt_batch, seq=smoke.seq, **seen)


def gpt_cross_check(smoke: Smoke, mesh) -> None:
    """The same global batch of 8 on the full mesh and on one device: the
    first three losses agree, so the gradient average over the
    interconnect matches no interconnect at all.  (AdamW normalizes the
    gradient's scale, so this catches a missing or partial reduction, not
    a wrong divisor.)"""
    import jax
    import numpy as np

    from horovod_tpu import training
    from horovod_tpu.parallel import MeshSpec, build_mesh

    check(XCHECK_GLOBAL_BATCH % smoke.n == 0,
          f"cross-check batch {XCHECK_GLOBAL_BATCH} does not divide over "
          f"{smoke.n} devices")
    one = build_mesh(MeshSpec(dp=1), devices=smoke.devices[:1])
    runs = {}
    for name, m in (("mesh", mesh), ("one_device", one)):
        trainer = gpt_trainer(smoke, m)
        batch = place(training.synthetic_text_batch(
            XCHECK_GLOBAL_BATCH, seq_len=smoke.seq,
            vocab_size=trainer.model.cfg.vocab_size), m, trainer)
        state = trainer.init(jax.random.key(0), batch)
        _, runs[name], _ = run_steps(trainer, state, batch, XCHECK_STEPS)
    rel = [abs(a - b) / abs(b)
           for a, b in zip(runs["mesh"], runs["one_device"])]
    smoke.observe("gpt_cross_check", global_batch=XCHECK_GLOBAL_BATCH,
                  losses_mesh=[round(x, 4) for x in runs["mesh"]],
                  losses_one_device=[round(x, 4)
                                     for x in runs["one_device"]],
                  rel_diff=[float(f"{r:.2e}") for r in rel])
    check(all(np.isfinite(rel)) and max(rel) <= XCHECK_RTOL,
          f"gpt_cross_check: {smoke.n}-device losses {runs['mesh']} differ "
          f"from one-device losses {runs['one_device']} by more than "
          f"{XCHECK_RTOL} relative")


def resnet_leg(smoke: Smoke, mesh) -> None:
    import optax

    from horovod_tpu import training
    from horovod_tpu.parallel import GradSyncConfig

    trainer = training.Trainer(
        smoke.resnet, optax.sgd(0.1, momentum=0.9), mesh,
        sync=GradSyncConfig(axes=("dp",), op="average",
                            compression="bf16"))
    batch = training.synthetic_image_batch(
        smoke.image_batch * smoke.n, image_size=smoke.image_size,
        num_classes=smoke.classes)
    seen = train_leg(smoke, "resnet_train", trainer, mesh, batch)
    smoke.observe("resnet_train", batch_per_chip=smoke.image_batch,
                  image_size=smoke.image_size, **seen)


def serve_once(smoke: Smoke, prompts: list, paged: bool) -> dict:
    """One ReplicaExecutor, 8 requests through its ingress queue, drained;
    returns rid -> generated tokens after checking the outcome."""
    from horovod_tpu.serving import ReplicaExecutor, ServeConfig

    leg = "serve_paged" if paged else "serve_dense"
    slo_ms = 120_000.0
    compile0, hits0 = smoke.compile_s, smoke.cache_hits
    t0 = time.perf_counter()
    executor = ReplicaExecutor(ServeConfig(
        model_cfg=smoke.gpt_preset(dtype=smoke.dtype), paged=paged,
        slo_ms=slo_ms, **smoke.serve))
    try:
        warm_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for toks in prompts:   # as loadgen.drive_ingress submits
            executor.stats["offered"] += 1
            check(executor.queue.submit(toks, smoke.new_tokens, slo_ms)
                  is not None, f"{leg}: a request was shed at ingress")
        executor.queue.close()
        executor.serve_loop(stop_when=lambda: True)
        wall_s = time.perf_counter() - t0   # completions are on the host
        stats, done = executor.stats, executor.completed
        check(stats["served"] == len(prompts) and len(done) == len(prompts)
              and stats["expired"] == 0 and stats["lost"] == 0,
              f"{leg}: served {stats['served']} of {len(prompts)} "
              f"(expired {stats['expired']}, lost {stats['lost']})")
        short = {rid: rec["tokens"] for rid, rec in done.items()
                 if rec["tokens"] != smoke.new_tokens}
        check(not short, f"{leg}: requests without exactly "
              f"{smoke.new_tokens} tokens: {short}")
        latencies = sorted(stats["latencies_ms"])
        tokens = sum(rec["tokens"] for rec in done.values())
        smoke.observe(
            leg, served=stats["served"], tokens=tokens,
            warmup_s=round(warm_s, 2),
            compile_s=round(smoke.compile_s - compile0, 2),
            compile_cache_hits=smoke.cache_hits - hits0,
            drain_s=round(wall_s, 2),
            request_ms_p50=round(statistics.median(latencies), 1),
            request_ms_max=round(latencies[-1], 1),
            ms_per_token=round(wall_s * 1e3 / tokens, 2),
            **smoke.memory())
        return {rid: rec["generated"] for rid, rec in done.items()}
    finally:
        executor.close()


def serve_leg(smoke: Smoke) -> None:
    import horovod_tpu as hvd

    rng = random.Random(1234)
    vocab = smoke.gpt_preset().vocab_size
    prompts = [[rng.randrange(2, vocab)
                for _ in range(rng.randint(*smoke.prompt_range))]
               for _ in range(SERVE_REQUESTS)]
    hvd.init()   # size 1, no rendezvous: the executor's collectives are local
    try:
        dense = serve_once(smoke, prompts, paged=False)
        paged = serve_once(smoke, prompts, paged=True)
    finally:
        hvd.shutdown()
    # Printed, not gated: with random bf16 weights a near-tie in the argmax
    # is noise, not a fault.
    first = sum(dense[r][0] == paged[r][0] for r in dense)
    every = sum(a == b for r in dense for a, b in zip(dense[r], paged[r]))
    smoke.observe("serve_layouts_agree",
                  first_tokens=f"{first} of {len(dense)}",
                  all_tokens=f"{every} of "
                             f"{len(dense) * smoke.new_tokens}")


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--dry-run-cpu", action="store_true",
        help="exercise the control flow on the CPU at toy sizes with "
             "interpreted kernels (for tests; proves nothing about the "
             "chip and says so on every line)")
    args = parser.parse_args(argv)
    if args.dry_run_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        # Two virtual devices, so the multi-chip checks run too.
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                       os.environ.get("XLA_FLAGS", ""))
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=2").strip()

    import jax
    backend = jax.default_backend()   # opens the chip, once
    if backend != ("cpu" if args.dry_run_cpu else "tpu"):
        print(f"chip_smoke: no TPU: jax.default_backend() is {backend!r} "
              f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r}). "
              "This script checks the chip and has no CPU fallback; "
              "--dry-run-cpu only exercises its control flow.",
              file=sys.stderr)
        return 1

    from horovod_tpu.common.compile_cache import configure_compile_cache
    from horovod_tpu.parallel import MeshSpec, build_mesh

    t_start = time.perf_counter()
    cache_dir = configure_compile_cache()
    smoke = Smoke(args.dry_run_cpu)
    smoke.say(f"chip_smoke: {smoke.n} x {smoke.stamp['device_kind']} "
              f"({smoke.stamp['platform']}), jax {smoke.stamp['jax']}, "
              f"libtpu {smoke.stamp['libtpu']}, compile cache {cache_dir}")
    mesh = build_mesh(MeshSpec(dp=smoke.n))

    gpt_leg(smoke, mesh)
    if smoke.n > 1:
        gpt_cross_check(smoke, mesh)
    resnet_leg(smoke, mesh)
    # The trainers' state died with the legs' frames; collect what cycles
    # kept so the replicas start from an empty chip.
    gc.collect()
    smoke.observe("before_serve", **smoke.memory())
    serve_leg(smoke)

    smoke.say(f"chip_smoke: all legs passed in "
              f"{time.perf_counter() - t_start:.1f} s")
    smoke.say(json.dumps({"ok": True, "device": {
        "platform": smoke.stamp["platform"],
        "kind": smoke.stamp["device_kind"], "count": smoke.n}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
