"""Synthetic SPMD training benchmark (JAX-native path).

TPU-native analogue of the reference's synthetic benchmarks
(reference: examples/pytorch/pytorch_synthetic_benchmark.py): measures
end-to-end training throughput of the compiled train step — forward,
backward, fused gradient allreduce over the mesh, optimizer update.

    python examples/jax_synthetic_benchmark.py --model resnet50
    python examples/jax_synthetic_benchmark.py --model gpt --seq-len 2048
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import time

import jax
import optax
from jax.sharding import NamedSharding

from horovod_tpu import models, training
from horovod_tpu.parallel import GradSyncConfig, MeshSpec, build_mesh


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet50",
                   choices=["resnet50", "resnet101", "gpt"])
    p.add_argument("--batch-size", type=int, default=32,
                   help="per-device batch size")
    p.add_argument("--seq-len", type=int, default=2048)
    p.add_argument("--num-iters", type=int, default=10)
    p.add_argument("--num-warmup", type=int, default=3)
    args = p.parse_args()

    n_dev = len(jax.devices())
    mesh = build_mesh(MeshSpec(dp=n_dev))
    on_tpu = jax.default_backend() == "tpu"
    wire = "bf16" if on_tpu else "fp16"

    if args.model == "gpt":
        import jax.numpy as jnp
        cfg = models.gpt_small(
            max_seq_len=args.seq_len, remat=True,
            attention="flash" if on_tpu else "dense",
            dtype=jnp.bfloat16 if on_tpu else jnp.float32)
        model = models.TransformerLM(cfg)
        tx = optax.adamw(3e-4)
        batch = training.synthetic_text_batch(
            max(args.batch_size // 16, 1) * n_dev, seq_len=args.seq_len,
            vocab_size=cfg.vocab_size)
        units = "tokens"
        per_step = batch["input"].size
    else:
        model = {"resnet50": models.ResNet50,
                 "resnet101": models.ResNet101}[args.model](num_classes=1000)
        tx = optax.sgd(0.01, momentum=0.9)
        batch = training.synthetic_image_batch(args.batch_size * n_dev)
        units = "images"
        per_step = batch["image"].shape[0]

    trainer = training.Trainer(
        model, tx, mesh,
        sync=GradSyncConfig(axes=("dp",), op="average", compression=wire))
    # Placed once, one shard per device.
    batch = jax.device_put(batch, NamedSharding(mesh, trainer.batch_spec))
    state = trainer.init(jax.random.key(0), batch)

    for _ in range(max(args.num_warmup, 1)):  # >=1 keeps compile untimed
        state, metrics = trainer.step(state, batch)
    jax.block_until_ready(metrics)

    t0 = time.perf_counter()
    for _ in range(args.num_iters):
        state, metrics = trainer.step(state, batch)
    jax.block_until_ready(metrics)
    dt = time.perf_counter() - t0

    rate = per_step * args.num_iters / dt
    print(f"Model: {args.model} on {n_dev} device(s) "
          f"[{jax.default_backend()}]")
    print(f"Throughput: {rate:.1f} {units}/sec "
          f"({rate / n_dev:.1f} per device)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
