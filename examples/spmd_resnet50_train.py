"""SPMD ResNet-50 training — the reference's
examples/pytorch/pytorch_imagenet_resnet50.py slot, TPU-first: the whole
step (fwd + bwd + fused bf16 gradient allreduce + SGD momentum) compiles
into one XLA program over the chip mesh.

    python examples/spmd_resnet50_train.py --steps 20 --batch-size 128

One process drives every chip of the host.  Multi-host: launch one copy per
host under horovodrun-tpu (one slot per host) and the dp axis spans every
chip in the pod.
"""
import argparse
import time

import jax
import optax
from jax.sharding import NamedSharding

from horovod_tpu import models, training
from horovod_tpu.common.compile_cache import configure_compile_cache
from horovod_tpu.parallel import GradSyncConfig, MeshSpec, build_mesh


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--batch-size", type=int, default=128,
                        help="per-chip batch size")
    parser.add_argument("--steps", type=int, default=20)
    parser.add_argument("--image-size", type=int, default=224)
    parser.add_argument("--wire", default="bf16",
                        choices=["bf16", "fp16", "none"],
                        help="gradient wire compression")
    parser.add_argument("--adasum", action="store_true",
                        help="Adasum (scale-adaptive) gradient combine")
    args = parser.parse_args()

    configure_compile_cache()
    n = len(jax.devices())
    mesh = build_mesh(MeshSpec(dp=n))
    trainer = training.Trainer(
        models.ResNet50(num_classes=1000),
        optax.sgd(0.1, momentum=0.9), mesh,
        sync=GradSyncConfig(
            axes=("dp",),
            op="adasum" if args.adasum else "average",
            compression=None if args.wire == "none" else args.wire))

    # Placed once, one shard per chip; the default-device array would be
    # re-sharded from chip 0 on every step.
    batch = jax.device_put(
        training.synthetic_image_batch(args.batch_size * n,
                                       image_size=args.image_size),
        NamedSharding(mesh, trainer.batch_spec))
    state = trainer.init(jax.random.key(0), batch)
    state, metrics = trainer.step(state, batch)   # compile
    jax.block_until_ready(metrics)

    t0 = time.perf_counter()
    for _ in range(args.steps):
        state, metrics = trainer.step(state, batch)
    jax.block_until_ready(metrics)
    dt = time.perf_counter() - t0
    print(f"{args.batch_size * n * args.steps / dt:.1f} images/sec "
          f"({n} chip(s)); loss {float(metrics['loss']):.4f}")


if __name__ == "__main__":
    main()
