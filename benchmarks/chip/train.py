"""The train driver: whole ``Trainer.step`` calls, back to back.

One process, one part after another.  A part is the same ``Trainer`` on a
mesh of the part's devices at the traffic's per-chip batch: weights from
``--seed`` (``seeded_state``: jitted calls on the device), one
device-resident batch from the seed, the step warmed, then the window.
The window's share of ``--seconds`` is split evenly over the parts and
holds whole steps only; every ``steps_per_sync`` steps the host waits for
the device (``block_until_ready``), and the window closes at the first
such wait past its share.  A part's state is freed before the next is
built.  ``setup_s`` is everything before the last window closes that is
not inside a window.

The traced run measures no end-to-end metric: its window is one such
chunk of steps a part, under one profiler session, so that the device's
idle share is that of the measured window's form.
"""
from __future__ import annotations

import gc
import math
import time


def make_batch(run, mesh, spec, global_batch: int) -> dict:
    """The part's one batch of token sequences, made on its devices from
    the seed.  Another kind of input is another driver module."""
    import jax
    from jax.sharding import NamedSharding

    def make(key):
        ids = jax.random.randint(
            key, (global_batch, run.traffic["seq_len"] + 1), 0,
            run.config["vocab_size"])
        return {"input": ids[:, :-1], "label": ids[:, 1:]}

    # The key is an argument: a constant would compile a program a seed.
    return jax.jit(make, out_shardings=NamedSharding(mesh, spec))(
        jax.random.key(run.seed))


def seeded_state(run, trainer, batch: dict):
    """The train state with weights from the seed.  ``Trainer.init``
    closes over its key, which XLA compiles in as a constant: every new
    seed would compile the whole initialiser again (35 s for ResNet-50;
    my chip run, PR 24).  So the state is built from a fixed key, and the
    weights are drawn again from the seed by the same initialisers in
    one jitted call that takes the key as an argument.  The model is so
    initialised twice a part, and ``setup_s`` pays the second; only a
    ``Trainer.init`` that takes its key as an argument saves it."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    state = trainer.init(jax.random.key(0), batch)
    sample = jnp.zeros((1,) + batch["input"].shape[1:],
                       batch["input"].dtype)
    draw = jax.jit(
        lambda key: trainer.model.init(key, sample, train=False)["params"],
        out_shardings=jax.tree_util.tree_map(lambda x: x.sharding,
                                             state.params))
    return dataclasses.replace(state,
                               params=draw(jax.random.key(run.seed)))


def bits_sum(shard):
    """A witness of a shard's exact bits, computed where the shard is."""
    import jax
    import jax.numpy as jnp
    flat = shard.reshape(-1)
    if flat.dtype.itemsize == 4:
        flat = jax.lax.bitcast_convert_type(flat, jnp.uint32)
    return jnp.sum(flat.astype(jnp.uint32), dtype=jnp.uint32)


def layout_problems(run, devices, batch: dict, params) -> list[str]:
    """Across chips: every device holds its own shard of the batch, and
    the named parameters are bit-equal on all of them, which a missing
    or partial gradient reduction would break."""
    import jax

    problems = []
    for name, arr in batch.items():
        shards = arr.addressable_shards
        if {s.device for s in shards} != set(devices) \
                or len({str(s.index) for s in shards}) != len(devices):
            problems.append(f"batch[{name}] is not one distinct shard a "
                            "device")
    witness = jax.jit(bits_sum)
    for path in run.config["replicated_check"]:
        leaf = params
        for key in path.split("."):
            leaf = leaf[key]
        sums = {int(witness(s.data)) for s in leaf.addressable_shards}
        if len(leaf.addressable_shards) != len(devices) or len(sums) != 1:
            problems.append(f"parameter {path} differs across devices "
                            f"after the window: {sorted(sums)}")
    return problems


def build_trainer(run, devices: list):
    """The configuration's ``Trainer`` on a data-parallel mesh of
    ``devices``: its model, optimizer and gradient sync."""
    import optax
    from horovod_tpu import training
    from horovod_tpu.parallel import GradSyncConfig, MeshSpec, build_mesh

    cfg = run.config
    sync, optimizer = cfg["sync"], cfg["optimizer"]
    return training.Trainer(
        run.build_model(),
        getattr(optax, optimizer["name"])(**optimizer["args"]),
        build_mesh(MeshSpec(dp=len(devices)), devices=devices),
        sync=GradSyncConfig(axes=tuple(sync["axes"]), op=sync["op"],
                            compression=sync["compression"]))


def run_part(run, part: dict, share_s: float) -> dict:
    import contextlib

    import jax
    import numpy as np

    traffic = run.traffic
    devices = run.devices[:part["devices"]]
    trainer = build_trainer(run, devices)
    per_chip = traffic["batch_per_chip"]
    batch = make_batch(run, trainer.mesh, trainer.batch_spec,
                       per_chip * len(devices))
    state = seeded_state(run, trainer, batch)
    jax.block_until_ready(state)
    run.mark(f"{part['name']}.state")
    losses = []
    for _ in range(traffic["warmup_steps"]):
        state, metrics = trainer.step(state, batch)
        losses.append(metrics["loss"])
    jax.block_until_ready((state, losses))
    run.mark(f"{part['name']}.warm")
    items_a_step = per_chip * traffic.get("seq_len", 1)

    compiles0 = run.compiles.count
    steps = 0
    # The traced window has the measured window's form, one chunk long.
    share_s = 0.0 if run.tracer.enabled else share_s
    # Readers of the reported part find it by this span, whatever its name.
    reported = run.tracer.span("train.reported") \
        if part["name"] == traffic["report_part"] \
        else contextlib.nullcontext()
    with run.tracer.window([d.id for d in devices]), reported:
        opened = closed = time.perf_counter()
        while steps == 0 or closed - opened < share_s:
            with run.tracer.span("train.chunk", part["name"]):
                for _ in range(traffic["steps_per_sync"]):
                    with run.tracer.span("train.step", part["name"]):
                        state, metrics = trainer.step(state, batch)
                    losses.append(metrics["loss"])
                jax.block_until_ready((metrics["loss"], state.step))
            steps += traffic["steps_per_sync"]
            closed = time.perf_counter()
    compiled_inside = run.compiles.count - compiles0

    losses = [float(x) for x in np.asarray(jax.device_get(losses))]
    problems = []
    if not all(math.isfinite(x) for x in losses):
        problems.append(f"{part['name']}: a loss is not finite")
    elif not losses[-1] < losses[0]:
        problems.append(f"{part['name']}: the loss did not fall on the "
                        f"repeated batch: {losses[0]} -> {losses[-1]}")
    if int(state.step) != len(losses):
        problems.append(f"{part['name']}: state.step is {int(state.step)} "
                        f"after {len(losses)} steps")
    if compiled_inside:
        problems.append(f"{part['name']}: {compiled_inside} compilations "
                        "inside the window")
    layout = layout_problems(run, devices, batch, state.params) \
        if len(devices) > 1 else []
    problems += layout
    name = part["name"]
    # Each number that was compared, beside its limit (the most it may be).
    compared = {
        f"{name}.losses_not_finite":
            [sum(not math.isfinite(x) for x in losses), 0],
        f"{name}.loss_last_less_first": [losses[-1] - losses[0], 0],
        f"{name}.state_step_off": [abs(int(state.step) - len(losses)), 0],
        f"{name}.compilations_in_window": [compiled_inside, 0],
        f"{name}.layout_problems": [len(layout), 0]}
    elapsed = closed - opened
    return {"devices": len(devices), "steps": steps, "elapsed_s": elapsed,
            "opened": opened, "closed": closed,
            "items_per_s_per_chip": steps * items_a_step / elapsed,
            "loss_first": losses[0], "loss_last": losses[-1],
            "problems": problems, "compared": compared}


def drive(run) -> dict:
    traffic = run.traffic
    parts = {}
    for part in traffic["parts"]:
        parts[part["name"]] = seen = run_part(
            run, part, run.seconds / len(traffic["parts"]))
        gc.collect()     # the part's state is gone before the next is built
        run.say(f"part {part['name']}: " + str(
            {k: v for k, v in seen.items()
             if k not in ("problems", "compared")}))
    reported = parts[traffic["report_part"]]
    last_close = max(p["closed"] for p in parts.values())
    in_windows = sum(p["elapsed_s"] for p in parts.values())
    end_to_end = {
        "train_items_per_s_per_chip": reported["items_per_s_per_chip"],
        "setup_s": last_close - run.t_start - in_windows}
    if "scaling" in traffic:
        end_to_end["scaling_efficiency"] = (
            parts[traffic["scaling"]["of"]]["items_per_s_per_chip"]
            / parts[traffic["scaling"]["over"]]["items_per_s_per_chip"])
    flops = run.count("flops_per_item")(run.config, traffic)
    counters = {"flops_per_item": flops,
                "model_flops_per_s_per_chip":
                    flops * reported["items_per_s_per_chip"]}
    problems = [p for seen in parts.values() for p in seen.pop("problems")]
    compared = {name: pair for seen in parts.values()
                for name, pair in seen.pop("compared").items()}
    steps = sum(p["steps"] for p in parts.values())
    return {"problems": problems, "attempted": steps,
            "end_to_end": end_to_end, "compared": compared,
            "counters": counters,
            "notes": {"flops_per_item": flops, "parts": parts,
                      "compiles_total": run.compiles.count}}
