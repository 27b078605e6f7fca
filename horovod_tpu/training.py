"""SPMD training loop construction: the TPU-native DistributedOptimizer.

Reference shape: horovod's per-framework `DistributedOptimizer` wraps a
local optimizer and splices a gradient allreduce between backward and step
(reference: horovod/torch/optimizer.py:173-292,
horovod/tensorflow/__init__.py:427-502). On TPU the idiomatic equivalent
compiles the whole train step — forward, backward, fused gradient
allreduce, optimizer update — into ONE XLA program over the device mesh:
`shard_map` gives each device its batch shard, `sync_gradients` emits the
fused AllReduce HLOs that ride ICI, and the optimizer update runs
replicated. Zero host round-trips per step; negotiation cost is zero by
SPMD construction (every rank runs the identical program — the invariant
the reference's controller protocol exists to establish dynamically).
"""
from __future__ import annotations

import dataclasses
import os
import time
from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .common.logging import logger
from .parallel.collectives import allreduce
from .parallel.grad_sync import (GradSyncConfig, init_ring_optimizer_state,
                                 sync_and_apply, sync_gradients)
from .parallel.mesh import data_axes
from .parallel.sharding import ShardingRules
from .telemetry.spans import timed


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    """Replicated training state (params + optimizer + BN statistics)."""
    step: jax.Array
    params: Any
    opt_state: Any
    batch_stats: Any


# Above this size the loss streams over the vocab axis instead of
# materializing an fp32 log_softmax of the whole logits tensor (see
# ops/loss.py). Streaming is the memory-survival path, NOT a speed win:
# the on-TPU A/B at gpt-small benchmark scale (824M-element logits,
# v5e) measured dense 80.1k tok/s vs streaming 72.3k — the vocab-chunk
# scan serializes work XLA otherwise fuses. So the default threshold
# sits where the dense path's fp32 logits copy (4 bytes/elem, plus the
# bf16 logits and their gradient alongside) stops plausibly fitting:
# on a 16 GB chip that is 2^30 elements = 4 GiB fp32, i.e. HBM/16
# bytes-per-element of headroom — and the default SCALES by the local
# device's discoverable memory so a sub-16GB device (v5e-1-slice dev
# boxes, trimmed GPU partitions) streams earlier instead of OOMing.
# The benchmark config (824M) stays dense on 16 GB; the 8k-sequence
# long-context recipe (1.6G) stays streaming. Override via
# HOROVOD_STREAMING_CE_MIN_ELEMENTS (0 forces streaming everywhere).
_DEVICE_MEMORY_SENTINEL = object()
_device_memory_cache: Any = _DEVICE_MEMORY_SENTINEL


def _device_memory_bytes() -> int | None:
    """Discoverable memory of the first local device (None when the
    backend doesn't report it — e.g. the CPU backend)."""
    global _device_memory_cache
    if _device_memory_cache is _DEVICE_MEMORY_SENTINEL:
        limit = None
        try:
            stats = jax.local_devices()[0].memory_stats() or {}
            limit = stats.get("bytes_limit") \
                or stats.get("bytes_reservable_limit")
        except Exception:  # noqa: BLE001 - stats are best-effort
            limit = None
        _device_memory_cache = int(limit) if limit else None
    return _device_memory_cache


def ce_streaming_threshold() -> int:
    """Logit count at and above which `cross_entropy_loss` streams over
    the vocab axis: HOROVOD_STREAMING_CE_MIN_ELEMENTS, else a sixteenth
    of the local device's reported memory in bytes, else 2^30."""
    # Read per call (trace-time Python, so this is free): the documented
    # env override must work even when set after `import horovod_tpu`.
    raw = os.environ.get("HOROVOD_STREAMING_CE_MIN_ELEMENTS")
    if raw is not None:
        try:
            return int(raw)
        except ValueError as exc:
            raise ValueError(
                "HOROVOD_STREAMING_CE_MIN_ELEMENTS must be a plain "
                f"integer (got {raw!r})") from exc
    hbm = _device_memory_bytes()
    if hbm is not None:
        return max(hbm // 16, 1 << 20)
    return 1 << 30


_NO_BATCH = object()        # what ``next`` gives past the last batch


def _track_accuracy() -> bool:
    from .common import config
    return bool(config.TRACK_ACCURACY.get())


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       label_smoothing: float = 0.0) -> jax.Array:
    """Mean softmax cross entropy over integer labels (fp32 math)."""
    if logits.size >= ce_streaming_threshold():
        from .ops.loss import streaming_softmax_cross_entropy
        return streaming_softmax_cross_entropy(logits, labels,
                                               label_smoothing)
    num_classes = logits.shape[-1]
    onehot = jax.nn.one_hot(labels, num_classes, dtype=jnp.float32)
    if label_smoothing > 0.0:
        onehot = (1.0 - label_smoothing) * onehot \
            + label_smoothing / num_classes
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.mean(jnp.sum(onehot * logp, axis=-1))


class Trainer:
    """Builds and owns a compiled SPMD train step.

    >>> trainer = Trainer(model, optax.sgd(0.1), mesh)
    >>> state = trainer.init(jax.random.key(0), sample_batch)
    >>> state, metrics = trainer.step(state, batch)

    `sync` controls the gradient data plane exactly like the reference's
    env knobs control its fusion pipeline: fusion threshold bytes,
    fp16/bf16 wire compression (reference: torch/compression.py:46-63),
    and sum/average/adasum reduction.
    """

    def __init__(self, model: Any, tx: optax.GradientTransformation,
                 mesh: Mesh, *,
                 sync: GradSyncConfig | None = None,
                 param_rules: ShardingRules | None = None,
                 loss_fn: Callable = cross_entropy_loss,
                 batch_spec: P | None = None) -> None:
        self.model = model
        self.tx = tx
        self.mesh = mesh
        axes = data_axes(mesh) or ("dp",)
        self.sync = sync or GradSyncConfig(axes=axes, op="average")
        self.param_rules = param_rules or ShardingRules()
        self.loss_fn = loss_fn
        self.batch_spec = batch_spec if batch_spec is not None else P(axes)
        self._step_fn: Callable | None = None
        # AOT executable from the compile→barrier→dispatch path: dispatched
        # directly so the warm-up compile is never repeated (see step()).
        self._compiled: Callable | None = None
        # perfscope MFU ledger (telemetry/perfmodel.py): analytic FLOPs
        # per step, resolved once from the first batch's shape.  A step
        # is timed only where the host waits for the device (``fit``:
        # an epoch's metrics fetch), as that interval over the steps
        # dispatched in it: the distance between two dispatches says
        # nothing of the device, and blocking in ``step`` would
        # serialize the async dispatch.
        self._step_flops: float | None = None
        # Cumulative host seconds: waiting for the next batch, and
        # inside the callbacks (``fit``); enqueueing steps (``step``).
        self.stats = {"steps": 0, "dispatch_s": 0.0, "data_wait_s": 0.0,
                      "callbacks_s": 0.0}
        # Fleet continuous deployment (fleet/deploy.py): rank 0 wires a
        # WeightPublisher in via attach_fleet_publisher; the host-side
        # step counter drives the publish cadence (the device step
        # number lives in donated buffers — syncing it every step to
        # test a modulus would serialize the async dispatch).
        self._fleet_publisher = None
        self._fleet_step = 0

    # -- initialization ----------------------------------------------------
    def init(self, rng: jax.Array, sample_batch: dict) -> TrainState:
        images = _model_input(sample_batch)
        variables = jax.eval_shape(
            partial(self.model.init, train=False), rng,
            jnp.zeros((1,) + images.shape[1:], images.dtype))
        # Runtime twin of hvdshard's HVD801/802 (same rule_coverage/
        # missing_axes core, real mesh + real param tree): a dead rule
        # or unknown-axis spec surfaces at init, loudly, instead of as
        # a silently replicated layout three days into a run.
        for problem in self.param_rules.validate(self.mesh,
                                                 variables["params"]):
            logger.warning("sharding rules: %s", problem)
        param_specs = self.param_rules.tree_specs(variables["params"])

        def _init():
            variables = self.model.init(
                rng, jnp.zeros((1,) + images.shape[1:], images.dtype),
                train=False)
            params = variables["params"]
            batch_stats = variables.get("batch_stats", {})
            return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                              opt_state=self._init_opt_state(params),
                              batch_stats=batch_stats)

        if self.sync.optimizer_in_ring:
            opt_specs = _ring_opt_state_specs(
                self.tx, variables["params"], self._ring_world(),
                self.sync)
        else:
            opt_specs = _opt_state_specs(self.tx, variables["params"],
                                         param_specs)
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s),
            TrainState(step=P(),
                       params=param_specs,
                       opt_state=opt_specs,
                       batch_stats=jax.tree_util.tree_map(
                           lambda _: P(),
                           variables.get("batch_stats", {}))),
            is_leaf=lambda x: isinstance(x, P))
        return jax.jit(_init, out_shardings=shardings)()

    def _ring_world(self) -> int:
        """World size of the optimizer-in-ring shard layout: the product
        of the sync axes' mesh sizes."""
        world = 1
        for a in self.sync.axes:
            world *= int(self.mesh.shape[a])
        return world

    def _init_opt_state(self, params):
        """Optimizer state: replicated tx.init(params) normally; with
        optimizer_in_ring, per-rank flat-shard states stacked on a
        leading world axis (sharded over the sync axes — ZeRO-style,
        each rank physically holds 1/world of the moments)."""
        if not self.sync.optimizer_in_ring:
            return self.tx.init(params)
        world = self._ring_world()
        base = init_ring_optimizer_state(self.tx, params, world,
                                         self.sync)
        return jax.tree_util.tree_map(
            lambda l: jnp.broadcast_to(l, (world,) + l.shape)
            if getattr(l, "ndim", 0) >= 1 else l, base)

    # -- the compiled step -------------------------------------------------
    def _build(self, state: TrainState) -> Callable:
        sync_cfg = self.sync
        # Manual-map only the data axes; model axes (tp/sp/ep/pp) stay in
        # GSPMD-automatic mode so the model code keeps global shapes and
        # XLA inserts the tensor-parallel collectives from the arrays' own
        # shardings (set at init).
        manual_axes = frozenset(sync_cfg.axes)
        state_specs = jax.tree_util.tree_map(lambda _: P(), state)
        if sync_cfg.optimizer_in_ring:
            if not manual_axes:
                raise ValueError(
                    "optimizer_in_ring needs explicit sync axes "
                    "(pure-GSPMD mode has no manual axis to shard the "
                    "update over)")
            # Stacked opt-state leaves ride sharded over the sync axes:
            # inside the manual region each rank sees its (1, chunk)
            # shard — the ZeRO layout sync_and_apply updates in place.
            state_specs = dataclasses.replace(
                state_specs,
                opt_state=jax.tree_util.tree_map(
                    lambda l: P(sync_cfg.axes)
                    if getattr(l, "ndim", 0) >= 2 else P(),
                    state.opt_state))

        def local_step(state: TrainState, batch: dict):
            def loss_of(params):
                variables = {"params": params}
                mutable: Any = False
                if state.batch_stats:
                    variables["batch_stats"] = state.batch_stats
                    mutable = ["batch_stats"]
                out = self.model.apply(variables, _model_input(batch),
                                       train=True, mutable=mutable)
                logits, updated = out if mutable else (out, {})
                with jax.named_scope("hvd.loss"):
                    loss = self.loss_fn(logits, batch["label"])
                return loss, (logits, updated)

            grad_fn = jax.value_and_grad(loss_of, has_aux=True)
            (loss, (logits, updated)), grads = grad_fn(state.params)

            if sync_cfg.optimizer_in_ring:
                # The fused horovod moment: reduce-scatter the gradient
                # pytree, apply the optax update on this rank's shard
                # (optimizer state sharded ZeRO-style), and all-gather
                # the UPDATED PARAMS instead of gradients.
                opt_local = jax.tree_util.tree_map(
                    lambda l: l[0] if getattr(l, "ndim", 0) >= 2 else l,
                    state.opt_state)
                params, opt_local = sync_and_apply(
                    self.tx, grads, state.params, opt_local, sync_cfg)
                opt_state = jax.tree_util.tree_map(
                    lambda l: l[None] if getattr(l, "ndim", 0) >= 1
                    else l, opt_local)
            else:
                # The horovod moment: fused, compressed allreduce of the
                # gradient pytree over the data axes.
                grads = sync_gradients(grads, sync_cfg)

                with jax.named_scope("hvd.optimizer"):
                    updates, opt_state = self.tx.update(grads,
                                                        state.opt_state,
                                                        state.params)
                    params = optax.apply_updates(state.params, updates)

            metrics = {"loss": allreduce(loss, sync_cfg.axes, "average")}
            if _track_accuracy():
                # For LM-head-sized logits the argmax is a full extra
                # read of a multi-GB tensor per step; the knob lets a
                # throughput run drop it (HOROVOD_TRACK_ACCURACY=0).
                acc = jnp.mean(
                    (jnp.argmax(logits, -1) == batch["label"]).astype(
                        jnp.float32))
                metrics["accuracy"] = allreduce(acc, sync_cfg.axes,
                                                "average")
            new_stats = updated.get("batch_stats", state.batch_stats)
            if state.batch_stats and getattr(self.model, "axis_name",
                                             None) is None:
                # Per-replica BN stats must stay replicated state: average
                # them over the data axes (what the reference achieves by
                # broadcasting rank 0's stats at checkpoints).
                new_stats = jax.tree_util.tree_map(
                    lambda x: allreduce(x, sync_cfg.axes, "average"),
                    new_stats)
            return dataclasses.replace(
                state, step=state.step + 1, params=params,
                opt_state=opt_state, batch_stats=new_stats), metrics

        if not manual_axes:
            # Pure-GSPMD mode (sync.axes == ()): no manual axes at all —
            # XLA derives every collective (incl. gradient reductions)
            # from the arrays' shardings. Required when the model embeds
            # its own shard_map regions (e.g. MoE expert-parallel over
            # "ep"), which cannot nest inside a manual region.
            def gspmd_step(state, batch):
                batch = {k: jax.lax.with_sharding_constraint(
                    v, NamedSharding(self.mesh, self.batch_spec))
                    for k, v in batch.items()}
                return local_step(state, batch)

            return jax.jit(gspmd_step, donate_argnums=(0,))

        # Manual over ALL mesh axes, not just the sync axes: Mosaic
        # (Pallas) custom calls reject partial-manual lowering — a
        # shard_map manual over {"dp"} inside a mesh that also carries
        # size-1 tp/pp/sp axes would raise "cannot be automatically
        # partitioned" on TPU. Models that embed their own shard_map
        # regions use the pure-GSPMD mode above instead.
        mapped = jax.shard_map(
            local_step, mesh=self.mesh,
            in_specs=(state_specs, self.batch_spec),
            out_specs=(state_specs, P()),
            axis_names=frozenset(self.mesh.axis_names),
            check_vma=False)
        return jax.jit(mapped, donate_argnums=(0,))

    def _note_step_flops(self, batch: dict) -> None:
        """Resolve the step's analytic FLOPs from the first batch."""
        if self._step_flops is not None:
            return
        from .telemetry import metrics as _telemetry_metrics
        tm = _telemetry_metrics()
        if not tm.enabled:
            return
        from .telemetry import perfmodel
        x = _model_input(batch)
        ndim = getattr(x, "ndim", 0)
        self._step_flops = perfmodel.model_step_flops(
            self.model, int(x.shape[0]) if ndim else 1,
            seq=int(x.shape[1]) if ndim == 2 else 0,
            image_size=int(x.shape[1]) if ndim == 4 else 224,
            train=True)
        tm.gauge("horovod_train_step_flops").set(self._step_flops)

    def _note_interval(self, seconds: float, steps: int) -> None:
        """Fold an interval that ended in a host fetch, and the steps
        dispatched in it, into the step-time histogram and the MFU
        gauge."""
        from .telemetry import metrics as _telemetry_metrics
        tm = _telemetry_metrics()
        if not tm.enabled or steps <= 0 or seconds <= 0.0:
            return
        from .telemetry import perfmodel
        dt = seconds / steps
        tm.histogram("horovod_train_step_ms").observe(dt * 1e3)
        # The step consumes the GLOBAL batch, so the denominator is the
        # whole mesh's peak, not one chip's.  A device kind without a
        # known peak gets no MFU gauge.
        peak = perfmodel.peak_flops(self.mesh.devices.flat[0].device_kind)
        if peak is not None and self._step_flops is not None:
            tm.gauge("horovod_train_mfu").set(perfmodel.mfu(
                self._step_flops, dt, peak * self.mesh.size))

    # -- fleet continuous deployment (fleet/) ------------------------------
    def attach_fleet_publisher(self, publisher) -> None:
        """Wire a fleet ``WeightPublisher`` in (rank 0 only — the
        publisher is the single writer of the ``fleet.pub`` scope):
        every ``step`` offers the params snapshot on the publish cadence
        and the serving world pulls it (docs/fleet.md)."""
        self._fleet_publisher = publisher

    def _fleet_publish(self, state: TrainState) -> None:
        # Called with the step's OUTPUT state: the input state's
        # buffers are donated to the step executable and deleted by
        # the time this runs.
        if self._fleet_publisher is None:
            return
        self._fleet_step += 1
        version = self._fleet_publisher.maybe_publish(
            self._fleet_step, {"params": state.params})
        if version is not None:
            logger.info("fleet: offered params snapshot v%d at host "
                        "step %d", version, self._fleet_step)

    def step(self, state: TrainState, batch: dict):
        if self._step_fn is None:
            self._step_fn = self._build(state)
            from .parallel import multihost
            if multihost.sync_compile_needed():
                # Compile → KV-barrier → dispatch: gloo's per-program
                # transport context connects at the program's first
                # collective, and per-rank compile skew beyond its
                # ~30 s connect timeout would fail the step outright
                # (multihost.kv_barrier docstring). The AOT executable
                # is KEPT and dispatched directly below — discarding it
                # and re-dispatching through jit would repeat the whole
                # compile whenever the persistent cache doesn't engage
                # (fast-compiling programs, cold cache dir), exactly the
                # skew the barrier exists to remove.
                try:
                    self._compiled = self._step_fn.lower(state,
                                                         batch).compile()
                finally:
                    multihost.kv_barrier("trainer-step-compile")
        self._note_step_flops(batch)
        with self._timed("dispatch"):
            result = self._dispatch(state, batch)
        self.stats["steps"] += 1
        self._fleet_publish(result[0])
        return result

    def _timed(self, what: str) -> timed:
        """``hvd.train.<what>`` in a profiler session, and its host
        seconds added to ``stats["<what>_s"]`` always."""
        return timed(self.stats, what + "_s", "train." + what)

    def _timed_batches(self, batches):
        """``batches``, with the wait for each one timed."""
        batches = iter(batches)
        while True:
            with self._timed("data_wait"):
                batch = next(batches, _NO_BATCH)
            if batch is _NO_BATCH:
                return
            yield batch

    def _dispatch(self, state: TrainState, batch: dict):
        """Enqueue one step on the executable."""
        if self._compiled is not None:
            try:
                return self._compiled(state, batch)
            except TypeError:
                # Shape/dtype drift vs the AOT signature (e.g. a ragged
                # final batch): the executable rejects the call before
                # dispatch (donated buffers untouched), so fall back to
                # the jit path, which re-specializes per signature.
                self._compiled = None
        return self._step_fn(state, batch)

    # -- fit loop with callbacks ------------------------------------------
    def fit(self, state: TrainState, data, epochs: int = 1,
            callbacks: Sequence[Any] = (), steps_per_epoch: int | None = None):
        """Minimal epoch loop hosting the reference's callback surface
        (reference: horovod/_keras/callbacks.py): ``data`` is either an
        iterable of batches (re-iterated per epoch) or a callable
        ``epoch -> iterable``. Returns (state, history)."""
        for cb in callbacks:
            if hasattr(cb, "set_trainer"):
                cb.set_trainer(self)
            if hasattr(cb, "set_state"):
                cb.set_state(state)
        history: list[dict] = []
        from .common import config
        fleet_runtime = None
        if config.FLEET.get():
            # --fleet runtime wiring: rank 0 hosts the controller and
            # the weight publisher; every rank's loop drives the
            # throttled train-gauge publish (fleet/wiring.py).
            from .fleet.wiring import attach_trainer, trainer_gauges
            fleet_runtime = attach_trainer(self)
        try:
            for cb in callbacks:
                cb.on_train_begin()
            for epoch in range(epochs):
                for cb in callbacks:
                    cb.on_epoch_begin(epoch)
                batches = data(epoch) if callable(data) else data
                sums: dict[str, Any] = {}
                count = 0
                # The interval the epoch's steps are timed over: it ends
                # where the host fetches the metrics, and starts after
                # the first step's dispatch where that one compiles.
                compiles = self._step_fn is None
                began = time.perf_counter()
                for i, batch in enumerate(self._timed_batches(batches)):
                    if steps_per_epoch is not None \
                            and i >= steps_per_epoch:
                        break
                    if callbacks:
                        with self._timed("callbacks"):
                            for cb in callbacks:
                                cb.on_batch_begin(i)
                    state, metrics = self.step(state, batch)
                    if compiles:
                        compiles, began = False, time.perf_counter()
                    # Keep metrics as device arrays through the epoch:
                    # float() here would sync host↔device every step and
                    # serialize the async dispatch pipeline.
                    if callbacks:
                        with self._timed("callbacks"):
                            for cb in callbacks:
                                cb.on_batch_end(i, metrics)
                    for k, v in metrics.items():
                        sums[k] = v if k not in sums else sums[k] + v
                    count += 1
                    if fleet_runtime is not None:
                        from . import core
                        fleet_runtime.publish_gauge(
                            lambda: core.global_state().size,
                            trainer_gauges)
                epoch_logs = {k: float(v) / max(count, 1)
                              for k, v in sums.items()}
                if sums:               # the fetch above waited for them
                    self._note_interval(time.perf_counter() - began, count)
                with self._timed("callbacks"):
                    for cb in callbacks:
                        if hasattr(cb, "set_state"):
                            cb.set_state(state)
                        cb.on_epoch_end(epoch, epoch_logs)
                history.append(epoch_logs)
            for cb in callbacks:
                cb.on_train_end()
        finally:
            if fleet_runtime is not None:
                fleet_runtime.close()
        return state, history

    # -- evaluation --------------------------------------------------------
    def eval_step(self, state: TrainState, batch: dict):
        @partial(jax.jit, static_argnums=())
        def _eval(state, batch):
            variables = {"params": state.params}
            if state.batch_stats:
                variables["batch_stats"] = state.batch_stats
            logits = self.model.apply(variables, _model_input(batch),
                                      train=False)
            loss = self.loss_fn(logits, batch["label"])
            acc = jnp.mean((jnp.argmax(logits, -1)
                            == batch["label"]).astype(jnp.float32))
            return {"loss": loss, "accuracy": acc}
        return _eval(state, batch)


def _opt_state_specs(tx: optax.GradientTransformation, params: Any,
                     param_specs: Any) -> Any:
    """Optimizer-state PartitionSpecs: moment-like leaves mirror the param
    layout, scalars replicate."""
    shapes = jax.eval_shape(tx.init, params)
    flat_params, _ = jax.tree_util.tree_flatten(params)
    by_shape = {}
    specs_flat, _ = jax.tree_util.tree_flatten(param_specs)
    for leaf, spec in zip(flat_params, specs_flat):
        by_shape.setdefault(leaf.shape, spec)

    def spec_for(leaf):
        return by_shape.get(getattr(leaf, "shape", ()), P())

    return jax.tree_util.tree_map(spec_for, shapes)


def _ring_opt_state_specs(tx: optax.GradientTransformation, params: Any,
                          world: int, sync: GradSyncConfig) -> Any:
    """PartitionSpecs for the stacked optimizer-in-ring state: leaves
    stacked on the leading world axis shard over the sync axes, scalars
    (step counts) replicate."""
    shapes = jax.eval_shape(
        lambda: jax.tree_util.tree_map(
            lambda l: jnp.broadcast_to(l, (world,) + l.shape)
            if getattr(l, "ndim", 0) >= 1 else l,
            init_ring_optimizer_state(tx, params, world, sync)))
    return jax.tree_util.tree_map(
        lambda l: P(sync.axes) if len(l.shape) >= 2 else P(), shapes)


def _model_input(batch: dict):
    """The model's input tensor: "image" for vision batches, "input" for
    token batches."""
    return batch["image"] if "image" in batch else batch["input"]


def synthetic_text_batch(batch_size: int, seq_len: int = 2048,
                         vocab_size: int = 32000, seed: int = 0) -> dict:
    """Random next-token-prediction batch: label[t] = input[t+1]."""
    tokens = jax.random.randint(jax.random.key(seed),
                                (batch_size, seq_len + 1), 0, vocab_size)
    return {"input": tokens[:, :-1], "label": tokens[:, 1:]}


def synthetic_image_batch(batch_size: int, image_size: int = 224,
                          num_classes: int = 1000,
                          seed: int = 0) -> dict:
    """Random batch matching the reference's synthetic benchmark inputs
    (reference: examples/pytorch/pytorch_synthetic_benchmark.py:55-58)."""
    k1, k2 = jax.random.split(jax.random.key(seed))
    return {
        "image": jax.random.normal(
            k1, (batch_size, image_size, image_size, 3), jnp.float32),
        "label": jax.random.randint(k2, (batch_size,), 0, num_classes),
    }
