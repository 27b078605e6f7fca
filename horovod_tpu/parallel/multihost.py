"""Multi-host JAX world formation over the rendezvous control plane.

The TPU analogue of GlooContext initialization (reference:
horovod/common/gloo/gloo_context.cc:136-152): where the reference reads
HOROVOD_RANK/SIZE from the launcher's env and connects a Gloo full mesh
through the rendezvous HTTP store, we negotiate a JAX coordinator address
through the same KV store and call `jax.distributed.initialize`, after
which `jax.devices()` spans every process and `build_mesh` can lay a
hybrid ICI×DCN mesh over the whole pod.

Must run BEFORE any JAX backend initializes in the process (the same
constraint as NCCL unique-id exchange happening before the first
collective, reference: ops/nccl_operations.cc:61-94).
"""
from __future__ import annotations

import os
import socket
import threading
from typing import Any

import logging

logger = logging.getLogger(__name__)

_lock = threading.Lock()
_initialized_here = False
# (rank, size, kv, epoch) of the live world; drives ordered teardown.
_world: tuple | None = None

_COORD_SCOPE = "jaxdist"


def is_initialized() -> bool:
    return _initialized_here


def init_jax_distributed(rank: int, size: int, kv: Any = None,
                         coordinator_address: str | None = None,
                         local_device_ids: list[int] | None = None,
                         timeout: float = 120.0) -> bool:
    """Form the multi-process JAX world; returns True if initialized.

    Rank 0 picks a free port and publishes ``host:port`` under the
    ``jaxdist`` scope of the rendezvous KV store; everyone else blocks on
    that key, then all processes call ``jax.distributed.initialize``.
    Pass ``coordinator_address`` explicitly to skip the KV negotiation
    (e.g. on TPU pods where GCE metadata supplies it).
    """
    global _initialized_here
    with _lock:
        if _initialized_here or size <= 1:
            return _initialized_here
        import jax

        epoch = os.environ.get("HOROVOD_RENDEZVOUS_EPOCH", "0")
        key = f"coord:{epoch}"
        if coordinator_address is None:
            if kv is None:
                raise ValueError(
                    "init_jax_distributed needs a rendezvous KV client or "
                    "an explicit coordinator_address")
            if rank == 0:
                from ..runner.network import free_port
                host = socket.gethostbyname(socket.gethostname())
                coordinator_address = f"{host}:{free_port()}"
                kv.put(_COORD_SCOPE, key, coordinator_address.encode())
            else:
                coordinator_address = kv.wait(_COORD_SCOPE, key,
                                              timeout).decode()

        cpu_gloo = os.environ.get("JAX_PLATFORMS", "") == "cpu"
        if cpu_gloo:
            # Cross-process collectives on the CPU backend need the gloo
            # implementation (the virtual-mesh test path; real deployments
            # ride ICI/DCN through the TPU runtime instead).
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
            # The compile→barrier→dispatch pattern (Trainer.step →
            # kv_barrier) only shrinks skew if the post-barrier dispatch
            # can reload the AOT compile from a persistent cache —
            # lower().compile() does not seed jit's in-memory executable
            # cache.
            from ..common.compile_cache import configure_compile_cache
            configure_compile_cache()
            # JAX declines to persist programs that compiled faster than
            # jax_persistent_cache_min_compile_time_secs (default 1s), so
            # a fast-compiling step would silently repeat its AOT compile
            # after the barrier — exactly the skew the compile→barrier→
            # dispatch pattern exists to remove.  Persist everything.
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0)

        # Elastic worlds must SURVIVE peer death: without recoverability
        # the coordination service FATALs the surviving processes when the
        # shutdown barrier fails (absl fatal, not an exception), killing
        # the elastic retry loop before it can re-rendezvous.
        if os.environ.get("HOROVOD_ELASTIC"):
            jax.config.update("jax_enable_recoverability", True)
        heartbeat = int(os.environ.get(
            "HOROVOD_JAX_HEARTBEAT_TIMEOUT_SECONDS", "100"))
        logger.debug("jax.distributed.initialize rank=%d size=%d coord=%s",
                     rank, size, coordinator_address)
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=size, process_id=rank,
            local_device_ids=local_device_ids,
            heartbeat_timeout_seconds=heartbeat,
            initialization_timeout=int(timeout))
        if cpu_gloo:
            # Eagerly form the gloo transport pairs while every process
            # is still in init lockstep (reference parity: the gloo
            # context connects its pairs AT init, gloo_context.cc, not
            # lazily). Without this the pairs connect at the first REAL
            # collective — which under per-process compile skew can sit
            # beyond gloo's connect timeout and fail world formation
            # exactly when the program is largest.
            try:
                from jax.experimental import multihost_utils
                multihost_utils.sync_global_devices("horovod_tpu_init")  # hvdlint: disable=collective-under-lock -- init-time only: _lock orders init/shutdown on user threads (the background loop never takes it), every rank reaches this line by construction, and the barrier carries its own timeout
            except Exception:  # noqa: BLE001 - barrier is best-effort
                logger.debug("init barrier skipped", exc_info=True)
        global _world, _barrier_seq, _cpu_gloo_world
        _world = (rank, size, kv, epoch)
        # Every member of the (possibly re-formed elastic) world starts
        # the barrier sequence from zero — a survivor carrying its old
        # counter would wait on keys no newcomer ever writes.
        _barrier_seq = 0
        _cpu_gloo_world = cpu_gloo
        _initialized_here = True
        return True


_barrier_seq = 0
_cpu_gloo_world = False


def kv_barrier(tag: str, timeout: float = 300.0) -> None:
    """Rendezvous-KV barrier across the world — pure HTTP, NO collective.

    gloo forms a fresh transport context per compiled program, and its
    pair-connect timeout is a hardcoded ~30 s: any cross-rank skew
    larger than that (per-process compile of a big program on a loaded
    host) fails the program's FIRST collective with "Gloo context
    initialization failed: Connect timeout". A barrier that is itself a
    collective inherits the same bound, so this one rides the rendezvous
    KV instead. No-op outside a multi-process world.

    SYMMETRIC-CALL CONTRACT: every rank must call kv_barrier the same
    number of times, in the same order — keys are derived from an
    implicit per-process sequence counter, so an asymmetric extra call
    on one rank (e.g. constructing an extra Trainer, or ranks
    disagreeing on sync_compile_needed() because JAX_PLATFORMS differed
    at world formation) permanently misaligns every later barrier.
    hvdlint proves this contract statically (rank-gated-collective /
    duplicate-barrier-tag / dynamic-barrier-tag rules), and
    HOROVOD_FINGERPRINT checks the controller-plane half of it at
    runtime — see docs/analysis.md.  A
    timeout therefore means ONE of two distinct faults, and the raised
    error carries enough state (rank/tag/seq/waited-on key) to tell
    them apart: a dead or wedged peer (its key for THIS seq never
    appears), or a seq mismatch (the peer is alive but publishing under
    a different sequence number)."""
    global _barrier_seq
    if not _initialized_here or _world is None:
        return
    rank, size, kv, epoch = _world
    if kv is None or size <= 1:
        return
    with _lock:
        _barrier_seq += 1
        seq = _barrier_seq
    key = f"{epoch}:{tag}:{seq}"
    kv.put("barrier", f"{key}:{rank}", b"1")
    for r in range(size):
        try:
            kv.wait("barrier", f"{key}:{r}", timeout)
        except TimeoutError as exc:
            raise TimeoutError(
                _barrier_timeout_diagnosis(kv, key, rank, size, tag, seq,
                                           timeout)) from exc


def _barrier_timeout_diagnosis(kv, key: str, rank: int, size: int,
                               tag: str, seq: int,
                               timeout: float) -> str:
    """Name WHICH ranks are missing from the barrier (one probe per
    rank), cross-checked against the resilience liveness table when
    fault tolerance is on — the most common multihost debugging session
    ('who is stuck?') becomes a one-line answer instead of a single
    anonymous key timeout."""
    missing: list[int] = []
    for r in range(size):
        try:
            if kv.get("barrier", f"{key}:{r}") is None:
                missing.append(r)
        except Exception:  # noqa: BLE001 - KV gone: report what we know
            missing.append(r)
    dead: list[int] = []
    try:
        from ..resilience import active_state
        state = active_state()
        if state is not None:
            dead = sorted(set(missing) & state.failed_ranks())
    except Exception:  # noqa: BLE001 - diagnosis must never mask the timeout
        pass
    verdict = (f"rank(s) {dead} are DEAD/unreachable per the liveness "
               f"table — elastic recovery or HOROVOD_ON_FAILURE applies."
               if dead else
               "all missing ranks still heartbeat (or fault tolerance is "
               "off): either they are wedged/slow, or the barrier "
               "sequence numbers have diverged — every rank must call "
               "kv_barrier symmetrically (same count, same order); check "
               "for rank-dependent Trainer construction or JAX_PLATFORMS "
               "skew at world formation.")
    return (f"kv_barrier timeout: rank {rank}/{size} waited {timeout}s on "
            f"tag={tag!r} seq={seq}; missing ranks: "
            f"{missing or '<none — raced to completion>'} "
            f"(keys barrier/{key}:<r>). {verdict}")


def sync_compile_needed() -> bool:
    """True when the compile→barrier→dispatch pattern is required: a
    multi-process world on the CPU/gloo backend (see kv_barrier). Reads
    the decision RECORDED at world formation — a later JAX_PLATFORMS
    mutation must not make step-time behavior disagree with how the
    world was actually formed."""
    return _initialized_here and _cpu_gloo_world


def shutdown_jax_distributed() -> None:
    global _initialized_here, _world
    with _lock:
        if not _initialized_here:
            return
        import jax

        # ORDERED teardown under elastic.  With recoverability on, the
        # coordination service's shutdown barrier no longer blocks, so the
        # coordinator can tear the service down while peers are still
        # connected; a client that outlives the service is killed by
        # jaxlib's error-polling thread (LOG(FATAL), client.h:80 — the
        # callback that could soften it isn't reachable from Python, and
        # jaxlib 0.9's binding for it aborts on std::bad_cast).  A FATALed
        # survivor exits nonzero, gets its healthy host blacklisted, and
        # can sink the elastic job.  So: non-coordinator ranks disconnect
        # FIRST (service still up -> clean ShutdownTask, poll thread
        # stops), publishing a 'bye' marker to the rendezvous KV; the
        # coordinator waits for the markers (bounded grace — a dead peer
        # never writes one, and its agent is gone so it cannot FATAL)
        # before taking the service down.
        rank_size_kv = _world
        _world = None
        if rank_size_kv is not None and os.environ.get("HOROVOD_ELASTIC"):
            rank, size, kv, epoch = rank_size_kv
            if kv is not None and size > 1:
                import time
                if rank == 0:
                    # Dead peers never write a marker, so a plain
                    # wait-for-all would stall the full grace on every
                    # failure-triggered re-form.  Settle heuristic: live
                    # peers disconnect within moments of each other, so
                    # stop once no NEW marker has arrived for settle_s
                    # (grace remains the hard cap for starved hosts).
                    grace = float(os.environ.get(
                        "HOROVOD_JAX_TEARDOWN_GRACE_SECONDS", "30"))
                    settle = min(grace, float(os.environ.get(
                        "HOROVOD_JAX_TEARDOWN_SETTLE_SECONDS", "10")))
                    deadline = time.monotonic() + grace
                    last_progress = time.monotonic()
                    pending = set(range(1, size))
                    while pending:
                        now = time.monotonic()
                        if now > deadline or now > last_progress + settle:
                            break
                        for r in list(pending):
                            try:
                                if kv.get(_COORD_SCOPE,
                                          f"bye:{epoch}:{r}") is not None:
                                    pending.discard(r)
                                    last_progress = time.monotonic()
                            except Exception:  # noqa: BLE001 - kv gone
                                pending.clear()
                                break
                        if pending:
                            time.sleep(0.05)
                    if pending:
                        logger.warning(
                            "proceeding with coordination-service "
                            "teardown; ranks %s never disconnected "
                            "(dead peers cannot, live ones may FATAL)",
                            sorted(pending))
                else:
                    try:
                        jax.distributed.shutdown()
                    except Exception as exc:  # noqa: BLE001
                        logger.warning("jax.distributed.shutdown failed: "
                                       "%s", exc)
                        _force_clear_distributed_state()
                    try:
                        kv.put(_COORD_SCOPE, f"bye:{epoch}:{rank}", b"1")
                    except Exception:  # noqa: BLE001 - launcher gone
                        pass
                    _clear_backends()
                    _initialized_here = False
                    return
        try:
            jax.distributed.shutdown()
        except Exception as exc:  # noqa: BLE001 - best-effort teardown
            logger.warning("jax.distributed.shutdown failed: %s", exc)
            _force_clear_distributed_state()
        _clear_backends()
        _initialized_here = False


def _force_clear_distributed_state() -> None:
    """A failed disconnect (e.g. the coordinator tore down first after a
    peer death) leaves jax's global State partially populated, and the
    next initialize() would raise "should only be called once".  Finish
    the teardown field by field."""
    try:
        from jax._src import distributed as _dist_mod
        gs = _dist_mod.global_state
        for attr in ("preemption_sync_manager", "client", "service"):
            obj = getattr(gs, attr, None)
            if obj is not None:
                try:
                    obj.shutdown()
                except Exception:  # noqa: BLE001
                    pass
                setattr(gs, attr, None)
        gs.coordinator_address = None
    except Exception as exc:  # noqa: BLE001
        logger.warning("forced distributed-state cleanup failed: %s", exc)


def _clear_backends() -> None:
    """Evict the live backends: device lists from the old world would
    otherwise survive the shutdown, and the next jax.distributed.initialize
    (elastic re-rendezvous, SURVEY §7 "elastic re-init on TPU") could not
    re-form the client.  Validated in-process: see
    tests/test_elastic_integration.py (elastic XLA world) — shutdown →
    clear → initialize works on the gloo CPU plane."""
    try:
        import jax.extend.backend as _xb
        _xb.clear_backends()
    except Exception as exc:  # noqa: BLE001
        logger.warning("clear_backends failed: %s", exc)


def should_init(size: int, local_size: int = 1) -> bool:
    """Whether `hvd.init()` forms the multi-process JAX world.

    A TPU chip belongs to one process at a time, and a worker that
    initializes the TPU runtime opens EVERY local chip.  So workers that
    share a host (``local_size > 1``) must not each open the accelerator:
    under ``auto`` they form no JAX world, JAX in them is held to the CPU
    backend, and their tensors ride the shm/TCP host planes; forcing the
    world with ``HOROVOD_JAX_DISTRIBUTED=1`` is an error.  The supported
    way to compute on the chips of one host is ONE SPMD process driving
    all of them (``Trainer`` over ``build_mesh``); one process per host
    then forms the world across hosts.  A process pinned to the CPU
    backend (``JAX_PLATFORMS=cpu``: the tests) has no chip to contend
    for: ``auto`` forms no world there, ``1`` forms a gloo one."""
    from ..common import config
    mode = config.parse_tristate(config.JAX_DISTRIBUTED.get())
    if mode is False or size <= 1:
        return False
    cpu_pinned = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    if mode is True:
        if local_size > 1 and not cpu_pinned:
            raise RuntimeError(
                f"HOROVOD_JAX_DISTRIBUTED=1 with {local_size} workers on "
                "one host: each worker would initialize the accelerator "
                "runtime over every local chip, and a chip belongs to one "
                "process at a time.  Run one SPMD process per host "
                "(Trainer over build_mesh drives all local chips), or "
                "leave HOROVOD_JAX_DISTRIBUTED=auto so these workers ride "
                "the shm/TCP host planes.")
        return True
    if cpu_pinned:
        return False
    if local_size > 1:
        import jax
        jax.config.update("jax_platforms", "cpu")
        logger.warning(
            "%d workers share this host and a TPU chip belongs to one "
            "process at a time: this worker forms no JAX world and does "
            "not open the accelerator (JAX here is held to the CPU "
            "backend); eager tensors ride the shm/TCP host planes.  To "
            "compute on the chips run one SPMD process for all local "
            "chips (Trainer over build_mesh).", local_size)
        return False
    return True


def make_global_array(mesh, spec, array):
    """Build a global `jax.Array` from a process-local view of the full
    array: each process contributes only the shards the sharding places on
    its addressable devices. Works identically single- and multi-process
    (the multi-host data-feed path; the reference never needs this because
    each rank's framework owns its local batch outright)."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding

    sharding = NamedSharding(mesh, spec)
    arr = np.asarray(array)
    return jax.make_array_from_callback(arr.shape, sharding,
                                        lambda idx: arr[idx])


def make_global_batch(mesh, spec, batch: dict) -> dict:
    """`make_global_array` over a dict of per-example arrays."""
    import jax
    return {k: make_global_array(mesh, spec, v) if hasattr(v, "shape")
            else v for k, v in batch.items()}
