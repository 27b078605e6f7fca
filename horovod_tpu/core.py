"""Global state, background coordination thread, and the enqueue API.

TPU-native rebuild of the reference core runtime
(reference: horovod/common/operations.cc — InitializeHorovodOnce at 651-699,
BackgroundThreadLoop at 589-647, RunLoopOnce + PerformOperation at 256-329,
EnqueueTensor* at 919-1226) plus the handle/future layer
(reference: horovod/torch/handle_manager.cc).

Design: user threads enqueue TensorTableEntries + Requests; a single
background thread runs the controller protocol every CycleTime ms, receives
the identical fused ResponseList on every rank, and executes each Response
through the backend priority chain.  Completion flows back through per-entry
callbacks into Handle futures, never blocking the background thread.
"""
from __future__ import annotations

import os
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Sequence

import numpy as np

from .analysis.hvdshard.specs import spec_token
from .backend.base import OperationManager
from .backend.basic import BasicBackend
from .common import config
from .common.controller import Controller, LocalTransport
from .common.dtypes import from_any
from .common.group_table import GroupTable
from .common.logging import configure as configure_logging
from .common.logging import logger
from .common.message import (Request, RequestType, Response, ResponseType)
from .common.response_cache import ResponseCache
from .common.stall_inspector import StallInspector
from .common.status import Status
from .common.tensor_queue import TensorQueue, TensorTableEntry
from .common.timeline import Timeline

JOIN_TENSOR_NAME = "__join__"


class Handle:
    """Future for one (possibly grouped) async collective
    (reference: torch/handle_manager.cc)."""

    __slots__ = ("_event", "status", "entries", "_pending", "_hid",
                 "wrap_refs", "inplace_targets", "wants_recv_splits")

    def __init__(self, entries: list[TensorTableEntry]) -> None:
        self._event = threading.Event()
        self.status: Status | None = None
        self.entries = entries
        self._pending = len(entries)
        self._hid = -1
        # Original framework tensors (torch/jax/...) so async results can be
        # returned in the caller's framework, same as the sync API.
        self.wrap_refs: list[Any] = []
        # torch binding extras: in-place copy-back targets, alltoall
        # received-splits flag (see horovod_tpu/torch/mpi_ops.py).
        self.inplace_targets: list[Any] = []
        self.wants_recv_splits = False

    def done(self) -> bool:
        return self._event.is_set()

    def wait(self, timeout: float | None = None) -> Status:
        if not self._event.wait(timeout):
            raise TimeoutError("collective did not complete in time")
        assert self.status is not None
        return self.status

    def outputs(self) -> list[Any]:
        return [e.output for e in self.entries]


class HandleManager:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._next = 0
        self._handles: dict[int, Handle] = {}

    def allocate(self, entries: list[TensorTableEntry]) -> tuple[int, Handle]:
        handle = Handle(entries)
        with self._lock:
            hid = self._next
            self._next += 1
            handle._hid = hid
            self._handles[hid] = handle
        return hid, handle

    def get(self, hid: int) -> Handle:
        with self._lock:
            return self._handles[hid]

    def entry_done(self, handle: Handle, status: Status) -> None:
        with self._lock:
            handle._pending -= 1
            # First error wins; OK only recorded if nothing failed.
            if handle.status is None or (handle.status.ok_p()
                                         and not status.ok_p()):
                handle.status = status
            if handle._pending <= 0:
                # Auto-release: once complete, the caller's Handle reference
                # is the only owner — the table must not pin tensors forever.
                self._handles.pop(handle._hid, None)
                handle._event.set()

    def release(self, hid: int) -> None:
        with self._lock:
            self._handles.pop(hid, None)


class StreamDispatcher:
    """HOROVOD_NUM_STREAMS persistent worker threads executing the
    independent responses of one cycle concurrently — the multi-stream
    analogue of the reference's per-stream NCCL queues
    (HOROVOD_NUM_NCCL_STREAMS).  Workers live for the whole run (no
    per-cycle/per-response thread spawn); the background loop enqueues a
    cycle's responses with their deterministic stream assignment and
    blocks on the cycle latch, so the controller protocol still advances
    one fully-executed cycle at a time."""

    def __init__(self, num_streams: int) -> None:
        self.num_streams = num_streams
        self._queues: list[queue.Queue] = [queue.Queue()
                                           for _ in range(num_streams)]
        self._threads = [
            threading.Thread(target=self._worker, args=(k,), daemon=True,
                             name=f"hvd-stream-{k}")
            for k in range(num_streams)]
        for t in self._threads:
            t.start()

    def run_cycle(self, work: list[tuple[int, Any]]) -> None:
        """Execute [(stream, thunk)] concurrently across the stream
        workers; returns when every thunk finished."""
        if not work:
            return
        remaining = len(work)
        lock = threading.Lock()
        done = threading.Event()

        def _count_down() -> None:
            nonlocal remaining
            with lock:
                remaining -= 1
                if remaining == 0:
                    done.set()

        for stream, thunk in work:
            self._queues[stream].put((thunk, _count_down))
        done.wait()

    def _worker(self, k: int) -> None:
        q = self._queues[k]
        while True:
            item = q.get()
            if item is None:
                return
            thunk, count_down = item
            try:
                thunk()
            except Exception as exc:  # noqa: BLE001 - entry.finish reports
                logger.error("stream %d execution failed: %s", k, exc)
            finally:
                count_down()

    def stop(self) -> None:
        for q in self._queues:
            q.put(None)
        for t in self._threads:
            t.join(timeout=5)


@dataclass
class GlobalState:
    rank: int = 0
    size: int = 1
    local_rank: int = 0
    local_size: int = 1
    cross_rank: int = 0
    cross_size: int = 1
    initialized: bool = False
    shutdown_requested: bool = False
    background_thread: threading.Thread | None = None
    tensor_queue: TensorQueue = field(default_factory=TensorQueue)
    group_table: GroupTable = field(default_factory=GroupTable)
    controller: Controller | None = None
    op_manager: OperationManager | None = None
    # Multi-stream response dispatch (HOROVOD_NUM_STREAMS): op_managers[k]
    # is stream k's backend chain (stream 0 = the full chain above;
    # streams 1.. carry per-stream TCP/basic instances over their own
    # PeerMesh channel sets).  active_streams <= len(op_managers) is the
    # runtime width (autotuner-adjustable through the ResponseList).
    op_managers: list[OperationManager] = field(default_factory=list)
    stream_dispatcher: StreamDispatcher | None = None
    tcp_collectives: list[Any] = field(default_factory=list)
    active_streams: int = 1
    handle_manager: HandleManager = field(default_factory=HandleManager)
    timeline: Timeline | None = None
    # Metrics registry (telemetry/; HOROVOD_METRICS).  Null when off so
    # hot paths test one attribute and skip all instrumentation.
    telemetry: Any = None
    # Flight recorder (telemetry/flight.py; HOROVOD_FLIGHT).  Null when
    # off; records a bounded ring of trace events and dumps it on every
    # structured failure.
    flight: Any = None
    # Chaos engine (resilience/chaos.py; HOROVOD_CHAOS).  None when off;
    # the background loop fires its deterministic response-level actions.
    chaos: Any = None
    parameter_manager: Any = None
    cycle_time_ms: float = 1.0
    joined: bool = False
    elastic_enabled: bool = False
    # Runtime default wire codec (autotuner override via the ResponseList
    # tuned_codec field); None = honor HOROVOD_COMPRESSION.
    codec_override: str | None = None
    # Resolved fabric layout (common/topology.Topology) from
    # HOROVOD_TOPOLOGY + the launcher env; drives ring orders, the torus
    # allreduce eligibility and the hierarchical level ladder.
    topology: Any = None
    # resources to close at shutdown (sockets, rendezvous server, ...)
    resources: list[Any] = field(default_factory=list)

    def mark_done_callback(self, handle: Handle):
        def _cb(status: Status) -> None:
            self.handle_manager.entry_done(handle, status)
        return _cb


_global = GlobalState()
_init_lock = threading.Lock()
_atexit_registered = False


def global_state() -> GlobalState:
    return _global


# ---------------------------------------------------------------------------
# Initialization / shutdown (reference: operations.cc:651-769)
# ---------------------------------------------------------------------------
def init(*, rank: int | None = None, size: int | None = None,
         rendezvous_addr: str | None = None,
         rendezvous_port: int | None = None,
         local_rank: int | None = None, local_size: int | None = None,
         cross_rank: int | None = None, cross_size: int | None = None) -> None:
    """Initialize the runtime: discover the world from env/args, connect the
    control plane, build backends, spawn the background thread."""
    with _init_lock:
        if _global.initialized:
            return

        # Under jsrun (LSF) every rank receives the same environment; rank
        # identity arrives via JSM/PMIx vars instead of per-slot env.
        from .runner.js_run import adopt_jsm_env
        adopt_jsm_env()

        def _resolve(kwarg, knob, fallback):
            if kwarg is not None:
                return kwarg
            env = knob.get()
            return env if env >= 0 else fallback

        rank = _resolve(rank, config.RANK, 0)
        size = _resolve(size, config.SIZE, 1)
        # Topology default: one host holding every rank (local == global),
        # matching single-host launches without explicit env.
        local_rank = _resolve(local_rank, config.LOCAL_RANK, rank)
        local_size = _resolve(local_size, config.LOCAL_SIZE, size)
        cross_rank = _resolve(cross_rank, config.CROSS_RANK, 0)
        cross_size = _resolve(cross_size, config.CROSS_SIZE, 1)

        configure_logging(rank)
        # Telemetry registry BEFORE any mesh/controller construction —
        # they cache metric handles from the configured registry.
        from . import telemetry as _telemetry
        _global.telemetry = _telemetry.configure(rank)
        _global.flight = _telemetry.flight.configure(rank)
        if _global.telemetry.enabled:
            # Every elastic transition re-inits, so the gauge tracks
            # grow/shrink without statesync having to be loaded.
            _global.telemetry.gauge(
                "horovod_world_size",
                "Live world size as seen by this rank's statesync "
                "service (tracks every elastic grow/shrink transition)"
            ).set(size)
        _global.rank, _global.size = rank, size
        _global.local_rank, _global.local_size = local_rank, local_size
        _global.cross_rank, _global.cross_size = cross_rank, cross_size
        # Fabric layout (HOROVOD_TOPOLOGY; common/topology.py).  The knob
        # is launcher-uniform, so every rank resolves the same Topology —
        # the ring orders / torus shape derived below are rank-symmetric
        # by construction.
        from .common import topology as _topology
        # The launcher exports the full rank→host-index map (hosts.py
        # host_ids_env) for layouts that break the homogeneous host-major
        # assumption behind local/cross-size auto-detection; a map whose
        # length doesn't match the world (stale env across an elastic
        # resize) is ignored rather than trusted.
        host_ids = config.HOST_IDS.get()
        hosts = None
        if host_ids:
            try:
                parsed = tuple(int(x) for x in host_ids.split(","))
            except ValueError:
                parsed = ()
            if len(parsed) == size:
                hosts = parsed
        topo = _topology.resolve(size, local_size, cross_size, hosts=hosts)
        _global.topology = topo
        _global.cycle_time_ms = config.CYCLE_TIME.get()
        _global.shutdown_requested = False
        _global.tensor_queue.reset()
        _global.joined = False
        _global.elastic_enabled = config.ELASTIC.get()
        _global.tcp_collectives = []
        _global.stream_dispatcher = None
        _global.active_streams = 1

        timeline_path = config.TIMELINE.get()
        # EVERY rank records its own trace file (cross-rank stitching,
        # telemetry/trace.py): rank 0 keeps the exact configured path,
        # ranks > 0 get the '.r<rank>' suffix (timeline.rank_path) —
        # pre-PR behavior gave only rank 0 a file, so a merged trace and
        # critical-path attribution were structurally impossible.
        _global.timeline = Timeline(
            timeline_path,
            mark_cycles=config.TIMELINE_MARK_CYCLES.get(), rank=rank)

        backends = []
        if size > 1:
            addr = rendezvous_addr or config.RENDEZVOUS_ADDR.get()
            port = rendezvous_port if rendezvous_port is not None \
                else config.RENDEZVOUS_PORT.get()
            if not addr or port <= 0:
                raise RuntimeError(
                    "Multi-process world requires a rendezvous server: set "
                    "HOROVOD_GLOO_RENDEZVOUS_ADDR/PORT (the launcher does "
                    "this automatically).")
            from .common.tcp_transport import TcpTransport
            from .backend.tcp import TcpBackend, TcpCollectives
            from .runner.network import PeerMesh, RendezvousClient

            timeout = config.GLOO_TIMEOUT_SECONDS.get()
            kv = RendezvousClient(addr, port, timeout)
            epoch = os.environ.get("HOROVOD_RENDEZVOUS_EPOCH", "0")
            # Resilience BEFORE any mesh/shm formation: every PeerMesh
            # and ShmWorld captures the process ResilienceState (and the
            # chaos engine) at construction.  None when
            # HOROVOD_FAULT_TOLERANCE is off — the zero-overhead mode.
            from . import resilience
            _global.chaos = resilience.chaos.configure(rank)
            resilience.configure(rank, size, kv, epoch)
            # Form the multi-process JAX world FIRST (before any backend
            # below touches jax) — the analogue of GlooContext rendezvous
            # at init (reference: gloo/gloo_context.cc:136-152).
            from .parallel import multihost
            if multihost.should_init(size, local_size):
                multihost.init_jax_distributed(
                    rank, size, kv=kv,
                    timeout=max(timeout, 120.0))
            # XLA/ICI data plane (the NCCL-ops slot, reference:
            # operations.cc:143-252): first in the chain; enabled() falls
            # through to TCP when the JAX world doesn't span the ranks.
            xla_mode = config.parse_tristate(config.XLA_OPERATIONS.get())
            if xla_mode is True and not multihost.is_initialized():
                # Required mode must fail loudly, not silently degrade to
                # the TCP ring at a fraction of the bandwidth.
                raise RuntimeError(
                    "HOROVOD_XLA_OPERATIONS=1 requires the multi-process "
                    "JAX world; it did not form (check "
                    "HOROVOD_JAX_DISTRIBUTED and coordinator logs).")
            if xla_mode is not False and multihost.is_initialized():
                from .backend.xla import XlaBackend, XlaCommunicator
                backends.append(XlaBackend(XlaCommunicator(), size))
            # Same-host shared-memory plane (reference: Gloo shm transport
            # / MPI shared-memory windows): beats the TCP loopback ring
            # ~2x on intra-host worlds; formation is collective and
            # unanimous through the KV store.  Appended to the chain
            # AFTER the hierarchical backend below: an explicit
            # --hierarchical-* knob is a user decision and outranks the
            # auto-formed plane.
            shm_backend = None
            shm_mode = config.parse_tristate(config.SHM_OPERATIONS.get())
            shm_capacity = config.SHM_CAPACITY.get() or \
                max(config.FUSION_THRESHOLD.get(), 64 * 1024 * 1024)
            if shm_mode is not False:
                from .backend.shm import ShmBackend, ShmWorld
                shm_world = ShmWorld(
                    rank, size, kv, scope=f"shm{epoch}",
                    capacity=shm_capacity, timeout=timeout)
                if shm_world.formed:
                    _global.resources.append(shm_world)
                    shm_backend = ShmBackend(shm_world)
                elif shm_mode is True:
                    raise RuntimeError(
                        "HOROVOD_SHM_OPERATIONS=1 requires every rank on "
                        "one host/memory domain; formation failed.")
            ctrl_mesh = PeerMesh(rank, size, kv, scope=f"ctrl{epoch}",
                                 timeout=timeout)
            data_mesh = PeerMesh(rank, size, kv, scope=f"data{epoch}",
                                 timeout=timeout)
            _global.resources.extend([ctrl_mesh, data_mesh])
            transport = TcpTransport(ctrl_mesh)
            # Per-rank clock-offset estimate against the coordinator
            # (round-trip probes; the FIRST frames on the ctrl mesh, so
            # they precede every protocol frame on all ranks).  Recorded
            # as trace metadata — never applied to live timestamps.
            clock_offset_us, clock_rtt_us = transport.estimate_clock_offset()
            _global.timeline.set_clock_sync(clock_offset_us, clock_rtt_us)
            _global.flight.set_metadata(
                rank=rank, size=size, clock_offset_us=clock_offset_us,
                clock_rtt_us=clock_rtt_us)
            # Two-level eager path (reference: NCCLHierarchicalAllreduce,
            # nccl_operations.cc:187-398): refine the TCP plane with
            # local/cross sub-meshes when the knobs are on and the rank
            # layout is the launcher's homogeneous host-major assignment.
            hier_ar = config.HIERARCHICAL_ALLREDUCE.get()
            hier_ag = config.HIERARCHICAL_ALLGATHER.get()
            if (hier_ar or hier_ag) and topo.kind == "torus":
                # Declared torus (HOROVOD_TOPOLOGY=torus:RxC): the
                # hierarchical ladder follows the grid axes — RS along
                # the row, AR along the column, AG back along the row —
                # so every leg rides neighbor links.  The knob is
                # launcher-uniform and topology.parse degrades invalid
                # shapes to flat identically on every rank, so the
                # build decision is symmetric without a KV verdict.
                from .backend.hierarchical import HierarchicalTcpBackend
                t_row, t_col = divmod(rank, topo.cols)
                row_mesh = PeerMesh(
                    t_col, topo.cols, kv,
                    scope=f"htor{epoch}.r{t_row}", timeout=timeout)
                col_mesh = PeerMesh(
                    t_row, topo.rows, kv,
                    scope=f"htor{epoch}.c{t_col}", timeout=timeout)
                _global.resources.extend([row_mesh, col_mesh])
                backends.append(HierarchicalTcpBackend(
                    TcpCollectives(row_mesh),
                    TcpCollectives(col_mesh),
                    allreduce_on=hier_ar, allgather_on=hier_ag))
            elif hier_ar or hier_ag:
                # Every rank must make the SAME build-or-skip decision: a
                # rank skipping while peers form the sub-meshes would hang
                # their rendezvous.  The knob env is launcher-set (uniform),
                # and EVERY rank publishes a layout verdict — the verdict
                # itself carries per-rank eligibility (topology must be
                # two-level homogeneous host-major on every rank), so
                # heterogeneous slot counts unanimously fall back flat.
                layout_ok = (local_size > 1 and cross_size > 1 and
                             local_size * cross_size == size and
                             rank == cross_rank * local_size + local_rank)
                kv.put(f"hier{epoch}", f"ok:{rank}",
                       b"1" if layout_ok else b"0")
                all_ok = all(
                    kv.wait(f"hier{epoch}", f"ok:{r}", timeout) == b"1"
                    for r in range(size))
                if not all_ok:
                    logger.warning(
                        "hierarchical collectives requested but the rank "
                        "layout is not homogeneous host-major on every "
                        "rank (here: rank=%d local=%d/%d cross=%d/%d); "
                        "using the flat path", rank, local_rank,
                        local_size, cross_rank, cross_size)
                else:
                    from .backend.hierarchical import HierarchicalTcpBackend
                    local_mesh = PeerMesh(
                        local_rank, local_size, kv,
                        scope=f"hloc{epoch}.{cross_rank}", timeout=timeout)
                    cross_mesh = PeerMesh(
                        cross_rank, cross_size, kv,
                        scope=f"hcross{epoch}.{local_rank}", timeout=timeout)
                    _global.resources.extend([local_mesh, cross_mesh])
                    # Intra-host legs ride shm when the local ranks share
                    # a memory domain (per-host decision: the cross-leg
                    # pattern is identical either way, so hosts with and
                    # without shm interoperate).
                    hier_shm = None
                    if shm_mode is not False:
                        from .backend.shm import ShmWorld
                        hier_shm = ShmWorld(
                            local_rank, local_size, kv,
                            scope=f"hshm{epoch}.{cross_rank}",
                            capacity=shm_capacity, timeout=timeout)
                        if hier_shm.formed:
                            _global.resources.append(hier_shm)
                        else:
                            hier_shm = None
                    backends.append(HierarchicalTcpBackend(
                        TcpCollectives(local_mesh),
                        TcpCollectives(cross_mesh),
                        allreduce_on=hier_ar, allgather_on=hier_ag,
                        shm_local=hier_shm))
            # Topology-aware ring order + torus shape for the flat data
            # plane: a non-flat layout permutes the ring walk (grid
            # neighbors / host-adjacent slots) and, for a torus, enables
            # the two-phase row×column allreduce.  Identity order keeps
            # the pre-topology schedule bit-for-bit.
            ring_order = topo.ring_order() if topo.kind != "flat" else None
            torus_shape = (topo.rows, topo.cols) \
                if topo.kind == "torus" else None
            tcp_coll = TcpCollectives(data_mesh, ring_order=ring_order,
                                      torus=torus_shape)
            tcp_backend = TcpBackend(tcp_coll)
            _global.tcp_collectives = [tcp_coll]
            if shm_backend is not None:
                shm_backend.tcp = tcp_backend   # oversized-alltoall delegate
                backends.append(shm_backend)
            backends.append(tcp_backend)
            # Multi-stream response dispatch (HOROVOD_NUM_STREAMS): one
            # additional PeerMesh channel set + TCP backend chain per
            # stream, so concurrent responses never interleave bytes on a
            # shared socket and fusion staging buffers are per-stream.
            # Mesh formation is collective — the knob is launcher-set and
            # identical on every rank.
            num_streams = max(config.NUM_STREAMS.get(), 1)
            stream_managers: list[OperationManager] = []
            for s in range(1, num_streams):
                stream_mesh = PeerMesh(rank, size, kv,
                                       scope=f"data{epoch}.s{s}",
                                       timeout=timeout)
                _global.resources.append(stream_mesh)
                coll_s = TcpCollectives(stream_mesh,
                                        ring_order=ring_order,
                                        torus=torus_shape)
                _global.tcp_collectives.append(coll_s)
                tcp_s = TcpBackend(coll_s)
                basic_s = BasicBackend(size)
                tcp_s.stream = basic_s.stream = s
                tcp_s.timeline = basic_s.timeline = _global.timeline
                stream_managers.append(OperationManager([tcp_s, basic_s]))
            _global.active_streams = num_streams
            if num_streams > 1:
                _global.stream_dispatcher = StreamDispatcher(num_streams)
        else:
            transport = LocalTransport()
            stream_managers = []
            from . import resilience
            _global.chaos = resilience.chaos.configure(rank)
            _global.timeline.set_clock_sync(0.0, 0.0)
            _global.flight.set_metadata(rank=rank, size=size,
                                        clock_offset_us=0.0,
                                        clock_rtt_us=0.0)
        backends.append(BasicBackend(size))

        # Runtime collective-symmetry fingerprinting (HOROVOD_FINGERPRINT;
        # analysis/fingerprint.py): divergent ranks get a structured error
        # naming the first divergent op instead of a stall.
        from .analysis.fingerprint import FingerprintTracker
        _global.controller = Controller(
            rank=rank, size=size, transport=transport,
            tensor_queue=_global.tensor_queue,
            group_table=_global.group_table,
            response_cache=ResponseCache(config.CACHE_CAPACITY.get()),
            stall_inspector=StallInspector(),
            local_rank=local_rank, local_size=local_size,
            cross_rank=cross_rank, cross_size=cross_size,
            timeline=_global.timeline,
            fingerprint=FingerprintTracker.from_config())
        for backend in backends:
            backend.timeline = _global.timeline
        _global.op_manager = OperationManager(backends)
        _global.op_managers = [_global.op_manager] + stream_managers

        if config.AUTOTUNE.get():
            from .common.parameter_manager import ParameterManager
            _global.parameter_manager = ParameterManager(
                _global.controller, rank == 0)

        if _global.telemetry.enabled and config.METRICS_PORT.get() > 0:
            from .telemetry import MetricsExporter
            _global.resources.append(MetricsExporter(
                _global.telemetry, rank, config.METRICS_PORT.get()))

        _global.background_thread = threading.Thread(
            target=_background_loop, daemon=True, name="hvd-background")
        _global.initialized = True
        _global.background_thread.start()
        # On a TPU: the kernels' toolchain, imported while the program
        # loads its weights and not at the first trace of a kernel.
        from .common import prefetch
        kernels = prefetch.start_kernel_imports()
        if kernels is not None:
            _global.resources.append(kernels)
        # Finalize on interpreter exit like the reference (its library
        # destructor shuts Horovod down when the process ends): a script
        # that returns without calling hvd.shutdown() still flushes the
        # timeline writer and tears sockets/regions down cleanly.
        global _atexit_registered
        if not _atexit_registered:
            import atexit
            atexit.register(shutdown)
            _atexit_registered = True
        # hvdlife census witness (HOROVOD_LIFE_CENSUS): snapshot the
        # live thread/fd/socket/mmap fabric of the freshly formed world
        # — the elastic batteries diff these around grow/shrink cycles
        # (off mode: one cached knob read, nothing else).
        from .analysis.hvdlife import census as _census
        w = _census.witness()
        if w.enabled:
            w.note(f"world:{epoch if size > 1 else '0'}:{size}",
                   rank=rank)
        logger.debug("horovod_tpu initialized: rank=%d size=%d", rank, size)


def shutdown() -> None:
    with _init_lock:
        if not _global.initialized:
            return
        _global.shutdown_requested = True
        thread = _global.background_thread
    if thread is not None:
        thread.join(timeout=60)
    with _init_lock:
        if not _global.initialized:
            return   # a concurrent shutdown won the race past the join
        # Under the lock: only state flips and the queue abort (its
        # callbacks are event sets, never blocking).  The teardown that
        # can WAIT — stream-worker joins, the timeline writer join,
        # metrics dump file I/O, channel-close joins on possibly wedged
        # peers — runs below, outside the lock: hvdsan's HVD502 showed
        # that holding _init_lock across those joins lets one dead peer
        # stall every later init()/shutdown() caller for the full
        # close grace (docs/analysis.md, lock-hold manifest).
        _global.tensor_queue.finalize()
        dispatcher = _global.stream_dispatcher
        _global.stream_dispatcher = None
        timeline = _global.timeline
        telemetry = _global.telemetry
        resources = list(_global.resources)
        _global.resources.clear()
        # Drop the per-epoch object graph NOW, not at the next init():
        # the backend chains pin the TcpCollectives' per-(peer, dtype)
        # scratch views, which pin every closed channel's receive
        # scratch (multi-MB bytearrays) — without these resets one full
        # epoch's staging memory survived each reinit_world until the
        # next world happened to form (hvdlife's epoch-leak census
        # motivated the sweep, same shape as the HVD704 rule).
        _global.controller = None
        _global.op_manager = None
        _global.op_managers = []
        _global.tcp_collectives = []
        _global.parameter_manager = None
        _global.active_streams = 1
        _global.initialized = False
        _global.background_thread = None
    if dispatcher is not None:
        dispatcher.stop()
    if timeline is not None:
        timeline.stop()
    if telemetry is not None and telemetry.enabled:
        metrics_file = config.METRICS_FILE.get()
        if metrics_file:
            from .telemetry import dump_json
            try:
                dump_json(telemetry, metrics_file, _global.rank)
            except OSError as exc:
                logger.warning("telemetry: metrics dump to %s "
                               "failed: %s", metrics_file, exc)
    for res in resources:
        try:
            res.close()
        except Exception:  # noqa: BLE001 - best-effort cleanup
            pass
    from . import resilience
    resilience.shutdown()   # stop the heartbeat monitor (if any)
    from .parallel import multihost
    multihost.shutdown_jax_distributed()
    from .analysis.hvdlife import census as _census
    w = _census.witness()
    if w.enabled:
        w.note("down:%s" % os.environ.get("HOROVOD_RENDEZVOUS_EPOCH",
                                          "0"))


def reinit_world(*, rank: int, size: int, epoch: str) -> None:
    """Tear the world down and re-form it under a new rendezvous epoch
    with a (possibly) different rank/size — the elastic transition
    primitive shared by the serving shrink path (serving/replica.py)
    and the statesync grow/preemption transitions (statesync/service.py).
    Every mesh/shm/heartbeat scope keys on the epoch, so no stale state
    from the previous membership is ever touched; the env writes make
    the new identity survive any later env-driven re-init."""
    shutdown()
    os.environ["HOROVOD_RENDEZVOUS_EPOCH"] = epoch
    os.environ["HOROVOD_RANK"] = str(rank)
    os.environ["HOROVOD_SIZE"] = str(size)
    init()


def is_initialized() -> bool:
    return _global.initialized


def _require_init() -> GlobalState:
    if not _global.initialized:
        raise RuntimeError(
            "horovod_tpu has not been initialized; call hvd.init().")
    return _global


def rank() -> int:
    return _require_init().rank


def size() -> int:
    return _require_init().size


def local_rank() -> int:
    return _require_init().local_rank


def local_size() -> int:
    return _require_init().local_size


def cross_rank() -> int:
    return _require_init().cross_rank


def cross_size() -> int:
    return _require_init().cross_size


def is_homogeneous() -> bool:
    """True when every host runs the same number of ranks
    (reference: mpi_controller.cc:30-82 homogeneity check)."""
    st = _require_init()
    return st.size % max(st.local_size, 1) == 0 and \
        st.cross_size * st.local_size == st.size


def start_timeline(path: str, mark_cycles: bool = False) -> None:
    st = _require_init()
    if st.timeline is not None:
        st.timeline._mark_cycles = mark_cycles
        st.timeline.start(path)


def stop_timeline() -> None:
    st = _require_init()
    if st.timeline is not None:
        st.timeline.stop()


# ---------------------------------------------------------------------------
# Background loop (reference: operations.cc:589-647 RunLoopOnce)
# ---------------------------------------------------------------------------
def _background_loop() -> None:
    st = _global
    tm = st.telemetry
    tm_on = tm is not None and tm.enabled
    if tm_on:
        # Metric handles resolved once — the per-cycle cost is the update
        # itself (one uncontended per-metric lock), nothing else.
        m_cycle = tm.histogram(
            "horovod_controller_cycle_ms",
            "Background-loop cycle wall time (pop + sync + dispatch)")
        m_qdepth = tm.gauge(
            "horovod_controller_tensor_queue_depth",
            "Pending tensor-table entries after dispatch")
        m_fill = tm.histogram(
            "horovod_fusion_fill_ratio",
            "Fused-response payload bytes / fusion threshold")
    while True:
        t0 = time.monotonic()
        try:
            response_list = st.controller.compute_response_list(
                st.shutdown_requested)
        except Exception as exc:  # noqa: BLE001 - control-plane failure
            logger.error("controller failure: %s", exc)
            st.tensor_queue.finalize()
            return
        if st.timeline is not None:
            st.timeline.mark_cycle()

        # Pipeline autotune parameters apply BEFORE this cycle's dispatch:
        # they ride the identical broadcast ResponseList, so every rank
        # flips segment size / stream width on the same cycle and the
        # round-robin stream assignment below stays rank-symmetric.
        if response_list.tuned_segment_bytes >= 0:
            for coll in st.tcp_collectives:
                coll.segment_bytes = response_list.tuned_segment_bytes
        if response_list.tuned_num_streams > 0:
            st.active_streams = min(response_list.tuned_num_streams,
                                    max(len(st.op_managers), 1))
        if response_list.tuned_fused >= 0:
            # Fused-kernel dispatch flips on the same cycle on every rank
            # (both settings are bitwise identical AND frame-compatible,
            # so even a straggling flip cannot corrupt a reduce).  The
            # shm plane carries the same dispatch attribute.
            for coll in st.tcp_collectives:
                coll.fused = bool(response_list.tuned_fused)
            for mgr in (st.op_managers or
                        ([st.op_manager] if st.op_manager else [])):
                for be in mgr.backends:
                    if be.name == "shm":
                        be.fused = bool(response_list.tuned_fused)
        # Allreduce-algorithm autotune applies BEFORE dispatch for the
        # same reason as the pipeline knobs: all ranks flip on the same
        # broadcast cycle, so _select_algo stays rank-symmetric.
        if response_list.tuned_algo >= 0:
            from .common.topology import algo_name
            for coll in st.tcp_collectives:
                coll.algo = algo_name(response_list.tuned_algo)
        if response_list.tuned_tree_threshold >= 0:
            for coll in st.tcp_collectives:
                coll.tree_threshold = response_list.tuned_tree_threshold

        # Chaos harness (HOROVOD_CHAOS): deterministic response-level
        # fault injection fires HERE, on the coordinator-ordered
        # ResponseList — the global collective index is identical on
        # every rank, so a kill/freeze/fail at index N is replayable and
        # (for rank=*) rank-symmetric.
        if st.chaos is not None:
            for i, response in enumerate(response_list.responses):
                if response.response_type in (ResponseType.JOIN,
                                              ResponseType.ERROR):
                    continue
                if st.chaos.on_response(response.tensor_names) == "fail":
                    # REPLACE, never mutate: the original Response object
                    # may be held by the response cache, and an in-place
                    # flip to ERROR would poison every later cache hit.
                    response_list.responses[i] = Response(
                        response_type=ResponseType.ERROR,
                        tensor_names=list(response.tensor_names),
                        error_message=(
                            "chaos: injected collective failure "
                            f"(HOROVOD_CHAOS, tensors "
                            f"{response.tensor_names})"))

        if st.stream_dispatcher is not None \
                and len(response_list.responses) > 1:
            _dispatch_cycle(st, response_list.responses)
        else:
            for response in response_list.responses:
                _perform_operation(st, response)

        total_bytes = 0
        tensor_names: list[str] = []
        fusion_threshold = st.controller.fusion_threshold_bytes() \
            if tm_on else 0
        for response in response_list.responses:
            if response.response_type in (ResponseType.ALLREDUCE,
                                          ResponseType.ADASUM):
                from .common.dtypes import element_size
                resp_bytes = sum(response.tensor_sizes) * \
                    element_size(response.tensor_type)
                total_bytes += resp_bytes
                tensor_names.extend(response.tensor_names)
                if tm_on and fusion_threshold > 0 and \
                        len(response.tensor_names) > 1:
                    m_fill.observe(resp_bytes / fusion_threshold)

        # Autotune: coordinator scores the window and proposes new params;
        # every rank applies parameters broadcast through the ResponseList.
        if response_list.tuned_cycle_time_ms > 0:
            st.cycle_time_ms = response_list.tuned_cycle_time_ms
        if response_list.tuned_codec >= 0:
            from .compress import CompressionCodec, codec_name
            st.codec_override = codec_name(
                CompressionCodec(response_list.tuned_codec))
        if st.parameter_manager is not None:
            st.parameter_manager.observe(tensor_names, total_bytes)

        if response_list.shutdown:
            # Flip the visible flag: ranks that never submitted anything
            # (e.g. the stalled side of a one-sided collective) must be
            # able to observe that the world shut down around them.
            st.shutdown_requested = True
            st.tensor_queue.finalize()
            return

        elapsed = time.monotonic() - t0
        if tm_on:
            m_cycle.observe(elapsed * 1e3)
            st.controller.record_cycle(elapsed * 1e3)
            m_qdepth.set(st.tensor_queue.size())
        timeline = st.timeline
        if timeline is not None and timeline.enabled \
                and response_list.responses:
            # Counter tracks ("ph":"C") render queue depth and cumulative
            # wire bytes as series in the trace, next to the op spans.
            timeline.counter("tensor_queue_depth",
                             {"depth": st.tensor_queue.size()})
            if st.tcp_collectives:
                timeline.counter(
                    "wire_bytes",
                    {"sent": sum(c.mesh.bytes_sent
                                 for c in st.tcp_collectives),
                     "received": sum(c.mesh.bytes_received
                                     for c in st.tcp_collectives)})
        sleep_s = st.cycle_time_ms / 1000.0 - elapsed
        if sleep_s > 0:
            # Wake early on fresh enqueues (cached single-op latency is
            # otherwise dominated by this sleep), then grant a short
            # batching grace so bursts — per-gradient hooks firing during
            # backward — still fuse into one response like the
            # reference's fixed cadence achieves.
            if st.tensor_queue.wait_for_work(sleep_s):
                time.sleep(min(0.0003, st.cycle_time_ms / 5000.0))


def _perform_join(st: GlobalState, response: Response) -> None:
    st.joined = False
    if st.tensor_queue.has_tensor_entry(JOIN_TENSOR_NAME):
        entry = st.tensor_queue.pop_tensor_entry(JOIN_TENSOR_NAME)
        entry.output = np.int32(response.last_joined_rank)
        entry.finish(Status.ok())
        if st.timeline is not None and st.timeline.enabled:
            st.timeline.queue_end(JOIN_TENSOR_NAME,
                                  trace=response.trace_id())


def _pop_entries(st: GlobalState,
                 response: Response) -> list[TensorTableEntry]:
    """Pop the response's entries from the tensor table (background
    thread only — the queue has a single consumer) and close their
    negotiation spans."""
    entries: list[TensorTableEntry] = []
    for name in response.tensor_names:
        if st.tensor_queue.has_tensor_entry(name):
            entries.append(st.tensor_queue.pop_tensor_entry(name))
        else:
            # Joined rank: participate with a zero stand-in
            # (reference: controller.cc:254-308 joined-rank handling).
            entries.append(TensorTableEntry(tensor_name=name))
    # Stamp the response's cross-rank trace id on every entry: backend
    # sub-activity spans and the flight recorder read it from there, so
    # the planes need no extra plumbing (telemetry/trace.py).
    trace = response.trace_id()
    for e in entries:
        e.trace = trace
    timeline = st.timeline
    if timeline is not None and timeline.enabled:
        for e in entries:
            timeline.negotiate_end(e.tensor_name, trace=trace)
    return entries


def _execute_response(st: GlobalState, response: Response,
                      entries: list[TensorTableEntry],
                      stream: int = 0) -> None:
    """Execute one response on stream `stream`'s backend chain and finish
    its entries (runs on the background thread when streams == 1, on a
    stream worker otherwise)."""
    timeline = st.timeline
    trace = response.trace_id()
    if timeline is not None and timeline.enabled:
        for e in entries:
            timeline.activity_start(e.tensor_name,
                                    response.response_type.name,
                                    stream=stream, trace=trace)
    fl = st.flight
    fl_on = fl is not None and fl.enabled
    if fl_on:
        head = response.tensor_names[0] if response.tensor_names else ""
        fl.record("dispatch", head, trace=trace,
                  detail=f"{response.response_type.name.lower()}"
                         f" x{len(entries)} stream={stream}")

    if response.response_type == ResponseType.ERROR:
        status = Status.precondition_error(response.error_message)
    else:
        tm = st.telemetry
        tm_on = tm is not None and tm.enabled
        from .resilience import active_state, op_scope
        res = active_state()
        try:
            manager = st.op_managers[stream] if st.op_managers \
                else st.op_manager
            if tm_on:
                backend = manager.resolve(response, entries)
                plane = backend.name if backend is not None else "none"
                t0 = time.monotonic()
            if res is not None:
                # Label the blocking waits below for failure attribution
                # (RanksFailedError.op); off mode skips the string build.
                # The tightest propagated request deadline of the fused
                # entries bounds every transport wait of this op
                # (resilience.deadline_scope -> entry.deadline).
                deadlines = [e.deadline for e in entries
                             if e.deadline is not None]
                with op_scope(f"{response.response_type.name.lower()}"
                              f"({response.tensor_names[0]}"
                              f"{'…' if len(response.tensor_names) > 1 else ''})"
                              if response.tensor_names else
                              response.response_type.name.lower(),
                              deadline=min(deadlines) if deadlines
                              else None):
                    status = manager.execute_operation(response, entries)
            else:
                status = manager.execute_operation(response, entries)
            if tm_on:
                algo = getattr(backend, "last_algo", "none") \
                    if backend is not None else "none"
                _observe_collective(tm, response, plane, stream,
                                    (time.monotonic() - t0) * 1e3, algo,
                                    st)
        except Exception as exc:  # noqa: BLE001 - backend failure
            logger.error("collective execution failed: %s", exc)
            status = Status.unknown_error(str(exc))
            from .common.exceptions import RanksFailedError
            if fl_on and isinstance(exc, RanksFailedError):
                # A data-plane wait converted a dead/wedged peer into
                # the structured error: ship the evidence — the dump's
                # tail is the "dispatch" event of this in-flight op.
                fl.record("ranks-failed", head, trace=trace,
                          detail=str(exc)[:200])
                fl.dump(reason=str(exc))

    if timeline is not None and timeline.enabled:
        for e in entries:
            timeline.activity_end(e.tensor_name)

    if fl_on:
        fl.record("done" if status.ok_p() else "error", head,
                  trace=trace,
                  detail="" if status.ok_p() else status.reason[:200])

    # Release explicit groups everywhere — the coordinator deregisters
    # during response construction, but worker ranks would otherwise leak
    # one group per grouped collective.
    st.group_table.deregister_groups(response.tensor_names)

    for e in entries:
        e.finish(status)
    if timeline is not None and timeline.enabled:
        # Close the enqueue->callback spans AFTER the callbacks ran —
        # the span covers the waiter's full latency, not just dispatch.
        for e in entries:
            timeline.queue_end(e.tensor_name, trace=trace)


def _observe_collective(tm, response: Response, plane: str, stream: int,
                        latency_ms: float, algo: str = "none",
                        st: GlobalState | None = None) -> None:
    """Per-plane/per-codec collective latency+bytes, per-stream busy
    time, and the perfscope busbw observation (registry lookups are
    dict hits; metric objects are cached by the registry itself)."""
    from .common.dtypes import element_size
    from .compress import CompressionCodec, codec_name
    from .telemetry import perfmodel
    op = response.response_type.name.lower()
    codec = codec_name(CompressionCodec(response.codec))
    nbytes = sum(response.tensor_sizes) * element_size(response.tensor_type)
    tm.histogram(
        "horovod_collective_latency_ms",
        "End-to-end latency of one executed response, by data plane, "
        "op, wire codec and collective algorithm",
        labels={"plane": plane, "op": op, "codec": codec, "algo": algo}
    ).observe(latency_ms)
    tm.counter(
        "horovod_collective_algo_total",
        "Executed responses by collective algorithm (ring / tree / rhd "
        "/ torus / hierarchical / ... — the per-size selection verdict)",
        labels={"algo": algo}).inc(1)
    tm.counter(
        "horovod_collective_bytes_total",
        "Uncompressed payload bytes of executed responses (allgather "
        "counts per-rank first dims as elements)",
        labels={"plane": plane, "op": op}).inc(nbytes)
    tm.counter(
        "horovod_stream_busy_ms_total",
        "Cumulative execution time on each dispatch stream",
        labels={"stream": str(stream)}).inc(latency_ms)
    # perfscope (ISSUE 19): bus bandwidth per (plane, op, codec, algo,
    # size-bucket) — the nccl-tests normalization, so the ledger compares
    # cells across algorithms and world sizes on one scale.
    size = st.size if st is not None else 1
    if size > 1 and nbytes > 0 and latency_ms > 0.0:
        busbw = perfmodel.busbw_mbps(op, nbytes, latency_ms, size)
        bucket = perfmodel.size_bucket(nbytes)
        tm.histogram(
            "horovod_collective_busbw_mbps",
            "Bus bandwidth of one executed collective (busbw = algbw x "
            "op factor, MB/s) by data plane, op, wire codec, algorithm "
            "and payload size bucket — the perf ledger's raw table "
            "(telemetry/perfmodel.py)",
            labels={"plane": plane, "op": op, "codec": codec,
                    "algo": algo, "size_bucket": bucket}
        ).observe(busbw)
        peak = tm.gauge(
            "horovod_collective_busbw_peak_mbps",
            "Best bus bandwidth any collective demonstrated on this "
            "rank's data planes (the self-calibrated roofline when "
            "HOROVOD_PERF_PEAK_MBPS is unset)")
        if busbw > peak.value:
            peak.set(busbw)
        roof = float(config.PERF_PEAK_MBPS.get()) or peak.value
        tm.gauge(
            "horovod_collective_efficiency",
            "Roofline-relative bus-bandwidth efficiency of the most "
            "recent collective in each (plane, algo, size-bucket) cell: "
            "busbw / peak (HOROVOD_PERF_PEAK_MBPS, else the "
            "self-calibrated peak gauge)",
            labels={"plane": plane, "algo": algo, "size_bucket": bucket}
        ).set(busbw / roof if roof > 0.0 else 0.0)


def _perform_operation(st: GlobalState, response: Response) -> None:
    """Reference: operations.cc:256-329 PerformOperation."""
    if response.response_type == ResponseType.JOIN:
        _perform_join(st, response)
        return
    _execute_response(st, response, _pop_entries(st, response), stream=0)


def _dispatch_cycle(st: GlobalState, responses: list[Response]) -> None:
    """Multi-stream dispatch of one cycle's responses.

    Stream assignment is round-robin over the coordinator-ordered
    ResponseList, counting only stream-safe responses — both the order
    and each response's resolved backend are identical on every rank
    (enabled() checks are rank-symmetric by contract), so rank R's
    stream-k worker exchanges bytes exactly with every peer's stream-k
    worker and hvdlint's symmetric-call contract holds.  Responses whose
    plane keeps process-global protocol state (shm lockstep, XLA program
    order, hierarchical sub-meshes) all ride stream 0, preserving their
    relative execution order."""
    work: list[tuple[int, Any]] = []
    rr = 0
    for response in responses:
        if response.response_type == ResponseType.JOIN:
            _perform_join(st, response)
            continue
        entries = _pop_entries(st, response)
        stream = 0
        if response.response_type != ResponseType.ERROR:
            backend = st.op_managers[0].resolve(response, entries)
            if backend is not None and backend.stream_safe:
                stream = rr % max(st.active_streams, 1)
                rr += 1

        def _thunk(response=response, entries=entries, stream=stream):
            _execute_response(st, response, entries, stream=stream)

        work.append((stream, _thunk))
    st.stream_dispatcher.run_cycle(work)


# ---------------------------------------------------------------------------
# Enqueue API (reference: operations.cc:919-1226)
# ---------------------------------------------------------------------------
def _as_array(tensor) -> np.ndarray:
    """Stage a framework tensor as a numpy array (zero-copy where the
    framework allows it; torch CPU and jax host arrays both support the
    buffer protocol / __array__)."""
    return np.asarray(tensor)


def _enqueue(entries: list[TensorTableEntry],
             requests: list[Request]) -> tuple[int, Handle]:
    st = _require_init()
    hid, handle = st.handle_manager.allocate(entries)
    cb = st.mark_done_callback(handle)
    for e in entries:
        e.callback = cb
    # Open the enqueue->callback trace span BEFORE submission: the
    # background loop may pop and finish an entry before this thread
    # runs again, and a queue_end without its begin would be dropped.
    timeline = st.timeline
    tl_on = timeline is not None and timeline.enabled
    fl = st.flight
    # Per-request deadline propagation (serving SLOs): the enqueuing
    # thread's deadline_scope rides the entries to the dispatch thread,
    # which re-raises it through op_scope around the transport waits.
    from .resilience.context import pending_deadline
    deadline = pending_deadline()
    for e in entries:
        if deadline is not None:
            e.deadline = deadline
        if tl_on:
            timeline.queue_start(e.tensor_name)
        if fl is not None and fl.enabled:
            fl.record("enqueue", e.tensor_name)
    status = st.tensor_queue.add_to_tensor_queue_multi(entries, requests)
    if not status.ok_p():
        # Fail synchronously (duplicate name / shut down).
        for e in entries:
            e.callback = None
            if tl_on:
                timeline.queue_end(e.tensor_name)
        handle.status = status
        st.handle_manager.release(hid)
        handle._event.set()
    return hid, handle


def _resolve_codec(codec) -> tuple[int, int]:
    """(codec id, block size) for a Request: explicit argument beats the
    autotuner's runtime override beats the HOROVOD_COMPRESSION knob."""
    from .common import config as _config
    from .compress import (QUANTIZED_CODECS, CompressionCodec,
                           codec_from_name, default_block_size)
    if codec is None:
        codec = _global.codec_override
    if codec is None:
        codec = _config.COMPRESSION.get()
    c = codec_from_name(codec)
    if c not in QUANTIZED_CODECS:
        return int(c), 0
    bs = default_block_size()
    if bs <= 0:
        raise ValueError(
            f"HOROVOD_COMPRESSION_BLOCK_SIZE must be positive (got {bs})")
    if c == CompressionCodec.UINT4 and bs % 2:
        raise ValueError(
            "uint4 compression requires an even "
            f"HOROVOD_COMPRESSION_BLOCK_SIZE (got {bs})")
    return int(c), int(bs)


def enqueue_allreduce(name: str, tensor, *, op: str = "sum",
                      prescale_factor: float = 1.0,
                      postscale_factor: float = 1.0,
                      adasum: bool = False,
                      codec=None, spec=None) -> tuple[int, Handle]:
    return enqueue_grouped_allreduce([name], [tensor], op=op,
                                     prescale_factor=prescale_factor,
                                     postscale_factor=postscale_factor,
                                     adasum=adasum, register_group=False,
                                     codec=codec, spec=spec)


def enqueue_grouped_allreduce(names: Sequence[str], tensors: Sequence[Any], *,
                              op: str = "sum",
                              prescale_factor: float = 1.0,
                              postscale_factor: float = 1.0,
                              adasum: bool = False,
                              register_group: bool = True,
                              codec=None, spec=None) -> tuple[int, Handle]:
    """``spec`` annotates the tensor's sharding layout (a PartitionSpec,
    an axis-entry iterable, or an already-canonical token string); it
    rides the Request as the sp_spec wire field and joins the
    collective's fingerprint identity — op×name×dtype×dims×spec — when
    the mesh negotiated FEATURE_SHARDING (hvdshard; docs/analysis.md)."""
    st = _require_init()
    if op == "average":
        postscale_factor = postscale_factor / st.size
    elif op != "sum":
        raise ValueError(f"Unknown allreduce op: {op}")
    rtype = RequestType.ADASUM if adasum else RequestType.ALLREDUCE
    codec_id, codec_bs = _resolve_codec(codec)
    sp = spec_token(spec)
    entries, requests = [], []
    if register_group and len(names) > 1:
        st.group_table.register_group(list(names))
    for name, tensor in zip(names, tensors):
        arr = _as_array(tensor)
        entries.append(TensorTableEntry(tensor_name=name, tensor=arr))
        requests.append(Request(
            request_rank=st.rank, request_type=rtype,
            tensor_type=from_any(arr.dtype), tensor_name=name,
            tensor_shape=tuple(arr.shape),
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor,
            codec=codec_id, codec_block_size=codec_bs,
            sp_spec=sp))
    return _enqueue(entries, requests)


def enqueue_reducescatter(name: str, tensor, *, op: str = "sum",
                          prescale_factor: float = 1.0,
                          postscale_factor: float = 1.0,
                          spec=None) -> tuple[int, Handle]:
    """Reduce over all ranks, scatter dim-0 slices back (the eager analogue
    of upstream Horovod's reducescatter; rides the XLA device plane when
    dim 0 divides evenly, the TCP plane otherwise)."""
    st = _require_init()
    if op == "average":
        postscale_factor = postscale_factor / st.size
    elif op != "sum":
        raise ValueError(f"Unknown reducescatter op: {op}")
    arr = _as_array(tensor)
    entry = TensorTableEntry(tensor_name=name, tensor=arr)
    request = Request(request_rank=st.rank,
                      request_type=RequestType.REDUCESCATTER,
                      tensor_type=from_any(arr.dtype), tensor_name=name,
                      tensor_shape=tuple(arr.shape),
                      prescale_factor=prescale_factor,
                      postscale_factor=postscale_factor,
                      sp_spec=spec_token(spec))
    return _enqueue([entry], [request])


def enqueue_allgather(name: str, tensor, *, spec=None) -> tuple[int, Handle]:
    st = _require_init()
    arr = _as_array(tensor)
    entry = TensorTableEntry(tensor_name=name, tensor=arr)
    request = Request(request_rank=st.rank,
                      request_type=RequestType.ALLGATHER,
                      tensor_type=from_any(arr.dtype), tensor_name=name,
                      tensor_shape=tuple(arr.shape),
                      sp_spec=spec_token(spec))
    return _enqueue([entry], [request])


def enqueue_broadcast(name: str, tensor, root_rank: int, *,
                      spec=None) -> tuple[int, Handle]:
    st = _require_init()
    arr = _as_array(tensor)
    entry = TensorTableEntry(tensor_name=name, tensor=arr,
                             root_rank=root_rank)
    request = Request(request_rank=st.rank,
                      request_type=RequestType.BROADCAST,
                      tensor_type=from_any(arr.dtype), tensor_name=name,
                      root_rank=root_rank, tensor_shape=tuple(arr.shape),
                      sp_spec=spec_token(spec))
    return _enqueue([entry], [request])


def enqueue_alltoall(name: str, tensor,
                     splits=None) -> tuple[int, Handle]:
    st = _require_init()
    arr = _as_array(tensor)
    split_list = [int(x) for x in np.asarray(splits).reshape(-1)] \
        if splits is not None else []
    # Validate at ENQUEUE like the reference (operations.cc:1176): the
    # submitting rank fails fast before negotiation, so an invalid table
    # never reaches a pairwise exchange where a rank-local rejection
    # would strand peers mid-protocol.  resolve_alltoall_splits repeats
    # these checks defensively for internal callers.
    if split_list:
        if len(split_list) != st.size:
            raise ValueError(
                f"alltoall splits must have one entry per rank (got "
                f"{len(split_list)} for world size {st.size})")
        if any(s < 0 for s in split_list):
            raise ValueError(
                f"alltoall splits must be non-negative (got {split_list})")
        if sum(split_list) != arr.shape[0]:
            raise ValueError(
                f"alltoall splits sum to {sum(split_list)} but tensor "
                f"first dimension is {arr.shape[0]}")
    entry = TensorTableEntry(tensor_name=name, tensor=arr,
                             splits=split_list)
    request = Request(request_rank=st.rank,
                      request_type=RequestType.ALLTOALL,
                      tensor_type=from_any(arr.dtype), tensor_name=name,
                      tensor_shape=tuple(arr.shape))
    return _enqueue([entry], [request])


def enqueue_barrier() -> tuple[int, Handle]:
    st = _require_init()
    name = "__barrier__"
    entry = TensorTableEntry(tensor_name=name)
    request = Request(request_rank=st.rank, request_type=RequestType.BARRIER,
                      tensor_name=name)
    return _enqueue([entry], [request])


def enqueue_join() -> tuple[int, Handle]:
    """Graceful uneven-data exit (reference: operations.cc:1202-1226).

    After join() this rank keeps participating in negotiated collectives
    with zero stand-ins until every rank has joined."""
    st = _require_init()
    st.joined = True
    entry = TensorTableEntry(tensor_name=JOIN_TENSOR_NAME)
    request = Request(request_rank=st.rank, request_type=RequestType.JOIN,
                      tensor_name=JOIN_TENSOR_NAME)
    return _enqueue([entry], [request])
