"""horovod_tpu: a TPU-native distributed training framework.

Horovod-compatible public API (reference: horovod/torch/mpi_ops.py,
horovod/common/basics.py) over a TPU-first runtime:

- control plane: rendezvous KV + coordinator protocol with response caching
  over TCP (DCN), mirroring the reference's Gloo controller;
- data plane: XLA collectives (psum/all_gather/all_to_all/ppermute) compiled
  over the ICI device mesh inside jit for SPMD training, plus a CPU TCP ring
  backend for multi-process worlds without TPUs;
- the same semantics: tensor fusion, grouped ops, pre/postscale, Adasum,
  Join-based uneven-data handling, elastic state, timeline, autotune.

Synchronous ops return results in the caller's framework (numpy in → numpy
out, torch in → torch out, jax in → jax out).
"""
from __future__ import annotations

# hvdsan runtime witness (HOROVOD_SAN=1; analysis/hvdsan/san.py) must
# patch the threading factories BEFORE any package module creates a
# lock — core's module-level _init_lock is born a few imports below.
from .analysis.hvdsan import maybe_enable as _hvdsan_maybe_enable

_hvdsan_maybe_enable()

from typing import Any, Sequence

import numpy as np

from . import core
from .common.exceptions import (HorovodInternalError, HorovodTpuError,
                                HostsUpdatedInterrupt, RanksFailedError)
from .common.status import Status
from .core import (Handle, init, is_initialized, shutdown, rank, size,
                   local_rank, local_size, cross_rank, cross_size,
                   is_homogeneous, start_timeline, stop_timeline)


def run(func, args=(), kwargs=None, np=None, hosts=None, env=None,
        use_gloo=True, start_timeout=120.0, min_np=None, max_np=None,
        host_discovery_script=None, reset_limit=None,
        elastic_timeout=None, slots=None):
    """Programmatic N-worker launch of a function
    (reference: horovod/runner/__init__.py:92-210 horovod.run).
    min_np/max_np/host_discovery_script switch to the elastic driver."""
    from .runner.run_api import run as _run
    return _run(func, args=args, kwargs=kwargs, np=np, hosts=hosts,
                env=env, use_gloo=use_gloo, start_timeout=start_timeout,
                min_np=min_np, max_np=max_np,
                host_discovery_script=host_discovery_script,
                reset_limit=reset_limit, elastic_timeout=elastic_timeout,
                slots=slots)

__version__ = "0.1.0"


# --- Reduce-op markers (reference: horovod/common/basics.py Sum/Average/Adasum)
class _ReduceOp:
    def __init__(self, name: str) -> None:
        self.name = name

    def __repr__(self) -> str:
        return f"hvd.{self.name}"


Sum = _ReduceOp("Sum")
Average = _ReduceOp("Average")
Adasum = _ReduceOp("Adasum")


def _op_kind(op, average: bool | None) -> tuple[str, bool]:
    """Map (op, legacy average flag) → (sum|average, adasum?)."""
    if average is not None:
        if op is not None and op is not Average and op is not Sum:
            raise ValueError("Cannot specify both op and average")
        return ("average" if average else "sum"), False
    if op is None or op is Average:
        return "average", False
    if op is Sum:
        return "sum", False
    if op is Adasum:
        return "sum", True
    raise ValueError(f"Unknown reduce op: {op}")


# --- Framework-preserving output wrapping ----------------------------------
def _wrap_like(reference: Any, out: np.ndarray) -> Any:
    mod = type(reference).__module__
    if mod.startswith("torch"):
        import torch
        return torch.from_numpy(np.ascontiguousarray(out)).to(
            reference.dtype)
    if mod.startswith(("jax", "jaxlib")):
        import jax.numpy as jnp
        return jnp.asarray(out)
    return out


def _wrap_int_like(reference: Any, out: np.ndarray) -> Any:
    """Wrap an integer auxiliary result (e.g. received splits) into the
    caller's framework *keeping its integer dtype*."""
    mod = type(reference).__module__
    if mod.startswith("torch"):
        import torch
        return torch.from_numpy(np.ascontiguousarray(out))
    if mod.startswith(("jax", "jaxlib")):
        import jax.numpy as jnp
        return jnp.asarray(out)
    return out


def _result(handle: Handle, reference: Any) -> Any:
    status = handle.wait()
    status.raise_if_error()
    return _wrap_like(reference, handle.entries[0].output)


_name_counters: dict[str, int] = {}


def _auto_name(prefix: str, name: str | None) -> str:
    if name is not None:
        return name
    n = _name_counters.get(prefix, 0)
    _name_counters[prefix] = n + 1
    return f"{prefix}.noname.{n}"


# ---------------------------------------------------------------------------
# Async collectives + handle plumbing (reference: torch/mpi_ops.py:95-900)
# ---------------------------------------------------------------------------
def allreduce_async(tensor, average: bool | None = None, name: str | None = None,
                    op=None, prescale_factor: float = 1.0,
                    postscale_factor: float = 1.0,
                    compression=None, spec=None) -> Handle:
    """``compression`` selects the wire codec: a name ("fp16", "bf16",
    "int8", "uint4"), a compress.CompressionCodec, or a framework
    Compression marker class; None honors HOROVOD_COMPRESSION.
    ``spec`` annotates the tensor's sharding layout (PartitionSpec,
    axis-entry iterable, or canonical token string): it joins the
    collective's cross-rank fingerprint identity and rides the wire as
    sp_spec (hvdshard; docs/analysis.md)."""
    kind, adasum = _op_kind(op, average)
    _, handle = core.enqueue_allreduce(
        _auto_name("allreduce", name), tensor, op=kind,
        prescale_factor=prescale_factor, postscale_factor=postscale_factor,
        adasum=adasum, codec=compression, spec=spec)
    handle.wrap_refs = [tensor]
    return handle


def grouped_allreduce_async(tensors: Sequence[Any],
                            average: bool | None = None,
                            name: str | None = None, op=None,
                            prescale_factor: float = 1.0,
                            postscale_factor: float = 1.0,
                            compression=None) -> Handle:
    kind, adasum = _op_kind(op, average)
    base = _auto_name("grouped_allreduce", name)
    names = [f"{base}.{i}" for i in range(len(tensors))]
    _, handle = core.enqueue_grouped_allreduce(
        names, list(tensors), op=kind, prescale_factor=prescale_factor,
        postscale_factor=postscale_factor, adasum=adasum,
        codec=compression)
    handle.wrap_refs = list(tensors)
    return handle


def allgather_async(tensor, name: str | None = None, spec=None) -> Handle:
    _, handle = core.enqueue_allgather(_auto_name("allgather", name), tensor,
                                       spec=spec)
    handle.wrap_refs = [tensor]
    return handle


def broadcast_async(tensor, root_rank: int, name: str | None = None,
                    spec=None) -> Handle:
    _, handle = core.enqueue_broadcast(_auto_name("broadcast", name), tensor,
                                       root_rank, spec=spec)
    handle.wrap_refs = [tensor]
    return handle


def alltoall_async(tensor, splits=None, name: str | None = None) -> Handle:
    _, handle = core.enqueue_alltoall(_auto_name("alltoall", name), tensor,
                                      splits)
    handle.wrap_refs = [tensor]
    return handle


def synchronize(handle: Handle):
    """Wait for an async op; return its output(s) in the caller's framework
    (reference: torch/mpi_ops.py:862-884)."""
    status = handle.wait()
    status.raise_if_error()
    refs = handle.wrap_refs or [None] * len(handle.entries)
    outs = [e.output if r is None else _wrap_like(r, e.output)
            for r, e in zip(refs, handle.entries)]
    return outs[0] if len(outs) == 1 else outs


def poll(handle: Handle) -> bool:
    """True if the async op has completed
    (reference: torch/mpi_ops.py:846)."""
    return handle.done()


# ---------------------------------------------------------------------------
# Synchronous collectives
# ---------------------------------------------------------------------------
def allreduce(tensor, average: bool | None = None, name: str | None = None,
              op=None, prescale_factor: float = 1.0,
              postscale_factor: float = 1.0, compression=None, spec=None):
    handle = allreduce_async(tensor, average, name, op, prescale_factor,
                             postscale_factor, compression, spec)
    return _result(handle, tensor)


def grouped_allreduce(tensors: Sequence[Any], average: bool | None = None,
                      name: str | None = None, op=None,
                      prescale_factor: float = 1.0,
                      postscale_factor: float = 1.0, compression=None):
    handle = grouped_allreduce_async(tensors, average, name, op,
                                     prescale_factor, postscale_factor,
                                     compression)
    status = handle.wait()
    status.raise_if_error()
    return [_wrap_like(t, e.output)
            for t, e in zip(tensors, handle.entries)]


def reducescatter_async(tensor, name: str | None = None, op=None,
                        prescale_factor: float = 1.0,
                        postscale_factor: float = 1.0) -> Handle:
    # op=None averages, matching upstream Horovod's reducescatter default
    # (and this package's allreduce _op_kind mapping).
    if op in (None, Average):
        op_name = "average"
    elif op is Sum:
        op_name = "sum"
    else:
        raise ValueError(f"Unknown reducescatter op: {op}")
    _, handle = core.enqueue_reducescatter(
        _auto_name("reducescatter", name), tensor, op=op_name,
        prescale_factor=prescale_factor, postscale_factor=postscale_factor)
    handle.wrap_refs = [tensor]
    return handle


def allgather(tensor, name: str | None = None, spec=None):
    return _result(allgather_async(tensor, name, spec=spec), tensor)


def reducescatter(tensor, name: str | None = None, op=None,
                  prescale_factor: float = 1.0,
                  postscale_factor: float = 1.0):
    """Reduce over all ranks and return this rank's dim-0 slice."""
    return _result(reducescatter_async(tensor, name, op, prescale_factor,
                                       postscale_factor), tensor)


def broadcast(tensor, root_rank: int, name: str | None = None):
    return _result(broadcast_async(tensor, root_rank, name), tensor)


def alltoall(tensor, splits=None, name: str | None = None):
    handle = alltoall_async(tensor, splits, name)
    status = handle.wait()
    status.raise_if_error()
    entry = handle.entries[0]
    out = _wrap_like(tensor, entry.output)
    if splits is None:
        return out
    recv_splits = np.asarray(entry.received_splits, dtype=np.int32)
    return out, _wrap_int_like(tensor, recv_splits)


def barrier() -> None:
    _, handle = core.enqueue_barrier()
    handle.wait().raise_if_error()


def join() -> int:
    """Block until every rank has joined; meanwhile this rank participates
    in outstanding collectives with zero stand-ins
    (reference: torch/mpi_ops.py:885-900)."""
    _, handle = core.enqueue_join()
    handle.wait().raise_if_error()
    return int(handle.entries[0].output)


# ---------------------------------------------------------------------------
# Convenience object/parameter sync (reference: torch/functions.py)
# ---------------------------------------------------------------------------
def broadcast_object(obj: Any, root_rank: int = 0,
                     name: str | None = None) -> Any:
    """Broadcast an arbitrary picklable object by serializing to bytes
    (reference: torch/functions.py broadcast_object)."""
    import pickle
    name = _auto_name("broadcast_object", name)
    if rank() == root_rank:
        payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8).copy()
        sz = np.array([payload.size], dtype=np.int64)
    else:
        payload = None
        sz = np.array([0], dtype=np.int64)
    sz = broadcast(sz, root_rank, name=f"{name}.size")
    if payload is None:
        payload = np.zeros(int(sz[0]), dtype=np.uint8)
    payload = broadcast(payload, root_rank, name=f"{name}.data")
    return pickle.loads(payload.tobytes()) if rank() != root_rank else obj


def start_profiler(logdir: str) -> None:
    """Start a device trace (reference analogue: the Horovod Timeline /
    NVTX ranges, SURVEY §5.1 — on TPU the native tool is the jax profiler;
    view with tensorboard or xprof)."""
    import jax
    jax.profiler.start_trace(logdir)


def stop_profiler() -> None:
    import jax
    jax.profiler.stop_trace()


def profiler_annotation(name: str, **args):
    """Context manager labelling a region in device traces (the NVTX-range
    analogue, reference: common/nvtx_op_range.h): the span ``hvd.<name>``
    on the profiler's clock, like the program's own
    (telemetry/spans.py; docs/observability.md)."""
    from .telemetry.spans import span
    return span(name, **args)


def allgather_object(obj: Any, name: str | None = None) -> list:
    """Gather one arbitrary picklable object per rank; every rank receives
    the full list ordered by rank (reference: torch/mpi_ops.py
    allgather_object)."""
    import pickle
    name = _auto_name("allgather_object", name)
    payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8).copy()
    sizes = allgather(np.array([payload.size], dtype=np.int64),
                      name=f"{name}.size")
    data = allgather(payload, name=f"{name}.data")
    data = np.asarray(data)
    objs, offset = [], 0
    for sz in np.asarray(sizes).reshape(-1):
        objs.append(pickle.loads(data[offset:offset + int(sz)].tobytes()))
        offset += int(sz)
    return objs


# Build-variant introspection (reference: horovod/common/util.py:137-186)
def xla_built() -> bool:
    try:
        import jax  # noqa: F401
        return True
    except ImportError:
        return False


def tcp_built() -> bool:
    return True


def gloo_built() -> bool:   # compat alias: our TCP plane plays gloo's role
    return True


def nccl_built() -> bool:
    return False


def mpi_built() -> bool:
    return False


def mpi_enabled() -> bool:
    return False


def mpi_threads_supported() -> bool:
    return False


# --- Resilience surface (resilience/; docs/resilience.md) -------------------
def run_with_recovery(fn, *, policy=None, max_retries=None,
                      base_backoff=None):
    """Run an idempotent eager collective under HOROVOD_ON_FAILURE
    (raise | retry-with-rebuilt-channels | shrink-via-elastic)."""
    from .resilience import run_with_recovery as _rwr
    return _rwr(fn, policy=policy, max_retries=max_retries,
                base_backoff=base_backoff)
