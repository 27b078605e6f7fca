"""serving/ — continuous-batching data-parallel inference serving on the
collective runtime (ISSUE 9; docs/serving.md).

The training world's machinery reused for a traffic profile training
never produces:

- :class:`~.queue.RequestQueue` — bounded ingress, SLO deadline stamped
  at the door (hvdlint HVD1006 keeps serving/ queues bounded).
- :class:`~.batcher.ContinuousBatcher` — token-budgeted batch assembly
  that admits new requests into in-flight decode batches (Orca-style
  slot scheduling, no run-to-completion batches).
- :class:`~.admission.AdmissionController` — deadline-feasibility +
  load shedding keyed off live telemetry (queue depth, the shared
  ``Histogram.quantile`` step-time path, straggler lag); a request that
  cannot meet its SLO is shed at admission, never executed.
- :class:`~.replica.ReplicaExecutor` — the per-rank serve loop on the
  core/controller dispatch path: broadcast-consistent batch plans (so
  replicas never diverge on a collective), per-request deadlines
  propagated into resilience per-op deadlines, and elastic shrink
  mid-serve on RanksFailedError (survivors keep serving).
- ``serving/slotcache.py`` — the replica's slot cache, dense or paged,
  behind one interface; :class:`~.kvpool.KVBlockPool` — the paged one's
  blocks (ISSUE 14): free-list allocation with refcounts, FNV-chain
  prefix caching, copy-on-write and LRU eviction.
- ``serving/kvstream.py`` — disaggregated prefill/decode: prefill-only
  ranks stream finished KV blocks to decode replicas over a dedicated
  PeerMesh (addressed CRC'd chunks, the STATE_MAGIC mold), keeping
  long prompts out of decode steps.
- ``python -m horovod_tpu.serving.loadgen`` — open-loop Poisson SLO
  load harness; reports p50/p99/p999 latency, goodput vs offered load
  and shed rate to ``SERVE_r{rank}.json``.
"""
from __future__ import annotations

from .admission import AdmissionController
from .batcher import Assignment, BatchPlan, ContinuousBatcher
from .kvpool import KVBlockPool
from .queue import RequestQueue, ServeRequest
from .replica import ReplicaExecutor, ServeConfig

__all__ = [
    "AdmissionController", "Assignment", "BatchPlan",
    "ContinuousBatcher", "KVBlockPool", "ReplicaExecutor",
    "RequestQueue", "ServeConfig", "ServeRequest",
]
