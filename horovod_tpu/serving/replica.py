"""Replica executor: the serve loop every rank runs, on the same
core/controller dispatch path training uses.

Execution model (ISSUE 9 tentpole, extended by ISSUE 14):

- The **front end** (lowest live rank) owns the ingress queue, the
  continuous batcher and admission control.  Every serve step it
  assembles one :class:`~.batcher.BatchPlan` and **broadcasts** it
  (``hvd.broadcast_object`` — a real negotiated collective on the data
  plane).  Because every rank executes the identical plan sequence,
  replicas can never diverge on a collective: the broadcast IS the
  schedule.  A replica that is **alone in its world** (``self.size ==
  1``, read at every step) has nobody to send it to: the plan and the
  completions stay in the process, and the step runs no eager
  collective until a grow makes the world two ranks
  (``stats["local_exchanges"]`` of ``stats["exchanges"]``).
- Each **replica group** (``HOROVOD_SERVE_GROUP_SIZE`` ranks; 1 = pure
  data-parallel) prefills newly assigned requests into free KV-cache
  slots and advances every in-flight slot by one greedy token per step
  (continuous batching, not run-to-completion), through the model's
  family (models/family.py).
- **One decode step of lookahead** (ISSUE 35): a step enqueues decode
  program k+1 and only then waits for the tokens of program k, so the
  device has its next program queued when one ends and everything the
  host does in a step runs under the device's step.  Program k+1 reads
  its input tokens from program k's result on the device; what a
  dispatch needs of a slot (its length, how many tokens it has been
  issued) advances at dispatch, what only the device knows (the tokens,
  the end-of-sequence test) at fetch.  So a token is visible one
  ``_serve_step`` after the one that enqueued its program, and a request
  is complete only once every row issued for it has been fetched.  A
  step's first admission enqueues its prefill and insert behind the
  step in flight and only then waits for that step, so the host lowers
  and enqueues the prompt under the device's step; whatever reads or
  replaces the cache, the parameters or the world from outside a step
  settles the step in flight first (``_settle``).
- **The slot cache** is an object of ``serving/slotcache.py``, chosen
  once from ``HOROVOD_SERVE_PAGED``: dense per-slot arrays, or paged
  blocks with prefix reuse (ISSUE 14).  The executor knows neither
  layout: it admits, decodes and releases through one interface.
- **Disaggregated prefill/decode** (``HOROVOD_SERVE_PREFILL_RANKS``):
  the highest N ranks run prompt prefill only and stream finished KV
  blocks to decode replicas over the kvstream mesh (point-to-point: the
  plan broadcast stays the only schedule source), so a long prompt
  overlaps decode steps instead of stalling them.
- Completions ride back on an **allgather** each step, so the front end
  frees slots and records latencies without any side channel.
- **Deadline propagation**: the earliest in-flight request deadline
  bounds the step's collective waits via
  ``resilience.deadline_scope`` → per-op deadlines
  (resilience/context.py), so a dead peer surfaces within the SLO
  budget instead of the full fault window.
- **Elastic shrink mid-serve**: when a collective raises
  :class:`RanksFailedError`, every survivor converges on the
  heartbeat-confirmed dead set, deterministically renumbers itself,
  rebuilds the world one rank smaller (fresh rendezvous epoch), resyncs
  the in-flight map from ground truth, and keeps serving.  In-flight
  requests on surviving replicas are untouched — their KV state is
  process-local and does not care about the mesh.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import json
import os
import resource
import statistics
import threading
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..common import config
from ..common.exceptions import RanksFailedError
from ..common.logging import logger
from ..telemetry.spans import StepParts, mark, span
from .admission import AdmissionController
from .batcher import Assignment, BatchPlan, ContinuousBatcher
from .queue import RequestQueue
from .slotcache import DenseSlotCache, PagedSlotCache


@dataclasses.dataclass
class ServeConfig:
    """Serving knobs (env defaults: the HOROVOD_SERVE_* family)."""
    max_batch: int = 8
    token_budget: int = 256
    max_seq: int = 256
    group_size: int = 1
    slo_ms: float = 30000.0
    queue_depth: int = 1024
    eos_id: int = -1                   # -1 disables EOS stopping
    seed: int = 0
    # The model's configuration object; None = a tiny TransformerLM.
    # Its ``family`` (models/family.py) says how to build the model, a
    # fresh cache, prefill and a decode step: TransformerConfig or
    # HybridConfig today.
    model_cfg: object | None = None
    # Paged KV cache (serving/slotcache.py): blocks of block_tokens from
    # a pool_blocks pool; 0 = auto (the dense layout's token memory).
    # paged_slots (0 = auto: 2 x max_batch) is the decode batch width:
    # the pool, not the batch shape, bounds concurrency.
    paged: bool = False
    block_tokens: int = 16
    pool_blocks: int = 0
    paged_slots: int = 0
    # Disaggregated prefill/decode: highest N ranks prefill-only
    # (requires paged; clamped so at least one decode rank remains).
    prefill_ranks: int = 0
    # Prefill shape buckets compiled at startup so the first real
    # requests never stall a broadcast-consistent step on an XLA
    # compile (a multi-second stall looks exactly like a wedged rank
    # to a peer's SLO-bounded wait).
    warmup_buckets: tuple = (8, 16)

    @classmethod
    def from_env(cls, **overrides) -> "ServeConfig":
        base = dict(
            max_batch=config.SERVE_MAX_BATCH.get(),
            token_budget=config.SERVE_TOKEN_BUDGET.get(),
            max_seq=config.SERVE_MAX_SEQ.get(),
            group_size=config.SERVE_GROUP_SIZE.get(),
            slo_ms=config.SERVE_SLO_MS.get(),
            queue_depth=config.SERVE_QUEUE_DEPTH.get(),
            paged=config.SERVE_PAGED.get(),
            block_tokens=config.SERVE_BLOCK_TOKENS.get(),
            pool_blocks=config.SERVE_POOL_BLOCKS.get(),
            paged_slots=config.SERVE_PAGED_SLOTS.get(),
            prefill_ranks=config.SERVE_PREFILL_RANKS.get())
        base.update(overrides)
        return cls(**base)

    @property
    def slots(self) -> int:
        """Decode slots per replica: the dense batch, or the (wider)
        paged slot count backed by the shared pool."""
        if not self.paged:
            return self.max_batch
        return self.paged_slots if self.paged_slots > 0 \
            else 2 * self.max_batch

    @property
    def table_width(self) -> int:
        return -(-self.max_seq // self.block_tokens)

    @property
    def resolved_pool_blocks(self) -> int:
        """Pool size; the auto default reserves exactly the dense
        layout's token memory (max_batch x max_seq tokens)."""
        if self.pool_blocks > 0:
            return self.pool_blocks
        return self.max_batch * self.table_width


@dataclasses.dataclass
class _Slot:
    """One in-flight sequence in this replica's decode batch."""
    rid: int
    remaining: int                     # decode tokens still to produce
    deadline: float                    # absolute local monotonic
    assigned_at: float
    age_ms: float                      # ingress age when assigned
    step: int                          # the serve step that admitted it
    slo_ms: float
    generated: list[int]
    seq_len: int = 0                   # the write cursor, at dispatch
    in_flight: int = 0                 # decode rows enqueued, not fetched
    # Disaggregated mode: the original assignment while the streamed
    # prefill is still in flight (slot skips decode until it lands or
    # the fallback re-prefills locally), and when it went pending.
    pending: Assignment | None = None
    pending_since: float = 0.0


class ReplicaExecutor:
    """One rank's half of the data-parallel serving world."""

    def __init__(self, serve_cfg: ServeConfig | None = None,
                 params=None) -> None:
        import horovod_tpu as hvd
        self.hvd = hvd
        self.cfg = serve_cfg or ServeConfig.from_env()
        self.rank = hvd.rank()
        self.size = hvd.size()
        self.front = 0
        self._gen = 0                  # shrink generation (name/epoch tag)
        self._step = 0
        self._stop_requested = False
        self._configure_groups()

        model_cfg = _decode_model_cfg(self.cfg)
        self.family = model_cfg.family
        layout = DenseSlotCache
        if self.cfg.paged:
            layout = PagedSlotCache
            if self.family.paged_missing:
                raise ValueError(
                    f"ServeConfig.paged=True (and with it prefix reuse, "
                    f"disaggregated prefill and kvstream) cannot serve a "
                    f"{self.family.name} configuration yet; missing: "
                    f"{self.family.paged_missing}")
            model_cfg = dataclasses.replace(
                model_cfg, paged=True,
                kv_pool_blocks=self.cfg.resolved_pool_blocks,
                kv_block_tokens=self.cfg.block_tokens)
        self.model = self.family.build(model_cfg)
        if params is None:
            # Seeded, deterministic: every replica materializes identical
            # weights without a broadcast (replace with a checkpoint
            # restore or hvd.broadcast_object for real weights).
            params = _seeded_params(self.model, self.cfg.seed)
        self.params = params

        self.slots: list[_Slot | None] = [None] * self.cfg.slots
        self._last_tokens = np.zeros(self.cfg.slots, np.int32)
        # Where _last_tokens holds a slot's newest token and the last
        # decode result on the device does not: the slots admitted or
        # landed since the last dispatch, and all before the first.
        self._token_on_host = np.ones(self.cfg.slots, bool)
        # The decode step enqueued and not fetched yet: (its result on
        # the device, the (index, slot) pairs it was enqueued for).
        self._in_flight: tuple | None = None
        self.completed: dict[int, dict] = {}
        self.prefilled: set[int] = set()
        # Completions not yet acknowledged by a successful exchange: a
        # step that fails mid-allgather re-sends them after the shrink,
        # so a request finished during the failure window is never
        # misclassified as lost (front dedups via batcher membership).
        self._unreported: list[dict] = []
        # perfscope serve ledger (telemetry/perfmodel.py): smoothed
        # accepted-tokens/s.
        self._perf_tps = 0.0
        self.stats = {"offered": 0, "expired": 0, "served": 0,
                      "served_slo": 0, "lost": 0,
                      "latencies_ms": [], "completed_at": [],
                      "shrinks": [], "grows": [],
                      "prefill_streams": 0, "prefill_fallbacks": 0,
                      "prefill_skipped": 0, "weight_swaps": [],
                      # The slot cache, and how much of it the compiled
                      # decode program updates in place (set by warm-up);
                      # state_bytes of it are a family's recurrent state.
                      "cache_bytes": 0, "cache_aliased_bytes": 0,
                      "state_bytes": 0,
                      # Decode attention, summed over decode dispatches:
                      # the active slots' live contexts, and the positions
                      # the compiled path reads for them (a layer).
                      "attend_live_positions": 0,
                      "attend_read_positions": 0,
                      # The grid steps that reading takes, one a live
                      # block, and the (slots, max_seq // block) a static
                      # grid would take (the plain form: one a slot, of
                      # one).
                      "attend_grid_steps": 0, "attend_grid_full": 0,
                      # The dense cache's attention layers, and those of
                      # them whose decode kernel writes the step's key
                      # and value row itself (ops/decode_attention.py:
                      # kernel_writes); the others write it in a serial
                      # loop over the slots.
                      "attend_layers": 0, "attend_write_fused_layers": 0,
                      # Decode programs enqueued, and those of them
                      # enqueued while the one before was still unfetched
                      # (the device had its next program when one ended).
                      "decode_dispatches": 0, "decode_overlapped": 0,
                      # What a family's decode program counts on the
                      # device (ModelFamily.decode_counters; routed
                      # experts: models/moe.py:COUNTERS) joins these,
                      # summed over layers and decode dispatches.
                      # Always-on part timers of the serve step
                      # (telemetry/spans.py), by kind of step: "admit"
                      # steps prefilled at least one request here,
                      # "decode" steps none.
                      "steps": {"admit": 0, "decode": 0},
                      # Plan and completion exchanges begun, and those of
                      # them that stayed in the process (world size 1).
                      "exchanges": 0, "local_exchanges": 0,
                      "step_parts_s": {"admit": {}, "decode": {}},
                      # An admission, counted where it happens, every
                      # one since the executor was built (its warm-up is
                      # none): requests given a slot, and the host
                      # seconds inside hvd.serve.admit, which every
                      # running stream waits.
                      "admissions": 0, "admit_s": 0.0,
                      # Of those, the ones whose prefill was enqueued
                      # while a decode step was in flight.
                      "admit_overlapped": 0,
                      # By the slot cache's admit: prompt tokens computed
                      # anew (a prefix hit's are not) and the positions
                      # the prefill program ran over (their bucket).
                      "prefill_prompt_tokens": 0,
                      "prefill_bucket_positions": 0,
                      # positions an admission prefilled (0: parked for
                      # a streamed prefill) -> [admissions, prompt tokens
                      # computed, admit_s], for close()'s table.
                      "prefill_by_bucket": {},
                      "slow_steps": [], "slow_steps_total": 0}
        # Host seconds of the last steps of each kind: a step slower
        # than _SLOW_FACTOR times their median leaves a record.
        self._recent_steps = {
            kind: collections.deque(maxlen=_RECENT_STEPS)
            for kind in ("admit", "decode")}
        # Elastic grow mid-serve (statesync/): attach_statesync wires a
        # membership service in; None = the pre-ISSUE-10 behavior with
        # zero extra collectives.
        self.statesync = None
        # Fleet continuous weight deployment (fleet/deploy.py): the
        # puller thread stages verified snapshots here; the front
        # schedules the swap into a BatchPlan once EVERY rank's staged
        # set (piggybacked on the completions allgather) holds it.
        self.weight_version = 0
        self._weight_step = 0          # trainer step of the live weights
        self._fleet_lock = threading.Lock()
        # version -> (params tree, trainer step, digest).  Keyed by
        # version, NOT a single newest-wins slot: the puller can stage
        # a newer version between the completions exchange (which
        # reported this rank's staged set) and the plan's scheduled
        # swap, and every rank of a sharded replica group must still
        # swap exactly plan.swap_version at that boundary.
        self._fleet_staged: dict[int, tuple] = {}
        self._fleet_reported: set[int] = set()
        self._fleet_puller = None
        self._fleet_gauge = None       # --fleet front gauge hook (wiring)
        self._fleet_runtime = None
        self._fleet_common = 0         # newest version staged on EVERY rank
        self._fleet_scheduled = 0      # newest version the front swapped

        self.queue = RequestQueue(maxsize=self.cfg.queue_depth,
                                  default_slo_ms=self.cfg.slo_ms)
        self.admission = AdmissionController(
            queue_depth_limit=self.cfg.queue_depth)
        # The slot cache in the layout chosen above (serving/slotcache.py).
        self.cache = layout(self.cfg, self.family, self.model, self.stats)
        self.batcher = self._make_batcher()
        self._kvstream = None
        self._init_cache()
        self._warmup()
        # Warm-up prefilled each bucket through cache.admit: no admission.
        self.stats.update(prefill_prompt_tokens=0,
                          prefill_bucket_positions=0)
        if self.prefill_rank_list:
            self._rebuild_kvstream()

    # -- topology --------------------------------------------------------
    def _configure_groups(self) -> None:
        n_pref = 0
        if self.cfg.prefill_ranks > 0:
            if not self.cfg.paged:
                logger.warning(
                    "serving: HOROVOD_SERVE_PREFILL_RANKS needs "
                    "HOROVOD_SERVE_PAGED (block streaming); ignoring")
            else:
                n_pref = min(self.cfg.prefill_ranks, self.size - 1)
        self.decode_size = self.size - n_pref
        self.prefill_rank_list = list(range(self.decode_size, self.size))
        self.is_prefill = self.rank >= self.decode_size
        gs = self.cfg.group_size
        if gs <= 0 or self.decode_size % gs:
            if gs > 1:
                logger.warning(
                    "serving: group size %d does not divide decode size "
                    "%d; falling back to per-rank replicas", gs,
                    self.decode_size)
            gs = 1
        self.group_size = gs
        self.group = self.rank // gs if not self.is_prefill else -1
        self.num_groups = self.decode_size // gs
        self.group_leader = (not self.is_prefill
                             and self.rank % gs == 0)

    def _make_batcher(self) -> ContinuousBatcher:
        return ContinuousBatcher(
            self.num_groups, slots_per_replica=self.cfg.slots,
            token_budget=self.cfg.token_budget,
            block_capacity=self.cache.block_capacity,
            block_tokens=self.cfg.block_tokens)

    def _rebuild_kvstream(self) -> None:
        """(Re)form the dedicated prefill-stream mesh — collectively,
        every serving rank, epoch+generation-scoped so a post-shrink
        mesh never collides with the dying one's sockets."""
        from ..statesync.service import _kv_client
        from .kvstream import KVStreamMesh, kvstream_scope

        if self._kvstream is not None:
            self._kvstream.close()
            self._kvstream = None
        base = os.environ.get("HOROVOD_RENDEZVOUS_EPOCH", "0")
        self._kvstream = KVStreamMesh(
            _kv_client(), kvstream_scope(base, self._gen), self.rank,
            self.size, self.prefill_rank_list)

    # -- the slot cache (serving/slotcache.py) ---------------------------
    def _init_cache(self) -> None:
        self.cache.fresh(self.params)

    def _warmup(self) -> None:
        self.cache.warm(self.params, self._last_tokens)

    # -- per-step halves -------------------------------------------------
    def _assemble(self) -> BatchPlan:
        stop = (self._stop_requested and self.queue.depth() == 0
                and self.batcher.inflight_count() == 0)
        plan, expired = self.batcher.assemble(
            self._step, self.queue, self.admission, stop=stop,
            prefill_ranks=self.prefill_rank_list)
        for req in expired:
            # Expired while queued: shed at admission, never executed.
            self.admission.count("expired")
            self.stats["expired"] += 1
        # Fleet weight rollout: schedule the newest version that EVERY
        # rank reported staged in the last completions exchange — an
        # intersection, not min(newest staged), so a rank that skipped
        # a version (its head poll raced the publisher GC) is never
        # scheduled for an image it does not hold.
        if self._fleet_common > max(self.weight_version,
                                    self._fleet_scheduled):
            plan.swap_version = self._fleet_common
            self._fleet_scheduled = plan.swap_version
        return plan

    def _exchange_plan(self, plan: BatchPlan | None) -> BatchPlan:
        self.stats["exchanges"] += 1
        if self.size == 1:
            # Alone, this rank is the front and the plan is its own: the
            # broadcast would hand the root this same object back.  The
            # size is the one shrink and grow maintain, read every step.
            self.stats["local_exchanges"] += 1
            return plan
        from ..resilience import deadline_scope
        deadlines = [s.deadline for s in self.slots if s is not None]
        with deadline_scope(min(deadlines) if deadlines else None):
            return self.hvd.broadcast_object(
                plan, root_rank=self.front,
                name=f"serve.plan.g{self._gen}.{self._step}")

    def _apply_plan(self, plan: BatchPlan, parts: StepParts) -> int:
        """Execute the plan's assignments; returns how many requests
        this replica admitted into a slot.  A local admission settles the
        decode step in flight between the dispatch of its prefill and the
        fetch of its first token (``_prefill_slot``); one parked for a
        streamed prefill settles before it begins."""
        now = time.monotonic()
        admits = 0
        if plan.swap_version:
            self._fleet_swap(plan.swap_version)
        for a in plan.assign:
            if self.is_prefill:
                if a.prefill == self.rank:
                    self._prefill_and_stream(a)
                continue
            if a.replica != self.group:
                continue
            if a.prefill >= 0:
                self._settle(parts)    # a parked admission settles first
            slot = next(i for i, s in enumerate(self.slots) if s is None)
            admits += 1
            # Every stream that is decoding waits for this admission.
            running = sum(s is not None and s.pending is None
                          for s in self.slots)
            # The plan formed at the head of this step (the ranks enter a
            # step together: the completion exchange ends the one before).
            waited = a.age_ms / 1e3 + parts.elapsed()
            stats = self.stats
            before = (stats["prefill_prompt_tokens"],
                      stats["prefill_bucket_positions"])
            admit = parts("admit", rid=a.rid, slot=slot, running=running,
                          queue_wait_ms=round(waited * 1e3, 3))
            with admit as annotation:
                if a.prefill >= 0:
                    self._admit_disaggregated(slot, a, now)
                else:
                    self._prefill_slot(slot, a, now, parts)
                # What the cache's admit ran: on the paged layout the
                # prefix cache's hits are neither computed nor padded.
                tokens = stats["prefill_prompt_tokens"] - before[0]
                positions = stats["prefill_bucket_positions"] - before[1]
                annotation.set_metadata(bucket=positions,
                                        prompt_tokens=tokens)
            stats["admissions"] += 1
            stats["admit_s"] += admit.seconds
            by_bucket = stats["prefill_by_bucket"].setdefault(
                positions, [0, 0, 0.0])
            by_bucket[0] += 1
            by_bucket[1] += tokens
            by_bucket[2] += admit.seconds
        return admits

    def _prefill_slot(self, slot: int, a: Assignment, now: float,
                      parts: StepParts | None = None) -> None:
        """Prefill ``a`` into ``slot``; the slot is its once the first
        token is fetched.  Given the step's ``parts`` (an admission of the
        plan), the decode step in flight is settled in between: the host
        lowers and enqueues the prefill and the insert while the device
        runs that step, and the device goes from it to the prefill.  The
        slot is in no step in flight (it was free when that step was
        enqueued), so nothing the prefill reads waits for the settle.  One
        prefill at most is unfetched: its first token is fetched before
        the next admission begins."""
        toks = self._clamped_tokens(a)
        first = self.cache.admit(self.params, slot, toks, a.max_new_tokens)
        if parts is not None:
            self.stats["admit_overlapped"] += self._in_flight is not None
            self._settle(parts)        # a no-op after the first
        first = self.cache.first_token(first)
        self._last_tokens[slot] = first
        self._token_on_host[slot] = True
        self.slots[slot] = _Slot(
            rid=a.rid, remaining=a.max_new_tokens - 1,
            deadline=now + a.deadline_rel_ms / 1e3, assigned_at=now,
            age_ms=a.age_ms, step=self._step, slo_ms=a.slo_ms,
            generated=[first], seq_len=len(toks))
        self.prefilled.add(a.rid)

    def _clamped_tokens(self, a: Assignment) -> list[int]:
        # Clamp so prompt + generation always fits the KV cache.
        limit = self.cfg.max_seq - a.max_new_tokens
        return a.tokens[:max(1, limit)]

    # -- disaggregated prefill/decode ------------------------------------
    def _admit_disaggregated(self, slot: int, a: Assignment,
                             now: float) -> None:
        """Decode-rank admission of a prefill-rank-assigned request: a
        full local prefix hit admits immediately (the stream, when it
        lands, is discarded); otherwise the slot parks PENDING: no
        decode until the streamed blocks arrive (or the fallback
        re-prefills locally), so a long prompt never stalls a step."""
        if self.cache.holds_prompt(self._clamped_tokens(a)):
            self._prefill_slot(slot, a, now)
            if self._kvstream is not None:
                self._kvstream.discard(a.rid)
            return
        self.slots[slot] = _Slot(
            rid=a.rid, remaining=a.max_new_tokens,
            deadline=now + a.deadline_rel_ms / 1e3, assigned_at=now,
            age_ms=a.age_ms, step=self._step, slo_ms=a.slo_ms,
            generated=[], pending=a, pending_since=now)
        self.prefilled.add(a.rid)

    def _prefill_and_stream(self, a: Assignment) -> None:
        """Prefill-rank half: compute the prompt's KV blocks and stream
        them to every rank of the decode replica group."""
        toks = self._clamped_tokens(a)
        first, image = self.cache.prefill_image(self.params, toks)
        dests = list(range(a.replica * self.group_size,
                           (a.replica + 1) * self.group_size))
        from ..resilience import deadline_scope

        # The stream is bounded twice over: the request's SLO deadline
        # scopes the step, and the KVStreamGuard silence timeout aborts
        # a send wedged on a dead decode peer (the decode side then
        # re-prefills locally — degradation, never a stall).
        try:
            with deadline_scope(time.monotonic()
                                + a.deadline_rel_ms / 1e3):
                self._kvstream.send_image(
                    a.rid, dests, image.tobytes(), first=first,
                    plen=len(toks), cursor=len(toks), shape=image.shape,
                    dtype=str(image.dtype))
        except (ConnectionError, OSError) as exc:
            # The decode side's pending-patience fallback re-prefills
            # locally; a broken stream is degradation, never a stall.
            logger.warning("serving: prefill stream for rid %d failed: "
                           "%s", a.rid, exc)
            return
        self.stats["prefill_streams"] += 1

    def _integrate_prefills(self) -> None:
        """Decode-rank step hook: land fully streamed transfers into
        pending slots (non-blocking — a transfer still in flight just
        keeps its slot pending), re-prefill locally when a transfer
        outlived its patience (prefill rank died / stream lost), and
        drop orphaned images."""
        now = time.monotonic()
        pending_rids = set()
        for i, s in enumerate(self.slots):
            if s is None or s.pending is None:
                continue
            pending_rids.add(s.rid)
            img = self._kvstream.pop_ready(s.rid) \
                if self._kvstream is not None else None
            if img is not None:
                self._land_streamed(i, img)
                continue
            patience = max(1.0, s.slo_ms / 4e3)
            if now - s.pending_since > patience:
                a = s.pending
                self.slots[i] = None
                self._prefill_slot(i, a, now)
                self.stats["prefill_fallbacks"] += 1
                if self._kvstream is not None:
                    self._kvstream.discard(a.rid)
        if self._kvstream is not None:
            for rid in self._kvstream.ready_rids():
                if rid not in pending_rids:
                    self._kvstream.discard(rid)

    def _land_streamed(self, slot: int, img) -> None:
        """Land a streamed prefill in the pool and activate the slot."""
        s = self.slots[slot]
        image = np.frombuffer(bytes(img.data),
                              np.dtype(img.dtype)).reshape(img.shape)
        self.cache.land(slot, self._clamped_tokens(s.pending),
                        s.pending.max_new_tokens, image)
        self._last_tokens[slot] = img.first
        self._token_on_host[slot] = True
        self.slots[slot] = dataclasses.replace(
            s, remaining=s.remaining - 1, generated=[img.first],
            seq_len=img.cursor, pending=None, pending_since=0.0)

    # -- decode ----------------------------------------------------------
    def _decode_once(self, parts: StepParts) -> tuple[list, Any]:
        """Enqueue the next decode step, then fetch the one before it,
        which the device has had queued since the last call: (the
        (index, slot) pairs that one was enqueued for, the slot array's
        tokens)."""
        with parts("decode_dispatch"):
            ahead = self._enqueue_decode()
        fetched = self._fetch_in_flight(parts)
        self._in_flight = ahead
        return fetched

    def _enqueue_decode(self) -> tuple | None:
        """Enqueue one decode step for the slots that still have a token
        to be issued, whatever is in flight, and advance what a dispatch
        knows of them: (the result on the device, the (index, slot)
        pairs), or None where no slot wants one."""
        issued = [(i, s) for i, s in enumerate(self.slots)
                  if s is not None and s.pending is None
                  and s.remaining > s.in_flight]
        if not issued:
            return None
        active = [i for i, _ in issued]
        result = self.cache.decode(self.params, self._last_tokens,
                                   self._token_on_host, active, self.slots)
        self.stats["decode_dispatches"] += 1
        self.stats["decode_overlapped"] += self._in_flight is not None
        for _, s in issued:
            s.in_flight += 1
            s.seq_len += 1
        # The newest token of these slots is the device's from here on.
        self._token_on_host[:] = True
        self._token_on_host[active] = False
        return result, issued

    def _fetch_in_flight(self, parts: StepParts | None = None
                         ) -> tuple[list, Any]:
        """Wait for the decode step in flight: (the (index, slot) pairs
        it was enqueued for, the slot array's tokens); no pairs where
        none is."""
        if self._in_flight is None:
            return [], None
        (result, issued), self._in_flight = self._in_flight, None
        with parts("token_fetch") if parts else span("serve.token_fetch"):
            return issued, self.cache.fetch(result)   # waits for the device

    def _advance_slots(self, issued: list, nxt) -> None:
        """What only the device knew of a fetched step.  A row is dropped
        where its slot no longer holds the occupant it was enqueued for,
        or that occupant wants no more: the row enqueued behind an end
        token, before the host had seen it."""
        for i, s in issued:
            s.in_flight -= 1
            if self.slots[i] is not s or s.remaining <= 0:
                continue
            tok = int(nxt[i])
            s.generated.append(tok)
            s.remaining -= 1
            self._last_tokens[i] = tok
            if self.cfg.eos_id >= 0 and tok == self.cfg.eos_id:
                s.remaining = 0

    def _settle(self, parts: StepParts | None = None) -> None:
        """Leave nothing in flight: fetch the decode step that is, and
        advance its slots.  Inside a step's first admission, between its
        prefill's dispatch and its first token's fetch (a parked one's,
        before it begins), and before anything outside a step reads or
        replaces the cache, the parameters or the world."""
        self._advance_slots(*self._fetch_in_flight(parts))

    def _collect_completions(self) -> None:
        now = time.monotonic()
        stale = self._fleet_staleness_steps()
        for i, s in enumerate(self.slots):
            if s is None or s.pending is not None or s.remaining > 0 \
                    or s.in_flight:
                continue               # its last row is still in flight
            rec = {"rid": s.rid, "replica": self.group,
                   "latency_ms": s.age_ms + (now - s.assigned_at) * 1e3,
                   "tokens": len(s.generated),
                   "slo_met": now <= s.deadline,
                   # Which published weights served this request, and
                   # how many trainer steps behind the newest staged
                   # snapshot — the loadgen staleness accounting
                   # (docs/fleet.md).
                   "weights": self.weight_version,
                   "weights_stale_steps": stale}
            # The local record also keeps the token stream itself (the
            # answer); only counts ride the completions allgather.
            self.completed[s.rid] = {**rec, "generated": list(s.generated)}
            # The last of a request's three marks in a trace (enqueue,
            # admit, complete: one rid); ``steps`` from the one that
            # admitted it to this one, both counted.
            mark("serve.complete", rid=s.rid, tokens=rec["tokens"],
                 steps=self._step - s.step + 1)
            if self.group_leader:
                # Every group member frees slots identically; only the
                # leader reports, so completions appear exactly once.
                self._unreported.append(rec)
            self._release_slot(i)

    def _release_slot(self, i: int) -> None:
        self.cache.release(i)
        if self._kvstream is not None:
            self._kvstream.discard(self.slots[i].rid)
        self.slots[i] = None

    def _exchange_completions(self) -> list[dict]:
        # Completions plus this rank's staged weight versions ride one
        # allgather: the front learns the version set every rank holds
        # with zero extra collectives, exactly like completions ride
        # the step.
        mine = {"done": list(self._unreported),
                "staged": self._fleet_staged_versions()}
        self.stats["exchanges"] += 1
        if self.size == 1:
            # Alone, the gathered list is this rank's own entry: no
            # pickle, no wait on a peer and so no deadline to bound it.
            self.stats["local_exchanges"] += 1
            per_rank = [mine]
        else:
            from ..resilience import deadline_scope
            deadlines = [s.deadline for s in self.slots if s is not None]
            with deadline_scope(min(deadlines) if deadlines else None):
                per_rank = self.hvd.allgather_object(
                    mine, name=f"serve.done.g{self._gen}.{self._step}")
        self._unreported.clear()       # acknowledged by the exchange
        common = set.intersection(
            *(set(p.get("staged") or ()) for p in per_rank))
        self._fleet_common = max(common) if common else 0
        with self._fleet_lock:
            # Only a version every rank holds can be scheduled; the rest
            # may be evicted again.  Kept exempt, two ranks whose windows
            # filled with different versions refuse every newer head and
            # never swap.
            self._fleet_reported &= common
        return [rec for p in per_rank for rec in p["done"]]

    def _account(self, completions: list[dict]) -> None:
        if self.rank != self.front:
            return
        now = time.monotonic()
        for rec in completions:
            if rec["rid"] not in self.batcher.inflight:
                continue   # duplicate re-send after a failed exchange
            self.batcher.note_done(rec["rid"])
            self.admission.count("served")
            self.admission.observe_latency_ms(rec["latency_ms"])
            self.stats["served"] += 1
            self.stats["served_slo"] += bool(rec["slo_met"])
            self.stats["latencies_ms"].append(rec["latency_ms"])
            # Completion wall times let the load harness report goodput
            # before/during/after an elastic grow (docs/serving.md).
            self.stats["completed_at"].append(now)

    # -- elastic grow mid-serve (statesync/) -----------------------------
    def attach_statesync(self, service) -> None:
        """Wire a statesync membership service in: every serve step ends
        with its boundary check, so a joining replica is admitted at a
        step boundary and enters after its streamed params verify."""
        self.statesync = service

    def state_tree(self) -> dict:
        """The streamed-state template/provider for serving: params are
        the only cross-replica state (KV caches are per-request), and
        they never change between steps — the statesync service runs in
        static mode, so the bulk image IS the joiner's entry state."""
        import jax

        self._settle()
        return {"params": jax.tree_util.tree_map(np.asarray,
                                                 self.params)}

    # -- fleet continuous weight deployment (fleet/) ---------------------
    def attach_fleet(self, kv, *, interval_s: float | None = None):
        """Start a fleet weight puller against the coordinator KV: it
        polls the published ``head``, digest-verifies new snapshots and
        stages them here; the front end schedules the swap into a
        broadcast BatchPlan once every rank has staged (docs/fleet.md).
        Returns the puller (owned by this executor — ``close`` joins
        it)."""
        from ..fleet.deploy import WeightPuller

        kwargs = {} if interval_s is None else {"interval_s": interval_s}
        self._fleet_puller = WeightPuller(kv, self._fleet_stage,
                                          **kwargs)
        self._fleet_puller.start()
        return self._fleet_puller

    # Staged-but-unswapped versions a rank holds at most, so a group
    # whose swaps cannot land never accumulates unbounded full param
    # images.  At the cap, a staged version never REPORTED in a
    # completions exchange is evicted for a newer one (the front cannot
    # have scheduled what it never saw); once every staged version has
    # been reported the puller is refused and retries.  An exchange
    # leaves exempt only the versions every rank reported.
    _FLEET_STAGE_CAP = 4

    def _fleet_stage(self, version: int, image, meta) -> bool:
        """WeightPuller stage callback (puller thread): decode the
        already-verified image into a params-shaped tree and park it,
        keyed by version, for the front-scheduled boundary swap.  Never
        touches live params — the swap happens on the serve thread
        inside ``_apply_plan``.

        At the window cap, the oldest version NOT yet reported in a
        completions exchange is evicted to admit the newer one —
        unreported versions cannot be in any plan, and while the serve
        loop is paused (a grow resync: the joiner compiles for many
        publish intervals) refusing instead would wedge the whole
        group: this rank's window fills with versions the publisher
        GCs before the joiner can ever pull them, the staged sets then
        never intersect, and no swap ever frees the window.  A version
        that HAS been reported may already be scheduled, so once every
        staged version is reported the puller is refused (False) and
        retries — until the exchange's intersection frees the versions
        no other rank holds, a reported image is only ever dropped by
        the swap path."""
        from ..statesync.snapshot import unflatten_state

        if version <= self.weight_version:
            return True                # already serving newer weights
        with self._fleet_lock:
            if version in self._fleet_staged:
                return True            # duplicate push
            if not self._fleet_can_admit():
                return False
        template = {"params": jax.tree_util.tree_map(np.asarray,
                                                     self.params)}
        tree = unflatten_state(image, template)
        with self._fleet_lock:
            if not self._fleet_can_admit():
                return False
            self._fleet_staged[version] = (tree["params"],
                                           int(meta.get("step", 0)),
                                           int(meta.get("digest", 0)))
        return True

    def _fleet_can_admit(self) -> bool:
        """Make room under the stage cap (lock held): evict the oldest
        never-reported version if the window is full; False when every
        staged version has been reported (and so may be scheduled)."""
        if len(self._fleet_staged) < self._FLEET_STAGE_CAP:
            return True
        evictable = sorted(set(self._fleet_staged)
                           - self._fleet_reported)
        if not evictable:
            return False
        del self._fleet_staged[evictable[0]]
        return True

    def _fleet_staged_versions(self) -> tuple:
        """The versions this rank holds staged, for the completions
        exchange: the front schedules the newest version present in
        EVERY rank's report.  Reported versions become eviction-exempt
        until the exchange's intersection, which keeps only those every
        rank holds."""
        with self._fleet_lock:
            versions = tuple(sorted(self._fleet_staged))
            self._fleet_reported.update(versions)
            return versions

    def _fleet_staleness_steps(self) -> int:
        """Trainer steps between the newest snapshot this rank has
        staged and the weights currently serving (0 when current) — the
        loadgen staleness accounting (docs/fleet.md)."""
        with self._fleet_lock:
            steps = [s[1] for s in self._fleet_staged.values()]
        newest = max(steps) if steps else self._weight_step
        return max(0, newest - self._weight_step)

    def _fleet_swap(self, version: int) -> None:
        """Swap exactly the scheduled version in at the plan boundary
        the front broadcast.  Every rank executes this at the same step
        with the same version — never "whatever is staged locally",
        which can differ across ranks when a puller staged a newer
        image after the completions exchange, and would let ranks of
        one sharded replica group decode a step under mixed weights.
        In-flight slots keep decoding under the new weights, no
        admitted request is dropped."""
        with self._fleet_lock:
            staged = self._fleet_staged.pop(version, None)
            if staged is not None:
                # Older staged versions are superseded the moment a
                # newer one swaps in; they are dropped only now, after
                # the scheduled swap — never at stage time.
                for old in [v for v in self._fleet_staged
                            if v < version]:
                    del self._fleet_staged[old]
                self._fleet_reported &= set(self._fleet_staged)
        if staged is None:
            # The front schedules from the intersection of every
            # rank's reported staged set, so the version can only be
            # missing after a local restart; keep serving the old
            # weights until the puller re-stages.
            return
        params, meta_step, digest = staged
        self.params = jax.tree_util.tree_map(jnp.asarray, params)
        self.weight_version = version
        self._weight_step = meta_step
        self.stats["weight_swaps"].append(
            {"version": version, "step": self._step, "digest": digest,
             "at": time.monotonic()})
        from ..telemetry import flight
        from ..telemetry import metrics as telemetry_metrics

        rec = flight.recorder()
        if rec.enabled:
            rec.record("fleet-swap", name=f"v{version}",
                       detail=f"swapped at plan step {self._step}")
        tm = telemetry_metrics()
        if tm.enabled:
            tm.gauge("horovod_fleet_weight_version").set(version)
        logger.info("serving: weights v%d swapped at step %d", version,
                    self._step)

    def _statesync_boundary(self) -> None:
        change = self.statesync.step_boundary()
        if change is not None and change.kind == "grow":
            self._grow_resync(change.join_id, change.rank, change.size)

    def _grow_resync(self, join_id: int, new_rank: int,
                     new_size: int) -> None:
        """Realign the serving world after a grow: every rank (the
        joiner included — this is its first collective) exchanges
        (step, gen, resident rids), adopts the maxima, and rebuilds the
        batcher with the new replica group present but empty.  Nothing
        in flight is touched: incumbents' KV caches are process-local."""
        self._settle()
        old_size = self.size
        self.rank, self.size = new_rank, new_size
        self.front = 0
        self._configure_groups()
        mine = {"step": self._step, "gen": self._gen,
                "rids": (sorted(s.rid for s in self.slots
                                if s is not None)
                         if self.group_leader else [])}
        per_rank = self.hvd.allgather_object(
            mine, name=f"serve.growsync.{join_id}")
        self._step = max(p["step"] for p in per_rank)
        # Fresh gen: post-grow collective names never collide with any
        # pre-grow step another rank might still have cached.
        self._gen = max(p["gen"] for p in per_rank) + 1
        per_group = [per_rank[g * self.group_size]["rids"]
                     for g in range(self.num_groups)]
        self.batcher.rebuild(per_group)
        if self.prefill_rank_list:
            self._rebuild_kvstream()
        windows = getattr(self.statesync, "grow_windows", [])
        self.stats["grows"].append(
            {"join": join_id, "from": old_size, "to": new_size,
             "step": self._step, "at": time.monotonic(),
             "window_s": windows[-1][1] - windows[-1][0]
             if windows else 0.0})
        logger.warning("serving: grow %d->%d (join %d) at step %d",
                       old_size, new_size, join_id, self._step)

    def _note_perf(self, tokens: int, ctx_sum: int, dt_s: float) -> None:
        """Fold one decode step into the perfscope serve ledger gauges:
        accepted tokens/s, analytic FLOPs per token at the step's mean
        KV context, and their product over the chip peak (serve MFU) —
        the step ledger telemetry/perfmodel.build_ledger merges."""
        from ..telemetry import metrics as telemetry_metrics
        tm = telemetry_metrics()
        if not tm.enabled or tokens <= 0 or dt_s <= 0.0:
            return
        from ..telemetry import perfmodel
        tps = tokens / dt_s
        # EMA over steps: a serve step is milliseconds, and the raw
        # per-step rate whipsaws with batch occupancy.
        self._perf_tps = tps if self._perf_tps <= 0.0 \
            else 0.8 * self._perf_tps + 0.2 * tps
        flops_per_token = self.family.decode_flops(self.model.cfg,
                                                   ctx_sum / tokens)
        tm.gauge("horovod_serve_tokens_per_sec").set(self._perf_tps)
        tm.gauge("horovod_serve_flops_per_token").set(flops_per_token)
        # A device kind without a known peak gets no MFU gauge.
        peak = perfmodel.peak_flops(jax.local_devices()[0].device_kind)
        if peak is not None:
            tm.gauge("horovod_serve_mfu").set(
                self._perf_tps * flops_per_token / peak)

    # -- the loop --------------------------------------------------------
    def _serve_step(self) -> bool:
        before = (gc.get_stats()[2]["collections"],
                  resource.getrusage(resource.RUSAGE_THREAD))
        step = self._step
        admits = decoded = ctx_sum = 0
        parts = StepParts("serve", step=step)
        try:
            plan = None
            if self.rank == self.front:
                with parts("assemble"):
                    plan = self._assemble()
            with parts("plan_exchange"):
                plan = self._exchange_plan(plan)
            self._step += 1
            if plan.stop:
                self._settle(parts)
                return False
            admits = self._apply_plan(plan, parts)
            if not self.is_prefill:
                if self.prefill_rank_list:
                    self._integrate_prefills()
                issued, nxt = self._decode_once(parts)
                with parts("slot_update"):
                    self._advance_slots(issued, nxt)
                    for s in self.slots:
                        if s is not None and s.pending is None:
                            decoded += 1
                            ctx_sum += s.seq_len
                    self._collect_completions()
            with parts("completion_exchange"):
                completions = self._exchange_completions()
            with parts("account"):
                self._account(completions)
                if self.statesync is not None:
                    self._statesync_boundary()
                # The step so far: what is left of it is the tail of
                # this part, some microseconds.
                dt = parts.elapsed()
                self.admission.observe_step_ms(dt * 1e3)
                self._note_perf(decoded, ctx_sum, dt)
                if self._fleet_gauge is not None \
                        and self.rank == self.front:
                    self._fleet_gauge(self)
        finally:
            seconds = parts.close(admits=admits, decoded=decoded)
        self._note_step_parts(step, seconds, admits, before)
        return True

    def _note_step_parts(self, step: int, seconds: dict, admits: int,
                         before: tuple) -> None:
        """Fold one finished step's part timers into the always-on
        counters, and keep the record of a step that was slow for its
        kind: more than ``_SLOW_FACTOR`` times the median of the last
        ``_RECENT_STEPS`` such steps (the threshold is the data's).
        ``before`` is what the step sampled as it began: the collector's
        generation-2 count and this thread's ``getrusage``."""
        kind = "admit" if admits else "decode"
        total = seconds["total"]
        self.stats["steps"][kind] += 1
        sums = self.stats["step_parts_s"][kind]
        for part, s in seconds.items():
            sums[part] = sums.get(part, 0.0) + s
        recent = self._recent_steps[kind]
        slow = len(recent) >= _MIN_RECENT_STEPS \
            and total > _SLOW_FACTOR * statistics.median(recent)
        recent.append(total)
        from ..telemetry import metrics as telemetry_metrics
        tm = telemetry_metrics()
        if tm.enabled:
            for part, s in seconds.items():
                if part != "total":    # that is horovod_serve_step_ms
                    tm.histogram(
                        "horovod_serve_step_part_ms",
                        "Host time of one part of a serve step",
                        labels={"part": part}).observe(s * 1e3)
        if not slow:
            return
        parts_ms = {part: round(s * 1e3, 3) for part, s in seconds.items()
                    if part != "total"}
        gc2_before, usage0 = before
        usage = resource.getrusage(resource.RUSAGE_THREAD)
        record = {
            "step": step, "kind": kind, "wall_time": time.time(),
            "total_ms": round(total * 1e3, 3), "parts_ms": parts_ms,
            "slowest": max(parts_ms, key=parts_ms.get), "admits": admits,
            # Did a full (generation-2) collection of Python's garbage
            # collector run inside the step?
            "gc2": gc.get_stats()[2]["collections"] > gc2_before,
            # Was the serving thread running?  Its user plus system time
            # inside the step (the chip's host counts it in ticks of 10
            # ms, and time.thread_time() no finer) and how often the
            # kernel took the core from it: next to no CPU in a long step
            # names a thread that waited or was descheduled, the step's
            # worth the program.
            "cpu_ms": round((usage.ru_utime - usage0.ru_utime
                             + usage.ru_stime - usage0.ru_stime) * 1e3, 3),
            "nivcsw": usage.ru_nivcsw - usage0.ru_nivcsw}
        kept = self.stats["slow_steps"]
        if len(kept) >= _SLOW_STEPS_KEPT:
            del kept[0]                # the newest are kept
        kept.append(record)
        self.stats["slow_steps_total"] += 1
        tm.counter("horovod_serve_slow_steps_total",
                   "Serve steps slower than three times the running "
                   "median of their kind").inc()
        from ..telemetry import flight
        rec = flight.recorder()
        if rec.enabled:
            rec.record("serve_slow_step", name=f"step {step}",
                       detail=json.dumps(record))

    def serve_loop(self, *, stop_when=None, max_steps: int | None = None,
                   idle_sleep: float = 0.002) -> None:
        """Run serve steps until the front end declares the system
        drained (``stop_when()`` true on the front end AND queue and
        in-flight empty), riding elastic shrinks across rank failures.
        ``max_steps`` is a safety bound for tests."""
        if self._fleet_puller is None and config.FLEET.get():
            # HOROVOD_FLEET=1 (horovodrun --fleet): pull published
            # weights and, on the front, feed the controller's serve
            # gauges (fleet/wiring.py).
            from ..fleet.wiring import attach_replica
            self._fleet_runtime = attach_replica(self)
        while max_steps is None or self._step < max_steps:
            if self.rank == self.front:
                if stop_when is not None and stop_when():
                    self._stop_requested = True
                if (not self._stop_requested
                        and self.queue.depth() == 0
                        and self.batcher.inflight_count() == 0):
                    time.sleep(idle_sleep)   # don't hot-spin empty plans
            try:
                if not self._serve_step():
                    return
            except RanksFailedError as exc:
                self._shrink_and_resume(exc)

    # -- elastic shrink --------------------------------------------------
    def _shrink_and_resume(self, exc: RanksFailedError) -> None:
        from .. import core
        from ..resilience import converge_confirmed_dead

        # Converge on the heartbeat-CONFIRMED dead set (shared with the
        # statesync failure-shrink path, resilience/policy.py): every
        # survivor computes the same membership, and suspicion alone (a
        # slow peer) re-raises instead of shrinking.
        dead = converge_confirmed_dead(exc)
        survivors = [r for r in range(self.size) if r not in dead]
        new_rank = survivors.index(self.rank)
        new_size = len(survivors)
        from ..telemetry import flight

        rec = flight.recorder()
        if rec.enabled:
            rec.record("shrink", f"dead {sorted(dead)}",
                       detail=f"serving {self.size}->{new_size} at "
                              f"step {self._step}")
        logger.warning(
            "serving: shrink %d->%d (dead=%s); this rank %d -> %d",
            self.size, new_size, sorted(dead), self.rank, new_rank)
        base = os.environ.get("HOROVOD_RENDEZVOUS_EPOCH", "0")
        self._gen += 1
        tag = "_".join(str(r) for r in sorted(dead))
        core.reinit_world(
            rank=new_rank, size=new_size,
            epoch=f"{base.split('~', 1)[0]}~sv{self._gen}x{tag}")
        old = (self.rank, self.size)
        self.rank, self.size = new_rank, new_size
        self.front = 0
        self._configure_groups()
        if self.statesync is not None:
            self.statesync.notify_world_changed()
        self._resync()
        if self.prefill_rank_list:
            self._rebuild_kvstream()
        if not self.is_prefill:
            self._repair_pending()
        self.stats["shrinks"].append(
            {"dead": sorted(dead), "from": old[1], "to": new_size,
             "step": self._step})

    def _repair_pending(self) -> None:
        """After a world rebuild, any still-pending streamed prefill may
        have died with its prefill rank: re-prefill locally right away
        (the plan already committed these admissions — they are never
        dropped)."""
        now = time.monotonic()
        for i, s in enumerate(self.slots):
            if s is None or s.pending is None:
                continue
            a = s.pending
            self.slots[i] = None
            self._prefill_slot(i, a, now)
            self.stats["prefill_fallbacks"] += 1

    def _resync(self) -> None:
        """Rebuild shared state from ground truth after a world rebuild.

        - Survivors may have caught the failure at DIFFERENT steps (a
          per-rank data-plane error can abort rank A's plan broadcast
          while rank B fails one exchange later), so the step counter
          realigns to the maximum — collective names must match again.
        - Each group leader reports its resident rids (plus completions
          awaiting re-send); requests that vanished with dead replicas
          are counted lost.  Nothing on a surviving replica is ever
          dropped, so the zero-failed-on-survivors invariant holds.
        """
        self._settle()     # the step in flight at the failed exchange
        rids = sorted(s.rid for s in self.slots if s is not None)
        rids += [rec["rid"] for rec in self._unreported]
        mine = {"step": self._step,
                "rids": rids if self.group_leader else []}
        per_rank = self.hvd.allgather_object(
            mine, name=f"serve.resync.g{self._gen}")
        self._step = max(p["step"] for p in per_rank)
        per_group = [per_rank[g * self.group_size]["rids"]
                     for g in range(self.num_groups)]
        lost = self.batcher.rebuild(per_group)
        if self.rank == self.front:
            for _ in lost:
                self.admission.count("lost")
            self.stats["lost"] += len(lost)

    # -- introspection / teardown ----------------------------------------
    def request_stop(self) -> None:
        self._stop_requested = True

    def kv_stats(self) -> dict | None:
        """The paged pool's residency/reuse numbers for reports and the
        leak census (None in dense mode)."""
        kv = self.cache.kv_stats()
        if kv is not None:
            kv["max_concurrent_seqs"] = self.batcher.max_concurrent
            for key in ("prefill_streams", "prefill_fallbacks",
                        "prefill_skipped"):
                kv[key] = self.stats[key]
        return kv

    def close(self) -> None:
        """Release the serving resources this executor owns: the
        kvstream mesh (drain threads + sockets) and the slot cache (its
        KV block pool must not outlive the executor across elastic
        reinit cycles: hvdlife HVD702/704).  Leaves the part timers'
        totals and the slow steps in the log."""
        self._settle()
        if any(self.stats["steps"].values()):
            logger.info("serving: step parts %s", json.dumps(
                {key: self.stats[key]
                 for key in ("steps", "step_parts_s", "slow_steps_total",
                             "slow_steps", "admissions", "admit_s",
                             "prefill_prompt_tokens",
                             "prefill_bucket_positions")}))
        if self.stats["prefill_by_bucket"]:
            from ..telemetry.report import admission_table
            logger.info("serving: admissions by bucket\n%s",
                        admission_table(self.stats["prefill_by_bucket"]))
        if self._fleet_puller is not None:
            self._fleet_puller.close()
            self._fleet_puller = None
        if self._kvstream is not None:
            self._kvstream.close()
            self._kvstream = None
        self.cache.close()


# Slow-step records (``stats["slow_steps"]``): a step is slow when its
# host time passes _SLOW_FACTOR times the median of the last
# _RECENT_STEPS steps of its kind, once _MIN_RECENT_STEPS of them are in.
_SLOW_FACTOR = 3.0
_RECENT_STEPS = 64
_MIN_RECENT_STEPS = 8
_SLOW_STEPS_KEPT = 32


def _decode_model_cfg(cfg: ServeConfig):
    model_cfg = cfg.model_cfg
    if model_cfg is None:
        from ..models.transformer import gpt_tiny
        model_cfg = gpt_tiny(dtype=jnp.float32)
    return dataclasses.replace(model_cfg, decode=True,
                               max_seq_len=cfg.max_seq)


def _seeded_params(model, seed: int):
    """The seed's weights, as one compiled program (un-jitted, flax init
    compiles and dispatches each of its small ops on its own)."""
    return jax.jit(model.init)(jax.random.PRNGKey(seed),
                               jnp.zeros((1, 8), jnp.int32))["params"]


def serving_params_template(cfg: ServeConfig) -> dict:
    """The state tree a serving joiner offers to ``join_world``: the
    model's parameter pytree (shapes/dtypes only matter — values are
    replaced by the streamed image)."""
    import horovod_tpu  # noqa: F401 - jax config side effects

    model_cfg = _decode_model_cfg(cfg)
    params = _seeded_params(model_cfg.family.build(model_cfg), cfg.seed)
    return {"params": jax.tree_util.tree_map(np.asarray, params)}


def join_serving_world(serve_cfg: ServeConfig | None = None
                       ) -> "ReplicaExecutor":
    """Join a live serving world as a fresh replica (statesync grow):
    stream the incumbents' params peer-to-peer, enter as rank N, and
    return a ReplicaExecutor already realigned (step/gen/batcher) and
    ready for ``serve_loop``.  The incumbents' only stall is this
    rank's executor construction (model compile) between world rebuild
    and the first realign exchange — the bulk params transfer happened
    before they rebuilt anything."""
    from .. import statesync

    cfg = serve_cfg or ServeConfig.from_env()
    template = serving_params_template(cfg)
    tree, info = statesync.join_world(template)
    params = jax.tree_util.tree_map(jnp.asarray, tree["params"])
    ex = ReplicaExecutor(cfg, params=params)
    service = statesync.StateSyncService(state_provider=ex.state_tree,
                                         static_state=True)
    ex.attach_statesync(service)
    # First collective on the new world: adopt the incumbents'
    # step/gen and announce this (empty) replica group.
    ex._grow_resync(info.join_id, info.rank, info.size)
    return ex
