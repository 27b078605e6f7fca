"""Shared-memory data plane for same-host worlds.

The eager analogue of the reference's intra-node shared-memory paths —
Gloo's shm transport and MPIHierarchicalAllgather's node-shared window
(reference: horovod/common/ops/mpi_operations.cc) — rebuilt for the
multi-process-per-host layout of TPU VM hosts: ranks that share a machine
exchange bulk payloads through mmap'd /dev/shm regions instead of the TCP
loopback ring, cutting per-byte work from ~6 copies (user→kernel→user each
way plus staging) to ~3 (pack, reduce, copy-out) and roughly tripling
effective allreduce bandwidth on localhost worlds.

Protocol (per collective, lockstep across ranks — the identical-response-
order invariant guarantees every rank runs the same op sequence); this is
the allreduce shape, with broadcast/allgather using a 2-barrier variant
(stage, publish 3t+1, read peers, publish 3t+3 — monotonic ``>=`` waits
make the skipped middle word equivalent):

  wait all seq >= 3t      (peers finished reading my previous result)
  pack payload into my region;            publish seq = 3t+1
  wait all seq >= 3t+1    (everyone's payload visible)
  reduce chunk `rank` across all regions; publish seq = 3t+2
  wait all seq >= 3t+2    (all chunks reduced)
  gather chunks from owners, unpack;      publish seq = 3t+3

Sequence counters are 8-byte aligned words in each rank's region header;
aligned word stores/loads are atomic on the host ISAs we target and mmap
shared mappings are cache-coherent.  Liveness: each rank publishes its PID
at formation and waiters poll peer PIDs, so a dead peer surfaces as a
structured error in ~liveness-interval, not a transport timeout (SURVEY
§5.2 "mismatch → structured error, not hang").

SYMMETRIC-CALL CONTRACT: the barrier words above are sequence-counted
like multihost.kv_barrier — the protocol is only safe because every rank
executes the identical ResponseList in identical order, so a
rank-asymmetric collective upstream of this plane would wedge a peer at
``wait all seq >= 3t``.  That contract is proven statically by hvdlint
(``python -m horovod_tpu.analysis.lint``; rank-gated-collective /
collective-under-lock rules) and checked at runtime by
``HOROVOD_FINGERPRINT`` — which names the first divergent op in a
structured error before this plane's barrier deadline or the stall
inspector ever fire.  See docs/analysis.md.
"""
from __future__ import annotations

import mmap
import os
import time

import numpy as np

from ..common.dtypes import element_size, to_numpy
from ..common.message import Response, ResponseType
from ..common.status import Status
from ..common.tensor_queue import TensorTableEntry
from .base import (CollectiveBackend, accum_dtype as _accum_dtype,
                   dim0_row_bounds)

_HEADER = 4096          # one page: seq word + splits table + padding
_SEQ_OFFSET = 0
# Alltoall publishes the sender-side split row-counts in the header (the
# receiver needs the sender's offsets to find its slice): int64 count at
# +8, then up to _MAX_SPLITS int64 entries at +16.
_SPLITS_OFFSET = 16
_MAX_SPLITS = (_HEADER - _SPLITS_OFFSET) // 8
# Poison flag bit, OR'd onto the failing rank's LAST PUBLISHED sequence
# value (e.g. a rank failing after publishing 3t+1 poisons to
# _POISON + 3t+1).  Carrying the high-water mark matters: a rank that
# fails AFTER completing op t must not error a slow peer still inside op
# t's last wait — everything that peer needs was already published — so
# wait_all honors published progress below the mark and raises only for
# barriers beyond it (data that will never arrive).  The whole host then
# declines shm unanimously at the next op via ``poison_seen``.
_POISON = 1 << 62


def _boot_fingerprint() -> str:
    """Same-memory-domain fingerprint.  Hostname alone lies inside
    containers sharing a hostname on one box; the kernel boot id pins the
    machine, and the mount/IPC namespace inodes pin the /dev/shm tmpfs —
    two containers on one host share a boot id but NOT a mount ns, and a
    private /dev/shm must disqualify formation up front (the attach
    verdict round below is the backstop).  The NET namespace is included
    deliberately: it never splits ranks that could otherwise share
    /dev/shm in practice (container setups split mnt/ipc too), and it
    makes a network-namespace boundary behave exactly like a host
    boundary — which is what netns-based cross-host emulation relies
    on."""
    parts = []
    try:
        with open("/proc/sys/kernel/random/boot_id") as f:
            parts.append(f.read().strip())
    except OSError:
        parts.append("noboot")
    for ns in ("mnt", "ipc", "net"):
        try:
            parts.append(str(os.stat(f"/proc/self/ns/{ns}").st_ino))
        except OSError:
            parts.append("nons")
    import socket
    return socket.gethostname() + "." + ".".join(parts)


def _tune_malloc() -> None:
    """Keep multi-MB result buffers on the heap: glibc mmap()s allocations
    above the default threshold and munmap()s them on free, so every
    allreduce output repays ~4k page faults.  Raising the mmap/trim
    thresholds lets freed gradient-sized buffers be reused fault-free
    (measured: 16 MB op 17.9 ms -> 13.8 ms on one core).  Trade-off is
    retained RSS up to the threshold — right for bulk-data workers."""
    try:
        import ctypes
        libc = ctypes.CDLL("libc.so.6")
        libc.mallopt(-3, 256 << 20)   # M_MMAP_THRESHOLD
        libc.mallopt(-1, 256 << 20)   # M_TRIM_THRESHOLD
    except Exception:  # noqa: BLE001 - musl/macOS: no mallopt
        pass


def _shm_dir() -> str | None:
    for cand in ("/dev/shm", os.environ.get("TMPDIR", "/tmp")):
        if cand and os.path.isdir(cand) and os.access(cand, os.W_OK):
            return cand
    return None


class ShmWorld:
    """mmap'd per-rank regions + sequence-word lockstep for one world.

    Formation is collective through the rendezvous KV store and
    UNANIMOUS: every rank publishes (fingerprint, shm-usable, pid) and
    the world forms only if all ranks share one memory domain — so the
    backend chain stays rank-symmetric without extra negotiation.
    """

    def __init__(self, rank: int, size: int, kv, scope: str,
                 capacity: int, timeout: float = 30.0,
                 resilience=None) -> None:
        self.rank = rank
        self.size = size
        self.capacity = capacity
        self.timeout = timeout
        # Resilience (HOROVOD_FAULT_TOLERANCE): when on, the lockstep
        # barrier deadline derives from the per-op ResilienceContext
        # (one fault window) instead of the 600 s default, and the
        # liveness poll additionally consults the heartbeat monitor so a
        # WEDGED peer (PID alive, collective abandoned) is detected too.
        from ..resilience import active_state
        self._res = resilience if resilience is not None \
            else active_state()
        # Inter-op barrier deadline is deliberately MUCH larger than the
        # formation timeout: a live-but-slow peer (rank-0 checkpointing,
        # evaluation, CPU starvation) must not kill training — the 0.5 s
        # PID-liveness poll is the fail-fast path for actual death, and
        # one-sided submissions are the stall inspector's job upstream.
        self.barrier_timeout = float(os.environ.get(
            "HOROVOD_SHM_BARRIER_TIMEOUT_SECONDS", "600")) \
            if self._res is None else self._res.op_timeout()
        self._maps: list[mmap.mmap | None] = [None] * size
        self._seqs: list[np.ndarray | None] = [None] * size
        self._splits: list[np.ndarray | None] = [None] * size
        self._datas: list[np.ndarray | None] = [None] * size
        self._pids: list[int] = [0] * size
        self._paths: list[str] = [""] * size
        self.formed = False
        self._t = 0

        # Phase 1 — advertise (memory-domain fingerprint, capacity,
        # usability, pid); unanimity on domain AND capacity is required:
        # heterogeneous capacities would mmap past a smaller peer file
        # (SIGBUS on first touch) or desync enabled() across ranks.
        shm_dir = _shm_dir()
        usable = shm_dir is not None
        me = f"{_boot_fingerprint()}|{capacity}|{int(usable)}|{os.getpid()}"
        kv.put(scope, f"peer:{rank}", me.encode())
        peers = []
        for r in range(size):
            raw = kv.wait(scope, f"peer:{r}", timeout).decode()
            fp, cap, ok, pid = raw.rsplit("|", 3)
            peers.append((fp, int(cap), ok == "1", int(pid)))
        if not all(ok for _, _, ok, _ in peers) or \
                len({fp for fp, _, _, _ in peers}) != 1 or \
                len({cap for _, cap, _, _ in peers}) != 1:
            return   # not one memory domain: every rank skips unanimously

        # Phase 2 — create + attach, crash-proof: every rank ALWAYS
        # publishes a path (or "!") and then an attach verdict, so a
        # filesystem surprise on one rank degrades the whole world to the
        # TCP plane unanimously instead of crashing init or hanging peers.
        self._pids = [pid for _, _, _, pid in peers]
        attached = False
        try:
            path = os.path.join(shm_dir,
                                f"hvd_{scope}_{rank}_{os.getpid()}")
            try:   # stale region from a crashed same-pid predecessor
                os.unlink(path)
            except OSError:
                pass
            fd = os.open(path, os.O_CREAT | os.O_RDWR | os.O_EXCL, 0o600)
            try:
                os.ftruncate(fd, _HEADER + capacity)
                mm = mmap.mmap(fd, _HEADER + capacity)
            finally:
                os.close(fd)
            self._own_path = path
            self._attach(rank, mm, path)
            kv.put(scope, f"path:{rank}", path.encode())
        except OSError:
            kv.put(scope, f"path:{rank}", b"!")
        else:
            try:
                for r in range(size):
                    if r == rank:
                        continue
                    rpath = kv.wait(scope, f"path:{r}", timeout).decode()
                    if rpath == "!":
                        raise OSError("peer region unavailable")
                    fd = os.open(rpath, os.O_RDWR)
                    try:
                        mm = mmap.mmap(fd, _HEADER + capacity)
                    finally:
                        os.close(fd)
                    self._attach(r, mm, rpath)
                attached = True
            except OSError:
                attached = False

        # Phase 3 — unanimous attach verdict.
        kv.put(scope, f"att:{rank}", b"1" if attached else b"0")
        all_attached = all(
            kv.wait(scope, f"att:{r}", timeout) == b"1"
            for r in range(size))
        if not all_attached:
            self.close()
            return
        # Every peer holds an mmap now: unlink the file immediately so the
        # region becomes anonymous — a SIGKILLed job cannot leak
        # capacity-sized tmpfs files (the kernel frees the pages when the
        # last mapping dies with the processes).
        try:
            os.unlink(self._own_path)
        except OSError:
            pass
        self._own_path = ""
        _tune_malloc()
        self.formed = True

    def _attach(self, r: int, mm: mmap.mmap, path: str) -> None:
        self._maps[r] = mm
        self._paths[r] = path
        self._seqs[r] = np.frombuffer(mm, dtype=np.uint64, count=1,
                                      offset=_SEQ_OFFSET)
        self._splits[r] = np.frombuffer(mm, dtype=np.int64,
                                        count=1 + _MAX_SPLITS, offset=8)
        self._datas[r] = np.frombuffer(mm, dtype=np.uint8,
                                       count=self.capacity, offset=_HEADER)

    # -- lockstep ------------------------------------------------------
    def publish(self, value: int) -> None:
        self._seqs[self.rank][0] = value

    def poison(self) -> None:
        """Mark this world failed: peers blocked on data we never staged
        raise instead of timing out, peers merely draining barriers we
        already satisfied complete normally, and every rank declines shm
        for the next op (``poison_seen``), keeping the backend chain
        rank-symmetric."""
        self.formed = False
        try:
            cur = int(self._seqs[self.rank][0])   # type: ignore[index]
            if cur < _POISON:   # idempotent: keep the original mark
                self._seqs[self.rank][0] = _POISON + cur
        except Exception:  # noqa: BLE001 - already closed
            pass

    def poison_seen(self) -> bool:
        """Cross-rank poison probe for ``enabled()``.  A rank that fails
        AFTER its peers' last wait of op t (e.g. MemoryError during
        unpack) poisons and runs op t+1 on TCP — but peers that already
        finished op t would only notice inside op t+1's shm wait, a
        one-op plane desync that leaves the fallen-back rank blocked in
        the TCP ring until transport timeout.  Reading every seq word
        BEFORE claiming an op makes the decline unanimous.

        Residual race (accepted, bounded): a fast peer can pass this
        probe and enter op t+1's shm protocol before the failing rank
        writes its mark.  Outcome: the peer's first data wait (>= 3t+4)
        exceeds the decliner's boundary mark (3t+3) and raises a
        structured error for op t+1, while the decliner waits out the
        TCP transport timeout for the same op; from op t+2 every rank is
        on TCP.  Blast radius is ONE op, surfaced as
        HorovodInternalError on every affected rank (elastic recovery's
        trigger) — never stale data (see the freshness invariant in
        wait_all).  A TCP retry inside the raising op would be unsound:
        the mark cannot distinguish "declined to TCP" from "claimed op
        t+1 on shm and died before its first publish", and retrying
        against the latter mis-pairs payloads on the persistent TCP
        sockets."""
        if not self.formed:
            return True
        try:
            if any(int(s[0]) >= _POISON  # type: ignore[index]
                   for s in self._seqs):
                self.formed = False
                return True
        except Exception:  # noqa: BLE001 - region torn down under us
            self.formed = False
            return True
        return False

    def wait_all(self, target: int) -> None:
        start = time.monotonic()
        deadline = start + self.barrier_timeout
        next_liveness = start + 0.5
        while True:
            seqs = [int(s[0]) for s in self._seqs]  # type: ignore[index]
            # Published progress counts even from a poisoned rank (the
            # mark is its last publish + _POISON): barriers the failing
            # rank already satisfied complete; only barriers past its
            # high-water mark — data that will never arrive — raise.
            # A LIVE rank below the target is simply slow: keep waiting
            # (PID liveness and the barrier deadline cover death/stalls)
            # rather than letting a covering poison mark error an op the
            # slow rank is about to finish.  Freshness invariant: every
            # data-guarded wait in the five protocols targets >= 3t+1 of
            # its own op, while a rank that completed op t-1 and then
            # declined marks at exactly the 3t boundary — so a poison
            # mark can never satisfy a wait that would read data the
            # marked rank never staged.
            if all((s - _POISON if s >= _POISON else s) >= target
                   for s in seqs):
                return
            if any(s >= _POISON and s - _POISON < target for s in seqs):
                self.formed = False
                raise ConnectionError(
                    "shm world poisoned by a peer failure")
            now = time.monotonic()
            if now >= next_liveness:
                next_liveness = now + 0.5
                for r, pid in enumerate(self._pids):
                    if r == self.rank:
                        continue
                    try:
                        os.kill(pid, 0)
                    except OSError:
                        self._peer_died(r, pid)
                if self._res is not None:
                    # Heartbeat-declared failures (a peer wedged with its
                    # PID alive, or a death another rank witnessed first)
                    # convert this barrier too — same detection window as
                    # the socket planes.
                    failed = self._res.failed_ranks()
                    if failed:
                        self.poison()
                        from ..common.exceptions import RanksFailedError
                        from ..resilience import current_op
                        raise RanksFailedError(
                            failed, op=current_op(), phase="shm_barrier")
                if now > deadline:
                    self._barrier_deadline(target, seqs)
            # Small-op barriers resolve within a scheduling quantum:
            # yield-spin briefly.  Past that, the peer is mid-copy on a
            # core we may share — REALLY sleep (escalating to 1 ms) so it
            # gets whole quanta instead of alternating with our spin.
            waited = now - start
            if waited < 0.0003:
                time.sleep(0)
            else:
                time.sleep(min(max(waited / 4, 0.0004), 0.001))

    def _peer_died(self, r: int, pid: int) -> None:
        """PID-liveness verdict: always a RanksFailedError (a
        ConnectionError subclass, so pre-resilience handlers and the
        elastic loop both keep working); with fault tolerance on the
        death is also published to the liveness table so distant ranks
        attribute their own stalls to rank `r` within one poll."""
        from ..common.exceptions import RanksFailedError
        from ..resilience import current_op
        if self._res is not None:
            self._res.mark_failed(r, f"shm peer pid {pid} died")
        raise RanksFailedError(
            frozenset({r}), op=current_op(), phase="shm_barrier",
            message=f"shm peer rank {r} (pid {pid}) died")

    def _barrier_deadline(self, target: int, seqs: list[int]) -> None:
        """Deadline expiry: attribute the stall to the ranks still below
        the barrier target instead of a bare timeout (with resilience
        off this keeps the historical TimeoutError type)."""
        lagging = sorted(
            r for r, s in enumerate(seqs)
            if r != self.rank
            and (s - _POISON if s >= _POISON else s) < target)
        if self._res is None:
            raise TimeoutError(
                f"shm barrier target {target} not reached within "
                f"{self.barrier_timeout}s (lagging ranks: {lagging})")
        from ..common.exceptions import RanksFailedError
        from ..resilience import current_op
        for r in lagging:
            self._res.mark_failed(
                r, f"shm barrier target {target} missed for "
                   f"{self.barrier_timeout:g}s", confirmed=False)
        raise RanksFailedError(
            frozenset(lagging), op=current_op(), phase="shm_barrier",
            message=f"shm barrier target {target} not reached within "
                    f"{self.barrier_timeout:g}s; lagging ranks {lagging} "
                    f"are alive but absent from the collective (wedged).")

    def data(self, r: int) -> np.ndarray:
        return self._datas[r]   # type: ignore[return-value]

    def close(self) -> None:
        self._seqs = [None] * self.size
        self._splits = [None] * self.size
        self._datas = [None] * self.size
        for mm in self._maps:
            if mm is not None:
                try:
                    mm.close()
                except BufferError:   # outstanding views: leak, don't crash
                    pass
        self._maps = [None] * self.size
        own = getattr(self, "_own_path", None)
        if own:
            try:
                os.unlink(own)
            except OSError:
                pass


class ShmBackend(CollectiveBackend):
    """Same-host allreduce, broadcast, ragged allgather and alltoall over
    a ShmWorld; fused allreduce/allgather responses ride it natively
    (entry-major packed staging), other fused shapes fall through to the
    TCP/XLA planes via ``enabled()``.  Broadcast/allgather/alltoall use a
    2-barrier variant of the protocol (publish 3t+1 after staging, jump
    straight to 3t+3 after reading — the monotonic ``>=`` waits make the
    skipped middle word equivalent); alltoall additionally publishes its
    split table in the region header, with sentinel flags that delegate
    oversized payloads to TCP or surface invalid splits symmetrically."""

    name = "shm"

    def __init__(self, world: ShmWorld) -> None:
        self.world = world
        self.ops_executed = 0   # observability for tests/PERFORMANCE.md
        # Telemetry (no-op metrics when HOROVOD_METRICS=off): ops claimed
        # by this plane and bytes staged through the shared region.
        from ..telemetry import metrics as _tm_metrics
        _tm = _tm_metrics()
        self._m_ops = _tm.counter(
            "horovod_shm_ops_total",
            "Collectives executed on the shared-memory plane")
        self._m_staged = _tm.counter(
            "horovod_shm_staged_bytes_total",
            "Payload bytes staged into /dev/shm regions")
        # TcpBackend delegate for alltoall payloads that exceed the
        # region capacity: per-rank dim-0 sizes are not in the response,
        # so the fit decision can only be made mid-protocol — an
        # oversized rank raises a header flag and EVERY rank delegates
        # (set by core.init).
        self.tcp = None

    def enabled(self, response: Response,
                entries: list[TensorTableEntry]) -> bool:
        if self.world.poison_seen():
            return False
        rt = response.response_type
        if rt == ResponseType.ALLREDUCE:
            # Fused payload must fit one region.  All inputs to the
            # sizing decision come from the response, so it stays
            # rank-symmetric whatever the codec.
            n = sum(response.tensor_sizes)
            if self.quantized_codec(response) is not None:
                from ..compress import staged_nbytes
                per_chunk, stage_total = staged_nbytes(
                    n, self.world.size, self.quantized_codec(response),
                    self.codec_block_size(response))
                # Staged contribution chunks + the owner's requantized
                # result chunk live in one region concurrently.
                nbytes = stage_total + (max(per_chunk) if per_chunk
                                        else 0)
            else:
                wire_dt = self.wire_cast_dtype(response)
                itemsize = wire_dt.itemsize if wire_dt is not None \
                    else element_size(response.tensor_type)
                nbytes = n * itemsize
        elif rt == ResponseType.BROADCAST and len(entries) == 1:
            nbytes = response.tensor_sizes[0] * \
                element_size(response.tensor_type)
        elif rt == ResponseType.REDUCESCATTER and len(entries) == 1 \
                and entries[0].tensor is not None:
            # Shapes are cross-rank validated for reducescatter, so the
            # local staging size is a rank-symmetric decision.
            nbytes = np.asarray(entries[0].tensor).size * \
                element_size(response.tensor_type)
        elif rt == ResponseType.ALLTOALL:
            # Every clause is rank-symmetric (alltoall with a joined rank
            # is rejected upstream, so tensors are present everywhere);
            # capacity is checked mid-protocol via the header flag.
            return (self.world.formed and self.tcp is not None
                    and len(entries) == 1
                    and entries[0].tensor is not None
                    and self.world.size <= _MAX_SPLITS)
        elif rt == ResponseType.ALLGATHER \
                and all(e.tensor is not None for e in entries):
            # Each rank stages only its OWN blocks (entry-major packed
            # for fused responses); capacity must hold the LARGEST
            # per-rank packed payload anywhere so the decision is
            # rank-symmetric (dims come from the response, trailing
            # shapes from our own entries — cross-rank validated equal).
            esz = element_size(response.tensor_type)
            dims = self.allgather_entry_dims(response, len(entries),
                                             self.world.size)
            rests = []
            for e in entries:
                shape = np.asarray(e.tensor).shape
                rests.append(int(np.prod(shape[1:]))
                             if len(shape) > 1 else 1)
            per_rank, _ = self._fused_allgather_layout(dims, rests, esz)
            nbytes = int(per_rank.sum(axis=0).max())
        else:
            return False
        return self.world.formed and nbytes <= self.world.capacity

    @staticmethod
    def _stage_except(region: np.ndarray, flat_u8: np.ndarray,
                      lo_byte: int, hi_byte: int) -> None:
        """Stage a payload into this rank's region, skipping the
        [lo_byte, hi_byte) range destined to self: no peer ever reads it
        (the own slice is copied straight from the local buffer), so two
        writes save 1/size of the staging traffic."""
        region[:lo_byte] = flat_u8[:lo_byte]
        region[hi_byte:flat_u8.nbytes] = flat_u8[hi_byte:]

    def allreduce(self, response: Response,
                  entries: list[TensorTableEntry]) -> Status:
        t = self.world._t
        self.world._t += 1
        self._act_start(entries, "SHM_ALLREDUCE")
        try:
            return self._allreduce_locked(response, entries, t)
        except BaseException:
            # Leave no peer spinning on a barrier we will never publish.
            self.world.poison()
            raise
        finally:
            self._act_end(entries)

    def _allreduce_locked(self, response: Response,
                          entries: list[TensorTableEntry],
                          t: int) -> Status:
        w = self.world
        rank, size = w.rank, w.size
        result_dtype = to_numpy(response.tensor_type)
        codec = self.quantized_codec(response)
        if codec is not None:
            return self._allreduce_quantized(response, entries, t, codec)
        # Cast codecs (fp16/bf16) stage and reduce in the wire dtype —
        # the fp32-accumulation contract below already widens 16-bit
        # wires, so this reproduces the legacy cast-compression exactly
        # while shrinking the staged bytes 2x.
        np_dtype = self.wire_cast_dtype(response) or result_dtype
        n = sum(response.tensor_sizes)

        # Peers must be done READING my previous result before I repack.
        w.wait_all(3 * t)
        my_region = w.data(rank)[:n * np_dtype.itemsize].view(np_dtype)
        packed = self.pack_fusion_buffer(response, entries)
        packed = self.scale_buffer(packed, response.prescale_factor)
        my_region[:] = packed.astype(np_dtype, copy=False)
        w.publish(3 * t + 1)
        nbytes = n * np_dtype.itemsize
        self._m_ops.inc()
        self._m_staged.inc(nbytes)

        if size == 2:
            # Two ranks: one fused full-sum pass per rank beats the
            # chunked reduce+gather (2 barriers instead of 3, 2n touched
            # instead of 2.5n).
            w.wait_all(3 * t + 1)
            peer = w.data(1 - rank)[:nbytes].view(np_dtype)
            out = self._full_sum(my_region, peer, np_dtype)
            # 3t+2 and 3t+3 both published: peers wait on 3(t+1) before
            # repacking, so the skipped middle barrier stays consistent
            # with the general protocol.
            w.publish(3 * t + 3)
            out = out.astype(result_dtype, copy=False)
            out = self.scale_buffer(out, response.postscale_factor)
            self.unpack_fusion_buffer(out, response, entries)
            self.ops_executed += 1
            return Status.ok()

        # Reduce chunk `rank` across every rank's region (fp32 widening
        # for 16-bit wire dtypes, one rounding at the end — the flat-ring
        # numerics contract).
        base, rem = divmod(n, size)
        sizes = [base + (1 if i < rem else 0) for i in range(size)]
        bounds = np.cumsum([0] + sizes)
        lo, hi = int(bounds[rank]), int(bounds[rank + 1])
        w.wait_all(3 * t + 1)
        if hi > lo:
            acc_dt = _accum_dtype(np_dtype)
            mine = my_region[lo:hi]
            if acc_dt is np_dtype:
                # In-place accumulation into my chunk: peers only ever
                # read their OWN chunk index from my region, never mine,
                # so the read/write sets are disjoint.
                for r in range(size):
                    if r != rank:
                        mine += w.data(r)[lo * np_dtype.itemsize:
                                          hi * np_dtype.itemsize
                                          ].view(np_dtype)
            else:
                # 16-bit wire dtypes: widen once, round once.
                acc = mine.astype(acc_dt, copy=True)
                for r in range(size):
                    if r != rank:
                        acc += w.data(r)[lo * np_dtype.itemsize:
                                         hi * np_dtype.itemsize
                                         ].view(np_dtype).astype(acc_dt)
                mine[:] = acc.astype(np_dtype, copy=False)
        w.publish(3 * t + 2)

        # Gather the reduced chunks straight out of their owners' regions
        # into a FRESH private array (the regions are recycled next op;
        # entry outputs alias this array zero-copy and must outlive it).
        w.wait_all(3 * t + 2)
        out = np.empty(n, dtype=np_dtype)
        for r in range(size):
            rlo, rhi = int(bounds[r]), int(bounds[r + 1])
            if rhi > rlo:
                src = w.data(r)[rlo * np_dtype.itemsize:
                                rhi * np_dtype.itemsize].view(np_dtype)
                out[rlo:rhi] = src
        w.publish(3 * t + 3)

        out = out.astype(result_dtype, copy=False)
        out = self.scale_buffer(out, response.postscale_factor)
        self.unpack_fusion_buffer(out, response, entries)
        self.ops_executed += 1
        return Status.ok()

    def _allreduce_quantized(self, response: Response,
                             entries: list[TensorTableEntry],
                             t: int, codec) -> Status:
        """Quantized allreduce over the shm regions — the same
        owner-reduce math as TcpCollectives.quantized_allreduce (one
        input quantization, fp32 accumulation, one requantization of the
        reduced chunk), expressed in the 3-barrier lockstep:

          stage   serialized quantized chunks, one per destination rank,
                  at deterministic offsets;          publish 3t+1
          reduce  my chunk: dequantize every rank's contribution
                  (including my own) + sum in fp32, requantize once into
                  the region's RESULT area;          publish 3t+2
          gather  owners' requantized chunks, dequantize into a fresh
                  private array;                     publish 3t+3

        Regions carry ~1/4 (int8) / ~1/8 (uint4) of the fp32 bytes, and
        the reconstruction matches the tcp plane bit-for-bit (identical
        quantize/dequantize order — the fused kernels execute the same
        fp32 ops in the same rank order), so planes stay
        interchangeable.  Dispatch (HOROVOD_FUSED_KERNELS / the
        autotuned ``fused`` attribute): single-pass fused kernels
        (compress/fused.py — requantize straight into the shm region,
        dequantize+accumulate in place off the staged bytes) vs the
        reference per-chunk chain.  Bitwise identical either way."""
        fused = getattr(self, "fused", None)
        if fused is None:
            from ..common import config
            fused = self.fused = bool(config.FUSED_KERNELS.get())
        if fused:
            return self._allreduce_quantized_fused(response, entries, t,
                                                   codec)
        return self._allreduce_quantized_reference(response, entries, t,
                                                   codec)

    def _allreduce_quantized_fused(self, response: Response,
                                   entries: list[TensorTableEntry],
                                   t: int, codec) -> Status:
        from ..compress import chunk_bounds, staged_nbytes
        from ..compress.fused import FusedKernels
        fk = getattr(self, "_fk", None)
        if fk is None:
            fk = self._fk = FusedKernels()
        w = self.world
        rank, size = w.rank, w.size
        result_dtype = to_numpy(response.tensor_type)
        block_size = self.codec_block_size(response)
        n = sum(response.tensor_sizes)
        per_chunk, stage_total = staged_nbytes(n, size, codec, block_size)
        chunk_off = np.cumsum([0] + per_chunk)
        bounds = chunk_bounds(n, size)

        w.wait_all(3 * t)
        packed = self.pack_fusion_buffer(response, entries)
        packed = self.scale_buffer(packed, response.prescale_factor)
        x = packed.astype(np.float32, copy=False)
        region = w.data(rank)
        for j in range(size):
            wire = fk.encode(x[bounds[j]:bounds[j + 1]], codec,
                             block_size, ("enc",))
            region[int(chunk_off[j]):int(chunk_off[j]) + wire.size] = wire
        w.publish(3 * t + 1)

        w.wait_all(3 * t + 1)
        my_len = int(bounds[rank + 1] - bounds[rank])
        lo = int(chunk_off[rank])
        acc = fk.f32(("acc",), my_len)
        acc[:] = 0.0
        for r in range(size):                  # rank-order accumulate
            fk.decode_add(w.data(r)[lo:lo + per_chunk[rank]], my_len,
                          codec, block_size, acc, ("in",))
        reduced = fk.encode(acc, codec, block_size, ("red",))
        region[stage_total:stage_total + reduced.size] = reduced
        w.publish(3 * t + 2)

        w.wait_all(3 * t + 2)
        out = np.empty(n, np.float32)
        for r in range(size):
            fk.decode_into(w.data(r)[stage_total:stage_total
                                     + per_chunk[r]],
                           int(bounds[r + 1] - bounds[r]), codec,
                           block_size, out[bounds[r]:bounds[r + 1]],
                           ("out",))
        w.publish(3 * t + 3)

        out = out.astype(result_dtype, copy=False)
        out = self.scale_buffer(out, response.postscale_factor)
        self.unpack_fusion_buffer(out, response, entries)
        self.ops_executed += 1
        return Status.ok()

    def _allreduce_quantized_reference(self, response: Response,
                                       entries: list[TensorTableEntry],
                                       t: int, codec) -> Status:
        """Reference quantized lockstep (pre-fusion): per-chunk
        quantize/to_bytes into the region, from_bytes/dequantize out.
        Kept as the fused-vs-reference A/B baseline and the
        HOROVOD_FUSED_KERNELS=0 fallback."""
        from ..compress import (chunk_bounds, dequantize, from_bytes,
                                quantize, staged_nbytes, to_bytes)
        w = self.world
        rank, size = w.rank, w.size
        result_dtype = to_numpy(response.tensor_type)
        block_size = self.codec_block_size(response)
        n = sum(response.tensor_sizes)
        per_chunk, stage_total = staged_nbytes(n, size, codec, block_size)
        chunk_off = np.cumsum([0] + per_chunk)
        bounds = chunk_bounds(n, size)

        w.wait_all(3 * t)
        packed = self.pack_fusion_buffer(response, entries)
        packed = self.scale_buffer(packed, response.prescale_factor)
        x = packed.astype(np.float32, copy=False)
        region = w.data(rank)
        for j in range(size):
            raw = to_bytes(quantize(x[bounds[j]:bounds[j + 1]], codec,  # hvdlint: disable=per-segment-codec-loop -- this IS the reference chain the fused kernels replace; kept for the fused-vs-reference A/B and as the dispatch fallback
                                    block_size))
            region[int(chunk_off[j]):int(chunk_off[j]) + len(raw)] = \
                np.frombuffer(raw, np.uint8)
        w.publish(3 * t + 1)

        w.wait_all(3 * t + 1)
        my_len = int(bounds[rank + 1] - bounds[rank])
        lo = int(chunk_off[rank])
        acc = np.zeros(my_len, np.float32)
        for r in range(size):
            raw = w.data(r)[lo:lo + per_chunk[rank]]
            acc += dequantize(from_bytes(raw, my_len, codec, block_size))  # hvdlint: disable=per-segment-codec-loop -- reference A/B baseline (see above)
        reduced = to_bytes(quantize(acc, codec, block_size))
        region[stage_total:stage_total + len(reduced)] = \
            np.frombuffer(reduced, np.uint8)
        w.publish(3 * t + 2)

        w.wait_all(3 * t + 2)
        out = np.empty(n, np.float32)
        for r in range(size):
            raw = w.data(r)[stage_total:stage_total + per_chunk[r]]
            out[bounds[r]:bounds[r + 1]] = dequantize(  # hvdlint: disable=per-segment-codec-loop -- reference A/B baseline (see above)
                from_bytes(raw, int(bounds[r + 1] - bounds[r]), codec,  # hvdlint: disable=per-segment-codec-loop -- reference A/B baseline (see above)
                           block_size))
        w.publish(3 * t + 3)

        out = out.astype(result_dtype, copy=False)
        out = self.scale_buffer(out, response.postscale_factor)
        self.unpack_fusion_buffer(out, response, entries)
        self.ops_executed += 1
        return Status.ok()

    @staticmethod
    def _full_sum(a: np.ndarray, b: np.ndarray,
                  np_dtype: np.dtype) -> np.ndarray:
        acc_dt = _accum_dtype(np_dtype)
        if acc_dt is np_dtype:
            out = np.empty(a.shape, dtype=np_dtype)
            np.add(a, b, out=out)
            return out
        return (a.astype(acc_dt) + b.astype(acc_dt)).astype(np_dtype)

    def broadcast(self, response: Response,
                  entries: list[TensorTableEntry]) -> Status:
        """Root writes its payload once; every peer reads it straight out
        of the root's region — one copy in, one copy out per rank,
        vs the TCP star's per-peer socket round trips (big win for
        broadcast_parameters at model startup)."""
        w = self.world
        t = w._t
        w._t += 1
        self._act_start(entries, "SHM_BCAST")
        try:
            np_dtype = to_numpy(response.tensor_type)
            root = response.root_rank
            (entry,) = entries
            w.wait_all(3 * t)
            if w.rank == root:
                shape = np.asarray(entry.tensor).shape
                # NB: ascontiguousarray promotes 0-d to 1-d — restore the
                # original shape on the output.
                local = np.ascontiguousarray(
                    np.asarray(entry.tensor, dtype=np_dtype))
                w.data(root)[:local.nbytes] = \
                    local.reshape(-1).view(np.uint8)
                w.publish(3 * t + 1)
                entry.output = local.copy().reshape(shape)
            else:
                w.publish(3 * t + 1)
                w.wait_all(3 * t + 1)
                n = response.tensor_sizes[0]
                src = w.data(root)[:n * np_dtype.itemsize].view(np_dtype)
                shape = np.asarray(entry.tensor).shape \
                    if entry.tensor is not None else (n,)
                entry.output = src.reshape(shape).copy()
            w.publish(3 * t + 3)
            self.ops_executed += 1
            return Status.ok()
        except BaseException:
            w.poison()
            raise
        finally:
            self._act_end(entries)

    def allgather(self, response: Response,
                  entries: list[TensorTableEntry]) -> Status:
        """Each rank stages its (ragged dim-0) blocks in its own region —
        entry-major packed for fused responses — and peers assemble the
        rank-ordered concatenation directly from the owners' regions:
        one staging pass and one read pass regardless of how many
        tensors the response fused."""
        w = self.world
        t = w._t
        w._t += 1
        self._act_start(entries, "SHM_ALLGATHER")
        try:
            np_dtype = to_numpy(response.tensor_type)
            dims = self.allgather_entry_dims(response, len(entries),
                                             w.size)
            locals_ = [np.ascontiguousarray(
                np.asarray(e.tensor, dtype=np_dtype)) for e in entries]
            rests = [int(np.prod(a.shape[1:])) if a.ndim > 1 else 1
                     for a in locals_]
            itemsize = np_dtype.itemsize
            # bytes[i][r] and each entry's exclusive prefix inside rank
            # r's entry-major region (shared layout with the flat planes).
            nbytes, ent_off = self._fused_allgather_layout(dims, rests,
                                                           itemsize)
            w.wait_all(3 * t)
            staged = 0
            for a in locals_:
                w.data(w.rank)[staged:staged + a.nbytes] = \
                    a.reshape(-1).view(np.uint8)
                staged += a.nbytes
            w.publish(3 * t + 1)
            w.wait_all(3 * t + 1)
            for i, entry in enumerate(entries):
                total = sum(dims[i])
                out = np.empty(total * rests[i], dtype=np_dtype)
                offset = 0
                for r in range(w.size):
                    count = dims[i][r] * rests[i]
                    if r == w.rank:   # own block: skip the region trip
                        out[offset:offset + count] = \
                            locals_[i].reshape(-1)
                    else:
                        lo = int(ent_off[i, r])
                        out[offset:offset + count] = \
                            w.data(r)[lo:lo + count * itemsize
                                      ].view(np_dtype)
                    offset += count
                entry.output = out.reshape((total,)
                                           + locals_[i].shape[1:])
            w.publish(3 * t + 3)
            self.ops_executed += 1
            return Status.ok()
        except BaseException:
            w.poison()
            raise
        finally:
            self._act_end(entries)

    def reducescatter(self, response: Response,
                      entries: list[TensorTableEntry]) -> Status:
        """Stage the full buffer; reduce only my dim-0 row range across
        all regions (same uneven row split as the TCP plane) — no gather
        phase at all, 2 barriers, (size-1)/size of the payload read."""
        w = self.world
        t = w._t
        w._t += 1
        self._act_start(entries, "SHM_REDUCESCATTER")
        try:
            np_dtype = to_numpy(response.tensor_type)
            (entry,) = entries
            local = np.ascontiguousarray(
                np.asarray(entry.tensor, dtype=np_dtype))
            shape = local.shape
            rest = int(np.prod(shape[1:])) if len(shape) > 1 else 1
            rows = dim0_row_bounds(shape[0], w.size)
            lo = rows[w.rank] * rest
            hi = rows[w.rank + 1] * rest

            w.wait_all(3 * t)
            flat = self.scale_buffer(local.reshape(-1),
                                     response.prescale_factor)
            self._stage_except(w.data(w.rank), flat.view(np.uint8),
                               lo * np_dtype.itemsize,
                               hi * np_dtype.itemsize)
            w.publish(3 * t + 1)
            w.wait_all(3 * t + 1)
            acc_dt = _accum_dtype(np_dtype)
            acc = flat[lo:hi].astype(acc_dt, copy=True)
            for r in range(w.size):
                if r != w.rank:
                    peer = w.data(r)[lo * np_dtype.itemsize:
                                     hi * np_dtype.itemsize].view(np_dtype)
                    acc += peer.astype(acc_dt) if acc_dt != np_dtype \
                        else peer
            w.publish(3 * t + 3)
            out = self.scale_buffer(acc.astype(np_dtype, copy=False),
                                    response.postscale_factor)
            my_rows = rows[w.rank + 1] - rows[w.rank]
            entry.output = out.reshape((my_rows,) + shape[1:])
            self.ops_executed += 1
            return Status.ok()
        except BaseException:
            w.poison()
            raise
        finally:
            self._act_end(entries)

    def alltoall(self, response: Response,
                 entries: list[TensorTableEntry]) -> Status:
        """Each rank stages its full send buffer + its split row-counts
        (header table); peers pull exactly their targeted slice from each
        sender's region — no pairwise socket exchange."""
        w = self.world
        t = w._t
        w._t += 1
        self._act_start(entries, "SHM_ALLTOALL")
        try:
            np_dtype = to_numpy(response.tensor_type)
            (entry,) = entries
            local = np.ascontiguousarray(
                np.asarray(entry.tensor, dtype=np_dtype))
            splits = self.resolve_alltoall_splits(entry, local.shape[0],
                                                  w.size)
            rest = int(np.prod(local.shape[1:])) if local.ndim > 1 else 1
            w.wait_all(3 * t)
            table = w._splits[w.rank]
            if isinstance(splits, Status):
                # Rank-local argument error: the sentinel keeps every
                # peer IN the lockstep (a bare return would strand them
                # at the barrier) and makes the failure symmetric — an
                # improvement over pairwise planes, where one bad rank
                # can stall its partners.
                table[0] = -2
            elif local.nbytes > w.capacity:
                table[0] = -1   # too big: ask every rank to delegate
            else:
                own_lo = sum(splits[:w.rank]) * rest * np_dtype.itemsize
                own_hi = own_lo + splits[w.rank] * rest * np_dtype.itemsize
                self._stage_except(w.data(w.rank),
                                   local.reshape(-1).view(np.uint8),
                                   own_lo, own_hi)
                table[0] = len(splits)
                table[1:1 + len(splits)] = splits
            w.publish(3 * t + 1)
            w.wait_all(3 * t + 1)
            flags = [int(w._splits[r][0]) for r in range(w.size)]
            if any(f == -2 for f in flags):
                w.publish(3 * t + 3)
                return splits if isinstance(splits, Status) else \
                    Status.invalid_argument(
                        "a peer submitted invalid alltoall splits")
            if any(f == -1 for f in flags):
                # Unanimous fallback: some rank's buffer exceeds the
                # region; all ranks run the pairwise TCP exchange.
                w.publish(3 * t + 3)
                return self.tcp.alltoall(response, entries)
            recv_splits = []
            slices = []
            for r in range(w.size):
                peer_table = w._splits[r]
                peer_splits = [int(x)
                               for x in peer_table[1:1 + int(peer_table[0])]]
                start = sum(peer_splits[:w.rank]) * rest
                rows = peer_splits[w.rank]
                slices.append((start, rows * rest))
                recv_splits.append(rows)
            out = np.empty(sum(n for _, n in slices), dtype=np_dtype)
            offset = 0
            for r, (start, count) in enumerate(slices):
                if r == w.rank:   # own block: skip the region round-trip
                    out[offset:offset + count] = \
                        local.reshape(-1)[start:start + count]
                else:
                    lo = start * np_dtype.itemsize
                    out[offset:offset + count] = \
                        w.data(r)[lo:lo + count * np_dtype.itemsize
                                  ].view(np_dtype)
                offset += count
            w.publish(3 * t + 3)
            entry.output = out.reshape((sum(recv_splits),)
                                       + local.shape[1:])
            entry.received_splits = recv_splits
            self.ops_executed += 1
            return Status.ok()
        except BaseException:
            w.poison()
            raise
        finally:
            self._act_end(entries)
