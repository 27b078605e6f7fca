"""Centralised knob registry, env-var driven like the reference.

The reference scatters ~30 `HOROVOD_*` env knobs across
horovod/common/common.h:66-96 and parses them ad hoc inside
BackgroundThreadLoop (operations.cc:395-540) + utils/env_parser.cc.  Here
every knob is declared once with its type, default and documentation, and the
same `HOROVOD_*` names are honoured so existing launch scripts keep working.
The runtime autotuner (parameter_manager) may override a subset at runtime.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable


def _parse_bool(v: str) -> bool:
    return v.strip().lower() in ("1", "true", "yes", "on")


@dataclasses.dataclass
class Knob:
    name: str            # env var name (HOROVOD_* for compatibility)
    default: Any
    parser: Callable[[str], Any]
    doc: str = ""

    def get(self) -> Any:
        raw = os.environ.get(self.name)
        if raw is None or raw == "":
            return self.default
        try:
            return self.parser(raw)
        except (ValueError, TypeError):
            return self.default


_REGISTRY: dict[str, Knob] = {}


def register(name: str, default: Any, parser: Callable[[str], Any], doc: str = "") -> Knob:
    knob = Knob(name, default, parser, doc)
    _REGISTRY[name] = knob
    return knob


def get(name: str) -> Any:
    return _REGISTRY[name].get()


def all_knobs() -> dict[str, Knob]:
    return dict(_REGISTRY)


def _knob_type_name(parser: Callable[[str], Any]) -> str:
    return {"_parse_bool": "bool", "parse_tristate": "tristate"}.get(
        getattr(parser, "__name__", ""),
        getattr(parser, "__name__", "str"))


def _knob_default_repr(default: Any) -> str:
    if isinstance(default, bool):
        return "1" if default else "0"
    if default == "" or default is None:
        return "*(unset)*"
    return f"`{default}`"


def configuration_markdown() -> str:
    """The generated knob table: one row per registered ``HOROVOD_*``
    knob (name, type, default, doc).  ``python -m
    horovod_tpu.analysis.lint --knobs`` prints it, docs/configuration.md
    embeds it, and CI asserts the two are byte-identical — an
    undocumented knob cannot exist, and hvdflow's HVD604 flags any raw
    environment read of a name missing from this registry."""
    lines = [
        "# Configuration — the typed `HOROVOD_*` knob registry",
        "",
        "<!-- GENERATED FILE — do not edit by hand.  Regenerate with",
        "     `python -m horovod_tpu.analysis.lint --knobs >"
        " docs/configuration.md`;",
        "     tests/test_lint_clean.py asserts this file matches the",
        "     registry in horovod_tpu/common/config.py. -->",
        "",
        f"Every knob is declared once in `horovod_tpu/common/config.py`"
        f" with its type,",
        "default and doc line; raw `os.environ` reads of `HOROVOD_*`"
        " names outside the",
        "registry are flagged by hvdflow rule HVD604"
        " (docs/analysis.md).",
        "",
        f"{len(_REGISTRY)} knobs:",
        "",
        "| knob | type | default | description |",
        "|---|---|---|---|",
    ]
    for name in sorted(_REGISTRY):
        k = _REGISTRY[name]
        doc = " ".join(k.doc.split())
        lines.append(f"| `{name}` | {_knob_type_name(k.parser)} | "
                     f"{_knob_default_repr(k.default)} | {doc} |")
    lines.append("")
    return "\n".join(lines)


# --- Core cycle / fusion knobs (reference: common/common.h:66-96) -----------
FUSION_THRESHOLD = register(
    "HOROVOD_FUSION_THRESHOLD", 64 * 1024 * 1024, int,
    "Tensor-fusion buffer threshold in bytes (0 disables fusion).")
CYCLE_TIME = register(
    "HOROVOD_CYCLE_TIME", 1.0, float,
    "Background-loop cycle time in milliseconds.")
CACHE_CAPACITY = register(
    "HOROVOD_CACHE_CAPACITY", 1024, int,
    "Response-cache capacity (0 disables caching).")
HIERARCHICAL_ALLREDUCE = register(
    "HOROVOD_HIERARCHICAL_ALLREDUCE", False, _parse_bool,
    "Two-level reduce: reduce-scatter over ICI, cross-reduce over DCN, "
    "all-gather over ICI.")
HIERARCHICAL_ALLGATHER = register(
    "HOROVOD_HIERARCHICAL_ALLGATHER", False, _parse_bool,
    "Two-level allgather over (ICI, DCN) axes.")
SHM_OPERATIONS = register(
    "HOROVOD_SHM_OPERATIONS", "auto", str,
    "Same-host shared-memory data plane for eager allreduce: 1=require, "
    "0=disable, auto=use when every rank shares one memory domain.")
SHM_CAPACITY = register(
    "HOROVOD_SHM_CAPACITY", 0, int,
    "Per-rank shm region bytes (0 = max(fusion threshold, 64MB)); "
    "payloads above it fall through to the TCP plane.")
SEGMENT_BYTES = register(
    "HOROVOD_SEGMENT_BYTES", 256 * 1024, int,
    "TCP ring pipeline segment: the receiver consumes each ring chunk in "
    "segments of this many bytes, accumulating segment k while the NIC "
    "streams segment k+1 (comm/compute overlap; bit-identical numerics). "
    "0 disables segmentation (one monolithic receive+add per chunk).")
TOPOLOGY = register(
    "HOROVOD_TOPOLOGY", "", str,
    "Physical layout declaration for topology-aware collectives: flat "
    "(layout-oblivious) | host (two-level host x slot; rings keep "
    "intra-host peers adjacent) | torus:RxC (R x C grid, rank = "
    "row*C + col; rings walk grid neighbors and the two-phase torus "
    "allreduce becomes eligible).  Empty = auto: host when the env "
    "describes a homogeneous two-level layout, else flat.  Must be "
    "launcher-uniform across ranks.")
HOST_IDS = register(
    "HOROVOD_HOST_IDS", "", str,
    "World-wide rank-to-host-index map as comma-separated ints "
    "(\"0,0,1,1\"), set by the launcher from the slot layout so topology "
    "resolution can group ring orders by host even when the layout is "
    "not homogeneous host-major (elastic re-assignments, uneven slots "
    "per host).  Empty = derive from local/cross sizes.  Ignored unless "
    "its length equals the world size.  Launcher-uniform across ranks.")
ALGO = register(
    "HOROVOD_ALGO", "auto", str,
    "Eager-plane allreduce algorithm: auto (tree under "
    "HOROVOD_TREE_THRESHOLD_BYTES, torus two-phase on a declared torus, "
    "segmented ring otherwise) | ring | tree (binomial gather-to-root + "
    "broadcast, O(log N) latency) | rhd (recursive halving-doubling; "
    "power-of-two worlds, else tree) | torus.  Launcher-uniform; the "
    "autotuner can retune it at runtime (ResponseList.tuned_algo).")
TREE_THRESHOLD_BYTES = register(
    "HOROVOD_TREE_THRESHOLD_BYTES", 64 * 1024, int,
    "Payloads at or below this many wire bytes take the O(log N) tree "
    "allreduce instead of the O(N)-step ring under HOROVOD_ALGO=auto "
    "(latency-bound small tensors; the ring stays bandwidth-optimal "
    "above it).  0 disables the small-tensor path; the autotuner sweeps "
    "it (ResponseList.tuned_tree_threshold).")
BATCH_D2D_MEMCOPIES = register(
    "HOROVOD_BATCH_D2D_MEMCOPIES", True, _parse_bool,
    "Fuse gather/scatter staging copies into batched device ops.")
DISABLE_GROUP_FUSION = register(
    "HOROVOD_DISABLE_GROUP_FUSION", False, _parse_bool,
    "Disable fusion across explicitly grouped collectives.")
ELASTIC = register(
    "HOROVOD_ELASTIC", False, _parse_bool,
    "Enable elastic (fault tolerant / autoscaling) mode.")

# --- Wire compression (compress/ subsystem; EQuARX-style, PAPERS.md) --------
COMPRESSION = register(
    "HOROVOD_COMPRESSION", "none", str,
    "Default wire codec for eager allreduces: none | fp16 | bf16 | int8 "
    "| uint4.  Quantized codecs apply blockwise scale+zero-point "
    "compression to floating tensors; integer tensors always ride "
    "uncompressed.  Per-call `codec=`/`compression=` arguments override.")
COMPRESSION_BLOCK_SIZE = register(
    "HOROVOD_COMPRESSION_BLOCK_SIZE", 256, int,
    "Elements per quantization block for the int8/uint4 codecs (must be "
    "even for uint4).  Smaller blocks: tighter error bound, more scale "
    "metadata on the wire (8 bytes/block).")
AUTOTUNE_COMPRESSION = register(
    "HOROVOD_AUTOTUNE_COMPRESSION", False, _parse_bool,
    "Let the autotuner sweep wire codecs (none/fp16/int8) by measured "
    "allreduce throughput and broadcast the winner to every rank.")
FUSED_KERNELS = register(
    "HOROVOD_FUSED_KERNELS", True, _parse_bool,
    "Single-pass fused codec kernels on the quantized/cast collective "
    "legs (compress/fused.py): dequantize+accumulate straight off the "
    "wire, requantize straight into a persistent wire image.  Bitwise "
    "identical to the reference chain; 0 restores the per-chunk "
    "dequant/requant path (the fused-vs-reference A/B baseline).  Must "
    "be set identically on every rank; the autotuner can retune it at "
    "runtime (ResponseList.tuned_fused).")

# --- Autotune (reference: common/parameter_manager.cc) ----------------------
AUTOTUNE = register(
    "HOROVOD_AUTOTUNE", False, _parse_bool,
    "Enable Bayesian autotuning of fusion threshold and cycle time.")
AUTOTUNE_LOG = register(
    "HOROVOD_AUTOTUNE_LOG", "", str,
    "CSV file to log autotune samples to.")
AUTOTUNE_WARMUP_SAMPLES = register(
    "HOROVOD_AUTOTUNE_WARMUP_SAMPLES", 3, int,
    "Discarded warmup samples per autotune step.")
AUTOTUNE_STEPS_PER_SAMPLE = register(
    "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE", 10, int,
    "Training steps scored per autotune sample.")
AUTOTUNE_BAYES_OPT_MAX_SAMPLES = register(
    "HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES", 20, int,
    "Max Bayesian-optimization samples before fixing parameters.")
AUTOTUNE_GAUSSIAN_PROCESS_NOISE = register(
    "HOROVOD_AUTOTUNE_GAUSSIAN_PROCESS_NOISE", 0.8, float,
    "GP observation-noise hyperparameter (alpha).")

# --- Timeline (reference: common/timeline.cc) -------------------------------
TIMELINE = register(
    "HOROVOD_TIMELINE", "", str,
    "Path for the Chrome-trace timeline JSON ('DYNAMIC' = start stopped).")
TIMELINE_MARK_CYCLES = register(
    "HOROVOD_TIMELINE_MARK_CYCLES", False, _parse_bool,
    "Mark background-loop cycles in the timeline.")

# --- Telemetry (telemetry/ subsystem; docs/observability.md) ----------------
METRICS = register(
    "HOROVOD_METRICS", False, _parse_bool,
    "Per-rank metrics registry + cross-rank straggler aggregation "
    "(on|off).  Off (the default) keeps every hot path free of new "
    "locks and syscalls: all instrumentation resolves to shared no-op "
    "metrics.")
METRICS_PORT = register(
    "HOROVOD_METRICS_PORT", 0, int,
    "Base port for the Prometheus text exposition endpoint; rank r "
    "serves on port+r (ephemeral fallback if taken).  0 disables the "
    "HTTP server (the registry still records).")
METRICS_FILE = register(
    "HOROVOD_METRICS_FILE", "", str,
    "Path for the shutdown JSON metrics dump; '{rank}' substitutes the "
    "rank, otherwise '.r<rank>' is inserted before the extension.  "
    "Empty disables the dump.  Summarize with "
    "python -m horovod_tpu.telemetry.report.")
METRICS_BIND = register(
    "HOROVOD_METRICS_BIND", "127.0.0.1", str,
    "Bind address for the Prometheus exposition endpoint.  Defaults to "
    "localhost: metrics name tensors, hosts and failure details, so "
    "off-host exposure must be an explicit decision ('' or 0.0.0.0 "
    "binds all interfaces for real scrape deployments).")
METRICS_WINDOW = register(
    "HOROVOD_METRICS_WINDOW", 32, int,
    "Negotiated tensors per straggler-aggregation window: the "
    "coordinator publishes min/mean/max/p99 cross-rank arrival lag and "
    "names the slowest rank once per window.")
STRAGGLER_THRESHOLD_MS = register(
    "HOROVOD_STRAGGLER_THRESHOLD_MS", 5.0, float,
    "Median arrival lag (ms behind the fastest rank, per window) above "
    "which the coordinator logs a structured straggler warning and sets "
    "the straggler-rank gauge.")

# --- perfscope roofline accounting (telemetry/perfmodel.py; ISSUE 19) -------
PERF_PEAK_MBPS = register(
    "HOROVOD_PERF_PEAK_MBPS", 0.0, float,
    "Peak per-link bus bandwidth (MB/s) the perfscope roofline divides "
    "measured busbw by (docs/observability.md).  0 = self-calibrate: "
    "the best measured (plane, algo, codec, size-bucket) cell in the "
    "ledger window IS the roofline, so efficiencies answer 'how far "
    "below the best this fabric demonstrated' without a link spec.")
PERF_PEAK_FLOPS = register(
    "HOROVOD_PERF_PEAK_FLOPS", 0.0, float,
    "Peak per-chip dense FLOP/s the MFU ledger divides by.  0 = the "
    "published per-device_kind table (telemetry/perfmodel.py); a device "
    "kind the table does not know (CPU dev boxes) has no peak, and no "
    "MFU gauge is set there.")
PERF_TOLERANCE_PCT = register(
    "HOROVOD_PERF_TOLERANCE_PCT", 10.0, float,
    "Regression-gate tolerance: telemetry.perfcheck fails (exit 1, "
    "structured finding) when a (plane, op, size-bucket) busbw cell or "
    "the MFU drops more than this percentage below the baseline "
    "ledger.")
PERF_MIN_SAMPLES = register(
    "HOROVOD_PERF_MIN_SAMPLES", 1, int,
    "Observations a (plane, op, codec, algo, size-bucket) cell needs "
    "before the perf ledger includes it (noise floor for the busbw "
    "table and the perfcheck gate).")

# --- Flight recorder (telemetry/flight.py; docs/observability.md) -----------
FLIGHT = register(
    "HOROVOD_FLIGHT", True, _parse_bool,
    "Always-on flight recorder: a lock-light bounded ring of recent "
    "trace events per rank (enqueue, dispatch, completion, failure "
    "conversions), dumped as rank-stamped JSON when a structured "
    "failure fires (RanksFailedError, fingerprint divergence, deadline "
    "poison, SIGTERM) — trace evidence without HOROVOD_TIMELINE.  "
    "0 restores the exact zero-overhead posture: a shared no-op "
    "recorder, no ring, no signal handler, no threads either way.")
FLIGHT_EVENTS = register(
    "HOROVOD_FLIGHT_EVENTS", 256, int,
    "Ring capacity of the flight recorder: the last N trace events per "
    "rank survive into a failure dump.")
FLIGHT_FILE = register(
    "HOROVOD_FLIGHT_FILE", "horovod_flight.json", str,
    "Path of the flight-recorder failure dump; '{rank}' substitutes, "
    "otherwise '.r<rank>' is inserted before the extension (the "
    "HOROVOD_METRICS_FILE convention).  Written only when a structured "
    "failure fires.")

# --- hvdsan runtime witness (analysis/hvdsan/; docs/analysis.md) ------------
# NOTE: san.py reads the raw environment directly (it must run at
# package import, before this registry is touched); the knobs are
# registered here so `all_knobs()` documents them.
SAN = register(
    "HOROVOD_SAN", False, _parse_bool,
    "hvdsan runtime lock-order witness: wrap every package "
    "threading.Lock/RLock/Condition in a recording proxy, record "
    "per-thread acquisition-order edges (first observations also land "
    "in the flight-recorder ring), and dump the observed lock-order "
    "graph as rank-stamped JSON at interpreter exit.  CI diffs it "
    "against the static graph (python -m horovod_tpu.analysis.hvdsan): "
    "observed edges missing statically fail the build.  Off (the "
    "default) patches nothing — zero overhead.")
SAN_FILE = register(
    "HOROVOD_SAN_FILE", "hvdsan_witness.json", str,
    "Path of the hvdsan witness dump; '{rank}' substitutes, otherwise "
    "'.r<rank>' is inserted before the extension (the "
    "HOROVOD_METRICS_FILE convention).")

# --- hvdlife runtime census witness (analysis/hvdlife/; docs/analysis.md) ---
LIFE_CENSUS = register(
    "HOROVOD_LIFE_CENSUS", False, _parse_bool,
    "hvdlife runtime resource census: snapshot the process's live "
    "threads (normalized names), fds (sockets / shm / pipes / files) "
    "and /dev/shm mmap regions around every world transition "
    "(core.init, reinit_world) and dump the labeled snapshots as "
    "rank-stamped JSON at exit.  CI diffs an elastic cycle's "
    "return-to-baseline snapshot against its baseline — the dynamic "
    "twin of the HVD704 epoch-scoped-leak rule.  Off (the default) "
    "takes no snapshots and reads no /proc files — zero overhead.")
LIFE_CENSUS_FILE = register(
    "HOROVOD_LIFE_CENSUS_FILE", "hvdlife_census.json", str,
    "Path of the hvdlife census dump; '{rank}' substitutes, otherwise "
    "'.r<rank>' is inserted before the extension (the "
    "HOROVOD_METRICS_FILE convention).")

# --- Resilience (resilience/ subsystem; docs/resilience.md) -----------------
FAULT_TOLERANCE = register(
    "HOROVOD_FAULT_TOLERANCE", False, _parse_bool,
    "Failure detection + deadline-bounded collectives: heartbeats over "
    "the rendezvous liveness table, socket-level deadlines on every "
    "blocking collective wait, and structured RanksFailedError instead "
    "of a hang when a peer dies or wedges.  Off (the default) keeps "
    "every hot path byte-identical to the pre-resilience behavior: no "
    "monitor thread, no socket timeouts, no per-recv branches beyond "
    "one None test.")
FAULT_TIMEOUT = register(
    "HOROVOD_FAULT_TIMEOUT", 30.0, float,
    "Failure-detection window in seconds: a peer whose heartbeat stops "
    "advancing for this long is declared failed, and a blocking "
    "collective wait that exceeds it raises RanksFailedError naming the "
    "unresponsive peer.  Also the default per-op deadline of the "
    "ResilienceContext.")
ON_FAILURE = register(
    "HOROVOD_ON_FAILURE", "raise", str,
    "Recovery policy applied by resilience.run_with_recovery when a "
    "collective raises RanksFailedError: raise (safe default) | retry "
    "(re-run an idempotent eager collective with exponential backoff "
    "over rebuilt channels, only while every rank is still live) | "
    "shrink (hand the surviving-rank set to the elastic driver for a "
    "world-resize and blacklist the dead host).")
FAULT_RETRIES = register(
    "HOROVOD_FAULT_RETRIES", 3, int,
    "Maximum retry attempts under HOROVOD_ON_FAILURE=retry.")
FAULT_BACKOFF_SECONDS = register(
    "HOROVOD_FAULT_BACKOFF_SECONDS", 0.5, float,
    "Base of the exponential retry backoff (attempt k sleeps "
    "base * 2**k seconds).")
CHAOS = register(
    "HOROVOD_CHAOS", "", str,
    "Deterministic fault-injection spec (resilience/chaos.py): "
    "';'-separated actions 'kind:key=val,...' — kill/freeze/fail at a "
    "global collective index, delay/drop/dup a specific peer-channel "
    "send.  Empty (the default) installs nothing.  See "
    "docs/resilience.md for the grammar.")

# --- Elastic state streaming (statesync/ subsystem; docs/statesync.md) ------
STATESYNC = register(
    "HOROVOD_STATESYNC", False, _parse_bool,
    "Peer-to-peer live state streaming + the grow side of elasticity: "
    "a per-step membership check (one tiny symmetric collective) lets "
    "incumbents admit a joining rank at a step boundary, donate a "
    "copy-on-write state snapshot from live peers (no checkpoint file, "
    "no training pause), and rebuild the world one rank larger once the "
    "joiner's streamed state digest-verifies.  Off (the default) adds "
    "no collectives and no threads.")
STATESYNC_CHUNK_BYTES = register(
    "HOROVOD_STATESYNC_CHUNK_BYTES", 1 << 20, int,
    "Chunk size of one streamed state frame (donor->joiner).  Chunks "
    "are independently addressed (offset, length, crc), so a transfer "
    "resumes at chunk granularity when a donor dies mid-stream.")
STATESYNC_POLL_SECONDS = register(
    "HOROVOD_STATESYNC_POLL_SECONDS", 0.1, float,
    "Interval of the statesync watcher thread's rendezvous-KV polls "
    "for join announcements / joiner-ready marks.")
STATESYNC_TIMEOUT_SECONDS = register(
    "HOROVOD_STATESYNC_TIMEOUT_SECONDS", 60.0, float,
    "Deadline for one streaming round (mesh formation + transfer + "
    "verify) on both the donor and joiner side; a round that exceeds "
    "it is abandoned (the joiner re-announces, donors stand down).")
STATESYNC_WORLD = register(
    "HOROVOD_STATESYNC_WORLD", "world", str,
    "Name of this process's world-membership record in the coordinator "
    "KV (scope 'statesync').  A fleet deployment runs TWO live worlds "
    "— training and serving — against one coordinator "
    "(fleet/controller.py), so each names its record distinctly "
    "('train' / 'serve') and a joiner targets the right one; single-"
    "world deployments keep the default.")
PREEMPT_GRACE_SECONDS = register(
    "HOROVOD_PREEMPT_GRACE_S", 0.0, float,
    "Preemption-notice grace window: > 0 installs a SIGTERM handler "
    "that lets the rank finish its in-flight step, announce an orderly "
    "departure through the statesync membership check (survivors "
    "shrink proactively — no RanksFailedError, no heartbeat deadline), "
    "write its bye| liveness stamp and exit 0.  If no step boundary "
    "arrives within the window, a backstop stamps bye|, dumps the "
    "flight recorder and re-delivers the default SIGTERM disposition.  "
    "0 (the default) keeps the stock SIGTERM behavior.")
PREEMPT_DONATE = register(
    "HOROVOD_PREEMPT_DONATE", True, _parse_bool,
    "On an orderly preemption departure, fast-donate this rank's "
    "ring-sharded (ZeRO) optimizer-state shard to the rendezvous KV so "
    "survivors can re-shard without the departed rank (only when the "
    "training loop registered a shard provider; see docs/statesync.md).")

# --- Autoscale policy loop (statesync/autoscale.py) -------------------------
AUTOSCALE = register(
    "HOROVOD_AUTOSCALE", False, _parse_bool,
    "Rank-0 autoscale controller thread: watches the straggler-lag / "
    "queue-depth gauges (telemetry/) and the serving shed rate, and "
    "drives the elastic driver's target world size up/down with "
    "hysteresis.  Decisions are metrics + flight-recorder events.")
AUTOSCALE_INTERVAL_SECONDS = register(
    "HOROVOD_AUTOSCALE_INTERVAL_S", 5.0, float,
    "Observation interval of the autoscale controller loop.")
AUTOSCALE_UP_SHED_RATE = register(
    "HOROVOD_AUTOSCALE_UP_SHED_RATE", 0.05, float,
    "Scale up when the serving shed rate over one interval exceeds "
    "this fraction (capacity, not deadline, is the binding constraint).")
AUTOSCALE_UP_QUEUE_FRACTION = register(
    "HOROVOD_AUTOSCALE_UP_QUEUE_FRACTION", 0.5, float,
    "Scale up when queue depth exceeds this fraction of "
    "HOROVOD_SERVE_QUEUE_DEPTH (or the configured depth limit).")
AUTOSCALE_DOWN_LAG_MS = register(
    "HOROVOD_AUTOSCALE_DOWN_LAG_MS", 50.0, float,
    "Scale down when the coordinator straggler lag exceeds this many "
    "ms while the queue is idle and nothing is shed: one dragging rank "
    "costs more step time than its share of the work is worth.")
AUTOSCALE_HYSTERESIS_ROUNDS = register(
    "HOROVOD_AUTOSCALE_HYSTERESIS_ROUNDS", 3, int,
    "Consecutive intervals a scale condition must hold before a "
    "decision fires (and the cooldown after each decision), so one "
    "burst never flaps the world size.")

# --- Fleet controller (fleet/ subsystem; docs/fleet.md) ---------------------
FLEET = register(
    "HOROVOD_FLEET", False, _parse_bool,
    "Unified train+serve fleet controller: a rank-0-hosted, "
    "coordinator-KV-backed loop that arbitrates one shared host pool "
    "between a training world and a serving world — traffic-driven "
    "rank rebalancing plus continuous weight deployment.")
FLEET_INTERVAL_S = register(
    "HOROVOD_FLEET_INTERVAL_S", 2.0, float,
    "Observation interval of the fleet controller loop (gauge poll + "
    "policy tick + migration-journal advance).")
FLEET_PUBLISH_STEPS = register(
    "HOROVOD_FLEET_PUBLISH_STEPS", 50, int,
    "The trainer publishes a version-stamped param snapshot to the "
    "fleet KV scope every this many optimizer steps (0 disables "
    "continuous weight deployment).")
FLEET_PUBLISH_KEEP = register(
    "HOROVOD_FLEET_PUBLISH_KEEP", 2, int,
    "Published snapshot versions retained in the KV before the "
    "publisher garbage-collects the oldest (>= 2, so a puller mid-"
    "fetch never races the GC of the version it is verifying).")
FLEET_CHUNK_BYTES = register(
    "HOROVOD_FLEET_CHUNK_BYTES", 1 << 20, int,
    "Shard size of one published-snapshot KV record; serving pullers "
    "fetch shards independently and digest-verify the reassembly.")
FLEET_HYSTERESIS_ROUNDS = register(
    "HOROVOD_FLEET_HYSTERESIS_ROUNDS", 3, int,
    "Consecutive controller intervals a rebalance condition must hold "
    "before a migration fires, so one traffic burst never flaps ranks "
    "between the worlds.")
FLEET_COOLDOWN_ROUNDS = register(
    "HOROVOD_FLEET_COOLDOWN_ROUNDS", 5, int,
    "Controller intervals the policy stays silent after each "
    "migration decision (on top of hysteresis): a move must settle — "
    "join complete, gauges refreshed — before the next is considered.")
FLEET_UP_SHED_RATE = register(
    "HOROVOD_FLEET_UP_SHED_RATE", 0.05, float,
    "Move a rank train->serve when the serving shed rate over one "
    "interval exceeds this fraction (serving capacity, not deadline, "
    "is the binding constraint).")
FLEET_UP_QUEUE_FRACTION = register(
    "HOROVOD_FLEET_UP_QUEUE_FRACTION", 0.5, float,
    "Move a rank train->serve when serving queue depth exceeds this "
    "fraction of the configured depth limit.")
FLEET_IDLE_QUEUE_FRACTION = register(
    "HOROVOD_FLEET_IDLE_QUEUE_FRACTION", 0.05, float,
    "Move a rank serve->train when serving queue depth stays under "
    "this fraction (and nothing is shed) while the trainer drags: the "
    "serving world is over-provisioned.")
FLEET_TRAIN_LAG_MS = register(
    "HOROVOD_FLEET_TRAIN_LAG_MS", 50.0, float,
    "Trainer straggler-lag threshold (ms) that, combined with an idle "
    "serving queue, marks the trainer as the starved world.")
FLEET_MIN_TRAIN = register(
    "HOROVOD_FLEET_MIN_TRAIN", 2, int,
    "Floor on the training world size: the policy never proposes a "
    "migration that would shrink training below this many ranks.")
FLEET_MIN_SERVE = register(
    "HOROVOD_FLEET_MIN_SERVE", 1, int,
    "Floor on the serving world size: the policy never proposes a "
    "migration that would shrink serving below this many ranks.")
FLEET_MIGRATE_TIMEOUT_S = register(
    "HOROVOD_FLEET_MIGRATE_TIMEOUT_S", 120.0, float,
    "Deadline for one journaled migration (depart directive written -> "
    "joined mark observed); a migration that exceeds it is marked "
    "aborted so a wedged mover never blocks the controller forever.")

# --- Fleet-scale harness (fleetsim/ subsystem; docs/fleetsim.md) ------------
FLEETSIM_RANKS = register(
    "HOROVOD_FLEETSIM_RANKS", 32, int,
    "Virtual ranks the fleetsim harness runs inside one process: each "
    "executes the real control-plane client, heartbeat monitor, and "
    "membership boundary exchange (compute is stubbed).")
FLEETSIM_STEPS = register(
    "HOROVOD_FLEETSIM_STEPS", 12, int,
    "Boundary exchanges (virtual training steps) one fleetsim episode "
    "runs before the orderly fleet-wide stop.")
FLEETSIM_STEP_MS = register(
    "HOROVOD_FLEETSIM_STEP_MS", 5.0, float,
    "Stubbed per-step compute delay of every virtual rank, ms (the "
    "model-compute stand-in between membership boundaries).")
FLEETSIM_HOST_GROUP = register(
    "HOROVOD_FLEETSIM_HOST_GROUP", 16, int,
    "Virtual ranks per simulated host: one host group shares a "
    "rendezvous client, batches its heartbeat stamps into a single "
    "PUT /.batch/ per window, and refreshes liveness from one scope "
    "dump instead of size-many gets.")
FLEETSIM_HEARTBEAT_S = register(
    "HOROVOD_FLEETSIM_HEARTBEAT_S", 1.0, float,
    "Heartbeat publish/poll interval of every virtual rank's monitor.")
FLEETSIM_FAULT_TIMEOUT_S = register(
    "HOROVOD_FLEETSIM_FAULT_TIMEOUT_S", 20.0, float,
    "Heartbeat staleness window before a virtual rank declares a peer "
    "failed (must exceed the control-plane failover window under "
    "coordkill chaos, or the whole fleet condemns itself).")
FLEETSIM_STRAGGLER_RANK = register(
    "HOROVOD_FLEETSIM_STRAGGLER_RANK", -1, int,
    "Launch id of one virtual rank made to drag every step "
    "(HOROVOD_FLEETSIM_STRAGGLER_MS extra delay); -1 disables.  "
    "Exercises the coordinator straggler-attribution path at fleet "
    "scale.")
FLEETSIM_STRAGGLER_MS = register(
    "HOROVOD_FLEETSIM_STRAGGLER_MS", 40.0, float,
    "Extra per-step delay of the designated straggler virtual rank.")
FLEETSIM_STEP_TIMEOUT_S = register(
    "HOROVOD_FLEETSIM_STEP_TIMEOUT_S", 60.0, float,
    "Bound on one boundary exchange: a virtual rank that cannot "
    "complete the membership allgather inside it counts a failed step "
    "and leaves the fleet (desync backstop, never silent hang).")
FLEETSIM_DUMP_DIR = register(
    "HOROVOD_FLEETSIM_DUMP_DIR", "", str,
    "Directory the episode's rank-stamped evidence lands in (flight "
    "ring, metrics snapshot, control-plane role probes, episode "
    "summary) — the operator console replays an episode from it.  "
    "Empty disables dumping.")
FLEETSIM_AUTOSCALE = register(
    "HOROVOD_FLEETSIM_AUTOSCALE", False, _parse_bool,
    "Drive the real autoscale policy from the harness's synthetic "
    "serving load: up-decisions admit joiner virtual ranks, "
    "down-decisions preempt the highest launch id (exercises "
    "autoscale oscillation against the live control plane).")

# --- Operator console (console/ subsystem; docs/observability.md) -----------
CONSOLE_REFRESH_S = register(
    "HOROVOD_CONSOLE_REFRESH_S", 2.0, float,
    "Delay between live-mode console frames (scrape mode).")
CONSOLE_TOPK = register(
    "HOROVOD_CONSOLE_TOPK", 8, int,
    "Rows per console section (top-K ranks, last-K membership events).")

# --- Inference serving (serving/ subsystem; docs/serving.md) ----------------
SERVE_MAX_BATCH = register(
    "HOROVOD_SERVE_MAX_BATCH", 8, int,
    "Decode slots per replica: the continuous batcher admits new "
    "requests into in-flight decode batches up to this many concurrent "
    "sequences per replica (the KV cache is allocated for exactly this "
    "batch).")
SERVE_TOKEN_BUDGET = register(
    "HOROVOD_SERVE_TOKEN_BUDGET", 256, int,
    "Per-replica token budget of one serve step: prefill tokens of "
    "newly admitted requests plus one decode token per active slot "
    "must fit; the batcher defers admissions that would exceed it "
    "(keeps step time — and therefore SLO math — predictable).")
SERVE_QUEUE_DEPTH = register(
    "HOROVOD_SERVE_QUEUE_DEPTH", 1024, int,
    "Front-end ingress queue bound; submissions beyond it are shed at "
    "the door (never silently buffered — an unbounded queue turns "
    "overload into unbounded latency, hvdlint HVD1006).")
SERVE_SLO_MS = register(
    "HOROVOD_SERVE_SLO_MS", 30000.0, float,
    "Default per-request SLO in ms, stamped as an absolute deadline at "
    "ingress; per-request slo_ms overrides.  Flows into "
    "resilience.context per-op deadlines (deadline_scope) and into "
    "admission control: a request that cannot finish inside it is shed "
    "at admission, never executed.")
SERVE_SHED_QUEUE_FRACTION = register(
    "HOROVOD_SERVE_SHED_QUEUE_FRACTION", 0.9, float,
    "Admission sheds new requests while the live queue-depth gauge "
    "exceeds this fraction of HOROVOD_SERVE_QUEUE_DEPTH (load-based "
    "shedding keyed off telemetry, not just deadline feasibility).")
SERVE_MAX_SEQ = register(
    "HOROVOD_SERVE_MAX_SEQ", 256, int,
    "KV-cache length per decode slot (prompt + generated tokens).")
SERVE_GROUP_SIZE = register(
    "HOROVOD_SERVE_GROUP_SIZE", 1, int,
    "Ranks per serving replica group: 1 = pure data-parallel (every "
    "rank an independent replica); N > 1 runs each group's members in "
    "lockstep on identical batch plans (the sharded-replica posture — "
    "model-parallel groups reuse parallel/ meshes inside the model).  "
    "Must divide the world size; falls back to 1 after an elastic "
    "shrink breaks divisibility.")
SERVE_PAGED = register(
    "HOROVOD_SERVE_PAGED", False, _parse_bool,
    "Paged KV cache (serving/kvpool.py): decode-slot KV state lives in "
    "fixed-size blocks drawn from a per-replica free-list pool instead "
    "of dense per-slot arrays, so concurrent-sequence count is bounded "
    "by live token residency (the pool), not the batch shape.  Enables "
    "prefix/prompt caching and copy-on-write block sharing.")
SERVE_BLOCK_TOKENS = register(
    "HOROVOD_SERVE_BLOCK_TOKENS", 16, int,
    "Tokens per KV block under HOROVOD_SERVE_PAGED: the paged "
    "allocator's unit of allocation, prefix-hash granularity (one FNV "
    "chain link per full block) and copy-on-write granularity.")
SERVE_POOL_BLOCKS = register(
    "HOROVOD_SERVE_POOL_BLOCKS", 0, int,
    "KV blocks in the per-replica paged pool (0 = auto: "
    "HOROVOD_SERVE_MAX_BATCH x ceil(max_seq / block_tokens), i.e. the "
    "same token memory the dense layout reserves).  The pool — not the "
    "slot count — bounds max concurrent sequences.")
SERVE_PAGED_SLOTS = register(
    "HOROVOD_SERVE_PAGED_SLOTS", 0, int,
    "Decode slots per replica under HOROVOD_SERVE_PAGED (0 = auto: "
    "2 x HOROVOD_SERVE_MAX_BATCH).  Slots beyond the dense batch are "
    "backed by the shared block pool, so short sequences pack more "
    "concurrency into the same KV memory; admission defers when the "
    "pool cannot cover a prompt's worst-case blocks.")
SERVE_MAX_DEFERRALS = register(
    "HOROVOD_SERVE_MAX_DEFERRALS", 8, int,
    "Steps a queued prompt may be deferred for budget/slot pressure "
    "before the batcher turns it urgent: an urgent prompt reserves the "
    "step's admission budget (nothing behind it is admitted) and "
    "bypasses the token budget for its own admission, so a stream of "
    "small prompts can never starve a large one indefinitely.")
SERVE_PREFILL_RANKS = register(
    "HOROVOD_SERVE_PREFILL_RANKS", 0, int,
    "Disaggregated prefill/decode: the highest N ranks of the serving "
    "world run prompt prefill only and stream finished KV blocks to "
    "the decode ranks over a dedicated PeerMesh (serving/kvstream.py, "
    "CRC'd addressed chunks), so long prompts never occupy a decode "
    "step.  0 = every rank prefills its own admissions (clamped so at "
    "least one decode rank remains).")
SERVE_KVSTREAM_CHUNK_BYTES = register(
    "HOROVOD_SERVE_KVSTREAM_CHUNK_BYTES", 1 << 18, int,
    "Chunk size of one prefill-to-decode KV-block stream frame "
    "(serving/kvstream.py); each chunk is independently addressed and "
    "CRC-verified on arrival.")

# --- Collective fingerprinting (analysis/fingerprint.py) --------------------
FINGERPRINT = register(
    "HOROVOD_FINGERPRINT", "off", str,
    "Runtime collective-symmetry fingerprinting: off | cycle (compare "
    "rolling per-rank op fingerprints on every natural negotiation "
    "cycle) | strict (force a negotiation heartbeat every cycle so "
    "divergence is caught even in response-cache steady state).  "
    "Cross-rank divergence becomes a structured ERROR naming the first "
    "divergent op instead of a stall (docs/analysis.md).")
FINGERPRINT_WINDOW = register(
    "HOROVOD_FINGERPRINT_WINDOW", 64, int,
    "Ops of fingerprint history each rank ships with its RequestList; "
    "divergences older than the window are reported as 'at or before' "
    "the oldest commonly-visible op.")
SHARD_SPEC_IDENTITY = register(
    "HOROVOD_SHARD_SPEC_IDENTITY", True, _parse_bool,
    "Fold each collective's canonical sharding-spec token (the sp_spec "
    "wire field) into the runtime fingerprint, making collective "
    "identity op×name×dtype×dims×spec (hvdshard; docs/analysis.md).  "
    "Only effective when the mesh negotiated FEATURE_SHARDING; "
    "launcher-set and identical on every rank.  0 restores the "
    "5-column identity.")

# --- Stall inspector (reference: common/stall_inspector.cc) -----------------
STALL_CHECK_DISABLE = register(
    "HOROVOD_STALL_CHECK_DISABLE", False, _parse_bool,
    "Disable the stalled-tensor warning check.")
STALL_CHECK_TIME_SECONDS = register(
    "HOROVOD_STALL_CHECK_TIME_SECONDS", 60.0, float,
    "Seconds before warning about ranks with missing submissions.")
STALL_SHUTDOWN_TIME_SECONDS = register(
    "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS", 0.0, float,
    "Seconds before a stall aborts the job (0 = never).")

# --- Logging ----------------------------------------------------------------
LOG_LEVEL = register(
    "HOROVOD_LOG_LEVEL", "warning", str,
    "trace|debug|info|warning|error|fatal")
LOG_HIDE_TIME = register(
    "HOROVOD_LOG_HIDE_TIME", False, _parse_bool,
    "Hide timestamps in log output.")

# --- Rendezvous / cluster layout (set by the launcher) ----------------------
# (reference: gloo_context.cc:136-152 reads the same family of variables)
RANK = register("HOROVOD_RANK", -1, int, "Global rank of this process.")
SIZE = register("HOROVOD_SIZE", -1, int, "Global number of ranks.")
LOCAL_RANK = register("HOROVOD_LOCAL_RANK", -1, int, "Rank within this host.")
LOCAL_SIZE = register("HOROVOD_LOCAL_SIZE", -1, int, "Ranks on this host.")
CROSS_RANK = register("HOROVOD_CROSS_RANK", -1, int, "Host index.")
CROSS_SIZE = register("HOROVOD_CROSS_SIZE", -1, int, "Number of hosts.")
HOSTNAME = register("HOROVOD_HOSTNAME", "", str, "Assigned hostname.")
RENDEZVOUS_ADDR = register(
    "HOROVOD_GLOO_RENDEZVOUS_ADDR", "", str,
    "Rendezvous KV-store host (control plane over DCN).")
RENDEZVOUS_PORT = register(
    "HOROVOD_GLOO_RENDEZVOUS_PORT", -1, int, "Rendezvous KV-store port.")
RENDEZVOUS_REPLICAS = register(
    "HOROVOD_RENDEZVOUS_REPLICAS", 0, int,
    "Standby rendezvous replicas launched next to the primary (0 = the "
    "single-server control plane); requires HOROVOD_RENDEZVOUS_WAL_DIR. "
    "Standbys tail the primary's WAL and promote on lease lapse "
    "(docs/controlplane.md).")
RENDEZVOUS_LEASE_MS = register(
    "HOROVOD_RENDEZVOUS_LEASE_MS", 3000.0, float,
    "Rendezvous leader lease in milliseconds: the primary renews every "
    "third of it, a standby promotes after ~2x of silence, and a "
    "primary whose lease lapsed must re-verify the log (epoch fence) "
    "before accepting another write.")
RENDEZVOUS_WAL_DIR = register(
    "HOROVOD_RENDEZVOUS_WAL_DIR", "", str,
    "Directory of the rendezvous write-ahead log (shared by the "
    "replica set).  Empty = no WAL: the KV is in-memory only and does "
    "not survive coordinator death.")
PROTO_COMPAT = register(
    "HOROVOD_PROTO_COMPAT", 0, int,
    "Advertise this wire protocol version (masking newer feature bits) "
    "at every channel HELLO instead of the build's native version; 0 = "
    "native.  The rolling-upgrade lever: peers negotiate the min "
    "common schema per mesh.")
CONTROLLER = register(
    "HOROVOD_CONTROLLER", "local", str,
    "Controller plane: local (in-process) | tcp (multi-process rendezvous).")
GLOO_TIMEOUT_SECONDS = register(
    "HOROVOD_GLOO_TIMEOUT_SECONDS", 30.0, float,
    "Control-plane connect/recv timeout.")

# --- TPU-specific knobs (no reference analogue) -----------------------------
MESH_SHAPE = register(
    "HOROVOD_TPU_MESH_SHAPE", "", str,
    "Override device mesh shape, e.g. '4,2' → axes (replica, local).")
XLA_DONATE = register(
    "HOROVOD_TPU_DONATE_BUFFERS", True, _parse_bool,
    "Donate input buffers to fused XLA collectives (in-place on HBM).")
NUM_STREAMS = register(
    "HOROVOD_NUM_STREAMS", 1, int,
    "Parallel response-dispatch streams (analogue of "
    "HOROVOD_NUM_NCCL_STREAMS): N worker threads execute independent "
    "responses of one cycle concurrently, each over its own dedicated "
    "TCP channel set so streams never interleave bytes on a shared "
    "socket.  Stream assignment is round-robin over the coordinator-"
    "ordered ResponseList (identical on every rank).  1 = the serial "
    "background-loop dispatch, unchanged.")
AUTOTUNE_PIPELINE = register(
    "HOROVOD_AUTOTUNE_PIPELINE", False, _parse_bool,
    "Let the autotuner sweep the TCP pipeline knobs (segment bytes x "
    "active streams, bounded by HOROVOD_NUM_STREAMS) by measured "
    "allreduce throughput before the Bayesian phase, broadcasting the "
    "winner to every rank.")
TRACK_ACCURACY = register(
    "HOROVOD_TRACK_ACCURACY", True, _parse_bool,
    "Compute the per-step training-accuracy metric in Trainer.step. "
    "For LM-head-sized logits the argmax is a full extra read of a "
    "multi-GB tensor per step; disable for throughput runs.")
def parse_tristate(value: str) -> bool | None:
    """'1'/'true'/... -> True, '0'/'false'/... -> False, else None (auto).
    Shared by the tri-state knobs (JAX_DISTRIBUTED, XLA_OPERATIONS)."""
    v = value.strip().lower()
    if v in ("1", "true", "yes", "on"):
        return True
    if v in ("0", "false", "no", "off"):
        return False
    return None


JAX_DISTRIBUTED = register(
    "HOROVOD_JAX_DISTRIBUTED", "auto", str,
    "Form the multi-process JAX world at init (jax.distributed.initialize "
    "via the rendezvous KV): 1 | 0 | auto.  auto forms it on accelerator "
    "hosts with ONE worker per host (that worker drives every local "
    "chip); workers that share a host never open the accelerator — a "
    "chip belongs to one process at a time — and ride the shm/TCP host "
    "planes (1 is an error there).  Processes pinned to JAX_PLATFORMS=cpu "
    "form a world only on 1.")
JAX_HEARTBEAT_TIMEOUT_SECONDS = register(
    "HOROVOD_JAX_HEARTBEAT_TIMEOUT_SECONDS", 100.0, float,
    "jax.distributed coordinator heartbeat timeout passed through to "
    "jax.distributed.initialize.")
JAX_TEARDOWN_GRACE_SECONDS = register(
    "HOROVOD_JAX_TEARDOWN_GRACE_SECONDS", 30.0, float,
    "Grace window for jax.distributed.shutdown at world teardown "
    "before the process gives up waiting on the coordination service.")
JAX_TEARDOWN_SETTLE_SECONDS = register(
    "HOROVOD_JAX_TEARDOWN_SETTLE_SECONDS", 10.0, float,
    "Settle pause after a jax.distributed teardown so late peer RPCs "
    "drain before the next epoch's world forms (elastic rebuilds).")
SHM_BARRIER_TIMEOUT_SECONDS = register(
    "HOROVOD_SHM_BARRIER_TIMEOUT_SECONDS", 600.0, float,
    "Timeout of the shared-memory plane's 3-phase lockstep barrier; a "
    "rank missing past it aborts the op with a structured error naming "
    "the lagging rank instead of spinning forever.")
STREAMING_CE_MIN_ELEMENTS = register(
    "HOROVOD_STREAMING_CE_MIN_ELEMENTS", 0, int,
    "Logit-tensor element count above which the trainer switches to "
    "the streaming (chunked) cross-entropy loss; unset derives the "
    "threshold from discoverable device memory (HBM/16), 0 forces "
    "streaming everywhere (training.py).")
TPU_DISABLE_NATIVE = register(
    "HOROVOD_TPU_DISABLE_NATIVE", False, _parse_bool,
    "Force the pure-numpy fallbacks for the native C codec/fused "
    "kernels (native/): a perf switch, never a correctness one — both "
    "implementations are bitwise identical.")

# --- Launcher / cluster integration (read at their launch-time sites) -------
# These are set by launchers for the worker processes they spawn and
# read before (or outside) any registry import; they are declared here
# so the typed registry — and docs/configuration.md, generated from it —
# is the one complete knob inventory (hvdflow HVD604 flags any raw
# HOROVOD_* read whose name is missing from this file).
DRIVER_ADDR = register(
    "HOROVOD_DRIVER_ADDR", "", str,
    "Elastic driver RPC address the worker dials back to "
    "(elastic/worker.py; set by the elastic launcher).")
DRIVER_PORT = register(
    "HOROVOD_DRIVER_PORT", -1, int,
    "Elastic driver RPC port (elastic/worker.py; set by the launcher).")
GLOO_IFACE = register(
    "HOROVOD_GLOO_IFACE", "", str,
    "Network interface name that pins the address peers dial for the "
    "TCP data/control planes (runner/network.py); empty = the default "
    "route's interface.")
RENDEZVOUS_EPOCH = register(
    "HOROVOD_RENDEZVOUS_EPOCH", "0", str,
    "Rendezvous-KV key namespace of the current world incarnation; "
    "elastic rebuilds, retry recovery and statesync grow bump it "
    "(e.g. '3~r1', '3+j2') so a rebuilt world never collides with "
    "stale keys from the previous epoch.  Set by launchers and "
    "recovery paths, not by hand.")
SECRET_KEY = register(
    "HOROVOD_SECRET_KEY", "", str,
    "Shared HMAC secret authenticating elastic driver<->worker RPCs "
    "(elastic/rpc.py); generated by the launcher per run.")
JSRUN_CPU_PER_SLOT = register(
    "HOROVOD_JSRUN_CPU_PER_SLOT", -1, int,
    "CPUs per resource-set slot for the LSF/jsrun launcher "
    "(runner/js_run.py); unset derives it from the allocation.")
JSRUN_HOSTS = register(
    "HOROVOD_JSRUN_HOSTS", "", str,
    "Explicit host list override for the LSF/jsrun launcher.")
LSF_COMPUTE_HOSTS = register(
    "HOROVOD_LSF_COMPUTE_HOSTS", "", str,
    "LSF compute-host list override consulted before LSB_MCPU_HOSTS "
    "(runner/js_run.py).")
XLA_OPERATIONS = register(
    "HOROVOD_XLA_OPERATIONS", "auto", str,
    "Eager-core device data plane: 1 (require XLA backend) | 0 (TCP only) "
    "| auto (use XLA collectives when a device mesh is available).")
