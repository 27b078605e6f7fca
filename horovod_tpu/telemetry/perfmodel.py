"""perfscope — the algorithm-aware roofline cost model (ISSUE 19).

Pure functions only: everything here is deterministic arithmetic over
the collective-algorithm vocabulary (common/topology.ALGO_NAMES), the
snapshot schema (telemetry/registry.py) and the model configs in
models/.  Three layers share it:

- **core dispatch** (core._observe_collective) folds each executed
  response's measured latency into a bus-bandwidth observation —
  ``busbw = algbw x op_factor(N)``, the nccl-tests convention, so the
  number is comparable across world sizes and algorithms;
- **the perf CLI** (``python -m horovod_tpu.telemetry.perf``) merges
  rank dumps into the PERF.json ledger: per (plane, op, codec, algo,
  size-bucket) busbw with roofline-relative efficiency, where the
  roofline is the peak link bandwidth (HOROVOD_PERF_PEAK_MBPS, or
  self-calibrated to the best cell in the window) discounted by each
  algorithm's wire-byte overhead versus the bandwidth-optimal ring;
- **MFU accounting**: analytic FLOPs for TransformerLM (train and
  paged/dense decode), HybridLM (decode) and the conv models, against
  the per-chip peak
  (arXiv:1909.09756 attributes MLPerf scaling exactly this way).

Reference formulas (S = payload bytes, N = ranks):

=============  =========================  ====================
algo           critical-path wire bytes   hops
=============  =========================  ====================
ring           2(N-1)/N * S               2(N-1)
tree           2*ceil(log2 N) * S         2*ceil(log2 N)
rhd            2(N-1)/N * S               2*ceil(log2 N)
torus (RxC)    2(N-1)/N * S               2(C-1) + 2(R-1)
hierarchical   sum_i 2(l_i-1)/l_i * S_i   sum_i 2(l_i-1)
=============  =========================  ====================

(two-phase torus: per-row ring reduce-scatter (C-1)/C * S + per-column
allreduce of the row shard 2(R-1)/(RC) * S + row allgather — the total
telescopes to exactly 2(N-1)/N * S, i.e. torus is bandwidth-optimal;
its win is the hop count, every hop a grid-neighbor link.  N-level
hierarchical: level i moves 2(l_i-1)/l_i of the shard S_i =
S / prod(levels[:i]) surviving the inner levels.)
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

# ---------------------------------------------------------------------------
# Size buckets — the ledger's third axis.  Power-of-16 boundaries from
# 4 KiB keep the label set small while separating the latency-bound,
# crossover and bandwidth-bound regimes the algo selector distinguishes
# (backend/tcp._select_algo; bench_eager's ladder sizes 4KiB/64KiB/1MiB
# land in three distinct buckets).
# ---------------------------------------------------------------------------
_BUCKET_BOUNDS = ((4 << 10, "4KiB"), (64 << 10, "64KiB"),
                  (1 << 20, "1MiB"), (16 << 20, "16MiB"),
                  (256 << 20, "256MiB"))
SIZE_BUCKETS = tuple(label for _, label in _BUCKET_BOUNDS) + ("huge",)


def size_bucket(nbytes: float) -> str:
    """Ledger bucket label of a payload size (upper-bound buckets)."""
    for bound, label in _BUCKET_BOUNDS:
        if nbytes <= bound:
            return label
    return "huge"


# ---------------------------------------------------------------------------
# Peak dense bf16 FLOP/s per chip, by substring of device_kind.
# Public numbers from cloud.google.com/tpu/docs (v2-v6e system
# architecture pages).  Order matters: first match wins.  The Trainer,
# the serving replica and the bench all read this one table.
# ---------------------------------------------------------------------------
PEAK_FLOPS_TABLE = (
    ("v6", 918e12),       # Trillium / v6e
    ("v5p", 459e12),
    ("v5 lite", 197e12),  # v5e reports device_kind "TPU v5 lite"
    ("v5e", 197e12),
    ("v5", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)

def peak_flops(device_kind: str) -> float | None:
    """Peak dense FLOP/s for a device kind (override via
    HOROVOD_PERF_PEAK_FLOPS).  None for a kind the table does not know
    (CPU runs, emulators): an MFU needs a real peak, so callers set no
    MFU gauge there instead of dividing by a made-up one."""
    from ..common import config
    knob = float(config.PERF_PEAK_FLOPS.get())
    if knob > 0.0:
        return knob
    kind = (device_kind or "").lower()
    for key, peak in PEAK_FLOPS_TABLE:
        if key in kind:
            return peak
    return None


# ---------------------------------------------------------------------------
# Wire cost per algorithm
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class WireCost:
    """Critical-path cost of one collective under one algorithm."""
    wire_bytes: float     # bytes crossing any single rank's links
    hops: int             # serialized link traversals (latency terms)


def _hierarchical_cost(nbytes: float, levels: list[int]) -> WireCost:
    wire = 0.0
    hops = 0
    shard = float(nbytes)
    for size in levels:
        if size <= 1:
            continue
        wire += 2.0 * (size - 1) / size * shard
        hops += 2 * (size - 1)
        shard /= size
    return WireCost(wire, hops)


def wire_cost(algo: str, nbytes: float, topology: Any) -> WireCost:
    """Expected critical-path (wire bytes, hops) of one allreduce of
    ``nbytes`` under ``algo`` on ``topology`` (common/topology.Topology
    or anything with .size/.rows/.cols/.levels())."""
    n = max(int(getattr(topology, "size", 1)), 1)
    if n <= 1:
        return WireCost(0.0, 0)
    log2n = int(math.ceil(math.log2(n)))
    ring_bytes = 2.0 * (n - 1) / n * nbytes
    if algo == "tree":
        return WireCost(2.0 * log2n * nbytes, 2 * log2n)
    if algo == "rhd":
        return WireCost(ring_bytes, 2 * log2n)
    if algo == "torus" and getattr(topology, "kind", "") == "torus":
        rows = max(int(getattr(topology, "rows", 1)), 1)
        cols = max(int(getattr(topology, "cols", 1)), 1)
        return WireCost(ring_bytes, 2 * (cols - 1) + 2 * (rows - 1))
    if algo in ("hier", "hierarchical"):
        levels = topology.levels() if hasattr(topology, "levels") else [n]
        return _hierarchical_cost(nbytes, levels)
    # ring, auto, torus-on-flat, and unknown labels: the bandwidth-
    # optimal ring schedule is the reference cost.
    return WireCost(ring_bytes, 2 * (n - 1))


def algo_overhead(algo: str, topology: Any) -> float:
    """Wire-byte overhead of ``algo`` versus the bandwidth-optimal ring:
    >= 1.0; the roofline divisor (tree at 4 MiB can at best reach
    peak / overhead)."""
    ring = wire_cost("ring", 1.0, topology).wire_bytes
    mine = wire_cost(algo, 1.0, topology).wire_bytes
    if ring <= 0.0 or mine <= 0.0:
        return 1.0
    return max(mine / ring, 1.0)


# ---------------------------------------------------------------------------
# Bus bandwidth (nccl-tests convention)
# ---------------------------------------------------------------------------
def busbw_factor(op: str, n: int) -> float:
    """busbw = algbw x factor: the hardware-normalized multiplier that
    makes measured bandwidth comparable across ops and world sizes
    (nccl-tests PERFORMANCE.md convention)."""
    if n <= 1:
        return 1.0
    if op in ("allreduce", "adasum"):
        return 2.0 * (n - 1) / n
    if op in ("allgather", "reducescatter", "alltoall"):
        return float(n - 1) / n
    return 1.0     # broadcast / barrier-ish ops move S end to end


def busbw_mbps(op: str, nbytes: float, latency_ms: float, n: int) -> float:
    """Measured bus bandwidth in MB/s of one executed collective."""
    if latency_ms <= 0.0 or nbytes <= 0.0:
        return 0.0
    algbw = nbytes / (latency_ms / 1e3)          # bytes/s
    return algbw * busbw_factor(op, n) / 1e6


def expected_ms(algo: str, nbytes: float, topology: Any,
                peak_mbps: float, hop_us: float = 25.0) -> float:
    """Roofline time of one allreduce: critical-path wire bytes at peak
    link bandwidth plus the serialized hop latency."""
    if peak_mbps <= 0.0:
        return 0.0
    cost = wire_cost(algo, nbytes, topology)
    return cost.wire_bytes / (peak_mbps * 1e6) * 1e3 \
        + cost.hops * hop_us / 1e3


# ---------------------------------------------------------------------------
# Analytic FLOPs — TransformerLM
# ---------------------------------------------------------------------------
def param_count(params: Any) -> int:
    """Total parameter count of a (possibly nested) param tree."""
    import jax
    return sum(int(getattr(leaf, "size", 0))
               for leaf in jax.tree_util.tree_leaves(params))


def transformer_param_count(cfg: Any) -> int:
    """Analytic parameter count of a TransformerLM config (embed +
    per-block attention/MLP/norms + final norm; the LM head shares the
    embedding)."""
    d, L = cfg.d_model, cfg.num_layers
    attn = 4 * d * d
    if getattr(cfg, "moe_experts", 0) > 0:
        mlp = cfg.moe_experts * 3 * d * cfg.ff_dim + d * cfg.moe_experts
    else:
        mlp = 3 * d * cfg.ff_dim       # SwiGLU: gate, up, down
    return cfg.vocab_size * d + L * (attn + mlp + 2 * d) + d


def transformer_train_flops(cfg: Any, batch: int, seq: int,
                            n_params: int | None = None) -> float:
    """FLOPs of ONE train step (fwd+bwd): 6*P per token of matmul work
    plus the attention term 12*L*d*S (halved causal), the PaLM-appendix
    accounting MFU reports are defined against."""
    p = n_params if n_params else transformer_param_count(cfg)
    tokens = batch * seq
    attn = 12.0 * cfg.num_layers * cfg.d_model * seq \
        * (0.5 if getattr(cfg, "causal", True) else 1.0)
    return tokens * (6.0 * p + attn)


def transformer_decode_flops(cfg: Any, context_len: float,
                             n_params: int | None = None) -> float:
    """FLOPs of ONE generated token at KV context ``context_len``
    (forward only: 2*P matmul + 4*L*d*ctx attention reads — identical
    for the dense and paged KV layouts, which move the same bytes)."""
    p = n_params if n_params else transformer_param_count(cfg)
    return 2.0 * p + 4.0 * cfg.num_layers * cfg.d_model * context_len


def hybrid_decode_flops(cfg: Any, context_len: float) -> float:
    """FLOPs of ONE generated token of a HybridLM config (models/
    hybrid.py) at context ``context_len``, as this chip computes it: 2 a
    matmul weight that the token meets here (the head once; of routed
    experts the router's whole width, the shared expert, and of the
    ``experts_per_token`` it takes the share that ``experts_held`` is of
    the router's width: the rest run on the chips that hold them; the
    MLP of ``d_ff`` in the layers ``dense_layers`` lists), the state
    update's 6*H*P*N a Mamba layer and 8*H*K*V a delta-rule layer, and
    scores (over the keys' width) and values (over theirs) over the
    context in the attention layers, over its last ``window`` positions
    in the window layers.  A latent layer decodes in the absorbed form
    (``ops/mla.py``): its projections, ``W_kvb`` once as ``W_UK`` on the
    query and once as ``W_UV`` on the output, and scores over the
    latent row's ``kv_lora_rank + qk_rope_head_dim`` channels and values
    over its ``kv_lora_rank``, a head and a position: 139,264 operations
    a position at A.X-K1's widths, where the expanded form's keys and
    values would cost 40,960 and the expansion of every cached latent.
    A looped stack (``loops``) runs every layer that many times a token,
    each pass over a cache of its own: all of the above but the head
    counts once a pass."""
    d = cfg.d_model
    kinds = cfg.layer_types
    mamba, delta, window, latent = (
        kinds.count(kind) for kind in ("mamba", "kda", "window", "latent"))
    attn = len(kinds) - mamba - delta - window - latent
    width, wide = cfg.num_heads * cfg.head_dim, cfg.num_heads * cfg.value_dim

    def projections(kv_heads: int) -> int:
        return (1 + cfg.attn_gate) * d * width + wide * d \
            + d * kv_heads * (cfg.head_dim + cfg.value_dim)

    dense = len(cfg.dense_layers) if cfg.num_experts else len(kinds)
    ffn = dense * 3 * d * cfg.ff_dim
    if cfg.num_experts:
        held = cfg.experts_held[1] / cfg.num_experts
        ffn += (len(kinds) - dense) * (
            d * cfg.num_experts + 3 * d * cfg.expert_ff * (
                cfg.experts_per_token * held + cfg.shared_experts))
    inner, rank = cfg.kda_inner, cfg.kda_rank
    layers = (mamba * (d * (2 * cfg.d_inner + 2 * cfg.mamba_state
                            + cfg.mamba_heads) + cfg.d_inner * d)
              + delta * (d * (3 * inner + 2 * rank + cfg.kda_heads)
                         + 2 * rank * inner + inner * d)
              + attn * projections(cfg.num_kv_heads)
              + window * projections(cfg.window_kv_heads
                                     or cfg.num_kv_heads)
              + latent * _latent_projections(cfg)
              + ffn)
    update = 6.0 * mamba * cfg.mamba_heads * cfg.mamba_head_dim \
        * cfg.mamba_state + 8.0 * delta * cfg.kda_heads * cfg.kda_head_dim ** 2
    seen = attn * context_len + window * min(context_len, cfg.window)
    row = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    a_pass = 2.0 * layers + update + 2.0 * (width + wide) * seen \
        + 2.0 * latent * cfg.num_heads * (row + cfg.kv_lora_rank) \
        * context_len
    return cfg.loops * a_pass + 2.0 * d * cfg.vocab_size


def _latent_projections(cfg: Any) -> int:
    """A latent layer's weights, every one of which a decoded token
    meets once: ``W_qa``, ``W_qb``, ``W_kva``, ``W_kvb`` (absorbed: its
    key rows on the query, its value rows on the output) and ``W_o``."""
    d, h = cfg.d_model, cfg.num_heads
    rank, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    return d * cfg.q_lora_rank + cfg.q_lora_rank * h * cfg.head_dim \
        + d * (rank + rope) + rank * h * (cfg.qk_nope_head_dim
                                          + cfg.value_dim) \
        + h * cfg.value_dim * d


# ---------------------------------------------------------------------------
# Analytic FLOPs — the conv models in models/
# ---------------------------------------------------------------------------
def _conv_flops(c_in: int, c_out: int, k: int, hw: float) -> float:
    return 2.0 * k * k * c_in * c_out * hw * hw


def vgg_forward_flops(stages, image_size: int = 224,
                      num_classes: int = 1000) -> float:
    """Walk VGG.stages: 3x3 SAME convs, 2x2 pool after each stage, then
    the two 4096 Dense layers and the head."""
    hw = float(image_size)
    c_in, total = 3, 0.0
    for n_convs, filters in stages:
        for _ in range(n_convs):
            total += _conv_flops(c_in, filters, 3, hw)
            c_in = filters
        hw /= 2.0
    flat = c_in * hw * hw
    total += 2.0 * (flat * 4096 + 4096 * 4096 + 4096 * num_classes)
    return total


def resnet_forward_flops(stage_sizes, bottleneck: bool = True,
                         num_filters: int = 64, image_size: int = 224,
                         num_classes: int = 1000) -> float:
    """Walk the ResNet stage plan (models/resnet.py): 7x7/2 stem, /2
    pool, stages with stride-2 first blocks, global pool, Dense head."""
    hw = image_size / 2.0
    total = _conv_flops(3, num_filters, 7, hw)
    hw /= 2.0                                   # max_pool /2
    c_in = num_filters
    for i, block_count in enumerate(stage_sizes):
        f = num_filters * 2 ** i
        c_out = 4 * f if bottleneck else f
        for j in range(block_count):
            if j == 0 and i > 0:
                hw /= 2.0                       # stride-2 first block
            if bottleneck:
                total += _conv_flops(c_in, f, 1, hw) \
                    + _conv_flops(f, f, 3, hw) \
                    + _conv_flops(f, c_out, 1, hw)
            else:
                total += _conv_flops(c_in, f, 3, hw) \
                    + _conv_flops(f, f, 3, hw)
            if j == 0 and c_in != c_out:
                total += _conv_flops(c_in, c_out, 1, hw)  # projection
            c_in = c_out
    return total + 2.0 * c_in * num_classes


# InceptionV3 at 299x299 is ~5.7e9 multiply-adds (the published figure
# for the V3 layer plan models/inception.py implements); conv work
# scales with spatial area.
_INCEPTION3_FWD_FLOPS_299 = 2.0 * 5.7e9


def inception3_forward_flops(image_size: int = 299) -> float:
    return _INCEPTION3_FWD_FLOPS_299 * (image_size / 299.0) ** 2


def model_step_flops(model: Any, batch: int, *, seq: int = 0,
                     image_size: int = 224, train: bool = True,
                     n_params: int | None = None) -> float:
    """Analytic FLOPs of one step for any model this tree ships,
    dispatched on the model's own config attributes (train = 3x forward:
    the standard fwd+bwd accounting)."""
    cfg = getattr(model, "cfg", None)
    if cfg is not None and hasattr(cfg, "num_layers"):   # TransformerLM
        if train:
            return transformer_train_flops(cfg, batch, max(seq, 1),
                                           n_params)
        return batch * transformer_decode_flops(cfg, max(seq, 1),
                                                n_params)
    if hasattr(model, "stages"):                          # VGG
        fwd = batch * vgg_forward_flops(model.stages, image_size)
    elif hasattr(model, "stage_sizes"):                   # ResNet
        bottleneck = "Bottleneck" in getattr(
            getattr(model, "block_cls", None), "__name__", "Bottleneck")
        fwd = batch * resnet_forward_flops(
            model.stage_sizes, bottleneck,
            getattr(model, "num_filters", 64), image_size)
    else:                                                 # InceptionV3
        fwd = batch * inception3_forward_flops(image_size)
    return 3.0 * fwd if train else fwd


def mfu(flops_per_step: float, step_seconds: float,
        peak: float) -> float:
    """Model FLOPs utilization: achieved / peak."""
    if step_seconds <= 0.0 or peak <= 0.0:
        return 0.0
    return flops_per_step / step_seconds / peak


# ---------------------------------------------------------------------------
# Ledger construction — merge rank snapshots into the PERF.json tables
# ---------------------------------------------------------------------------
BUSBW_METRIC = "horovod_collective_busbw_mbps"

_LEDGER_LABELS = ("plane", "op", "codec", "algo", "size_bucket")


def _merged_quantile(buckets: list[list[float]], q: float) -> float:
    """Geometric-interpolated quantile over merged [bound, count] bucket
    lists (the snapshot schema; same math as Histogram.quantile without
    the min/max clamp, which does not survive a merge)."""
    count = sum(n for _, n in buckets)
    if count == 0:
        return 0.0
    target = q * count
    cum = 0.0
    for bound, n in sorted(buckets):
        prev, cum = cum, cum + n
        if cum >= target:
            frac = (target - prev) / n
            lo = bound / 2.0
            return lo * (bound / lo) ** frac
    return sorted(buckets)[-1][0]


def _fold_histograms(snapshots: list[dict], name: str) -> dict[tuple, dict]:
    """label-tuple -> merged {count, sum, buckets} across rank dumps."""
    cells: dict[tuple, dict] = {}
    for snap in snapshots:
        for entry in snap.get("metrics", ()):
            if entry.get("name") != name \
                    or entry.get("type") != "histogram":
                continue
            labels = entry.get("labels", {})
            key = tuple(labels.get(k, "") for k in _LEDGER_LABELS)
            cell = cells.setdefault(
                key, {"count": 0, "sum": 0.0, "buckets": {}})
            cell["count"] += int(entry.get("count", 0))
            cell["sum"] += float(entry.get("sum", 0.0))
            for bound, n in entry.get("buckets", ()):
                cell["buckets"][bound] = cell["buckets"].get(bound, 0) + n
    return cells


def _gauge_value(snapshots: list[dict], name: str) -> float | None:
    """Max of a gauge across rank dumps (None when absent everywhere)."""
    values = [float(e.get("value", 0.0))
              for snap in snapshots for e in snap.get("metrics", ())
              if e.get("name") == name and e.get("type") == "gauge"]
    return max(values) if values else None


def build_ledger(snapshots: list[dict], topology: Any = None, *,
                 peak_mbps: float = 0.0, min_samples: int = 1) -> dict:
    """Merge rank metric snapshots into the perf ledger.

    ``peak_mbps`` <= 0 self-calibrates: the best measured cell IS the
    roofline, so every efficiency lands in (0, 1] and the table answers
    "how far below the best this fabric demonstrated is each cell"
    without needing the link spec.  An explicit peak answers the
    absolute question instead."""
    if topology is None:
        from ..common.topology import Topology
        topology = Topology(size=max(len(snapshots), 1))
    cells = _fold_histograms(snapshots, BUSBW_METRIC)
    rows = []
    for key in sorted(cells):
        cell = cells[key]
        if cell["count"] < max(min_samples, 1):
            continue
        labels = dict(zip(_LEDGER_LABELS, key))
        buckets = [[b, n] for b, n in cell["buckets"].items()]
        rows.append({
            **labels,
            "samples": cell["count"],
            "busbw_mbps": cell["sum"] / cell["count"],
            "p50_mbps": _merged_quantile(buckets, 0.5),
            "algo_overhead": algo_overhead(labels["algo"], topology),
        })
    calibrated = peak_mbps
    if calibrated <= 0.0:
        calibrated = max((r["busbw_mbps"] for r in rows), default=0.0)
    for r in rows:
        roofline = calibrated / r["algo_overhead"]
        r["roofline_mbps"] = roofline
        # Fabric efficiency: against the peak itself — the number the
        # smoke battery bounds to (0, 1.05] and perfcheck trends.
        r["efficiency"] = r["busbw_mbps"] / calibrated \
            if calibrated > 0.0 else 0.0
        # Schedule efficiency: against what THIS algo can at best do;
        # > 1 here means the analytic overhead model is pessimistic for
        # this fabric (informational, never gated).
        r["algo_efficiency"] = r["busbw_mbps"] / roofline \
            if roofline > 0.0 else 0.0
    ledger: dict = {
        "schema": 1,
        "world": {"ranks": int(getattr(topology, "size", len(snapshots))
                               or len(snapshots)),
                  "dumps": len(snapshots),
                  "topology": topology.describe()
                  if hasattr(topology, "describe") else "flat"},
        "peak_mbps": calibrated,
        "peak_source": "knob" if peak_mbps > 0.0 else "self-calibrated",
        "busbw": rows,
    }
    step = {}
    for gauge, field in (("horovod_train_mfu", "train_mfu"),
                         ("horovod_train_step_flops", "train_step_flops"),
                         ("horovod_serve_tokens_per_sec",
                          "serve_tokens_per_sec"),
                         ("horovod_serve_flops_per_token",
                          "serve_flops_per_token"),
                         ("horovod_serve_mfu", "serve_mfu")):
        value = _gauge_value(snapshots, gauge)
        if value is not None:
            step[field] = value
    if step:
        ledger["step"] = step
    return ledger


def ledger_summary(ledger: dict, top: int = 6) -> list[str]:
    """Compact human lines for console/report rendering."""
    rows = ledger.get("busbw", [])
    if not rows:
        return ["no busbw samples (HOROVOD_METRICS off, or no "
                "collectives executed)"]
    out = [f"peak {ledger.get('peak_mbps', 0.0):.1f} MB/s "
           f"({ledger.get('peak_source', '?')}), "
           f"{len(rows)} cells, "
           f"world {ledger.get('world', {}).get('ranks', '?')}"]
    ranked = sorted(rows, key=lambda r: -r["samples"])[:top]
    for r in ranked:
        out.append(f"  {r['plane']}/{r['op']}/{r['algo']}"
                   f"@{r['size_bucket']}: "
                   f"{r['busbw_mbps']:.1f} MB/s "
                   f"eff={r['efficiency']:.2f} "
                   f"(n={r['samples']})")
    step = ledger.get("step", {})
    if step:
        out.append("  step: " + " ".join(
            f"{k}={v:.4g}" for k, v in sorted(step.items())))
    return out
