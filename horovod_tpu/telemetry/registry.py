"""Lock-light per-rank metrics registry: counters, gauges, log2 histograms.

The in-process half of the observability layer (ISSUE 4; the Horovod paper
leans on exactly this counter+timeline introspection to find fusion and
negotiation bottlenecks, arxiv 1802.05799 §5).  Design constraints:

- **Lock-light.**  Each metric owns one uncontended ``threading.Lock``
  taken only for the few instructions of its own update — there is no
  registry-wide lock on the update path, so stream workers, sender lanes
  and the background loop never serialize on each other.  Metric lookup
  (``counter()``/``gauge()``/``histogram()``) takes the registry lock and
  is meant for init-time caching; hot paths hold the metric object.
- **Zero cost when off.**  ``HOROVOD_METRICS=off`` (the default) yields a
  :class:`NullRegistry` whose metrics are shared no-op singletons: no
  locks, no syscalls, no allocation on any hot path.
- **Bounded.**  Histograms are fixed-size log2 bucket arrays (64 buckets
  spanning ~1e-6..1e13), so snapshots that ride the negotiation wire or
  the Prometheus scrape never grow with run length.
"""
from __future__ import annotations

import math
import threading

# Histogram buckets: bucket k holds observations in (2^(k-1+_LOW), 2^(k+_LOW)]
# with everything below 2^_LOW in bucket 0.  _LOW=-20 puts the smallest
# bound near 1e-6 (sub-microsecond) and the largest near 1.7e13 (bytes of
# a 17 TB transfer / ms of a 544-year stall) — wide enough for every unit
# this tree observes (ms, bytes, ratios).
_NBUCKETS = 64
_LOW = -20


def _bucket_index(value: float) -> int:
    if value <= 0.0:
        return 0
    idx = int(math.ceil(math.log2(value))) - _LOW
    return min(max(idx, 0), _NBUCKETS - 1)


def bucket_upper_bound(index: int) -> float:
    """Inclusive upper bound of bucket ``index``."""
    return 2.0 ** (index + _LOW)


class Counter:
    """Monotonic counter."""

    __slots__ = ("name", "labels", "_value", "_lock")

    def __init__(self, name: str, labels: dict[str, str]) -> None:
        self.name = name
        self.labels = labels
        self._value = 0.0
        self._lock = threading.Lock()

    def inc(self, value: float = 1.0) -> None:
        with self._lock:
            self._value += value

    @property
    def value(self) -> float:
        return self._value


class Gauge:
    """Last-write-wins instantaneous value."""

    __slots__ = ("name", "labels", "_value")

    def __init__(self, name: str, labels: dict[str, str]) -> None:
        self.name = name
        self.labels = labels
        self._value = 0.0

    def set(self, value: float) -> None:
        # A single attribute store — atomic under the GIL, no lock needed.
        self._value = float(value)

    @property
    def value(self) -> float:
        return self._value


class Histogram:
    """Fixed-size log2-bucketed histogram with sum/count/min/max."""

    __slots__ = ("name", "labels", "_buckets", "_count", "_sum",
                 "_min", "_max", "_lock")

    def __init__(self, name: str, labels: dict[str, str]) -> None:
        self.name = name
        self.labels = labels
        self._buckets = [0] * _NBUCKETS
        self._count = 0
        self._sum = 0.0
        self._min = math.inf
        self._max = -math.inf
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        idx = _bucket_index(value)
        with self._lock:
            self._buckets[idx] += 1
            self._count += 1
            self._sum += value
            if value < self._min:
                self._min = value
            if value > self._max:
                self._max = value

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    @property
    def mean(self) -> float:
        return self._sum / self._count if self._count else 0.0

    def percentile(self, p: float) -> float:
        """Approximate percentile: the upper bound of the bucket holding
        the p-quantile observation (log2 resolution — factor-of-two
        accuracy, which is what "where did the milliseconds go" needs)."""
        with self._lock:
            count = self._count
            buckets = list(self._buckets)
        if count == 0:
            return 0.0
        target = p / 100.0 * count
        cum = 0
        for i, n in enumerate(buckets):
            cum += n
            if cum >= target:
                return bucket_upper_bound(i)
        return bucket_upper_bound(_NBUCKETS - 1)

    def quantile(self, q: float) -> float:
        """Interpolated quantile, ``q`` in [0, 1]: geometric (log-space)
        interpolation within the log2 bucket holding the q-th
        observation, clamped to the observed min/max so single-bucket
        histograms and extreme quantiles report a value that was
        actually plausible rather than a power-of-two bound.  This is
        the one quantile path serving SLO reports (p50/p99/p999) and
        training step-time summaries share (ISSUE 9)."""
        with self._lock:
            count = self._count
            buckets = list(self._buckets)
            lo_obs, hi_obs = self._min, self._max
        if count == 0:
            return 0.0
        q = min(max(q, 0.0), 1.0)
        target = q * count
        cum = 0
        value = bucket_upper_bound(_NBUCKETS - 1)
        for i, n in enumerate(buckets):
            if not n:
                continue
            prev, cum = cum, cum + n
            if cum >= target:
                frac = (target - prev) / n
                hi = bucket_upper_bound(i)
                lo = hi / 2.0
                value = lo * (hi / lo) ** frac
                break
        return min(max(value, lo_obs), hi_obs)

    def nonzero_buckets(self) -> list[tuple[float, int]]:
        """(upper bound, count) for populated buckets, ascending."""
        return [(bucket_upper_bound(i), n)
                for i, n in enumerate(self._buckets) if n]


class _NullMetric:
    """Shared no-op stand-in for every metric type when metrics are off."""

    __slots__ = ()
    name = ""
    labels: dict[str, str] = {}
    value = 0.0
    count = 0
    sum = 0.0
    mean = 0.0

    def inc(self, value: float = 1.0) -> None:
        pass

    def set(self, value: float) -> None:
        pass

    def observe(self, value: float) -> None:
        pass

    def percentile(self, p: float) -> float:
        return 0.0

    def quantile(self, q: float) -> float:
        return 0.0

    def nonzero_buckets(self):
        return []


NULL_METRIC = _NullMetric()


def _label_key(labels: dict[str, str] | None) -> tuple:
    return tuple(sorted((labels or {}).items()))


def _format_labels(labels: dict[str, str]) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))
    return "{" + inner + "}"


class MetricsRegistry:
    """Process-wide registry; one per rank (see telemetry.configure)."""

    enabled = True

    def __init__(self, rank: int = 0) -> None:
        self.rank = rank
        self._lock = threading.Lock()
        self._metrics: dict[tuple, Counter | Gauge | Histogram] = {}
        self._help: dict[str, str] = {}

    # -- get-or-create (init-time; hot paths cache the returned object) --
    def _get(self, cls, name: str, help_: str,
             labels: dict[str, str] | None):
        key = (name, _label_key(labels))
        with self._lock:
            m = self._metrics.get(key)
            if m is None:
                m = cls(name, dict(labels or {}))
                self._metrics[key] = m
                if help_:
                    self._help.setdefault(name, help_)
            return m

    def counter(self, name: str, help: str = "",
                labels: dict[str, str] | None = None) -> Counter:
        return self._get(Counter, name, help, labels)

    def gauge(self, name: str, help: str = "",
              labels: dict[str, str] | None = None) -> Gauge:
        return self._get(Gauge, name, help, labels)

    def histogram(self, name: str, help: str = "",
                  labels: dict[str, str] | None = None) -> Histogram:
        return self._get(Histogram, name, help, labels)

    def _sorted_metrics(self):
        with self._lock:
            return sorted(self._metrics.items(), key=lambda kv: kv[0])

    # -- exposition ------------------------------------------------------
    def render_prometheus(self) -> str:
        """Prometheus text exposition format 0.0.4."""
        out: list[str] = []
        seen_header: set[str] = set()
        for (name, _), m in self._sorted_metrics():
            kind = {Counter: "counter", Gauge: "gauge",
                    Histogram: "histogram"}[type(m)]
            if name not in seen_header:
                seen_header.add(name)
                help_ = self._help.get(name, "")
                if help_:
                    out.append(f"# HELP {name} {help_}")
                out.append(f"# TYPE {name} {kind}")
            if isinstance(m, Histogram):
                cum = 0
                for bound, n in m.nonzero_buckets():
                    cum += n
                    lab = _format_labels({**m.labels, "le": f"{bound:g}"})
                    out.append(f"{name}_bucket{lab} {cum}")
                lab = _format_labels({**m.labels, "le": "+Inf"})
                out.append(f"{name}_bucket{lab} {m.count}")
                base = _format_labels(m.labels)
                out.append(f"{name}_sum{base} {m.sum:g}")
                out.append(f"{name}_count{base} {m.count}")
                # Interpolated p50/p99 as summary-style series: serving
                # SLO dashboards and training step times read the same
                # quantile path (Histogram.quantile, ISSUE 9).
                for q in (0.5, 0.99):
                    lab = _format_labels({**m.labels, "quantile": f"{q:g}"})
                    out.append(f"{name}{lab} {m.quantile(q):g}")
            else:
                out.append(
                    f"{name}{_format_labels(m.labels)} {m.value:g}")
        return "\n".join(out) + "\n"

    def snapshot(self) -> dict:
        """JSON-able dump of every metric (the HOROVOD_METRICS_FILE
        payload)."""
        metrics = []
        for (name, _), m in self._sorted_metrics():
            entry: dict = {"name": name, "labels": m.labels}
            if isinstance(m, Counter):
                entry["type"] = "counter"
                entry["value"] = m.value
            elif isinstance(m, Gauge):
                entry["type"] = "gauge"
                entry["value"] = m.value
            else:
                entry["type"] = "histogram"
                entry["count"] = m.count
                entry["sum"] = m.sum
                entry["mean"] = m.mean
                entry["p50"] = m.quantile(0.5)
                entry["p99"] = m.quantile(0.99)
                entry["buckets"] = [[b, n] for b, n in m.nonzero_buckets()]
            metrics.append(entry)
        return {"rank": self.rank, "metrics": metrics}


class NullRegistry:
    """HOROVOD_METRICS=off: every lookup returns the shared no-op metric —
    the hot path sees no new locks, syscalls, or allocations."""

    enabled = False
    rank = -1

    def counter(self, name: str, help: str = "",
                labels: dict[str, str] | None = None):
        return NULL_METRIC

    def gauge(self, name: str, help: str = "",
              labels: dict[str, str] | None = None):
        return NULL_METRIC

    def histogram(self, name: str, help: str = "",
                  labels: dict[str, str] | None = None):
        return NULL_METRIC

    def render_prometheus(self) -> str:
        return ""

    def snapshot(self) -> dict:
        return {"rank": self.rank, "metrics": []}


NULL_REGISTRY = NullRegistry()
