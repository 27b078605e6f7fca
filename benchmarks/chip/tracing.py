"""Tracing for the benchmark: spans and profiler windows in, metrics out.

Recording.  ``Tracer.window`` runs one profiler session around a short
steady window and ``Tracer.span`` puts a ``bench.*`` annotation on the
profiler's own clock (``jax.profiler.TraceAnnotation``), so host spans and
device operations share one timeline.  With tracing off both do nothing.
The program's own spans (``hvd.*``, ``horovod_tpu/telemetry/spans.py``)
land in the same session and are kept beside the benchmark's.

Reduction.  ``load`` reads the sessions' ``.xplane.pb`` files into one
``Timeline`` (later sessions are shifted past earlier ones; times inside
one session are all that is ever compared).  ``device_busy`` and
``breakdown`` give what the result line's ``device`` and ``breakdown``
keys ask for, and ``evaluate`` computes one per-layer metric from its
``layer_metrics/<name>.json``: a small tree of generic readers, so a
later metric is a new data file and no new code.

Reader kinds (every reader may carry ``"scale"``):
  counter      {"key": k}               a number the driver counted
  peak         {"key": k}               peaks.json, for the device run on
  metric       {"name": n}              another per-layer metric's value
  span_stat    {"span": s, "label": l, "within": {"span": s, "label": l},
                "stat": "median|sum|count"}
  device_time  {"lines": {line: regex on "<opcode> <name>"}, "device": 0,
                "within": {"span": s, "label": l}, "reduce":
                "median|sum|busy", "per": {"span": s, "label": l}}
  ratio        {"num": reader, "den": reader}
  diff         {"a": reader, "b": reader}
A span is named without its prefix when it is the benchmark's
(``serve.step`` is ``bench.serve.step``) and in full when it is the
program's (``hvd.serve.token_fetch``).  The driver's labels pair with the
calls of a ``bench.*`` span only, so a program span is narrowed by
``within``: the spans that start inside a selected frame.  A device
operation is selected by ``<opcode> <name>`` alone: ``ProfileData`` does
not show the ``tf_op`` name stack, so a kernel that wants a device-time
metric of its own carries its name (as ``hvd.flash_fwd`` does).
A reader that finds nothing to read returns None and the metric is left
out of the line.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
import shutil
import statistics
import tempfile
from typing import NamedTuple

SPAN_PREFIX = "bench."
PROGRAM_PREFIX = "hvd."            # the program's spans, kept as they are
WINDOW_SPAN = "bench.window"
NAME_LIMIT = 80
_SHIFT_S = 10.0          # gap put between two sessions on the timeline


class Span(NamedTuple):
    name: str
    start: float
    end: float
    opcode: str = ""     # of a device operation: "all-reduce", "fusion"

    @property
    def text(self) -> str:
        """What a reader's pattern is searched in: ``<opcode> <name>``
        for an operation (an all-reduce may be named ``psum.3``), the
        bare name for a module or a span."""
        return f"{self.opcode} {self.name}" if self.opcode else self.name


class Timeline(NamedTuple):
    spans: list          # bench.* and hvd.* host annotations, by start
    device: dict         # device id -> line name -> [Span] by start


# ---------------------------------------------------------------- recording
class Tracer:
    """Profiler sessions and spans of one run; off, it costs nothing."""

    def __init__(self, enabled: bool) -> None:
        self.enabled = enabled
        self._slots: dict[str, list] = {}    # span name -> [label] a call
        self.windows: list[list[int]] = []   # device ids of each window
        self.files: list[str] = []
        self._open = False               # inside a window right now
        self._dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
            if enabled else None

    @contextlib.contextmanager
    def span(self, name: str, label: str | None = None):
        """One host span; ``label`` may also be set afterwards through
        the list this yields (``slot[0] = "admit"``)."""
        slot = [label]
        if not self._open:               # outside a window nothing is kept
            yield slot
            return
        import jax
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
            yield slot
        self._slots.setdefault(SPAN_PREFIX + name, []).append(slot)

    @property
    def labels(self) -> dict[str, list]:
        """Span name -> the label of each of its calls, in order."""
        return {name: [slot[0] for slot in slots]
                for name, slots in self._slots.items()}

    @contextlib.contextmanager
    def window(self, device_ids: list[int]):
        """One profiler session; what runs inside is the traced window."""
        if not self.enabled:
            yield
            return
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # host spans only: cheap
        session = os.path.join(self._dir, f"w{len(self.windows)}")
        jax.profiler.start_trace(session, profiler_options=options)
        self._open = True
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                yield
        finally:
            self._open = False
            jax.profiler.stop_trace()
        self.windows.append(list(device_ids))
        found = glob.glob(os.path.join(session, "**", "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise RuntimeError(f"expected one .xplane.pb under {session}, "
                               f"found {found}")
        self.files.append(found[0])

    def close(self) -> None:
        if self._dir:
            shutil.rmtree(self._dir, ignore_errors=True)


# ---------------------------------------------------------------- reduction
def short_name(raw: str) -> tuple[str, str]:
    """(name, opcode) of a device event: the profiler names an operation
    by its whole HLO line, ``%psum.3 = bf16[8]{0} all-reduce(...)`` ->
    ``("psum.3", "all-reduce")``, and a module ``jit_step(123456)`` ->
    ``("jit_step", "")``."""
    raw = raw.strip()
    if not raw.startswith("%"):
        return re.sub(r"\(\d+\)$", "", raw), ""
    name, _, rest = raw[1:].partition(" = ")
    opcode = re.search(r"(?:^|\s)([a-z][a-z0-9_-]*)\(", rest)
    return name.split(" ", 1)[0], opcode.group(1) if opcode else ""


def load(paths: list[str]) -> Timeline:
    """Read profiler sessions into one timeline, in seconds."""
    from jax.profiler import ProfileData

    spans: list[Span] = []
    device: dict[int, dict[str, list[Span]]] = {}
    shift = 0.0
    for path in paths:
        last = 0.0
        for plane in ProfileData.from_file(path).planes:
            match = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
            if not match and plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for ev in line.events:
                    if not match and not ev.name.startswith(
                            (SPAN_PREFIX, PROGRAM_PREFIX)):
                        continue
                    start = ev.start_ns * 1e-9 + shift
                    name, opcode = short_name(ev.name) if match \
                        else (ev.name, "")
                    item = Span(name, start,
                                start + ev.duration_ns * 1e-9, opcode)
                    last = max(last, item.end - shift)
                    if match:
                        device.setdefault(int(match.group(1)), {}) \
                            .setdefault(line.name, []).append(item)
                    else:
                        spans.append(item)
        shift += last + _SHIFT_S
    spans.sort(key=lambda s: s.start)
    for lines in device.values():
        for events in lines.values():
            events.sort(key=lambda s: s.start)
    return Timeline(spans, device)


def union(intervals) -> list[tuple[float, float]]:
    """Merge overlapping (start, end) intervals."""
    merged: list[list[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def clipped(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def select_spans(tl: Timeline, labels: dict, name: str,
                 label: str | None = None) -> list[Span]:
    """The spans called ``bench.<name>``, or ``name`` itself where it is
    one of the program's (``hvd.*``), in order; with ``label`` only the
    calls the driver labelled so (labels pair with calls by order)."""
    full = name if name.startswith((SPAN_PREFIX, PROGRAM_PREFIX)) \
        else SPAN_PREFIX + name
    found = [s for s in tl.spans if s.name == full]
    if label is None:
        return found
    marks = labels.get(full, [])
    if len(marks) != len(found):
        raise ValueError(f"{len(found)} spans {full!r} in the trace but "
                         f"{len(marks)} labels from the driver")
    return [s for s, mark in zip(found, marks) if mark == label]


def covering(frames: list[Span], starts: list[float],
             t: float) -> Span | None:
    """The frame that holds ``t``; frames are disjoint and by start, and
    ``starts`` are their starts."""
    at = bisect.bisect_right(starts, t) - 1
    return frames[at] if at >= 0 and t < frames[at].end else None


def started_within(items: list[Span], tl: Timeline, labels: dict,
           within: dict | None) -> list[Span]:
    """The items that start inside one of the frames ``within`` selects
    (``{"span", "label"}``); all of them where it is None."""
    if not within:
        return items
    frames = select_spans(tl, labels, within["span"], within.get("label"))
    starts = [f.start for f in frames]
    return [it for it in items if covering(frames, starts, it.start)]


def _ops(tl: Timeline, device_id: int) -> list[Span]:
    return tl.device.get(device_id, {}).get("XLA Ops", [])


def device_busy(tl: Timeline, windows: list[list[int]]
                ) -> tuple[float, float]:
    """(busy_s, window_s): in each traced window the union of the
    intervals in which an operation ran, averaged over the window's
    devices; both summed over the windows."""
    busy = total = 0.0
    for win, ids in zip(select_spans(tl, {}, WINDOW_SPAN), windows):
        total += win.end - win.start
        per_device = [sum(e - s for s, e in union(clipped(
            ((ev.start, ev.end) for ev in _ops(tl, i)),
            win.start, win.end))) for i in ids]
        busy += sum(per_device) / max(len(per_device), 1)
    return busy, total


def breakdown(tl: Timeline, device_id: int = 0, top: int = 10) -> dict:
    """Where one device's time went inside the last traced window (the
    reported part's, where a cell has several): the operations that took
    most of it, as ``<op>_in_<module>``, and the longest idle gaps, by
    the innermost span, the benchmark's or the program's, that holds
    their middle."""
    modules = tl.device.get(device_id, {}).get("XLA Modules", [])
    module_starts = [m.start for m in modules]
    totals: dict[str, float] = {}
    gaps: list[tuple[str, float]] = []
    for win in select_spans(tl, {}, WINDOW_SPAN)[-1:]:
        inside = [ev for ev in _ops(tl, device_id)
                  if win.start <= ev.start < win.end]
        for ev in inside:
            module = covering(modules, module_starts, ev.start)
            key = f"{ev.name}_in_{module.name if module else 'no_module'}"
            key = key[:NAME_LIMIT]
            totals[key] = totals.get(key, 0.0) + ev.end - ev.start
        edge = win.start
        for start, end in union((ev.start, ev.end) for ev in inside) \
                + [(win.end, win.end)]:
            if start > edge:
                mid = (edge + start) / 2
                inner = [s for s in tl.spans if s.start <= mid < s.end
                         and s.name != WINDOW_SPAN]
                name = max(inner, key=lambda s: s.start).name if inner \
                    else "outside_bench_spans"
                gaps.append((name[:NAME_LIMIT], start - edge))
            edge = max(edge, end)
    return {"device_ops": _largest(totals.items(), top),
            "idle_gaps": _largest(gaps, top)}


def _largest(pairs, top: int) -> list[list]:
    return [[name, seconds] for name, seconds
            in sorted(pairs, key=lambda p: -p[1])[:top]]


# ------------------------------------------------------------------ readers
_STATS = {"median": statistics.median, "sum": sum, "count": len}


def evaluate(reader: dict, facts: dict) -> float | None:
    """One reader of the tree; ``facts`` holds ``timeline``, ``labels``,
    ``counters``, the device's ``peaks`` and the ``metrics`` read so far."""
    value = _KINDS[reader["kind"]](reader, facts)
    if value is None:
        return None
    return value * reader.get("scale", 1.0)


def _counter(reader, facts):
    return facts["counters"].get(reader["key"])


def _peak(reader, facts):
    return facts["peaks"].get(reader["key"])


def _metric(reader, facts):
    return facts["metrics"].get(reader["name"])


def _span_stat(reader, facts):
    tl, labels = facts["timeline"], facts["labels"]
    spans = started_within(
        select_spans(tl, labels, reader["span"], reader.get("label")),
        tl, labels, reader.get("within"))
    if not spans and reader["stat"] != "count":
        return None
    return _STATS[reader["stat"]]([s.end - s.start for s in spans])


def _device_time(reader, facts):
    tl, labels = facts["timeline"], facts["labels"]
    lines = tl.device.get(reader.get("device", 0), {})
    events = [ev for line, pattern in reader["lines"].items()
              for ev in lines.get(line, []) if re.search(pattern, ev.text)]
    events = started_within(events, tl, labels, reader.get("within"))
    if not events:
        return None
    if reader["reduce"] == "median":
        return statistics.median(ev.end - ev.start for ev in events)
    total = sum(e - s for s, e in union((ev.start, ev.end)
                                        for ev in events)) \
        if reader["reduce"] == "busy" \
        else sum(ev.end - ev.start for ev in events)
    per = reader.get("per")
    if per is None:
        return total
    count = len(select_spans(tl, labels, per["span"], per.get("label")))
    return total / count if count else None


def _ratio(reader, facts):
    num, den = evaluate(reader["num"], facts), evaluate(reader["den"], facts)
    return None if num is None or not den else num / den


def _diff(reader, facts):
    a, b = evaluate(reader["a"], facts), evaluate(reader["b"], facts)
    return None if a is None or b is None else a - b


_KINDS = {"counter": _counter, "peak": _peak, "metric": _metric,
          "span_stat": _span_stat, "device_time": _device_time,
          "ratio": _ratio, "diff": _diff}
