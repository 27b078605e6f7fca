"""Offline summarizer for metrics dumps, timeline traces and profiler
sessions.

CLI::

    python -m horovod_tpu.telemetry.report DUMP_OR_TIMELINE.json [...]
    python -m horovod_tpu.telemetry.report SESSION.xplane.pb
    python -m horovod_tpu.telemetry.report SESSION.xplane.pb --request RID

Accepts every artifact the runtime produces and answers "where did the
milliseconds go" as a per-activity table:

- a **metrics dump** (HOROVOD_METRICS_FILE JSON): counters/gauges as-is,
  histograms as count/mean/p50/p99/max rows;
- a **Chrome-trace timeline** (HOROVOD_TIMELINE JSON): per-activity
  total/mean/max span durations aggregated over every tensor lane, plus
  the final value of each counter track ("ph":"C");
- a **profiler session** (``hvd.start_profiler``'s ``.xplane.pb``): the
  program's ``hvd.*`` spans (telemetry/spans.py) by the step they lie
  in, each with its count, median, tail, and self time (its duration
  less what its children on the same thread cover); a serving
  session's admissions by prompt bucket and its five slowest requests to
  a first token, or with ``--request RID`` that one request's queue
  wait, admission by part, decode steps and tokens (its ``enqueue``,
  ``admit`` and ``complete`` marks carry one ``rid``); and, where the
  session has device planes, each device's idle gaps between ``XLA Ops``
  by the innermost ``hvd.*`` span the host was in.

Output goes to stdout as aligned plain text (one table per input file).
"""
from __future__ import annotations

import argparse
import bisect
import json
import statistics
import sys
from pathlib import Path


def _fmt_table(rows: list[list[str]], header: list[str]) -> str:
    widths = [max(len(str(r[i])) for r in [header] + rows)
              for i in range(len(header))]
    def line(cells):
        return "  ".join(str(c).ljust(w) for c, w in zip(cells, widths))
    sep = "  ".join("-" * w for w in widths)
    return "\n".join([line(header), sep] + [line(r) for r in rows])


def _label_str(labels: dict) -> str:
    return ",".join(f"{k}={v}" for k, v in sorted(labels.items()))


def _collective_tables(payload: dict) -> list[str]:
    """The perfscope view of one dump: per-activity latency broken out
    by the algo label (one row per plane/op/codec/algo instead of one
    collapsed labels blob), and the busbw/efficiency rows the roofline
    ledger is built from (telemetry/perfmodel.py)."""
    lat_rows: list[list[str]] = []
    bus_rows: list[list[str]] = []
    eff = {}
    for m in payload.get("metrics", []):
        labels = m.get("labels", {})
        if m["name"] == "horovod_collective_efficiency":
            key = (labels.get("plane", ""), labels.get("algo", ""),
                   labels.get("size_bucket", ""))
            eff[key] = m.get("value", 0.0)
    for m in payload.get("metrics", []):
        if m.get("type") != "histogram":
            continue
        labels = m.get("labels", {})
        if m["name"] == "horovod_collective_latency_ms":
            lat_rows.append([
                labels.get("plane", ""), labels.get("op", ""),
                labels.get("codec", ""), labels.get("algo", ""),
                str(m["count"]), f"{m['p50']:.3f}", f"{m['p99']:.3f}"])
        elif m["name"] == "horovod_collective_busbw_mbps":
            key = (labels.get("plane", ""), labels.get("algo", ""),
                   labels.get("size_bucket", ""))
            bus_rows.append([
                labels.get("plane", ""), labels.get("op", ""),
                labels.get("algo", ""), labels.get("size_bucket", ""),
                str(m["count"]), f"{m['mean']:.1f}", f"{m['p50']:.1f}",
                f"{eff[key]:.2f}" if key in eff else "-"])
    parts = []
    if lat_rows:
        parts.append(_fmt_table(
            sorted(lat_rows),
            ["plane", "op", "codec", "algo", "count", "p50_ms",
             "p99_ms"]))
    if bus_rows:
        parts.append(_fmt_table(
            sorted(bus_rows),
            ["plane", "op", "algo", "size_bucket", "samples",
             "busbw_mbps", "p50_mbps", "efficiency"]))
    return parts


def summarize_dump(payload: dict) -> str:
    """Per-metric table for a HOROVOD_METRICS_FILE snapshot."""
    scalar_rows: list[list[str]] = []
    hist_rows: list[list[str]] = []
    for m in payload.get("metrics", []):
        name = m["name"]
        labels = _label_str(m.get("labels", {}))
        if m["type"] == "histogram":
            hist_rows.append([
                name, labels, str(m["count"]), f"{m['mean']:.3f}",
                f"{m['p50']:.3f}", f"{m['p99']:.3f}", f"{m['sum']:.1f}"])
        else:
            scalar_rows.append([name, labels, m["type"],
                                f"{m['value']:g}"])
    parts = [f"metrics dump (rank {payload.get('rank', '?')})"]
    if scalar_rows:
        parts.append(_fmt_table(scalar_rows,
                                ["metric", "labels", "type", "value"]))
    if hist_rows:
        parts.append(_fmt_table(
            hist_rows,
            ["histogram", "labels", "count", "mean", "p50", "p99", "sum"]))
    parts.extend(_collective_tables(payload))
    if not scalar_rows and not hist_rows:
        parts.append("(no metrics recorded — was HOROVOD_METRICS=on?)")
    return "\n\n".join(parts)


def summarize_timeline(events: list[dict]) -> str:
    """Per-activity duration table for a Chrome-trace timeline."""
    # Span matching: per (pid, tid) lane, a stack of open B events; an E
    # closes the innermost span (the format Timeline emits).
    stacks: dict[tuple, list[tuple[str, int]]] = {}
    totals: dict[str, list[float]] = {}
    counters: dict[str, dict] = {}
    for e in events:
        ph = e.get("ph")
        if ph == "C":
            counters[e.get("name", "")] = e.get("args", {})
            continue
        if ph not in ("B", "E"):
            continue
        key = (e.get("pid"), e.get("tid"))
        if ph == "B":
            stacks.setdefault(key, []).append(
                (e.get("name", ""), e.get("ts", 0)))
        else:
            stack = stacks.get(key)
            if stack:
                name, ts0 = stack.pop()
                totals.setdefault(name, []).append(
                    (e.get("ts", 0) - ts0) / 1e3)
    rows = []
    for name, spans in sorted(totals.items(),
                              key=lambda kv: -sum(kv[1])):
        rows.append([name, str(len(spans)), f"{sum(spans):.2f}",
                     f"{sum(spans) / len(spans):.3f}",
                     f"{max(spans):.3f}"])
    parts = []
    if rows:
        parts.append(_fmt_table(
            rows, ["activity", "spans", "total_ms", "mean_ms", "max_ms"]))
    else:
        parts.append("(no spans in trace)")
    if counters:
        crow = [[name, _label_str(args)]
                for name, args in sorted(counters.items())]
        parts.append(_fmt_table(crow, ["counter", "final value"]))
    return "\n\n".join(parts)


def _tail(values: list[float]) -> tuple[float, float]:
    """(percentile, value) of the highest percentile that still has ten
    samples beyond it; the maximum where that would lie under the
    median."""
    ordered = sorted(values)
    if len(ordered) <= 20:
        return 100.0, ordered[-1]
    return 100.0 * (1 - 10 / len(ordered)), ordered[-11]


def _nest(events: list[tuple]) -> list[dict]:
    """One thread's (start, end, name, stats) events as spans that know
    their self time and the outermost span they lie in."""
    spans, stack = [], []
    for start, end, name, stats in sorted(events,
                                          key=lambda e: (e[0], -e[1])):
        while stack and start >= stack[-1]["end"]:
            stack.pop()
        if stack:
            stack[-1]["self"] -= end - start
        stack.append({"name": name, "start": start, "end": end,
                      "self": end - start, "stats": stats,
                      "root": stack[0] if stack else None})
        spans.append(stack[-1])
    return spans


def _group(span: dict) -> str:
    """Spans are told apart by the step they lie in, and steps by
    whether they admitted anything."""
    root = span["root"] or span
    admits = root["stats"].get("admits")
    return root["name"] if admits is None \
        else f"{root['name']} [admits {'> 0' if admits else '= 0'}]"


def admission_table(by_bucket: dict) -> str:
    """Admissions by the positions their prefill ran over: ``{bucket:
    [count, prompt tokens computed, seconds]}`` (``executor.stats
    ["prefill_by_bucket"]``, or a session's ``hvd.serve.admit`` spans)
    as rows of count, mean ms, ms per 1,000 prompt tokens and the share
    of the prefilled positions that was padding.  Bucket 0 prefilled
    nothing here: parked for a prefill that another rank streams."""
    rows = []
    for bucket, (count, tokens, seconds) in sorted(by_bucket.items()):
        rows.append([
            str(bucket), str(count), f"{seconds / count * 1e3:.3f}",
            f"{seconds / tokens * 1e6:.3f}" if tokens else "-",
            f"{100 * (1 - tokens / (count * bucket)):.2f}" if bucket
            else "-"])
    return _fmt_table(rows, ["bucket", "count", "mean_ms", "ms_per_ktoken",
                             "padding_%"])


# A request's zero-length marks: where it entered the queue and where
# its last token was collected.  With hvd.serve.admit they carry its rid.
_MARKS = ("hvd.serve.enqueue", "hvd.serve.complete")
_ADMIT = "hvd.serve.admit"


def _requests(spans: list[dict]) -> dict[int, dict]:
    """rid -> the spans of one request that the session holds (``spans``
    by start): its ``enqueue`` and ``complete`` marks, its ``admit`` with
    the spans inside it (a step's first admission holds the settle of
    the decode step in flight, a ``token_fetch``), and the serve steps
    that began after the one that admitted it."""
    found: dict[int, dict] = {}
    for span in spans:
        if span["name"] in _MARKS + (_ADMIT,) and "rid" in span["stats"]:
            found.setdefault(int(span["stats"]["rid"]), {})[
                span["name"].rsplit(".", 1)[1]] = span
    steps = [s for s in spans if s["name"] == "hvd.serve.step"]
    session_end = max(s["end"] for s in spans)
    for req in found.values():
        admit, done = req.get("admit"), req.get("complete")
        # Only its enqueue mark: it had no slot yet when the session ended.
        req["queued"] = admit is None and done is None
        lo = admit["end"] if admit else spans[0]["start"]
        hi = done["start"] if done else session_end
        req["steps"] = [] if req["queued"] else [
            s for s in steps if lo <= s["start"] <= hi]
        req["until"] = hi
        if admit:
            req["inside"] = [s for s in spans if s is not admit
                             and admit["start"] <= s["start"]
                             and s["end"] <= admit["end"]]
    return found


def _ms(ns: float) -> str:
    return f"{ns / 1e6:.3f}"


def request_report(found: dict[int, dict], rid: int) -> str:
    """One request by phase: queue wait, admission by part, decode
    steps and tokens, first to last token."""
    req = found.get(rid)
    if req is None:
        held = f"rids {min(found)} to {max(found)}" if found else "none"
        return (f"request {rid}: no hvd.serve.enqueue, .admit or .complete "
                f"of it in the session (it holds {held})")
    admit, done = req.get("admit"), req.get("complete")
    rows = []
    if admit:
        stats = admit["stats"]
        seen = f"{_ms(admit['start'] - req['enqueue']['start'])} " \
            "from its enqueue mark" if "enqueue" in req \
            else "enqueued before the session opened"
        rows.append(["queue wait", str(stats.get("queue_wait_ms", "-")),
                     f"by the admission's own count; {seen}"])
        rows.append(["admission", _ms(admit["end"] - admit["start"]),
                     ", ".join(f"{key} {stats[key]}" for key in (
                         "bucket", "prompt_tokens", "slot", "running")
                         if key in stats)])
        rows += [["  " + s["name"].rsplit(".", 1)[1],
                  _ms(s["end"] - s["start"]),
                  "the decode step in flight, behind the prefill's dispatch"
                  if s["name"] == "hvd.serve.token_fetch" else ""]
                 for s in req["inside"]]
    else:
        rows.append(["admission", "-", "still queued at the session's end"
                     if req["queued"] else "before the session opened"])
    steps = req["steps"]
    stalls = [s for s in steps if s["stats"].get("admits")]
    ms = [(s["end"] - s["start"]) / 1e6 for s in steps]
    rows.append([
        "decode steps", str(len(steps)),
        f"median {statistics.median(ms):.3f} ms; {len(stalls)} of them "
        f"admitted another request and took "
        f"{sum((s['end'] - s['start']) / 1e6 for s in stalls):.3f} ms"
        if steps else "none in the session"])
    if done:
        rows.append(["tokens", str(done["stats"]["tokens"]),
                     f"in {done['stats']['steps']} serve steps, the "
                     "admitting and the completing one counted"])
    elif not req["queued"]:
        rows.append(["tokens", "-", "still decoding at the session's end"])
    if admit:
        span_ns = req["until"] - admit["end"]
        rows.append(["first to last token" if done
                     else "first token to the session's end",
                     _ms(span_ns), f"{span_ns / 1e6 / len(steps):.3f} ms "
                     "a step" if steps else ""])
    return f"request {rid}\n" + _fmt_table(rows, ["phase", "ms_or_count",
                                                  "what"])


def slowest_requests(found: dict[int, dict], top: int = 5) -> str:
    """The requests admitted inside the session that waited longest for
    their first token (queue wait plus admission)."""
    def first_token_ms(req):
        admit = req["admit"]
        return float(admit["stats"].get("queue_wait_ms", 0.0)) \
            + (admit["end"] - admit["start"]) / 1e6
    admitted = {rid: req for rid, req in found.items() if "admit" in req}
    rows = []
    for rid, req in sorted(admitted.items(),
                           key=lambda kv: -first_token_ms(kv[1]))[:top]:
        stats, done = req["admit"]["stats"], req.get("complete")
        rows.append([
            str(rid), str(stats.get("bucket", "-")),
            str(stats.get("prompt_tokens", "-")),
            str(stats.get("queue_wait_ms", "-")),
            _ms(req["admit"]["end"] - req["admit"]["start"]),
            str(stats.get("running", "-")), str(len(req["steps"])),
            str(done["stats"]["tokens"]) if done else "-"])
    return (f"slowest requests to a first token ({len(admitted)} admitted "
            "in the session; --request RID for one)\n" + _fmt_table(
                rows, ["rid", "bucket", "prompt_tokens", "queue_wait_ms",
                       "admit_ms", "running", "steps_seen", "tokens"]))


def summarize_xplane(path: str, top: int = 10,
                     request: int | None = None) -> str:
    """The ``hvd.*`` spans of one profiler session, its admissions and
    requests (``request``: that one alone) and, where it traced a
    device, the device's idle gaps by the span the host was in."""
    from jax.profiler import ProfileData

    spans: list[dict] = []
    devices: dict[str, list] = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            if not plane.name.startswith("/device:"):
                spans += _nest([
                    (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                     dict(ev.stats)) for ev in line.events
                    if ev.name.startswith("hvd.")])
            elif line.name == "XLA Ops":
                devices[plane.name] = sorted(
                    (ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events)
    if not spans:
        return "(no hvd.* spans in the session)"
    spans.sort(key=lambda s: s["start"])
    requests = _requests(spans)
    if request is not None:
        return request_report(requests, request)
    groups: dict[str, dict[str, list]] = {}
    for span in spans:
        if span["name"] not in _MARKS:
            groups.setdefault(_group(span), {}).setdefault(
                span["name"], []).append(span)
    parts = []
    for group, names in sorted(groups.items()):
        rows = []
        for name, found in names.items():
            ms = [(s["end"] - s["start"]) / 1e6 for s in found]
            pct, tail = _tail(ms)
            rows.append([
                name, str(len(ms)), f"{statistics.median(ms):.3f}",
                f"{tail:.3f}", f"p{pct:.1f}", f"{max(ms):.3f}",
                f"{statistics.median(s['self'] / 1e6 for s in found):.3f}",
                f"{sum(ms):.1f}"])
        parts.append(f"spans in {group}\n" + _fmt_table(
            rows, ["span", "count", "p50_ms", "tail_ms", "tail_at", "max_ms",
                   "self_p50_ms", "total_ms"]))
    by_bucket: dict = {}
    for span in spans:
        if span["name"] == _ADMIT and "bucket" in span["stats"]:
            row = by_bucket.setdefault(int(span["stats"]["bucket"]),
                                       [0, 0, 0.0])
            row[0] += 1
            row[1] += int(span["stats"].get("prompt_tokens", 0))
            row[2] += (span["end"] - span["start"]) / 1e9
    if by_bucket:
        parts.append("admissions by bucket\n" + admission_table(by_bucket))
    if any("admit" in req for req in requests.values()):
        parts.append(slowest_requests(requests))
    parts += [f"{device}: " + idle_gaps(ops, spans, top)
              for device, ops in sorted(devices.items())]
    return "\n\n".join(parts)


def idle_gaps(ops: list[tuple], spans: list[dict], top: int = 10) -> str:
    """Where one device waited: the gaps between its operations
    (``ops``: (start, end) by start), each put down to the innermost of
    ``spans`` (by start) that holds its middle."""
    starts = [s["start"] for s in spans]
    gaps, edge, busy = [], ops[0][0], 0
    for start, end in ops:
        if start > edge:
            gaps.append((start - edge, (edge + start) / 2))
        busy += max(0, end - max(edge, start))
        edge = max(edge, end)
    named = []                     # the longest gaps, by innermost span
    for length, mid in sorted(gaps, reverse=True)[:1000]:
        at = bisect.bisect_right(starts, mid) - 1
        while at >= 0 and spans[at]["end"] <= mid:
            at -= 1                # a sibling that ended: look outwards
        named.append((spans[at]["name"] if at >= 0
                      else "outside_hvd_spans", length / 1e6))
    totals: dict[str, list] = {}
    for name, ms in named:
        totals.setdefault(name, []).append(ms)
    window = edge - ops[0][0]
    return (f"busy {busy / 1e9:.4f} s of the {window / 1e9:.4f} s from its "
            f"first to its last operation, idle "
            f"{100 * (1 - busy / window):.2f}%\n" + _fmt_table(
                [[name, str(len(ms)), f"{sum(ms):.1f}",
                  f"{statistics.median(ms):.3f}"] for name, ms in sorted(
                      totals.items(), key=lambda kv: -sum(kv[1]))],
                ["idle gaps by innermost span", "gaps", "total_ms",
                 "p50_ms"]) + "\n\n" + _fmt_table(
                [[name, f"{ms:.3f}"] for name, ms in named[:top]],
                ["longest idle gaps", "ms"]))


def summarize_file(path: str, request: int | None = None) -> str:
    if path.endswith(".pb"):
        return (f"== {path} (profiler session) ==\n"
                f"{summarize_xplane(path, request=request)}\n")
    payload = json.loads(Path(path).read_text())
    if isinstance(payload, list):
        body = summarize_timeline(payload)
        kind = "timeline"
    else:
        body = summarize_dump(payload)
        kind = "metrics"
    return f"== {path} ({kind}) ==\n{body}\n"


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m horovod_tpu.telemetry.report",
        description="Summarize a HOROVOD_METRICS_FILE dump, a "
                    "HOROVOD_TIMELINE trace or a profiler session's "
                    ".xplane.pb into per-activity tables "
                    "(docs/observability.md).")
    parser.add_argument("paths", nargs="+",
                        help="metrics dump(s), timeline file(s) and/or "
                             "profiler session(s)")
    parser.add_argument("--request", type=int, metavar="RID",
                        help="of a profiler session, one request alone: "
                             "its queue wait, admission by part, decode "
                             "steps and tokens")
    args = parser.parse_args(argv)
    rc = 0
    for path in args.paths:
        try:
            sys.stdout.write(summarize_file(path, args.request) + "\n")
        except (OSError, ValueError) as exc:
            sys.stderr.write(f"report: cannot summarize {path}: {exc}\n")
            rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
