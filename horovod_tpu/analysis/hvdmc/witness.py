"""hvdmc trace witness — replay observed event logs against the model.

The hvdsan runtime witness closed the lock-graph soundness loop from
the runtime side; this is the same mold for the *protocol* models: the
statesync mp batteries and any flight-recorder dump carry the
membership events each rank actually emitted (``grow``, ``departed``,
``sigterm-grace``, ``donate``, ``join-*``, ``shrink*``,
``torn-reject``), and :func:`check` replays them against the specs and
the explored models:

- an observed **protocol** event kind that no spec transition claims is
  an **unsound spec** — the implementation runs a transition the model
  never explores — and fails CI (``problems``);
- an observed kind whose claimed transitions were never **fired** by
  the explored model is equally unsound (the spec names it, the
  semantics never reach it);
- two consecutive events of one rank that map into the same spec role
  must be **orderable** there (the second transition's source state
  reachable from the first's target) — a cheap per-rank replay;
- spec transitions with observable kinds that no dump ever exercised
  demote to **warnings** (coverage gaps, the hvdsan demotion contract).

Generic data-plane flight kinds (enqueue/dispatch/done/...) are not
protocol events and are ignored; a NEW membership-flavored kind must be
claimed by a spec before the batteries will pass.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field

__all__ = ["GENERIC_KINDS", "WitnessReport", "check", "load_dumps",
           "protocol_kinds"]

# Flight-event kinds of the generic data plane / observability layers —
# never protocol transitions (telemetry/flight.py taxonomy).
GENERIC_KINDS = frozenset({
    "enqueue", "dispatch", "done", "error", "ranks-failed",
    "fingerprint-divergence", "sigterm", "lock-order", "mark-failed",
    "deadline-convert", "autoscale", "serve_slow_step",
})


@dataclass
class WitnessReport:
    problems: list = field(default_factory=list)   # unsound: fail CI
    warnings: list = field(default_factory=list)   # coverage gaps
    observed: dict = field(default_factory=dict)   # kind -> count

    @property
    def ok(self) -> bool:
        return not self.problems


def protocol_kinds(specs=None) -> dict:
    """flight-event kind -> [(spec, transition)] across the specs."""
    from .conformance import all_specs

    out: dict = {}
    for sp in (all_specs() if specs is None else specs):
        for t in sp.transitions:
            if t.observe:
                out.setdefault(t.observe, []).append((sp, t))
    return out


def load_dumps(paths) -> list:
    payloads = []
    for p in paths:
        with open(p) as f:
            payloads.append(json.load(f))
    return payloads


def _fired_tids(specs) -> set:
    """Union of transition ids the head models actually fire."""
    from .machines import FleetModel, GrowModel, PreemptModel, ShrinkModel
    from .model import explore

    fired: set = set()
    for m in (GrowModel(3), PreemptModel(3), ShrinkModel(3),
              FleetModel(2)):
        fired |= explore(m).fired
    return fired


def check(payloads, specs=None, fired: set | None = None
          ) -> WitnessReport:
    """Replay flight dumps (``{"rank":..,"events":[{"kind":..},..]}``)
    against the specs + explored models."""
    from .conformance import all_specs

    specs = all_specs() if specs is None else specs
    kinds = protocol_kinds(specs)
    if fired is None:
        fired = _fired_tids(specs)
    report = WitnessReport()
    reach_cache: dict = {}
    for payload in payloads:
        rank = payload.get("rank", "?")
        prev_by_role: dict = {}
        for ev in payload.get("events", []):
            kind = ev.get("kind", "")
            if kind in GENERIC_KINDS:
                continue
            claimed = kinds.get(kind)
            if claimed is None:
                report.problems.append(
                    f"rank {rank}: observed protocol event "
                    f"{kind!r} ({ev.get('name', '')}) has no "
                    f"transition in any spec — the implementation "
                    f"runs a transition the model never explores "
                    f"(unsound spec)")
                continue
            report.observed[kind] = report.observed.get(kind, 0) + 1
            if not any(t.tid in fired for _sp, t in claimed):
                report.problems.append(
                    f"rank {rank}: observed event {kind!r} maps to "
                    f"transition(s) "
                    f"{[t.tid for _sp, t in claimed]} that the "
                    f"explored model never fires — the spec names a "
                    f"transition its semantics cannot reach")
            for sp, t in claimed[:1]:
                key = (sp.name, t.role)
                prev = prev_by_role.get(key)
                prev_by_role[key] = t
                if prev is None:
                    continue
                reach = reach_cache.get(key)
                if reach is None:
                    reach = reach_cache[key] = \
                        sp.role_reachability(t.role)
                if t.src not in reach.get(prev.dst, {prev.dst}):
                    report.problems.append(
                        f"rank {rank}: observed {prev.observe!r} then "
                        f"{kind!r}, but {t.tid} is not reachable "
                        f"after {prev.tid} in {sp.name} role "
                        f"{t.role} — the observed order contradicts "
                        f"the spec")
    for kind, claimed in sorted(kinds.items()):
        if kind not in report.observed:
            report.warnings.append(
                f"spec transition(s) {[t.tid for _sp, t in claimed]} "
                f"(kind {kind!r}) never observed in any replayed "
                f"dump — model state demoted to a coverage warning")
    report.problems.sort()
    return report
