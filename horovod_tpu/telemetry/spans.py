"""Spans on the chip path, on the profiler's own clock.

``span(name, **args)`` opens ``jax.profiler.TraceAnnotation("hvd." +
name)``: the event lands in the profiler session's host plane, on the
calling thread and on the clock of the device trace, so it nests inside
whatever span encloses it and can be laid beside the device's
operations (``python -m horovod_tpu.telemetry.report x.xplane.pb``).
With no session open an enter and exit find tracing off and cost well
under a microsecond.

``mark(name, **args)`` is a span of no length: where something happened
to a request (it entered the queue, its last token was collected).

``timed(slots, key, name)`` is a span that also adds its seconds to
``slots[key]``, whether or not anybody traces (``Trainer.stats``, the
replica's admission counters), and keeps them as ``.seconds`` for a
caller that splits them further (an admission's, by bucket).

``StepParts`` is the recorder one step owns.  It is the step's span
(``hvd.<family>.step``) and its only clock: ``with parts("token_fetch"):``
opens ``hvd.<family>.token_fetch`` and adds the ``time.perf_counter()``
difference to that part's slot, so the part timers are on whether or not
anybody traces.  ``close()`` returns ``{part: seconds}`` with the step's
``total`` and the remainder as ``other``: the parts always sum to the
step.  A part opened inside another (the settle of the decode step in
flight inside an admission) is taken out of the enclosing part's slot,
so they still do; the enclosing part's own ``seconds`` keep it.
"""
from __future__ import annotations

import time

import jax

PREFIX = "hvd."


def span(name: str, **args):
    """A context manager around one region: ``hvd.<name>`` in the
    profiler's trace, with ``args`` as the event's stats (more can be
    added before the exit with the yielded object's ``set_metadata``)."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **args)


def mark(name: str, **args) -> None:
    """``hvd.<name>`` as an event of no length that carries ``args``."""
    with span(name, **args):
        pass


class timed:
    """``span(name, **args)`` that also adds its ``time.perf_counter()``
    seconds to ``slots[key]``, traced or not; ``seconds`` is its own
    time once it has exited."""
    __slots__ = ("_slots", "_key", "_span", "_t0", "seconds")

    def __init__(self, slots: dict, key: str, name: str, **args) -> None:
        self._slots, self._key = slots, key
        self._span = span(name, **args)
        self.seconds = 0.0

    def __enter__(self):
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self._span

    def __exit__(self, *exc):
        self.seconds = dt = time.perf_counter() - self._t0
        self._slots[self._key] = self._slots.get(self._key, 0.0) + dt
        return self._span.__exit__(*exc)


class _Part(timed):
    """A part of a step: while it is open, the parts opened inside it
    take their seconds out of its slot."""
    __slots__ = ("_open",)

    def __init__(self, open_parts: list, slots: dict, key: str, name: str,
                 **args) -> None:
        super().__init__(slots, key, name, **args)
        self._open = open_parts

    def __enter__(self):
        self._open.append(self._key)
        return super().__enter__()

    def __exit__(self, *exc):
        try:
            return super().__exit__(*exc)
        finally:
            self._open.pop()
            if self._open:
                outer = self._open[-1]
                self._slots[outer] = self._slots.get(outer, 0.0) \
                    - self.seconds


class StepParts:
    """The span and the part timers of one step of ``family``."""
    __slots__ = ("_family", "_seconds", "_span", "_t0", "_open")

    def __init__(self, family: str, **args) -> None:
        self._family = family
        self._seconds: dict[str, float] = {}
        self._open: list[str] = []     # the parts open, innermost last
        self._span = span(family + ".step", **args)
        self._span.__enter__()
        self._t0 = time.perf_counter()

    def __call__(self, part: str, **args) -> timed:
        return _Part(self._open, self._seconds, part,
                     f"{self._family}.{part}", **args)

    def elapsed(self) -> float:
        """Seconds since the step began."""
        return time.perf_counter() - self._t0

    def close(self, **args) -> dict[str, float]:
        """End the step's span (``args`` become its stats) and return
        the parts, ``other`` and ``total``, in seconds."""
        total = self.elapsed()
        if args:
            self._span.set_metadata(**args)
        self._span.__exit__(None, None, None)
        parts = dict(self._seconds)
        parts["other"] = total - sum(parts.values())
        parts["total"] = total
        return parts
