"""Request-lifecycle spans on the scheduler-tick timeline, and program
spans on the profiler's clock.

A :class:`SpanEvent` is one slice (or instant) on a per-engine timeline:
``start_tick`` / ``dur_ticks`` are denominated in scheduler ticks; measured
wall-seconds, when known (stage execution), ride along as ``dur_s`` and are
laid out proportionally inside their tick by the Chrome exporter.  Spans
with a wall-clock extent also carry ``start_s`` / ``end_s``
(``time.perf_counter()``).  Every engine owns one :class:`SpanCollector`
(``engine.spans``); the cascade pipeline shares it, and the fleet router
owns one more for fleet-scope instants (scale/migrate).

**Program spans** (:meth:`SpanCollector.region`) wrap the serving layers
where the work happens (``serve/step``, ``serve/pod``,
``serve/stage/<name>``).  Each one is stamped on the wall clock, opens a
``jax.profiler.TraceAnnotation`` of the same name — so a profiled run shows
it on the host timeline beside the device ops it launched — and sits on a
thread-local stack of open spans while it runs, which is how
:mod:`repro.telemetry.compiles` charges JAX's compile events to the stage
that caused them.

Lifecycle vocabulary (``cat`` field):

- ``request``   — submit -> complete, one span per finished request
- ``admission`` — arrival -> batch/pod admission wait
- ``queue``     — time parked in a stage's bounded handoff buffer
- ``exec``      — one stage dispatch (``serve/stage/<name>``; ``dur_s`` is
  host seconds, not device time)
- ``serve``     — one engine step (``serve/step``) or pod (``serve/pod``)
- ``preempt``   — park / resume / migrate instants
- ``sched``     — scheduler instants (flush, scale events)

Fleet clock mapping: replica engines keep their own tick counters and only
advance when stepped, so a collector can carry a piecewise (local tick ->
fleet tick) map recorded by :meth:`SpanCollector.map_tick`; the exporter
remaps span timestamps through it so per-replica tracks align on the shared
fleet timeline without touching scheduling state.
"""

from __future__ import annotations

import bisect
import contextlib
import dataclasses
import threading
import time
from typing import Any

import jax

__all__ = ["SpanEvent", "SpanCollector", "OpenSpan", "open_spans"]


@dataclasses.dataclass
class SpanEvent:
    name: str
    cat: str
    start_tick: float
    dur_ticks: float | None = None  # None -> instant event
    dur_s: float | None = None  # measured host seconds (program spans)
    lane: str = "sched"
    rid: int | None = None
    args: dict[str, Any] = dataclasses.field(default_factory=dict)
    start_s: float | None = None  # time.perf_counter() at the start
    end_s: float | None = None  # ... and at the end

    @property
    def instant(self) -> bool:
        return self.dur_ticks is None


@dataclasses.dataclass
class OpenSpan:
    """A program span while it runs (see :func:`open_spans`).  ``charge`` is
    the label compile events inside it are counted under (the stage name,
    or ``"other"``); ``covered`` the compile intervals already counted
    while it is the thread's outermost span; ``seconds`` is set when it
    closes."""

    name: str
    collector: "SpanCollector"
    tick: float
    charge: str
    seconds: float = 0.0
    covered: list = dataclasses.field(default_factory=list)


_OPEN = threading.local()


def open_spans() -> list[OpenSpan]:
    """The program spans open on the calling thread, outermost first."""
    stack = getattr(_OPEN, "stack", None)
    if stack is None:
        stack = _OPEN.stack = []
    return stack


class SpanCollector:
    """Accumulates SpanEvents for one timeline track (engine/replica/fleet).

    ``metrics`` (optional ``MetricsRegistry``) is where compile events
    inside this collector's program spans are counted."""

    def __init__(self, track: str = "engine", enabled: bool = True,
                 metrics=None):
        self.track = track
        self.enabled = enabled
        self.metrics = metrics
        self.events: list[SpanEvent] = []
        # piecewise (local_tick, global_tick) pairs, appended in step order
        self._clock_map: list[tuple[int, int]] = []

    def __len__(self) -> int:
        return len(self.events)

    def clear(self) -> None:
        self.events.clear()
        self._clock_map.clear()

    # -- recording ---------------------------------------------------------
    def span(
        self,
        name: str,
        *,
        cat: str,
        start_tick: float,
        end_tick: float | None = None,
        dur_ticks: float | None = None,
        dur_s: float | None = None,
        lane: str = "sched",
        rid: int | None = None,
        start_s: float | None = None,
        end_s: float | None = None,
        **args,
    ) -> None:
        if not self.enabled:
            return
        if dur_ticks is None:
            dur_ticks = 0.0 if end_tick is None else max(float(end_tick) - float(start_tick), 0.0)
        self.events.append(SpanEvent(
            name=name, cat=cat, start_tick=float(start_tick),
            dur_ticks=float(dur_ticks), dur_s=dur_s, lane=lane, rid=rid,
            args=dict(args), start_s=start_s, end_s=end_s))

    @contextlib.contextmanager
    def region(
        self,
        name: str,
        *,
        cat: str,
        lane: str = "sched",
        tick: float | None = None,
        charge: str = "other",
        **args,
    ):
        """A program span around the body: wall-clock stamps, a profiler
        annotation named ``name``, and an entry on the thread's stack of
        open spans.  ``tick`` defaults to the enclosing span's; the span
        lasts one tick and carries its host seconds as ``dur_s``.  Yields
        the :class:`OpenSpan`, whose ``seconds`` is set on exit."""
        stack = open_spans()
        if tick is None:
            tick = stack[-1].tick if stack else 0.0
        span = OpenSpan(name, self, float(tick), charge)
        stack.append(span)
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield span
        finally:
            t1 = time.perf_counter()
            stack.pop()
            span.seconds = t1 - t0
            self.span(name, cat=cat, start_tick=span.tick, dur_ticks=1.0,
                      dur_s=span.seconds, lane=lane, start_s=t0, end_s=t1,
                      **args)

    def instant(
        self,
        name: str,
        *,
        tick: float,
        cat: str = "sched",
        lane: str = "sched",
        rid: int | None = None,
        **args,
    ) -> None:
        if not self.enabled:
            return
        self.events.append(SpanEvent(
            name=name, cat=cat, start_tick=float(tick), dur_ticks=None,
            lane=lane, rid=rid, args=dict(args)))

    # -- fleet clock alignment --------------------------------------------
    def map_tick(self, local_tick: int, global_tick: int) -> None:
        """Record that this collector's ``local_tick`` ran at ``global_tick``."""
        if self._clock_map and self._clock_map[-1][0] == local_tick:
            return
        self._clock_map.append((int(local_tick), int(global_tick)))

    def to_global_tick(self, t: float) -> float:
        """Remap a local tick stamp onto the fleet clock (identity if unmapped)."""
        if not self._clock_map:
            return t
        locals_ = [p[0] for p in self._clock_map]
        i = bisect.bisect_right(locals_, t) - 1
        if i < 0:
            i = 0
        local, global_ = self._clock_map[i]
        return global_ + (t - local)
