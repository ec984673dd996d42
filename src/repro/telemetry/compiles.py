"""Compile counters per stage: JAX's compile events, charged to the program
span open on the calling thread.

One ``jax.monitoring`` time-span listener serves the whole process
(:func:`install`, idempotent; every ``ServeEngine`` calls it).  JAX reports
three events per compile — tracing to a jaxpr, lowering to MLIR, and the
backend compile, whose span encloses the persistent-cache lookup — on the
thread that asked for the compile.  Each event is charged to the innermost
open :meth:`~repro.telemetry.SpanCollector.region` of that thread
(:func:`~repro.telemetry.spans.open_spans`), into the registry of the
region's collector:

* ``compiles/<label>`` — backend compile requests (cache hits included);
* ``compile_s/<label>`` — seconds covered by any of the three events (their
  union: a trace nested in another trace counts once).

``<label>`` is the stage name inside a ``serve/stage/<name>`` span and
``other`` inside any other program span.  Events outside every program
span belong to no engine and are not counted.

:func:`count_program` keeps a third counter the same way,
``stage_programs/<stage>``: one per trace of a stage body into a new
compiled program (``GenerativeWorkload._compiled_stage``), a cache miss.
"""

from __future__ import annotations

import threading

from jax import monitoring

from repro.telemetry.spans import open_spans

__all__ = ["COMPILE_EVENTS", "count_program", "install", "stage_compiles"]

COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_BACKEND = COMPILE_EVENTS[-1]
_lock = threading.Lock()
_installed = False


def install() -> None:
    """Register the process's compile listener, once however often called."""
    global _installed
    with _lock:
        if not _installed:
            monitoring.register_event_time_span_listener(_on_span)
            _installed = True


def _on_span(event: str, start: float, end: float, **_) -> None:
    if event not in COMPILE_EVENTS:
        return
    stack = open_spans()
    if not stack or stack[-1].collector.metrics is None:
        return
    span = stack[-1]
    metrics = span.collector.metrics
    metrics.counter(f"compile_s/{span.charge}").inc(
        _newly_covered(stack[0].covered, start, end))
    if event == _BACKEND:
        metrics.counter(f"compiles/{span.charge}").inc()


def count_program(stage: str) -> None:
    """Count one new compiled program of ``stage`` into the registry of the
    innermost program span open on this thread (called while the stage's
    body is traced, so it runs once per program, never on a cache hit)."""
    stack = open_spans()
    if stack and stack[-1].collector.metrics is not None:
        stack[-1].collector.metrics.counter(f"stage_programs/{stage}").inc()


def _newly_covered(covered: list, start: float, end: float) -> float:
    """Seconds of [start, end] not yet in ``covered`` (sorted, disjoint
    [s, e] pairs), which then takes the interval in.  A thread reports its
    events as they end, so ``end`` is the latest end yet and every interval
    that reaches past ``start`` merges into the new one."""
    overlap, lo = 0.0, start
    while covered and covered[-1][1] >= start:
        s, e = covered.pop()
        overlap += max(0.0, min(e, end) - max(s, start))
        lo = min(lo, s)
    covered.append([lo, max(end, start)])
    return max(0.0, end - start - overlap)


def stage_compiles(metrics) -> dict:
    """``{label: {"compiles": n, "compile_s": s}}`` from a registry's
    counters."""
    out: dict = {}
    for name, v in metrics.counters("compile").items():
        kind, _, label = name.partition("/")
        if kind in ("compiles", "compile_s"):
            out.setdefault(label, {"compiles": 0, "compile_s": 0.0})[kind] = v
    return out
