"""JAX's own compile events and persistent-cache counts, recorded as they
happen.  Install before the first compile.

A compile is the union of the spans of JAX's duration events for tracing
to a jaxpr, lowering to MLIR and the backend compile; the backend event
encloses the persistent-cache lookup, so a cache hit is counted as the
time the lookup took.
"""

from __future__ import annotations

import collections

from jax import monitoring

COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
    "/jax/compilation_cache/compile_requests_use_cache": "cache_requests",
}


class Monitor:
    def __init__(self):
        self.spans: list = []  # (start, end) on time.time()
        self.counts = collections.Counter()
        monitoring.register_event_time_span_listener(self._span)
        monitoring.register_event_listener(self._event)

    def _span(self, event, start, end, **_):
        if event in COMPILE_EVENTS:
            self.spans.append((start, end))
            self.counts["compiles"] += event == COMPILE_EVENTS[-1]

    def _event(self, event, **_):
        if event in CACHE_EVENTS:
            self.counts[CACHE_EVENTS[event]] += 1

    def close(self):
        monitoring.unregister_event_time_span_listener(self._span)
        monitoring.unregister_event_listener(self._event)

    def compile_seconds(self, t0: float, t1: float) -> float:
        """Seconds of [t0, t1] covered by any compile span."""
        return union_length(self.spans, t0, t1)

    def snapshot(self) -> dict:
        return dict(self.counts)


def union_length(spans, t0: float, t1: float) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, t0), min(e, t1)) for s, e in spans):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total
