"""Stage-level execution primitives for the cascade pipeline.

A :class:`StageExecutor` owns one ``CostDescriptor`` stage of one workload:
its own compiled shape (requests are grouped by state signature, so every
batch it runs is shape-homogeneous), its own batch size (derived from the
stage's HBM demand — the seq-256 base denoiser batches wider than the
seq-4096 SR stage), and its own ``impl=`` tier.  :class:`StageBuffer` is the
bounded inter-stage latent handoff queue; executors apply backpressure by
never popping more work than the downstream buffer has room for.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from collections import deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.telemetry import Histogram, SpanCollector


# ---------------------------------------------------------------------------
# Per-request state views
# ---------------------------------------------------------------------------


def stack_states(states: list) -> Any:
    """Per-request (unbatched) state dicts -> one batched state pytree."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


def split_state(state: Any, n: int) -> list:
    """Batched state pytree -> n per-request (unbatched) views."""
    return [jax.tree.map(lambda x: x[i], state) for i in range(n)]


def state_signature(state: Any) -> tuple:
    """Hashable (structure, shapes, dtypes) key: states with equal
    signatures stack into one compiled shape."""
    leaves, treedef = jax.tree.flatten(state)
    return (treedef,
            tuple((tuple(np.shape(x)), jnp.asarray(x).dtype.name)
                  for x in leaves))


def state_nbytes(state: Any) -> int:
    """Total bytes of all arrays in a state — the latent handoff payload."""
    return int(sum(np.prod(np.shape(x)) * jnp.asarray(x).dtype.itemsize
                   for x in jax.tree.leaves(state)))


@dataclasses.dataclass
class StageTask:
    """One request's state parked between stages.

    ``enqueued`` is the pipeline tick at which the task entered its current
    stage buffer; the buffer turns it into the per-stage queue-wait sample
    behind the p50/p95 tail-latency report."""

    rid: int
    state: dict
    group: tuple = ()  # (signature, workload group key) for batching
    enqueued: int = 0  # pipeline tick when pushed into the current buffer


@dataclasses.dataclass
class ParkedTask:
    """A request's per-stage state lifted out of a :class:`StageBuffer` at a
    stage boundary — the preemption/migration payload of fleet serving.

    Because the pipeline only advances whole stage dispatches, every queued
    task *is* at a stage boundary; parking never splits a dispatch.  Under
    the suite-wide ``stage_key(seed, rid, stage_index)`` PRNG contract a
    parked request resumed into any pipeline with the same seed — this one
    or another replica's — draws bit-identical noise from ``stage_index``
    onward (pinned by ``tests/test_route_parity.py``)."""

    rid: int
    stage_index: int  # descriptor stage the state is waiting to enter
    state: dict  # the unbatched per-request stage state


# ---------------------------------------------------------------------------
# Bounded handoff buffer
# ---------------------------------------------------------------------------


class StageBuffer:
    """Bounded FIFO of :class:`StageTask` between two stages.

    ``capacity=None`` makes it unbounded (the admission queue; everywhere
    else the bound is what turns the executor chain into a backpressured
    pipeline instead of an unbounded fan-in).

    The buffer is also the tail-latency probe: ``push(task, now=tick)``
    stamps the task, ``pop_group(..., now=tick)`` records how many ticks
    each popped task queued, and ``waits`` — a streaming
    :class:`~repro.telemetry.Histogram` at one-tick resolution — holds the
    per-stage queue-wait sample that :meth:`CascadePipeline.summary`
    reduces to p50/p95.  Under continuous admission a request arriving
    mid-flight simply lands in a partially-drained buffer via ``push`` —
    there is no separate "late" path."""

    def __init__(self, name: str, capacity: int | None = None):
        self.name = name
        self.capacity = capacity
        self._q: deque[StageTask] = deque()
        self.occupancy: list[int] = []  # sampled once per pipeline tick
        # queue-wait ticks of every popped task (streaming, 1-tick buckets)
        self.waits = Histogram(f"{name}/queue_wait_ticks")

    def __len__(self) -> int:
        return len(self._q)

    def free_slots(self) -> int | None:
        """Real free capacity: ``None`` when unbounded.  A load signal
        (e.g. the fleet router's queue-depth score) must be able to skip
        unbounded buffers — a fake "large finite number" here would
        spuriously saturate any sum over it."""
        if self.capacity is None:
            return None
        return max(0, self.capacity - len(self._q))

    def room(self) -> float:
        """Free slots as a backpressure bound (``math.inf`` when unbounded
        — safe under ``min``/comparison, never summed into a load score;
        use :meth:`free_slots` for capacity reporting)."""
        fs = self.free_slots()
        return math.inf if fs is None else fs

    def push(self, task: StageTask, now: int = 0, *,
             force: bool = False) -> bool:
        """Append ``task`` stamped with arrival tick ``now``; False when the
        buffer is full (the producer must retry next tick — backpressure).
        ``force=True`` bypasses the bound — the capacity is a scheduling
        signal, and a migrated request's parked state must land somewhere
        (:meth:`CascadePipeline.resume`)."""
        if not force and self.room() <= 0:
            return False
        task.enqueued = now
        self._q.append(task)
        return True

    def pop_group(self, max_n: int, now: int = 0) -> list[StageTask]:
        """Pop up to ``max_n`` tasks sharing the head task's group key
        (FIFO order preserved for the rest); records each popped task's
        queue wait (``now - enqueued`` ticks)."""
        if not self._q or max_n <= 0:
            return []
        head = self._q[0].group
        taken: list[StageTask] = []
        rest: deque[StageTask] = deque()
        while self._q:
            t = self._q.popleft()
            if len(taken) < max_n and t.group == head:
                taken.append(t)
            else:
                rest.append(t)
        self._q = rest
        self.waits.observe_many(now - t.enqueued for t in taken)
        return taken

    def tasks(self) -> tuple[StageTask, ...]:
        """Snapshot of the queued tasks (FIFO order), for load inspection."""
        return tuple(self._q)

    def drain(self, rids: set) -> list[StageTask]:
        """Remove and return every queued task whose rid is in ``rids``
        (FIFO order preserved for the rest) — the stage-boundary preemption
        primitive.  Drained tasks record no queue-wait sample; their wait
        continues in whichever buffer they resume into."""
        taken: list[StageTask] = []
        kept: deque[StageTask] = deque()
        while self._q:
            t = self._q.popleft()
            (taken if t.rid in rids else kept).append(t)
        self._q = kept
        return taken

    def sample_occupancy(self) -> None:
        self.occupancy.append(len(self._q))


# ---------------------------------------------------------------------------
# One stage dispatch: the span both stage drivers open
# ---------------------------------------------------------------------------

_UNCOUNTED = SpanCollector("stages", enabled=False)


@contextlib.contextmanager
def stage_span(spans: SpanCollector | None, stage, *, batch: int, tier: str,
               tick: float | None = None):
    """One dispatch of ``stage`` over ``batch`` requests on kernel tier
    ``tier``: the characterization tracer's scope named after the stage and
    the ``serve/stage/<name>`` program span (wall-clock stamps, profiler
    annotation, compile events charged to the stage).  With a registry on
    ``spans`` the dispatch is counted there: ``stage_exec_s/<name>`` (host
    seconds: dispatch plus any trace, lowering and compile, not device
    time), ``stage_items/<name>`` and ``stage_dispatches/<name>``.  Yields
    the open span; its ``seconds`` is set on exit."""
    from repro.core import tracer

    spans = _UNCOUNTED if spans is None else spans
    with tracer.scope(stage.name), spans.region(
            f"serve/stage/{stage.name}", cat="exec", lane=stage.name,
            tick=tick, charge=stage.name, batch=batch, tier=tier) as span:
        yield span
    if spans.metrics is not None:
        spans.metrics.counter(f"stage_exec_s/{stage.name}").inc(span.seconds)
        spans.metrics.counter(f"stage_items/{stage.name}").inc(batch)
        spans.metrics.counter(f"stage_dispatches/{stage.name}").inc()


def stage_counts(counters: dict, name: str) -> dict:
    """Stage ``name``'s dispatch counts as :func:`stage_span` keeps them,
    from a registry's ``counters()``: ``exec_s`` (host seconds), ``items``
    and ``dispatches``."""
    return {"exec_s": counters.get(f"stage_exec_s/{name}", 0.0),
            "items": int(counters.get(f"stage_items/{name}", 0)),
            "dispatches": int(counters.get(f"stage_dispatches/{name}", 0))}


# ---------------------------------------------------------------------------
# Stage executor
# ---------------------------------------------------------------------------


def mean_demand(stage) -> float:
    """Stage's mean per-tick relative HBM demand (flat seq_len fallback)."""
    prof = list(stage.demand) if stage.demand else [stage.seq_len]
    return float(sum(prof)) / max(len(prof), 1)


def stage_unit_cost(stage) -> float:
    """Modeled cost of pushing ONE request through the whole stage (all its
    iterative steps), in relative HBM-demand units."""
    return stage.steps * mean_demand(stage)


def effective_tier(impl: str) -> str:
    """The tier a stage actually runs, as the stats report it.

    ``auto`` resolves per backend: ``pallas`` on a TPU, the ``blocked_jax``
    fallback elsewhere (what every kernel package's own ``auto`` picks
    there).  A ``pallas`` request such as ``stage_impl={"sr": "pallas"}``
    runs the same kernel body in interpret mode (the CI tier) off-TPU
    instead of failing to lower.  All other tiers pass through."""
    on_tpu = jax.default_backend() == "tpu"
    if impl == "auto":
        return "pallas" if on_tpu else "blocked_jax"
    if impl == "pallas" and not on_tpu:
        return "interpret"
    return impl


class StageExecutor:
    """Runs one workload stage over shape-homogeneous request batches.

    Owns the stage's batch size (``max_batch``, derived from its mean HBM
    demand under the shared budget) and its kernel tier: ``impl`` is the
    tier requested for *this stage* (``ServeConfig.stage_impl`` override or
    the engine-wide default), ``effective_impl`` what actually runs
    (:func:`effective_tier`).  Each dispatch runs in a :func:`stage_span`
    on ``spans`` (a collector with a registry), whose counters and the
    per-batch service-time histogram feed ``summary()``.

    ``stage_index`` is the stage's position in the cost descriptor — what
    the suite-wide ``stage_key(seed, rid, stage_index)`` PRNG contract
    folds, so a request's noise is identical to the ``generate`` driver's
    no matter which stage-batch it lands in."""

    def __init__(self, workload, stage, *, impl: str = "auto",
                 max_batch: int = 4, temperature: float = 0.0,
                 stage_index: int = 0, mesh=None,
                 spans: SpanCollector):
        self.workload = workload
        self.stage = stage
        self.stage_index = stage_index
        self.impl = impl  # requested tier (stage override or engine default)
        self.effective_impl = effective_tier(impl)
        self.max_batch = max_batch
        self.temperature = temperature
        self.mesh = mesh  # optional per-stage device slice (see cascade.py)
        self.spans = spans  # where each dispatch is recorded and counted
        # per-batch wall time (streaming log-bucket histogram, ~2% rel. res.)
        self.service_s = Histogram(f"{stage.name}/service_s",
                                   lo=1e-7, hi=1e4, resolution=0.02,
                                   scale="log")

    @property
    def name(self) -> str:
        return self.stage.name

    @property
    def batches(self) -> int:
        """Dispatches so far (the stage span's counter)."""
        return self._counts()["dispatches"]

    def _counts(self) -> dict:
        return stage_counts(self.spans.metrics.counters("stage_"), self.name)

    def run_batch(self, params, tasks: list[StageTask], key,
                  tick: float = 0) -> list[StageTask]:
        """Execute the stage over ``tasks`` as one batch; returns the tasks
        with their post-stage states.  ``key`` is the pipeline's base seed
        key — per-request keys are derived here via the shared
        ``stage_key`` fold, and the dispatch runs under the same
        :func:`stage_span` the ``generate`` driver opens (at pipeline tick
        ``tick``).  The executor waits for the result inside the span, so
        its seconds here include the device time."""
        from repro.workload.base import stage_keys

        batched = stack_states([t.state for t in tasks])
        keys = stage_keys(key, [t.rid for t in tasks], self.stage_index)
        # forwarded only when set, so mesh-free run_stage doubles keep working
        mesh_kw = {} if self.mesh is None else {"mesh": self.mesh}
        with stage_span(self.spans, self.stage, batch=len(tasks),
                        tier=self.effective_impl, tick=tick) as span:
            new = self.workload.run_stage(params, self.stage, batched, keys,
                                          impl=self.effective_impl,
                                          temperature=self.temperature,
                                          **mesh_kw)
            new = jax.block_until_ready(new)
        self.service_s.observe(span.seconds)
        states = split_state(new, len(tasks))
        return [dataclasses.replace(t, state=s)
                for t, s in zip(tasks, states)]

    def summary(self) -> dict:
        """Per-stage serving report: batch counts, tiers, throughput, and
        the p50/p95 per-batch service-time sample."""
        c = self._counts()
        items, batches, exec_s = c["items"], c["dispatches"], c["exec_s"]
        out = {
            "batches": batches,
            "items": items,
            "exec_s": exec_s,
            "mean_batch": (items / batches) if batches else 0.0,
            "max_batch": self.max_batch,
            "impl": self.impl,
            "effective_impl": self.effective_impl,
            "service_s": self.service_s.summary(),
            "throughput_rps": (items / exec_s) if exec_s else 0.0,
        }
        if self.mesh is not None:
            out["mesh"] = {"axes": dict(self.mesh.shape),
                           "devices": int(self.mesh.devices.size)}
        return out
