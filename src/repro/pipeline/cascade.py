"""Cascade pipeline: stage-level serving for multi-stage generative models.

The paper's serving observation (§IV-C, §V-A) is that TTI/TTV inference is a
*cascade* — base denoise then super-resolution, keyframe then temporal
refinement — with sequence length varying up to 4x across stages.  Running a
request end-to-end in lockstep forces every stage to the batch size the most
HBM-hungry stage can afford, and synchronizes all concurrent requests into
the same phase (the aligned-demand peak of Fig. 7).

:class:`CascadePipeline` instead turns each ``CostDescriptor`` stage into a
:class:`StageExecutor` with its own batch size and compiled shapes, joined
by bounded :class:`StageBuffer` handoff queues.  Requests from different
users batch together *per stage*: the pipeline pops shape-homogeneous groups
off each stage's input queue, so the seq-256 base denoiser and the seq-4096
SR stage each run at their own optimal batch size, and the instantaneous
stage mix flattens HBM demand relative to lockstep.

Every scheduling decision is recorded: per-stage throughput, queue
occupancy, per-tick stage concurrency, and the modeled lockstep-vs-pipelined
comparison (time from a dispatch-overhead + per-item HBM-cost model; demand
profiles at stage granularity) that backs ``ServeEngine.stats`` and
``benchmarks`` A/Bs.
"""

from __future__ import annotations

import jax

from repro.core import tracer
from repro.pipeline.stage import (
    ParkedTask,
    StageBuffer,
    StageExecutor,
    StageTask,
    mean_demand,
    stage_unit_cost,
    state_nbytes,
    state_signature,
)
from repro.telemetry import MetricsRegistry, SpanCollector

# Modeled per-dispatch launch overhead, as a fraction of the mean stage unit
# cost: what a stage-batch pays for compiled-graph dispatch regardless of
# batch size.  Batching a cheap stage wider amortizes it — the modeled
# source of the stage-batched throughput win over lockstep.
DISPATCH_OVERHEAD_FRAC = 0.15


def stage_batch_sizes(stages, pod_size: int, queue_capacity: int) -> list[int]:
    """Per-stage batch size under a shared HBM budget.

    The budget is set so the most demanding stage runs at ``pod_size`` (the
    batch the lockstep pod route is provisioned for); lighter stages batch
    wider, up to the handoff queue depth.  Every stage gets at least the pod
    size, so stage-batching never runs narrower than lockstep."""
    demands = [max(mean_demand(s), 1e-9) for s in stages]
    budget = pod_size * max(demands)
    cap = max(queue_capacity, pod_size)
    return [max(1, min(cap, int(budget // d))) for d in demands]


def resolve_stage_impls(stages, impl: str, stage_impl: dict | None) -> list[str]:
    """Per-stage kernel tier: ``stage_impl`` overrides the engine-wide
    ``impl`` default, matched by exact stage name first, then by prefix (so
    ``{"sr": "pallas"}`` covers ``sr0``/``sr1``...).  Keys matching no stage
    raise — a typo must not silently serve the default tier."""
    stage_impl = dict(stage_impl or {})
    names = [s.name for s in stages]
    unused = [k for k in stage_impl
              if not any(n == k or n.startswith(k) for n in names)]
    if unused:
        raise ValueError(
            f"stage_impl keys {sorted(unused)} match no stage "
            f"(stages: {names})")
    out = []
    for name in names:
        exact = stage_impl.get(name)
        if exact is not None:
            out.append(exact)
            continue
        prefixes = [k for k in stage_impl if name.startswith(k)]
        out.append(stage_impl[max(prefixes, key=len)] if prefixes else impl)
    return out


class CascadePipeline:
    """Drives one workload's stage cascade with cross-request batching.

    Construction turns ``workload.cost_descriptor().stages`` into a chain
    of :class:`StageExecutor` joined by bounded :class:`StageBuffer`
    handoff queues; ``submit`` enqueues a request's initial stage state,
    and each ``tick()`` is one scheduling round.  Requests may be submitted
    at any point — mid-flight submissions join the (partially drained)
    first-stage queue, which is what continuous admission in
    ``ServeEngine(route="cascade")`` relies on.

    ``stage_impl`` maps stage names (exact or prefix, e.g. ``{"sr":
    "pallas"}``) to kernel tiers, overriding the engine-wide ``impl`` for
    those stages; ``temperature`` threads to every ``run_stage`` (only
    LM-style sampling stages consume it)."""

    def __init__(self, workload, params, *, impl: str = "auto",
                 pod_size: int = 4, queue_capacity: int = 8, seed: int = 0,
                 stage_impl: dict | None = None, temperature: float = 0.0,
                 spans: SpanCollector | None = None, mesh=None):
        self.workload = workload
        # lifecycle span sink — the owning engine passes its collector so
        # pipeline queue/exec/preempt spans land on the engine's timeline
        # and the stage spans count each dispatch into its registry
        self.spans = spans if spans is not None else SpanCollector(
            "pipeline", metrics=MetricsRegistry())
        self.params = params
        self.impl = impl
        self.pod_size = max(1, pod_size)
        self.queue_capacity = max(queue_capacity, self.pod_size)
        self.stages = list(workload.cost_descriptor().stages)
        if not self.stages:
            raise ValueError("workload has no cost-descriptor stages")
        batches = stage_batch_sizes(self.stages, self.pod_size,
                                    self.queue_capacity)
        impls = resolve_stage_impls(self.stages, impl, stage_impl)
        # per-stage device assignment: carve the mesh into one slice per
        # stage sized from its HBM-demand profile (text-encode on a sliver
        # while SR saturates the rest).  jit requires params and state on
        # one device set, so each stage's weights live on its own slice.
        self.mesh = mesh
        if mesh is not None:
            from repro.parallel.mesh_exec import stage_mesh_slices

            self.stage_meshes = stage_mesh_slices(self.stages, mesh)
            self.stage_params = [workload.shard_params(params, m)
                                 for m in self.stage_meshes]
        else:
            self.stage_meshes = [None] * len(self.stages)
            self.stage_params = [params] * len(self.stages)
        self.reshard_events = 0  # cross-slice latent handoffs
        self.reshard_bytes = 0
        self.executors = [
            StageExecutor(workload, s, impl=im, max_batch=b,
                          temperature=temperature, stage_index=i,
                          mesh=self.stage_meshes[i], spans=self.spans)
            for i, (s, b, im) in enumerate(zip(self.stages, batches, impls))
        ]
        # buffers[i] feeds stage i; buffers[0] is the (unbounded) admission
        # queue — the serving scheduler is its backpressure
        self.buffers = [
            StageBuffer(f"in/{s.name}",
                        capacity=None if i == 0 else self.queue_capacity)
            for i, s in enumerate(self.stages)
        ]
        # the base seed key of the (seed, rid, stage_index) PRNG contract:
        # executors fold per-request keys from it, so a request's noise is
        # independent of which stage-batch serves it (route parity)
        self._key = jax.random.PRNGKey(seed)
        self.submitted = 0
        self.completed = 0
        self.parked = 0  # tasks preempted out at a stage boundary
        self.resumed = 0  # parked tasks injected back (possibly from elsewhere)
        self.ticks = 0
        self.concurrency: list[int] = []  # stages executed per tick
        self.executed: list[tuple[int, int]] = []  # (stage index, batch size)

    # -- submission ----------------------------------------------------------

    def submit(self, rid: int, tokens, max_new_tokens: int = 0) -> None:
        """Admit one request into the first stage's queue — legal at any
        tick, including mid-flight while earlier requests occupy deeper
        stages (continuous admission)."""
        state = self.workload.init_stage_state(
            tokens, max_new_tokens=max_new_tokens)
        self.buffers[0].push(self._task(rid, state, 0), now=self.ticks)
        self.submitted += 1

    def _task(self, rid: int, state: dict, stage_idx: int) -> StageTask:
        group = (state_signature(state),
                 self.workload.stage_group_key(self.stages[stage_idx], state))
        return StageTask(rid=rid, state=state, group=group)

    def pending(self) -> int:
        return sum(len(b) for b in self.buffers)

    # -- stage-boundary preemption (fleet serving) ---------------------------

    def queued_rids(self) -> list[int]:
        """Rids with state parked in a stage buffer right now — i.e. at a
        stage boundary, preemptible by :meth:`park`.  (The pipeline never
        holds state anywhere else between ``tick()`` calls.)"""
        return [t.rid for b in self.buffers for t in b.tasks()]

    def park(self, rids) -> list[ParkedTask]:
        """Preempt ``rids`` at their current stage boundary: remove their
        per-stage state from the buffers and return it as
        :class:`ParkedTask` payloads.  Because ``tick()`` only advances
        whole stage dispatches, every queued task is between stages —
        parking never splits a dispatch, and under the
        ``stage_key(seed, rid, stage_index)`` fold the resumed request
        draws bit-identical noise no matter which pipeline (this one or
        another same-seed replica's) it resumes into."""
        wanted = set(rids)
        out: list[ParkedTask] = []
        for idx, buf in enumerate(self.buffers):
            for t in buf.drain(wanted):
                out.append(ParkedTask(rid=t.rid, stage_index=idx,
                                      state=t.state))
                self.spans.instant("park", tick=self.ticks, cat="preempt",
                                   lane=self.stages[idx].name, rid=t.rid)
        self.parked += len(out)
        return out

    def resume(self, parked: list[ParkedTask]) -> None:
        """Re-inject parked state at its recorded stage boundary.  The push
        is forced past the buffer bound — capacity is a scheduling signal
        and migrated state must land; the buffer's backpressure still
        throttles upstream *dispatches*.  ``completed`` may end up above
        ``submitted`` on a pipeline that absorbs migrations (the fleet's
        ledger, not the per-replica counters, is authoritative)."""
        for p in parked:
            self.buffers[p.stage_index].push(
                self._task(p.rid, p.state, p.stage_index),
                now=self.ticks, force=True)
            self.spans.instant("resume", tick=self.ticks, cat="preempt",
                               lane=self.stages[p.stage_index].name,
                               rid=p.rid)
        self.resumed += len(parked)

    # -- scheduling ----------------------------------------------------------

    def tick(self) -> list[tuple[int, object]]:
        """One scheduling round: every stage with queued work (and downstream
        room) runs one shape-homogeneous batch, downstream stages first so
        handoff buffers drain before they refill.  Returns completed
        ``(rid, output)`` pairs."""
        done: list[tuple[int, object]] = []
        executed = 0
        for i in reversed(range(len(self.stages))):
            ex, buf = self.executors[i], self.buffers[i]
            out_buf = self.buffers[i + 1] if i + 1 < len(self.buffers) else None
            room = out_buf.room() if out_buf is not None else ex.max_batch
            tasks = buf.pop_group(min(ex.max_batch, room), now=self.ticks)
            if not tasks:
                continue
            name = self.stages[i].name
            for t in tasks:  # queue-wait slice: push tick -> this dispatch
                self.spans.span("queue", cat="queue", start_tick=t.enqueued,
                                end_tick=self.ticks, lane=name, rid=t.rid)
            new_tasks = ex.run_batch(self.stage_params[i], tasks, self._key,
                                     tick=self.ticks)
            executed += 1
            self.executed.append((i, len(tasks)))
            if out_buf is None:
                for t in new_tasks:
                    done.append((t.rid, self.workload.stage_output(t.state)))
                self.completed += len(new_tasks)
            else:
                self._handoff(i, new_tasks)
                for t in new_tasks:
                    out_buf.push(self._task(t.rid, t.state, i + 1),
                                 now=self.ticks)
        for b in self.buffers:
            b.sample_occupancy()
        self.concurrency.append(executed)
        self.ticks += 1
        return done

    def run(self) -> dict:
        """Drain everything submitted so far; returns {rid: output}."""
        results: dict = {}
        while self.pending():
            for rid, out in self.tick():
                results[rid] = out
        return results

    def _handoff(self, stage_idx: int, tasks: list[StageTask]) -> None:
        """Latent handoff between stages: the producer writes the batch's
        state to the buffer, the consumer reads it back — one read+write
        round trip of the latent payload.  Recorded as a tracer OpEvent so
        characterization reflects pipeline traffic; the event is independent
        of the ``impl`` tier, preserving the Amdahl-consistency invariant
        (naive and fallback traces stay identical)."""
        self.spans.instant(
            "handoff", tick=self.ticks, cat="sched",
            lane=self.stages[stage_idx].name, n=len(tasks),
            to=self.stages[stage_idx + 1].name)
        self._reshard(stage_idx, tasks)
        if not tracer.active():
            return
        payload = sum(state_nbytes(t.state) for t in tasks)
        tracer.record(
            "other",
            f"handoff/{self.stages[stage_idx].name}->"
            f"{self.stages[stage_idx + 1].name}",
            flops=0.0, bytes_hbm=2.0 * payload,
            batch=len(tasks), stage=self.stages[stage_idx].name,
        )

    def _reshard(self, stage_idx: int, tasks: list[StageTask]) -> None:
        """Move latents whose next stage runs on a different device slice:
        ``device_put`` each task's state onto the consumer's slice and count
        the traffic honestly — cross-slice handoffs are the cost per-stage
        device assignment pays for the HBM-fit win."""
        cur = self.stage_meshes[stage_idx]
        nxt = self.stage_meshes[stage_idx + 1]
        if cur is None or nxt is None:
            return
        if set(cur.devices.flat) == set(nxt.devices.flat):
            return
        from repro.parallel.sharding import replicated

        payload = sum(state_nbytes(t.state) for t in tasks)
        sh = replicated(nxt)
        for t in tasks:
            t.state = jax.device_put(t.state, sh)
        self.reshard_events += 1
        self.reshard_bytes += payload
        if tracer.active():
            tracer.record(
                "other",
                f"reshard/{self.stages[stage_idx].name}->"
                f"{self.stages[stage_idx + 1].name}",
                flops=0.0, bytes_hbm=float(payload),
                batch=len(tasks), stage=self.stages[stage_idx].name,
            )

    # -- reporting -----------------------------------------------------------

    def modeled_comparison(self) -> dict:
        """Stage-batched (as actually scheduled) vs end-to-end lockstep, on
        the shared dispatch-overhead + per-item HBM-cost model, plus the
        aligned-vs-pipelined instantaneous HBM-demand profile (§V-A)."""
        costs = [stage_unit_cost(s) for s in self.stages]
        demands = [mean_demand(s) for s in self.stages]
        overhead = DISPATCH_OVERHEAD_FRAC * sum(costs) / len(costs)

        # lockstep baseline: pods of pod_size run every stage together
        n = self.submitted
        pods = [self.pod_size] * (n // self.pod_size)
        if n % self.pod_size:
            pods.append(n % self.pod_size)
        t_lock = sum(overhead + p * c for p in pods for c in costs)
        prof_lock = [p * d for p in pods for d in demands]

        # pipelined: the executed stage-batch log.  The demand profile is
        # per *dispatch* (stage-batches within a tick time-share the
        # device): stage-batching levels it by folding many low-demand
        # dispatches (text encoder at pod batch) into few wide ones, while
        # the heaviest stage stays at pod batch — same peak, higher floor.
        t_pipe = sum(overhead + b * costs[i] for i, b in self.executed)
        prof_pipe = [b * demands[i] for i, b in self.executed]

        def side(t, prof):
            peak = max(prof) if prof else 0.0
            mean = sum(prof) / len(prof) if prof else 0.0
            return {
                "modeled_time": t,
                "modeled_throughput": (n / t) if t else 0.0,
                "peak_demand": peak,
                "mean_demand": mean,
                "flatness": (peak / mean) if mean else 0.0,
            }

        out = {"lockstep": side(t_lock, prof_lock),
               "pipelined": side(t_pipe, prof_pipe)}
        out["throughput_gain"] = (
            out["pipelined"]["modeled_throughput"]
            / out["lockstep"]["modeled_throughput"]
            if out["lockstep"]["modeled_throughput"] else 0.0)
        return out

    def summary(self) -> dict:
        """The ``engine.stats["cascade"]`` payload: per-stage execution,
        queue, tail-latency (p50/p95 queue-wait ticks + service seconds)
        and tier reports, plus pipeline-level concurrency, per-tier
        attribution, and the modeled §V-A comparison.  Schema documented in
        ``docs/serving.md``."""
        per_stage = {}
        tiers: dict[str, dict] = {}
        for ex, buf in zip(self.executors, self.buffers):
            s = ex.summary()
            occ = buf.occupancy
            s["queue"] = {
                "capacity": buf.capacity,
                "mean_occupancy": (sum(occ) / len(occ)) if occ else 0.0,
                "max_occupancy": max(occ) if occ else 0,
            }
            s["queue_wait_ticks"] = buf.waits.summary()
            per_stage[ex.name] = s
            t = tiers.setdefault(ex.effective_impl,
                                 {"requested": set(), "stages": [],
                                  "items": 0, "exec_s": 0.0})
            t["requested"].add(ex.impl)
            t["stages"].append(ex.name)
            t["items"] += s["items"]
            t["exec_s"] += s["exec_s"]
        for t in tiers.values():
            t["requested"] = sorted(t["requested"])
            t["rps"] = (t["items"] / t["exec_s"]) if t["exec_s"] else 0.0
        conc = self.concurrency
        mesh_report = None
        if self.mesh is not None:
            mesh_report = {
                "axes": {k: int(v) for k, v in self.mesh.shape.items()},
                "devices": int(self.mesh.devices.size),
                "stage_devices": {
                    s.name: int(m.devices.size)
                    for s, m in zip(self.stages, self.stage_meshes)
                },
                "reshard_events": int(self.reshard_events),
                "reshard_bytes": int(self.reshard_bytes),
            }
        return {
            "stages": per_stage,
            "tiers": tiers,
            "submitted": self.submitted,
            "completed": self.completed,
            "parked": self.parked,
            "resumed": self.resumed,
            "ticks": self.ticks,
            "concurrency": {
                "max": max(conc) if conc else 0,
                "mean": (sum(conc) / len(conc)) if conc else 0.0,
            },
            "hbm": self.modeled_comparison(),
            **({"mesh": mesh_report} if mesh_report is not None else {}),
        }
