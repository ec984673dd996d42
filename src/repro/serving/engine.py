"""Modality-agnostic serving engine over the GenerativeWorkload API.

One ``submit/step/run`` surface for every suite model, and ONE execution
path behind it: every route drives the workload's canonical stage
composition (``GenerativeWorkload.generate`` -> ``run_stage``) under the
shared ``stage_key(seed, rid, stage_index)`` PRNG contract, so outputs are
bit-identical across routes and ``ServeConfig.stage_impl`` per-stage tier
overrides + per-stage time attribution apply everywhere.  The routes differ
only in *scheduling*:

  * **LM route** (Table III Prefill/Decode): requests are admitted through
    the bucketed scheduler, then served through the stage driver (prefill +
    decode) — one greedy/temperature decode loop shared with every route.
    Per-batch ``padding_waste`` — the §V-B bucket-quantum trade — lands in
    ``stats``.
  * **Pod route** (diffusion / AR-image / TTV): requests accumulate into
    denoise pods; each pod runs the stage driver as one batch while
    ``DenoisePodScheduler`` staggers the pod's step indices (paper §V-A) —
    the resulting ``bandwidth_profile`` (aligned vs staggered HBM peak) is
    reported in ``stats``.
  * **Cascade route** (``ServeConfig(route="cascade")``, any workload): pods
    feed ``repro.pipeline.CascadePipeline``, which executes the same
    ``CostDescriptor.stages`` as a stage-level pipeline — cross-request
    batching per stage, bounded latent-handoff queues, per-stage tail
    latency (p50/p95 queue-wait ticks + service time) and kernel-tier
    attribution in ``stats["cascade"]``.

**Online serving.**  ``submit(..., arrival_tick=t)`` defers a request to
scheduling tick ``t`` (one tick = one ``step()`` call); ``arrival_tick=None``
is the closed-loop sentinel — the request is released when an earlier one
completes.  ``repro.serving.ArrivalTrace`` generates these ticks
(poisson / burst / closed-loop).  Under ``ServeConfig.admission =
"continuous"`` a partial pod whose oldest request has waited
``arrival_flush_wait`` ticks is flushed into the pipeline, where it joins
the partially-drained stage queues mid-flight; ``admission="pod"`` holds
partial pods for future arrivals (the lockstep baseline the ``bench_online``
A/B measures against).  See ``docs/serving.md``.

Every route threads ``ServeConfig.impl`` down to ``generate``/``run_stage``
(cascade stages individually overridable via ``ServeConfig.stage_impl``)
and reports per-tier served throughput in ``stats["tier_throughput"]``.

Runs the reduced configs on CPU (tests/examples) and the full configs on the
production mesh via the same code path.
"""

from __future__ import annotations

import dataclasses
import heapq
import time
from collections import deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.pipeline import CascadePipeline, effective_tier, resolve_stage_impls
from repro.pipeline.stage import stage_counts
from repro.serving.scheduler import (
    BucketedScheduler,
    DenoisePodScheduler,
    Request,
    bucket_of,
)
from repro.telemetry import (
    STATS_SCHEMA_VERSION,
    MetricsRegistry,
    SpanCollector,
    compiles,
    write_chrome_trace,
)
from repro.workload import GenerativeWorkload, workload_for
from repro.workload.base import SERVE_ROUTES


@dataclasses.dataclass
class ServeConfig:
    """Engine-level serving knobs (workload-independent).

    ``temperature`` is the LM sampling temperature (0 = greedy, bit-stable);
    ``impl`` the engine-wide kernel tier, with ``stage_impl`` overriding it
    per stage by exact name or prefix (``{"sr": "pallas"}`` puts every SR
    stage on the Pallas kernel while the rest keep ``impl``) — honored on
    **every** route, since all routes execute the same stage driver;
    ``admission`` selects the online pod-admission policy — ``"continuous"``
    flushes a partial pod after ``arrival_flush_wait`` ticks of arrival
    pressure, ``"pod"`` holds partials until arrivals fill them.

    ``route`` selects the *serve* route: ``"auto"`` uses the workload's
    native route (``"lm"`` or ``"pod"``), ``"cascade"`` forces stage-level
    pipeline serving (see the route-taxonomy note in
    ``repro.workload.base``).

    ``tick_seconds`` maps the engine's scheduling-tick clock to wall time:
    ``None`` auto-calibrates from the measured median busy-tick service
    time (median: robust to the JIT-compile outlier on first-shape ticks),
    so arrival rates and tail latencies can be stated in requests/second
    and seconds (``engine.stats["clock"]``).

    ``mesh`` (optional ``jax.sharding.Mesh`` with ``data``/``model`` axes,
    e.g. from ``repro.launch.mesh.make_debug_mesh``) turns on sharded
    serving: params are TP-sharded once at engine construction, every
    stage dispatch runs data-parallel over the batch, and the cascade
    route additionally carves the mesh into per-stage device slices.
    ``stats["mesh"]`` reports the axes plus sharded-vs-replicated param
    bytes ("TP coverage").  Outputs stay bit-equivalent to single-device
    serving under the ``stage_key`` PRNG contract (up to XLA accumulation
    order; pinned in ``tests/test_route_parity.py``)."""

    max_batch: int = 4
    max_len: int = 256
    buckets: tuple = (32, 64, 128)
    temperature: float = 0.0  # 0 = greedy
    pod_size: int = 0  # 0 -> max_batch
    seed: int = 0
    impl: str = "auto"  # kernel tier threaded down to generate/run_stage
    stage_impl: dict | None = None  # per-stage tier overrides (any route)
    route: str = "auto"  # "auto" (workload default) | "cascade"
    queue_capacity: int = 8  # cascade inter-stage handoff buffer depth
    admission: str = "continuous"  # "continuous" | "pod" (online pod flush)
    arrival_flush_wait: int = 2  # ticks a partial pod waits before flushing
    tick_seconds: float | None = None  # None -> calibrate from measurement
    mesh: Any = None  # optional jax Mesh ("data"/"model") -> sharded serving

    @property
    def resolved_pod_size(self) -> int:
        return self.pod_size or self.max_batch

    def __post_init__(self):
        if self.admission not in ("continuous", "pod"):
            raise ValueError(
                f"unknown admission policy {self.admission!r} "
                f"(expected 'continuous' or 'pod')")
        if self.route not in ("auto",) + SERVE_ROUTES:
            raise ValueError(
                f"unknown serve route {self.route!r} (expected 'auto' or "
                f"one of {SERVE_ROUTES}; workload routes are documented in "
                f"repro.workload.base)")
        if self.tick_seconds is not None and self.tick_seconds <= 0:
            raise ValueError(
                f"tick_seconds must be > 0 (or None to auto-calibrate), "
                f"got {self.tick_seconds}")


class ServeEngine:
    """Serves any registered GenerativeWorkload behind submit/step/run."""

    def __init__(self, workload, params, serve_cfg: ServeConfig = ServeConfig()):
        if not isinstance(workload, GenerativeWorkload):
            workload = workload_for(workload)  # accept a raw config too
        self.workload = workload
        self.cfg = workload.cfg
        self.model = workload.model
        # -- sharded serving: place params on the mesh ONCE, here ------------
        self.mesh = serve_cfg.mesh
        self._mesh_report = None
        if self.mesh is not None:
            from repro.parallel.sharding import (
                REPLICATION_FALLBACKS,
                SERVE_TP_RULES,
                shard_report,
            )

            before = REPLICATION_FALLBACKS.value
            params = workload.shard_params(params, self.mesh)
            self._mesh_report = shard_report(
                params, workload.model.specs(), self.mesh, SERVE_TP_RULES)
            self._mesh_report["replication_fallbacks"] = (
                REPLICATION_FALLBACKS.value - before)
        self.params = params
        self.serve_cfg = serve_cfg
        self.cost = workload.cost_descriptor()
        self.route = (workload.route if serve_cfg.route == "auto"
                      else serve_cfg.route)
        if self.route not in SERVE_ROUTES:
            raise ValueError(
                f"unknown serve route {self.route!r} (expected one of "
                f"{SERVE_ROUTES}; the workload route — "
                f"{workload.route!r} here — names the scheduler family, "
                f"see repro.workload.base)")
        # validate per-stage tier overrides up front on EVERY route (a typo
        # must not silently serve the default tier); all routes execute the
        # same stage driver, so the overrides apply everywhere
        self._stage_tiers = {
            st.name: (im, effective_tier(im)) for st, im in zip(
                self.cost.stages,
                resolve_stage_impls(self.cost.stages, serve_cfg.impl,
                                    serve_cfg.stage_impl))}
        self._stats: dict = {"schema": STATS_SCHEMA_VERSION,
                            "requests": 0, "impl": serve_cfg.impl,
                            "tier_throughput": {},
                            "stage_impl": dict(serve_cfg.stage_impl or {}),
                            "stages": {}}
        self.pipeline = None
        # -- telemetry: typed metrics + lifecycle spans ----------------------
        self.metrics = MetricsRegistry()
        if self.mesh is not None:
            self._stats["mesh"] = {
                "axes": {k: int(v) for k, v in self.mesh.shape.items()},
                "devices": int(self.mesh.devices.size),
                "params": self._mesh_report,
            }
            self.metrics.counter(
                "sharding_replication_fallbacks",
                "param dims replicated by the divisibility fallback",
            ).inc(self._mesh_report["replication_fallbacks"])
        # program spans count their compiles into this engine's registry
        self.spans = SpanCollector(track="engine", metrics=self.metrics)
        compiles.install()
        self._requests_c = self.metrics.counter(
            "requests_submitted", "requests accepted by submit()")
        self._completed_c = self.metrics.counter(
            "requests_completed", "requests finished")
        self._pending_g = self.metrics.gauge(
            "pending_requests", "requests anywhere in the system")
        # -- online-serving clock + arrival queues ---------------------------
        self._tick = 0  # one tick == one step() call
        self._future: list = []  # heap of (arrival_tick, seq, Request)
        self._closed_loop: deque = deque()  # released on completions
        self._ready_pods: deque = deque()  # pod route: admitted, unserved
        self._seq = 0
        self._arrival_tick: dict[int, int] = {}
        self._submit_s: dict[int, float] = {}  # perf_counter() at submit
        # arrival -> admission / completion waits, streamed at 1-tick buckets
        self._admission_waits = self.metrics.histogram(
            "admission_wait_ticks", "arrival -> pipeline admission")
        self._admission_wait_s = self.metrics.histogram(
            "admission_wait_s", "arrival -> pipeline admission, wall seconds",
            lo=1e-7, hi=1e4, resolution=0.02, scale="log")
        self._e2e_ticks = self.metrics.histogram(
            "request_e2e_ticks", "arrival -> completion")
        self._completed = 0
        # per-tick wall s (work done); log buckets span the JIT-compile
        # outlier to microsecond ticks at ~2% relative resolution
        self._busy_wall_s = self.metrics.histogram(
            "busy_tick_s", "wall seconds of each busy tick",
            lo=1e-7, hi=1e4, resolution=0.02, scale="log")

        if self.route == "cascade":
            # DenoisePodScheduler-staggered pods feed the stage pipeline:
            # admission stays pod-based (the §V-A stagger report is still
            # meaningful per pod), execution is stage-batched across pods.
            self.scheduler = DenoisePodScheduler(
                pod_size=serve_cfg.resolved_pod_size,
                total_steps=self.cost.iterative_steps(),
            )
            self.pipeline = CascadePipeline(
                workload, params, impl=serve_cfg.impl,
                stage_impl=serve_cfg.stage_impl,
                temperature=serve_cfg.temperature,
                pod_size=serve_cfg.resolved_pod_size,
                queue_capacity=serve_cfg.queue_capacity,
                seed=serve_cfg.seed,
                spans=self.spans,  # pipeline spans join the engine timeline
                mesh=self.mesh,  # per-stage device slices (see cascade.py)
            )
            self._stats.update(generate_s=0.0, pods=0, bandwidth_profile=[],
                              cascade={})
        elif self.route == "lm":
            self.scheduler = BucketedScheduler(serve_cfg.buckets,
                                               serve_cfg.max_batch)
            self._stats.update(prefill_s=0.0, decode_s=0.0, tokens=0,
                              padding_waste=[])
        else:
            self.scheduler = DenoisePodScheduler(
                pod_size=serve_cfg.resolved_pod_size,
                total_steps=self.cost.iterative_steps(),
            )
            self._stats.update(generate_s=0.0, pods=0, bandwidth_profile=[])

    def _record_tier(self, n_done: int, wall_s: float) -> None:
        """Per-``impl``-tier served-request throughput; stage-level tier
        attribution lives in ``stats["cascade"]["tiers"]``."""
        t = self._stats["tier_throughput"].setdefault(
            self.serve_cfg.impl, {"requests": 0, "wall_s": 0.0, "rps": 0.0})
        t["requests"] += n_done
        t["wall_s"] += wall_s
        t["rps"] = t["requests"] / t["wall_s"] if t["wall_s"] else 0.0

    @property
    def stats(self) -> dict:
        """The engine's report (schema: ``repro.telemetry.schema``).  Its
        ``stages`` block is built here, when read, from the counters the
        stage spans keep (:func:`repro.pipeline.stage.stage_span`, one per
        dispatch on every route): ``exec_s`` is host seconds in the stage —
        dispatch plus any trace, lowering and compile, and on the cascade
        route the wait for the result — not device time; ``compiles`` /
        ``compile_s`` are the JAX compiles charged to the stage
        (``repro.telemetry.compiles``); ``programs`` counts the stage's
        compiled programs, one per (tier, mesh, batch shape), 0 for a stage
        that runs eagerly.  The cascade route's richer
        per-stage report lives in ``stats["cascade"]``; the legacy lm keys
        (``prefill_s`` / ``decode_s``) mirror their stages' ``exec_s``."""
        s = self._stats
        c = self.metrics.counters()
        for name, (impl, effective) in self._stage_tiers.items():
            if f"stage_dispatches/{name}" in c:
                s["stages"][name] = dict(
                    stage_counts(c, name), impl=impl,
                    effective_impl=effective,
                    compiles=int(c.get(f"compiles/{name}", 0)),
                    compile_s=c.get(f"compile_s/{name}", 0.0),
                    programs=int(c.get(f"stage_programs/{name}", 0)))
        for name, key in (("prefill", "prefill_s"), ("decode", "decode_s")):
            if key in s and name in s["stages"]:
                s[key] = s["stages"][name]["exec_s"]
        return s

    # -- submission ----------------------------------------------------------

    def submit(self, rid: int, tokens, max_new_tokens: int = 0,
               arrival_tick: int | None = 0, *,
               slo_tier: str | None = None,
               deadline_ticks: int | None = None) -> None:
        """Admit one request; ``tokens`` are the prompt/conditioning ids.

        ``arrival_tick`` places the request on the engine's scheduling clock
        (one tick per :meth:`step`): 0 — or any tick already passed — admits
        immediately (the offline/batch case), a future tick defers admission
        until the clock reaches it, and ``None`` (closed loop,
        :data:`repro.serving.ON_COMPLETION`) releases the request when an
        earlier one completes.  ``ArrivalTrace.ticks`` generates these
        values for poisson / burst / closed-loop experiments.

        ``slo_tier`` / ``deadline_ticks`` are the request's SLO class
        (validated in ``prepare_request``; tier ``None`` = modality
        default).  A single engine serves tiers FIFO — the class matters to
        ``repro.fleet.FleetRouter``, which places, preempts and reports by
        tier."""
        req = self.workload.prepare_request(rid, tokens,
                                            max_new_tokens=max_new_tokens,
                                            slo_tier=slo_tier,
                                            deadline_ticks=deadline_ticks)
        if self.workload.route == "lm":  # lm + cascaded-lm routes alike
            limit = max(self.serve_cfg.buckets)
            if req.prompt_len > limit:
                raise ValueError(
                    f"request {rid}: prompt length {req.prompt_len} exceeds "
                    f"the largest configured bucket ({limit}); raise "
                    f"ServeConfig.buckets or truncate the prompt")
        sreq = Request(rid=req.rid, prompt_len=req.prompt_len,
                       max_new_tokens=req.max_new_tokens,
                       denoise_steps=req.denoise_steps,
                       state={"prompt": jnp.asarray(req.tokens, jnp.int32)})
        if arrival_tick is None:
            # a closed-loop request only makes sense while something is in
            # flight to complete and release it; into an idle engine it is
            # admitted immediately (otherwise run() would spin forever
            # waiting on a completion that can never happen)
            if self.pending() == len(self._closed_loop):
                self._enqueue(sreq, self._tick)
            else:
                self._closed_loop.append(sreq)
        elif arrival_tick <= self._tick:
            self._enqueue(sreq, self._tick)
        else:
            self._seq += 1
            heapq.heappush(self._future, (int(arrival_tick), self._seq, sreq))
        self._stats["requests"] += 1
        self._requests_c.inc()
        self._submit_s[req.rid] = time.perf_counter()

    def _enqueue(self, sreq: Request, tick: int) -> None:
        """Hand an arrived request to the route scheduler, stamped with its
        arrival tick and wall time (what the admission-wait and e2e
        latencies key off)."""
        sreq.arrived_at = float(tick)
        sreq.arrived_s = time.perf_counter()
        self._arrival_tick[sreq.rid] = tick
        self.scheduler.submit(sreq)

    def _admit_arrivals(self) -> None:
        """Release every deferred request whose arrival tick has come."""
        while self._future and self._future[0][0] <= self._tick:
            tick, _, sreq = heapq.heappop(self._future)
            self._enqueue(sreq, tick)

    def _arrivals_deferred(self) -> int:
        return len(self._future) + len(self._closed_loop)

    # -- online pod admission ------------------------------------------------

    def _admit_pods_ready(self) -> list[list]:
        """Pop every pod the admission policy allows this tick.

        Full pods always go.  A partial (open) pod goes when (a) nothing
        that could still fill it remains — no timed arrivals, and no
        closed-loop waiters that in-flight work could release — or (b) the
        policy is ``continuous`` and its oldest request has waited
        ``arrival_flush_wait`` ticks (arrival-pressure flush; the §V-A
        stagger profile of such a pod is computed from its *actual* size,
        and its membership is frozen at flush time so no request's offset
        is ever double-counted)."""
        sched, cfg = self.scheduler, self.serve_cfg
        pods = []
        while True:
            pod = sched.pop_pod()
            if not pod and sched.open_size():
                # work whose completions could still release closed-loop
                # waiters: the stage pipeline, pods admitted but not yet
                # served (pod route), and pods popped earlier in THIS call
                in_flight = (
                    (self.pipeline.pending() if self.pipeline is not None
                     else 0)
                    + sum(len(p) for p in self._ready_pods)
                    + sum(len(p) for p in pods))
                can_fill = bool(self._future) or bool(
                    self._closed_loop and in_flight)
                if not can_fill:
                    sched.flush()  # nothing left that could fill the pod
                elif cfg.admission == "continuous":
                    sched.flush_stale(self._tick, cfg.arrival_flush_wait)
                pod = sched.pop_pod()
            if not pod:
                return pods
            pods.append(pod)

    def _record_pod_profile(self, pod: list) -> None:
        """Stagger schedule + §V-A bandwidth profile for one admitted pod."""
        schedule = self.scheduler.schedule(pod)
        self._stats["bandwidth_profile"].append(
            DenoisePodScheduler.bandwidth_profile(
                self.cost.step_demands(), schedule))
        self._stats["pods"] += 1
        for r in pod:
            self._record_admission(r)

    def _record_admission(self, r) -> None:
        """Arrival -> scheduler-admission wait: histogram samples (ticks and
        wall seconds) + span."""
        arrived, now = int(r.arrived_at), time.perf_counter()
        self._admission_waits.observe(self._tick - arrived)
        self._admission_wait_s.observe(now - r.arrived_s)
        self.spans.span("admission_wait", cat="admission",
                        start_tick=arrived, end_tick=self._tick,
                        lane="admission", rid=r.rid, start_s=r.arrived_s,
                        end_s=now)

    # -- LM route ------------------------------------------------------------

    def _pad_prompts(self, batch, width: int):
        toks = jnp.zeros((len(batch), width), jnp.int32)
        for i, r in enumerate(batch):
            toks = toks.at[i, : r.prompt_len].set(r.state["prompt"])
        return toks

    def _drive(self, requests: list, width: int) -> list:
        """Execute one batch of scheduled requests through THE stage driver
        (``GenerativeWorkload.generate_requests``): every route serves the
        same ``init_stage_state -> run_stage* -> stage_output`` composition
        under the ``stage_key(seed, rid, stage_index)`` PRNG contract, with
        ``ServeConfig.stage_impl`` per-stage tier overrides and per-stage
        time attribution (``stats["stages"]``) applied on every route."""
        toks = self._pad_prompts(requests, width)
        # mesh forwarded only when set (mesh-free driver doubles keep working)
        mesh_kw = {} if self.mesh is None else {"mesh": self.mesh}
        return self.workload.generate_requests(
            self.params, toks, jax.random.PRNGKey(self.serve_cfg.seed),
            impl=self.serve_cfg.impl,
            stage_impl=self.serve_cfg.stage_impl,
            temperature=self.serve_cfg.temperature,
            max_new_tokens=[r.max_new_tokens for r in requests],
            rids=[r.rid for r in requests],
            spans=self.spans, **mesh_kw)

    def _step_lm(self) -> list[tuple[int, Any]]:
        """Serve one bucketed batch through the stage driver — the same
        prefill/decode loop the cascade route runs, so greedy tokens are
        identical across routes and ``ServeConfig.temperature`` sampling
        lives in one place."""
        t_step = time.perf_counter()
        bucket, batch = self.scheduler.next_batch()
        if not batch:
            return []
        for r in batch:
            self._record_admission(r)
        self._stats["padding_waste"].append(
            self.scheduler.padding_waste(batch, bucket))
        outs = self._drive(batch, bucket)
        self._stats["tokens"] += (
            max(r.max_new_tokens for r in batch) * len(batch))
        self._record_tier(len(batch), time.perf_counter() - t_step)
        return [(r.rid, [int(t) for t in outs[i]])
                for i, r in enumerate(batch)]

    # -- pod route -----------------------------------------------------------

    def _step_pod(self) -> list[tuple[int, Any]]:
        if not self._ready_pods:
            self._ready_pods.extend(self._admit_pods_ready())
        pod = self._ready_pods.popleft() if self._ready_pods else []
        if not pod:
            return []
        with self.spans.region("serve/pod", cat="serve", lane="pod",
                               batch=len(pod),
                               pod_size=self.serve_cfg.resolved_pod_size):
            # staggered step indices for the pod (paper §V-A) + the
            # resulting instantaneous-HBM-demand flattening vs the aligned
            # baseline
            self._record_pod_profile(pod)

            t0 = time.perf_counter()
            outs = self._drive(pod, max(r.prompt_len for r in pod))
            outs = [jax.block_until_ready(o) for o in outs]
            dt = time.perf_counter() - t0
            self._stats["generate_s"] += dt
            self._record_tier(len(pod), dt)
            return [(r.rid, np.asarray(outs[i])) for i, r in enumerate(pod)]

    # -- cascade route -------------------------------------------------------

    def _admit_cascade_pods(self) -> None:
        """Feed every admission-ready pod into the stage pipeline.  The
        stagger schedule (§V-A) is recorded per pod; inside the pipeline
        requests from all admitted pods batch together per stage, and a
        pod admitted mid-flight joins the partially-drained first-stage
        queue (continuous admission)."""
        for pod in self._admit_pods_ready():
            self._record_pod_profile(pod)
            for r in pod:
                width = min(bucket_of(r.prompt_len, self.serve_cfg.buckets),
                            self.workload.max_prompt_len)
                width = max(width, r.prompt_len)
                toks = np.zeros(width, np.int32)
                toks[: r.prompt_len] = np.asarray(r.state["prompt"])
                self.pipeline.submit(r.rid, toks,
                                     max_new_tokens=r.max_new_tokens)

    def _step_cascade(self) -> list[tuple[int, Any]]:
        self._admit_cascade_pods()
        t0 = time.perf_counter()
        done = self.pipeline.tick()
        dt = time.perf_counter() - t0
        self._stats["generate_s"] += dt
        self._record_tier(len(done), dt)
        return [(rid, np.asarray(out)) for rid, out in done]

    # -- fleet hooks: stage-boundary preemption / migration ------------------

    def _require_pipeline(self, what: str):
        if self.pipeline is None:
            raise ValueError(
                f"{what} requires the cascade route (stage-boundary state "
                f"lives in the pipeline's StageBuffers); this engine serves "
                f"route {self.route!r} — construct it with "
                f"ServeConfig(route='cascade')")
        return self.pipeline

    def parked_rids(self) -> list[int]:
        """Rids whose per-stage state is parked at a stage boundary inside
        this engine's pipeline — the preemptible set (empty off the cascade
        route)."""
        return ([] if self.pipeline is None
                else self.pipeline.queued_rids())

    def preempt(self, rids) -> list:
        """Preempt ``rids`` at their current cascade stage boundary and
        return their parked state (``ParkedTask`` payloads).  The fleet
        router resumes them later — on this engine or on another replica
        whose engine shares this one's ``ServeConfig.seed``; under the
        ``stage_key(seed, rid, stage_index)`` fold the output is
        bit-identical either way (``tests/test_route_parity.py``)."""
        return self._require_pipeline("preempt()").park(rids)

    def resume(self, parked: list) -> None:
        """Re-admit parked stage state (from :meth:`preempt`, possibly on a
        different replica) at its recorded stage boundary."""
        self._require_pipeline("resume()").resume(parked)

    def _finalize_cascade_stats(self) -> None:
        """Refresh ``stats["cascade"]`` once the pipeline drains (summary
        walks the full dispatch/occupancy logs — O(ticks^2) if per-tick),
        folding in the engine-level admission/latency report."""
        self._stats["cascade"] = self.pipeline.summary()
        self._stats["cascade"]["admission"] = {
            "policy": self.serve_cfg.admission,
            "flush_wait_ticks": self.serve_cfg.arrival_flush_wait,
            "wait_ticks": self._admission_waits.summary(),
        }
        self._stats["cascade"]["request_latency_ticks"] = (
            self._e2e_ticks.summary())

    # -- unified loop --------------------------------------------------------

    def step(self) -> list[tuple[int, Any]]:
        """Advance the serving clock one tick: admit due arrivals, serve one
        scheduled batch / pod / pipeline round, release closed-loop
        requests for completions.  Returns completed ``(rid, out)`` pairs
        (often empty mid-pipeline).  The tick runs inside the ``serve/step``
        program span."""
        with self.spans.region("serve/step", cat="serve", lane="step",
                               tick=self._tick):
            t0 = time.perf_counter()
            self._admit_arrivals()
            if self.route == "cascade":
                n_exec = len(self.pipeline.executed)
                done = self._step_cascade()
                busy = len(self.pipeline.executed) > n_exec
            elif self.route == "lm":
                done = self._step_lm()
                busy = bool(done)
            else:
                done = self._step_pod()
                busy = bool(done)
            if busy:  # tick->wall-clock calibration sample (busy only)
                self._busy_wall_s.observe(time.perf_counter() - t0)
            self._completed += len(done)
            self._completed_c.inc(len(done))
            now = time.perf_counter()
            for rid, _ in done:
                if rid in self._arrival_tick:
                    arrival = self._arrival_tick[rid]
                    self._e2e_ticks.observe(self._tick - arrival)
                    self.spans.span(
                        "request", cat="request", start_tick=arrival,
                        end_tick=self._tick, lane="request", rid=rid,
                        start_s=self._submit_s.pop(rid, None), end_s=now)
                if self._closed_loop:  # one completion releases one waiter
                    self._enqueue(self._closed_loop.popleft(), self._tick)
        self._tick += 1
        self._pending_g.set(self.pending())
        if not self.pending():
            if self.route == "cascade":
                self._finalize_cascade_stats()
            self._finalize_clock()
        return done

    # -- tick -> wall-clock calibration --------------------------------------

    def tick_seconds(self) -> float:
        """Wall-clock seconds per scheduling tick: the configured
        ``ServeConfig.tick_seconds``, else the measured MEDIAN busy-tick
        service time (the ROADMAP calibration item) — what lets tick-based
        arrival rates and latencies be stated in req/s and seconds.  The
        median, not the mean: the first busy tick of each compiled shape pays
        XLA trace+compile, and on short runs that outlier would dominate a
        mean and inflate every second-denominated stat derived from it."""
        if self.serve_cfg.tick_seconds is not None:
            return float(self.serve_cfg.tick_seconds)
        if self._busy_wall_s.count:
            return self._busy_wall_s.median()
        return 0.0

    def _finalize_clock(self) -> None:
        """``stats["clock"]`` + wall-clock req/s and tail latencies derived
        from the tick clock (schema in ``docs/serving.md``)."""
        ts = self.tick_seconds()
        self._stats["clock"] = {
            "tick_seconds": ts,
            "source": ("configured" if self.serve_cfg.tick_seconds is not None
                       else "calibrated"),
            "ticks": self._tick,
            "busy_ticks": len(self._busy_wall_s),
        }
        lat_ticks = self._e2e_ticks.summary()
        self._stats["request_latency_ticks"] = lat_ticks
        self._stats["request_latency_s"] = {k: v * ts
                                           for k, v in lat_ticks.items()}
        wall = self._tick * ts
        self._stats["requests_per_s"] = (self._completed / wall) if wall else 0.0

    def pending(self) -> int:
        """Requests anywhere in the system: deferred arrivals, scheduler
        queues, admitted-but-unserved pods, and the stage pipeline."""
        return (self.scheduler.pending()
                + self._arrivals_deferred()
                + sum(len(p) for p in self._ready_pods)
                + (self.pipeline.pending() if self.pipeline is not None else 0))

    def run(self) -> dict:
        """Step until drained; returns ``{rid: output}``.  With deferred
        arrivals the loop idles through empty ticks until the clock reaches
        them — the tick clock, not wall time, is the simulation axis."""
        results = {}
        while self.pending():
            for rid, out in self.step():
                results[rid] = out
        return results

    # -- telemetry export ----------------------------------------------------

    def snapshot(self) -> dict:
        """Versioned ``MetricsRegistry.snapshot()`` of the typed metrics
        behind ``stats`` (schema: ``repro.telemetry.schema``)."""
        return self.metrics.snapshot()

    def export_chrome_trace(self, path: str, **metadata) -> int:
        """Write this engine's span timeline as Chrome trace-event JSON
        (open at https://ui.perfetto.dev); returns the event count.  Tick
        timestamps are converted to wall microseconds via the calibrated
        :meth:`tick_seconds`."""
        return write_chrome_trace(path, [self.spans],
                                  self.tick_seconds() or 1.0, **metadata)


class LMServeEngine(ServeEngine):
    """Back-compat name for the LM-route engine (pre-unification API)."""
