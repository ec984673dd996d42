"""The ``GenerativeWorkload`` protocol + config-keyed workload registry.

The paper's core systems argument is that TTI/TTV generation must be served
as a first-class workload, not an LLM afterthought.  Concretely that means
one API over the whole eight-model suite: a serving engine, the abstract
characterizer, and every benchmark should be written once against

  * ``init(key)``                 — materialize parameters
  * ``prepare_request(...)``      — modality-specific inputs -> ``GenRequest``
  * ``generate(params, tokens, key)`` — the canonical stage composition:
    ``init_stage_state`` -> the descriptor's stage sequence via
    ``run_stage`` -> ``stage_output`` (there is no other pipeline driver)
  * ``trace_inputs()`` / ``trace_events(impl)`` — abstract characterization
    (traces the same ``generate`` driver served execution runs)
  * ``cost_descriptor()``         — the stage/step structure (denoise steps,
    decode steps, SR stages) that schedulers consume

instead of five bespoke ``sample``/``prefill`` signatures dispatched through
``isinstance`` chains.  Dispatch is a registry keyed by *config type*,
mirroring the ``--arch`` name registry in ``repro.configs.base``: each
workload class declares ``@register_workload(SomeConfig)`` and
``workload_for(cfg)`` resolves through the config's MRO.  Adding a ninth
model is one new config class + one decorated workload class — no existing
call site changes.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import numpy as np

# ---------------------------------------------------------------------------
# Route taxonomy (THE one place it is defined)
# ---------------------------------------------------------------------------
#
# Two distinct notions share the word "route":
#
# * **workload route** — ``GenerativeWorkload.route`` / ``GenRequest.route``
#   / ``CostDescriptor.route``: which *scheduler family* the workload's
#   requests natively belong to.  ``"lm"`` = bucketed prefill+decode
#   (paper §V-B), ``"pod"`` = staggered denoise pods (paper §V-A).
# * **serve route** — how ``ServeEngine`` actually executes: the two
#   workload routes plus ``"cascade"`` (stage-level pipeline serving,
#   paper §IV-C), selected by ``ServeConfig.route``.  Every serve route
#   executes through the same stage driver (``generate``/``run_stage``),
#   so outputs are bit-identical across routes under the shared PRNG
#   contract below.

WORKLOAD_ROUTES = ("lm", "pod")
SERVE_ROUTES = ("lm", "pod", "cascade")

# ---------------------------------------------------------------------------
# SLO classes (fleet serving)
# ---------------------------------------------------------------------------
#
# Deployment-level scheduling (the arXiv:2410.00215 follow-up knob) needs a
# per-request service class: ``"interactive"`` requests are latency-bound
# (short TTI / LM traffic, steered and preempted for), ``"batch"`` requests
# are throughput-bound (long TTV jobs, preemptible at cascade stage
# boundaries).  The tier + optional ``deadline_ticks`` live on ``GenRequest``
# and are validated at ``prepare_request``; ``repro.fleet.FleetRouter``
# consumes them for placement, preemption and deadline-attainment reporting.

SLO_TIERS = ("interactive", "batch")


def default_slo_tier(modality: str) -> str:
    """The paper's traffic-mix default: video generation is long-running
    batch work, text/image requests are interactive."""
    return "batch" if modality == "video" else "interactive"


# ---------------------------------------------------------------------------
# Per-request PRNG contract
# ---------------------------------------------------------------------------


def stage_key(key, rid: int, stage_index: int):
    """The suite-wide per-request PRNG contract: stage randomness is the
    serve seed folded with ``(rid, stage_index)`` — never with the batch
    index or pod composition.  Every route (the ``generate`` driver,
    ``ServeEngine._step_pod``/``_step_lm``, ``CascadePipeline``) derives
    noise through this fold, which is what makes outputs bit-identical no
    matter how requests are batched."""
    import jax

    return jax.random.fold_in(jax.random.fold_in(key, rid), stage_index)


def stage_keys(key, rids, stage_index: int):
    """Stacked ``(B, ...)`` per-request keys for one batched stage dispatch.
    ``run_stage`` implementations draw per-request noise by ``jax.vmap``-ing
    over axis 0 (see ``DiffusionWorkload.stage_body``)."""
    import jax.numpy as jnp

    return jnp.stack([stage_key(key, rid, stage_index) for rid in rids])


# ---------------------------------------------------------------------------
# Uniform request / cost views
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class GenRequest:
    """One generation request, uniform across modalities.

    ``tokens`` is always the conditioning text/prompt token ids (1-D);
    modality-specific knobs (decode budget, denoise steps) ride along so a
    scheduler never needs to know which model family it is batching.

    ``route`` is the *workload* route (``"lm" | "pod"`` — which scheduler
    family admits the request); the engine may still *serve* it on the
    ``"cascade"`` route.  See the route-taxonomy note at the top of this
    module.

    ``slo_tier`` (``SLO_TIERS``) + ``deadline_ticks`` are the request's SLO
    class for fleet serving: ``"interactive"`` traffic is placed and
    preempted for, ``"batch"`` traffic is preemptible at cascade stage
    boundaries; ``deadline_ticks`` (``None`` = best-effort) is the e2e
    latency budget on the fleet's tick clock that deadline-attainment
    reporting keys off."""

    rid: int
    modality: str  # "text" | "image" | "video"
    route: str  # workload route: "lm" | "pod" (see WORKLOAD_ROUTES)
    tokens: Any  # (S,) int32 prompt / text-conditioning ids
    max_new_tokens: int = 0  # LM decode budget
    denoise_steps: int = 0  # iterative-refinement step count (pod route)
    slo_tier: str = "interactive"  # SLO class (see SLO_TIERS)
    deadline_ticks: int | None = None  # e2e budget in ticks (None = none)
    meta: dict = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        if self.route not in WORKLOAD_ROUTES:
            raise ValueError(
                f"unknown workload route {self.route!r} (expected one of "
                f"{WORKLOAD_ROUTES}; 'cascade' is a serve route — pass it "
                f"via ServeConfig.route, not on the request)")
        if self.slo_tier not in SLO_TIERS:
            raise ValueError(
                f"unknown SLO tier {self.slo_tier!r} (expected one of "
                f"{SLO_TIERS})")
        if self.deadline_ticks is not None and self.deadline_ticks <= 0:
            raise ValueError(
                f"deadline_ticks must be > 0 (or None for best-effort), "
                f"got {self.deadline_ticks}")

    @property
    def prompt_len(self) -> int:
        return int(np.shape(self.tokens)[-1])


@dataclasses.dataclass(frozen=True)
class Stage:
    """One pipeline stage of a generative workload.

    ``steps`` is how many times the stage's graph executes (denoise steps,
    unmasking steps, AR decode steps); ``seq_len`` a representative attention
    sequence length; ``demand`` an optional per-tick relative HBM-demand
    profile inside the stage (the Fig. 7 U-shape for UNets, linear cache
    growth for AR decode) that ``DenoisePodScheduler`` staggering consumes."""

    name: str
    steps: int
    seq_len: int
    demand: tuple = ()


@dataclasses.dataclass(frozen=True)
class CostDescriptor:
    """Scheduler-facing cost structure of one workload (paper Table III).

    ``route`` is the workload route (``WORKLOAD_ROUTES``); ``stages`` is
    *executable* — the default ``GenerativeWorkload.generate`` driver and
    the cascade pipeline both run exactly this sequence through
    ``run_stage``."""

    arch: str
    route: str  # workload route: "lm" | "pod" (see WORKLOAD_ROUTES)
    stages: tuple  # tuple[Stage, ...]

    def __post_init__(self):
        if self.route not in WORKLOAD_ROUTES:
            raise ValueError(
                f"unknown workload route {self.route!r} for {self.arch!r} "
                f"(expected one of {WORKLOAD_ROUTES})")

    def total_steps(self) -> int:
        return sum(s.steps for s in self.stages)

    def iterative_steps(self) -> int:
        """Steps of the dominant iterative stage (what a pod staggers over)."""
        return max((s.steps for s in self.stages), default=1)

    def step_demands(self) -> list:
        """Relative per-tick HBM demand across the iterative stages, for
        ``DenoisePodScheduler.bandwidth_profile``.  Stages without an explicit
        profile contribute their (flat) seq_len."""
        out: list = []
        for s in self.stages:
            if s.steps <= 1 and not s.demand:
                continue  # one-shot stages (text encoder, VAE) don't stagger
            prof = list(s.demand) if s.demand else [s.seq_len]
            reps = max(1, s.steps // max(len(prof), 1))
            out += (prof * reps)[: max(s.steps, len(prof))]
        return out or [1.0]


# ---------------------------------------------------------------------------
# Protocol
# ---------------------------------------------------------------------------


class GenerativeWorkload:
    """Base class every suite workload implements.

    Subclasses set ``route``/``modality``, implement ``build_model`` and the
    modality-specific hooks; everything downstream (``ServeEngine``,
    ``benchmarks.workloads``, the examples) talks only to this interface."""

    route: str = "pod"  # workload route (WORKLOAD_ROUTES): "lm" | "pod"
    modality: str = "image"

    def __init__(self, cfg):
        if self.route not in WORKLOAD_ROUTES:
            raise ValueError(
                f"{type(self).__name__}.route={self.route!r} is not a "
                f"workload route (expected one of {WORKLOAD_ROUTES})")
        self.cfg = cfg
        self.model = self.build_model(cfg)
        self._stage_programs: dict = {}  # (stage, impl, mesh) -> jitted body

    # -- construction --------------------------------------------------------

    def build_model(self, cfg):
        raise NotImplementedError

    def init(self, key, mesh=None):
        """Materialize parameters; with a ``mesh``, shard them once here via
        ``shard_params_tree`` (serving rules) — the single sharding point of
        the serving path."""
        params = self.model.init(key)
        if mesh is not None:
            params = self.shard_params(params, mesh)
        return params

    def shard_params(self, params, mesh):
        """Place a params tree on ``mesh`` under the serving TP rules
        (weights replicated over ``data``, TP-sharded over ``model``,
        channel-parallel conv for the attention-free SR UNets).  Dims that
        don't divide their axis replicate — with a warning and a telemetry
        count (see ``parallel.sharding.REPLICATION_FALLBACKS``)."""
        from repro.parallel.sharding import SERVE_TP_RULES, shard_params_tree

        return shard_params_tree(params, self.model.specs(), mesh,
                                 SERVE_TP_RULES)

    def reduced(self):
        """Tiny same-structure config for CPU execution/benchmarks."""
        raise NotImplementedError

    # -- serving -------------------------------------------------------------

    @property
    def prompt_vocab(self) -> int:
        """Vocab to draw conditioning prompt ids from."""
        return self.cfg.text.vocab

    @property
    def max_prompt_len(self) -> int:
        return self.cfg.text.max_len

    def prepare_request(self, rid: int, tokens, *, max_new_tokens: int = 0,
                        slo_tier: str | None = None,
                        deadline_ticks: int | None = None,
                        **meta) -> GenRequest:
        """Modality-specific inputs -> a validated :class:`GenRequest`.
        ``slo_tier=None`` picks the modality default (video = batch, else
        interactive); an unknown tier or non-positive deadline raises here,
        before the request reaches any scheduler."""
        cd = self.cost_descriptor()
        return GenRequest(
            rid=rid, modality=self.modality, route=self.route,
            tokens=np.asarray(tokens, np.int32),
            max_new_tokens=max_new_tokens,
            denoise_steps=cd.iterative_steps() if self.route == "pod" else 0,
            slo_tier=(default_slo_tier(self.modality) if slo_tier is None
                      else slo_tier),
            deadline_ticks=deadline_ticks,
            meta=meta,
        )

    def generate(self, params, tokens, key, *, impl="auto",
                 max_new_tokens: int = 0, temperature: float = 0.0,
                 rids=None, stage_impl: dict | None = None, spans=None,
                 mesh=None):
        """Batched full-pipeline inference: (B, S) tokens -> stacked output.

        This is THE canonical stage composition: ``init_stage_state`` per
        request, then the descriptor's stage sequence through ``run_stage``
        (each dispatch wrapped in :func:`repro.pipeline.stage.stage_span`:
        a ``tracer.scope`` named after the stage and the
        ``serve/stage/<name>`` program span), then ``stage_output``.  The serving engine's pod
        and lm routes and the cascade pipeline all execute this same
        machinery, so served outputs and ``trace_events`` characterization
        can never drift — and under the ``stage_key`` PRNG contract the
        routes are bit-identical.

        ``rids`` are the per-request ids the PRNG contract folds (default
        ``range(B)``); ``max_new_tokens`` is a scalar decode budget shared
        by the batch (per-request budgets produce ragged outputs — use
        :meth:`generate_requests`, which returns a list); ``stage_impl``
        overrides the kernel tier per stage (exact name or prefix, same
        semantics as ``ServeConfig.stage_impl``); ``spans`` (optional
        ``SpanCollector``) records the stage spans and, with its registry,
        the per-stage counts behind the engine's ``stats["stages"]``;
        ``mesh`` (optional ``jax.sharding.Mesh``
        with ``data``/``model`` axes) runs every stage data-parallel over
        the batch with TP-sharded params — outputs stay mesh-invariant
        under the PRNG contract (see ``parallel.mesh_exec``)."""
        import jax.numpy as jnp

        return jnp.stack(self.generate_requests(
            params, tokens, key, impl=impl, max_new_tokens=max_new_tokens,
            temperature=temperature, rids=rids, stage_impl=stage_impl,
            spans=spans, mesh=mesh))

    def generate_requests(self, params, tokens, key, *, impl="auto",
                          max_new_tokens=0, temperature: float = 0.0,
                          rids=None, stage_impl: dict | None = None,
                          spans=None, mesh=None) -> list:
        """The :meth:`generate` driver, returning per-request outputs as a
        list (what the serving routes consume — per-request outputs may
        differ in length, so ``max_new_tokens`` may also be a per-request
        sequence here, e.g. heterogeneous LM decode budgets)."""
        from repro.pipeline.stage import split_state, stack_states, stage_span

        stages, impls = self._stage_plan(impl, stage_impl)
        B = int(tokens.shape[0])
        rids = list(range(B)) if rids is None else list(rids)
        if len(rids) != B:
            raise ValueError(f"got {len(rids)} rids for batch of {B}")
        mnt = (list(max_new_tokens) if np.ndim(max_new_tokens)
               else [int(max_new_tokens)] * B)
        state = stack_states([
            self.init_stage_state(tokens[i], max_new_tokens=mnt[i])
            for i in range(B)
        ])
        # mesh is forwarded only when set so that run_stage doubles (test
        # spies, minimal subclasses) keep their mesh-free signature working.
        mesh_kw = {} if mesh is None else {"mesh": mesh}
        for idx, stage in enumerate(stages):
            keys = stage_keys(key, rids, idx)
            with stage_span(spans, stage, batch=B, tier=impls[idx]):
                state = self.run_stage(
                    params, stage, state, keys,
                    impl=impls[idx], temperature=temperature, **mesh_kw)
        return [self.stage_output(s) for s in split_state(state, B)]

    def _stage_plan(self, impl: str, stage_impl: dict | None):
        """(stages, effective per-stage tiers) for one driver invocation,
        memoized per (impl, stage_impl): serving dispatches the driver once
        per pod/bucket, and rebuilding the cost descriptor (a full UNet
        topology walk for diffusion) plus re-resolving overrides every
        dispatch is pure hot-path waste — the inputs are immutable config."""
        from repro.pipeline.cascade import resolve_stage_impls
        from repro.pipeline.stage import effective_tier

        cache_key = (impl, tuple(sorted((stage_impl or {}).items())))
        cached = getattr(self, "_stage_plan_cache", None)
        if cached is not None and cached[0] == cache_key:
            return cached[1]
        stages = self.cost_descriptor().stages
        impls = [effective_tier(i)
                 for i in resolve_stage_impls(stages, impl, stage_impl)]
        plan = (stages, impls)
        self._stage_plan_cache = (cache_key, plan)
        return plan

    # -- the stage protocol (the ONLY execution path) ------------------------
    #
    # ``cost_descriptor().stages`` is not just a cost annotation: each Stage
    # is *executable* through ``run_stage``, and the default ``generate``
    # driver above composes exactly that sequence — there is no model-level
    # pipeline driver anymore.  State is a dict pytree of arrays whose
    # leading axis is the batch; the pipeline stacks/splits the per-request
    # views on axis 0, so every entry a stage stores must carry the batch
    # axis first (scalars go in as shape-() arrays, stacked to (B,)).
    # Diffusion splits base/SR stages, TTV splits keyframe/temporal denoise,
    # LM degenerates to prefill+decode — one machinery for all.

    def init_stage_state(self, tokens, *, max_new_tokens: int = 0) -> dict:
        """Per-request state entering the first pipeline stage (unbatched:
        no leading batch axis; the pipeline stacks requests per stage)."""
        import jax.numpy as jnp

        del max_new_tokens  # LM workloads keep it; pod workloads don't
        return {"tokens": jnp.asarray(tokens, jnp.int32)}

    def run_stage(self, params, stage: Stage, state: dict, key, *,
                  impl="auto", temperature: float = 0.0,
                  mesh=None) -> dict:
        """Execute one descriptor ``stage`` over batched ``state`` -> new
        batched state.  The final stage must store the result under
        ``"out"`` (or override ``stage_output``).

        ``mesh`` (optional) requests mesh-aware execution: implementations
        delegate to :func:`repro.parallel.mesh_exec.run_stage_on_mesh`,
        which shards the batch over the mesh's data axes and re-enters the
        same body under ``with mesh:`` (so TP activation constraints
        apply).  Drivers only pass the kwarg when a mesh is set, keeping
        mesh-free ``run_stage`` doubles valid.

        ``key`` is the stacked ``(B, ...)`` per-request key batch from
        :func:`stage_keys` — one key per request, folded on
        ``(seed, rid, stage_index)``.  Stages drawing noise must derive it
        per request (``jax.vmap`` over axis 0), never from the batch as a
        whole; that is the invariant that makes every serve route
        bit-identical regardless of batch composition.

        ``impl`` selects the kernel tier *for this stage* (the drivers
        resolve per-stage overrides before calling); ``temperature`` is the
        sampling temperature for token-sampling stages (0 = greedy) —
        workloads whose samplers don't take a temperature ignore it.

        This default is the eager entry of a workload whose stages are
        traceable (they read no value on the host): it runs
        :meth:`stage_body` as one jitted program, built once per (stage,
        tier, ambient mesh) on this instance and keyed by XLA on shapes, so
        a later pod of the same shape traces, lowers and compiles nothing.
        Drivers call this entry, so wrappers placed on it see concrete
        states.  While the characterization tracer is active the body runs
        eagerly: it records operator events as Python side effects of
        tracing, which a compiled-program cache hit would skip.  Workloads
        that read values on the host (LM decode) override ``run_stage``."""
        if mesh is not None:
            from repro.parallel.mesh_exec import run_stage_on_mesh

            return run_stage_on_mesh(self, params, stage, state, key,
                                     impl=impl, temperature=temperature,
                                     mesh=mesh)
        from repro.core import tracer

        del temperature  # no traceable stage takes a temperature
        if tracer.active():
            return self.stage_body(params, stage, state, key, impl=impl)
        return self._compiled_stage(stage, impl)(params, state, key)

    def stage_body(self, params, stage: Stage, state: dict, key, *,
                   impl: str) -> dict:
        """The traceable body of :meth:`run_stage`: ``(params, state, key)
        -> state`` for one ``stage`` on kernel tier ``impl``."""
        raise NotImplementedError(
            f"{type(self).__name__} implements neither run_stage nor "
            f"stage_body (stage {stage.name!r})")

    def _compiled_stage(self, stage: Stage, impl: str):
        """The jitted ``(params, state, key) -> state`` program of ``stage``
        on tier ``impl`` under the ambient mesh.  ``params`` stay an
        argument (closing over them would bake the weights into the program
        as constants); no buffer is donated, since callers keep each stage's
        input state.  Each trace of the body — a new program — counts one
        ``stage_programs/<stage>`` in the open span's registry."""
        import jax

        from repro.parallel.sharding import ambient_mesh
        from repro.telemetry.compiles import count_program

        cache_key = (stage, impl, ambient_mesh())
        fn = self._stage_programs.get(cache_key)
        if fn is None:
            def body(params, state, key):
                count_program(stage.name)
                return self.stage_body(params, stage, state, key, impl=impl)

            fn = self._stage_programs[cache_key] = jax.jit(body)
        return fn

    def stage_group_key(self, stage: Stage, state: dict):
        """Extra batch-compatibility key for ``stage`` over an unbatched
        ``state`` (beyond array shapes/dtypes) — e.g. LM decode may only
        merge requests at the same cache position.  None = shape-only."""
        return None

    def stage_output(self, state: dict):
        """Final per-request output from a completed (unbatched) state."""
        return state["out"]

    # -- characterization ----------------------------------------------------

    def trace_inputs(self):
        """Abstract (ShapeDtypeStruct) args for ``generate`` under tracing."""
        import jax
        import jax.numpy as jnp

        return (jax.ShapeDtypeStruct((1, self.max_prompt_len), jnp.int32),)

    def trace_events(self, impl: str = "auto") -> list:
        """Full-workload operator event stream, traced abstractly."""
        import jax

        from repro.core import characterize

        key = jax.random.PRNGKey(0)
        params = characterize.abstract_params(self.model)
        (toks,) = self.trace_inputs()
        return characterize.trace_workload(
            lambda p, t: self.generate(p, t, key, impl=impl), params, toks)

    def cost_descriptor(self) -> CostDescriptor:
        raise NotImplementedError


# ---------------------------------------------------------------------------
# Registry (decorator-based, keyed by config type — mirrors --arch registry)
# ---------------------------------------------------------------------------

_WORKLOADS: dict[type, type] = {}


def register_workload(*config_types) -> Callable:
    """Class decorator: ``@register_workload(DiffusionConfig)``."""

    def deco(cls):
        for t in config_types:
            _WORKLOADS[t] = cls
        return cls

    return deco


def workload_types() -> dict:
    return dict(_WORKLOADS)


def workload_for(cfg) -> GenerativeWorkload:
    """Config -> workload instance (single dispatch over the registry)."""
    for t in type(cfg).__mro__:
        if t in _WORKLOADS:
            return _WORKLOADS[t](cfg)
    raise TypeError(
        f"no GenerativeWorkload registered for {type(cfg).__name__}; "
        f"known: {sorted(t.__name__ for t in _WORKLOADS)}"
    )


def build_model(cfg):
    """Config -> model instance (back-compat for build_suite_model)."""
    return workload_for(cfg).model


def reduced_config(cfg):
    """Config -> tiny same-structure config, any modality."""
    return workload_for(cfg).reduced()


def reduced_workload(cfg) -> GenerativeWorkload:
    """Config -> workload over its reduced config (the CPU test/demo path)."""
    return workload_for(reduced_config(cfg))
