"""Unified GenerativeWorkload/ServeEngine API tests: every reduced suite
model served end-to-end through one submit/run surface, plus the scheduler
views (cost descriptors, denoise-pod staggering) the engine consumes."""

import numpy as np
import pytest

import repro.configs.suite  # noqa: F401 — registers the paper suite
from repro.configs import get_config
from repro.configs.suite import SUITE
from repro.serving.engine import LMServeEngine, ServeConfig, ServeEngine
from repro.serving.scheduler import DenoisePodScheduler, Request
from repro.workload import (
    CostDescriptor,
    GenerativeWorkload,
    reduced_workload,
    workload_for,
)

N_REQ = 3


@pytest.mark.slow
@pytest.mark.parametrize("name", SUITE)
def test_serve_engine_all_suite_models_end_to_end(name, rng_key):
    """Every suite arch — LM, diffusion, AR-image, TTV — serves through the
    same ServeEngine.submit/run API on its reduced config."""
    wl = reduced_workload(get_config(name))
    params = wl.init(rng_key)
    engine = ServeEngine(wl, params,
                         ServeConfig(max_batch=2, buckets=(8, 16)))
    rng = np.random.default_rng(0)
    for rid in range(N_REQ):
        plen = int(rng.integers(4, min(wl.max_prompt_len, 12) + 1))
        prompt = rng.integers(0, wl.prompt_vocab, size=plen)
        engine.submit(rid, prompt, max_new_tokens=4)
    results = engine.run()

    assert set(results) == set(range(N_REQ))
    assert engine.stats["requests"] == N_REQ
    if wl.route == "lm":
        assert all(len(v) == 4 for v in results.values())
        assert engine.stats["tokens"] > 0
        assert engine.stats["padding_waste"]  # per served batch (§V-B)
        assert all(0.0 <= w < 1.0 for w in engine.stats["padding_waste"])
    else:
        for out in results.values():
            assert out.shape == results[0].shape  # uniform per-request shape
            assert np.all(np.isfinite(out.astype(np.float32)))
        assert engine.stats["pods"] >= 1
        profiles = engine.stats["bandwidth_profile"]  # §V-A stagger report
        assert profiles and all(p["peak_reduction"] >= 1.0 for p in profiles)


def test_workload_registry_covers_suite_and_rejects_unknown():
    for name in SUITE:
        wl = workload_for(get_config(name))
        assert isinstance(wl, GenerativeWorkload)
        cd = wl.cost_descriptor()
        assert isinstance(cd, CostDescriptor) and cd.stages
        assert cd.total_steps() >= 1
        assert wl.route in ("lm", "pod")
    with pytest.raises(TypeError, match="no GenerativeWorkload registered"):
        workload_for(object())


def test_prepare_request_is_uniform_across_modalities():
    toks = np.arange(8)
    for name, route in [("llama2-7b", "lm"), ("stable-diffusion", "pod"),
                        ("phenaki", "pod")]:
        wl = reduced_workload(get_config(name))
        req = wl.prepare_request(7, toks, max_new_tokens=5)
        assert req.rid == 7 and req.prompt_len == 8 and req.route == route
        if route == "pod":
            assert req.denoise_steps >= 1


def test_denoise_pod_stagger_reduces_peak_for_nonuniform_demand():
    """Staggering a pod over a non-uniform step-demand profile must beat the
    aligned schedule's peak (paper §V-A)."""
    wl = reduced_workload(get_config("stable-diffusion"))
    demands = wl.cost_descriptor().step_demands()
    assert len(set(demands)) > 1  # U-shape: genuinely non-uniform
    sched = DenoisePodScheduler(pod_size=4, total_steps=len(demands))
    for i in range(4):
        sched.submit(Request(rid=i, prompt_len=8, denoise_steps=len(demands)))
    pod = sched.next_pod()
    prof = DenoisePodScheduler.bandwidth_profile(demands, sched.schedule(pod))
    assert prof["peak_reduction"] > 1.0
    assert prof["staggered_peak"] < prof["aligned_peak"]


@pytest.mark.parametrize("name", SUITE)
def test_step_demands_shape_and_cascade_ordering(name):
    """CostDescriptor.step_demands across all eight suite archs: positive
    demands, tick count monotonic in stage steps, and SR-stage demand above
    base-stage demand for the cascade models (seq length grows up to 4x
    across stages, paper §IV-C)."""
    import dataclasses as dc

    cd = workload_for(get_config(name)).cost_descriptor()
    demands = cd.step_demands()
    assert demands and all(d > 0 for d in demands)

    # doubling every stage's step count never shrinks the tick count
    doubled = dc.replace(
        cd, stages=tuple(dc.replace(s, steps=s.steps * 2) for s in cd.stages))
    assert len(doubled.step_demands()) >= len(demands)

    sr = [s for s in cd.stages if s.name.startswith("sr")]
    if sr:  # cascade models: imagen's SR stages dominate the base denoiser
        base = next(s for s in cd.stages if s.name == "denoise")
        assert max(sr[0].demand) > max(base.demand)
        assert sr[0].seq_len > base.seq_len


def test_pod_scheduler_handles_pods_larger_than_total_steps():
    """Stagger offsets spread evenly instead of silently collapsing to
    stagger 1 when the pod outnumbers the denoise steps."""
    sched = DenoisePodScheduler(pod_size=6, total_steps=4)
    pod = [Request(rid=i, prompt_len=8, denoise_steps=4) for i in range(6)]
    ticks = sched.schedule(pod)
    assert len(ticks) == 4 and all(len(t) == 6 for t in ticks)
    assert all(0 <= s < 4 for t in ticks for s in t)
    # pigeonhole: multiplicity per step index stays balanced (<= ceil(6/4))
    for t in ticks:
        counts = [t.count(v) for v in set(t)]
        assert max(counts) <= 2

    with pytest.raises(ValueError, match="total_steps"):
        DenoisePodScheduler(pod_size=2, total_steps=0)


def test_pod_scheduler_pops_pods_fifo_from_deque():
    from collections import deque

    sched = DenoisePodScheduler(pod_size=2, total_steps=8)
    assert isinstance(sched.pods, deque)
    for i in range(4):
        sched.submit(Request(rid=i, prompt_len=8, denoise_steps=8))
    assert [r.rid for r in sched.next_pod()] == [0, 1]
    assert [r.rid for r in sched.next_pod()] == [2, 3]


def test_pod_scheduler_next_pod_flushes_partial():
    sched = DenoisePodScheduler(pod_size=4, total_steps=8)
    for i in range(6):  # one full pod + one partial
        sched.submit(Request(rid=i, prompt_len=8, denoise_steps=8))
    assert sched.pending() == 6
    assert [r.rid for r in sched.next_pod()] == [0, 1, 2, 3]
    assert [r.rid for r in sched.next_pod()] == [4, 5]
    assert sched.pending() == 0 and sched.next_pod() == []


def test_lm_serve_engine_backcompat_alias(rng_key):
    """Pre-unification call sites (LMServeEngine(cfg, ...)) keep working."""
    from repro.configs import reduced

    cfg = reduced(get_config("olmo-1b"))
    wl = workload_for(cfg)
    engine = LMServeEngine(cfg, wl.init(rng_key),
                           ServeConfig(max_batch=2, buckets=(8, 16)))
    engine.submit(0, np.arange(5) % cfg.vocab, 3)
    out = engine.run()
    assert len(out[0]) == 3


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_location(monkeypatch, tmp_path, from_env):
    """The persistent compile cache sits at a fixed place: the
    environment's ``JAX_COMPILATION_CACHE_DIR`` when set (JAX's own
    setting, left alone), else ``.jax_cache/`` at the checkout root."""
    from pathlib import Path

    import jax

    from repro.launch.cache import configure_compile_cache

    before = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = configure_compile_cache()
        if from_env:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            root = Path(__file__).resolve().parents[1]  # the checkout
            assert got == str(root / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
