"""Program spans on the wall clock and in the profiler's trace, and compile
counters per stage (``repro.telemetry.spans`` / ``repro.telemetry.compiles``):
one ``serve/pod`` span a pod and one ``serve/stage/<name>`` span a stage,
nested on the wall clock; compiles charged to the stage that caused them;
one listener however many engines; the annotations in a CPU profile; the
per-stage ``compiles`` / ``compile_s`` keys on every route."""

import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs.suite  # noqa: F401 — registers the paper suite
from repro.configs import get_config
from repro.configs.tiny import TINY_TTI_CASCADE
from repro.pipeline.stage import stage_span
from repro.serving.engine import ServeConfig, ServeEngine
from repro.telemetry import MetricsRegistry, SpanCollector, json_ready
from repro.telemetry import compiles, validate_engine_stats
from repro.telemetry.compiles import _newly_covered, stage_compiles
from repro.workload import reduced_workload, workload_for
from repro.workload.base import Stage


def _prompt(wl, seed=0, n=6):
    return np.random.default_rng(seed).integers(0, wl.prompt_vocab, n)


@pytest.fixture(scope="module")
def sd():
    wl = reduced_workload(get_config("stable-diffusion"))
    return wl, wl.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tti():
    wl = workload_for(TINY_TTI_CASCADE)
    return wl, wl.init(jax.random.PRNGKey(0))


def _serve_pod(wl, params, n=2, **cfg):
    eng = ServeEngine(wl, params, ServeConfig(max_batch=n, pod_size=n,
                                              **cfg))
    for rid in range(n):
        eng.submit(rid, _prompt(wl, rid))
    eng.run()
    return eng


def _inside(inner, outer) -> bool:
    return outer.start_s <= inner.start_s <= inner.end_s <= outer.end_s


def test_one_pod_one_pod_span_and_one_span_per_stage(sd):
    wl, params = sd
    eng = _serve_pod(wl, params)
    ev = eng.spans.events
    names = [s.name for s in wl.cost_descriptor().stages]
    [pod] = [e for e in ev if e.name == "serve/pod"]
    assert pod.args == {"batch": 2, "pod_size": 2}
    [step] = [e for e in ev if e.name == "serve/step" and _inside(pod, e)]
    for name in names:
        [st] = [e for e in ev if e.name == f"serve/stage/{name}"]
        assert st.cat == "exec" and st.lane == name
        assert st.args == {"batch": 2, "tier": "blocked_jax"}
        assert _inside(st, pod) and st.dur_s == st.end_s - st.start_s > 0
    stages = sorted((e for e in ev if e.name.startswith("serve/stage/")),
                    key=lambda e: e.start_s)
    assert [e.name for e in stages] == [f"serve/stage/{n}" for n in names]
    assert all(a.end_s <= b.start_s for a, b in zip(stages, stages[1:]))
    # the engine's own tick-only stage span is gone: one span per dispatch
    assert not [e for e in ev if e.name in names]
    # request and admission spans carry wall stamps from submit/admission
    for e in ev:
        if e.name in ("request", "admission_wait"):
            assert e.start_s <= e.end_s
    reqs = [e for e in ev if e.name == "request"]
    assert len(reqs) == 2 and all(r.end_s >= pod.end_s for r in reqs)
    assert eng.snapshot()["histograms"]["admission_wait_s"]["count"] == 2


def test_first_call_of_a_new_shape_counts_a_compile_in_its_stage():
    reg = MetricsRegistry()
    col = SpanCollector(track="t", metrics=reg)
    compiles.install()
    f = jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)
    stage = Stage("x", 1, 1)
    with stage_span(col, stage, batch=1, tier="blocked_jax"):
        f(jnp.ones((7, 13))).block_until_ready()
    first = stage_compiles(reg)["x"]
    assert first["compiles"] >= 1 and first["compile_s"] > 0
    with stage_span(col, stage, batch=1, tier="blocked_jax"):
        f(jnp.ones((7, 13))).block_until_ready()
    assert stage_compiles(reg)["x"] == first
    c = reg.counters()
    assert c["stage_dispatches/x"] == 2 and c["stage_items/x"] == 2
    # outside every program span nothing is charged
    f(jnp.ones((5, 3))).block_until_ready()
    assert stage_compiles(reg)["x"] == first and "other" not in \
        stage_compiles(reg)


def test_compile_seconds_count_nested_events_once():
    cov = []
    assert _newly_covered(cov, 2.0, 3.0) == pytest.approx(1.0)
    assert _newly_covered(cov, 4.0, 5.0) == pytest.approx(1.0)
    # an outer event enclosing both, reported last (as it ends last)
    assert _newly_covered(cov, 1.0, 6.0) == pytest.approx(3.0)
    assert _newly_covered(cov, 5.5, 7.0) == pytest.approx(1.0)
    assert cov == [[1.0, 7.0]]


def test_ten_engines_register_one_listener(tti):
    from jax._src import monitoring

    wl, params = tti
    for _ in range(10):
        ServeEngine(wl, params, ServeConfig(max_batch=2))
    listeners = monitoring.get_event_time_span_listeners()
    assert listeners.count(compiles._on_span) == 1


def test_cpu_profile_holds_the_serve_annotations(tti, tmp_path):
    from jax.profiler import ProfileData

    wl, params = tti
    with jax.profiler.trace(str(tmp_path)):
        _serve_pod(wl, params)
    [pb] = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                     recursive=True)
    names = {ev.name for p in ProfileData.from_file(pb).planes
             if p.name.startswith("/host:") for line in p.lines
             for ev in line.events}
    want = {"serve/step", "serve/pod"} | {
        f"serve/stage/{s.name}" for s in wl.cost_descriptor().stages}
    assert want <= names


@pytest.mark.parametrize("route", ["lm", "pod", "cascade"])
def test_stats_carry_per_stage_compiles_on_every_route(route, tti, rng_key):
    if route == "lm":
        wl = reduced_workload(get_config("olmo-1b"))
        eng = ServeEngine(wl, wl.init(rng_key),
                          ServeConfig(max_batch=2, buckets=(8, 16)))
        for rid in range(2):
            eng.submit(rid, _prompt(wl), max_new_tokens=3)
        eng.run()
    else:
        wl, params = tti
        eng = _serve_pod(wl, params,
                         route="cascade" if route == "cascade" else "auto")
    assert eng.route == route
    validate_engine_stats(eng.stats, route)
    stages = eng.stats["stages"]
    assert set(stages) == {s.name for s in wl.cost_descriptor().stages}
    for st in stages.values():
        assert st["dispatches"] >= 1 and st["exec_s"] > 0
        assert st["compiles"] >= 0 and st["compile_s"] >= 0.0
    if route == "lm":
        assert eng.stats["prefill_s"] == stages["prefill"]["exec_s"]
    if route == "cascade":  # one source: the stage spans' counters
        for name, st in eng.stats["cascade"]["stages"].items():
            assert (st["batches"], st["items"], st["exec_s"]) == (
                stages[name]["dispatches"], stages[name]["items"],
                stages[name]["exec_s"])
    broken = json.loads(json.dumps(json_ready(eng.stats)))
    del next(iter(broken["stages"].values()))["compiles"]
    with pytest.raises(ValueError, match="compiles"):
        validate_engine_stats(broken, route)
