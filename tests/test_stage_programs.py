"""Compiled stage bodies (``GenerativeWorkload._compiled_stage``): each stage
of a pod-route workload runs as one jitted program, built once per (stage,
tier, ambient mesh) and keyed by XLA on shape.  A second pod of a shape
already served traces, lowers and compiles nothing; a new batch size adds
one program a stage (``stage_programs/<stage>``, ``stats["stages"][*]
["programs"]``); characterization still traces the eager body; a program
traced without a mesh is never reused under one."""

import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

import repro.configs.suite  # noqa: F401 — registers the paper suite
from repro.configs import get_config
from repro.configs.tiny import TINY_TTI_CASCADE
from repro.serving.engine import ServeConfig, ServeEngine
from repro.telemetry import MetricsRegistry, SpanCollector, validate_engine_stats
from repro.workload import reduced_workload, workload_for

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _prompt(wl, rid, n=6):
    return np.random.default_rng(rid).integers(0, wl.prompt_vocab, n)


def _counts(eng) -> dict:
    return {k: v for k, v in eng.metrics.counters().items()
            if k.startswith(("compiles/", "stage_programs/"))}


@pytest.mark.parametrize("arch", ["stable-diffusion", "make-a-video"])
def test_a_stage_compiles_once_per_batch_shape(arch):
    wl = reduced_workload(get_config(arch))
    eng = ServeEngine(wl, wl.init(jax.random.PRNGKey(0)),
                      ServeConfig(max_batch=2, pod_size=2,
                                  impl="blocked_jax"))
    names = [s.name for s in wl.cost_descriptor().stages]

    def pod(rids):
        for r in rids:
            eng.submit(r, _prompt(wl, r))
        assert set(eng.run()) == set(rids)
        return _counts(eng)

    warm = pod([0, 1])
    assert {n: warm[f"stage_programs/{n}"] for n in names} == \
        {n: 1 for n in names}
    # same shape again: no compile anywhere, no new program
    assert pod([2, 3]) == warm
    # a new batch size: exactly one more program a stage
    single = pod([4])
    assert {n: single[f"stage_programs/{n}"] for n in names} == \
        {n: 2 for n in names}
    stats = eng.stats
    validate_engine_stats(stats, "pod")
    assert {n: stats["stages"][n]["programs"] for n in names} == \
        {n: 2 for n in names}


def test_trace_events_unchanged_by_a_served_generate():
    """Characterization records operator events while tracing; a served
    call of the same shapes leaves a compiled program behind, and
    ``trace_events`` must still see every event (the tracer bypass)."""
    wl = reduced_workload(get_config("stable-diffusion"))
    before = wl.trace_events(impl="blocked_jax")
    (toks,) = wl.trace_inputs()
    wl.generate(wl.init(jax.random.PRNGKey(0)),
                np.zeros(toks.shape, toks.dtype), jax.random.PRNGKey(0),
                impl="blocked_jax")
    assert len(wl._stage_programs) == len(wl.cost_descriptor().stages)
    after = wl.trace_events(impl="blocked_jax")
    assert before and after == before


def check_mesh_programs_not_shared(n_data: int) -> None:
    """Serve a batch without a mesh, then on an ``n_data`` x 1 mesh: the
    mesh run builds its own program for every stage and matches the
    single-device output."""
    from repro.launch.mesh import make_debug_mesh

    wl = workload_for(TINY_TTI_CASCADE)
    params = wl.init(jax.random.PRNGKey(0))
    prompts = np.stack([_prompt(wl, r, 8) for r in range(4)])
    names = [s.name for s in wl.cost_descriptor().stages]
    reg = MetricsRegistry()
    spans = SpanCollector(track="t", metrics=reg)

    def programs():
        c = reg.counters()
        return {n: c.get(f"stage_programs/{n}", 0) for n in names}

    ref = np.asarray(wl.generate(params, prompts, jax.random.PRNGKey(0),
                                 spans=spans))
    assert programs() == {n: 1 for n in names}
    mesh = make_debug_mesh(n_data, 1)
    out = np.asarray(wl.generate(wl.shard_params(params, mesh), prompts,
                                 jax.random.PRNGKey(0), spans=spans,
                                 mesh=mesh))
    assert programs() == {n: 2 for n in names}
    assert {k[2] for k in wl._stage_programs} == {None, mesh}
    scale = float(np.max(np.abs(ref)))
    assert float(np.max(np.abs(ref - out))) <= 1e-5 * scale


def test_stage_program_without_mesh_not_reused_on_one_device_mesh():
    check_mesh_programs_not_shared(1)


def test_stage_program_without_mesh_not_reused_on_four_device_mesh():
    """The same check on four host devices, in a process of its own: the
    device count is fixed when JAX starts."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), str(ROOT / "tests"),
                    os.environ.get("PYTHONPATH", "")]),
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=4"))
    code = ("import jax; assert jax.device_count() == 4; "
            "import test_stage_programs as t; "
            "t.check_mesh_programs_not_shared(4)")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
