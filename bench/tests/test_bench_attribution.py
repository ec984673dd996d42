"""Device time by program stage (``attribution.py``) on traces recorded on a
TPU v5e, and the three readers that use it and the engine's compile
counters (``window_compiles``, ``denoise_step_ms``, ``vae_image_ms``)."""

import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
DATA = BENCH / "tests" / "data"

import attribution  # noqa: E402
import devtrace  # noqa: E402
import spec  # noqa: E402
from roofline import FAMILIES  # noqa: E402

SERVE = "serve_pod_v5e"


def _load(name):
    from jax.profiler import ProfileData

    meta = json.loads((DATA / f"{name}.json").read_text())
    return meta, ProfileData.from_file(str(DATA / f"{name}.xplane.pb"))


def _kernels(ops):
    return {k: len(devtrace.matching(ops, t)) for k, t in FAMILIES.items()}


def test_small_trace_second_round_is_the_stage_the_first_is_not():
    """The small trace runs the same kernels twice inside ``engine.step``;
    only the second round is inside ``stage/vae`` (the harness's span)."""
    meta, pd = _load("small_v5e")
    t0, t1 = meta["marker_wall"], meta["end_wall"]
    [dev] = attribution.attribute(pd, t0, t0, t1, prefix="stage/").values()
    assert list(dev["stages"]) == ["vae"]
    vae, rest = dev["stages"]["vae"]["ops"], dev["unattributed"]["ops"]
    assert _kernels(vae) == {"conv": 3, "attention": 2, "groupnorm": 1}
    assert _kernels(rest) == {"conv": 3, "attention": 2, "groupnorm": 1}
    assert max(e for _, _, _, e in rest) < min(s for _, _, s, _ in vae)
    busy = devtrace.reduce(pd, t0, t0, t1)["devices"]["/device:TPU:0"]
    assert dev["busy_s"] == pytest.approx(busy["busy_s"], rel=1e-9)
    assert dev["stages"]["vae"]["busy_s"] + dev["unattributed"]["busy_s"] \
        == pytest.approx(busy["busy_s"], rel=1e-6)


def test_small_trace_worker_thread_enqueues_are_joined():
    """Eight of its programs were enqueued on the ``pjrt-tpu-tasks`` thread,
    five of them in the second round: each is put down to the stage span
    open on the Python thread when it was launched."""
    _, pd = _load("small_v5e")
    worker = {dict(ev.stats)["run_id"] for p in pd.planes
              if p.name.startswith("/host:") for line in p.lines
              if line.name.startswith("pjrt-tpu-tasks")
              for ev in line.events if ev.name == attribution.ENQUEUE}
    assert len(worker) == 8
    _, spans, by_run, _ = attribution.launches(pd, "stage/")
    starts = [s for s, _, _ in spans]
    stages = [attribution.stage_at(spans, starts, by_run[r])
              for r in sorted(worker)]
    assert stages.count("vae") == 5 and stages.count(None) == 3


def test_serve_trace_attributes_its_device_time_to_stages():
    """One pod of the reduced stable-diffusion configuration served by
    ``ServeEngine`` on a v5e: the program's own ``serve/stage/*`` spans
    hold nearly all of the device time, each stage some of it, and the
    engine's compile counters agree with JAX's own count."""
    meta, pd = _load(SERVE)
    t0, t1 = meta["start_wall"], meta["end_wall"]
    [dev] = attribution.attribute(pd, meta["marker_wall"], t0, t1).values()
    assert set(dev["stages"]) == {"text_encoder", "denoise", "vae"}
    staged = sum(s["busy_s"] for s in dev["stages"].values())
    assert staged >= 0.95 * dev["busy_s"] > 0
    busy = devtrace.reduce(pd, meta["marker_wall"], t0, t1)["devices"]
    assert dev["busy_s"] == pytest.approx(
        busy["/device:TPU:0"]["busy_s"], rel=1e-9)
    # the readers on this run give what was read on the chip, and the
    # engine's compile counters sum to JAX's own count
    import repro.configs.suite  # noqa: F401  (registers the paper suite)
    from repro.configs import get_config
    from repro.workload import workload_for

    steps = workload_for(get_config("stable-diffusion")).reduced() \
        .denoise_steps
    run = {"config": {"denoise_steps": steps}, "chips": 1,
           "pods": meta["pods"],
           "completed": meta["requests"],
           "stage_compiles": meta["stage_compiles"],
           "stage_busy": attribution.attribute(pd, meta["marker_wall"], t0,
                                               t1)}
    read = {m: spec.load_module("metrics", m).read(run)
            for m in ("window_compiles", "denoise_step_ms", "vae_image_ms")}
    assert read == pytest.approx(meta["readings"], rel=1e-9)
    assert read["window_compiles"] == meta["monitor"]["compiles"]


def _run(stage_busy, pods=2, completed=8, compiles=None):
    return {"config": {"denoise_steps": 50}, "chips": 1,
            "pods": [{"requests": completed // pods}] * pods,
            "completed": completed, "stage_busy": stage_busy,
            "stage_compiles": compiles}


def _busy(**stages):
    return {"/device:TPU:0": {"stages": {k: {"busy_s": v, "ops": []}
                                         for k, v in stages.items()},
                              "unattributed": {"busy_s": 0.0, "ops": []},
                              "busy_s": sum(stages.values())}}


@pytest.mark.parametrize("name,run,want", [
    ("denoise_step_ms", _run(_busy(denoise=17.0, vae=1.2)), 170.0),
    ("vae_image_ms", _run(_busy(denoise=17.0, vae=1.2)), 150.0),
    ("window_compiles", _run(None, compiles={
        "denoise": {"compiles": 2, "compile_s": 46.0},
        "vae": {"compiles": 68, "compile_s": 7.5},
        "other": {"compiles": 2, "compile_s": 0.1}}), 72),
    # no trace, or a stage that launched nothing: no reading
    ("denoise_step_ms", _run(None), None),
    ("vae_image_ms", _run(None), None),
    ("vae_image_ms", _run(_busy(denoise=17.0)), None),
    ("window_compiles", _run(None), None),
])
def test_readers(name, run, want):
    got = spec.load_module("metrics", name).read(run)
    assert got == (None if want is None else pytest.approx(want))
