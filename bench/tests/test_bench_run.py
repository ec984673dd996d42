"""A whole run of the harness on the CPU at a reduced configuration, the
chip check skipped: the served outputs pass the check, and they fail it
when the timed path is broken underneath (a denoise stage that returns its
starting noise unchanged, half of a pod left out, an answer altered where
it is produced, a latent altered in the pod's last row, a stage fed
another request's state) or when the control (the reference at three
bfloat16 passes) stands in for the program."""

import dataclasses
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

import spec  # noqa: E402

PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SEED = 2**31 + 977


@pytest.fixture(scope="module", autouse=True)
def jax_config():
    """The harness points the compile cache at the checkout, caches every
    compile and sets the matmul precision; give the rest of this worker's
    tests their own."""
    import jax

    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_compilation_cache_max_size",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_default_matmul_precision")}
    yield
    for k, v in keep.items():
        jax.config.update(k, v)


# make-a-video is measured on the chip by no cell yet; its files are here,
# but no limits file, so the harness refuses it.  The CPU run holds it to
# limits of its own, far above the reduced program's readings (under 1e-6)
# and far below an unchanged denoise stage's (about 1).
VIDEO = {"name": "mav16f-c1", "config": "make-a-video", "traffic": "c1",
         "chips": 1}
VIDEO_LIMITS = {"control": "float32-high", "limits": {
    "handoff_err": 0.0, "text_encoder_err": 1e-4,
    "keyframe_denoise_err": 1e-4, "temporal_denoise_err": 1e-4}}


def reduced_cell(name, **changes):
    import repro.configs.suite  # noqa: F401
    from repro.configs import get_config
    from repro.workload import workload_for

    cell = spec.cell(name, VIDEO if name == VIDEO["name"] else None)
    pc = workload_for(get_config(cell["config"]["arch"])).reduced()
    pc = dataclasses.replace(pc, **changes)
    cell["config"] = dict(cell["config"], config=spec.plain(pc))
    return cell, pc


def run(name, fault=None, **changes):
    import harness

    cell, pc = reduced_cell(name, **changes)
    return harness.run(cell, SEED, 0.5, False, time.time(), program_cfg=pc,
                       stage_fault=fault, peaks=PEAKS)


def wrap(workload, after):
    orig = workload.run_stage

    def run_stage(params, stage, state, key, **kw):
        return after(stage, key, orig(params, stage, state, key, **kw))

    workload.run_stage = run_stage


def unchanged(workload):
    """The denoise stage hands on its starting noise, no step applied."""
    import jax

    def after(stage, key, out):
        if "denoise" not in stage.name:
            return out
        k = "z" if "z" in out else "out"
        x = out[k]
        noise = jax.vmap(lambda kk: jax.random.normal(kk, x.shape[1:],
                                                      x.dtype))(key)
        return dict(out, **{k: noise})

    wrap(workload, after)


def half_batch(workload):
    """The last stage serves half of the pod; the rest get its mean."""
    def after(stage, key, out):
        if "out" not in out:
            return out
        x = out["out"]
        h = x.shape[0] // 2
        return dict(out, out=x.at[h:].set(x[:h].mean(0)))

    wrap(workload, after)


def altered(workload):
    """One value of the first image is off by 5% of the image's range."""
    def after(stage, key, out):
        if "out" not in out:
            return out
        x = out["out"]
        return dict(out, out=x.at[0, 0, 0, 0].add(0.05 * abs(x).max()))

    wrap(workload, after)


def late_row(workload):
    """The denoise stage's last row is off by 5% of its range in one value:
    the VAE then decodes that latent faithfully."""
    def after(stage, key, out):
        if stage.name != "denoise":
            return out
        x = out["z"]
        return dict(out, z=x.at[-1, 0, 0, 0].add(0.05 * abs(x).max()))

    wrap(workload, after)


def test_sound_run_is_correct_and_reports_its_metrics():
    out = run("sd512-c4")
    assert out["correct"] is True, out["check"]
    assert out["attempted"] == 4 and out["failed"] == 0
    assert set(out["metrics"]) == {"requests_per_s", "latency_p50_s",
                                   "latency_p95_s", "setup_s"}
    assert list(out)[-1] == "check"
    for v in out["check"].values():
        assert v["value"] <= v["limit"]


@pytest.mark.parametrize("fault", [unchanged, half_batch, altered, late_row],
                         ids=lambda f: f.__name__)
def test_broken_timed_path_is_not_correct(fault):
    out = run("sd512-c4", fault)
    assert out["correct"] is False, out["check"]


def test_stage_fed_another_requests_state_is_not_correct(monkeypatch):
    """The VAE is handed the pod's latents one row out of place, above
    where the harness records it: each stage alone is right, and only the
    check of the handoffs can see it."""
    import jax.numpy as jnp

    import harness

    class Shifted(harness.Recorder):
        def __init__(self, workload, annotate):
            super().__init__(workload, annotate)
            inner = workload.run_stage

            def run_stage(params, stage, state, key, **kw):
                if stage.name == "vae":
                    state = {k: jnp.roll(v, 1, axis=0)
                             for k, v in state.items()}
                return inner(params, stage, state, key, **kw)

            workload.run_stage = run_stage

    monkeypatch.setattr(harness, "Recorder", Shifted)
    out = run("sd512-c4")
    assert out["correct"] is False
    assert out["check"]["handoff_err"]["value"] > 0
    assert out["check"]["vae_err"]["value"] < out["check"]["vae_err"]["limit"]


def test_video_cell_correct_and_its_unchanged_step_caught(monkeypatch):
    with pytest.raises(SystemExit, match="no correctness limits"):
        spec.cell(VIDEO["name"], VIDEO)
    monkeypatch.setattr(spec, "limits", lambda config: VIDEO_LIMITS)
    out = run("mav16f-c1", denoise_steps=4)
    assert out["correct"] is True, out["check"]
    assert run("mav16f-c1", unchanged, denoise_steps=4)["correct"] is False


def test_control_is_not_correct():
    import control
    import harness
    from check import verdict

    cell, pc = reduced_cell("sd512-c4")
    ctx = harness.build(cell, pc)
    try:
        r = harness.start(ctx, cell, SEED)
        win = harness.serve_window(r["engine"], r["traffic"], 1e-3,
                                   r["recorder"], annotate=False)
        args = (cell, r["params"], win, r["seeds"]["serve"])
        assert verdict(harness.check(*args), cell["limits"])[0] is True
        assert verdict(control.control_numbers(*args), cell["limits"])[0] is False
    finally:
        ctx["mon"].close()
