"""The benchmark's operation counts agree with the program's own analytic
tracer, operator class by operator class, at reduced configurations."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(BENCH.parent / "src"))

import spec  # noqa: E402
from counts import common  # noqa: E402

ARCHS = ["stable-diffusion", "make-a-video"]


def traced(workload, B):
    """Tracer FLOPs by class over one request's full generation."""
    import jax
    import jax.numpy as jnp

    from repro.core import characterize

    params = characterize.abstract_params(workload.model)
    toks = jax.ShapeDtypeStruct((B, workload.max_prompt_len), jnp.int32)
    key = jax.random.PRNGKey(0)
    events = characterize.trace_workload(
        lambda p, t: workload.generate(p, t, key, impl="blocked_jax"),
        params, toks)
    out = {"conv": 0.0, "tconv": 0.0, "attention": 0.0, "linear": 0.0}
    for e in events:
        if e.op == "conv":
            out["tconv" if "tconv" in e.name else "conv"] += e.total_flops
        elif e.op in out:
            out[e.op] += e.total_flops
    return out


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("B", [1, 2])
def test_counts_match_tracer(arch, B):
    import repro.configs.suite  # noqa: F401
    from repro.configs import get_config
    from repro.workload import workload_for

    w = workload_for(workload_for(get_config(arch)).reduced())
    cfg = spec.plain(w.cfg)
    fam = spec.load_module("counts", cfg["family"])
    stages = fam.stage_calls(cfg, B, w.max_prompt_len)
    got = {k: sum(rep * common.total(calls, k) for _, rep, calls in stages)
           for k in ("conv", "attention", "linear")}
    want = traced(w, B)
    assert got["attention"] == pytest.approx(want["attention"], rel=1e-12)
    assert got["linear"] == pytest.approx(want["linear"], rel=1e-12)
    # the tracer charges the temporal conv for the taps that fall on the
    # zero padding at the first and last frame; the counts do not
    pad_taps = 0.0
    if cfg["family"] == "ttv_diffusion":
        for _, rep, calls in stages:
            for c in calls:
                if c.name.startswith("tconv/"):
                    F = cfg["frames"]
                    pad_taps += rep * c.flops * 2 / (3 * F - 2)
    assert got["conv"] == pytest.approx(want["conv"] + want["tconv"] - pad_taps,
                                        rel=1e-12)


def test_full_size_work_per_request():
    """Work per request at the published widths (42.2 and 536 TFLOP)."""
    import repro.configs.suite  # noqa: F401
    from repro.configs import get_config

    tot = {}
    for arch in ARCHS:
        cfg = spec.plain(get_config(arch))
        fam = spec.load_module("counts", cfg["family"])
        tot[arch] = sum(rep * common.total(calls)
                        for _, rep, calls in fam.stage_calls(cfg, 1, 77))
    assert tot["stable-diffusion"] == pytest.approx(42.19e12, rel=1e-3)
    assert tot["make-a-video"] == pytest.approx(535.86e12, rel=1e-3)


def test_conv_bytes_are_inputs_weights_outputs_once():
    c = common.conv2d("x", 2, 8, 8, 16, 32, 3, bias=False)
    assert c.flops == 2 * 2 * 8 * 8 * 32 * 9 * 16
    assert c.bytes == 4 * (2 * 8 * 8 * 16 + 9 * 16 * 32 + 2 * 8 * 8 * 32)
    s2 = common.conv2d("x", 1, 8, 8, 16, 16, 3, stride=2, bias=False)
    assert s2.flops == 2 * 4 * 4 * 16 * 9 * 16
