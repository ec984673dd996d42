#!/usr/bin/env python3
"""Bring-up smoke test: stable-diffusion at its published widths on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded path, on a four-chip host

One chip.  Builds full-width ``stable-diffusion`` the way
``repro.launch.serve`` does (``get_config`` -> ``workload_for`` ->
``ServeConfig`` -> ``ServeEngine``) with random weights from ``--seed``, and
serves two requests on the pod route and two on the cascade route with
``impl=auto``.  It checks that:

  * every output is a finite (512, 512, 3) image;
  * every stage ran the ``pallas`` tier, as ``engine.stats`` reports it;
  * the lowered denoise program holds Pallas kernels (``tpu_custom_call``);
  * one request's first UNet epsilon-prediction on the ``pallas`` tier
    agrees with the plain-XLA tier (``blocked_jax``) on the same chip, both
    under float32 matmul precision, within ``EPS_TOL`` of max |eps|.

Four chips (``--chips 4``).  Serves four requests on one chip, then the same
requests on ``--mesh 4x1`` (data parallel, pod route) and ``--mesh 2x2``
(tensor parallel stages, cascade route); each mesh result must match the
one-chip result within ``MESH_TOL`` of max |image|.

The last line of stdout is ``{"ok": true, "device": {...}}``.  Any failed
check raises, so the script exits non-zero without printing it.  Without a
TPU it exits non-zero before building anything.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

# pallas vs float32 XLA on one UNet call, relative to max |eps|.  Wrong
# tiling, halo or masking gives errors of order 1; rounding of the MXU
# passes on float32 operands stays well below 1e-2 after ~70 layers.
EPS_TOL = 2e-2
# A mesh result against the one-chip result, relative to max |image|: the
# same per-request math, summed in another order on the model axis.
MESH_TOL = 1e-2
ARCH = "stable-diffusion"
IMAGE_SHAPE = (512, 512, 3)


def require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: check failed: {what}")


def prompts(workload, n: int, seed: int) -> list:
    """``n`` prompts of up to 32 tokens, the first exactly 32: the pod route
    pads a pod to its longest prompt and the cascade route to the 32-token
    bucket, so both routes then encode identical inputs."""
    rng = np.random.default_rng(seed)
    top = min(32, workload.max_prompt_len)
    lens = [top] + [int(rng.integers(4, top + 1)) for _ in range(n - 1)]
    return [rng.integers(0, workload.prompt_vocab, size=k) for k in lens]


def serve(workload, params, reqs, *, route="auto", mesh=None, seed=0):
    """Serve ``reqs`` through a fresh engine; returns (outputs, stats, s)."""
    from repro.serving.engine import ServeConfig, ServeEngine

    cfg = ServeConfig(route=route, impl="auto", seed=seed, mesh=mesh,
                      pod_size=len(reqs))
    engine = ServeEngine(workload, params, cfg)
    t0 = time.perf_counter()
    for rid, toks in enumerate(reqs):
        engine.submit(rid, toks, 0)
    results = engine.run()
    wall = time.perf_counter() - t0
    outs = [np.asarray(results[rid], np.float32) for rid in range(len(reqs))]
    return outs, engine.stats, wall


def stage_tiers(stats: dict) -> dict:
    stages = stats["cascade"]["stages"] if "cascade" in stats and stats[
        "cascade"] else stats["stages"]
    return {n: (s["effective_impl"], s["exec_s"]) for n, s in stages.items()}


def check_outputs(outs, stats, label: str) -> None:
    for rid, o in enumerate(outs):
        require(o.shape == IMAGE_SHAPE, f"{label} req {rid} shape {o.shape}")
        require(bool(np.isfinite(o).all()), f"{label} req {rid} not finite")
    tiers = stage_tiers(stats)
    for name, (tier, secs) in tiers.items():
        print(f"  {label} stage {name}: tier {tier}, {secs:.3f} s")
    require(set(tiers) == {"text_encoder", "denoise", "vae"},
            f"{label} stages {sorted(tiers)}")
    require(all(t == "pallas" for t, _ in tiers.values()),
            f"{label} tiers {tiers}")


def rel_err(a, b) -> float:
    return float(np.max(np.abs(a - b)) / max(float(np.max(np.abs(b))), 1e-30))


def compare(label: str, got: list, want: list) -> None:
    errs = [rel_err(g, w) for g, w in zip(got, want)]
    same = all(np.array_equal(g, w) for g, w in zip(got, want))
    print(f"  {label}: bit-identical {same}, max rel err {max(errs):.3e} "
          f"(tolerance {MESH_TOL:.0e})")
    require(max(errs) <= MESH_TOL, f"{label} rel err {max(errs)} > {MESH_TOL}")


def eps_reference(jax, workload, params, toks, seed: int) -> None:
    """First UNet epsilon-prediction of request 0, pallas vs blocked_jax."""
    import jax.numpy as jnp

    from repro.workload.base import stage_keys

    model, cfg = workload.model, workload.cfg
    hw, c = cfg.latent_size, cfg.unet.in_channels
    tok = jnp.asarray(toks[None], jnp.int32)
    ctx = model.encode_text(params, tok, impl="auto")
    key = stage_keys(jax.random.PRNGKey(seed), [0], 1)  # denoise stage key
    z = jax.vmap(lambda k: jax.random.normal(k, (hw, hw, c)))(key)
    t = jnp.full((1,), 999.0, jnp.float32)  # first DDIM step
    outs = {}
    with jax.default_matmul_precision("float32"):
        for impl in ("pallas", "blocked_jax"):
            f = jax.jit(lambda p, z, t, ctx, impl=impl: model.unet(
                p, z, t, ctx, impl=impl))
            t0 = time.perf_counter()
            outs[impl] = np.asarray(f(params["unet"], z, t, ctx))
            print(f"  eps [{impl}]: {time.perf_counter() - t0:.2f} s "
                  f"(compile + run)")
    err = rel_err(outs["pallas"], outs["blocked_jax"])
    require(bool(np.isfinite(outs["pallas"]).all()), "pallas eps not finite")
    print(f"eps reference: pallas vs blocked_jax max rel err {err:.3e} "
          f"(tolerance {EPS_TOL:.0e})")
    require(err <= EPS_TOL, f"eps rel err {err} > {EPS_TOL}")


def denoise_has_kernels(jax, workload, params, n: int) -> None:
    import jax.numpy as jnp

    model, cfg = workload.model, workload.cfg
    hw = cfg.latent_size
    z = jnp.zeros((n, hw, hw, cfg.unet.in_channels), jnp.float32)
    ctx = jnp.zeros((n, min(32, workload.max_prompt_len), cfg.text.d_model),
                    jnp.float32)
    text = jax.jit(lambda p, z, ctx: model.denoise_loop(
        p, model.unet, z, ctx, cfg.denoise_steps, impl="auto")).lower(
            params["unet"], z, ctx).as_text()
    k = text.count("tpu_custom_call")
    print(f"lowered denoise program: {k} tpu_custom_call ops")
    require(k > 0, "denoise program holds no Pallas kernel")


def memory_line(jax) -> None:
    for d in jax.devices():
        m = d.memory_stats() or {}
        print(f"  device {d.id}: bytes_in_use {m.get('bytes_in_use')} "
              f"peak_bytes_in_use {m.get('peak_bytes_in_use')}")


def one_chip(jax, workload, params, seed: int) -> None:
    reqs = prompts(workload, 2, seed)
    denoise_has_kernels(jax, workload, params, len(reqs))
    outs = {}
    for route in ("auto", "cascade"):
        o, stats, wall = serve(workload, params, reqs, route=route, seed=seed)
        print(f"route {route}: served {len(o)} requests in {wall:.2f} s")
        check_outputs(o, stats, f"route {route}")
        outs[route] = o
    print(f"route parity pod vs cascade: max rel err "
          f"{max(rel_err(a, b) for a, b in zip(outs['cascade'], outs['auto'])):.3e}")
    eps_reference(jax, workload, params, reqs[0], seed)
    memory_line(jax)


def four_chips(jax, workload, params, seed: int) -> None:
    from repro.launch.mesh import make_debug_mesh

    reqs = prompts(workload, 4, seed)
    base, stats, wall = serve(workload, params, reqs, seed=seed)
    print(f"one chip, route auto: served {len(base)} requests in {wall:.2f} s")
    check_outputs(base, stats, "one chip")
    for (d, m), route in (((4, 1), "auto"), ((2, 2), "cascade")):
        mesh = make_debug_mesh(d, m)
        got, stats, wall = serve(workload, params, reqs, route=route,
                                 mesh=mesh, seed=seed)
        label = f"mesh {d}x{m} route {route}"
        print(f"{label}: served {len(got)} requests in {wall:.2f} s")
        check_outputs(got, stats, label)
        compare(f"{label} vs one chip", got, base)
        memory_line(jax)
        del got, stats
        gc.collect()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    import repro.configs.suite  # noqa: F401  (registers the paper suite)
    from repro.configs import get_config
    from repro.launch.cache import configure_compile_cache
    from repro.workload import workload_for

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found; JAX sees {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    cache = configure_compile_cache()
    print(f"device: {devices[0].device_kind} x{len(devices)} | "
          f"compile cache {cache}")

    t0 = time.perf_counter()
    workload = workload_for(get_config(ARCH))
    params = jax.block_until_ready(workload.init(jax.random.PRNGKey(args.seed)))
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"{ARCH}: {nbytes} param bytes, set-up {time.perf_counter() - t0:.2f} s")

    if args.chips == 4:
        four_chips(jax, workload, params, args.seed)
    else:
        one_chip(jax, workload, params, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
