"""Diffusion TTI pipelines (paper Fig. 2, top two rows).

Two systems variants, exactly as the paper taxonomizes them:
  * latent (Stable-Diffusion-like): text encoder -> UNet denoising loop in
    latent space -> VAE decoder.
  * pixel  (Imagen-like): text encoder -> base 64x64 UNet loop -> cascade of
    super-resolution UNets (which trade attention for convolution at high
    resolution — the paper's C1/C6 observation about SR networks).

The denoising loop is a ``lax.fori_loop`` over DDIM steps.  For
characterization the per-step operator events are recorded once and scaled
by the step count (every step executes the identical graph).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracer
from repro.models.text_encoder import TextEncoder, TextEncoderConfig
from repro.models.unet import UNet2D, UNetConfig
from repro.models.vae import ConvDecoder, DecoderConfig
from repro.nn import Module


# ---------------------------------------------------------------------------
# Noise schedule (DDIM over a linear-beta DDPM schedule)
# ---------------------------------------------------------------------------


def ddpm_alphas(n_train_steps: int = 1000):
    betas = jnp.linspace(1e-4, 0.02, n_train_steps, dtype=jnp.float32)
    return jnp.cumprod(1.0 - betas)


def ddim_step(x, eps, a_t, a_prev):
    """Deterministic DDIM update (eta=0)."""
    x0 = (x - jnp.sqrt(1.0 - a_t) * eps) / jnp.sqrt(a_t)
    return jnp.sqrt(a_prev) * x0 + jnp.sqrt(1.0 - a_prev) * eps


def ddim_range(eps_fn, z, total_steps, start, stop):
    """Run DDIM step indices ``[start, stop)`` of a ``total_steps`` schedule.

    ``eps_fn(z, t)`` predicts noise at (int32 scalar) train timestep ``t``.
    Splitting one denoise schedule across several calls is what lets a
    cascade stage hand a partially-denoised latent to the next stage (e.g.
    TTV keyframe denoise -> temporal refinement).  Under an active trace the
    single-step events are scaled by ``stop - start`` instead of tracing the
    loop (every step executes the identical graph).

    The schedule (``alphas``, ``ts``) is computed eagerly on the device even
    inside a jitted stage, and enters the program as constants: XLA never
    folds it on the host, where its rounding could differ.
    """
    with jax.ensure_compile_time_eval():
        alphas = ddpm_alphas()
        ts = jnp.linspace(999, 0, total_steps).astype(jnp.int32)

    if tracer.active():
        from repro.core.tracer import _traces

        tr = _traces()[-1]
        t0 = len(tr.events)
        eps = eps_fn(z, ts[start])
        for i in range(t0, len(tr.events)):
            tr.events[i] = tr.events[i].scaled(stop - start)
        return ddim_step(z, eps, alphas[ts[start]], 1.0)

    def body(i, z):
        t = ts[i]
        a_prev = jnp.where(
            i + 1 < total_steps,
            alphas[ts[jnp.minimum(i + 1, total_steps - 1)]], 1.0,
        )
        eps = eps_fn(z, t)
        return ddim_step(z, eps, alphas[t], a_prev)

    return jax.lax.fori_loop(start, stop, body, z)


# ---------------------------------------------------------------------------
# Configs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SRStage:
    """Super-resolution stage: upsample cond image, denoise at high res."""

    out_size: int
    unet: UNetConfig
    steps: int = 20


@dataclasses.dataclass(frozen=True)
class DiffusionConfig:
    name: str
    kind: str  # "latent" | "pixel"
    image_size: int
    latent_down: int  # 8 for SD; 1 for pixel models
    unet: UNetConfig
    text: TextEncoderConfig
    vae: DecoderConfig | None = None
    sr_stages: tuple = ()
    denoise_steps: int = 50
    text_len: int = 77
    family: str = "diffusion"
    source: str = ""

    @property
    def latent_size(self):
        return self.image_size // self.latent_down


# ---------------------------------------------------------------------------
# Pipeline
# ---------------------------------------------------------------------------


class DiffusionPipeline(Module):
    def __init__(self, cfg: DiffusionConfig):
        self.cfg = cfg
        self.text_encoder = TextEncoder(cfg.text)
        self.unet = UNet2D(cfg.unet)
        self.vae = ConvDecoder(cfg.vae) if cfg.vae is not None else None
        self.sr_unets = [UNet2D(s.unet) for s in cfg.sr_stages]

    def defs(self):
        d = {"text": self.text_encoder.defs(), "unet": self.unet.defs()}
        if self.vae is not None:
            d["vae"] = self.vae.defs()
        for i, sr in enumerate(self.sr_unets):
            d[f"sr{i}"] = sr.defs()
        return d

    # -- training ----------------------------------------------------------

    def train_loss(self, params, batch, key, *, impl="auto"):
        """Denoising loss on the base UNet.

        batch: {"latents": (B,h,w,C), "text": (B,L)} — for latent models the
        latents come from the (frozen) VAE encoder in the data pipeline; for
        pixel models they are 64x64 RGB images.
        """
        cfg = self.cfg
        z0 = batch["latents"].astype(jnp.float32)
        B = z0.shape[0]
        k_t, k_eps = jax.random.split(key)
        alphas = ddpm_alphas()
        t = jax.random.randint(k_t, (B,), 0, alphas.shape[0])
        a_t = alphas[t][:, None, None, None]
        eps = jax.random.normal(k_eps, z0.shape, jnp.float32)
        x_t = jnp.sqrt(a_t) * z0 + jnp.sqrt(1.0 - a_t) * eps

        ctx = self.text_encoder(params["text"], batch["text"], impl=impl)
        pred = self.unet(params["unet"], x_t.astype(cfg.unet.dtype),
                         t.astype(jnp.float32), ctx, impl=impl)
        return jnp.mean((pred.astype(jnp.float32) - eps) ** 2)

    # -- inference stage primitives (driven ONLY by the workload's
    # run_stage; the per-stage tracer scopes are emitted by the
    # GenerativeWorkload.generate driver, not here) -------------------------

    def encode_text(self, params, tokens, *, impl="auto"):
        return self.text_encoder(params["text"], tokens, impl=impl)

    def denoise_loop(self, params_unet, unet: UNet2D, z, ctx, steps, *,
                     cond=None, impl="auto", start=0, stop=None):
        """DDIM loop.  ``cond`` (SR stages: the upsampled low-res image) is
        concatenated on channels at every step but not denoised.  ``start``/
        ``stop`` select a sub-range of the ``steps``-long schedule (cascade
        stages resume a partially-denoised latent)."""

        def unet_eps(z, t_scalar):
            # channel concat pinned unsharded: conv-channel TP may shard
            # cond/z channels, and XLA miscompiles concat on a sharded axis
            from repro.parallel.sharding import concat_unsharded

            inp = z if cond is None else concat_unsharded([z, cond], axis=-1)
            return unet(params_unet, inp,
                        jnp.full((z.shape[0],), t_scalar, jnp.float32), ctx,
                        impl=impl)

        return ddim_range(unet_eps, z, steps, start,
                          steps if stop is None else stop)
