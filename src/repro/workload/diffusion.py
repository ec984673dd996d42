"""Diffusion TTI workloads (Stable Diffusion / Imagen / Prod-Image).

Stage structure: text encoder -> base-UNet denoise loop -> (latent) VAE
decode or (pixel) SR-UNet cascade.  The denoise stage carries the analytic
Fig. 7 U-shape as its per-tick demand profile, which is what the
``DenoisePodScheduler`` staggers to flatten instantaneous HBM demand
(paper §V-A).
"""

from __future__ import annotations

import dataclasses

from repro.core import analytical
from repro.models.diffusion import DiffusionConfig, DiffusionPipeline, SRStage
from repro.models.text_encoder import TextEncoderConfig
from repro.workload.base import (
    CostDescriptor,
    GenerativeWorkload,
    Stage,
    register_workload,
)

REDUCED_TEXT = TextEncoderConfig(vocab=512, max_len=16, n_layers=2,
                                 d_model=64, n_heads=4, d_ff=128)


def unet_demand(latent_hw: int, unet_cfg) -> tuple:
    """Per-tick relative HBM demand over one UNet pass (Fig. 7 U-shape).

    Serving-facing: every ResBlock reads+writes its ``hw^2 x channels``
    activations (the conv traffic floor — SR UNets trade attention for
    convolution but their resolution still dominates HBM, paper C1/C6);
    attention levels pay one extra activation round trip (qkv/out).  The
    attention-only sequence-length view of the same block walk is
    ``core.analytical.unet_seq_profile`` (Fig. 7/8 characterization).
    """
    return tuple(analytical.unet_block_profile(
        latent_hw, unet_cfg.channel_mult, unet_cfg.num_res_blocks,
        unet_cfg.attn_levels,
        lambda hw, mult, attn: hw * hw * mult * (2.0 if attn else 1.0)))


@register_workload(DiffusionConfig)
class DiffusionWorkload(GenerativeWorkload):
    route = "pod"
    modality = "image"

    def build_model(self, cfg: DiffusionConfig) -> DiffusionPipeline:
        return DiffusionPipeline(cfg)

    def reduced(self) -> DiffusionConfig:
        cfg = self.cfg
        small_unet = dataclasses.replace(
            cfg.unet, model_channels=32,
            channel_mult=cfg.unet.channel_mult[:3] or (1, 2),
            num_res_blocks=1, attn_levels=(0, 1), context_dim=64,
            head_channels=8, groups=8,
        )
        sr = tuple(
            SRStage(
                out_size=cfg.image_size // 2 * 4,
                unet=dataclasses.replace(
                    s.unet, model_channels=16, channel_mult=(1, 2),
                    num_res_blocks=1, attn_levels=(), context_dim=64, groups=8,
                ),
                steps=2,
            )
            for s in cfg.sr_stages[:1]
        )
        vae = None
        if cfg.vae is not None:
            vae = dataclasses.replace(cfg.vae, base_channels=16,
                                      channel_mult=(1, 2), num_res_blocks=1,
                                      groups=8)
        return dataclasses.replace(
            cfg, name=cfg.name + "-reduced",
            image_size=32 if cfg.kind == "latent" else 16,
            latent_down=8 if cfg.kind == "latent" else 1,
            unet=small_unet, text=REDUCED_TEXT, vae=vae, sr_stages=sr,
            denoise_steps=3,
        )

    def cost_descriptor(self) -> CostDescriptor:
        cfg = self.cfg
        stages = [
            Stage("text_encoder", 1, cfg.text.max_len),
            Stage("denoise", cfg.denoise_steps, cfg.latent_size ** 2,
                  demand=unet_demand(cfg.latent_size, cfg.unet)),
        ]
        for i, s in enumerate(cfg.sr_stages):
            stages.append(Stage(f"sr{i}", s.steps, s.out_size ** 2,
                                demand=unet_demand(s.out_size, s.unet)))
        if cfg.vae is not None:
            stages.append(Stage("vae", 1, cfg.image_size ** 2))
        return CostDescriptor(arch=cfg.name, route=self.route,
                              stages=tuple(stages))

    def stage_body(self, params, stage, state, key, *, impl):
        import jax

        model, cfg = self.model, self.cfg
        if stage.name == "text_encoder":
            ctx = model.encode_text(params, state["tokens"], impl=impl)
            return {"ctx": ctx}
        if stage.name == "denoise":
            ctx = state["ctx"]
            hw = cfg.latent_size
            # per-request noise from the (seed, rid, stage) key contract:
            # batch composition can never change a request's sample
            z = jax.vmap(lambda k: jax.random.normal(
                k, (hw, hw, cfg.unet.in_channels), cfg.unet.dtype))(key)
            z = model.denoise_loop(params["unet"], model.unet, z, ctx,
                                   stage.steps, impl=impl)
            if cfg.kind == "latent":
                return {"z": z} if cfg.vae is not None else {"out": z}
            return {"ctx": ctx, "img": z}
        if stage.name.startswith("sr"):
            i = int(stage.name[2:])
            s = cfg.sr_stages[i]
            img, ctx = state["img"], state["ctx"]
            B, H, W, C = img.shape
            up = jax.image.resize(img, (B, s.out_size, s.out_size, C),
                                  "bilinear")
            noise = jax.vmap(lambda k: jax.random.normal(
                k, (s.out_size, s.out_size, 3), img.dtype))(key)
            img = model.denoise_loop(params[f"sr{i}"], model.sr_unets[i],
                                     noise, ctx, s.steps, cond=up, impl=impl)
            last = i == len(cfg.sr_stages) - 1
            return {"out": img} if last else {"ctx": ctx, "img": img}
        if stage.name == "vae":
            return {"out": model.vae(params["vae"], state["z"], impl=impl)}
        raise ValueError(f"unknown diffusion stage {stage.name!r}")

    def stage_output(self, state):
        for k in ("out", "img", "z"):
            if k in state:
                return state[k]
        raise KeyError("no output in cascade state")
