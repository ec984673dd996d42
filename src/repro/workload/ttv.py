"""Text-to-Video workloads (Make-A-Video diffusion / Phenaki transformer).

Make-A-Video denoises a (frames x H x W) video volume: per-tick demand is
the spatial-UNet U-shape times the frame count, plus the temporal-attention
passes the paper singles out (Fig. 11: 2x time at 9x fewer FLOPs).  Phenaki
parallel-decodes a constant-length (frames x tokens) grid like Muse.
"""

from __future__ import annotations

import dataclasses

from repro.models.ttv import (
    MakeAVideoPipeline,
    PhenakiConfig,
    PhenakiModel,
    TTVConfig,
)
from repro.workload.base import (
    CostDescriptor,
    GenerativeWorkload,
    Stage,
    register_workload,
)
from repro.workload.diffusion import REDUCED_TEXT, unet_demand


@register_workload(TTVConfig)
class MakeAVideoWorkload(GenerativeWorkload):
    route = "pod"
    modality = "video"

    def build_model(self, cfg: TTVConfig) -> MakeAVideoPipeline:
        return MakeAVideoPipeline(cfg)

    def reduced(self) -> TTVConfig:
        cfg = self.cfg
        return dataclasses.replace(
            cfg, name=cfg.name + "-reduced",
            unet=dataclasses.replace(
                cfg.unet, model_channels=32, channel_mult=(1, 2),
                num_res_blocks=1, attn_levels=(0,), context_dim=64,
                head_channels=8, groups=8,
            ),
            text=REDUCED_TEXT, frames=4, image_size=16, denoise_steps=2,
            temporal_head_channels=8,
        )

    # Temporal attn/conv add one extra q/k/v/out round trip over the spatial
    # activations at every attention site — modeled as a flat traffic factor
    # on the temporal-refinement stage's demand profile.
    TEMPORAL_TRAFFIC = 1.5

    def _denoise_split(self) -> tuple[int, int]:
        """(keyframe, temporal) step counts: the cascade runs the first half
        of the DDIM schedule spatial-only (per-frame keyframe content), then
        refines with the temporal layers active (Make-A-Video's spatial->
        temporal factorization as a serving pipeline).  A 1-step schedule
        cannot be factorized — it runs as a single temporal stage so the
        cascade never executes more denoise passes than configured."""
        steps = self.cfg.denoise_steps
        if steps < 2:
            return 0, steps
        kf = steps // 2
        return kf, steps - kf

    def cost_descriptor(self) -> CostDescriptor:
        cfg = self.cfg
        hw = cfg.image_size // cfg.latent_down
        # frames fold into batch for the spatial UNet: demand scales by F
        spatial = tuple(d * cfg.frames for d in unet_demand(hw, cfg.unet))
        temporal = tuple(d * self.TEMPORAL_TRAFFIC for d in spatial)
        kf, tp = self._denoise_split()
        stages = [Stage("text_encoder", 1, cfg.text.max_len)]
        if kf:
            stages.append(Stage("keyframe_denoise", kf,
                                cfg.frames * hw * hw, demand=spatial))
        stages.append(Stage("temporal_denoise", tp, cfg.frames * hw * hw,
                            demand=temporal))
        return CostDescriptor(arch=cfg.name, route=self.route,
                              stages=tuple(stages))

    def stage_body(self, params, stage, state, key, *, impl):
        import jax
        import jax.numpy as jnp

        from repro.models.diffusion import ddim_range

        model, cfg = self.model, self.cfg

        def initial_noise(keys):
            hw = cfg.image_size // cfg.latent_down
            return jax.vmap(lambda k: jax.random.normal(
                k, (cfg.frames, hw, hw, cfg.unet.in_channels),
                cfg.dtype))(keys)

        if stage.name == "text_encoder":
            ctx = model.text_encoder(params["text"], state["tokens"],
                                     impl=impl)
            return {"ctx": ctx}

        kf, tp = self._denoise_split()
        total = kf + tp
        ctx = state["ctx"]
        if stage.name == "keyframe_denoise":
            z = initial_noise(key)

            def spatial_eps(z, t):
                # frames folded into batch; temporal layers inactive
                Bz, F, H, W, C = z.shape
                eps = model.video_unet.unet(
                    params["vunet"]["unet"], z.reshape(Bz * F, H, W, C),
                    jnp.full((Bz * F,), t, jnp.float32),
                    jnp.repeat(ctx, F, axis=0), impl=impl)
                return eps.reshape(Bz, F, H, W, C)

            z = ddim_range(spatial_eps, z, total, 0, kf)
            return {"ctx": ctx, "z": z}
        if stage.name == "temporal_denoise":
            if kf:
                z = state["z"]
            else:  # unfactorized 1-step schedule: no keyframe stage ran
                z = initial_noise(key)

            def video_eps(z, t):
                return model.video_unet(
                    params["vunet"], z,
                    jnp.full((z.shape[0],), t, jnp.float32), ctx, impl=impl)

            out = ddim_range(video_eps, z, total, kf, total)
            return {"out": out}
        raise ValueError(f"unknown TTV stage {stage.name!r}")


@register_workload(PhenakiConfig)
class PhenakiWorkload(GenerativeWorkload):
    route = "pod"
    modality = "video"

    def build_model(self, cfg: PhenakiConfig) -> PhenakiModel:
        return PhenakiModel(cfg)

    def reduced(self) -> PhenakiConfig:
        cfg = self.cfg
        return dataclasses.replace(
            cfg, name=cfg.name + "-reduced", n_layers=2, d_model=64, n_heads=4,
            d_ff=128, video_vocab=128, frames=3, tokens_per_frame=16,
            parallel_steps=3, text=REDUCED_TEXT,
        )

    def cost_descriptor(self) -> CostDescriptor:
        cfg = self.cfg
        S = cfg.frames * cfg.tokens_per_frame
        return CostDescriptor(
            arch=cfg.name, route=self.route,
            stages=(
                Stage("text_encoder", 1, cfg.text.max_len),
                Stage("parallel_decode", cfg.parallel_steps, S, demand=(S,)),
            ),
        )

    def stage_body(self, params, stage, state, key, *, impl):
        del key  # confidence-based unmasking: deterministic
        model = self.model
        if stage.name == "text_encoder":
            ctx = model.text_encoder(params["text"], state["tokens"],
                                     impl=impl)
            ctx = model._ctx_proj()(params["ctx_proj"], ctx)
            return {"ctx": ctx}
        if stage.name == "parallel_decode":
            return {"out": model.decode_tokens(params, state["ctx"],
                                               impl=impl)}
        raise ValueError(f"unknown Phenaki stage {stage.name!r}")
