"""Reference stages of the Make-A-Video family: the text encoder; the
first half of the DDIM schedule with the spatial UNet over every frame
(frames folded into the batch, the text encoding repeated per frame);
the second half with the video UNet.  The keyframe stage draws its own
starting noise from the serve seed."""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from counts.ttv_diffusion import split
from reference import nn, text, unet
from reference.noise import stage_noise

STAGES = (("text_encoder", "ctx"), ("keyframe_denoise", "z"),
          ("temporal_denoise", "out"))


@functools.lru_cache(maxsize=None)
def _programs(cfg_json: str):
    cfg = json.loads(cfg_json)
    total, (kf, _) = cfg["denoise_steps"], split(cfg["denoise_steps"])
    u, F = cfg["unet"], cfg["frames"]

    @functools.partial(jax.jit, static_argnums=(2,))
    def text_stage(p, tokens, dt):
        return text.encode(p["text"], tokens, cfg["text"], dt)

    @functools.partial(jax.jit, static_argnums=(3,))
    def keyframe_stage(p, z, ctx, dt):
        ctx_f = jnp.repeat(ctx.astype(nn.dtype(dt)), F, axis=0)

        def eps(z, t):
            B = z.shape[0]
            e = unet.unet(p["vunet"]["unet"], z.reshape(B * F, *z.shape[2:]),
                          jnp.full((B * F,), t), ctx_f, u, dt)
            return e.reshape(z.shape)

        return unet.ddim(eps, z.astype(nn.dtype(dt)), total, 0, kf)

    @functools.partial(jax.jit, static_argnums=(3,))
    def temporal_stage(p, z, ctx, dt):
        eps = lambda z, t: unet.video_unet(
            p["vunet"], z, jnp.full((z.shape[0],), t), ctx.astype(nn.dtype(dt)), u,
            cfg["temporal_head_channels"], dt)
        return unet.ddim(eps, z.astype(nn.dtype(dt)), total, kf, total)

    return text_stage, keyframe_stage, temporal_stage


def stage(cfg: dict, name: str, params, state: dict, rids, serve_seed: int,
          dt):
    """Reference stage ``name`` over a batch of served input ``state``."""
    text_stage, keyframe_stage, temporal_stage = _programs(
        json.dumps(cfg, sort_keys=True))
    if name == "text_encoder":
        return text_stage(params, state["tokens"], dt)
    if name == "keyframe_denoise":
        hw = cfg["image_size"] // cfg["latent_down"]
        shape = (cfg["frames"], hw, hw, cfg["unet"]["in_channels"])
        z = jnp.stack([stage_noise(serve_seed, r, 1, shape, jnp.float32)
                       for r in rids])
        return keyframe_stage(params, z, state["ctx"], dt)
    if name == "temporal_denoise":
        return temporal_stage(params, state["z"], state["ctx"], dt)
    raise ValueError(f"no reference for stage {name!r}")
