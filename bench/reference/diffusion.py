"""Reference stages of the latent diffusion family.

Each stage takes the state the served stage took (the tokens, the text
encoding the served text encoder handed on, the latent the served denoise
loop handed on) and returns what the served stage should have returned.
The denoise stage draws its own starting noise from the serve seed.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from reference import nn, text, unet, vae
from reference.noise import stage_noise

# (stage, the key of the served state it returns)
STAGES = (("text_encoder", "ctx"), ("denoise", "z"), ("vae", "out"))


@functools.lru_cache(maxsize=None)
def _programs(cfg_json: str):
    cfg = json.loads(cfg_json)
    steps = cfg["denoise_steps"]

    @functools.partial(jax.jit, static_argnums=(2,))
    def text_stage(p, tokens, dt):
        return text.encode(p["text"], tokens, cfg["text"], dt)

    @functools.partial(jax.jit, static_argnums=(3,))
    def denoise_stage(p, z, ctx, dt):
        eps = lambda z, t: unet.unet(p["unet"], z, jnp.full((z.shape[0],), t),
                                     ctx.astype(nn.dtype(dt)), cfg["unet"], dt)
        return unet.ddim(eps, z.astype(nn.dtype(dt)), steps, 0, steps)

    @functools.partial(jax.jit, static_argnums=(2,))
    def vae_stage(p, z, dt):
        return vae.decode(p["vae"], z, cfg["vae"], dt)

    return text_stage, denoise_stage, vae_stage


def stage(cfg: dict, name: str, params, state: dict, rids, serve_seed: int,
          dt):
    """Reference stage ``name`` over a batch of served input ``state``."""
    text_stage, denoise_stage, vae_stage = _programs(
        json.dumps(cfg, sort_keys=True))
    if name == "text_encoder":
        return text_stage(params, state["tokens"], dt)
    if name == "denoise":
        hw = cfg["image_size"] // cfg["latent_down"]
        shape = (hw, hw, cfg["unet"]["in_channels"])
        z = jnp.stack([stage_noise(serve_seed, r, 1, shape, jnp.float32)
                       for r in rids])
        return denoise_stage(params, z, state["ctx"], dt)
    if name == "vae":
        return vae_stage(params, state["z"], dt)
    raise ValueError(f"no reference for stage {name!r}")
