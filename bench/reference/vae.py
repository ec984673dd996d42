"""Reference VAE decoder: conv in, per level (deepest first) ResNet blocks
(with no time embedding: their embedding projection sees zeros) and a
nearest-neighbour upsample with a conv, GroupNorm-SiLU, conv out."""

from __future__ import annotations

import jax.numpy as jnp

from reference import nn
from reference.unet import res_block, upsample


def decode(p, z, v: dict, dt):
    g, mults = v["groups"], len(v["channel_mult"])
    zero = jnp.zeros((z.shape[0], 4), nn.dtype(dt))
    h = nn.conv(p["conv_in"], z, dt)
    for li in range(mults):
        for i in range(v["num_res_blocks"]):
            h = res_block(p[f"res_{li}_{i}"], h, zero, dt, g)
        if li != mults - 1:
            h = upsample(p[f"up_{li}"], h, dt)
    h = nn.group_norm(p["gn_out"], h, dt, g, silu=True)
    return nn.conv(p["out"], h, dt)
