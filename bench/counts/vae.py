"""Kernel calls of the VAE decoder: a conv in, then per level (deepest
first) ResNet blocks and a nearest-neighbour upsample with a conv, then
a GroupNorm-SiLU conv out.  Its ResNet blocks take no time embedding, so
their embedding projection sees a zero input."""

from __future__ import annotations

from counts import common
from counts.unet import res_calls


def vae_calls(v: dict, B: int, hw: int) -> list:
    mults = list(reversed(v["channel_mult"]))
    c_cur = v["base_channels"] * mults[0]
    calls = [common.conv2d("vae/conv_in", B, hw, hw, v["latent_channels"],
                           c_cur, 3)]
    for li, m in enumerate(mults):
        c_out = v["base_channels"] * m
        for i in range(v["num_res_blocks"]):
            calls += res_calls(f"vae/res_{li}_{i}", B, hw, c_cur, c_out, 4)
            c_cur = c_out
        if li != len(mults) - 1:
            hw *= 2
            calls.append(common.conv2d(f"vae/up_{li}", B, hw, hw, c_cur,
                                       c_cur, 3))
    calls.append(common.conv2d("vae/conv_out", B, hw, hw, c_cur,
                               v["out_channels"], 3, gn=True))
    return calls
