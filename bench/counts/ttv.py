"""Kernel calls of the video UNet: the spatial UNet over every frame, and
after each spatial attention block a temporal attention (across frames,
one sequence per pixel) and a temporal conv (kernel 3 over frames)."""

from __future__ import annotations

from counts import common
from counts.unet import unet_calls


def temporal_calls(name, B, F, hw, c, head_channels) -> list:
    n, h = B * F * hw * hw, max(1, c // head_channels)
    calls = [common.linear(f"tattn/{name}/{w}", n, c, h * head_channels)
             for w in "qkv"]
    calls.append(common.attention(f"tattn/{name}", B * hw * hw, h, F, F,
                                  head_channels))
    calls.append(common.linear(f"tattn/{name}/out", n, h * head_channels, c))
    calls.append(common.temporal_conv(f"tconv/{name}", B, F, hw * hw, c, c, 3))
    return calls


def video_unet_calls(u: dict, B: int, F: int, hw: int, ctx_len: int,
                     head_channels: int) -> list:
    return unet_calls(u, B * F, hw, ctx_len, hook=lambda name, c, side:
                      temporal_calls(name, B, F, side, c, head_channels))
