"""Convolutional image decoders: VAE (latent diffusion) and VQ-GAN (token
-> pixel for transformer TTI).  Paper Fig. 2: latent diffusion requires a
VAE/GAN decoder to map latent space back to pixels; transformer TTI models
decode image tokens through a GAN decoder."""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import tracer
from repro.kernels.conv2d import ops as conv_ops
from repro.models.layers.basic import Embedding
from repro.models.layers.conv import Conv2D, fused_gn_producer
from repro.models.layers.norms import GroupNorm
from repro.models.unet import ResBlock, Upsample
from repro.nn import Module


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    latent_channels: int = 4
    out_channels: int = 3
    base_channels: int = 128
    channel_mult: tuple = (1, 2, 4, 4)  # deepest first when decoding
    num_res_blocks: int = 2
    groups: int = 32
    dtype: Any = jnp.float32


class ConvDecoder(Module):
    """Latent (B, h, w, C_lat) -> image (B, h*2^(L-1), w*2^(L-1), 3)."""

    def __init__(self, cfg: DecoderConfig):
        self.cfg = cfg

    def _plan(self):
        cfg = self.cfg
        mults = list(reversed(cfg.channel_mult))  # start deepest
        blocks = []
        c_cur = cfg.base_channels * mults[0]
        blocks.append(("conv_in", cfg.latent_channels, c_cur))
        for li, m in enumerate(mults):
            c_out = cfg.base_channels * m
            for i in range(cfg.num_res_blocks):
                blocks.append((f"res_{li}_{i}", c_cur, c_out))
                c_cur = c_out
            if li != len(mults) - 1:
                blocks.append((f"up_{li}", c_cur, c_cur))
        blocks.append(("out", c_cur, cfg.out_channels))
        return blocks

    def _module(self, name, ci, co):
        cfg = self.cfg
        if name == "conv_in":
            return Conv2D(ci, co, 3, dtype=cfg.dtype, name="conv_in")
        if name.startswith("res"):
            # decoders have no time conditioning; reuse ResBlock with temb=0
            return ResBlock(ci, co, 4, cfg.groups, cfg.dtype)
        if name.startswith("up"):
            return Upsample(co, cfg.dtype)
        if name == "out":
            return Conv2D(ci, co, 3, dtype=cfg.dtype, name="conv_out")
        raise ValueError(name)

    def defs(self):
        d = {name: self._module(name, ci, co).defs() for name, ci, co in self._plan()}
        d["gn_out"] = GroupNorm(
            self._plan()[-1][1], min(self.cfg.groups, self._plan()[-1][1]),
            fuse_silu=True, dtype=self.cfg.dtype,
        ).defs()
        return d

    def __call__(self, params, z, *, impl="auto"):
        B = z.shape[0]
        temb = jnp.zeros((B, 4), z.dtype)
        h = z
        plan = self._plan()
        for name, ci, co in plan:
            mod = self._module(name, ci, co)
            with tracer.scope(f"decoder/{name}"):
                if name.startswith("res"):
                    h = mod(params[name], h, temb, impl=impl)
                elif name == "out":
                    if conv_ops.is_fused(impl):
                        a, b = fused_gn_producer(
                            h, params["gn_out"],
                            groups=min(self.cfg.groups, ci),
                            name="gn_out_stats")
                        h = mod(params[name], h, impl=impl, gn_affine=(a, b))
                    else:
                        h = GroupNorm(ci, min(self.cfg.groups, ci),
                                      fuse_silu=True, dtype=self.cfg.dtype)(
                                          params["gn_out"], h, impl=impl)
                        h = mod(params[name], h, impl=impl)
                else:
                    h = mod(params[name], h, impl=impl)
        return h


@dataclasses.dataclass(frozen=True)
class VQDecoderConfig:
    codebook_size: int = 8192
    token_hw: int = 16  # 16x16 image tokens
    embed_dim: int = 256
    decoder: DecoderConfig = DecoderConfig(latent_channels=256, channel_mult=(1, 1, 2, 4))
    dtype: Any = jnp.float32


class VQGANDecoder(Module):
    """Image tokens (B, token_hw^2) int32 -> pixels."""

    def __init__(self, cfg: VQDecoderConfig):
        self.cfg = cfg
        self.conv_decoder = ConvDecoder(cfg.decoder)

    def defs(self):
        c = self.cfg
        return {
            "codebook": Embedding(c.codebook_size, c.embed_dim, dtype=c.dtype).defs(),
            "decoder": self.conv_decoder.defs(),
        }

    def __call__(self, params, tokens, *, impl="auto"):
        c = self.cfg
        B = tokens.shape[0]
        z = Embedding(c.codebook_size, c.embed_dim, dtype=c.dtype)(
            params["codebook"], tokens
        )
        z = z.reshape(B, c.token_hw, c.token_hw, c.embed_dim)
        return self.conv_decoder(params["decoder"], z, impl=impl)
