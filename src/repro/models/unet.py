"""Diffusion UNet (SD / Imagen style) — the paper's Fig. 3 left diagram.

Alternating ResNet blocks (GroupNorm -> SiLU -> Conv3x3, time-embedding
injection) and attention blocks (spatial Self-Attention over HW tokens +
Cross-Attention to the text encoding) across a downsample/upsample pyramid.
The per-level spatial size is what drives the paper's §V sequence-length
profile: seq = (H_L * W_L) / d^(2*level), the U-shaped Fig. 7 curve.

Layout is NHWC throughout (TPU conv-native).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracer
from repro.kernels.conv2d import ops as conv_ops
from repro.models.layers.attention import Attention
from repro.models.layers.basic import Dense, nbytes, sinusoidal_embedding
from repro.models.layers.conv import Conv2D, fused_gn_producer
from repro.models.layers.norms import GroupNorm, LayerNorm
from repro.nn import Module


def _record_pointwise(name, x, reads=1):
    """Standalone elementwise op (unfused epilogue): reads + one write."""
    if not tracer.active():
        return
    n = int(np.prod(x.shape)) * tracer.dtype_bytes(x.dtype)
    tracer.record("pointwise", name, flops=float(np.prod(x.shape)),
                  bytes_hbm=(reads + 1) * n)


@dataclasses.dataclass(frozen=True)
class UNetConfig:
    in_channels: int = 4
    out_channels: int = 4
    model_channels: int = 320
    channel_mult: tuple = (1, 2, 4, 4)
    num_res_blocks: int = 2
    attn_levels: tuple = (0, 1, 2)  # pyramid levels with attention blocks
    cross_attn: bool = True
    context_dim: int = 768
    head_channels: int = 8  # per-head channels (paper Table I: SD=8, Imagen=64)
    n_heads: int = 0  # if set, fixed head count (SD-style: head_dim = C/heads)
    tf_depth: int = 1
    groups: int = 32
    dtype: Any = jnp.float32

    @property
    def temb_dim(self):
        return self.model_channels * 4


@dataclasses.dataclass(frozen=True)
class ResBlock(Module):
    c_in: int
    c_out: int
    temb_dim: int
    groups: int = 32
    dtype: Any = jnp.float32

    def _gn1(self):
        return GroupNorm(self.c_in, min(self.groups, self.c_in), fuse_silu=True,
                         dtype=self.dtype, name="gn1")

    def _gn2(self):
        return GroupNorm(self.c_out, min(self.groups, self.c_out), fuse_silu=True,
                         dtype=self.dtype, name="gn2")

    def _conv1(self):
        return Conv2D(self.c_in, self.c_out, 3, dtype=self.dtype, name="conv1")

    def _conv2(self):
        return Conv2D(self.c_out, self.c_out, 3, dtype=self.dtype, name="conv2")

    def _temb(self):
        return Dense(self.temb_dim, self.c_out, True, axes=(None, "conv_out"),
                     dtype=self.dtype, name="temb_proj")

    def _skip(self):
        return Conv2D(self.c_in, self.c_out, 1, dtype=self.dtype, name="skip")

    def defs(self):
        d = {
            "gn1": self._gn1().defs(), "conv1": self._conv1().defs(),
            "temb": self._temb().defs(),
            "gn2": self._gn2().defs(), "conv2": self._conv2().defs(),
        }
        if self.c_in != self.c_out:
            d["skip"] = self._skip().defs()
        return d

    def __call__(self, params, x, temb, *, impl="auto"):
        t = self._temb()(params["temb"], jax.nn.silu(temb))
        if conv_ops.is_fused(impl):
            # Fused path: gn1 -> conv1 -> (+temb) -> gn2 -> conv2 -> (+skip)
            # in two conv passes.  gn1 collapses to a per-(batch, channel)
            # affine applied inside conv1 (one stats read over x); conv1
            # emits gn2's channel statistics alongside its output, so gn2
            # costs no activation read at all; conv2 applies gn2's affine to
            # its input blocks and adds the residual in its epilogue.
            a1, b1 = fused_gn_producer(
                x, params["gn1"], groups=min(self.groups, self.c_in),
                name="gn1_stats")
            skip = (x if self.c_in == self.c_out
                    else self._skip()(params["skip"], x, impl=impl))
            h, stats = self._conv1()(
                params["conv1"], x, impl=impl, gn_affine=(a1, b1),
                temb=t.astype(jnp.float32), emit_stats=True)
            a2, b2 = conv_ops.affine_from_stats(
                stats, params["gn2"]["scale"], params["gn2"]["bias"],
                groups=min(self.groups, self.c_out),
                count=h.shape[1] * h.shape[2])
            return self._conv2()(
                params["conv2"], h, impl=impl, gn_affine=(a2, b2),
                residual=skip)
        h = self._gn1()(params["gn1"], x, impl=impl)
        h = self._conv1()(params["conv1"], h, impl=impl)
        h = h + t[:, None, None, :].astype(h.dtype)
        _record_pointwise("temb_add", h)
        h = self._gn2()(params["gn2"], h, impl=impl)
        h = self._conv2()(params["conv2"], h, impl=impl)
        skip = x if self.c_in == self.c_out else self._skip()(params["skip"], x, impl=impl)
        _record_pointwise("residual_add", h, reads=2)
        return skip + h


@dataclasses.dataclass(frozen=True)
class SpatialTransformer(Module):
    """norm -> proj_in -> depth x (self-attn, cross-attn, GeGLU-FF) -> proj_out."""

    channels: int
    head_channels: int
    context_dim: int
    cross: bool = True
    depth: int = 1
    groups: int = 32
    fixed_heads: int = 0  # if set, n_heads is fixed and head_dim = C/heads
    dtype: Any = jnp.float32

    @property
    def n_heads(self):
        if self.fixed_heads:
            return self.fixed_heads
        return max(1, self.channels // self.head_channels)

    @property
    def head_dim(self):
        return self.channels // self.n_heads

    def _gn(self):
        return GroupNorm(self.channels, min(self.groups, self.channels),
                         dtype=self.dtype, name="gn")

    def _proj(self, name):
        return Dense(self.channels, self.channels, True,
                     axes=("embed", "embed2"), dtype=self.dtype, name=name)

    def _ln(self, name):
        return LayerNorm(self.channels, dtype=self.dtype, name=name)

    def _self_attn(self):
        return Attention(
            d_model=self.channels, n_heads=self.n_heads, n_kv_heads=self.n_heads,
            head_dim=self.head_dim, causal=False, rope=False,
            dtype=self.dtype, name="self_attn",
        )

    def _cross_attn(self):
        a = Attention(
            d_model=self.channels, n_heads=self.n_heads, n_kv_heads=self.n_heads,
            head_dim=self.head_dim, causal=False, rope=False, cross=True,
            dtype=self.dtype, name="cross_attn",
        )
        return a

    def _ctx_proj(self):
        return Dense(self.context_dim, self.channels, False,
                     axes=(None, "embed"), dtype=self.dtype, name="ctx_proj")

    def _ff_in(self):
        return Dense(self.channels, 4 * self.channels, True,
                     axes=("embed", "mlp"), dtype=self.dtype, name="ff_in")

    def _ff_gate(self):
        return Dense(self.channels, 4 * self.channels, True,
                     axes=("embed", "mlp"), dtype=self.dtype, name="ff_gate")

    def _ff_out(self):
        return Dense(4 * self.channels, self.channels, True,
                     axes=("mlp", "embed"), dtype=self.dtype, name="ff_out")

    def defs(self):
        layer = {
            "ln1": self._ln("ln1").defs(),
            "self_attn": self._self_attn().defs(),
            "ln3": self._ln("ln3").defs(),
            "ff_in": self._ff_in().defs(),
            "ff_gate": self._ff_gate().defs(),
            "ff_out": self._ff_out().defs(),
        }
        if self.cross:
            layer["ln2"] = self._ln("ln2").defs()
            layer["cross_attn"] = self._cross_attn().defs()
        d = {
            "gn": self._gn().defs(),
            "proj_in": self._proj("proj_in").defs(),
            "proj_out": self._proj("proj_out").defs(),
            "ctx_proj": self._ctx_proj().defs() if self.cross else {},
        }
        for i in range(self.depth):
            d[f"layer{i}"] = layer if i == 0 else dict(layer)
        return d

    def __call__(self, params, x, context=None, *, impl="auto"):
        B, H, W, C = x.shape
        res = x
        h = self._gn()(params["gn"], x, impl=impl)
        tokens = h.reshape(B, H * W, C)
        tokens = self._proj("proj_in")(params["proj_in"], tokens)
        ctx = None
        if self.cross and context is not None:
            ctx = self._ctx_proj()(params["ctx_proj"], context)
        for i in range(self.depth):
            p = params[f"layer{i}"]
            t = self._ln("ln1")(p["ln1"], tokens)
            tokens = tokens + self._self_attn()(p["self_attn"], t, impl=impl)
            if self.cross and ctx is not None:
                t = self._ln("ln2")(p["ln2"], tokens)
                tokens = tokens + self._cross_attn()(
                    p["cross_attn"], t, context=ctx, impl=impl
                )
            t = self._ln("ln3")(p["ln3"], tokens)
            ff = jax.nn.gelu(self._ff_gate()(p["ff_gate"], t)) * self._ff_in()(p["ff_in"], t)
            tokens = tokens + self._ff_out()(p["ff_out"], ff)
        h = self._proj("proj_out")(params["proj_out"], tokens).reshape(B, H, W, C)
        return res + h


@dataclasses.dataclass(frozen=True)
class Downsample(Module):
    channels: int
    dtype: Any = jnp.float32

    def _conv(self):
        return Conv2D(self.channels, self.channels, 3, stride=2,
                      dtype=self.dtype, name="down")

    def defs(self):
        return {"conv": self._conv().defs()}

    def __call__(self, params, x, *, impl="auto"):
        return self._conv()(params["conv"], x, impl=impl)


@dataclasses.dataclass(frozen=True)
class Upsample(Module):
    channels: int
    dtype: Any = jnp.float32

    def _conv(self):
        return Conv2D(self.channels, self.channels, 3, dtype=self.dtype, name="up")

    def defs(self):
        return {"conv": self._conv().defs()}

    def __call__(self, params, x, *, impl="auto"):
        B, H, W, C = x.shape
        small = x
        x = jax.image.resize(x, (B, 2 * H, 2 * W, C), "nearest")
        if tracer.active():
            # the nearest-resize materializes the 4x tensor before the conv
            # reads it back — real HBM traffic the breakdown must count.
            tracer.record(
                "pointwise", "upsample_resize", flops=0.0,
                bytes_hbm=nbytes((small.shape, small.dtype), (x.shape, x.dtype)),
            )
        return self._conv()(params["conv"], x, impl=impl)


class UNet2D(Module):
    """Full UNet; optionally extended with temporal layers by VideoUNet."""

    def __init__(self, cfg: UNetConfig):
        self.cfg = cfg

    # -- plan: static structure description used by defs() and __call__ ------

    def _plan(self):
        cfg = self.cfg
        ch = cfg.model_channels
        plan = {"down": [], "mid": None, "up": []}
        c_cur = ch
        skip_chans = [ch]
        for level, mult in enumerate(cfg.channel_mult):
            c_out = ch * mult
            blocks = []
            for i in range(cfg.num_res_blocks):
                blocks.append(("res", c_cur, c_out))
                c_cur = c_out
                if level in cfg.attn_levels:
                    blocks.append(("attn", c_cur, c_cur))
                skip_chans.append(c_cur)
            is_last = level == len(cfg.channel_mult) - 1
            if not is_last:
                blocks.append(("down", c_cur, c_cur))
                skip_chans.append(c_cur)
            plan["down"].append(blocks)
        plan["mid"] = [("res", c_cur, c_cur), ("attn", c_cur, c_cur), ("res", c_cur, c_cur)]
        for level in reversed(range(len(cfg.channel_mult))):
            c_out = ch * cfg.channel_mult[level]
            blocks = []
            for i in range(cfg.num_res_blocks + 1):
                c_skip = skip_chans.pop()
                blocks.append(("res", c_cur + c_skip, c_out))
                c_cur = c_out
                if level in cfg.attn_levels:
                    blocks.append(("attn", c_cur, c_cur))
            if level != 0:
                blocks.append(("up", c_cur, c_cur))
            plan["up"].append(blocks)
        return plan

    def _module(self, kind, c_in, c_out):
        cfg = self.cfg
        if kind == "res":
            return ResBlock(c_in, c_out, cfg.temb_dim, cfg.groups, cfg.dtype)
        if kind == "attn":
            return SpatialTransformer(
                c_out, cfg.head_channels, cfg.context_dim,
                cross=cfg.cross_attn, depth=cfg.tf_depth,
                groups=cfg.groups, fixed_heads=cfg.n_heads, dtype=cfg.dtype,
            )
        if kind == "down":
            return Downsample(c_out, cfg.dtype)
        if kind == "up":
            return Upsample(c_out, cfg.dtype)
        raise ValueError(kind)

    def defs(self):
        cfg = self.cfg
        plan = self._plan()
        d = {
            "conv_in": Conv2D(cfg.in_channels, cfg.model_channels, 3,
                              dtype=cfg.dtype, name="conv_in").defs(),
            "temb1": Dense(cfg.model_channels, cfg.temb_dim, True,
                           axes=(None, "mlp"), dtype=cfg.dtype).defs(),
            "temb2": Dense(cfg.temb_dim, cfg.temb_dim, True,
                           axes=("mlp", "mlp2"), dtype=cfg.dtype).defs(),
            "gn_out": GroupNorm(cfg.model_channels,
                                min(cfg.groups, cfg.model_channels),
                                fuse_silu=True, dtype=cfg.dtype).defs(),
            "conv_out": Conv2D(cfg.model_channels, cfg.out_channels, 3,
                               dtype=cfg.dtype, name="conv_out").defs(),
        }
        for si, blocks in enumerate(plan["down"]):
            for bi, (kind, ci, co) in enumerate(blocks):
                d[f"down_{si}_{bi}_{kind}"] = self._module(kind, ci, co).defs()
        for bi, (kind, ci, co) in enumerate(plan["mid"]):
            d[f"mid_{bi}_{kind}"] = self._module(kind, ci, co).defs()
        for si, blocks in enumerate(plan["up"]):
            for bi, (kind, ci, co) in enumerate(blocks):
                d[f"up_{si}_{bi}_{kind}"] = self._module(kind, ci, co).defs()
        return d

    def __call__(self, params, x, t, context=None, *, impl="auto",
                 temporal_hook=None, frames: int = 1):
        """x: (B, H, W, C_in); t: (B,) timesteps; context: (B, L, ctx_dim).

        ``temporal_hook(name, h, frames)`` lets VideoUNet interleave temporal
        attention/conv after every spatial attention block (paper §VI).
        """
        cfg = self.cfg
        plan = self._plan()
        temb = sinusoidal_embedding(t, cfg.model_channels)
        temb = Dense(cfg.model_channels, cfg.temb_dim, True, axes=(None, "mlp"),
                     dtype=cfg.dtype)(params["temb1"], temb)
        temb = Dense(cfg.temb_dim, cfg.temb_dim, True, axes=("mlp", "mlp2"),
                     dtype=cfg.dtype)(params["temb2"], jax.nn.silu(temb))

        h = Conv2D(cfg.in_channels, cfg.model_channels, 3, dtype=cfg.dtype,
                   name="conv_in")(params["conv_in"], x, impl=impl)
        skips = [h]

        def run_block(name, kind, ci, co, h):
            mod = self._module(kind, ci, co)
            with tracer.scope(name):
                if kind == "res":
                    h = mod(params[name], h, temb, impl=impl)
                elif kind == "attn":
                    h = mod(params[name], h, context, impl=impl)
                    if temporal_hook is not None:
                        h = temporal_hook(name, h, frames)
                else:
                    h = mod(params[name], h, impl=impl)
            return h

        for si, blocks in enumerate(plan["down"]):
            for bi, (kind, ci, co) in enumerate(blocks):
                h = run_block(f"down_{si}_{bi}_{kind}", kind, ci, co, h)
                if kind in ("res", "down") or (kind == "attn"):
                    if kind != "attn":
                        skips.append(h)
                    else:
                        skips[-1] = h  # attn refines the last skip
        for bi, (kind, ci, co) in enumerate(plan["mid"]):
            h = run_block(f"mid_{bi}_{kind}", kind, ci, co, h)
        # Skip concat goes through concat_unsharded: under conv-channel TP
        # the skip tensor arrives model-sharded on channels, and XLA's CPU
        # backend miscompiles concatenate along a sharded axis (silently
        # wrong values).  See repro.parallel.sharding.concat_unsharded.
        from repro.parallel.sharding import concat_unsharded

        for si, blocks in enumerate(plan["up"]):
            for bi, (kind, ci, co) in enumerate(blocks):
                if kind == "res":
                    h = concat_unsharded([h, skips.pop()], axis=-1)
                h = run_block(f"up_{si}_{bi}_{kind}", kind, ci, co, h)

        conv_out = Conv2D(cfg.model_channels, cfg.out_channels, 3,
                          dtype=cfg.dtype, name="conv_out")
        if conv_ops.is_fused(impl):
            a, b = fused_gn_producer(
                h, params["gn_out"],
                groups=min(cfg.groups, cfg.model_channels),
                name="gn_out_stats")
            return conv_out(params["conv_out"], h, impl=impl, gn_affine=(a, b))
        h = GroupNorm(cfg.model_channels, min(cfg.groups, cfg.model_channels),
                      fuse_silu=True, dtype=cfg.dtype)(params["gn_out"], h,
                                                       impl=impl)
        return conv_out(params["conv_out"], h, impl=impl)
