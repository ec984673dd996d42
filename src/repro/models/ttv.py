"""Text-to-Video models (paper §II-B, §VI).

* Make-A-Video-style: a diffusion VideoUNet — the spatial UNet runs with
  frames folded into batch, and **Temporal Attention + Temporal Conv layers
  are inserted after every Spatial Attention block** (paper Fig. 3/10).
  Temporal attention attends across frames: sequence length = num frames,
  batch = B * H * W — the low-arithmetic-intensity regime behind the paper's
  Fig. 11 finding (2x the execution time at 9x fewer FLOPs).

* Phenaki-style: a masked transformer over (frames x spatial) video tokens
  with factorized spatial/temporal attention, sampled by parallel decoding.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracer
from repro.kernels.flash_attention import ops as attn_ops
from repro.models.diffusion import ddpm_alphas
from repro.models.layers.basic import Dense, Embedding, nbytes
from repro.models.layers.conv import TemporalConv1D
from repro.models.layers.norms import LayerNorm
from repro.models.text_encoder import TextEncoder, TextEncoderConfig
from repro.models.transformer import Block
from repro.models.unet import UNet2D, UNetConfig, _record_pointwise
from repro.nn import Module, ParamDef, normal_init


# ---------------------------------------------------------------------------
# Temporal attention layer (paper Fig. 10)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TemporalAttention(Module):
    """Attention across the frame axis of (B, F, H, W, C) tensors.

    ``impl='pallas'/'interpret'`` uses the fused-layout kernel (the TPU
    adaptation: the (B,F,HW,H,D) tensor is tiled in place by the BlockSpec
    index_map, never permuted in HBM).  ``blocked_jax``/``naive`` use the
    conventional permute-then-attend the paper profiles on GPU.
    """

    channels: int
    head_channels: int = 64
    impl: str = "auto"
    dtype: Any = jnp.float32
    name: str = "temporal_attn"

    @property
    def n_heads(self):
        return max(1, self.channels // self.head_channels)

    def _ln(self):
        return LayerNorm(self.channels, dtype=self.dtype, name="ln")

    def _proj(self, name):
        return Dense(self.channels, self.n_heads * self.head_channels, True,
                     axes=("embed", "heads"), dtype=self.dtype, name=name)

    def _out(self):
        return Dense(self.n_heads * self.head_channels, self.channels, True,
                     axes=("heads", "embed"), dtype=self.dtype, name="out")

    def defs(self):
        return {
            "ln": self._ln().defs(),
            "wq": self._proj("wq").defs(),
            "wk": self._proj("wk").defs(),
            "wv": self._proj("wv").defs(),
            "out": self._out().defs(),
        }

    def __call__(self, params, x, *, impl=None):
        """x: (B, F, H, W, C)."""
        impl = impl or self.impl
        B, F, H, W, C = x.shape
        HW = H * W
        h = self._ln()(params["ln"], x)
        hx = h.reshape(B, F, HW, C)
        nh, hd = self.n_heads, self.head_channels
        q = self._proj("wq")(params["wq"], hx).reshape(B, F, HW, nh, hd)
        k = self._proj("wk")(params["wk"], hx).reshape(B, F, HW, nh, hd)
        v = self._proj("wv")(params["wv"], hx).reshape(B, F, HW, nh, hd)
        out = attn_ops.temporal_attention(q, k, v, impl=impl)
        if tracer.active():
            elem = tracer.dtype_bytes(x.dtype)
            flops = 4.0 * B * HW * nh * F * F * hd
            qkv_o = 4 * B * F * HW * nh * hd * elem
            fused = attn_ops._resolve(impl) in ("pallas", "interpret")
            # conventional path materializes the (B,F,HW,.) -> (B,HW,F,.)
            # permute for q/k/v and the inverse for out: 8 extra passes —
            # and those passes are F-strided in HBM, achieving a fraction of
            # peak bandwidth (the TPU analogue of the paper's Fig. 12 10x
            # L1-miss evidence).  The fused-index_map kernel avoids both.
            traffic = qkv_o + (0 if fused else 2 * qkv_o)
            tracer.record(
                "attention", self.name, flops=flops, bytes_hbm=traffic,
                seq_len=F, temporal=True, q_len=F, impl=attn_ops._resolve(impl),
                bw_efficiency=1.0 if fused else 0.5,
            )
        out = out.reshape(B, F, HW, nh * hd)
        y = self._out()(params["out"], out).reshape(B, F, H, W, C)
        return x + y


# ---------------------------------------------------------------------------
# Make-A-Video: VideoUNet = UNet2D + temporal layers via hook
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TTVConfig:
    name: str
    unet: UNetConfig
    text: TextEncoderConfig
    frames: int = 16
    image_size: int = 64
    latent_down: int = 1
    denoise_steps: int = 50
    temporal_head_channels: int = 64
    family: str = "ttv_diffusion"
    dtype: Any = jnp.float32
    source: str = ""


class VideoUNet(Module):
    """UNet2D with temporal attention + temporal conv after each spatial
    attention block (and after the mid attention)."""

    def __init__(self, cfg: TTVConfig):
        self.cfg = cfg
        self.unet = UNet2D(cfg.unet)
        # enumerate spatial-attn block names + their channel counts
        self.attn_sites: list[tuple[str, int]] = []
        plan = self.unet._plan()
        for si, blocks in enumerate(plan["down"]):
            for bi, (kind, ci, co) in enumerate(blocks):
                if kind == "attn":
                    self.attn_sites.append((f"down_{si}_{bi}_{kind}", co))
        for bi, (kind, ci, co) in enumerate(plan["mid"]):
            if kind == "attn":
                self.attn_sites.append((f"mid_{bi}_{kind}", co))
        for si, blocks in enumerate(plan["up"]):
            for bi, (kind, ci, co) in enumerate(blocks):
                if kind == "attn":
                    self.attn_sites.append((f"up_{si}_{bi}_{kind}", co))

    def _tattn(self, ch):
        return TemporalAttention(ch, self.cfg.temporal_head_channels,
                                 dtype=self.cfg.dtype)

    def _tconv(self, ch):
        return TemporalConv1D(ch, 3, dtype=self.cfg.dtype)

    def defs(self):
        d = {"unet": self.unet.defs()}
        for name, ch in self.attn_sites:
            d[f"tattn/{name}"] = self._tattn(ch).defs()
            d[f"tconv/{name}"] = self._tconv(ch).defs()
        return d

    def __call__(self, params, x, t, context, *, impl="auto"):
        """x: (B, F, H, W, C) video; t: (B,); context: (B, L, ctx)."""
        cfg = self.cfg
        B, F, H, W, C = x.shape
        x2d = x.reshape(B * F, H, W, C)
        t2d = jnp.repeat(t, F)
        ctx2d = jnp.repeat(context, F, axis=0)

        def temporal_hook(name, h, frames):
            bh, hh, wh, ch = h.shape
            hv = h.reshape(bh // frames, frames, hh, wh, ch)
            with tracer.scope(f"temporal/{name}"):
                hv = self._tattn(ch)(params[f"tattn/{name}"], hv, impl=impl)
                hv = hv + self._tconv(ch)(params[f"tconv/{name}"], hv, impl=impl)
                _record_pointwise("tconv_residual_add", hv, reads=2)
            return hv.reshape(bh, hh, wh, ch)

        out = self.unet(params["unet"], x2d, t2d, ctx2d, impl=impl,
                        temporal_hook=temporal_hook, frames=F)
        return out.reshape(B, F, H, W, C)


class MakeAVideoPipeline(Module):
    """Text -> 16-frame video via diffusion with temporal layers."""

    def __init__(self, cfg: TTVConfig):
        self.cfg = cfg
        self.text_encoder = TextEncoder(cfg.text)
        self.video_unet = VideoUNet(cfg)

    def defs(self):
        return {"text": self.text_encoder.defs(), "vunet": self.video_unet.defs()}

    def train_loss(self, params, batch, key, *, impl="auto"):
        cfg = self.cfg
        v0 = batch["video"].astype(jnp.float32)  # (B, F, H, W, C)
        B = v0.shape[0]
        k_t, k_eps = jax.random.split(key)
        alphas = ddpm_alphas()
        t = jax.random.randint(k_t, (B,), 0, alphas.shape[0])
        a_t = alphas[t][:, None, None, None, None]
        eps = jax.random.normal(k_eps, v0.shape, jnp.float32)
        x_t = jnp.sqrt(a_t) * v0 + jnp.sqrt(1.0 - a_t) * eps
        ctx = self.text_encoder(params["text"], batch["text"], impl=impl)
        pred = self.video_unet(params["vunet"], x_t.astype(cfg.dtype),
                               t.astype(jnp.float32), ctx, impl=impl)
        return jnp.mean((pred.astype(jnp.float32) - eps) ** 2)

    # Inference is driven ONLY by MakeAVideoWorkload.stage_body: the
    # factorized keyframe (spatial-only) -> temporal-refinement sampler is
    # the one sampler definition on every serve route (there is no separate
    # joint-schedule pipeline driver anymore).


# ---------------------------------------------------------------------------
# Phenaki: masked transformer over video tokens, factorized attention
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PhenakiConfig:
    name: str
    n_layers: int = 20
    d_model: int = 1536
    n_heads: int = 24
    d_ff: int = 6144
    video_vocab: int = 8192
    frames: int = 11
    tokens_per_frame: int = 256  # 16x16
    parallel_steps: int = 24
    text: TextEncoderConfig = TextEncoderConfig()
    family: str = "ttv_transformer"
    dtype: Any = jnp.float32
    source: str = ""


class PhenakiModel(Module):
    """Bidirectional transformer over (F, HW) video tokens.  Each layer:
    spatial self-attn (per frame) -> temporal self-attn (per position) ->
    cross-attn (text) -> FF.  MaskGit-style parallel decode."""

    def __init__(self, cfg: PhenakiConfig):
        self.cfg = cfg
        self.text_encoder = TextEncoder(cfg.text)
        self.head_dim = cfg.d_model // cfg.n_heads

    @property
    def mask_token(self):
        return self.cfg.video_vocab

    def _ln(self, name):
        return LayerNorm(self.cfg.d_model, dtype=self.cfg.dtype, name=name)

    def _attn(self, name, cross=False):
        from repro.models.layers.attention import Attention

        c = self.cfg
        return Attention(
            d_model=c.d_model, n_heads=c.n_heads, n_kv_heads=c.n_heads,
            head_dim=self.head_dim, causal=False, rope=False, cross=cross,
            dtype=c.dtype, name=name,
        )

    def _tattn(self):
        return TemporalAttention(self.cfg.d_model, self.head_dim, dtype=self.cfg.dtype)

    def _ctx_proj(self):
        return Dense(self.cfg.text.d_model, self.cfg.d_model, False,
                     axes=(None, "embed"), dtype=self.cfg.dtype, name="ctx_proj")

    def _ff_in(self):
        return Dense(self.cfg.d_model, self.cfg.d_ff, True,
                     axes=("embed", "mlp"), dtype=self.cfg.dtype, name="ff_in")

    def _ff_out(self):
        return Dense(self.cfg.d_ff, self.cfg.d_model, True,
                     axes=("mlp", "embed"), dtype=self.cfg.dtype, name="ff_out")

    def _layer_defs(self):
        return {
            "ln_s": self._ln("ln_s").defs(),
            "spatial": self._attn("spatial").defs(),
            "temporal": self._tattn().defs(),
            "ln_c": self._ln("ln_c").defs(),
            "cross": self._attn("cross", cross=True).defs(),
            "ln_f": self._ln("ln_f").defs(),
            "ff_in": self._ff_in().defs(),
            "ff_out": self._ff_out().defs(),
        }

    def defs(self):
        c = self.cfg
        S = c.frames * c.tokens_per_frame
        d = {
            "text": self.text_encoder.defs(),
            "ctx_proj": self._ctx_proj().defs(),
            "embed": Embedding(c.video_vocab + 1, c.d_model, dtype=c.dtype,
                               name="vid_embed").defs(),
            "pos": ParamDef((S, c.d_model), (None, "embed"), normal_init(0.01), c.dtype),
            "final_ln": self._ln("final_ln").defs(),
            "head": Dense(c.d_model, c.video_vocab, False, axes=("embed", "vocab"),
                          dtype=c.dtype, name="head").defs(),
        }
        for i in range(c.n_layers):
            d[f"layer{i}"] = self._layer_defs()
        return d

    def backbone(self, params, tokens, ctx, *, impl="auto"):
        """tokens: (B, F*HW) -> logits (B, F*HW, vocab)."""
        c = self.cfg
        B, S = tokens.shape
        F, HW = c.frames, c.tokens_per_frame
        x = Embedding(c.video_vocab + 1, c.d_model, dtype=c.dtype,
                      name="vid_embed")(params["embed"], tokens)
        x = x + params["pos"][:S].astype(x.dtype)[None]
        side = int(np.sqrt(HW))
        for i in range(c.n_layers):
            p = params[f"layer{i}"]
            with tracer.scope(f"layer{i}"):
                # spatial: attend within each frame (batch folds frames)
                h = self._ln("ln_s")(p["ln_s"], x)
                h2 = h.reshape(B * F, HW, c.d_model)
                h2 = self._attn("spatial")(p["spatial"], h2, impl=impl)
                x = x + h2.reshape(B, S, c.d_model)
                # temporal: attend across frames per spatial position
                hv = x.reshape(B, F, side, side, c.d_model)
                hv = self._tattn()(p["temporal"], hv, impl=impl)
                x = hv.reshape(B, S, c.d_model)
                # cross-attention to text
                h = self._ln("ln_c")(p["ln_c"], x)
                x = x + self._attn("cross", cross=True)(
                    p["cross"], h, context=ctx, impl=impl
                )
                # FF
                h = self._ln("ln_f")(p["ln_f"], x)
                x = x + self._ff_out()(
                    p["ff_out"], jax.nn.gelu(self._ff_in()(p["ff_in"], h))
                )
        x = self._ln("final_ln")(params["final_ln"], x)
        return Dense(c.d_model, c.video_vocab, False, axes=("embed", "vocab"),
                     dtype=c.dtype, name="head")(params["head"], x)

    def train_loss(self, params, batch, key, *, impl="auto"):
        c = self.cfg
        ctx = self.text_encoder(params["text"], batch["text"], impl=impl)
        ctx = self._ctx_proj()(params["ctx_proj"], ctx)
        tokens = batch["video_tokens"]  # (B, F*HW)
        B, S = tokens.shape
        frac = jax.random.uniform(key, (B, 1), minval=0.3, maxval=0.9)
        mask = jax.random.uniform(jax.random.fold_in(key, 1), (B, S)) < frac
        inp = jnp.where(mask, self.mask_token, tokens)
        labels = jnp.where(mask, tokens, -1)
        logits = self.backbone(params, inp, ctx, impl=impl).astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
        m = (labels >= 0).astype(jnp.float32)
        return jnp.sum((logz - ll) * m) / jnp.maximum(jnp.sum(m), 1.0)

    def decode_tokens(self, params, ctx, *, impl="auto"):
        """MaskGit-style parallel decode from a precomputed text context —
        the ``parallel_decode`` stage entry point (confidence-based
        unmasking over greedy predictions: deterministic, no PRNG)."""
        c = self.cfg
        B = ctx.shape[0]
        S = c.frames * c.tokens_per_frame
        tokens = jnp.full((B, S), self.mask_token, jnp.int32)
        steps = c.parallel_steps

        if tracer.active():
            from repro.core.tracer import _traces

            tr = _traces()[-1]
            t0 = len(tr.events)
            logits = self.backbone(params, tokens, ctx, impl=impl)
            for i in range(t0, len(tr.events)):
                tr.events[i] = tr.events[i].scaled(steps)
            return jnp.argmax(logits, -1).astype(jnp.int32)

        def body(i, tokens):
            logits = self.backbone(params, tokens, ctx, impl=impl)
            pred = jnp.argmax(logits, -1).astype(jnp.int32)
            conf = jnp.max(jax.nn.log_softmax(logits), -1)
            still = tokens == self.mask_token
            frac_keep = jnp.cos((i + 1) / steps * jnp.pi / 2)
            n_keep = (frac_keep * S).astype(jnp.int32)
            conf = jnp.where(still, conf, -jnp.inf)
            order = -jnp.sort(-conf, axis=-1)
            n_unmask = jnp.maximum(S - n_keep - jnp.sum(~still, -1), 0)
            cutoff = jnp.take_along_axis(
                order, jnp.maximum(n_unmask - 1, 0)[:, None], -1
            )
            unmask = still & (conf >= cutoff) & (n_unmask > 0)[:, None]
            return jnp.where(unmask, pred, tokens)

        tokens = jax.lax.fori_loop(0, steps, body, tokens)
        logits = self.backbone(params, tokens, ctx, impl=impl)
        pred = jnp.argmax(logits, -1).astype(jnp.int32)
        return jnp.where(tokens == self.mask_token, pred, tokens)
