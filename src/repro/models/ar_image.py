"""Transformer-based TTI models (paper Fig. 2 bottom row; Fig. 3 right).

Two decode disciplines, matching the paper's Table III mapping:
  * Parti-style: encoder-decoder, image tokens predicted autoregressively —
    the LLM-*Decode*-like regime.  Sequence length grows linearly over
    inference (paper Fig. 7, Parti panel).
  * Muse-style: decoder-only masked transformer with *parallel decoding* —
    constant sequence length across the (few) unmasking steps (Fig. 7, Muse
    panel).

Both condition on a text encoder through cross-attention and map final image
tokens to pixels through a VQ-GAN decoder.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import LMConfig
from repro.core import tracer
from repro.models.layers.attention import AttentionCache
from repro.models.layers.basic import Dense, Embedding, nbytes
from repro.models.layers.norms import LayerNorm
from repro.models.text_encoder import TextEncoder, TextEncoderConfig
from repro.models.transformer import Block
from repro.models.vae import VQDecoderConfig, VQGANDecoder
from repro.nn import Module, ParamDef, normal_init, init_defs


@dataclasses.dataclass(frozen=True)
class ARImageConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    d_ff: int
    image_vocab: int = 8192
    image_tokens: int = 1024  # 32x32 grid
    decode: str = "ar"  # "ar" (Parti) | "parallel" (Muse)
    parallel_steps: int = 12
    text: TextEncoderConfig = TextEncoderConfig()
    vq: VQDecoderConfig = VQDecoderConfig()
    family: str = "transformer_tti"
    dtype: Any = jnp.float32
    source: str = ""

    def lm_config(self) -> LMConfig:
        """Internal LMConfig used to build the image-transformer blocks."""
        return LMConfig(
            name=self.name + "-img",
            family="dense",
            n_layers=self.n_layers,
            d_model=self.d_model,
            n_heads=self.n_heads,
            n_kv_heads=self.n_heads,
            d_ff=self.d_ff,
            vocab=self.image_vocab + 1,  # +1: mask token (Muse)
            norm="layernorm",
            mlp_activation="gelu",
            mlp_gated=False,
            dtype=self.dtype,
        )


class ARImageModel(Module):
    def __init__(self, cfg: ARImageConfig):
        self.cfg = cfg
        self.lm_cfg = cfg.lm_config()
        self.text_encoder = TextEncoder(cfg.text)
        self.vq = VQGANDecoder(cfg.vq)
        causal = cfg.decode == "ar"
        self.block = Block(self.lm_cfg, "dense", causal=causal, with_cross=True)

    @property
    def mask_token(self):
        return self.cfg.image_vocab  # last id

    def _embed(self):
        return Embedding(self.cfg.image_vocab + 1, self.cfg.d_model,
                         dtype=self.cfg.dtype, name="img_embed")

    def _head(self):
        return Dense(self.cfg.d_model, self.cfg.image_vocab, False,
                     axes=("embed", "vocab"), dtype=self.cfg.dtype, name="head")

    def _ctx_proj(self):
        return Dense(self.cfg.text.d_model, self.cfg.d_model, False,
                     axes=(None, "embed"), dtype=self.cfg.dtype, name="ctx_proj")

    def _final_ln(self):
        return LayerNorm(self.cfg.d_model, dtype=self.cfg.dtype, name="final_ln")

    def defs(self):
        c = self.cfg
        d = {
            "text": self.text_encoder.defs(),
            "ctx_proj": self._ctx_proj().defs(),
            "embed": self._embed().defs(),
            "pos": ParamDef((c.image_tokens, c.d_model), (None, "embed"),
                            normal_init(0.01), c.dtype),
            "final_ln": self._final_ln().defs(),
            "head": self._head().defs(),
            "vq": self.vq.defs(),
        }
        for i in range(c.n_layers):
            d[f"layer{i}"] = self.block.defs()
        return d

    # -- shared forward over image tokens -----------------------------------

    def backbone(self, params, tokens, ctx, *, impl="auto"):
        c = self.cfg
        B, S = tokens.shape
        x = self._embed()(params["embed"], tokens)
        x = x + params["pos"][:S].astype(x.dtype)[None]
        for i in range(c.n_layers):
            with tracer.scope(f"layer{i}"):
                x, _, _ = self.block(params[f"layer{i}"], x, positions=None,
                                     context=ctx, impl=impl)
        x = self._final_ln()(params["final_ln"], x)
        return self._head()(params["head"], x)

    # -- training (next-token AR or masked modeling) -------------------------

    def train_loss(self, params, batch, key, *, impl="auto"):
        c = self.cfg
        ctx = self.text_encoder(params["text"], batch["text"], impl=impl)
        ctx = self._ctx_proj()(params["ctx_proj"], ctx)
        tokens = batch["image_tokens"]  # (B, S) int32
        B, S = tokens.shape
        if c.decode == "ar":
            inp = jnp.pad(tokens[:, :-1], [(0, 0), (1, 0)])  # BOS=0 shift
            labels = tokens
        else:
            # Muse: mask a random fraction, predict masked positions
            frac = jax.random.uniform(key, (B, 1), minval=0.2, maxval=0.9)
            mask = jax.random.uniform(jax.random.fold_in(key, 1), (B, S)) < frac
            inp = jnp.where(mask, self.mask_token, tokens)
            labels = jnp.where(mask, tokens, -1)  # only masked count
        logits = self.backbone(params, inp, ctx, impl=impl).astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
        m = (labels >= 0).astype(jnp.float32)
        return jnp.sum((logz - ll) * m) / jnp.maximum(jnp.sum(m), 1.0)

    # -- decode-loop primitives (driven ONLY by ARImageWorkload.stage_body) -

    def decode_parallel(self, params, ctx, *, impl="auto"):
        """Muse parallel decoding: iterative unmasking with a cosine schedule.
        Every step runs the full (constant-length) sequence — the paper's
        Fig. 7 'Muse' flat profile.  Confidence-based unmasking over greedy
        predictions is deterministic: no PRNG enters the loop."""
        c = self.cfg
        B = ctx.shape[0]
        S = c.image_tokens
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
            still_masked = tokens == self.mask_token
            # unmask the top fraction by confidence following cos schedule
            frac_keep_masked = jnp.cos((i + 1) / steps * jnp.pi / 2)
            n_keep = (frac_keep_masked * S).astype(jnp.int32)
            conf = jnp.where(still_masked, conf, -jnp.inf)
            thresh = -jnp.sort(-conf, axis=-1)  # descending
            n_unmask = jnp.maximum(S - n_keep - jnp.sum(~still_masked, -1), 0)
            cutoff = jnp.take_along_axis(
                thresh, jnp.maximum(n_unmask - 1, 0)[:, None], axis=-1
            )
            unmask = still_masked & (conf >= cutoff) & (n_unmask > 0)[:, None]
            return jnp.where(unmask, pred, tokens)

        tokens = jax.lax.fori_loop(0, steps, body, tokens)
        # any residual masks -> argmax fill
        logits = self.backbone(params, tokens, ctx, impl=impl)
        pred = jnp.argmax(logits, -1).astype(jnp.int32)
        return jnp.where(tokens == self.mask_token, pred, tokens)

    def decode_ar(self, params, ctx, *, impl="auto"):
        """Parti autoregressive decoding with a KV cache (LLM-Decode-like)."""
        c = self.cfg
        B = ctx.shape[0]
        S = c.image_tokens
        caches = [
            {"attn": self.block._attn().init_cache(B, S, dtype=c.dtype)}
            for _ in range(c.n_layers)
        ]
        cross = [
            AttentionCache(
                k=self.block._cross_attn()._split_heads(
                    self.block._cross_attn()._wk()(
                        params[f"layer{i}"]["cross_attn"]["wk"], ctx
                    ),
                    c.n_heads,
                ),
                v=self.block._cross_attn()._split_heads(
                    self.block._cross_attn()._wv()(
                        params[f"layer{i}"]["cross_attn"]["wv"], ctx
                    ),
                    c.n_heads,
                ),
            )
            for i in range(c.n_layers)
        ]

        def step(carry, t):
            tokens, caches = carry
            # BOS (=0) at t=0, else the previously generated token
            prev = jnp.where(
                t == 0,
                jnp.zeros((B, 1), jnp.int32),
                jax.lax.dynamic_slice_in_dim(tokens, jnp.maximum(t - 1, 0), 1, 1),
            )
            x = self._embed()(params["embed"], prev)
            x = x + jax.lax.dynamic_slice_in_dim(
                params["pos"], jnp.maximum(t - 1, 0), 1, 0
            ).astype(x.dtype)[None]
            new_caches = []
            for i in range(c.n_layers):
                x, st = self.block.decode(
                    params[f"layer{i}"], x, caches[i], t, cross_cache=cross[i]
                )
                new_caches.append(st)
            x = self._final_ln()(params["final_ln"], x)
            logits = self._head()(params["head"], x)[:, 0]
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            tokens = jax.lax.dynamic_update_slice(tokens, nxt[:, None], (0, t))
            return (tokens, new_caches), None

        tokens0 = jnp.zeros((B, S), jnp.int32)
        if tracer.active():
            # trace a handful of representative steps; core.seq_profile does
            # the per-step profiling with sliced caches
            (tokens, _), _ = step((tokens0, caches), jnp.int32(0))
            return tokens
        (tokens, _), _ = jax.lax.scan(
            step, (tokens0, caches), jnp.arange(S, dtype=jnp.int32)
        )
        return tokens
