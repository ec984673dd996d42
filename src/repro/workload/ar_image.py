"""Transformer TTI workloads (Muse parallel decode / Parti AR decode).

Muse's constant-length unmasking steps give a flat demand profile; Parti's
AR decode grows its KV cache linearly (Fig. 7, Parti panel), so its demand
ramp is what a staggered pod flattens.  Characterization reproduces the
paper's method: parallel decode traces one step scaled by the step count;
AR decode traces steps at sampled cache lengths.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import characterize, tracer
from repro.models.ar_image import ARImageConfig, ARImageModel
from repro.models.vae import DecoderConfig, VQDecoderConfig
from repro.workload.base import (
    CostDescriptor,
    GenerativeWorkload,
    Stage,
    register_workload,
)
from repro.workload.diffusion import REDUCED_TEXT


@register_workload(ARImageConfig)
class ARImageWorkload(GenerativeWorkload):
    route = "pod"
    modality = "image"

    def build_model(self, cfg: ARImageConfig) -> ARImageModel:
        return ARImageModel(cfg)

    def reduced(self) -> ARImageConfig:
        cfg = self.cfg
        return dataclasses.replace(
            cfg, name=cfg.name + "-reduced", n_layers=2, d_model=64, n_heads=4,
            d_ff=128, image_vocab=128, image_tokens=16, parallel_steps=3,
            text=REDUCED_TEXT,
            vq=VQDecoderConfig(
                codebook_size=128, token_hw=4, embed_dim=32,
                decoder=DecoderConfig(latent_channels=32, base_channels=16,
                                      channel_mult=(1, 2), num_res_blocks=1,
                                      groups=8),
            ),
        )

    def cost_descriptor(self) -> CostDescriptor:
        cfg = self.cfg
        S = cfg.image_tokens
        if cfg.decode == "parallel":
            decode = Stage("parallel_decode", cfg.parallel_steps, S,
                           demand=(S,))  # constant length (Fig. 7 Muse)
        else:
            decode = Stage("ar_decode", S, S,
                           demand=tuple(range(1, S + 1)))  # linear KV growth
        return CostDescriptor(
            arch=cfg.name, route=self.route,
            stages=(
                Stage("text_encoder", 1, cfg.text.max_len),
                decode,
                Stage("vq_decoder", 1, cfg.vq.token_hw ** 2),
            ),
        )

    def stage_body(self, params, stage, state, key, *, impl):
        del key  # greedy/confidence decode rules: deterministic
        model = self.model
        if stage.name == "text_encoder":
            ctx = model.text_encoder(params["text"], state["tokens"],
                                     impl=impl)
            ctx = model._ctx_proj()(params["ctx_proj"], ctx)
            return {"ctx": ctx}
        if stage.name == "parallel_decode":
            return {"img_tokens": model.decode_parallel(params, state["ctx"],
                                                        impl=impl)}
        if stage.name == "ar_decode":
            return {"img_tokens": model.decode_ar(params, state["ctx"],
                                                  impl=impl)}
        if stage.name == "vq_decoder":
            return {"out": model.vq(params["vq"], state["img_tokens"],
                                    impl=impl)}
        raise ValueError(f"unknown AR-image stage {stage.name!r}")

    def trace_events(self, impl: str = "auto") -> list:
        cfg = self.cfg
        if cfg.decode == "parallel":
            return super().trace_events(impl)
        # Parti AR: text enc + vq once, plus decode steps at sampled cache
        # lengths scaled to the full token count (Fig. 7 linear growth).
        # Events are scoped by descriptor stage name, exactly like the
        # generate() driver's per-stage scopes, so characterization and
        # served execution attribute time to the same stages.
        model = self.model
        params = characterize.abstract_params(model)
        (toks,) = self.trace_inputs()
        with tracer.trace() as tr:
            with tracer.scope("text_encoder"):
                jax.eval_shape(
                    lambda p, t: model.text_encoder(p["text"], t, impl=impl),
                    params, toks)
        ev = tr.events
        S = cfg.image_tokens
        sample_points = 8
        for i in range(sample_points):
            cur = max(1, (i * S) // sample_points)
            step_ev = self._ar_step_events(params, cur, impl)
            step_ev = [dataclasses.replace(e, name=f"ar_decode/{e.name}")
                       for e in step_ev]
            ev += tracer.scale_events(step_ev, S // sample_points)
        img_tokens = jax.ShapeDtypeStruct((1, cfg.image_tokens), jnp.int32)
        with tracer.trace() as tr:
            with tracer.scope("vq_decoder"):
                jax.eval_shape(
                    lambda p, t: model.vq(p["vq"], t, impl=impl),
                    params, img_tokens)
        return ev + tr.events

    def _ar_step_events(self, params_abs, cur: int, impl: str):
        """One AR decode step against a cache of length ``cur`` (abstract)."""
        from repro.models.layers.attention import AttentionCache

        model, cfg = self.model, self.cfg
        B = 1

        def step(params, tok, caches, ctx):
            x = model._embed()(params["embed"], tok)
            x = x + params["pos"][cur - 1: cur].astype(x.dtype)[None]
            for i in range(cfg.n_layers):
                cc = AttentionCache(
                    k=model.block._cross_attn()._split_heads(
                        model.block._cross_attn()._wk()(
                            params[f"layer{i}"]["cross_attn"]["wk"], ctx),
                        cfg.n_heads),
                    v=model.block._cross_attn()._split_heads(
                        model.block._cross_attn()._wv()(
                            params[f"layer{i}"]["cross_attn"]["wv"], ctx),
                        cfg.n_heads),
                )
                x, _ = model.block.decode(
                    params[f"layer{i}"], x, caches[i], jnp.int32(cur - 1),
                    cross_cache=cc)
            x = model._final_ln()(params["final_ln"], x)
            return model._head()(params["head"], x)

        tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        ctx = jax.ShapeDtypeStruct((B, cfg.text.max_len, cfg.d_model),
                                   cfg.dtype)
        caches = [
            {"attn": jax.eval_shape(
                lambda: model.block._attn().init_cache(B, cur,
                                                       dtype=cfg.dtype))}
            for _ in range(cfg.n_layers)
        ]
        return characterize.trace_workload(step, params_abs, tok, caches, ctx)
