"""Reference UNet (and the video UNet built on it).

The block layout is walked from the configuration by ``counts.unet.plan``,
the same walk the operation counts use.  A ResNet block is
GroupNorm-SiLU-conv, plus the projected time embedding, GroupNorm-SiLU-conv,
plus the (1x1-projected where channels change) input.  A spatial
transformer is GroupNorm, a linear projection in, self-attention,
cross-attention to the projected text context and a GEGLU feed-forward,
each pre-LayerNorm with a residual, a linear projection out and a residual.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from counts.unet import blocks, heads
from reference import nn


def res_block(p, x, temb, dt, groups):
    h = nn.conv(p["conv1"], nn.group_norm(p["gn1"], x, dt, groups, True), dt)
    h = h + nn.dense(p["temb"], nn.silu(temb), dt)[:, None, None, :]
    h = nn.conv(p["conv2"], nn.group_norm(p["gn2"], h, dt, groups, True), dt)
    skip = nn.conv(p["skip"], x, dt) if "skip" in p else x
    return skip + h


def transformer(p, x, context, u: dict, dt):
    B, H, W, C = x.shape
    nh = heads(u, C)
    h = nn.group_norm(p["gn"], x, dt, u["groups"]).reshape(B, H * W, C)
    h = nn.dense(p["proj_in"], h, dt)
    ctx = (nn.dense(p["ctx_proj"], context, dt)
           if u["cross_attn"] and context is not None else None)
    for i in range(u["tf_depth"]):
        lp = p[f"layer{i}"]
        t = nn.layer_norm(lp["ln1"], h, dt)
        h = h + nn.mha(lp["self_attn"], t, t, nh, dt)
        if ctx is not None:
            t = nn.layer_norm(lp["ln2"], h, dt)
            h = h + nn.mha(lp["cross_attn"], t, ctx, nh, dt)
        t = nn.layer_norm(lp["ln3"], h, dt)
        ff = nn.gelu(nn.dense(lp["ff_gate"], t, dt)) * nn.dense(lp["ff_in"], t, dt)
        h = h + nn.dense(lp["ff_out"], ff, dt)
    return x + nn.dense(p["proj_out"], h, dt).reshape(B, H, W, C)


def upsample(p, x, dt):
    x = jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)
    return nn.conv(p["conv"], x, dt)


def unet(p, x, t, context, u: dict, dt, after_attn=None):
    """x (B, H, W, C_in), t (B,), context (B, L, ctx) -> (B, H, W, C_out).
    ``after_attn(name, h)`` runs after each spatial attention block."""
    g = u["groups"]
    temb = nn.timestep_embedding(t, u["model_channels"]).astype(nn.dtype(dt))
    temb = nn.dense(p["temb2"], nn.silu(nn.dense(p["temb1"], temb, dt)), dt)
    h = nn.conv(p["conv_in"], x, dt)
    skips = [h]
    for name, kind, _, _, _ in blocks(u, x.shape[1]):
        if kind == "res":
            if name.startswith("up_"):
                h = jnp.concatenate([h, skips.pop()], axis=-1)
            h = res_block(p[name], h, temb, dt, g)
        elif kind == "attn":
            h = transformer(p[name], h, context, u, dt)
            if after_attn is not None:
                h = after_attn(name, h)
        elif kind == "down":
            h = nn.conv(p[name]["conv"], h, dt, stride=2)
        else:
            h = upsample(p[name], h, dt)
        if name.startswith("down_"):
            # a transformer refines the skip its ResNet block just pushed
            if kind == "attn":
                skips[-1] = h
            else:
                skips.append(h)
    h = nn.group_norm(p["gn_out"], h, dt, g, silu=True)
    return nn.conv(p["conv_out"], h, dt)


def temporal_attention(p, x, head_channels, dt):
    """x (B, F, H, W, C): each pixel attends across frames."""
    B, F, H, W, C = x.shape
    h = nn.layer_norm(p["ln"], x, dt)
    nh = max(1, C // head_channels)
    proj = lambda w: nn.dense(p[w], h, dt).reshape(B, F, H * W, nh,
                                                   head_channels)
    q, k, v = (proj(w).transpose(0, 2, 1, 3, 4).reshape(B * H * W, F, nh,
                                                         head_channels)
               for w in ("wq", "wk", "wv"))
    o = nn.attention(q, k, v, dt).reshape(B, H * W, F, nh * head_channels)
    o = o.transpose(0, 2, 1, 3).reshape(B, F, H, W, nh * head_channels)
    return x + nn.dense(p["out"], o, dt)


def temporal_conv(p, x, dt):
    """Conv of kernel K over the frame axis, zero padding K // 2."""
    w, F = p["kernel"].astype(nn.dtype(dt)), x.shape[1]
    pad = w.shape[0] // 2
    xp = jnp.pad(x.astype(nn.dtype(dt)), [(0, 0), (pad, pad), (0, 0), (0, 0), (0, 0)])
    ein = lambda a, b, **kw: jnp.einsum("bfhwc,cd->bfhwd", a, b, **kw)
    y = sum(nn.product(ein, xp[:, j:j + F], w[j], dt)
            for j in range(w.shape[0]))
    return y + p["bias"].astype(nn.dtype(dt))


def video_unet(p, x, t, context, u: dict, head_channels: int, dt):
    """x (B, F, H, W, C): the spatial UNet over frames, with temporal
    attention and a residual temporal conv after each attention block."""
    B, F, H, W, C = x.shape

    def after_attn(name, h):
        hv = h.reshape(B, F, *h.shape[1:])
        hv = temporal_attention(p[f"tattn/{name}"], hv, head_channels, dt)
        hv = hv + temporal_conv(p[f"tconv/{name}"], hv, dt)
        return hv.reshape(h.shape)

    out = unet(p["unet"], x.reshape(B * F, H, W, C), jnp.repeat(t, F),
               jnp.repeat(context, F, axis=0), u, dt, after_attn)
    return out.reshape(B, F, H, W, -1)


def alphas_cumprod(n=1000):
    """DDPM schedule: betas linear from 1e-4 to 0.02 over n steps."""
    betas = jnp.linspace(1e-4, 0.02, n, dtype=jnp.float32)
    return jnp.cumprod(1.0 - betas)


def ddim(eps_fn, z, total, start, stop):
    """Deterministic DDIM (eta = 0), step indices [start, stop) of a
    ``total``-step schedule from t = 999 down to t = 0."""
    a = alphas_cumprod()
    ts = jnp.linspace(999, 0, total).astype(jnp.int32)

    def body(i, z):
        a_t = a[ts[i]]
        a_prev = jnp.where(i + 1 < total, a[ts[jnp.minimum(i + 1, total - 1)]],
                           1.0)
        eps = eps_fn(z, ts[i]).astype(jnp.float32)
        zf = z.astype(jnp.float32)
        x0 = (zf - jnp.sqrt(1.0 - a_t) * eps) / jnp.sqrt(a_t)
        return (jnp.sqrt(a_prev) * x0 + jnp.sqrt(1.0 - a_prev) * eps
                ).astype(z.dtype)

    return jax.lax.fori_loop(start, stop, body, z)
