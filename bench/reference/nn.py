"""Plain jax.numpy layers for the reference models.

No kernels, no fusion, no batching tricks.  Every layer takes ``mode``,
the arithmetic it runs in:

* ``float32``: float32 storage, every matmul and conv at ``HIGHEST``
  precision (on a TPU anything less rounds the operands to bfloat16);
* ``float32-high``: float32 storage, each matmul and conv as three
  bfloat16 passes (``hi*hi + hi*lo + lo*hi`` of the operands split into
  bfloat16 halves, summed in float32), the next precision below;
* ``bfloat16``: bfloat16 storage and matmuls.

Norm statistics are taken in float32 in every mode.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_DTYPES = {"float32": jnp.float32, "float32-high": jnp.float32,
           "bfloat16": jnp.bfloat16}


def dtype(mode):
    return _DTYPES[mode]


def _split(x):
    """x = hi + lo (+ what bfloat16 cannot hold), both halves bfloat16.
    ``reduce_precision`` keeps the compiler from folding the rounding away
    as it may fold a round trip through a narrower type."""
    x = x.astype(jnp.float32)
    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)


def product(op, a, b, mode):
    """``op(a, b, precision=..., preferred_element_type=...)`` (a matmul,
    an einsum or a conv) in ``mode``'s arithmetic."""
    if mode == "float32":
        return op(a.astype(jnp.float32), b.astype(jnp.float32),
                  precision=jax.lax.Precision.HIGHEST,
                  preferred_element_type=jnp.float32)
    if mode == "bfloat16":
        return op(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                  preferred_element_type=jnp.bfloat16)
    (ah, al), (bh, bl) = _split(a), _split(b)
    f = lambda x, y: op(x, y, preferred_element_type=jnp.float32)
    return f(ah, bh) + (f(ah, bl) + f(al, bh))


def dense(p, x, dt):
    y = product(jnp.matmul, x, p["kernel"], dt)
    if "bias" in p:
        y = y + p["bias"].astype(dtype(dt))
    return y


def conv(p, x, dt, stride=1):
    """NHWC conv, HWIO kernel, zero padding k // 2 on each side."""
    pad = p["kernel"].shape[0] // 2
    op = lambda a, b, **kw: jax.lax.conv_general_dilated(
        a, b, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"), **kw)
    y = product(op, x, p["kernel"], dt)
    return y + p["bias"].astype(dtype(dt)) if "bias" in p else y


def layer_norm(p, x, dt, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    y = (xf - mu) / jnp.sqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(dtype(dt))


def group_norm(p, x, dt, groups, silu=False, eps=1e-5):
    """GroupNorm over the channels of a channels-last tensor."""
    shape = x.shape
    g = min(groups, shape[-1])
    xf = x.astype(jnp.float32).reshape(shape[0], -1, g, shape[-1] // g)
    mu = xf.mean((1, 3), keepdims=True)
    var = ((xf - mu) ** 2).mean((1, 3), keepdims=True)
    y = ((xf - mu) / jnp.sqrt(var + eps)).reshape(shape)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return (jax.nn.silu(y) if silu else y).astype(dtype(dt))


def gelu(x):
    """GELU, tanh form."""
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def silu(x):
    return x * jax.nn.sigmoid(x)


def attention(q, k, v, dt):
    """softmax(q k^T / sqrt(d)) v over (B, S, H, D) tensors."""
    ein = lambda spec: lambda a, b, **kw: jnp.einsum(spec, a, b, **kw)
    s = product(ein("bqhd,bkhd->bhqk"), q, k, dt)
    s = s.astype(jnp.float32) / math.sqrt(q.shape[-1])
    p = jax.nn.softmax(s, axis=-1).astype(dtype(dt))
    return product(ein("bhqk,bkhd->bqhd"), p, v, dt).astype(dtype(dt))


def mha(p, x, ctx, heads, dt):
    """Multi-head attention of x over ctx (ctx = x for self-attention)."""
    B, S, _ = x.shape
    split = lambda t: t.reshape(t.shape[0], t.shape[1], heads, -1)
    q, k, v = (split(dense(p[w], t, dt))
               for w, t in (("wq", x), ("wk", ctx), ("wv", ctx)))
    return dense(p["wo"], attention(q, k, v, dt).reshape(B, S, -1), dt)


def timestep_embedding(t, dim):
    """Sinusoidal features of the timestep: cosines then sines."""
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    args = t.astype(jnp.float32)[:, None] * freqs
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)
