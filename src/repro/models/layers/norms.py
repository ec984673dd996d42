"""Normalization layers (LayerNorm / RMSNorm / GroupNorm) with tracing.

``LayerNorm(with_scale=False, with_bias=False)`` is OLMo's non-parametric LN.
``GroupNorm`` dispatches to the fused Pallas kernel on TPU (optionally fusing
the SiLU that always follows it in diffusion ResNet blocks — the paper's
GroupNorm is 4-11% of diffusion execution time, C1).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracer
from repro.kernels.groupnorm_silu import ops as gn_ops
from repro.nn import Module, ParamDef, ones_init, zeros_init
from repro.models.layers.basic import nbytes


def _record_norm(name: str, x, fused: bool, n_params: int):
    if not tracer.active():
        return
    n = int(np.prod(x.shape))
    elem = tracer.dtype_bytes(x.dtype)
    # Unfused GroupNorm/LayerNorm costs ~3 HBM round trips (stats pass,
    # normalize pass, activation pass); fused costs 1 read + 1 write (+ a
    # second read for two-phase group stats when the slab exceeds VMEM).
    traffic = (2 if fused else 6) * n * elem + n_params * elem
    tracer.record("norm", name, flops=8.0 * n, bytes_hbm=traffic)


@dataclasses.dataclass(frozen=True)
class RMSNorm(Module):
    dim: int
    eps: float = 1e-6
    with_scale: bool = True
    dtype: Any = jnp.float32
    name: str = "rmsnorm"

    def defs(self):
        if not self.with_scale:
            return {}
        return {"scale": ParamDef((self.dim,), ("embed",), ones_init, self.dtype)}

    def __call__(self, params, x: jax.Array) -> jax.Array:
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + self.eps)
        if self.with_scale:
            y = y * params["scale"].astype(jnp.float32)
        _record_norm(self.name, x, fused=True, n_params=self.dim if self.with_scale else 0)
        return y.astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class LayerNorm(Module):
    dim: int
    eps: float = 1e-5
    with_scale: bool = True
    with_bias: bool = True
    dtype: Any = jnp.float32
    name: str = "layernorm"

    def defs(self):
        d = {}
        if self.with_scale:
            d["scale"] = ParamDef((self.dim,), ("embed",), ones_init, self.dtype)
        if self.with_bias:
            d["bias"] = ParamDef((self.dim,), ("embed",), zeros_init, self.dtype)
        return d

    def __call__(self, params, x: jax.Array) -> jax.Array:
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + self.eps)
        if self.with_scale:
            y = y * params["scale"].astype(jnp.float32)
        if self.with_bias:
            y = y + params["bias"].astype(jnp.float32)
        _record_norm(self.name, x, fused=True, n_params=2 * self.dim)
        return y.astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class GroupNorm(Module):
    """GroupNorm over channels-last tensors (B, ..., C), optional fused SiLU."""

    channels: int
    groups: int = 32
    eps: float = 1e-5
    fuse_silu: bool = False
    dtype: Any = jnp.float32
    name: str = "groupnorm"

    def defs(self):
        return {
            "scale": ParamDef((self.channels,), (None,), ones_init, self.dtype),
            "bias": ParamDef((self.channels,), (None,), zeros_init, self.dtype),
        }

    def __call__(self, params, x: jax.Array, *, impl: str = "auto") -> jax.Array:
        """``impl`` is the caller's model-level tier (auto / naive /
        blocked_jax / pallas / interpret), resolved by
        ``groupnorm_silu.ops.resolve_model_impl``."""
        shape = x.shape
        x3 = x.reshape(shape[0], -1, shape[-1])
        tier = gn_ops.resolve_model_impl(impl)
        out = gn_ops.groupnorm_silu(
            x3,
            params["scale"],
            params["bias"],
            groups=self.groups,
            eps=self.eps,
            silu=self.fuse_silu,
            impl=tier,
        )
        _record_norm(self.name, x, fused=tier != "jax",
                     n_params=2 * self.channels)
        return out.reshape(shape)
