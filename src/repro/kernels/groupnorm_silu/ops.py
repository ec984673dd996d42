"""Dispatching wrapper for fused GroupNorm + SiLU."""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

from repro.kernels.groupnorm_silu import ref as _ref
from repro.kernels.groupnorm_silu.groupnorm_silu import groupnorm_silu_pallas

Impl = Literal["auto", "pallas", "interpret", "jax"]

# model-level impl (attention tier names) -> GroupNorm tier; the fallback
# tiers of the other kernel packages all land on the jnp reference.
_MODEL_IMPL = {
    "auto": "auto",
    "pallas": "pallas",
    "interpret": "interpret",
    "blocked_jax": "jax",
    "xla": "jax",
    "naive": "jax",
    "jax": "jax",
}


def resolve_model_impl(impl: str | None) -> str:
    """Model-level tier -> concrete GroupNorm tier (``auto`` resolved per
    backend: pallas on TPU, jax elsewhere)."""
    key = impl or "auto"
    if key not in _MODEL_IMPL:
        raise ValueError(f"unknown impl {impl!r} (expected one of {sorted(_MODEL_IMPL)})")
    tier = _MODEL_IMPL[key]
    if tier == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jax"
    return tier


def groupnorm_silu(
    x: jax.Array,  # (B, N, C) or (B, H, W, C)
    scale: jax.Array,
    bias: jax.Array,
    *,
    groups: int,
    eps: float = 1e-5,
    silu: bool = True,
    impl: Impl = "auto",
    block_n: int = 1024,
) -> jax.Array:
    orig_shape = x.shape
    if x.ndim == 4:
        B, H, W, C = x.shape
        x = x.reshape(B, H * W, C)
    B, N, C = x.shape

    impl = resolve_model_impl(impl)
    if impl == "jax":
        out = _ref.groupnorm_silu_ref(x, scale, bias, groups=groups, eps=eps, silu=silu)
        return out.reshape(orig_shape)

    bn = min(block_n, N)
    pad = (-N) % bn
    if pad:
        x = jnp.pad(x, [(0, 0), (0, pad), (0, 0)])
    from repro.parallel.sharding import kernel_on_mesh

    out = kernel_on_mesh(
        functools.partial(
            groupnorm_silu_pallas, groups=groups, eps=eps, silu=silu,
            n_valid=N, block_n=bn, interpret=(impl == "interpret")),
        (x, scale, bias), (True, False, False))
    return out[:, :N].reshape(orig_shape)
