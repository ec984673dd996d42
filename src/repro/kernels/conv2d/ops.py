"""Dispatching wrappers around the fused Conv2D / temporal Conv1D kernels.

``conv2d(...)`` is the single call-site API used by every conv layer in the
framework, mirroring ``flash_attention.ops.attention``.  Implementations:

  * ``pallas``    — the fused implicit-GEMM Pallas TPU kernel (TARGET
                    hardware path).  Differentiable: the backward pass is
                    defined through the ``xla`` reference via
                    ``jax.custom_vjp`` (rematerializing forward).
  * ``interpret`` — same kernel body, interpreter mode (CPU validation).
  * ``xla``       — the fused semantics as one jnp expression
                    (``ref.conv2d_ref``): ``lax.conv_general_dilated`` plus
                    epilogues, fully differentiable on any backend.  Used for
                    training and as the CPU fallback.
  * ``naive``     — the unfused baseline: each stage (normalize-affine, conv,
                    bias, temb add, SiLU, residual add) is a separate XLA
                    computation (optimization barriers stop XLA re-fusing
                    them), the way the paper's profiled GPU stacks execute
                    it.  Kept deliberately as the characterization baseline.
  * ``auto``      — pallas on TPU, xla elsewhere.

``resolve_model_impl`` maps the *model-level* impl strings (which name
attention tiers: naive / blocked_jax / pallas / interpret / auto) onto conv
tiers, so one ``impl=`` flag steers the whole pipeline: ``naive`` and
``blocked_jax`` both land on the unfused-accounting conv tiers (the paper
varies only the attention algorithm between its baseline and Flash runs),
while ``pallas``/``interpret`` select the fused subsystem.
"""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

from repro.kernels.conv2d import ref as _ref
from repro.kernels.conv2d.conv2d import (
    _largest_divisor,
    conv2d_pallas,
    temporal_conv1d_pallas,
)

Impl = Literal["auto", "pallas", "interpret", "xla", "naive"]

# model-level impl (attention tier names) -> conv tier
_MODEL_IMPL = {
    "auto": "auto",
    "pallas": "pallas",
    "interpret": "interpret",
    "blocked_jax": "xla",
    "xla": "xla",
    "naive": "naive",
}


def resolve_model_impl(impl: str | None) -> str:
    key = impl or "auto"
    if key not in _MODEL_IMPL:
        raise ValueError(f"unknown impl {impl!r} (expected one of {sorted(_MODEL_IMPL)})")
    return _MODEL_IMPL[key]


def _resolve(impl: Impl) -> str:
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return impl


def is_fused(model_impl: str | None) -> bool:
    """True when the model-level impl selects the fused conv subsystem."""
    return _resolve(resolve_model_impl(model_impl)) in ("pallas", "interpret")


# ---------------------------------------------------------------------------
# GroupNorm -> affine collapse (the producer-fusion contract)
# ---------------------------------------------------------------------------


def _affine_from_moments(mean, var, scale, bias, *, cpg: int, eps: float):
    """(mean, var) per (batch, group) -> the per-(batch, channel) affine
    (a, b) with GroupNorm(x)[..., c] == x * a + b."""
    rstd = jax.lax.rsqrt(var + eps)
    sc = scale.astype(jnp.float32)[None]
    a = jnp.repeat(rstd, cpg, axis=1) * sc
    b = bias.astype(jnp.float32)[None] - jnp.repeat(mean * rstd, cpg, axis=1) * sc
    return a, b  # each (B, C) fp32


def groupnorm_affine(
    x: jax.Array,  # (B, ..., C)
    scale: jax.Array,  # (C,)
    bias: jax.Array,
    *,
    groups: int,
    eps: float = 1e-5,
):
    """One statistics pass over ``x``; returns the affine GroupNorm collapses
    to.  The fused conv kernel applies it to input blocks in VMEM, so the
    normalized tensor never round-trips HBM."""
    B, C = x.shape[0], x.shape[-1]
    cpg = C // groups
    xf = x.astype(jnp.float32).reshape(B, -1, groups, cpg)
    mean = jnp.mean(xf, axis=(1, 3))  # (B, G)
    var = jnp.mean(xf * xf, axis=(1, 3)) - mean * mean
    return _affine_from_moments(mean, var, scale, bias, cpg=cpg, eps=eps)


def affine_from_stats(
    stats: jax.Array,  # (B, 2, C): per-channel sum / sum-of-squares
    scale: jax.Array,  # (C,)
    bias: jax.Array,
    *,
    groups: int,
    count: int,  # spatial elements summed per channel (OH * OW)
    eps: float = 1e-5,
):
    """Same affine, but from the channel statistics a fused conv already
    emitted (``emit_stats=True``) — the second GroupNorm of a ResBlock then
    needs no read pass over the activation at all."""
    B, _, C = stats.shape
    cpg = C // groups
    n = count * cpg
    mean = stats[:, 0].reshape(B, groups, cpg).sum(-1) / n  # (B, G)
    var = stats[:, 1].reshape(B, groups, cpg).sum(-1) / n - mean * mean
    return _affine_from_moments(mean, var, scale, bias, cpg=cpg, eps=eps)


# ---------------------------------------------------------------------------
# Fused conv2d: custom_vjp around the Pallas kernel (bwd through the ref)
# ---------------------------------------------------------------------------


def _apply_ref(static, x, w, gn_a, gn_b, bias, temb, residual):
    stride, gn_silu, silu, emit_stats = static[:4]
    return _ref.conv2d_ref(
        x, w, stride=stride, gn_a=gn_a, gn_b=gn_b, gn_silu=gn_silu,
        bias=bias, temb=temb, silu=silu, residual=residual,
        emit_stats=emit_stats,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _conv2d_fused(static, x, w, gn_a, gn_b, bias, temb, residual):
    stride, gn_silu, silu, emit_stats, interpret, brows, bcin, bcout = static
    return conv2d_pallas(
        x, w, stride=stride, gn_a=gn_a, gn_b=gn_b, gn_silu=gn_silu,
        bias=bias, temb=temb, silu=silu, residual=residual,
        emit_stats=emit_stats, block_rows=brows, block_cin=bcin,
        block_cout=bcout, interpret=interpret,
    )


def _conv2d_fwd(static, *ops):
    return _conv2d_fused(static, *ops), ops


def _conv2d_bwd(static, ops, g):
    _, vjp = jax.vjp(lambda *o: _apply_ref(static, *o), *ops)
    return vjp(g)


_conv2d_fused.defvjp(_conv2d_fwd, _conv2d_bwd)


def conv2d(
    x: jax.Array,  # (B, H, W, C_in)
    w: jax.Array,  # (K, K, C_in, C_out)
    *,
    stride: int = 1,
    bias: jax.Array | None = None,  # (C_out,)
    gn_affine: tuple | None = None,  # (a, b) each (B, C_in) — fused producer
    gn_silu: bool = True,
    temb: jax.Array | None = None,  # (B, C_out)
    silu: bool = False,
    residual: jax.Array | None = None,  # (B, OH, OW, C_out)
    emit_stats: bool = False,
    impl: Impl = "auto",
    block_rows: int | None = None,
    block_cin: int = 256,
    block_cout: int = 256,
):
    """Fused NHWC Conv2D with selectable implementation.

    Returns ``y`` — or ``(y, stats)`` with per-(batch, out-channel)
    sum / sum-of-squares of the epilogue output when ``emit_stats=True``.
    """
    impl = _resolve(impl)
    gn_a, gn_b = gn_affine if gn_affine is not None else (None, None)

    if impl == "naive":
        # Unfused baseline: optimization barriers pin every stage to its own
        # XLA computation, preserving the per-stage HBM round trips a
        # library-op stack pays — so wall-clock A/Bs against the fused tiers
        # measure real fusion, and the tracer's unfused accounting matches
        # what actually executes.  Identical math to the ref (barriers are
        # identity).
        bar = jax.lax.optimization_barrier
        xf = x
        if gn_a is not None:
            xh = x.astype(jnp.float32) * gn_a[:, None, None, :] + gn_b[:, None, None, :]
            xf = bar(xh.astype(x.dtype))
            if gn_silu:
                xf = bar(jax.nn.silu(xf))
        k = w.shape[0]
        pad = k // 2
        y = bar(jax.lax.conv_general_dilated(
            xf, w.astype(x.dtype), (stride, stride),
            [(pad, pad), (pad, pad)], dimension_numbers=_ref._DIMSPEC,
            preferred_element_type=jnp.float32,
        ))
        if bias is not None:
            y = bar(y + bias.astype(jnp.float32))
        if temb is not None:
            y = bar(y + temb[:, None, None, :].astype(jnp.float32))
        if silu:
            y = bar(jax.nn.silu(y))
        if residual is not None:
            y = bar(y + residual.astype(jnp.float32))
        out = y.astype(x.dtype)
        if emit_stats:
            stats = jnp.stack([y.sum((1, 2)), (y * y).sum((1, 2))], axis=1)
            return out, stats
        return out

    if impl == "xla":
        return _ref.conv2d_ref(
            x, w, stride=stride, gn_a=gn_a, gn_b=gn_b, gn_silu=gn_silu,
            bias=bias, temb=temb, silu=silu, residual=residual,
            emit_stats=emit_stats,
        )

    if impl in ("pallas", "interpret"):
        from repro.parallel.sharding import kernel_on_mesh

        static = (stride, gn_silu, silu, emit_stats, impl == "interpret",
                  block_rows, block_cin, block_cout)
        return kernel_on_mesh(
            functools.partial(_conv2d_fused, static),
            (x, w, gn_a, gn_b, bias, temb, residual),
            (True, False, True, True, False, True, True))

    raise ValueError(f"unknown impl {impl!r}")


# ---------------------------------------------------------------------------
# Temporal Conv1D dispatch (TTV, paper §VI)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _tconv_fused(static, x4, w, bias):
    block_n, interpret = static
    return temporal_conv1d_pallas(x4, w, bias, block_n=block_n, interpret=interpret)


def _tconv_ref4(x4, w, bias):
    B, F, N, C = x4.shape
    y = _ref.temporal_conv1d_ref(x4.reshape(B, F, N, 1, C), w, bias)
    return y.reshape(B, F, N, w.shape[-1])


def _tconv_fwd(static, x4, w, bias):
    return _tconv_fused(static, x4, w, bias), (x4, w, bias)


def _tconv_bwd(static, ops, g):
    _, vjp = jax.vjp(_tconv_ref4, *ops)
    return vjp(g)


_tconv_fused.defvjp(_tconv_fwd, _tconv_bwd)


def temporal_conv1d(
    x: jax.Array,  # (B, F, H, W, C) — conv over the frame axis
    w: jax.Array,  # (K, C, C_out)
    bias: jax.Array,  # (C_out,)
    *,
    impl: Impl = "auto",
    block_n: int = 128,
) -> jax.Array:
    """Conv over frames without materializing the (B,F,H,W,C)->(BHW,F,C)
    permute: ``pallas``/``interpret`` tile the spatial axis in place via the
    BlockSpec index_map (like ``temporal_flash_attention``); ``xla``/``naive``
    use the conventional transpose -> conv -> transpose the paper profiles."""
    B, F, H, W, C = x.shape
    impl = _resolve(impl)
    if impl in ("pallas", "interpret"):
        from repro.parallel.sharding import kernel_on_mesh

        N = H * W
        # divisor-based blocking: the (B,F,N,C) view is tiled in place with
        # no padded HBM copy (the whole point of the fused layout)
        bn = _largest_divisor(N, block_n)
        y = kernel_on_mesh(
            functools.partial(_tconv_fused, (bn, impl == "interpret")),
            (x.reshape(B, F, N, C), w, bias), (True, False, False))
        return y.reshape(B, F, H, W, w.shape[-1])
    return _ref.temporal_conv1d_ref(x, w, bias)
