"""Fused GroupNorm + SiLU Pallas TPU kernel.

Beyond-paper optimization targeting the paper's C1 finding: after Flash
Attention, diffusion UNets are Convolution/GroupNorm-bound (GroupNorm alone
is 4-11% of execution time, and in the baseline it costs three HBM round
trips: stats read, normalize read/write, activation read/write).  This kernel
does one read + one write per element.

Tiling: the diffusion hot shapes are latents — (B, N=H*W <= 64*64, C <= 1280)
— so a whole (N, C) slab fits VMEM (64*64*1280*4B = 20 MB is too big in fp32;
we therefore tile N and use a two-phase grid: phase 0 accumulates
per-channel sum/sum-of-squares into VMEM scratch, phase 1 folds them into
group statistics, re-streams the tile, normalizes, applies scale/bias +
SiLU and writes.  2 reads + 1 write — still one fewer round trip than
unfused, and no materialized intermediate).
Grid = (B, 2, n_tiles); the phase axis exploits Pallas TPU's sequential grid.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _group_matrix(C: int, groups: int) -> jax.Array:
    """(C, groups) one-hot channel -> group membership."""
    c = jax.lax.broadcasted_iota(jnp.int32, (C, groups), 0)
    g = jax.lax.broadcasted_iota(jnp.int32, (C, groups), 1)
    return (c // (C // groups) == g).astype(jnp.float32)


def _matmul(a, b, contract_b: int) -> jax.Array:
    """``a @ b`` (contract_b=0) or ``a @ b.T`` (contract_b=1), exact in fp32."""
    return jax.lax.dot_general(
        a, b, (((1,), (contract_b,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _gn_kernel(
    x_ref,
    scale_ref,
    bias_ref,
    o_ref,
    sum_scr,
    sq_scr,
    *,
    groups: int,
    eps: float,
    silu: bool,
    n_valid: int,
    block_n: int,
):
    phase = pl.program_id(1)
    it = pl.program_id(2)

    @pl.when(jnp.logical_and(phase == 0, it == 0))
    def _init():
        sum_scr[...] = jnp.zeros_like(sum_scr)
        sq_scr[...] = jnp.zeros_like(sq_scr)

    x = x_ref[0].astype(jnp.float32)  # (block_n, C)
    C = x.shape[1]
    rows = it * block_n + jax.lax.broadcasted_iota(jnp.int32, (block_n, C), 0)
    xm = jnp.where(rows < n_valid, x, 0.0)

    @pl.when(phase == 0)
    def _accumulate():
        # Per-channel partial sums; channels are folded into groups only
        # once, in phase 1 (a lane-splitting reshape does not lower).
        sum_scr[...] += jnp.sum(xm, axis=0, keepdims=True)
        sq_scr[...] += jnp.sum(xm * xm, axis=0, keepdims=True)

    @pl.when(phase == 1)
    def _normalize():
        # channel sums -> group sums -> back to channels, as two exact
        # one-hot matmuls over the (C, groups) membership matrix
        m = _group_matrix(C, groups)
        count = n_valid * (C // groups)
        mean = _matmul(sum_scr[...], m, 0) / count  # (1, groups)
        var = _matmul(sq_scr[...], m, 0) / count - mean * mean
        rstd = jax.lax.rsqrt(var + eps)
        mean_c = _matmul(mean, m, 1)  # (1, C)
        rstd_c = _matmul(rstd, m, 1)
        y = (x - mean_c) * rstd_c
        y = y * scale_ref[...].astype(jnp.float32) + bias_ref[...].astype(jnp.float32)
        if silu:
            y = y * jax.nn.sigmoid(y)
        o_ref[0] = y.astype(o_ref.dtype)


def groupnorm_silu_pallas(
    x: jax.Array,  # (B, N, C), N pre-padded to block_n multiple
    scale: jax.Array,  # (C,)
    bias: jax.Array,
    *,
    groups: int,
    eps: float = 1e-5,
    silu: bool = True,
    n_valid: int | None = None,
    block_n: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    B, N, C = x.shape
    block_n = min(block_n, N)
    assert N % block_n == 0, (N, block_n)
    n_tiles = N // block_n
    n_valid = N if n_valid is None else n_valid

    kernel = functools.partial(
        _gn_kernel,
        groups=groups,
        eps=eps,
        silu=silu,
        n_valid=n_valid,
        block_n=block_n,
    )
    return pl.pallas_call(
        kernel,
        grid=(B, 2, n_tiles),
        in_specs=[
            pl.BlockSpec((1, block_n, C), lambda b, p, i: (b, i, 0)),
            pl.BlockSpec((1, C), lambda b, p, i: (0, 0)),
            pl.BlockSpec((1, C), lambda b, p, i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_n, C), lambda b, p, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, N, C), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, C), jnp.float32),
            pltpu.VMEM((1, C), jnp.float32),
        ],
        name="groupnorm_silu",
        interpret=interpret,
    )(x, scale[None], bias[None])
