"""Fused implicit-GEMM NHWC Conv2D Pallas TPU kernel (+ temporal Conv1D).

Targets the paper's C1 finding: once Flash Attention is applied, Convolution
is up to 44% of diffusion execution time, and the baseline conv stack
round-trips HBM between GroupNorm, conv, time-embedding add and residual add.
This kernel executes the whole chain in one pass:

  * **implicit GEMM**: the (KH x KW x C_in) patch contraction is never
    materialized.  The output is tiled as (row-block x C_out-block) MXU
    GEMMs.  Each input block is read once per C_out block; every (kh, kw)
    tap is one GEMM of a row window of that block (a slice along the
    untiled row axis) against the tap's (C_in, C_out) weight slice.  The
    column offset ``kw`` is applied to the GEMM *result* with a sublane
    rotate (``pltpu.roll``), so no slice ever starts at an unaligned
    column.
  * **halo**: the input is zero-padded once in HBM (conv padding plus
    column padding to a multiple of 8).  Output row-block ``io`` reads its
    own input rows plus the next ``K - 1`` rows through a second, small
    BlockSpec on the same array.
  * **stride 2** is folded into the layout: pairs of padded rows become a
    phase axis and pairs of columns become channels (both free reshapes in
    HBM), so the kernel body only ever runs unit-stride taps.
  * **fused epilogues**: bias, broadcast time-embedding add, SiLU and
    residual add are applied to the accumulator before the single output
    write.
  * **fused GroupNorm producer**: a GroupNorm (+SiLU) feeding the conv
    collapses — once its group statistics are known — to a per-(batch,
    channel) affine ``x * a + b``; the kernel applies it to input blocks in
    VMEM (re-zeroing the conv padding), so the normalized tensor never
    exists in HBM.
  * **stats emission**: optionally accumulates per-(batch, out-channel)
    sum / sum-of-squares of the epilogue output into a tiny second output,
    which is exactly what the *next* GroupNorm needs — a ResBlock's second
    norm then costs no extra read pass over the activation.

Tiling follows the TPU's (8, 128) rule: a channel block is a multiple of
128 that divides the channel count, or the whole channel dim.  The row
tile is the largest that keeps the blocks and the kernel's temporaries
within ``VMEM_BUDGET``; the kernel is compiled with
``repro.kernels.VMEM_LIMIT`` of scoped VMEM.  A shape that cannot be tiled
raises ``ValueError`` naming it.

Grid = (B, n_cout, n_oh, n_cin); the last axis is the sequential reduction
(the fp32 accumulator lives in VMEM scratch and the output block is written
once at its final step).  n_cout sits *outside* n_oh so the stats block
(b, cout-block) stays resident across all of its row-block visits.

Layouts: x (B, H, W, C_in); w (KH, KW, C_in, C_out); out (B, OH, OW, C_out).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import VMEM_LIMIT

_LANES = 128
# Bytes of blocks plus in-kernel temporaries one conv tile is planned for
# (well inside the scoped limit: Mosaic's own temporaries are not counted).
VMEM_BUDGET = 40 * 2**20


def _largest_divisor(n: int, cap: int) -> int:
    """Largest divisor of n that is <= cap (row / spatial block sizing)."""
    for d in range(min(n, cap), 0, -1):
        if n % d == 0:
            return d
    return 1


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def channel_block(c: int, cap: int) -> int:
    """Channel tile for a lane (last) dim: the largest multiple of 128 that
    divides ``c`` and is <= ``cap``, else the whole dim ``c``."""
    for d in range(min(c, cap) // _LANES * _LANES, 0, -_LANES):
        if c % d == 0:
            return d
    return c


def _conv2d_kernel(
    *refs,
    K: int,
    Kb: int,
    P: int,
    Q: int,
    bh: int,
    Wc: int,
    OWp: int,
    OH: int,
    OW: int,
    H: int,
    W: int,
    pad: int,
    C_in: int,
    bcin: int,
    n_cin: int,
    has_halo: bool,
    has_gn: bool,
    gn_silu: bool,
    has_bias: bool,
    has_temb: bool,
    has_res: bool,
    act_silu: bool,
    emit_stats: bool,
):
    it = iter(refs)
    x_ref = next(it)
    halo_ref = next(it) if has_halo else None
    w_ref = next(it)
    a_ref = next(it) if has_gn else None
    b_ref = next(it) if has_gn else None
    bias_ref = next(it) if has_bias else None
    temb_ref = next(it) if has_temb else None
    res_ref = next(it) if has_res else None
    o_ref = next(it)
    stats_ref = next(it) if emit_stats else None
    acc = next(it)

    io = pl.program_id(2)
    ci = pl.program_id(3)

    @pl.when(ci == 0)
    def _init_acc():
        acc[...] = jnp.zeros_like(acc)

    if emit_stats:

        @pl.when((io == 0) & (ci == 0))
        def _init_stats():
            stats_ref[...] = jnp.zeros_like(stats_ref)

    x = x_ref[0]  # (rows, P, Wc, bcin): row groups x row phase x cols x chans
    if has_halo:
        x = jnp.concatenate([x, halo_ref[0]], axis=0)
    x = x.astype(jnp.float32)
    if has_gn:
        x = x * a_ref[0].astype(jnp.float32) + b_ref[0].astype(jnp.float32)
        if gn_silu:
            x = x * jax.nn.sigmoid(x)
        # The affine must not turn the conv's zero padding into nonzero
        # values: re-zero every element outside the true image.
        iota = lambda d: jax.lax.broadcasted_iota(jnp.int32, x.shape, d)
        prow = (io * bh + iota(0)) * P + iota(1)
        pcol = iota(2) * Q
        if Q > 1:
            pcol = pcol + (ci * bcin + iota(3)) // C_in
        ok = (prow >= pad) & (prow < pad + H) & (pcol >= pad) & (pcol < pad + W)
        x = jnp.where(ok, x, 0.0)

    total = None
    for b in range(Kb):  # column tap group: applied as a rotate of the GEMM
        part = None
        for kh in range(K):
            a, ph = divmod(kh, P)
            xs = x[a:a + bh, ph].reshape(bh * Wc, bcin)
            d = jax.lax.dot_general(
                xs, w_ref[kh * Kb + b].astype(jnp.float32),
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            part = d if part is None else part + d
        if b:
            # output column c reads input column c + b of the same row;
            # the wrapped tail lands in columns >= OWp, which are dropped
            part = pltpu.roll(part, bh * Wc - b, 0)
        total = part if total is None else total + part
    acc[...] += total

    @pl.when(ci == n_cin - 1)
    def _finalize():
        y = acc[...].reshape(bh, Wc, -1)[:, :OWp]
        if has_bias:
            y = y + bias_ref[0].astype(jnp.float32)
        if has_temb:
            y = y + temb_ref[0].astype(jnp.float32)
        if act_silu:
            y = y * jax.nn.sigmoid(y)
        if has_res:
            y = y + res_ref[0].astype(jnp.float32)
        o_ref[0] = y.astype(o_ref.dtype)
        if emit_stats:
            rows = io * bh + jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
            ym = jnp.where((rows < OH) & (cols < OW), y, 0.0)  # padded tail
            stats_ref[0] += jnp.concatenate(
                [jnp.sum(ym, axis=(0, 1))[None], jnp.sum(ym * ym, axis=(0, 1))[None]])


def _row_tile(OH: int, hb: int, per_row: int, fixed: int, cap_rows: int,
              what: str) -> int:
    """Output rows per tile: as many as the VMEM budget and ``cap_rows``
    allow, balanced over the tiles (least padding), a multiple of the halo
    height ``hb`` when the image spans several tiles."""
    fit = (VMEM_BUDGET - fixed) // per_row
    if fit < max(hb, 1):
        raise ValueError(
            f"conv2d_pallas cannot tile {what}: one row tile needs "
            f"{fixed + max(hb, 1) * per_row} bytes of VMEM, budget "
            f"{VMEM_BUDGET}")
    bh_max = max(1, min(OH, fit, cap_rows))
    if bh_max >= OH:
        return OH
    if hb > 1:
        bh_max = max(hb, bh_max // hb * hb)
    n_oh = pl.cdiv(OH, bh_max)
    bh = pl.cdiv(OH, n_oh)
    return _round_up(bh, hb) if hb > 1 else bh


def conv2d_pallas(
    x: jax.Array,  # (B, H, W, C_in)
    w: jax.Array,  # (K, K, C_in, C_out)
    *,
    stride: int = 1,
    gn_a: jax.Array | None = None,  # (B, C_in)
    gn_b: jax.Array | None = None,
    gn_silu: bool = True,
    bias: jax.Array | None = None,  # (C_out,)
    temb: jax.Array | None = None,  # (B, C_out)
    silu: bool = False,
    residual: jax.Array | None = None,  # (B, OH, OW, C_out)
    emit_stats: bool = False,
    block_rows: int | None = None,  # cap on output pixels (rows * OW) per tile
    block_cin: int = 256,
    block_cout: int = 256,
    interpret: bool = False,
):
    B, H, W, C_in = x.shape
    K = w.shape[0]
    assert w.shape[:2] == (K, K) and w.shape[2] == C_in, w.shape
    if stride not in (1, 2):
        raise ValueError(f"conv2d_pallas supports stride 1 or 2, got {stride}")
    C_out = w.shape[-1]
    pad = K // 2
    OH = (H + 2 * pad - K) // stride + 1
    OW = (W + 2 * pad - K) // stride + 1
    what = f"x {x.shape} {x.dtype.name}, w {w.shape}, stride {stride}"

    # Stride-s layout: P row phases, Q column phases folded into channels.
    P = Q = stride
    Kb = pl.cdiv(K, Q)  # column taps after folding
    A = pl.cdiv(K, P)  # row-group taps
    hb = A - 1  # halo row groups below each row tile
    Cx = Q * C_in
    OWp = _round_up(OW, 8)
    Wc = _round_up(OWp + Kb - 1, 8)  # column groups per padded row

    bcin = channel_block(Cx, block_cin)
    bcout = channel_block(C_out, block_cout)
    n_cin = Cx // bcin
    n_cout = C_out // bcout

    isz = x.dtype.itemsize
    lin, lout = _round_up(bcin, _LANES), _round_up(bcout, _LANES)
    in_row = P * Wc * lin * (2 * isz + 8)  # 2 buffers + fp32 copy and concat
    per_row = (in_row + Wc * lout * 4 * 5  # acc + GEMM/rotate/epilogue temps
               + OWp * lout * isz * (2 + 2 * (residual is not None)))
    fixed = 2 * K * Kb * _round_up(bcin, 8) * lout * w.dtype.itemsize + hb * in_row
    cap_rows = OH if block_rows is None else max(1, block_rows // OWp)
    bh = _row_tile(OH, hb, per_row, fixed, cap_rows, what)
    n_oh = pl.cdiv(OH, bh)
    has_halo = n_oh > 1 and hb > 0
    rows_blk = bh if has_halo else bh + hb

    # Zero-pad once in HBM: conv padding, tail rows/cols of the last tile.
    R = n_oh * bh + hb  # row groups
    Hp, Wp = P * R, Q * Wc
    xp = x[:, : Hp - pad, : Wp - pad]
    xp = jnp.pad(xp, [(0, 0), (pad, Hp - pad - xp.shape[1]),
                      (pad, Wp - pad - xp.shape[2]), (0, 0)])
    xp = xp.reshape(B, R, P, Wc, Cx)
    # Taps (kh, column group b): weight rows [pw*C_in, (pw+1)*C_in) hold
    # w[kh, b*Q + pw]; columns past K are zero.
    wt = jnp.pad(w, [(0, 0), (0, Kb * Q - K), (0, 0), (0, 0)])
    wt = wt.reshape(K * Kb, Cx, C_out)

    OH_pad = n_oh * bh
    inputs = [xp]
    in_specs = [pl.BlockSpec((1, rows_blk, P, Wc, bcin),
                             lambda b, co, io, ci: (b, io, 0, 0, ci))]
    if has_halo:
        inputs.append(xp)
        in_specs.append(pl.BlockSpec(
            (1, hb, P, Wc, bcin),
            lambda b, co, io, ci: (b, (io + 1) * (bh // hb), 0, 0, ci)))
    inputs.append(wt)
    in_specs.append(pl.BlockSpec((K * Kb, bcin, bcout),
                                 lambda b, co, io, ci: (0, ci, co)))
    if gn_a is not None:
        for g in (gn_a, gn_b):
            g = jnp.tile(g.astype(jnp.float32).reshape(B, C_in), (1, Q))
            inputs.append(g.reshape(B, 1, Cx))
        in_specs += [pl.BlockSpec((1, 1, bcin),
                                  lambda b, co, io, ci: (b, 0, ci))] * 2
    if bias is not None:
        inputs.append(bias.reshape(1, C_out))
        in_specs.append(pl.BlockSpec((1, bcout), lambda b, co, io, ci: (0, co)))
    if temb is not None:
        inputs.append(temb.reshape(B, 1, C_out))
        in_specs.append(pl.BlockSpec((1, 1, bcout),
                                     lambda b, co, io, ci: (b, 0, co)))
    if residual is not None:
        residual = jnp.pad(residual, [(0, 0), (0, OH_pad - OH),
                                      (0, OWp - OW), (0, 0)])
        inputs.append(residual)
        in_specs.append(pl.BlockSpec((1, bh, OWp, bcout),
                                     lambda b, co, io, ci: (b, io, 0, co)))

    out_shape = [jax.ShapeDtypeStruct((B, OH_pad, OWp, C_out), x.dtype)]
    out_specs = [pl.BlockSpec((1, bh, OWp, bcout),
                              lambda b, co, io, ci: (b, io, 0, co))]
    if emit_stats:
        out_shape.append(jax.ShapeDtypeStruct((B, 2, C_out), jnp.float32))
        out_specs.append(pl.BlockSpec((1, 2, bcout),
                                      lambda b, co, io, ci: (b, 0, co)))

    kernel = functools.partial(
        _conv2d_kernel,
        K=K, Kb=Kb, P=P, Q=Q, bh=bh, Wc=Wc, OWp=OWp, OH=OH, OW=OW, H=H, W=W,
        pad=pad, C_in=C_in, bcin=bcin, n_cin=n_cin, has_halo=has_halo,
        has_gn=gn_a is not None, gn_silu=gn_silu, has_bias=bias is not None,
        has_temb=temb is not None, has_res=residual is not None,
        act_silu=silu, emit_stats=emit_stats,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, n_cout, n_oh, n_cin),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bh * Wc, bcout), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        name="conv2d",
        interpret=interpret,
    )(*inputs)
    y = out[0][:, :OH, :OW]
    return (y, out[1]) if emit_stats else y


# ---------------------------------------------------------------------------
# Temporal Conv1D (TTV, paper §VI) with the layout permute fused into the
# BlockSpec index_map — mirrors temporal_flash_attention.
# ---------------------------------------------------------------------------


def _tconv_kernel(x_ref, w_ref, bias_ref, o_ref, *, K: int, pad: int):
    x = x_ref[0].astype(jnp.float32)  # (F, bn, C)
    F = x.shape[0]
    y = jnp.zeros((F, x.shape[1], w_ref.shape[2]), jnp.float32)
    for k in range(K):
        s = k - pad  # output frame f reads input frame f + s
        f0, f1 = max(0, -s), min(F, F - s)
        if f1 <= f0:
            continue
        xs = jax.lax.slice(x, (f0 + s, 0, 0), (f1 + s, x.shape[1], x.shape[2]))
        wk = w_ref[k].astype(jnp.float32)  # (C, bcout)
        part = jax.lax.dot_general(
            xs.reshape((f1 - f0) * xs.shape[1], xs.shape[2]),
            wk,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).reshape(f1 - f0, xs.shape[1], wk.shape[1])
        y += jnp.pad(part, [(f0, F - f1), (0, 0), (0, 0)])
    y = y + bias_ref[0].astype(jnp.float32)
    o_ref[0] = y.astype(o_ref.dtype)


def temporal_conv1d_pallas(
    x: jax.Array,  # (B, F, N, C) — spatial layout, N = H*W (pre-padded to block)
    w: jax.Array,  # (K, C, C_out)
    bias: jax.Array,  # (C_out,)
    *,
    block_n: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, F, N, C = x.shape
    K, _, C_out = w.shape
    pad = K // 2
    block_n = min(block_n, N)
    assert N % block_n == 0, (N, block_n)
    bcout = channel_block(C_out, 256)
    kernel = functools.partial(_tconv_kernel, K=K, pad=pad)
    return pl.pallas_call(
        kernel,
        grid=(B, C_out // bcout, N // block_n),
        in_specs=[
            pl.BlockSpec((1, F, block_n, C), lambda b, co, i: (b, 0, i, 0)),
            pl.BlockSpec((K, C, bcout), lambda b, co, i: (0, 0, co)),
            pl.BlockSpec((1, bcout), lambda b, co, i: (0, co)),
        ],
        out_specs=pl.BlockSpec((1, F, block_n, bcout), lambda b, co, i: (b, 0, i, co)),
        out_shape=jax.ShapeDtypeStruct((B, F, N, C_out), x.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        name="temporal_conv1d",
        interpret=interpret,
    )(x, w, bias.reshape(1, C_out))
