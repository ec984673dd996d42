"""Operations and bytes of one kernel call, computed from its shapes.

Every count is the algorithm's: multiply-adds count as two operations,
and bytes are each input, weight and output read or written once.  Work
a kernel does on padding is not counted, so padding shows up as a lower
roofline share.  Nothing here imports the program.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Call:
    """One kernel launch: ``kind`` is the kernel family (``conv``,
    ``attention``) or ``linear`` for the matmuls XLA runs outside any
    kernel; ``name`` says where in the model it sits."""

    kind: str
    name: str
    flops: float
    bytes: float


def conv2d(name, B, H, W, cin, cout, k, stride=1, elem=4, *, bias=True,
           residual=False, temb=False, gn=False, stats=False) -> Call:
    pad = k // 2
    oh = (H + 2 * pad - k) // stride + 1
    ow = (W + 2 * pad - k) // stride + 1
    flops = 2.0 * B * oh * ow * cout * k * k * cin
    nbytes = elem * (B * H * W * cin + k * k * cin * cout + B * oh * ow * cout)
    nbytes += elem * cout * bias + elem * B * oh * ow * cout * residual
    nbytes += elem * B * cout * temb + 4 * 2 * B * cin * gn
    nbytes += 4 * 2 * B * cout * stats
    return Call("conv", name, flops, nbytes)


def temporal_conv(name, B, F, N, C, cout, k, elem=4) -> Call:
    """Conv over the frame axis with zero padding: output frame f sums the
    taps whose input frame lies inside [0, F)."""
    pad = k // 2
    taps = sum(min(F, F - (j - pad)) - max(0, -(j - pad)) for j in range(k))
    flops = 2.0 * B * N * C * cout * taps
    nbytes = elem * (B * F * N * C + k * C * cout + cout + B * F * N * cout)
    return Call("conv", name, flops, nbytes)


def attention(name, B, H, sq, skv, d, elem=4) -> Call:
    """Softmax attention: QK^T and PV, two matmuls of 2*sq*skv*d each."""
    flops = 4.0 * B * H * sq * skv * d
    nbytes = elem * B * H * d * (2 * sq + 2 * skv)
    return Call("attention", name, flops, nbytes)


def linear(name, rows, din, dout, elem=4, bias=True) -> Call:
    flops = 2.0 * rows * din * dout
    nbytes = elem * (rows * din + din * dout + rows * dout + dout * bias)
    return Call("linear", name, flops, nbytes)


def total(calls, kind=None) -> float:
    return sum(c.flops for c in calls if kind is None or c.kind == kind)
